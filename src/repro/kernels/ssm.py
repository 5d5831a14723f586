"""Pallas TPU kernel for the Mamba selective-SSM scan (Hymba's recurrence).

Fourth tunable kernel family.  The jnp reference (models/mamba.py) runs an
``associative_scan`` that materializes the (B, S, d, N) state history in HBM
— N=16× the activation traffic.  This kernel fuses the recurrence: the
running (d_block, N) state lives in VMEM scratch, the sequence streams
through in chunks, and only y (S, d) ever leaves the core.

Grid: (d_blocks, n_chunks) — d parallel, chunks sequential ('arbitrary');
the state scratch carries across the chunk dimension.  Config knobs:
``block_d`` (VMEM/occupancy) × ``chunk`` (stream granularity).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True, order=True)
class SsmConfig:
    block_d: int = 128
    chunk: int = 32

    def name(self) -> str:
        return f"ssm_bd{self.block_d}_c{self.chunk}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "SsmConfig":
        return SsmConfig(**d)


@functools.cache
def ssm_config_space() -> tuple[SsmConfig, ...]:
    out = []
    for bd in (64, 128, 256):
        for c in (16, 32, 64):
            out.append(SsmConfig(bd, c))
    return tuple(out)


DEFAULT_SSM_CONFIG = SsmConfig(128, 32)


def _ssm_kernel(dtx_ref, dta_ref, b_ref, c_ref, s0_ref, y_ref, sout_ref, h_ref, *, n_chunks: int, chunk: int):
    """One grid step = (d_block, chunk).

    dtx: (L, bd)   dt * x  (input term, f32)
    dta: (L, bd*N) dt * a  (log decay per channel/state, f32, flattened N-major)
    b/c: (L, N)    input/output mixing vectors
    s0:  (bd, N)   initial state for this d block
    """
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = s0_ref[...].astype(jnp.float32)

    dtx = dtx_ref[...].astype(jnp.float32)
    bvec = b_ref[...].astype(jnp.float32)
    cvec = c_ref[...].astype(jnp.float32)
    bd = dtx.shape[1]
    n = bvec.shape[1]
    dta = dta_ref[...].astype(jnp.float32).reshape(chunk, bd, n)

    def step(t, carry):
        h = carry
        abar = jnp.exp(dta[t])  # (bd, N)
        bx = dtx[t][:, None] * bvec[t][None, :]  # (bd, N)
        h = abar * h + bx
        y_t = jnp.sum(h * cvec[t][None, :], axis=1)  # (bd,)
        y_ref[pl.ds(t, 1), :] = y_t[None, :].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _store():
        sout_ref[...] = h.astype(sout_ref.dtype)


def ssm_scan_pallas(
    dtx: jax.Array,
    dta: jax.Array,
    b: jax.Array,
    c: jax.Array,
    state: jax.Array | None = None,
    config: SsmConfig = DEFAULT_SSM_CONFIG,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Selective-SSM scan for one batch element.

    dtx (S, d) = dt*x;  dta (S, d, N) = dt[..,None]*a;  b/c (S, N);
    state (d, N) or None.  Returns (y (S, d) f32, final_state (d, N) f32)
    where h_t = exp(dta_t) * h_{t-1} + dtx_t * b_t  and  y_t = <h_t, c_t>_N.
    """
    s_len, d = dtx.shape
    n = b.shape[1]
    bd = min(config.block_d, d)
    chunk = min(config.chunk, max(s_len, 8))
    pad_s = (-s_len) % chunk
    pad_d = (-d) % bd
    if pad_s or pad_d:
        dtx = jnp.pad(dtx, ((0, pad_s), (0, pad_d)))
        dta = jnp.pad(dta, ((0, pad_s), (0, pad_d), (0, 0)))
        b = jnp.pad(b, ((0, pad_s), (0, 0)))
        c = jnp.pad(c, ((0, pad_s), (0, 0)))
    sp, dp = s_len + pad_s, d + pad_d
    if state is None:
        state = jnp.zeros((dp, n), jnp.float32)
    elif pad_d:
        state = jnp.pad(state, ((0, pad_d), (0, 0)))
    n_chunks = sp // chunk
    n_d = dp // bd
    dta2 = dta.reshape(sp, dp * n)  # flatten (d, N) N-major for 2-D blocking

    kernel = functools.partial(_ssm_kernel, n_chunks=n_chunks, chunk=chunk)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((chunk, bd), lambda di, ci: (ci, di)),
            pl.BlockSpec((chunk, bd * n), lambda di, ci: (ci, di)),
            pl.BlockSpec((chunk, n), lambda di, ci: (ci, 0)),
            pl.BlockSpec((chunk, n), lambda di, ci: (ci, 0)),
            pl.BlockSpec((bd, n), lambda di, ci: (di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((chunk, bd), lambda di, ci: (ci, di)),
            pl.BlockSpec((bd, n), lambda di, ci: (di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sp, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
    )(dtx, dta2, b, c, state)
    return y[:s_len, :d], s_out[:d]
