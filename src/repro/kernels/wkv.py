"""Parameterized Pallas TPU kernel for the RWKV6 WKV recurrence.

Third tunable kernel family: the attention-free arch's perf-critical op.
The chunked WKV algorithm (see models/rwkv.py::wkv_chunked for the jnp
reference) splits the sequence into chunks; within a chunk the recurrence is
a small quadratic form, and a (hd, hd) key-value state carries across chunks.

TPU mapping:
  * grid = (n_chunks,), sequential ('arbitrary') — the state lives in a VMEM
    f32 scratch that persists across grid steps (the TPU-native analogue of
    a GPU persistent-CTA scan);
  * blocks are (chunk, hd) tiles of r/k/v/logw; hd = 64 aligns the MXU quarter
    tile, chunk is the tunable occupancy/VMEM knob (the config family);
  * all math f32 (the recurrence is exponentially sensitive; the reference
    does the same).

Config space: ``WkvConfig(chunk)`` — like the matmul/attention families,
every chunk size is a separate compiled binary that the deployment-selection
pipeline can prune.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True, order=True)
class WkvConfig:
    chunk: int = 16

    def name(self) -> str:
        return f"wkv_c{self.chunk}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "WkvConfig":
        return WkvConfig(**d)


@functools.cache
def wkv_config_space() -> tuple[WkvConfig, ...]:
    return tuple(WkvConfig(c) for c in (8, 16, 32, 64, 128))


DEFAULT_WKV_CONFIG = WkvConfig(16)


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sout_ref, s_ref, *, n_chunks: int):
    """One grid step = one chunk.  Blocks (L, hd); state scratch (hd, hd) f32."""
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _load_state():
        s_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)  # (1, hd)
    s = s_ref[...]

    l = r.shape[0]
    cum = jnp.cumsum(w, axis=0)
    # Midpoint stabilization: the factored form r̃=r·e^{cum-w}, k̃=k·e^{-cum}
    # is exact but its exponents grow with the chunk length (the classic
    # chunked-WKV instability).  Shifting both by the per-channel midpoint
    # decay m halves the exponent range: scores are unchanged
    # (e^{cum-w-m}·e^{m-cum'} = e^{cum-w-cum'}), enabling chunks ≥ 32.
    m = cum[l // 2][None, :]
    r_t = r * jnp.exp(cum - w - m)
    k_t = k * jnp.exp(m - cum)
    # State-in term uses the unshifted r̃ (its exponent cum-w <= 0 is bounded).
    r_s = r * jnp.exp(cum - w)
    scores = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    rows = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    scores = jnp.where(cols < rows, scores, 0.0)  # strictly causal within chunk
    diag = jnp.sum(r * (u * k), axis=1, keepdims=True)  # (L, 1)
    o = (
        jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        + diag * v
        + jax.lax.dot_general(r_s, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    )
    o_ref[...] = o.astype(o_ref.dtype)

    # state update: S' = e^{cum_L} ⊙_rows S + Σ_τ (k_τ e^{cum_L - cum_τ}) v_τᵀ
    cum_last = cum[-1:, :]  # (1, hd)
    k_hat = k * jnp.exp(cum_last - cum)
    s_new = jnp.exp(cum_last).T * s + jax.lax.dot_general(
        k_hat, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _store_state():
        sout_ref[...] = s_new.astype(sout_ref.dtype)


# Padding positions use logw = 0 (no decay) and zero k/v, so they alter
# neither the outputs nor the carried state (exactness for any chunk size).
_LOGW_PAD = 0.0


def wkv_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,
    u: jax.Array,
    state: jax.Array | None = None,
    config: WkvConfig = DEFAULT_WKV_CONFIG,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-head chunked WKV: r/k/v/logw (S, hd); u (hd,); state (hd, hd).

    Returns (o (S, hd) f32, final_state (hd, hd) f32).  Batch/head dims are
    vmapped by callers (see ops.wkv).
    """
    s_len, hd = r.shape
    chunk = min(config.chunk, max(s_len, 8))
    pad = (-s_len) % chunk
    if pad:
        zs = lambda t: jnp.pad(t, ((0, pad), (0, 0)))
        r, k, v = zs(r), zs(k), zs(v)
        logw = jnp.pad(logw, ((0, pad), (0, 0)), constant_values=_LOGW_PAD)
    n_chunks = (s_len + pad) // chunk
    if state is None:
        state = jnp.zeros((hd, hd), jnp.float32)

    kernel = functools.partial(_wkv_kernel, n_chunks=n_chunks)
    o, s_out = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((chunk, hd), lambda i: (i, 0)),
            pl.BlockSpec((chunk, hd), lambda i: (i, 0)),
            pl.BlockSpec((chunk, hd), lambda i: (i, 0)),
            pl.BlockSpec((chunk, hd), lambda i: (i, 0)),
            pl.BlockSpec((1, hd), lambda i: (0, 0)),
            pl.BlockSpec((hd, hd), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((chunk, hd), lambda i: (i, 0)),
            pl.BlockSpec((hd, hd), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chunks * chunk, hd), jnp.float32),
            jax.ShapeDtypeStruct((hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
        name=config.name(),
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(r, k, v, logw, u.reshape(1, hd), state)
    return o[:s_len], s_out
