"""Grouped matrix multiplication (Pallas TPU): the expert GEMM family.

A mixture-of-experts layer multiplies each routed token by its expert's
weights.  With the tokens sorted by expert, that is one grouped product:
group ``i`` is ``group_sizes[i]`` consecutive rows of ``lhs``, multiplied by
``rhs[i]``.  Rows after the last group (assignments to experts this chip does
not hold) are not computed; their output rows are zero.

TPU mapping (after ``jax.experimental.pallas.ops.tpu.megablox.gmm``, whose
group metadata this kernel uses):

  * the m axis is cut into ``tm``-row tiles; a tile that two groups share is
    visited once per group, and each visit stores only its group's rows;
  * the grid is ``(n tiles, visited m tiles, k tiles)``; the number of
    visited m tiles is computed on the device from the group sizes, so an
    empty group and the rows no group holds cost no grid step;
  * scalar-prefetched group offsets, group ids, m tile ids and a layer
    index steer each step's blocks: the rhs block comes from the visit's
    group of that layer, read in place from a layer stack ``(L, g, k, n)``,
    so a program that scans the layers copies no layer's weights out;
  * an f32 VMEM accumulator; the k remainder of the last k tile is masked.

Config space: ``GmmConfig(tm, tk, tn)``, each a separate compiled kernel
named ``gmm_tm<tm>_tk<tk>_tn<tn>``, so the deployment-selection pipeline
prunes and selects among them like the matmul family's.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from .matmul import VMEM_BYTES

_DOUBLE_BUFFER = 2
_SUBLANES = 16  # a bf16 tile's rows


@dataclasses.dataclass(frozen=True, order=True)
class GmmConfig:
    """One deployable grouped-matmul kernel: its tile shape."""

    tm: int
    tk: int
    tn: int

    def name(self) -> str:
        return f"gmm_tm{self.tm}_tk{self.tk}_tn{self.tn}"

    def vmem_bytes(self, dtype_bytes: int = 2) -> int:
        tiles = (self.tm * self.tk + self.tk * self.tn + self.tm * self.tn) * dtype_bytes
        return _DOUBLE_BUFFER * tiles + self.tm * self.tn * 4  # + f32 accumulator

    def is_valid(self, dtype_bytes: int = 2) -> bool:
        if self.tk % 128 or self.tn % 128 or self.tm % _SUBLANES:
            return False
        return self.vmem_bytes(dtype_bytes) <= VMEM_BYTES

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "GmmConfig":
        return GmmConfig(**d)


_TM = (16, 32, 64, 128, 256, 512)
_TK = (128, 256, 512, 1024, 2048)
_TN = (128, 256, 512, 1024)


@functools.cache
def gmm_config_space() -> tuple[GmmConfig, ...]:
    out = [GmmConfig(*t) for t in itertools.product(_TM, _TK, _TN)]
    return tuple(c for c in out if c.is_valid())


DEFAULT_GMM_CONFIG = GmmConfig(tm=128, tk=512, tn=512)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _gmm_kernel(offsets, group_ids, m_tile_ids, _layer, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                tiles_k: int, k_rem: int, out_dtype):
    t, kk = pl.program_id(1), pl.program_id(2)
    tm, tn = acc_ref.shape

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(last: bool):
        x, w = lhs_ref[...], rhs_ref[...]
        if last and k_rem:  # the last k tile runs past k: zero what lies beyond
            cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            rows = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
            x = jnp.where(cols < k_rem, x.astype(jnp.float32), 0).astype(x.dtype)
            w = jnp.where(rows < k_rem, w.astype(jnp.float32), 0).astype(w.dtype)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
        if last:  # store this visit's group's rows of the tile, keep the rest
            g = group_ids[t]
            row = m_tile_ids[t] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...].astype(jnp.float32)
                                     ).astype(out_dtype)

    pl.when(kk < tiles_k - 1)(lambda: accumulate(False))
    pl.when(kk == tiles_k - 1)(lambda: accumulate(True))


def gmm_pallas(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
               config: GmmConfig = DEFAULT_GMM_CONFIG, *, layer=None, out_dtype=None,
               interpret: bool = False) -> jax.Array:
    """``lhs`` (m, k) rows sorted by group, ``rhs`` (g, k, n), ``group_sizes``
    (g,) int32 with a sum of at most m -> (m, n); rows past the last group
    are zero.

    With ``layer`` (an int32 scalar, may be traced), ``rhs`` is a layer
    stack (L, g, k, n) and the groups multiply ``rhs[layer]``, which the
    kernel reads in place."""
    if rhs.ndim == 3 and layer is None:
        rhs, layer = rhs[None], 0
    elif rhs.ndim != 4 or layer is None:
        raise ValueError(f"gmm: rhs {rhs.shape} with layer {layer}: "
                         "a (g, k, n) rhs takes no layer, an (L, g, k, n) stack needs one")
    m, k = lhs.shape
    _, g, k2, n = rhs.shape
    if k != k2 or group_sizes.shape != (g,):
        raise ValueError(f"gmm: {lhs.shape} @ {rhs.shape} with group sizes {group_sizes.shape}")
    out_dtype = out_dtype or lhs.dtype
    tm = min(config.tm, _round_up(m, _SUBLANES))
    tk = min(config.tk, _round_up(k, 128))
    tn = min(config.tn, _round_up(n, 128))
    mp = _round_up(m, tm)
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    group_sizes = group_sizes.astype(jnp.int32)
    held = jnp.sum(group_sizes)
    # A last pseudo-group takes the rows no group holds; it is never visited.
    sizes = jnp.concatenate([group_sizes, (mp - held)[None]])
    (offsets, group_ids, m_tile_ids), visits = make_group_metadata(
        group_sizes=sizes, m=mp, tm=tm, start_group=jnp.int32(0), num_nonzero_groups=g,
        visit_empty_groups=False)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tiles_k = pl.cdiv(k, tk)
    kernel = functools.partial(_gmm_kernel, tiles_k=tiles_k, k_rem=k % tk, out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, t, kk, off, gid, mt, lyr: (mt[t], kk)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda j, t, kk, off, gid, mt, lyr: (lyr[0], gid[t], kk, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, kk, off, gid, mt, lyr: (mt[t], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        interpret=interpret,
        name=config.name(),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES,
        ),
    )(offsets, group_ids, m_tile_ids, layer, lhs, rhs)
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    return jnp.where(rows < held, out[:m], 0).astype(out_dtype)
