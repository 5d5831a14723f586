"""Parameterized Pallas TPU matmul — the tunable kernel family (paper §3).

The paper's case study tunes a SYCL GEMM over tile sizes (R, A, C) and
work-group shapes, 640 configurations.  The TPU-native analogue of that
parameter space is the Pallas ``BlockSpec`` tiling:

  * ``block_m``  — output-tile rows per grid step.  Small values (8/16/32)
    under-fill the 128x128 MXU but are the right choice for tall-skinny /
    decode-GEMV problems (the paper's "tall skinny" pathology, §3.2).
  * ``block_n``  — output-tile cols (lane dimension, multiples of 128).
  * ``block_k``  — contraction-tile depth: trades VMEM footprint against
    grid-step overhead and, when ``k <= block_k`` (single k-step), unlocks
    LHS-tile reuse across the inner grid dimension.
  * ``order``    — grid iteration order ``mnk`` or ``nmk`` (which of M/N is
    the inner loop); controls which operand's tiles get revisited without
    an HBM reload (the analogue of the paper's (8,16) vs (16,8) work-groups).

Every config is a distinct compiled artifact, exactly like the paper's SPIR
blobs — hence the deployment-subset-selection problem that `repro.core`
solves.

The kernel accumulates in an f32 VMEM scratch accumulator and writes the
output tile once on the final k step (standard TPU matmul pipeline shape).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# v5e-flavoured VMEM budget (of 128 MiB physical) used for config validity;
# the kernel asks the compiler for exactly this scoped limit, and the perf
# model uses the same constant.
VMEM_BYTES = 48 * 1024 * 1024
_DOUBLE_BUFFER = 2  # Pallas pipelines input tiles with double buffering.


@dataclasses.dataclass(frozen=True, order=True)
class MatmulConfig:
    """One deployable kernel instantiation (a 'binary blob' in paper terms)."""

    block_m: int
    block_n: int
    block_k: int
    order: str = "mnk"  # 'mnk' (n inner) or 'nmk' (m inner); k always fastest

    def name(self) -> str:
        return f"mm_bm{self.block_m}_bn{self.block_n}_bk{self.block_k}_{self.order}"

    def vmem_bytes(self, dtype_bytes: int = 2) -> int:
        lhs = self.block_m * self.block_k * dtype_bytes
        rhs = self.block_k * self.block_n * dtype_bytes
        out = self.block_m * self.block_n * dtype_bytes
        acc = self.block_m * self.block_n * 4  # f32 accumulator scratch
        return _DOUBLE_BUFFER * (lhs + rhs + out) + acc

    def is_valid(self, dtype_bytes: int = 2) -> bool:
        if self.order not in ("mnk", "nmk"):
            return False
        if self.block_n % 128 or self.block_k % 128:
            return False  # lane dimension must be 128-aligned
        if self.block_m % 8:
            return False  # sublane alignment
        return self.vmem_bytes(dtype_bytes) <= VMEM_BYTES

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "MatmulConfig":
        return MatmulConfig(**d)


_BLOCK_M = (8, 16, 32, 64, 128, 256, 512)
_BLOCK_N = (128, 256, 512)
_BLOCK_K = (128, 256, 512, 1024, 2048)
_ORDERS = ("mnk", "nmk")


@functools.cache
def config_space() -> tuple[MatmulConfig, ...]:
    """The full tunable space (all VMEM-valid combinations)."""
    out = []
    for bm, bn, bk, order in itertools.product(_BLOCK_M, _BLOCK_N, _BLOCK_K, _ORDERS):
        cfg = MatmulConfig(bm, bn, bk, order)
        if cfg.is_valid():
            out.append(cfg)
    return tuple(out)


DEFAULT_CONFIG = MatmulConfig(block_m=128, block_n=128, block_k=512, order="mnk")


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------
def _matmul_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, n_k: int, out_dtype):
    """Grid step: accumulate lhs_block @ rhs_block into the f32 scratch."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        lhs_ref[...],
        rhs_ref[...],
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_idx == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_dtype)


def matmul_pallas(
    lhs: jax.Array,
    rhs: jax.Array,
    config: MatmulConfig = DEFAULT_CONFIG,
    *,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``lhs @ rhs`` via the parameterized Pallas kernel.

    ``lhs``: (m, k), ``rhs``: (k, n).  Blocks are padded by Pallas when the
    problem dims do not divide the block dims.
    """
    if lhs.ndim != 2 or rhs.ndim != 2:
        raise ValueError(f"matmul_pallas expects 2-D operands, got {lhs.shape} @ {rhs.shape}")
    m, k = lhs.shape
    k2, n = rhs.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {lhs.shape} @ {rhs.shape}")
    out_dtype = out_dtype or lhs.dtype
    orig_m, orig_n = m, n
    bm = min(config.block_m, _round_up(m, 8))
    bn = min(config.block_n, _round_up(n, 128))
    bk = min(config.block_k, _round_up(k, 128))
    # Zero-pad to block multiples: k-padding must be zeros for correctness
    # (it participates in the contraction); m/n padding is sliced off below.
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    if (mp, kp) != (m, k):
        lhs = jnp.pad(lhs, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        rhs = jnp.pad(rhs, ((0, kp - k), (0, np_ - n)))
    m, k, n = mp, kp, np_
    n_m = pl.cdiv(m, bm)
    n_n = pl.cdiv(n, bn)
    n_k = pl.cdiv(k, bk)

    if config.order == "mnk":
        grid = (n_m, n_n, n_k)
        lhs_map = lambda i, j, s: (i, s)
        rhs_map = lambda i, j, s: (s, j)
        out_map = lambda i, j, s: (i, j)
    else:  # 'nmk': m is the inner spatial loop
        grid = (n_n, n_m, n_k)
        lhs_map = lambda j, i, s: (i, s)
        rhs_map = lambda j, i, s: (s, j)
        out_map = lambda j, i, s: (i, j)

    kernel = functools.partial(_matmul_kernel, n_k=n_k, out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lhs_map),
            pl.BlockSpec((bk, bn), rhs_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # The budget is_valid() admits configs against; the compiler's
            # default scoped limit is smaller and refuses the largest tiles.
            vmem_limit_bytes=VMEM_BYTES,
        ),
    )(lhs, rhs)
    if (orig_m, orig_n) != (m, n):
        out = out[:orig_m, :orig_n]
    return out


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
