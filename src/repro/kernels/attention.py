"""Parameterized Pallas TPU flash attention.

Second tunable kernel family (the perf-critical op of every assigned
transformer): online-softmax attention with BlockSpec tiling over the query
and key/value sequence dimensions.

Tunable parameters (the analogue of the matmul tile space):
  * ``block_q``   — query rows per grid step (MXU rows / VMEM).
  * ``block_kv``  — key/value rows per inner step (VMEM vs revisit count).

Causal masking aligns the diagonal to the *end* of the KV sequence, so the
same kernel serves training (sq == skv), chunked prefill, and decode
(sq == 1, skv == cache length).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True, order=True)
class AttentionConfig:
    block_q: int
    block_kv: int

    def name(self) -> str:
        return f"fa_bq{self.block_q}_bkv{self.block_kv}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "AttentionConfig":
        return AttentionConfig(**d)


_BLOCK_Q = (128, 256, 512)
_BLOCK_KV = (128, 256, 512, 1024)


@functools.cache
def attention_config_space() -> tuple[AttentionConfig, ...]:
    return tuple(AttentionConfig(bq, bkv) for bq, bkv in itertools.product(_BLOCK_Q, _BLOCK_KV))


DEFAULT_ATTN_CONFIG = AttentionConfig(block_q=256, block_kv=512)


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *, n_kv: int, causal: bool, scale: float, sq: int, skv: int
):
    kv_idx = pl.program_id(1)
    q_idx = pl.program_id(0)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)

    bq, bkv = logits.shape
    cols = kv_idx * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = cols < skv  # padded KV columns contribute nothing
    if causal:
        # Global row/col positions; diagonal aligned to the end of KV.
        rows = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + (skv - sq)
        mask &= cols <= rows
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_cur = jnp.maximum(m_prev, logits.max(axis=-1))
    correction = jnp.exp(m_prev - m_cur)
    p = jnp.exp(logits - m_cur[:, None])
    l_cur = l_prev * correction + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * correction[:, None] + p @ v_ref[...].astype(jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(kv_idx == n_kv - 1)
    def _store():
        l = l_ref[:, 0]
        out_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)[:, None]).astype(out_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    config: AttentionConfig = DEFAULT_ATTN_CONFIG,
    *,
    causal: bool = True,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-head flash attention: q (sq, d), k/v (skv, d) -> (sq, d)."""
    sq, d = q.shape
    skv = k.shape[0]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    bq = min(config.block_q, _round_up(sq, 8))
    bkv = min(config.block_kv, _round_up(skv, 128))
    # Pad sequences to block multiples; padded KV columns are masked off via
    # the causal/global column index test below, padded Q rows are sliced off.
    sqp, skvp = _round_up(sq, bq), _round_up(skv, bkv)
    orig_sq = sq
    if sqp != sq:
        q = jnp.pad(q, ((0, sqp - sq), (0, 0)))
    if skvp != skv:
        k = jnp.pad(k, ((0, skvp - skv), (0, 0)))
        v = jnp.pad(v, ((0, skvp - skv), (0, 0)))
    n_q = sqp // bq
    n_kv = skvp // bkv

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, causal=causal, scale=scale, sq=sq, skv=skv
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_q, n_kv),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bkv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bkv, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name=config.name(),
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
    )(q, k, v)
    if sqp != orig_sq:
        out = out[:orig_sq]
    return out


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
