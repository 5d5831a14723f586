"""Shared model building blocks (pure JAX, functional params-in/out).

All GEMMs route through ``repro.kernels.ops.matmul`` (the ML-guided kernel
dispatcher; compiled Pallas on a TPU, ``jnp.dot`` elsewhere).  Attention uses
a memory-bounded chunked online-softmax implementation (flash-attention
algorithm at the jnp level) so that 32k-token prefill fits per-device HBM
without relying on XLA fusion heuristics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops

_NEG_INF = -1e30

# ---------------------------------------------------------------------------
# activation-sharding context (set by the launcher per mesh/shape; models
# call shard_act() at layer boundaries to anchor GSPMD propagation — without
# it the embedding gather can leave the batch axis replicated).
# ---------------------------------------------------------------------------
_ACT_SPEC: dict = {"batch": None, "seq": None}


def set_activation_sharding(batch_axes=None, seq_axes=None) -> None:
    _ACT_SPEC["batch"] = batch_axes
    _ACT_SPEC["seq"] = seq_axes


def shard_act(x: jax.Array) -> jax.Array:
    """Constrain a (B, S, ...) activation to the configured DP/SP axes."""
    if _ACT_SPEC["batch"] is None and _ACT_SPEC["seq"] is None:
        return x
    from jax.sharding import PartitionSpec as P

    spec = [None] * x.ndim
    spec[0] = _ACT_SPEC["batch"]
    if x.ndim >= 2:
        spec[1] = _ACT_SPEC["seq"]
    return jax.lax.with_sharding_constraint(x, P(*spec))


# MoE dispatch-buffer sharding (set by the launcher; see moe.moe_ffn).
# Constraining the (E, C, …) buffers' capacity dim turns the expert-GEMM
# partial-sum all-reduce into a reduce-scatter (§Perf hillclimb).
_MOE_SPEC: dict = {"ep": None, "cap": None}


def set_moe_sharding(ep_axes=None, cap_axes=None) -> None:
    _MOE_SPEC["ep"] = ep_axes
    _MOE_SPEC["cap"] = cap_axes


def shard_moe_buf(x: jax.Array) -> jax.Array:
    """Constrain an (E, C, feature) MoE dispatch/expert buffer."""
    if _MOE_SPEC["ep"] is None and _MOE_SPEC["cap"] is None:
        return x
    from jax.sharding import PartitionSpec as P

    spec = [None] * x.ndim
    spec[0] = _MOE_SPEC["ep"]
    if x.ndim >= 2:
        spec[1] = _MOE_SPEC["cap"]
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# remat (activation checkpoint) policy — set by the launcher per §Perf config.
# 'full' recomputes the whole layer in the backward (min memory, 4F flops);
# 'dots' saves GEMM outputs and recomputes only cheap elementwise ops
# (3F flops, more activation memory).
# ---------------------------------------------------------------------------
_REMAT: dict = {"policy": "full"}


def set_remat_policy(policy: str) -> None:
    assert policy in ("full", "dots"), policy
    _REMAT["policy"] = policy


def ckpt(fn):
    """jax.checkpoint with the configured save policy (used by layer scans)."""
    if _REMAT["policy"] == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(rng, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(rng, (d_in, d_out)) * scale).astype(dtype)


def stacked_dense_init(rng, n: int, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(rng, (n, d_in, d_out)) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf * scale) * w.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) absolute token positions."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, :, None, None] * freqs  # (B, S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked flash attention (jnp; grouped-query layout, no KV repeat)
# ---------------------------------------------------------------------------
def _attn_chunk(q, k, v, row0, col0, *, causal: bool, window: int, scale: float, valid_len=None):
    """One (q-chunk x kv-chunk) tile.  q: (B,KV,G,Lq,hd)  k/v: (B,KV,Lk,hd)."""
    logits = jnp.einsum("bkgqh,bkth->bkgqt", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    lq, lk = q.shape[-2], k.shape[-2]
    rows = row0 + jnp.arange(lq)[:, None]
    cols = col0 + jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), dtype=bool)
    if causal:
        mask &= cols <= rows
    if isinstance(window, jax.Array):
        # traced per-layer window (hymba layer scan): 0 => global attention
        mask &= jnp.where(window > 0, cols > rows - window, True)
    elif window:
        mask &= cols > rows - window
    if valid_len is not None:
        mask = mask & (cols < valid_len)
    logits = jnp.where(mask, logits, _NEG_INF)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgqt,bkth->bkgqh", p, v.astype(jnp.float32))
    return m, l, o


def flash_attention_jnp(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    valid_len: jax.Array | None = None,
    q_start: jax.Array | int | None = None,
) -> jax.Array:
    """Grouped-query online-softmax attention.

    q: (B, S, H, hd) with H = KV * G;  k/v: (B, T, KV, hd).
    Memory is bounded by q_chunk x kv_chunk tiles (flash algorithm), which is
    what lets 32k prefill / 4k train fit per device without Pallas.

    ``q_start`` places the queries at absolute positions ``q_start + i``
    within the KV sequence (chunked prefill: a mid-prompt chunk attends over
    the whole cache, causally bounded at its own frontier).  Default aligns
    the causal diagonal to the *end* of KV (``t - s``), the train/prefill
    convention.  May be a traced scalar.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    qg = q.reshape(b, s, kv, g, hd).transpose(0, 2, 3, 1, 4)  # (B,KV,G,S,hd)
    kt = k.transpose(0, 2, 1, 3)  # (B,KV,T,hd)
    vt = v.transpose(0, 2, 1, 3)
    qc = min(q_chunk, s)
    kc = min(kv_chunk, t)
    n_q = -(-s // qc)
    n_k = -(-t // kc)
    # Pad sequence dims to chunk multiples.
    if n_q * qc != s:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, n_q * qc - s), (0, 0)))
    if n_k * kc != t:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, n_k * kc - t), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, n_k * kc - t), (0, 0)))
        valid_len = jnp.asarray(t) if valid_len is None else valid_len
    # Causal diagonal: queries sit at q_start..q_start+s-1 (chunked prefill)
    # or end-aligned (train/prefill default).
    diag_off = q_start if q_start is not None else t - s

    kt_c = kt.reshape(b, kv, n_k, kc, hd).transpose(2, 0, 1, 3, 4)  # (n_k,B,KV,kc,hd)
    vt_c = vt.reshape(b, kv, n_k, kc, hd).transpose(2, 0, 1, 3, 4)

    def q_block(carry, qi):
        del carry
        qblk = jax.lax.dynamic_slice_in_dim(qg, qi * qc, qc, axis=3)
        row0 = qi * qc + diag_off

        def kv_step(state, inputs):
            ki, kblk, vblk = inputs
            m_prev, l_prev, acc = state
            m_c, l_c, o_c = _attn_chunk(
                qblk, kblk, vblk, row0, ki * kc, causal=causal, window=window, scale=scale, valid_len=valid_len
            )
            m_new = jnp.maximum(m_prev, m_c)
            corr = jnp.exp(m_prev - m_new)
            corr_c = jnp.exp(m_c - m_new)
            l_new = l_prev * corr + l_c * corr_c
            acc = acc * corr[..., None] + o_c * corr_c[..., None]
            return (m_new, l_new, acc), None

        init = (
            jnp.full((b, kv, g, qc), _NEG_INF, jnp.float32),
            jnp.zeros((b, kv, g, qc), jnp.float32),
            jnp.zeros((b, kv, g, qc, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (jnp.arange(n_k), kt_c, vt_c))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_block, None, jnp.arange(n_q))
    # blocks: (n_q, B, KV, G, qc, hd) -> (B, S, H, hd)
    out = blocks.transpose(1, 2, 3, 0, 4, 5).reshape(b, kv, g, n_q * qc, hd)
    out = out[:, :, :, :s].transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
    return out


def decode_attention_jnp(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid_len: jax.Array,
    *,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """One-token attention over a (B, T, KV, hd) cache.

    ``valid_len`` (B,): number of valid cache slots per sequence (supports
    both linear caches — pos+1 — and full ring buffers — min(pos+1, W)).

    int8-quantized caches pass per-(B,T,KV) ``k_scale``/``v_scale``; the
    dequant folds into the einsums (logits *= k_scale along t; probs *=
    v_scale before the value einsum) so only int8 bytes leave HBM (§Perf).
    """
    b, one, h, hd = q.shape
    t, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    qg = q.reshape(b, kv, g, hd)
    kc = k_cache.astype(q.dtype) if k_cache.dtype == jnp.int8 else k_cache
    # preferred_element_type keeps the accumulate in f32 WITHOUT materializing
    # an f32 copy of the (huge, resident) cache — §Perf: halves decode HBM
    # traffic vs .astype(f32) on the cache operands.
    logits = (
        jnp.einsum("bkgh,btkh->bkgt", qg, kc, preferred_element_type=jnp.float32) * scale
    )
    if k_scale is not None:
        logits = logits * k_scale.transpose(0, 2, 1)[:, :, None, :]  # (B,KV,1,T)
    cols = jnp.arange(t)[None, :]
    mask = cols < valid_len[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        p = p * v_scale.transpose(0, 2, 1)[:, :, None, :]
    vc = v_cache.astype(q.dtype) if v_cache.dtype == jnp.int8 else v_cache
    out = jnp.einsum("bkgt,btkh->bkgh", p.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# int8 KV quantization (per-token, per-kv-head absmax scales) — §Perf
# ---------------------------------------------------------------------------
def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(…, KV, hd) -> int8 values + f32 scales over the hd axis."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


# ---------------------------------------------------------------------------
# attention layer (GQA + optional cross-attention), cache-aware
# ---------------------------------------------------------------------------
def init_attention(rng, cfg, dtype=jnp.float32, n_layers: int | None = None) -> dict:
    """Stacked (n_layers leading dim) attention projection params."""
    n = n_layers if n_layers is not None else cfg.n_layers
    ks = jax.random.split(rng, 4)
    p = {
        "wq": stacked_dense_init(ks[0], n, cfg.d_model, cfg.q_dim, dtype),
        "wk": stacked_dense_init(ks[1], n, cfg.d_model, cfg.kv_dim, dtype),
        "wv": stacked_dense_init(ks[2], n, cfg.d_model, cfg.kv_dim, dtype),
        "wo": stacked_dense_init(ks[3], n, cfg.q_dim, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n, cfg.head_dim), dtype)
        p["k_norm"] = jnp.ones((n, cfg.head_dim), dtype)
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n, cfg.q_dim), dtype)
        p["bk"] = jnp.zeros((n, cfg.kv_dim), dtype)
        p["bv"] = jnp.zeros((n, cfg.kv_dim), dtype)
    return p


def attention_layer(
    p: dict,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    use_rope: bool = True,
    kv_input: jax.Array | None = None,
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_positions: jax.Array | None = None,
    cache_valid: jax.Array | None = None,
    chunk_start: jax.Array | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """GQA attention for one layer (params already sliced to this layer).

    Modes:
      * self-attention over x (train/prefill): kv_input is None, cache None.
      * cross-attention: kv_input is the memory sequence (no rope/causal).
      * cached decode: cache = (k_cache, v_cache) of shape (B, T, KV, hd),
        cache_positions (B,) current write positions; returns updated cache.
      * chunk append (chunked prefill): cache set, s > 1, ``chunk_start`` a
        traced scalar — writes k/v at absolute positions
        ``[chunk_start, chunk_start + s)`` and attends causally over the
        whole cache from those positions; returns updated cache.
    """
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = ops.matmul(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    kv_src = kv_input if kv_input is not None else x
    k = ops.matmul(kv_src, p["wk"])
    v = ops.matmul(kv_src, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, kv_src.shape[1], kvh, hd)
    v = v.reshape(b, kv_src.shape[1], kvh, hd)
    if cfg.qk_norm:  # per-head RMSNorm, before RoPE
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope and kv_input is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        quant = len(cache) == 4  # (k, v, k_scale, v_scale): int8 KV cache
        if quant:
            k_cache, v_cache, ks_cache, vs_cache = cache
        else:
            k_cache, v_cache = cache
        if s == 1 and chunk_start is None:  # decode: write one token
            bidx = jnp.arange(b)
            if quant:
                kq, ks = quantize_kv(k[:, 0])
                vq, vs = quantize_kv(v[:, 0])
                k_cache = k_cache.at[bidx, cache_positions].set(kq)
                v_cache = v_cache.at[bidx, cache_positions].set(vq)
                ks_cache = ks_cache.at[bidx, cache_positions].set(ks)
                vs_cache = vs_cache.at[bidx, cache_positions].set(vs)
            else:
                k_cache = k_cache.at[bidx, cache_positions].set(k[:, 0])
                v_cache = v_cache.at[bidx, cache_positions].set(v[:, 0])
            valid = cache_valid if cache_valid is not None else cache_positions + 1
            with jax.named_scope("ops.decode_attention"):
                out = decode_attention_jnp(
                    q, k_cache, v_cache, valid,
                    k_scale=ks_cache if quant else None,
                    v_scale=vs_cache if quant else None,
                )
            new_cache = (k_cache, v_cache, ks_cache, vs_cache) if quant else (k_cache, v_cache)
        elif chunk_start is not None:  # chunk append: write [start, start+s)
            if quant:
                raise NotImplementedError(
                    "chunked prefill does not support int8 KV caches"
                )
            # Scatter with mode="drop" (not dynamic_update_slice, which would
            # clamp a partially-out-of-range start and corrupt real tokens):
            # a padded final chunk may extend past cache_len — those writes
            # must vanish, and pad rows inside range are causally dead.
            pos = chunk_start + jnp.arange(s)
            k_cache = k_cache.at[:, pos].set(k, mode="drop")
            v_cache = v_cache.at[:, pos].set(v, mode="drop")
            out = flash_attention_jnp(
                q, k_cache, v_cache, causal=causal, window=window,
                q_start=chunk_start,
            )
            new_cache = (k_cache, v_cache)
        else:  # prefill: write the whole prefix
            t_cache = k_cache.shape[1]
            if quant:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                writes = ((k_cache, kq), (v_cache, vq), (ks_cache, ks), (vs_cache, vs))
            else:
                writes = ((k_cache, k), (v_cache, v))
            written = []
            for dst, src in writes:
                if s >= t_cache:
                    # Ring buffer (SWA): keep the last t_cache tokens, placed
                    # at their ring slots p % t_cache so decode can continue.
                    start = (s - t_cache) % t_cache
                    written.append(jnp.roll(src[:, -t_cache:], start, axis=1))
                else:
                    written.append(jax.lax.dynamic_update_slice_in_dim(dst, src, 0, axis=1))
            out = flash_attention_jnp(q, k, v, causal=causal, window=window)
            new_cache = tuple(written)
    else:
        out = flash_attention_jnp(q, k, v, causal=causal and kv_input is None, window=window)
    out = ops.matmul(out.reshape(b, s, h * hd), p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(rng, cfg, dtype=jnp.float32, n_layers: int | None = None) -> dict:
    n = n_layers if n_layers is not None else cfg.n_layers
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": stacked_dense_init(ks[0], n, cfg.d_model, cfg.d_ff, dtype),
        "w_up": stacked_dense_init(ks[1], n, cfg.d_model, cfg.d_ff, dtype),
        "w_down": stacked_dense_init(ks[2], n, cfg.d_ff, cfg.d_model, dtype),
    }


def mlp_layer(p: dict, x: jax.Array) -> jax.Array:
    gate = ops.matmul(x, p["w_gate"])
    up = ops.matmul(x, p["w_up"])
    return ops.matmul(jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up, p["w_down"])


# ---------------------------------------------------------------------------
# embeddings / loss
# ---------------------------------------------------------------------------
def init_embedding(rng, cfg, dtype=jnp.float32) -> dict:
    pv = cfg.padded_vocab()
    ks = jax.random.split(rng, 2)
    p = {"embed": (jax.random.normal(ks[0], (pv, cfg.d_model)) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(ks[1], (cfg.d_model, pv)) * 0.02).astype(dtype)
    return p


def embed_tokens(p: dict, tokens: jax.Array) -> jax.Array:
    return p["embed"][tokens]


def logits_from_hidden(p: dict, x: jax.Array, cfg) -> jax.Array:
    if "unembed" in p:
        return ops.matmul(x, p["unembed"], out_dtype=jnp.float32)
    return ops.matmul(x, p["embed"].T, out_dtype=jnp.float32)


def cross_entropy_loss(logits: jax.Array, targets: jax.Array, vocab: int) -> jax.Array:
    """Mean token NLL; padded-vocab slots are masked out of the softmax."""
    pv = logits.shape[-1]
    if pv != vocab:
        pad_mask = jnp.arange(pv) >= vocab
        logits = jnp.where(pad_mask, _NEG_INF, logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
