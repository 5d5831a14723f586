"""Plain reference of a dense decoder-only transformer, in float32.

The configuration as its file states it: token embedding; per layer an
RMSNorm, grouped-query attention with rotary positions (rotate-half form,
``theta ** (-i / (head_dim / 2))``) over causal keys, a residual, an RMSNorm
and a SwiGLU feed-forward (``silu(x W_gate) * (x W_up) W_down``) with a
residual; a final RMSNorm and the head (untied, or the embedding's transpose
when tied).  Every product runs in float32 at ``Precision.HIGHEST`` on the
bfloat16 weights.  It imports nothing of the program; it reads the parameter
tree by the names the serving program takes (``emb``, ``blocks``,
``final_norm``).  No cache, no batching, no kernels: one sequence at a time,
layer after layer.

``control=True`` computes every weight product in fp8 (e4m3) instead: both
operands scaled to the format's range per row (activations) and per column
(weights), rounded to fp8, multiplied and summed in float32.  That is the
step below the configuration's bfloat16 that would tempt a later change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn
ROWS = 256  # head rows per block, to bound the logits buffer


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, control):
    x, w = x.astype(F32), w.astype(F32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _hidden(params, tokens, c, control):
    t = tokens.shape[0]
    h, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(t)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    x = params["emb"]["embed"][tokens].astype(F32)

    def layer(x, blk):
        a, f = blk["attn"], blk["ffn"]
        y = _rms(x, blk["ln1"], eps)
        q = _rope(_mm(y, a["wq"], control).reshape(t, h, hd), pos, theta)
        k = _rope(_mm(y, a["wk"], control).reshape(t, kvh, hd), pos, theta)
        v = _mm(y, a["wv"], control).reshape(t, kvh, hd)
        k, v = jnp.repeat(k, h // kvh, axis=1), jnp.repeat(v, h // kvh, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h * hd)
        x = x + _mm(o, a["wo"], control)
        y = _rms(x, blk["ln2"], eps)
        x = x + _mm(jax.nn.silu(_mm(y, f["w_gate"], control)) * _mm(y, f["w_up"], control),
                    f["w_down"], control)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"], eps)


def _head(params, c):
    emb = params["emb"]
    return emb["embed"].T if c["tie_word_embeddings"] else emb["unembed"]


def _logit_blocks(params, x, c, control, fn):
    """Apply ``fn(logits_block, row_index_block)`` to the head's logits in
    blocks of ``ROWS`` rows and concatenate the results."""
    t = x.shape[0]
    w = _head(params, c)
    rows = jnp.arange(t).reshape(t // ROWS, ROWS)

    def one(r):
        logits = _mm(x[r], w, control)[:, : c["vocab_size"]]
        return fn(logits, r)

    out = jax.lax.map(one, rows)
    return jax.tree.map(lambda a: a.reshape(t, *a.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _reference_stats(params, tokens, targets, alt, cfg_items):
    c = dict(cfg_items)
    x = _hidden(params, tokens, c, False)

    def fn(logits, r):
        pick = lambda idx: jnp.take_along_axis(logits, idx[r][:, None], axis=1)[:, 0]
        return logits.max(axis=1), pick(targets), pick(alt)

    return _logit_blocks(params, x, c, False, fn)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _control_argmax(params, tokens, cfg_items):
    c = dict(cfg_items)
    x = _hidden(params, tokens, c, True)
    return _logit_blocks(params, x, c, True, lambda logits, r: logits.argmax(axis=1))


def _items(config: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings", "vocab_size")
    return tuple((k, config[k]) for k in keys)


def reference_stats(params, config: dict, tokens, targets, alt):
    """Per position of ``tokens`` (length a multiple of ``ROWS``): the float32
    reference's largest logit, its logit of ``targets[i]`` and of ``alt[i]``."""
    return _reference_stats(params, tokens, targets, alt, _items(config))


def control_argmax(params, config: dict, tokens):
    """Per position: the token that the fp8 control puts first."""
    return _control_argmax(params, tokens, _items(config))
