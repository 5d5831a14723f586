"""Plain reference of a mixture-of-experts decoder with grouped-query
attention and QK-norm (Qwen3-MoE), in float32, at a chip's expert share.

The configuration as its file states it: token embedding; per layer an
RMSNorm, grouped-query attention whose q and k heads each get an RMSNorm
over the head dimension before rotary positions (rotate-half form,
``theta ** (-i / (head_dim / 2))``) over causal keys, a residual, an RMSNorm
and the expert layer with a residual; a final RMSNorm and the untied head.

The expert layer: the router's logits over all ``num_experts`` experts,
softmax, the ``num_experts_per_tok`` largest probabilities renormalised to
sum to one (``norm_topk_prob``), and the gate-weighted SwiGLU
(``silu(x W_gate) * (x W_up) W_down``) of each chosen expert.  At a share,
the sum runs over the held experts only, ``first_expert_held`` to
``first_expert_held + num_experts_held - 1`` (file keys): a token's other
experts live on other chips, and what they add is left out here as in the
program.  Every expert held is computed for every token, weighted by its
gate, zero where the token did not choose it: no sorting, no groups.

Every product runs in float32 at ``Precision.HIGHEST`` on the bfloat16
weights, upcast one layer at a time (a scan over the layers, and over the
held experts inside a layer).  It imports nothing of the program; it reads
the parameter tree by the names the serving program takes (``emb``,
``blocks`` with ``attn``, ``moe``, ``ln1``, ``ln2``, ``final_norm``).

``control=True`` computes every weight product, the router's included, in
fp8 (e4m3) instead: both operands scaled to the format's range per row
(activations) and per column (weights), rounded, multiplied and summed in
float32.  That is the step below the configuration's bfloat16.
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


def _experts(y, moe, c, control):
    """The expert layer's output at this chip's share, (t, d)."""
    k = c["num_experts_per_tok"]
    first, held = c["first_expert_held"], c["num_experts_held"]
    probs = jax.nn.softmax(_mm(y, moe["router"], control), axis=-1)  # (t, e)
    top, ids = jax.lax.top_k(probs, k)
    top = top / top.sum(axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(y.shape[0])[:, None], ids].set(top)

    def expert(out, i):
        w_gate, w_up, w_down = (jax.lax.dynamic_index_in_dim(moe[n], i, keepdims=False)
                                for n in ("w_gate", "w_up", "w_down"))
        h = jax.nn.silu(_mm(y, w_gate, control)) * _mm(y, w_up, control)
        return out + gate[:, first + i, None] * _mm(h, w_down, control), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y), jnp.arange(held))
    return out


def _hidden(params, tokens, c, control):
    t = tokens.shape[0]
    h, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(t)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    x = params["emb"]["embed"][tokens].astype(F32)

    def layer(x, blk):
        a = blk["attn"]
        y = _rms(x, blk["ln1"], eps)
        q = _rms(_mm(y, a["wq"], control).reshape(t, h, hd), a["q_norm"], eps)
        k = _rms(_mm(y, a["wk"], control).reshape(t, kvh, hd), a["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        v = _mm(y, a["wv"], control).reshape(t, kvh, hd)
        k, v = jnp.repeat(k, h // kvh, axis=1), jnp.repeat(v, h // kvh, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, h * hd)
        x = x + _mm(o, a["wo"], control)
        return x + _experts(_rms(x, blk["ln2"], eps), blk["moe"], c, control), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"], eps)


def _logit_blocks(params, x, c, control, fn):
    """Apply ``fn(logits_block, row_index_block)`` to the head's logits in
    blocks of ``ROWS`` rows and concatenate the results."""
    t = x.shape[0]
    w = params["emb"]["unembed"]
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


def _items(params, config: dict) -> tuple:
    if config["tie_word_embeddings"]:
        raise ValueError("this reference reads an untied head (emb.unembed)")
    held = params["blocks"]["moe"]["w_gate"].shape[1]
    if held != config["num_experts_held"]:
        raise ValueError(f"the weights hold {held} experts, the file states "
                         f"{config['num_experts_held']}")
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
            "rope_theta", "vocab_size", "num_experts_per_tok",
            "first_expert_held", "num_experts_held")
    return tuple((k, config[k]) for k in keys)


def reference_stats(params, config: dict, tokens, targets, alt):
    """Per position of ``tokens`` (length a multiple of ``ROWS``): the float32
    reference's largest logit, its logit of ``targets[i]`` and of ``alt[i]``."""
    return _reference_stats(params, tokens, targets, alt, _items(params, config))


def control_argmax(params, config: dict, tokens):
    """Per position: the token that the fp8 control puts first."""
    return _control_argmax(params, tokens, _items(params, config))


def logits(params, config: dict, tokens):
    """The float32 reference's logits at every position of ``tokens``
    (length a multiple of ``ROWS``), for tests."""
    return _logits(params, tokens, _items(params, config))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _logits(params, tokens, cfg_items):
    c = dict(cfg_items)
    x = _hidden(params, tokens, c, False)
    return _logit_blocks(params, x, c, False, lambda logits, r: logits)
