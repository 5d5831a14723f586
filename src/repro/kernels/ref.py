"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(lhs: jax.Array, rhs: jax.Array, *, out_dtype=None) -> jax.Array:
    """Oracle for kernels/matmul.py: f32-accumulated 2-D matmul."""
    out_dtype = out_dtype or lhs.dtype
    return jnp.dot(lhs, rhs, preferred_element_type=jnp.float32).astype(out_dtype)


def gmm_ref(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *, out_dtype=None) -> jax.Array:
    """Oracle for kernels/gmm.py: XLA's ragged dot, f32-accumulated; rows
    past the last group are zero."""
    out_dtype = out_dtype or lhs.dtype
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32).astype(out_dtype)


def wkv_ref(r, k, v, logw, u, state=None):
    """Oracle for kernels/wkv.py: the chunked-WKV jnp reference.

    r/k/v/logw: (B, S, H, hd); u: (H, hd); state: (B, H, hd, hd) or None.
    """
    from repro.models.rwkv import wkv_chunked

    return wkv_chunked(r, k, v, logw, u, state)


def ssm_scan_ref(dtx, dta, b, c, state=None):
    """Oracle for kernels/ssm.py: associative-scan selective SSM.

    dtx (B,S,d); dta (B,S,d,N); b/c (B,S,N); state (B,d,N) or None.
    Returns (y (B,S,d) f32, final_state (B,d,N) f32).
    """
    bsz, s, d = dtx.shape
    n = b.shape[-1]
    abar = jnp.exp(dta.astype(jnp.float32))
    bx = dtx.astype(jnp.float32)[..., None] * b.astype(jnp.float32)[:, :, None, :]
    if state is not None:
        # fold the initial state in as a virtual step 0
        abar = jnp.concatenate([jnp.ones((bsz, 1, d, n), jnp.float32), abar], axis=1)
        bx = jnp.concatenate([state.astype(jnp.float32)[:, None], bx], axis=1)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (abar, bx), axis=1)
    if state is not None:
        h = h[:, 1:]
    y = (h * c.astype(jnp.float32)[:, :, None, :]).sum(-1)
    return y, h[:, -1]


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Oracle for kernels/attention.py.

    q: (sq, d), k/v: (skv, d) — single head; batching is vmapped by callers.
    """
    sq, d = q.shape
    skv = k.shape[0]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    logits = (q.astype(jnp.float32) * scale) @ k.astype(jnp.float32).T
    if causal:
        # Align the causal diagonal to the end (decode-style when sq < skv).
        qi = jnp.arange(sq)[:, None] + (skv - sq)
        ki = jnp.arange(skv)[None, :]
        logits = jnp.where(ki <= qi, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return (probs @ v.astype(jnp.float32)).astype(q.dtype)
