"""Mixture-of-Experts FFN: token-choice top-k routing, two expert layers.

Serving (prefill, chunked prefill, decode) runs :func:`moe_serve`, a dropless
layer over the experts this chip holds (``MoESpec.first_expert`` and
``n_held``: an expert-parallel share, every expert by default):

  1. the router keeps its published form: float32 logits over every expert,
     softmax, top-k, gates renormalised over the k;
  2. the assignments that fall on held experts are sorted by expert (the
     others sort last and are not computed);
  3. the three SwiGLU products run as grouped GEMMs through
     ``ops.expert_gemm``, one group per held expert, sized on the device;
     given the layer index, the GEMMs read the layer's experts in place from
     the whole layer stacks, so the serving scan slices no expert weights;
  4. each assignment's gate-weighted output is gathered back and summed
     over k.  No assignment is dropped; one on an expert held elsewhere adds
     nothing here (its chip adds it).

It also returns the layer's counters: assignments on held experts and held
experts that got at least one.

Training runs :func:`moe_ffn`, which holds every expert.  Its dispatch uses
scatter/gather with capacity buckets (no dense (T, E, C) dispatch tensor,
which would be quadratically infeasible at 1M tokens):

  1. router top-k -> (token, expert) assignments;
  2. position-in-expert via a cumsum over expert one-hots;
  3. scatter tokens into an (E, C, d) buffer — sharded E over the 'model'
     mesh axis, so under GSPMD the scatter lowers to the expert all-to-all;
  4. per-expert SwiGLU GEMMs (einsum over the local experts);
  5. gather back + weighted combine; tokens over capacity are dropped
     (standard capacity-factor semantics) and pass through the residual.

Returns an auxiliary load-balance loss (Switch-style).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .layers import shard_moe_buf, stacked_dense_init

# The serving layer's counters, per layer, in the order moe_serve returns them.
COUNTERS = ("moe_held_assignments", "moe_held_experts_used")
# The held experts' weights: (n_layers, held, k, n) stacks.
EXPERTS = ("w_gate", "w_up", "w_down")


def init_moe(rng, cfg, dtype=jnp.float32, n_layers: int | None = None) -> dict:
    """The router over every expert, and the held experts' weights."""
    n = n_layers if n_layers is not None else cfg.n_layers
    e, held = cfg.moe.n_experts, cfg.moe.held
    ks = jax.random.split(rng, 4)
    expert = lambda k, a, b: (jax.random.normal(k, (n, held, a, b)) * 0.02).astype(dtype)
    return {
        "router": stacked_dense_init(ks[0], n, cfg.d_model, e, dtype),
        "w_gate": expert(ks[1], cfg.d_model, cfg.d_ff),
        "w_up": expert(ks[2], cfg.d_model, cfg.d_ff),
        "w_down": expert(ks[3], cfg.d_ff, cfg.d_model),
    }


def route(p: dict, xt: jax.Array, moe) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router over all ``moe.n_experts``: (probs (T, E), renormalised gates
    (T, k), expert ids (T, k))."""
    logits = ops.matmul(xt, p["router"], out_dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, moe.top_k)
    return probs, gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9), ids


def moe_serve(p: dict, x: jax.Array, cfg, layer=None) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, counts (2,) int32: assignments on held experts,
    held experts with at least one).  The router is one layer's; the expert
    weights are one layer's (held, k, n), or with ``layer`` (an int32 scalar,
    may be traced) the stacks (n_layers, held, k, n), read at that layer."""
    b, s, d = x.shape
    moe = cfg.moe
    held, top_k = moe.held, moe.top_k
    t = b * s
    xt = x.reshape(t, d)
    with jax.named_scope("ops.moe_route"):
        _, gates, ids = route(p, xt, moe)
        local = ids.reshape(-1) - moe.first_expert  # (T*k,), token-major
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)  # not held: a last group, not computed
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        rows = xt[order // top_k]
    expected = moe.held_rows(t)
    gate = ops.expert_gemm(rows, p["w_gate"], sizes, layer=layer, rows=expected)
    up = ops.expert_gemm(rows, p["w_up"], sizes, layer=layer, rows=expected)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    y = ops.expert_gemm(h, p["w_down"], sizes, layer=layer, rows=expected)
    with jax.named_scope("ops.moe_combine"):
        slot = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
        w = jnp.where(mine, gates.reshape(-1), 0.0)
        out = (y[slot].astype(jnp.float32) * w[:, None]).reshape(t, top_k, d).sum(axis=1)
    counts = jnp.stack([mine.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out.astype(x.dtype).reshape(b, s, d), counts


def moe_ffn(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).  Params already sliced to one layer.
    The training layer: capacity buckets over every expert."""
    b, s, d = x.shape
    e, top_k = cfg.moe.n_experts, cfg.moe.top_k
    if cfg.moe.held != e:
        raise ValueError("the capacity-bucketed layer holds every expert; "
                         f"this configuration holds {cfg.moe.held} of {e}")
    t = b * s
    xt = x.reshape(t, d)

    probs, gate_vals, expert_ids = route(p, xt, cfg.moe)  # (T, E), (T, k), (T, k)

    # Load-balance aux loss (Switch): E * sum_e f_e * p_e.
    me = probs.mean(0)  # mean router prob per expert
    ce = jnp.zeros(e).at[expert_ids.reshape(-1)].add(1.0) / (t * top_k)
    aux = e * jnp.sum(me * ce)

    # Position of each assignment within its expert's capacity bucket.
    flat_e = expert_ids.reshape(-1)  # (T*k,) — k-major per token
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos_in_e = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)  # (T*k,)
    capacity = max(1, int(t * top_k / e * cfg.moe.capacity_factor))
    keep = pos_in_e < capacity
    slot = jnp.minimum(pos_in_e, capacity - 1)

    # Dispatch: (E, C, d) buffer, sharded E over the 'model' axis by callers.
    tok_idx = jnp.repeat(jnp.arange(t), top_k)
    buf = jnp.zeros((e, capacity, d), x.dtype)
    buf = buf.at[flat_e, slot].add(xt[tok_idx] * keep[:, None].astype(x.dtype))
    buf = shard_moe_buf(buf)

    # Expert SwiGLU (local experts under EP sharding).
    gate = shard_moe_buf(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    up = shard_moe_buf(jnp.einsum("ecd,edf->ecf", buf, p["w_up"]))
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    y = shard_moe_buf(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))

    # Combine: gather each assignment's expert output, weight, sum over k.
    # Cast y to the activation dtype BEFORE the gather: the gather crosses
    # the expert (EP) shards, so its collective moves half the bytes in bf16
    # (§Perf — the f32 combine all-reduce dominated the MoE prefill profile).
    out_flat = y.astype(x.dtype)[flat_e, slot] * (
        keep[:, None] * gate_vals.reshape(-1)[:, None]
    ).astype(x.dtype)
    out = out_flat.reshape(t, top_k, d).sum(axis=1)
    return out.reshape(b, s, d), aux
