"""The dropless expert layer that serving runs, at an expert-parallel share.

A toy of Qwen3-MoE's layout (16 experts, top-4, QK-norm) on seeded random
float32 weights, against the plain reference the benchmark judges the chip
runs with (``chipbench/references/moe_gqa.py``): the serving programs
(monolithic prefill, chunks into the paged pool, paged decode) agree with
its full forward pass; the shares of every chip add up to the whole layer;
a router that sends every token to the same experts drops none; planted
faults (capacity buckets, experts not held, another layer's experts) fail
the comparison; the counters equal a host count of the routing.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MoESpec
from repro.models import moe as MOE
from repro.models.model import build_model
from repro.serve.kvpool import KVPool

ROOT = Path(__file__).resolve().parents[1]
E, TOP_K, HELD = 16, 4, 4
TOL = 2e-3  # float32 program against float32-at-HIGHEST reference: rounding only


def _reference():
    path = ROOT / "chipbench" / "references" / "moe_gqa.py"
    spec = importlib.util.spec_from_file_location("moe_gqa_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _cfg(first: int = 0, held: int = HELD):
    return dataclasses.replace(
        registry.get("qwen3-moe-235b-a22b"), n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=32, vocab=256, moe=MoESpec(E, TOP_K, first_expert=first, n_held=held))


def _ref_config(cfg) -> dict:
    return {"num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "vocab_size": cfg.vocab, "num_experts_per_tok": cfg.moe.top_k,
            "first_expert_held": cfg.moe.first_expert, "num_experts_held": cfg.moe.held,
            "tie_word_embeddings": False}


def _params(cfg, seed=0):
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.key(seed))
    # Experts at the benchmark's scale (normal * sqrt(2 / (fan_in + fan_out)),
    # not the program's 0.02), so that what they add shows in the logits.
    moe = params["blocks"]["moe"]
    for n in ("w_gate", "w_up", "w_down"):
        moe[n] = moe[n] / 0.02 * (2.0 / sum(moe[n].shape[-2:])) ** 0.5
    # QK-norm scales away from 1, so that a missing norm shows
    scale = lambda a: 1.0 + 0.5 * jax.random.uniform(jax.random.key(seed + 1), a.shape)
    params["blocks"]["attn"]["q_norm"] = scale(params["blocks"]["attn"]["q_norm"])
    params["blocks"]["attn"]["k_norm"] = scale(params["blocks"]["attn"]["k_norm"]) * 0.9
    return model, params


def _ref_logits(params, cfg, seq) -> np.ndarray:
    tokens = np.zeros(REF.ROWS, np.int32)
    tokens[: len(seq)] = seq
    return np.asarray(REF.logits(params, _ref_config(cfg), tokens))[: len(seq)]


def _serve(model, params, prompt, n_new, chunk=8, lanes=4):
    """Chunked prefill of ``prompt`` into lane 0 of a paged pool, then greedy
    decode at ``lanes`` lanes (the others idle, as padding); returns the
    served tokens and the logits of each (first from the final chunk)."""
    pool = KVPool(model, lanes=lanes, cache_len=64, block_size=8)
    pool.ensure(0, len(prompt) + n_new)
    chunk_fn = jax.jit(model.prefill_chunk)
    decode_fn = jax.jit(model.decode_step)
    for s0 in range(0, len(prompt), chunk):
        part = np.zeros(chunk, np.int32)
        real = prompt[s0: s0 + chunk]
        part[: len(real)] = real
        logits, cache = chunk_fn(params, pool.gather([0]), jnp.asarray(part[None]),
                                 jnp.int32(s0), jnp.int32(len(real) - 1))
        pool.scatter([0], cache)
    out, got = [], [np.asarray(logits[0, -1])]
    pos = len(prompt)
    for _ in range(n_new):
        out.append(int(np.argmax(got[-1])))
        if len(out) == n_new:
            break
        tokens = np.zeros((lanes, 1), np.int32)
        tokens[0, 0] = out[-1]
        positions = np.zeros(lanes, np.int32)
        positions[0] = pos
        logits, cache = decode_fn(params, pool.gather(range(lanes)), jnp.asarray(tokens),
                                  jnp.asarray(positions))
        pool.scatter(range(lanes), cache)
        got.append(np.asarray(logits[0, -1]))
        pos += 1
    return out, np.stack(got), pool


def _gap(cfg, params, ref_params=None, ref_cfg=None, n_new=6):
    """Largest logit difference of the paged serving path against the
    reference's full forward pass over the prompt and the served tokens."""
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    prompt = np.asarray(jax.random.randint(jax.random.key(7), (19,), 0, cfg.vocab))
    out, got, _ = _serve(model, params, prompt, n_new)
    seq = np.concatenate([prompt, out[:-1]])
    want = _ref_logits(ref_params or params, ref_cfg or cfg, seq)[len(prompt) - 1:]
    return float(np.abs(got[:, : cfg.vocab] - want).max())


@pytest.mark.parametrize("first", [0, 12])
def test_paged_serving_agrees_with_the_reference(first):
    cfg = _cfg(first)
    _, params = _params(cfg)
    assert _gap(cfg, params) < TOL


def _prefill_gap(cfg, params):
    """Largest logit difference of monolithic prefill's last row against the
    reference."""
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    prompt = np.asarray(jax.random.randint(jax.random.key(3), (1, 23), 0, cfg.vocab))
    logits, _ = jax.jit(model.prefill, static_argnums=2)(params, {"tokens": prompt}, 32)
    want = _ref_logits(params, cfg, prompt[0])[-1]
    return float(np.abs(np.asarray(logits[0, -1, : cfg.vocab]) - want).max())


def test_monolithic_prefill_agrees_with_the_reference():
    cfg = _cfg()
    _, params = _params(cfg)
    assert _prefill_gap(cfg, params) < TOL


@pytest.mark.parametrize("program", [_gap, _prefill_gap], ids=["paged", "prefill"])
def test_planted_wrong_layer_of_the_expert_stacks_fails_the_comparison(monkeypatch, program):
    """The programs pass the whole expert stacks and the scan's layer index;
    a layer that reads another layer's experts (of the two) fails."""
    cfg = _cfg()
    assert cfg.n_layers == 2
    _, params = _params(cfg)
    serve = MOE.moe_serve
    monkeypatch.setattr(MOE, "moe_serve", lambda p, x, cfg, layer: serve(p, x, cfg, 1 - layer))
    assert program(cfg, params) > 10 * TOL


def test_the_shares_add_up_to_the_whole_layer():
    cfg = _cfg(held=0)
    _, params = _params(cfg)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.key(5), (3, 5, cfg.d_model))
    whole, counts = MOE.moe_serve(p, x, cfg)
    parts, total = [], 0
    for first in range(0, E, HELD):
        share = dict(p, **{n: p[n][first: first + HELD] for n in ("w_gate", "w_up", "w_down")})
        out, c = MOE.moe_serve(share, x, _cfg(first))
        parts.append(out)
        total += int(c[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-5)
    assert total == int(counts[0]) == 3 * 5 * TOP_K


def _skewed(cfg, p, t):
    """Inputs and a router that send every one of ``t`` tokens to experts
    0..top_k-1 (all held)."""
    x = jnp.abs(jax.random.normal(jax.random.key(9), (t, 1, cfg.d_model))) + 0.1
    router = jnp.zeros_like(p["router"]).at[:, :TOP_K].set(
        jnp.linspace(1.0, 2.0, TOP_K)[None, :] * jnp.ones((cfg.d_model, 1)))
    return x, dict(p, router=router)


def _dense_layer(p, x, cfg):
    """Every held expert on every token, weighted by its renormalised gate."""
    xt = x.reshape(-1, cfg.d_model)
    _, gates, ids = MOE.route(p, xt, cfg.moe)
    out = jnp.zeros_like(xt)
    for j in range(cfg.moe.held):
        w = jnp.where(ids == cfg.moe.first_expert + j, gates, 0.0).sum(-1)
        h = jax.nn.silu(xt @ p["w_gate"][j]) * (xt @ p["w_up"][j])
        out = out + w[:, None] * (h @ p["w_down"][j])
    return out.reshape(x.shape)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    cfg = _cfg()
    _, params = _params(cfg)
    x, p = _skewed(cfg, jax.tree.map(lambda a: a[0], params["blocks"]["moe"]), 32)
    out, counts = MOE.moe_serve(p, x, cfg)
    want = _dense_layer(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert int(counts[0]) == 32 * TOP_K and int(counts[1]) == TOP_K
    assert float(jnp.abs(want).min(axis=-1).max()) > 0  # every token got its experts'


def test_planted_capacity_layer_fails_the_comparison(monkeypatch):
    """The training layer's capacity buckets in the serving programs: at
    decode, int(4 lanes x 4 / 16 x 1.25) = 1 slot an expert, so colliding
    assignments drop."""
    cfg = _cfg(held=0)
    _, params = _params(cfg)
    assert _gap(cfg, params) < TOL

    def capacity(p, x, cfg, layer):
        out, _ = MOE.moe_ffn(dict(p, **{n: p[n][layer] for n in MOE.EXPERTS}), x, cfg)
        return out, jnp.zeros(2, jnp.int32)

    monkeypatch.setattr(MOE, "moe_serve", capacity)
    assert _gap(cfg, params) > 10 * TOL


def test_planted_count_of_experts_not_held_fails_the_comparison():
    """A layer that adds the experts another chip holds: the program holds
    all 16 while the reference is given the share of 4."""
    share = _cfg()
    _, params = _params(_cfg(held=0))
    ref_params = jax.tree.map(lambda a: a, params)
    for n in ("w_gate", "w_up", "w_down"):
        ref_params["blocks"]["moe"][n] = params["blocks"]["moe"][n][:, :HELD]
    assert _gap(_cfg(held=0), params, ref_params, share) > 10 * TOL


def test_counters_equal_a_host_count_of_the_routing():
    cfg = _cfg(first=4)
    _, params = _params(cfg)
    p = jax.tree.map(lambda a: a[1], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.key(2), (6, 3, cfg.d_model))
    _, counts = MOE.moe_serve(p, x, cfg)
    logits = np.asarray(x.reshape(-1, cfg.d_model) @ p["router"])
    ids = np.argsort(-logits, axis=1)[:, :TOP_K]
    mine = (ids >= 4) & (ids < 4 + HELD)
    assert int(counts[0]) == int(mine.sum())
    assert int(counts[1]) == len(set(ids[mine].tolist()))


def test_the_programs_sum_the_counters_in_the_cache():
    cfg = _cfg()
    model, params = _params(cfg)
    prompt = np.asarray(jax.random.randint(jax.random.key(4), (13,), 0, cfg.vocab))
    _, _, pool = _serve(model, params, prompt, 4)
    got = pool.counters()
    assert set(got) == set(MOE.COUNTERS)
    held, used = got["moe_held_assignments"], got["moe_held_experts_used"]
    assert held.shape == used.shape == (cfg.n_layers,)
    # two chunks of 8 rows and three decodes of 4 lanes: 28 rows, 4 choices each;
    # an expert counted as used in a program got at least one of its rows
    assert np.all(held <= 28 * TOP_K) and held.sum() > 0
    assert np.all(used <= held) and np.all(used <= 5 * HELD)


def test_parameter_counts_follow_the_share():
    whole, share = _cfg(held=0), _cfg(first=4)
    expert = 3 * whole.d_model * whole.d_ff * whole.n_layers
    assert whole.n_params() - share.n_params() == (E - HELD) * expert
    # a token's top-4 of 16 experts puts one, on average, on a chip holding 4
    assert whole.n_active_params() - share.n_active_params() == (TOP_K - 1) * expert
