"""The expert-parallel Qwen3-MoE configuration: its file through the
harness, its work counts by hand, and its reference on a toy file (a whole
run on the CPU, judged by the reference)."""
import json

import pytest

from chipbench import harness
from conftest import ROOT, add_cell, make_root
from repro.configs.base import MoESpec

CONFIG = ROOT / "chipbench" / "configs" / "qwen3-moe-235b-a22b-ep8.json"
TOY = "tiny-moe-ep.tiny"


def _load_work():
    return harness.load_module(ROOT / "chipbench", "works", "moe_gqa")


def test_the_ep8_file_goes_through_arch_config_unchanged():
    config = json.loads(CONFIG.read_text())
    cfg = harness.arch_config(config)
    assert cfg.moe == MoESpec(n_experts=128, top_k=8, first_expert=0, n_held=16)
    assert cfg.moe.held == 16 and cfg.qk_norm and cfg.vocab == 18992
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == (
        8, 4096, 64, 4, 128, 1536)
    assert (cfg.norm_eps, cfg.rope_theta, cfg.tie_embeddings) == (1e-6, 1e6, False)
    # the reference and the work counts read the share from config: the same one
    c = config["config"]
    assert (c["first_expert_held"], c["num_experts_held"]) == (
        cfg.moe.first_expert, cfg.moe.held)
    assert sorted(config["reduced"]) == ["num_experts_held", "num_hidden_layers", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 94
    assert config["published"]["vocab_size"] == 8 * c["vocab_size"]
    assert round(cfg.n_params() / 1e9, 2) == 3.15


def test_work_counts_by_hand():
    work = _load_work()
    s = work.Shapes.from_config(json.loads(CONFIG.read_text())["config"])
    d, q, kv, ff, layers, v = 4096, 8192, 512, 1536, 8, 18992
    dense = 2 * d * q + 2 * d * kv + d * 128  # q, o, k, v, router
    # one decode of 32 lanes at contexts 100..131: 32 * 8 * 16 / 128 = 32
    # assignments an expert layer; an expert is idle with (1 - 8/128)^32
    ctx = list(range(100, 132))
    used = 16 * (1 - (15 / 16) ** 32)
    flops = layers * 32 * 2 * (dense + 1 * 3 * d * ff) + 32 * 2 * d * v
    flops += layers * 4 * q * sum(ctx)
    nbytes = layers * dense * 2 + layers * 32 * (2 * d + 2 * q + 2 * kv + 128) * 2
    nbytes += layers * (used * 3 * d * ff * 2 + 32 * (2 * d + 3 * ff) * 2)
    nbytes += (d * v + 32 * (d + v)) * 2 + sum(ctx) * layers * 2 * kv * 2
    got = work.decode_work(s, ctx)
    assert abs(got[0] - flops) <= 1e-9 * flops and abs(got[1] - nbytes) <= 1e-9 * nbytes
    # a final chunk of 100 tokens at positions 256..355
    n, start = 100, 256
    used = 16 * (1 - (15 / 16) ** n)
    flops = layers * n * 2 * dense + layers * n * 8 * 16 / 128 * 2 * 3 * d * ff
    flops += layers * 4 * q * (n * start + n * (n + 1) // 2) + 2 * d * v
    nbytes = layers * dense * 2 + layers * n * (2 * d + 2 * q + 2 * kv + 128) * 2
    nbytes += layers * (used * 3 * d * ff * 2 + n * 8 * 16 / 128 * (2 * d + 3 * ff) * 2)
    nbytes += (start + n) * layers * 2 * kv * 2 + (d * v + d + v) * 2
    got = work.chunk_work(s, start, n, True)
    assert abs(got[0] - flops) <= 1e-9 * flops and abs(got[1] - nbytes) <= 1e-9 * nbytes
    # the expert GEMMs alone: what expert_gemm_roofline counts
    f, b = work.expert_work(s, 32)
    assert f == layers * 32 * 2 * 3 * d * ff
    assert abs(b - layers * (used_32 := 16 * (1 - (15 / 16) ** 32)) * 3 * d * ff * 2
               - layers * 32 * (2 * d + 3 * ff) * 2) < 1e-3 * b and used_32 > 13.9


def _toy(tmp_path):
    config = json.loads(CONFIG.read_text())
    config["name"] = "tiny-moe-ep"
    config["config"].update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                            num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
                            num_experts=16, num_experts_per_tok=4, first_expert_held=4,
                            num_experts_held=4, vocab_size=256)
    config["program"] = {"moe": {"first_expert": 4, "n_held": 4}}
    config["engine"] = {"max_batch": 4, "cache_len": 256, "block_size": 16,
                        "prefill_chunk_tokens": 64}
    path = tmp_path / "tiny-moe-ep.json"
    path.write_text(json.dumps(config))
    return path


def test_the_reference_judges_a_toy_run(tmp_path):
    root = make_root(tmp_path / "c")
    add_cell(root, _toy(tmp_path), TOY, limit=0.05)
    out = harness.run_cell(TOY, 2**31 + 29, 2.0, False, t_start=harness.clock(), root=root,
                           require_chip=False, tune_device="tpu_v5e")
    checks = out["checks"]
    assert out["correct"], checks
    assert checks["compared_tokens"]["value"] >= checks["compared_tokens"]["limit"]
    assert 0 <= checks["max_logit_gap"]["value"] <= 0.05


def test_the_expert_gemm_readers_on_a_hand_made_trace():
    import types

    from chipbench import trace_reduce
    from chipbench.reduce import least_time

    dev, host = "/device:TPU:0", "/host:CPU"
    gmm = ('%gmm_tm16_tk512_tn512.{} = bf16[256,1536] custom-call(bf16[256,4096] %g, '
           'bf16[16,4096,1536] %{}), custom_call_target="tpu_custom_call"')
    move = "%dynamic-slice_bitcast_fusion.{} = bf16[16,4096,1536] fusion(s32[] %i), kind=kLoop"
    rows = [
        [host, "python3", "engine.step", 0.0, 1e6, {"step_id": 4}],  # decodes only
        [dev, "XLA Ops", move.format(7), 0.5e5, 0.5e5, {}],  # the expert weights gmm.1 reads
        [dev, "XLA Ops", gmm.format(1, "dynamic-slice_bitcast_fusion.7"), 1e5, 2e5, {}],
        [dev, "XLA Ops", "%fusion.2 = bf16[32,4096] fusion(bf16[32,4096] %x), kind=kLoop",
         3e5, 1e5, {}],
        [dev, "XLA Ops", '%mm_bm8_bn512_bk1024_nmk.9 = bf16[32,1536] custom-call(bf16[32,4096] '
         '%x, bf16[4096,1536] %w), custom_call_target="tpu_custom_call"', 4.6e5, 0.2e5, {}],
        [dev, "XLA Ops", gmm.format(3, "w"), 5e5, 3e5, {}],
        [host, "python3", "engine.step", 1.2e6, 1e6, {"step_id": 5}],  # a chunk and a decode
        [dev, "XLA Ops", gmm.format(4, "w"), 1.5e6, 4e5, {}],
        [dev, "XLA Ops", gmm.format(5, "w"), 2.5e6, 1e5, {}],  # after every step
    ]
    work = _load_work()
    shapes = work.Shapes.from_config(json.loads(CONFIG.read_text())["config"])
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    decodes = [(0.0, 32, list(range(1, 33))), (0.0, 32, list(range(1, 21)))]
    steps = [types.SimpleNamespace(step_id=4, decodes=decodes, chunks=[]),
             types.SimpleNamespace(step_id=5, decodes=decodes[:1], chunks=[(64, 0, 40, True)])]
    run = types.SimpleNamespace(trace=trace_reduce.summarize(rows), work=work, shapes=shapes,
                                peaks=peaks, traced_steps=lambda: steps)
    read = lambda name: harness.load_reader(ROOT / "chipbench", name)(run)
    least = sum(least_time(*work.expert_work(shapes, n), peaks) for n in (32, 20))
    # the kernels of the step without a chunk, alone: not the slice that fed
    # gmm.1, nor the step with a chunk, nor a kernel after the steps
    assert read("expert_gemm_roofline.decodeheavy") == pytest.approx(100 * least / 5e-4)
    # every kernel of the traced range, over its busy time
    assert read("expert_gemm_pct.decodeheavy") == pytest.approx(100 * 9e-4 / 1.07e-3)
    steps[0].chunks = [(32, 0, 32, False)]
    assert read("expert_gemm_roofline.decodeheavy") is None  # no step without a chunk
    assert harness.load_reader(ROOT / "chipbench", "expert_gemm_pct.decodeheavy")(
        types.SimpleNamespace(trace=None)) is None
