"""The trace reduction, on a hand-made trace and on a small recorded one."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def rows():
    # two steps: [0, 100) and [150, 300); an idle span between them
    return [
        [HOST, "python3", "engine.step", 0.0, 100.0, {"step_id": 0}],
        [HOST, "python3", "harness.idle", 100.0, 50.0, {}],
        [HOST, "python3", "engine.step", 150.0, 150.0, {"step_id": 1}],
        [DEV, "XLA Ops", "%fusion.1 = bf16[8,64] fusion(bf16[8,64] %p, bf16[64,64] %w), kind=kOutput", 10.0, 30.0, {}],
        [DEV, "XLA Ops", "%dynamic-slice_fusion.2 = bf16[64,64] fusion(bf16[4,64,64] %ws, s32[] %i), kind=kLoop", 40.0, 20.0, {}],
        [DEV, "XLA Ops", '%closed_call.3 = bf16[8,64] custom-call(bf16[8,64] %fusion.1, bf16[64,64] %dynamic-slice_fusion.2), custom_call_target="tpu_custom_call"', 60.0, 10.0, {}],
        [DEV, "XLA Ops", "%fusion.3 = bf16[8,64] fusion(bf16[8,64] %closed_call.3), kind=kLoop", 120.0, 10.0, {}],
        [DEV, "XLA Ops", "%while.4 = (s32[], bf16[8,64]) while((s32[], bf16[8,64]) %t), body=%b", 160.0, 100.0, {}],
        [DEV, "XLA Ops", "%convolution.5 = bf16[8,64] convolution(bf16[8,64] %x, bf16[64,64] %y)", 160.0, 100.0, {}],
    ]


def test_busy_idle_and_matmul_time():
    ts = tr.summarize(rows())
    busy, window = tr.busy(ts)
    # union of [10,70) [120,130) [160,260) within [0, 300)
    assert busy == pytest.approx(170e-9)
    assert window == pytest.approx(300e-9)
    # inside the step spans: 60 + 100 of 250 ns
    assert tr.idle_share(ts) == pytest.approx(1 - 160 / 250)
    # the output fusion, the Pallas kernel, the weight slice it read, the
    # convolution; not the loop around it nor the elementwise fusion after
    assert tr.matmul_seconds(ts) == pytest.approx(160e-9)
    assert tr.top_ops(ts)[0] == ["convolution", pytest.approx(100e-9)]
    assert ["pallas:closed_call", pytest.approx(10e-9)] in tr.top_ops(ts)
    assert "while" not in [n for n, _ in tr.top_ops(ts)]


def test_parse_op():
    name, op, args = tr.parse_op(
        '%closed_call.47 = bf16[8,1024]{1,0:T(8,128)(2,1)S(1)} custom-call(bf16[8,3072]{1,0} %pad.59, '
        'bf16[3072,1024]{1,0} %dynamic-slice_bitcast_fusion.21), custom_call_target="tpu_custom_call"')
    assert (name, op, args) == ("closed_call.47", "custom-call",
                                ["pad.59", "dynamic-slice_bitcast_fusion.21"])
    name, op, _ = tr.parse_op("%while.2 = (s32[]{:T(128)}, bf16[4,1,3072]{2,0,1:T(4,128)(2,1)S(1)}) "
                              "while((s32[], bf16[4,1,3072]) %tuple), condition=%c, body=%b")
    assert (name, op) == ("while.2", "while")


def test_idle_gaps_are_named_by_the_host_span():
    gaps = tr.idle_gaps(tr.summarize(rows()))
    # gaps [70,120) [260,300) [130,160) [0,10)
    assert gaps[0] == ["engine.step", pytest.approx(50e-9)]
    assert gaps[1] == ["engine.step", pytest.approx(40e-9)]
    assert gaps[2] == ["harness.idle", pytest.approx(30e-9)]
    assert gaps[3] == ["engine.step", pytest.approx(10e-9)]
    assert all(g[1] > 0 for g in gaps)


def test_recorded_trace():
    """Three decode steps of phi4-mini-3.8b.chat recorded on a TPU v5e (HLO
    text cut to name, opcode, operands and kind)."""
    ts = tr.summarize(tr.load_rows(DATA / "trace_small.json.gz"))
    assert len(ts.steps()) == 3
    busy, window = tr.busy(ts)
    assert busy == pytest.approx(0.20899324) and window == pytest.approx(0.318898853)
    # 32 layers x 7 Pallas projections + the head, and the weight slices
    # they read, per decode: about 18 ms a step
    assert tr.matmul_seconds(ts) == pytest.approx(0.053497648)
    assert tr.idle_share(ts) == pytest.approx(0.3445970307828047)
    names = [n for n, _ in tr.top_ops(ts)]
    assert "pallas:closed_call" in names and "copy" in names
