"""Metric arithmetic: tails over all requests, and work from unpadded shapes."""
import math
import types

from chipbench import reduce, work


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert reduce.percentile(xs, 95) == 95
    assert reduce.percentile(xs, 90) == 90
    assert reduce.percentile([5.0], 90) == 5.0


def test_a_missing_request_counts_as_a_miss():
    run = types.SimpleNamespace(t_open=0.0, t_close=10.0, requests=[])
    ok = types.SimpleNamespace(state="done", retries=0)
    starved = types.SimpleNamespace(state="starved", retries=0)
    for i in range(9):
        run.requests.append(types.SimpleNamespace(due=float(i), times=[i + 0.1], request=ok))
    run.requests.append(types.SimpleNamespace(due=9.0, times=[], request=ok))
    run.requests.append(types.SimpleNamespace(due=9.5, times=[9.6], request=starved))
    run.requests.append(types.SimpleNamespace(due=11.0, times=[11.1], request=ok))  # not due in window
    t = reduce.ttfts(run)
    assert len(t) == 11 and sum(math.isinf(x) for x in t) == 2
    assert math.isinf(reduce.percentile(t, 90))
    assert abs(reduce.percentile(t, 50) - 0.1) < 1e-9


def test_token_gaps_stay_inside_the_window():
    r = types.SimpleNamespace(times=[0.5, 1.0, 1.5, 2.5, 11.0])
    run = types.SimpleNamespace(t_open=1.0, t_close=10.0, requests=[r])
    assert reduce.token_gaps(run) == [0.5, 1.0]
    assert reduce.tokens_in_window(run) == 3


S = work.Shapes(layers=2, d=64, heads=4, kv_heads=2, head_dim=16, ff=128, vocab=256)


def test_decode_work_counts_real_rows_and_real_context():
    f1, b1 = work.decode_work(S, [10])
    f2, b2 = work.decode_work(S, [10, 30])
    per_token = 2 * (S.layers * S.layer_weights + S.head_weights)
    assert f1 == per_token + S.layers * 4 * S.q_dim * 10
    assert f2 - f1 == per_token + S.layers * 4 * S.q_dim * 30
    # weights are read once per call whatever the batch
    assert b2 - b1 == 30 * S.kv_bytes_per_token + S.act_bytes_per_token + (S.d + S.vocab) * 2


def test_chunk_work_counts_real_tokens_and_the_head_only_when_final():
    f, _ = work.chunk_work(S, start=0, n=3, final=False)
    ff, _ = work.chunk_work(S, start=0, n=3, final=True)
    assert f == 3 * 2 * S.layers * S.layer_weights + S.layers * 4 * S.q_dim * (1 + 2 + 3)
    assert ff - f == 2 * S.head_weights
    g, _ = work.chunk_work(S, start=5, n=1, final=False)
    assert g == 2 * S.layers * S.layer_weights + S.layers * 4 * S.q_dim * 6


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peaks) == 10.0
    assert work.least_time(100.0, 50.0, peaks) == 5.0
