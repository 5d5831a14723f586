"""The generator: one schedule per seed, the same set of sizes for every seed."""
import json

import pytest

from chipbench import traffic
from conftest import DATA, ROOT


def mix(name):
    return json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json").read_text())


def data_mix():
    return json.loads((DATA / "tiny.json").read_text())


def key(sched):
    return [(round(a.due, 9), a.max_new, a.prompt.tobytes()) for a in sched]


MIXES = pytest.mark.parametrize("tr", [mix("chat"), data_mix()], ids=["chat", "tiny"])


@MIXES
def test_same_seed_same_schedule_other_seed_other(tr):
    a = traffic.schedule(tr, 2**31 + 5, 60.0, 1000)
    b = traffic.schedule(tr, 2**31 + 5, 60.0, 1000)
    c = traffic.schedule(tr, 2**31 + 6, 60.0, 1000)
    assert a and key(a) == key(b)
    assert key(a) != key(c)


@MIXES
def test_every_seed_gets_the_same_sizes(tr):
    n = int(tr["output"]["stratum"]) * 3
    sizes = []
    for seed in (1, 2):
        horizon = 3 * n / tr["arrival"]["rate_per_s"]
        s = traffic.schedule(tr, seed, horizon, 1000)[:n]
        assert len(s) == n
        sizes.append(sorted(a.max_new for a in s))
    assert sizes[0] == sizes[1]


def test_stratified_gaps_keep_the_rate():
    tr = mix("chat")
    k = int(tr["arrival"]["stratum"])
    gaps = traffic.quantiles(tr["arrival"], k)
    assert abs(gaps.mean() - 1 / tr["arrival"]["rate_per_s"]) < 0.05 / tr["arrival"]["rate_per_s"]


@MIXES
def test_prompts_respect_the_cap(tr):
    s = traffic.schedule(tr, 9, 120.0, 1000)
    assert all(tr["prompt"]["min"] <= len(a.prompt) <= tr["max_prompt"] for a in s)
    assert traffic.longest_request(tr) == tr["max_prompt"] + tr["output"]["max"]


def test_lead_in_burst_is_due_at_once():
    tr = mix("chat")
    n = int(tr["lead_in_burst"])
    s = traffic.schedule(tr, 5, 60.0, 1000)
    assert [a.due for a in s[:n]] == [0.0] * n and s[n].due > 0
