"""The harness end to end on the CPU at a toy size: data-driven lookup, no
result without a chip, the control, and faults planted under the timed path."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from chipbench import harness
from conftest import ROOT, TINY, make_root, tiny_run


def digest(d):
    return {p.relative_to(d).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*")) if p.is_file() and "__pycache__" not in p.parts
            and "tests" not in p.relative_to(d).parts}


def test_new_config_mix_and_metric_are_files_and_entries(tiny_root):
    bench = tiny_root / "chipbench"
    (bench / "metrics" / "requests_in_window.py").write_text(
        "def read(run):\n    return sum(run.t_open <= r.due < run.t_close for r in run.requests)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({"name": "requests_in_window", "unit": "count", "better": "higher",
                              "source": "host_clock", "layer": "engine (serve/engine.py)",
                              "moves": "output_tok_per_s", "workloads": [TINY]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    # nothing that was there changed: only new files were added
    before, after = digest(ROOT / "chipbench"), digest(bench)
    assert all(after[k] == v for k, v in before.items())
    cell = harness.load_cell(TINY, tiny_root)
    assert cell.config["name"] == "tiny-dense" and cell.traffic["max_prompt"] == 100
    assert "requests_in_window" in [m["name"] for m in cell.per_layer]
    run = types.SimpleNamespace(t_open=0.0, t_close=5.0, requests=[
        types.SimpleNamespace(due=1.0), types.SimpleNamespace(due=6.0)])
    assert harness.load_reader(cell.bench, "requests_in_window")(run) == 1


def run_script(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "phi4-mini-3.8b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = run_script(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_script(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    root = make_root(tmp_path / "c", limit=0.02)
    out = tiny_run(root, 2**31 + 11)
    gap, limit = out["checks"]["max_logit_gap"]["value"], out["checks"]["max_logit_gap"]["limit"]
    assert out["correct"] is True and gap <= limit
    assert out["checks"]["compared_tokens"]["value"] >= out["checks"]["compared_tokens"]["limit"]
    assert out["metrics"]["output_tok_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    # the fp8 control's tokens in the served tokens' place, through the same check
    ctrl = tiny_run(root, 2**31 + 11, control=True)
    assert ctrl["correct"] is False
    assert ctrl["checks"]["max_logit_gap"]["value"] > limit
    assert ctrl["control"]["program_max_logit_gap"] <= limit


def alter_token(monkeypatch):
    from repro.serve.engine import ServingEngine

    orig = ServingEngine._decode_active

    def decode(self):
        got = orig(self)
        for r in got:
            if len(r.output) == 4:
                r.output[-1] = (r.output[-1] + 1) % self.model.cfg.vocab
        return got

    monkeypatch.setattr(ServingEngine, "_decode_active", decode)


def state_unchanged(monkeypatch):
    from repro.serve.kvpool import KVPool

    monkeypatch.setattr(KVPool, "scatter", lambda self, lane_ids, cache: None)


@pytest.mark.parametrize("fault", [alter_token, state_unchanged])
def test_a_fault_under_the_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    out = tiny_run(tiny_root, 5)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]
