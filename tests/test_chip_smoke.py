"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
serve-and-compare path passes on phi4-mini-3.8b's reduced config with the
Pallas kernels in interpret mode."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import repro
from repro.configs import registry

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_exits_nonzero_without_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not _printed_result(out.stdout)


def test_exits_nonzero_without_the_program(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(lone, tmp_path)
    assert out.returncode != 0
    assert "cannot import the program" in out.stderr
    assert not _printed_result(out.stdout)


def test_serve_and_compare_interpret_mode():
    smoke = _load_chip_smoke()
    rt = repro.tune([smoke.ARCH], devices=("tpu_v5e",), seed=0).runtime(device="tpu_v5e")
    rt.set_pallas_enabled(True, interpret=True)
    assert rt.kernel_path("matmul") == "pallas"
    ref = repro.KernelRuntime(name="xla-reference")
    ref.set_pallas_enabled(False)
    report = smoke.serve_and_compare(registry.get(smoke.ARCH).reduced(), rt, ref, seed=0)

    assert smoke.failures(report, rt) == []
    assert all(r.done for r in report["requests"])
    assert report["tokens"] == len(smoke.PROMPT_LENS) * smoke.MAX_NEW_TOKENS
    assert max(report["rel_l2"]) <= smoke.REL_L2_TOL
    # the late request aliased the shared prefix: 128 tokens = 8 blocks of 16
    assert report["status"].prefix_hits == 1
    assert report["pool"]["prefix_hit_tokens"] == smoke.SHARED_PREFIX
    # tuned configs were selected at trace time (interpret mode compiles no
    # tpu_custom_call: the chip run checks that)
    assert rt.shape_cache_stats()["misses"] > 0


def test_failures_catch_a_bad_run():
    smoke = _load_chip_smoke()
    rt = repro.KernelRuntime()
    rt.record_incident({"site": "dispatch.matmul", "error": "boom"})

    class _Status:
        exhausted = True

    class _Req:
        uid, done = 3, False

    report = {"status": _Status(), "requests": [_Req()], "rel_l2": [0.0, 0.5]}
    reasons = " ".join(smoke.failures(report, rt))
    assert "dispatch incidents" in reasons
    assert "not completed: [3]" in reasons
    assert "requests [1] disagree" in reasons


def test_exits_nonzero_when_backend_fails(monkeypatch, capsys):
    smoke = _load_chip_smoke()

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(smoke.jax, "default_backend", broken)
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert not _printed_result(captured.out)
