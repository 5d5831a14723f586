"""CLI launcher smoke tests: tune / train / serve mains end to end."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatch import Deployment
from repro.core.runtime import reset_default_runtime
from repro.kernels import ops


@pytest.fixture(autouse=True)
def _fresh_runtime():
    # Real test isolation: each test gets a brand-new default runtime instead
    # of the old clear_*-everything teardown choreography.  The mains point
    # JAX's compile cache at the checkout; later tests keep the setting they
    # started with.
    rt = reset_default_runtime()
    rt.set_selection_logging(True)
    cache_dir = jax.config.jax_compilation_cache_dir
    yield rt
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    reset_default_runtime()


def test_compile_cache_dir(monkeypatch):
    from pathlib import Path

    from repro.launch.compile_cache import ENV_VAR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, "/elsewhere/jax")
    assert use_compile_cache() == "/elsewhere/jax"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env itself
    monkeypatch.delenv(ENV_VAR)
    checkout = Path(__file__).resolve().parents[1]
    assert use_compile_cache() == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(checkout / ".jax_cache")


def test_tune_cli_v5e(tmp_path):
    from repro.launch.tune import main

    out = tmp_path / "deploy.json"
    main(["--device", "tpu_v5e", "--archs", "granite-8b", "--n-kernels", "6",
          "--max-problems", "60", "--out", str(out)])
    dep = Deployment.load(out)
    assert len(dep.configs) == 6
    assert dep.attention_tree is not None
    assert dep.meta["oracle_fraction"] > 0.8


def test_tune_cli_measured_cpu(tmp_path):
    from repro.launch.tune import main

    out = tmp_path / "deploy_cpu.json"
    main(["--device", "host_cpu", "--cpu-problems", "6", "--n-kernels", "4",
          "--out", str(out)])
    dep = Deployment.load(out)
    assert dep.device == "host_cpu"
    assert len(dep.configs) == 4


def test_train_cli_with_deployment(tmp_path):
    from repro.launch.train import main as train_main
    from repro.launch.tune import main as tune_main

    dep = tmp_path / "d.json"
    tune_main(["--device", "tpu_v5e", "--archs", "granite-8b", "--max-problems", "40",
               "--out", str(dep)])
    train_main([
        "--arch", "granite-8b", "--reduced", "--steps", "4", "--batch", "2",
        "--seq", "16", "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "2",
        "--deployment", str(dep),
    ])
    # the deployment was installed and consulted at trace time
    assert any(op == "matmul" for op, _, _ in ops.selection_log())


def test_serve_cli(tmp_path, capsys):
    from repro.launch.serve import main as serve_main

    serve_main(["--arch", "granite-8b", "--reduced", "--requests", "3",
                "--max-new-tokens", "4", "--max-batch", "2", "--cache-len", "64"])
    out = capsys.readouterr().out
    assert "served 3 requests" in out


def test_tune_cli_bundle_then_serve_cli(tmp_path, capsys, monkeypatch):
    """Fleet-tune a two-device bundle, then serve from it on a chosen device."""
    from repro.core.bundle import DeploymentBundle
    from repro.launch.serve import main as serve_main
    from repro.launch.tune import main as tune_main

    out = tmp_path / "bundle.json"
    tune_main(["--devices", "tpu_v5e,tpu_v4", "--archs", "granite-8b",
               "--n-kernels", "4", "--max-problems", "40", "--bundle", str(out)])
    bundle = DeploymentBundle.load(out)
    assert bundle.devices == ["tpu_v4", "tpu_v5e"]
    capsys.readouterr()

    serve_main(["--arch", "granite-8b", "--reduced", "--requests", "2",
                "--max-new-tokens", "4", "--max-batch", "2", "--cache-len", "64",
                "--bundle", str(out), "--serve-device", "tpu_v4"])
    printed = capsys.readouterr().out
    assert "serving with the 'tpu_v4' deployment" in printed
    assert "served 2 requests" in printed
    # the serving traces consulted the bundle's tuned policy (the CLI's
    # private runtime reports nonzero selection counters only when a live
    # policy answered trace-time dispatch)
    m = re.search(r"policy selections at trace time: (\d+)", printed)
    assert m and int(m.group(1)) > 0, printed
    # the launcher owns an isolated KernelRuntime: serving from the bundle
    # must leave the process default runtime untouched (multi-tenant contract)
    assert ops.active_device() is None


def test_serve_engine_with_kv_quant():
    """Serving engine composes with the int8 KV cache."""
    from repro.configs import registry
    from repro.models.model import build_model
    from repro.serve.engine import Request, ServingEngine

    cfg = registry.get("granite-8b").reduced()
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32, kv_quant=True)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_batch=2, cache_len=64)
    assert eng.cache["k"].dtype == jnp.int8
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=5).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    eng.run(reqs)
    assert all(r.done and len(r.output) == 4 for r in reqs)


def test_tune_cli_measure_budget_type():
    import argparse

    from repro.launch.tune import _measure_budget

    assert _measure_budget("auto") == "auto"
    assert _measure_budget("0.4") == 0.4
    for bad in ("0", "1", "1.5", "-0.2", "most", ""):
        with pytest.raises(argparse.ArgumentTypeError):
            _measure_budget(bad)


def test_ctl_cli_submit_status_artifacts(capsys):
    from repro.control import ControlPlane
    from repro.core.bundle import DeploymentBundle
    from repro.core.dataset import build_model_dataset, synthetic_problems
    from repro.core.tuner import tune
    from repro.launch.ctl import main

    ds = build_model_dataset(synthetic_problems(40), device_name="tpu_v5e")
    bundle = DeploymentBundle({"tpu_v5e": tune(ds, n_kernels=4).deployment})
    with ControlPlane(port=0, tuner=lambda spec: bundle) as plane:
        main(["submit", "--url", plane.url, "--name", "fleet",
              "--devices", "tpu_v5e", "--measure-budget", "auto", "--wait"])
        out = capsys.readouterr().out
        assert "job-0001 queued" in out and "job-0001 succeeded" in out
        ver = plane.registry.latest("fleet").version
        assert f"artifact fleet@{ver}" in out
        assert f"registry://127.0.0.1:{plane.port}/fleet/{ver}" in out

        main(["status", "--url", plane.url])
        out = capsys.readouterr().out
        assert '"status": "ok"' in out
        assert "job-0001 [tune] succeeded -> fleet@" in out

        main(["artifacts", "--url", plane.url])
        assert f"fleet@{ver} seq=0" in capsys.readouterr().out


def test_ctl_cli_submit_failed_job_exits_nonzero(capsys):
    from repro.control import ControlPlane
    from repro.launch.ctl import main

    def tuner(spec):
        raise ValueError("no benchmarks on this host")

    with ControlPlane(port=0, tuner=tuner) as plane:
        with pytest.raises(SystemExit):
            main(["submit", "--url", plane.url, "--wait", "--timeout", "30"])
        out = capsys.readouterr().out
        assert "failed: ValueError: no benchmarks on this host" in out
