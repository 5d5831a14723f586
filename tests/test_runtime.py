"""KernelRuntime: explicit multi-tenant runtime handles (DESIGN.md §10).

Covers the api_redesign contract:
  * activation scoping — ops dispatch against the innermost active runtime,
    falling back to the process default;
  * the legacy module-level ops API is a deprecated shim over the default
    runtime with byte-identical selections (proven on the committed v1-v5
    deployment fixtures);
  * two runtimes serving different tunings concurrently from separate
    threads share no policy, shape-cache, or selection-log state.
"""
import json
import threading
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.core.bundle import DeploymentBundle, install_bundle
from repro.core.dispatch import Deployment
from repro.core.runtime import (
    KernelRuntime,
    current_runtime,
    default_runtime,
    reset_default_runtime,
)
from repro.kernels import ops
from repro.kernels.matmul import MatmulConfig

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _fresh_default():
    reset_default_runtime()
    yield
    reset_default_runtime()


def _policy(bm: int) -> ops.FixedPolicy:
    return ops.FixedPolicy(matmul_config=MatmulConfig(bm, 128, 128))


@pytest.fixture(scope="module")
def tuned_pair():
    """Two real tuned deployments whose matmul selections differ."""
    from repro.core.dataset import build_model_dataset, synthetic_problems
    from repro.core.tuner import tune

    ds = build_model_dataset(synthetic_problems(60), device_name="tpu_v5e")
    a = tune(ds, n_kernels=6, families=[]).deployment
    b = tune(ds, n_kernels=2, families=[]).deployment
    assert a.configs != b.configs
    return a, b


# ---------------------------------------------------------------------------
# activation scoping
# ---------------------------------------------------------------------------
def test_current_runtime_defaults_and_scopes():
    assert current_runtime() is default_runtime()
    rt1, rt2 = KernelRuntime("one"), KernelRuntime("two")
    with rt1.activate():
        assert current_runtime() is rt1
        with rt2.activate():  # innermost wins
            assert current_runtime() is rt2
        assert current_runtime() is rt1
    assert current_runtime() is default_runtime()


def test_ops_dispatch_follows_active_runtime():
    rt1, rt2 = KernelRuntime(), KernelRuntime()
    rt1.install(_policy(64))
    rt2.install(_policy(256))
    default_runtime().install(_policy(8))
    with rt1.activate():
        assert ops.select_matmul_config(64, 64, 64).block_m == 64
        with rt2.activate():
            assert ops.select_matmul_config(64, 64, 64).block_m == 256
    assert ops.select_matmul_config(64, 64, 64).block_m == 8  # default again


def test_activation_is_per_thread():
    rt = KernelRuntime()
    rt.install(_policy(512))
    seen = {}

    def other_thread():
        seen["runtime"] = current_runtime()
        seen["cfg"] = ops.select_matmul_config(32, 32, 32)

    with rt.activate():
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert seen["runtime"] is default_runtime()  # activation did not leak
    assert seen["cfg"] is None  # default runtime has no policy


def test_runtime_state_is_isolated():
    rt1, rt2 = KernelRuntime(), KernelRuntime()
    rt1.install(_policy(64))
    rt2.install(_policy(256))
    rt1.set_selection_logging(True)
    rt2.set_selection_logging(True)
    rt1.select_matmul_config(128, 128, 128)
    assert len(rt1.selection_log()) == 1 and rt2.selection_log() == []
    assert rt1.shape_cache_stats()["size"] == 1
    assert rt2.shape_cache_stats()["size"] == 0
    assert rt1.policy_epoch() == rt2.policy_epoch() == 1
    rt2.install(None)  # epoch bump in rt2 only
    assert rt1.policy_epoch() == 1 and rt2.policy_epoch() == 2


def test_kernel_path_follows_the_platform(monkeypatch):
    """CPU: the XLA reference, no switch set.  A TPU backend: compiled Pallas,
    except families whose kernel the TPU compiler refuses.  Only an explicit
    override (tests) runs the interpreter."""
    import jax

    families = ("matmul", "attention", "wkv", "ssm_scan")
    rt = KernelRuntime()
    assert not rt.use_pallas and not rt.interpret
    assert {rt.kernel_path(f) for f in families} == {"xla"}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tpu = KernelRuntime()
    assert tpu.use_pallas and not tpu.interpret
    assert [tpu.kernel_path(f) for f in families] == ["pallas", "pallas", "xla", "xla"]
    tpu.set_pallas_enabled(False)  # a reference runtime on the chip
    assert {tpu.kernel_path(f) for f in families} == {"xla"}

    rt.set_pallas_enabled(True, interpret=True)
    assert {rt.kernel_path(f) for f in families} == {"pallas"}


def test_shape_cache_cap_reaches_other_threads():
    """rt.set_shape_cache_cap is runtime-scoped: fresh threads adopt it."""
    rt = KernelRuntime()
    rt.install(_policy(64))
    rt.set_shape_cache_cap(3)
    seen = {}

    def worker():
        for i in range(8):
            rt.select_matmul_config(16 + i, 16, 16)
        seen.update(rt.shape_cache_stats())

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen["cap"] == 3 and seen["size"] == 3  # LRU-bounded, not 1024/8


def test_engine_ctor_adopts_current_runtime():
    from repro.serve.engine import ServingEngine

    class _NullModel:
        def init_cache(self, b, n):
            return {}

        def decode_step(self, params, cache, tokens, positions):
            raise NotImplementedError

    rt = KernelRuntime()
    with rt.activate():
        eng = ServingEngine(_NullModel(), params={}, max_batch=1, cache_len=8)
    assert eng.runtime is rt
    eng2 = ServingEngine(_NullModel(), params={}, max_batch=1, cache_len=8)
    assert eng2.runtime is default_runtime()


# ---------------------------------------------------------------------------
# legacy shims: deprecation + byte-identical routing
# ---------------------------------------------------------------------------
LEGACY_MUTATORS = [
    (lambda: ops.set_kernel_policy(None), "set_kernel_policy"),
    (lambda: ops.set_kernel_policy_for_device("tpu_v5e", ops.FixedPolicy()),
     "set_kernel_policy_for_device"),
    (lambda: ops.clear_device_policies(), "clear_device_policies"),
    (lambda: ops.set_pallas_enabled(False), "set_pallas_enabled"),
    (lambda: ops.set_selection_logging(False), "set_selection_logging"),
    (lambda: ops.clear_selection_log(), "clear_selection_log"),
    (lambda: ops.clear_shape_cache(), "clear_shape_cache"),
    (lambda: ops.set_shape_cache_cap(512), "set_shape_cache_cap"),
]


@pytest.mark.parametrize("call,name", LEGACY_MUTATORS, ids=[n for _, n in LEGACY_MUTATORS])
def test_legacy_mutators_warn(call, name):
    with pytest.warns(DeprecationWarning, match=name):
        call()


def test_legacy_activate_device_warns():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        ops.set_kernel_policy_for_device("tpu_v5e", ops.FixedPolicy())
    with pytest.warns(DeprecationWarning, match="activate_device"):
        ops.activate_device("tpu_v5e")


def test_legacy_mutators_route_to_default_runtime():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        ops.set_kernel_policy(_policy(32))
        ops.set_selection_logging(True)
    rt = default_runtime()
    assert rt.policy().matmul_config.block_m == 32
    assert rt.selection_logging_enabled()
    assert ops.select_matmul_config(16, 16, 16).block_m == 32
    assert rt.selection_log() == [("matmul", (16, 16, 16, 1), _policy(32).matmul_config)]
    assert ops.shape_cache_stats() == rt.shape_cache_stats()
    assert ops.get_kernel_policy() is rt.policy()


# ---------------------------------------------------------------------------
# byte-identical selections on the committed v1-v5 fixtures
# ---------------------------------------------------------------------------
def _expected():
    return json.loads((DATA / "expected_selections.json").read_text())


@pytest.mark.parametrize("fixture", ["dep_v1.json", "dep_v2.json"])
def test_legacy_shim_selections_match_fixtures(fixture):
    """ops.* (default-runtime shim) == KernelRuntime handle == committed bytes."""
    exp = _expected()
    dep = Deployment.load(DATA / fixture)
    want = exp["devices"]["tpu_v5e"]["matmul"]

    rt = KernelRuntime()
    rt.install(dep)
    via_handle = [rt.select_matmul_config(*p).to_dict() for p in exp["matmul_probes"]]

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        ops.set_kernel_policy(dep)
    via_legacy = [ops.select_matmul_config(*p).to_dict() for p in exp["matmul_probes"]]

    assert via_handle == want
    assert via_legacy == want


@pytest.mark.parametrize("fixture", ["bundle_v3.json", "bundle_v4.json"])
def test_bundle_runtime_selections_match_fixtures(fixture):
    """bundle.runtime(device=...) serves the committed per-device selections."""
    exp = _expected()
    bundle = DeploymentBundle.load(DATA / fixture)
    for device, want in exp["devices"].items():
        rt = bundle.runtime(device=device)
        assert rt.active_device() == device
        got_m = [rt.select_matmul_config(*p).to_dict() for p in exp["matmul_probes"]]
        got_a = [rt.select_config("attention", p).to_dict() for p in exp["attention_probes"]]
        assert got_m == want["matmul"], device
        assert got_a == want["attention"], device
        # legacy install_bundle into the default runtime: same bytes
        install_bundle(bundle, device=device)
        got_legacy = [ops.select_matmul_config(*p).to_dict() for p in exp["matmul_probes"]]
        assert got_legacy == want["matmul"], device


def test_install_bundle_targets_explicit_runtime():
    bundle = DeploymentBundle.load(DATA / "bundle_v4.json")
    rt = KernelRuntime()
    dep = install_bundle(bundle, device="tpu_v4", runtime=rt)
    assert rt.active_device() == "tpu_v4"
    assert dep is bundle.deployments["tpu_v4"]
    assert default_runtime().active_device() is None  # untouched


# ---------------------------------------------------------------------------
# concurrent runtimes: two tunings serving from separate threads, zero
# cross-talk (the multi-tenant acceptance criterion)
# ---------------------------------------------------------------------------
def test_concurrent_runtimes_no_cross_talk(tuned_pair):
    dep_a, dep_b = tuned_pair
    rt_a, rt_b = KernelRuntime("tenant-a"), KernelRuntime("tenant-b")
    rt_a.install(dep_a)
    rt_b.install(dep_b)
    rt_a.set_selection_logging(True)
    rt_b.set_selection_logging(True)

    probes = [(512, 784, 512, 16), (1, 4096, 512, 1), (2048, 2048, 2048, 1),
              (64, 512, 64, 4), (4096, 128, 4096, 1)]
    n_rounds = 200
    errors: list[str] = []
    barrier = threading.Barrier(2)

    def worker(rt: KernelRuntime, dep: Deployment, tag: str):
        try:
            barrier.wait(timeout=10)
            with rt.activate():
                for i in range(n_rounds):
                    p = probes[i % len(probes)]
                    got = ops.select_matmul_config(*p)
                    want = dep.select_matmul(*p)
                    if got != want:
                        errors.append(f"{tag}: {p} -> {got}, want {want}")
                        return
        except Exception as e:  # pragma: no cover - surfaced via errors
            errors.append(f"{tag}: {e!r}")

    ta = threading.Thread(target=worker, args=(rt_a, dep_a, "a"))
    tb = threading.Thread(target=worker, args=(rt_b, dep_b, "b"))
    ta.start(); tb.start(); ta.join(); tb.join()
    assert not errors, errors

    # every logged selection belongs to the runtime's own deployment
    log_a, log_b = rt_a.selection_log(), rt_b.selection_log()
    assert len(log_a) == len(log_b) == n_rounds
    assert all(cfg in dep_a.configs for _, _, cfg in log_a)
    assert all(cfg in dep_b.configs for _, _, cfg in log_b)
    # shape caches stayed per-runtime (the worker thread's locals, but the
    # stats read from this thread must also show zero leakage into default)
    assert default_runtime().shape_cache_stats()["size"] == 0
    assert default_runtime().selection_log() == []


def test_concurrent_hot_swap_isolated(tuned_pair):
    """A retune-style hot swap in tenant A never invalidates tenant B."""
    dep_a, dep_b = tuned_pair
    rt_a, rt_b = KernelRuntime(), KernelRuntime()
    rt_a.install_for_device("tpu_v5e", dep_a)
    rt_a.activate_device("tpu_v5e")
    rt_b.install_for_device("tpu_v5e", dep_b)
    rt_b.activate_device("tpu_v5e")

    stop = threading.Event()
    errors: list[str] = []

    def swapper():
        for _ in range(50):
            rt_a.install_for_device("tpu_v5e", dep_a)  # epoch bump in A only
        stop.set()

    def reader():
        epoch0 = rt_b.policy_epoch()
        while not stop.is_set():
            cfg = rt_b.select_matmul_config(512, 784, 512, 16)
            if cfg != dep_b.select_matmul(512, 784, 512, 16):
                errors.append(f"B served {cfg}")
                return
        if rt_b.policy_epoch() != epoch0:
            errors.append("B's epoch moved during A's swaps")

    ts, tr = threading.Thread(target=swapper), threading.Thread(target=reader)
    tr.start(); ts.start(); ts.join(); tr.join()
    assert not errors, errors
    assert rt_a.policy_epoch() > rt_b.policy_epoch()
    # B's warm shape cache survived all of A's swaps (no spurious resync)
    assert rt_b.select_matmul_config(512, 784, 512, 16) == dep_b.select_matmul(512, 784, 512, 16)


def test_two_engines_two_runtimes_one_process(tuned_pair):
    """Engine-level multi-tenancy: different bundles, same thread, no leaks."""
    from repro.serve.engine import Request, ServingEngine

    dep_a, dep_b = tuned_pair
    bundle_a = DeploymentBundle({"tpu_v5e": dep_a})
    bundle_b = DeploymentBundle({"tpu_v5e": dep_b})
    rt_a = bundle_a.runtime(device="tpu_v5e", name="tenant-a")
    rt_b = bundle_b.runtime(device="tpu_v5e", name="tenant-b")
    rt_a.set_selection_logging(True)
    rt_b.set_selection_logging(True)

    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.models.model import build_model

    cfg = registry.get("granite-8b").reduced()
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    eng_a = rt_a.serve(model, params, max_batch=1, cache_len=64)
    eng_b = ServingEngine(model, params, max_batch=1, cache_len=64, runtime=rt_b)
    assert eng_a.runtime is rt_a and eng_b.runtime is rt_b

    rng = np.random.default_rng(0)
    reqs_a = [Request(uid=0, prompt=rng.integers(0, cfg.vocab, size=8).astype(np.int32),
                      max_new_tokens=4)]
    reqs_b = [Request(uid=1, prompt=rng.integers(0, cfg.vocab, size=8).astype(np.int32),
                      max_new_tokens=4)]
    assert eng_a.run(reqs_a).completed == 1
    assert eng_b.run(reqs_b).completed == 1

    sel_a = {cfg_ for _, _, cfg_ in rt_a.selection_log()}
    sel_b = {cfg_ for _, _, cfg_ in rt_b.selection_log()}
    assert sel_a and sel_a <= set(dep_a.configs)
    assert sel_b and sel_b <= set(dep_b.configs)
    assert default_runtime().selection_log() == []  # nothing global leaked
    assert default_runtime().active_device() is None
