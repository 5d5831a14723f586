"""Generic kernel-family registry: tune -> deploy -> dispatch -> retune for
every op, plus v1-v4 blob back-compat and v5 forward-compat."""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import retune
from repro.core.bundle import DeploymentBundle
from repro.core.dispatch import Deployment
from repro.core.families import (
    FamilyTuning,
    KernelFamily,
    build_family_dataset,
    family_names,
    get_family,
    is_registered,
    register_family,
    unregister_family,
)
from repro.core.tuner import FamilyTuneResult, tune, tune_family
from repro.kernels import ops
from repro.kernels.ops import FixedPolicy
from repro.kernels.ssm import DEFAULT_SSM_CONFIG, SsmConfig
from repro.kernels.wkv import DEFAULT_WKV_CONFIG, WkvConfig
from repro.core.runtime import default_runtime as rt
from repro.core.runtime import reset_default_runtime

DATA = Path(__file__).parent / "data"


@pytest.fixture(autouse=True)
def _clean_policy():
    # Fresh default runtime per test: no hand-maintained clear_* choreography.
    yield
    reset_default_runtime()


@pytest.fixture(scope="module")
def tuned():
    from repro.core.dataset import build_model_dataset, synthetic_problems

    ds = build_model_dataset(synthetic_problems(80), device_name="tpu_v5e")
    return tune(ds, n_kernels=6)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_builtin_families_registered():
    names = family_names()
    assert names[0] == "matmul"  # matmul anchors the Deployment
    assert set(names) >= {"matmul", "attention", "wkv", "ssm_scan"}
    for name in names:
        fam = get_family(name)
        assert fam.name == name
        assert len(fam.feature_names) > 0
        assert fam.default_config in fam.config_space() or name == "matmul"
        probs = fam.harvest(None)
        assert probs, name
        assert all(len(p) == fam.problem_arity for p in probs), name
        feats = fam.features(probs)
        assert feats.shape == (len(probs), len(fam.feature_names))
        assert np.all(np.isfinite(feats))


def test_get_family_unknown_raises():
    with pytest.raises(KeyError, match="unknown kernel family"):
        get_family("conv3d")


def test_register_custom_family_roundtrip():
    fam = KernelFamily(
        name="toy_op",
        config_cls=WkvConfig,
        config_space=lambda: (WkvConfig(8), WkvConfig(64)),
        default_config=WkvConfig(8),
        feature_names=("log2_s",),
        features=lambda ps: np.log2(np.asarray(ps, float).reshape(-1, 1)),
        harvest=lambda arch_ids: [(128,), (4096,)],
        perf_matrix=lambda ps, cs, dev: 1.0 + np.arange(len(ps) * len(cs), dtype=float).reshape(len(ps), len(cs)),
        policy_attr="select_toy",
        problem_arity=1,
        reference="n/a",
    )
    register_family(fam)
    try:
        assert is_registered("toy_op")
        assert get_family("toy_op") is fam
        # one registry entry is enough to ride the whole tuning pipeline
        res = tune_family("toy_op")
        assert isinstance(res, FamilyTuneResult)
        # tuple-unpack compat shim warns for one release, then goes away
        with pytest.warns(DeprecationWarning, match="configs"):
            configs, tree = res
        assert configs and tree is not None
        assert configs == res.configs and tree is res.tree
    finally:
        unregister_family("toy_op")
    assert not is_registered("toy_op")


def test_build_family_dataset_features_route_through_registry():
    ds = build_family_dataset("wkv")
    assert ds.family == "wkv"
    assert ds.features.shape == (len(ds.problems), 3)
    tr, te = ds.split(0.25)
    assert tr.family == "wkv" and te.family == "wkv"


def test_recmodel_long_tail():
    """Multiple configs win somewhere — the selectable structure exists."""
    from repro.core.recmodel import build_ssm_matrix, build_wkv_matrix

    wkv = build_wkv_matrix([(s, hd) for s in (1, 64, 512, 2048, 32768) for hd in (16, 64, 128)])
    ssm = build_ssm_matrix([(s, d) for s in (64, 512, 2048, 32768) for d in (48, 256, 1600)])
    assert len(set(wkv.argmax(1).tolist())) >= 3
    assert len(set(ssm.argmax(1).tolist())) >= 3
    assert np.all(wkv >= 0) and np.all(ssm >= 0)


# ---------------------------------------------------------------------------
# tuning: every family through the same pipeline
# ---------------------------------------------------------------------------
def test_tune_ships_all_registered_families(tuned):
    dep = tuned.deployment
    assert set(dep.family_names()) >= {"matmul", "attention", "wkv", "ssm_scan"}
    dists = dep.meta["family_distributions"]
    assert set(dists) >= {"attention", "wkv", "ssm_scan"}
    for fname in ("wkv", "ssm_scan"):
        configs, tree = dep.family_tuning(fname)
        assert configs and tree is not None
        assert tuned.family_results[fname].oracle_fraction > 0.8
    # generic select answers every family with a deployed config
    assert dep.select("wkv", (4096, 64)) in dep.family_tuning("wkv").configs
    assert dep.select_ssm(2048, 1600) in dep.family_tuning("ssm_scan").configs


def test_tune_family_rejects_matmul_and_empty():
    with pytest.raises(ValueError, match="tuned via tune"):
        tune_family("matmul")
    with pytest.raises(ValueError, match="no benchmark problems"):
        tune_family("wkv", problems=[])


def test_tune_skips_families_foreign_to_archs():
    """A dense-only arch set leaves wkv/ssm untuned instead of failing."""
    from repro.core.tuner import tune_for_archs

    res = tune_for_archs(["granite-8b"], n_kernels=4, max_problems=30)
    dep = res.deployment
    assert not dep.families.get("wkv")
    assert dep.select_wkv(4096, 64) == DEFAULT_WKV_CONFIG  # reference default


# ---------------------------------------------------------------------------
# dispatch: registry-driven hooks, family-qualified keys, policy coverage
# ---------------------------------------------------------------------------
def test_fixed_policy_covers_every_family():
    pol = FixedPolicy(wkv_config=WkvConfig(64), ssm_config=SsmConfig(64, 16))
    rt().install(pol)
    assert ops.select_wkv_config(2048, 64) == WkvConfig(64)
    assert ops.select_ssm_config(2048, 1600) == SsmConfig(64, 16)


def test_partial_policy_falls_back_to_default():
    """A matmul-only policy no longer needs duck-typed hasattr hooks."""

    class MatmulOnly:
        def select_matmul(self, m, k, n, batch):
            return "mm"

    rt().install(MatmulOnly())
    assert ops.select_wkv_config(2048, 64) is None  # op runs its default config
    assert ops.select_ssm_config(2048, 1600) is None


def test_family_qualified_cache_and_log(tuned):
    """An ssm (s, d) problem can never alias a matmul (m, k) tuple."""
    dep = tuned.deployment
    rt().install(dep)
    rt().set_selection_logging(True)
    rt().clear_selection_log()
    ops.select_ssm_config(512, 784)
    ops.select_matmul_config(512, 784, 512, 16)
    ops.select_wkv_config(512, 784)
    log = ops.selection_log()
    assert [e[0] for e in log] == ["ssm_scan", "matmul", "wkv"]
    assert isinstance(log[0][2], SsmConfig)
    assert isinstance(log[2][2], WkvConfig)
    stats = ops.shape_cache_stats()
    per = stats["per_family"]
    assert per["ssm_scan"] == {"hits": 0, "misses": 1, "size": 1}
    assert per["matmul"]["misses"] == 1 and per["wkv"]["size"] == 1
    ops.select_ssm_config(512, 784)  # memo hit under the family-qualified key
    assert ops.shape_cache_stats()["per_family"]["ssm_scan"]["hits"] == 1


def test_ssm_wkv_ops_dispatch_through_policy(tuned):
    """The model-facing ops consult the tuned policy (no hasattr hooks)."""
    import jax.numpy as jnp

    dep = tuned.deployment
    rt().install(dep)
    rt().set_selection_logging(True)
    rt().clear_selection_log()
    b, s, h, hd = 1, 8, 2, 16
    r = jnp.ones((b, s, h, hd), jnp.float32)
    ops.wkv(r, r, r, -jnp.ones_like(r), jnp.ones((h, hd)), None)
    dtx = jnp.ones((1, 8, 16), jnp.float32)
    dta = -jnp.ones((1, 8, 16, 4), jnp.float32)
    bv = jnp.ones((1, 8, 4), jnp.float32)
    ops.ssm_scan(dtx, dta, bv, bv)
    logged = {e[0]: e[1] for e in ops.selection_log()}
    assert logged["wkv"] == (8, 16)
    assert logged["ssm_scan"] == (8, 16)  # distinct families, same tuple: no clash


def test_online_policy_family_coverage(tuned):
    from repro.core.online import OnlinePolicy

    dep = tuned.deployment
    pol = OnlinePolicy(lambda p, c: 1.0, dep.configs, prior=dep)
    assert pol.select_wkv(4096, 64) == dep.select_wkv(4096, 64)
    assert pol.select_ssm(2048, 1600) == dep.select_ssm(2048, 1600)
    bare = OnlinePolicy(lambda p, c: 1.0, dep.configs)
    assert bare.select_wkv(4096, 64) == DEFAULT_WKV_CONFIG
    assert bare.select_ssm(2048, 1600) == DEFAULT_SSM_CONFIG


# ---------------------------------------------------------------------------
# blob back-compat: committed v1-v4 artifacts load with identical selections
# ---------------------------------------------------------------------------
def _expected():
    return json.loads((DATA / "expected_selections.json").read_text())


@pytest.mark.parametrize("fixture", ["dep_v1.json", "dep_v2.json"])
def test_committed_deployment_blobs_load_identically(fixture):
    exp = _expected()
    dep = Deployment.load(DATA / fixture)
    got_m = [dep.select_matmul(*p).to_dict() for p in exp["matmul_probes"]]
    got_a = [dep.select_attention(*p).to_dict() for p in exp["attention_probes"]]
    assert got_m == exp["devices"]["tpu_v5e"]["matmul"]
    assert got_a == exp["devices"]["tpu_v5e"]["attention"]
    # pre-family artifacts serve reference defaults for the new families
    assert dep.select_wkv(4096, 64) == DEFAULT_WKV_CONFIG
    assert dep.select_ssm(2048, 1600) == DEFAULT_SSM_CONFIG


@pytest.mark.parametrize("fixture", ["bundle_v3.json", "bundle_v4.json"])
def test_committed_bundle_blobs_load_identically(fixture):
    exp = _expected()
    bundle = DeploymentBundle.load(DATA / fixture)
    assert bundle.devices == ["tpu_v4", "tpu_v5e"]
    for device, want in exp["devices"].items():
        dep = bundle.deployments[device]
        got_m = [dep.select_matmul(*p).to_dict() for p in exp["matmul_probes"]]
        got_a = [dep.select_attention(*p).to_dict() for p in exp["attention_probes"]]
        assert got_m == want["matmul"], device
        assert got_a == want["attention"], device
    if fixture == "bundle_v4.json":  # provenance block survives the upgrade
        assert "train_distribution" in bundle.deployments["tpu_v5e"].meta


def test_v5_roundtrip_preserves_family_selections(tmp_path, tuned):
    dep = tuned.deployment
    path = tmp_path / "dep_v5.json"
    dep.save(path)
    blob = json.loads(path.read_text())
    assert blob["version"] == 5
    assert set(blob["families"]) == {"expert_gemm", "ssm_scan", "wkv"}
    back = Deployment.load(path)
    for p in [(1, 64), (2048, 64), (32768, 64)]:
        assert back.select_wkv(*p) == dep.select_wkv(*p)
    for p in [(32, 4096, 1536, 16), (4096, 1536, 4096, 128)]:
        assert back.select("expert_gemm", p) == dep.select("expert_gemm", p)
    for p in [(2048, 1600), (32768, 1600)]:
        assert back.select_ssm(*p) == dep.select_ssm(*p)
    assert back.meta["family_distributions"] == dep.meta["family_distributions"]


def test_unknown_family_ignored_forward_compat(tuned):
    """A blob from a future build with an unknown op stays loadable."""
    blob = tuned.deployment.to_blob()
    blob["families"]["fancy_conv"] = {"configs": [{"tile": 9}], "tree": None}
    back = Deployment.from_blob(blob)
    assert "fancy_conv" not in back.families
    assert set(back.families) == {"expert_gemm", "ssm_scan", "wkv"}  # known families intact


def test_family_tree_labels_validated(tuned):
    blob = tuned.deployment.to_blob()
    bad = blob["families"]["wkv"]["tree"]
    bad["label"] = [99 for _ in bad["label"]]
    with pytest.raises(ValueError, match="families.wkv.tree"):
        Deployment.from_blob(blob)


# ---------------------------------------------------------------------------
# retune: per-(family, shape) buckets; an ssm-only shift touches only ssm
# ---------------------------------------------------------------------------
def _ssm_snapshot(n=60):
    snap = retune.TelemetrySnapshot()
    for i in range(n):
        p = (96 if i % 2 else 160, 48)
        b = retune.shape_bucket(p)
        fam = snap.counts.setdefault("ssm_scan", {})
        fam[b] = fam.get(b, 0) + 1
        snap.family_problems.setdefault("ssm_scan", {})[b] = p
        snap.n_events += 1
    return snap


def test_snapshot_buckets_per_family(tuned):
    rt().install(tuned.deployment)
    rt().set_selection_logging(True)
    rt().clear_selection_log()
    ops.select_matmul_config(512, 784, 512, 16)
    ops.select_ssm_config(512, 784)
    ops.select_wkv_config(2048, 64)
    snap = retune.TelemetrySnapshot.from_selection_log(ops.selection_log())
    assert snap.families() == ["matmul", "ssm_scan", "wkv"]
    assert snap.family_events("matmul") == 1 and snap.family_events("wkv") == 1
    # the same bucket tuple under different families never merges
    assert retune.shape_bucket((512, 784)) in snap.counts["ssm_scan"]
    assert retune.shape_bucket((512, 784)) not in snap.counts["matmul"]
    other = retune.TelemetrySnapshot.from_selection_log(
        [("ssm_scan", (512, 784), None)]
    )
    snap.merge(other)
    assert snap.family_events("ssm_scan") == 2


def test_ssm_only_shift_drifts_and_retunes_only_ssm(tuned):
    dep = tuned.deployment
    snap = _ssm_snapshot()
    rep_mm = retune.detect_drift(snap, dep, family="matmul")
    rep_ssm = retune.detect_drift(snap, dep, family="ssm_scan")
    assert not rep_mm.triggered and rep_mm.score == 0.0  # no matmul traffic
    assert rep_ssm.triggered and rep_ssm.family == "ssm_scan"
    assert rep_ssm.unseen_fraction > 0.9  # serving shapes the harvest never saw
    out = retune.incremental_retune(dep, snap, family="ssm_scan", report=rep_ssm)
    nd = out.deployment
    assert out.family == "ssm_scan" and out.n_harvested > 0
    assert nd.configs == dep.configs  # matmul untouched
    assert nd.classifier is dep.classifier
    assert nd.attention_tree is dep.attention_tree
    assert nd.family_tuning("ssm_scan").tree is not dep.family_tuning("ssm_scan").tree
    assert nd.meta["retune"]["family"] == "ssm_scan"
    # the retuned family is measurably closer to the live distribution
    rep2 = retune.detect_drift(snap, nd, family="ssm_scan")
    assert rep2.score < rep_ssm.score
    assert nd.select_ssm(96, 48) in nd.family_tuning("ssm_scan").configs


def test_engine_maybe_retune_handles_ssm_only_traffic(tuned):
    from test_retune import _ToyModel

    from repro.serve.engine import ServingEngine

    rt().install(tuned.deployment)
    eng = ServingEngine(_ToyModel(), params={}, max_batch=1, cache_len=16,
                        retune_interval=10_000, retune_min_events=8)
    rt().clear_selection_log()
    for _ in range(40):
        ops.select_ssm_config(96, 48)
    ev = eng.maybe_retune()
    assert ev is not None and ev.swapped and ev.families == ("ssm_scan",)
    assert eng.deployment.configs == tuned.deployment.configs  # matmul untouched
    assert eng.deployment.meta["retune"]["family"] == "ssm_scan"


# ---------------------------------------------------------------------------
# codegen: the generated launcher routes every family
# ---------------------------------------------------------------------------
def test_bundle_to_python_family_routing(tuned):
    from repro.core.codegen import bundle_to_python

    bundle = DeploymentBundle({"tpu_v5e": tuned.deployment})
    ns = {}
    exec(bundle_to_python(bundle), ns)  # noqa: S102 — generated launcher code
    assert set(ns["FAMILY_SELECTORS"]) == {"matmul", "attention", "expert_gemm", "ssm_scan", "wkv"}
    for fname in ("attention", "expert_gemm", "wkv", "ssm_scan"):
        fam = get_family(fname)
        _cfgs, tree = tuned.deployment.family_tuning(fname)
        probs = fam.harvest(None)[:4]
        feats = fam.features(probs)
        want = list(tree.predict(feats))
        got = [ns["select_kernel_family"](fname, "tpu_v5e", *row) for row in feats]
        assert got == want, fname
    with pytest.raises(KeyError):
        ns["select_kernel_family"]("conv3d", "tpu_v5e", 1.0)


# ---------------------------------------------------------------------------
# fig7 artifact idempotency
# ---------------------------------------------------------------------------
def test_fig7_merge_is_idempotent(tmp_path, monkeypatch):
    import benchmarks.common as common
    import benchmarks.fig7_end_to_end as fig7

    monkeypatch.setattr(common, "OUT_DIR", tmp_path)
    art = tmp_path / "fig7_end_to_end.json"
    art.write_text(json.dumps({
        "device": "tpu_v5e",
        "per_arch_ms": {"other-arch": {"tuned8": 1.0}, "phi4-mini-3.8b": {"tuned8": 999.0}},
    }))
    merged = fig7._merge_artifact({"phi4-mini-3.8b": {"tuned8": 2.0}})
    # re-measured arch replaced (no duplicate provenance), others preserved
    assert merged["phi4-mini-3.8b"] == {"tuned8": 2.0}
    assert merged["other-arch"] == {"tuned8": 1.0}
    # idempotent: merging the same rows again changes nothing
    assert fig7._merge_artifact({"phi4-mini-3.8b": {"tuned8": 2.0}}) == merged
    # unreadable artifact: rebuild from this run alone
    art.write_text("{corrupt")
    assert fig7._merge_artifact({"a": {"tuned8": 3.0}}) == {"a": {"tuned8": 3.0}}


def test_perf_gate_gates_family_rows():
    from benchmarks.perf_gate import collect_metrics

    gated, _ = collect_metrics(None, {"rows": [
        ["families_wkv_speedup", 2.5, "derived"],
        ["fig7_x_tuned8_ms", 100.0, "derived"],
        ["families_wkv_other", 9.9, "not gated"],
    ]})
    assert gated["families_wkv_speedup"] == (2.5, "higher")
    assert gated["fig7_x_tuned8_ms"] == (100.0, "lower")
    assert "families_wkv_other" not in gated
