"""Multi-device DeploymentBundle: detection, fallback, round-trip, install."""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core.bundle import (
    BundleFormatError,
    BundleIntegrityError,
    DeploymentBundle,
    install_bundle,
)
from repro.core.codegen import bundle_to_python
from repro.core.dataset import build_model_dataset, synthetic_problems
from repro.core.devices import (
    DEVICE_ENV_VAR,
    canonical_device_name,
    detect_device,
    resolve_device,
)
from repro.core.dispatch import Deployment, train_deployment
from repro.core.selection import select_from_dataset
from repro.core.tuner import save_fleet, tune_fleet
from repro.kernels import ops
from repro.core.runtime import default_runtime as rt
from repro.core.runtime import reset_default_runtime


@pytest.fixture(autouse=True)
def _clean_policy():
    # Fresh default runtime per test: no hand-maintained clear_* choreography.
    yield
    reset_default_runtime()


def _mini_deployment(device_name: str, n_kernels: int = 5, seed: int = 0) -> Deployment:
    ds = build_model_dataset(synthetic_problems(60, seed=seed), device_name=device_name)
    tr, _ = ds.split()
    chosen = select_from_dataset(tr, n_kernels, "kmeans", "standard", seed=seed)
    return train_deployment(tr, chosen, "DecisionTreeB")


@pytest.fixture(scope="module")
def bundle2() -> DeploymentBundle:
    return DeploymentBundle(
        {
            "tpu_v5e": _mini_deployment("tpu_v5e"),
            "tpu_v4": _mini_deployment("tpu_v4", n_kernels=4, seed=1),
        },
        meta={"archs": "synthetic"},
    )


# ---------------------------------------------------------------------------
# device canonicalization + detection
# ---------------------------------------------------------------------------
def test_canonical_device_name():
    assert canonical_device_name("TPU v5 lite") == "tpu_v5e"
    assert canonical_device_name("TPU v5e") == "tpu_v5e"
    assert canonical_device_name("TPU v4") == "tpu_v4"
    assert canonical_device_name("TPU v4i") == "tpu_v4"
    assert canonical_device_name("TPU v5p") == "tpu_v5p"
    assert canonical_device_name("cpu") == "host_cpu"
    assert canonical_device_name("", "cpu") == "host_cpu"
    assert canonical_device_name("NVIDIA H100", "gpu") == "gpu_nvidia_h100"
    # canonical slugs are fixed points
    for name in ("tpu_v5e", "tpu_v4", "host_cpu"):
        assert canonical_device_name(name) == name


def test_detect_device_env_override(monkeypatch):
    monkeypatch.setenv(DEVICE_ENV_VAR, "TPU v4")
    assert detect_device() == "tpu_v4"
    monkeypatch.delenv(DEVICE_ENV_VAR)
    # this CI/container host has no accelerator
    assert detect_device() == "host_cpu"


def test_detect_device_raises_when_backend_fails(monkeypatch):
    """A backend that fails to initialise is an error, never a CPU host."""
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.delenv(DEVICE_ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        detect_device()


def test_resolve_device_order():
    avail = ["tpu_v5e", "tpu_v4", "host_cpu"]
    assert resolve_device("tpu_v4", avail) == "tpu_v4"  # exact
    assert resolve_device("tpu_v5p", avail) == "tpu_v4"  # fallback chain
    assert resolve_device("tpu_v7", ["tpu_v5e", "host_cpu"]) == "tpu_v5e"  # family
    assert resolve_device("gpu_h100", ["tpu_v4"]) == "tpu_v4"  # last resort
    assert resolve_device("gpu_h100", []) is None
    with pytest.raises(KeyError):
        resolve_device("gpu_h100", ["tpu_v4"], strict=True)


# ---------------------------------------------------------------------------
# bundle round-trip + back-compat
# ---------------------------------------------------------------------------
def test_bundle_roundtrip_two_devices(tmp_path, bundle2):
    path = tmp_path / "bundle.json"
    bundle2.save(path)
    blob = json.loads(path.read_text())
    assert blob["version"] == 6 and blob["format"] == "bundle"
    assert blob["deployments"]["tpu_v5e"]["version"] == 5  # embeds v5 blobs
    assert blob["checksums"]  # v6: per-section CRCs over every device blob
    back = DeploymentBundle.load(path)
    assert back.devices == ["tpu_v4", "tpu_v5e"]
    for name in back.devices:
        a, b = back.deployments[name], bundle2.deployments[name]
        assert a.configs == b.configs
        for p in [(64, 256, 512, 1), (1, 4096, 1024, 1), (2048, 2048, 2048, 8)]:
            assert a.select_matmul(*p) == b.select_matmul(*p)
    # the two devices genuinely carry different tuned artifacts
    assert back.deployments["tpu_v4"].configs != back.deployments["tpu_v5e"].configs


def test_bundle_loads_single_device_files(tmp_path, bundle2):
    """v1 and v2 single-device artifacts are degenerate one-entry bundles."""
    dep = bundle2.deployments["tpu_v5e"]
    for fmt in ("flat", "nested"):  # v2 and v1 payloads
        p = tmp_path / f"dep_{fmt}.json"
        dep.save(p, tree_format=fmt)
        wrapped = DeploymentBundle.load(p)
        assert wrapped.devices == ["tpu_v5e"]
        assert wrapped.deployments["tpu_v5e"].configs == dep.configs


def test_bundle_rejects_future_version(bundle2):
    blob = bundle2.to_blob()
    blob["version"] = 99
    with pytest.raises(ValueError, match="newer than supported"):
        DeploymentBundle.from_blob(blob)


def test_bundle_normalizes_device_keys():
    dep = _mini_deployment("tpu_v5e")
    b = DeploymentBundle({"TPU v5 lite": dep})
    assert b.devices == ["tpu_v5e"]


# ---------------------------------------------------------------------------
# install + per-device ops registry
# ---------------------------------------------------------------------------
def test_install_bundle_picks_detected_device(monkeypatch, bundle2):
    monkeypatch.setenv(DEVICE_ENV_VAR, "tpu_v4")
    dep = install_bundle(bundle2)
    assert dep is bundle2.deployments["tpu_v4"]
    assert ops.active_device() == "tpu_v4"
    assert ops.get_kernel_policy() is dep
    assert set(ops.device_policies()) == {"tpu_v4", "tpu_v5e"}


def test_install_bundle_untuned_host_falls_back(monkeypatch, bundle2):
    """An untuned v5p host degrades to its nearest tuned sibling (tpu_v4)."""
    monkeypatch.setenv(DEVICE_ENV_VAR, "tpu_v5p")
    dep = install_bundle(bundle2)
    assert dep is bundle2.deployments["tpu_v4"]
    assert ops.active_device() == "tpu_v4"
    assert ops.device_resolution() == ("tpu_v5p", "tpu_v4")
    assert "fallback_for" not in dep.meta  # shared artifacts are not mutated
    # the selections served are the tuned sibling's, not FixedPolicy defaults
    cfg = ops.select_matmul_config(512, 784, 512, 16)
    assert cfg in dep.configs


def test_install_bundle_replaces_stale_registrations(monkeypatch, bundle2):
    """A prior install's policies must not shadow this bundle's resolution."""
    stale = _mini_deployment("tpu_v5e", n_kernels=3, seed=7)
    rt().install_for_device("tpu_v5p", stale)  # from an earlier install
    monkeypatch.setenv(DEVICE_ENV_VAR, "tpu_v5p")
    dep = install_bundle(bundle2)  # bundle2 has no tpu_v5p entry
    # resolution happened within the bundle: fallback to tpu_v4, not stale
    assert dep is bundle2.deployments["tpu_v4"]
    assert ops.get_kernel_policy() is dep
    assert ops.device_resolution() == ("tpu_v5p", "tpu_v4")
    assert set(ops.device_policies()) == {"tpu_v4", "tpu_v5e"}


def test_clear_device_policies_deactivates_live_policy(monkeypatch, bundle2):
    monkeypatch.setenv(DEVICE_ENV_VAR, "tpu_v5e")
    install_bundle(bundle2)
    assert ops.get_kernel_policy() is not None
    rt().clear_device_policies()
    # the registry-owned live policy is uninstalled with the registry
    assert ops.get_kernel_policy() is None and ops.active_device() is None
    # a manual (non-registry) install survives a registry clear
    manual = bundle2.deployments["tpu_v4"]
    rt().install(manual)
    rt().clear_device_policies()
    assert ops.get_kernel_policy() is manual


def test_install_bundle_strict_raises(monkeypatch, bundle2):
    monkeypatch.setenv(DEVICE_ENV_VAR, "gpu_h100")
    with pytest.raises(KeyError):
        install_bundle(bundle2, strict=True)
    # non-strict still serves *something* tuned
    dep = install_bundle(bundle2)
    assert dep in bundle2.deployments.values()


def test_ops_device_registry_semantics(bundle2):
    v5e = bundle2.deployments["tpu_v5e"]
    v4 = bundle2.deployments["tpu_v4"]
    rt().install_for_device("tpu_v5e", v5e)
    rt().install_for_device("tpu_v4", v4)
    assert ops.get_kernel_policy() is None  # registration does not activate
    assert rt().activate_device("tpu_v5e") == "tpu_v5e"
    assert ops.get_kernel_policy() is v5e
    # re-registering the active device refreshes the live policy
    rt().install_for_device("tpu_v5e", v4)
    assert ops.get_kernel_policy() is v4
    # dropping the live device's policy deactivates it — no stale marker
    rt().install_for_device("tpu_v5e", None)
    assert ops.active_device() is None and ops.get_kernel_policy() is None
    assert ops.device_resolution() == (None, None)
    rt().install_for_device("tpu_v5e", v5e)
    rt().activate_device("tpu_v5e")
    # a manual single-device install detaches from the registry
    rt().install(v5e)
    assert ops.active_device() is None
    rt().clear_device_policies()
    with pytest.raises(KeyError):
        rt().activate_device("tpu_v5e")


def test_serving_engine_consumes_bundle(monkeypatch, bundle2):
    from test_serve_engine import ToyModel

    from repro.serve.engine import Request, ServingEngine

    monkeypatch.setenv(DEVICE_ENV_VAR, "tpu_v5e")
    eng = ServingEngine(ToyModel(), params={}, max_batch=1, cache_len=32,
                        prefill_buckets=(8,), bundle=bundle2)
    assert eng.device == "tpu_v5e"
    assert eng.deployment is bundle2.deployments["tpu_v5e"]
    assert ops.get_kernel_policy() is eng.deployment
    req = Request(uid=0, prompt=np.array([1, 2, 3], dtype=np.int32), max_new_tokens=2)
    status = eng.run([req])
    assert status.completed == 1


# ---------------------------------------------------------------------------
# fleet tuning
# ---------------------------------------------------------------------------
def test_tune_fleet_two_devices(tmp_path):
    fleet = tune_fleet(["granite-8b"], device_names=("tpu_v5e", "tpu_v4"),
                       n_kernels=4, max_problems=40)
    assert sorted(fleet.results) == ["tpu_v4", "tpu_v5e"]
    for name, res in fleet.results.items():
        assert res.oracle_fraction > 0.7
        assert fleet.bundle.deployments[name] is res.deployment
        assert res.deployment.meta["oracle_fraction"] == res.oracle_fraction
    path = tmp_path / "fleet.json"
    save_fleet(fleet, path)
    back = DeploymentBundle.load(path)
    assert back.devices == ["tpu_v4", "tpu_v5e"]
    assert back.meta["archs"] == ["granite-8b"]
    dep, resolved = back.deployment_for("tpu_v5e")
    assert resolved == "tpu_v5e" and len(dep.configs) == 4


# ---------------------------------------------------------------------------
# structured load errors + v6 section checksums (DESIGN.md §11)
# ---------------------------------------------------------------------------
DATA = Path(__file__).parent / "data"
FIXTURES = [  # every committed artifact version, v1 through v5
    "dep_v1.json", "dep_v2.json", "bundle_v3.json", "bundle_v4.json",
    "bundle_v5.json",
]


@pytest.fixture(scope="module")
def bundle_fam(bundle2) -> DeploymentBundle:
    """bundle2 with a wkv family tuning attached, so v6 blobs carry a
    per-family checksum section worth corrupting."""
    from repro.core.cluster import select_configs
    from repro.core.dispatch import build_labels
    from repro.core.families import build_family_dataset, get_family
    from repro.core.normalize import normalize

    fam = get_family("wkv")
    ds = build_family_dataset("wkv", device_name="tpu_v5e")
    chosen = select_configs(normalize(ds.perf, "standard"), 3, "kmeans", seed=0)
    tree = fam.make_tree()
    tree.fit(fam.features(ds.problems), build_labels(ds.perf, chosen))
    cfgs = list(fam.config_space())
    dep = bundle2.deployments["tpu_v5e"].clone()
    dep.set_family_tuning("wkv", [cfgs[i] for i in chosen], tree)
    return DeploymentBundle(
        {"tpu_v5e": dep, "tpu_v4": bundle2.deployments["tpu_v4"]}
    )


@pytest.mark.parametrize("fixture", FIXTURES)
def test_committed_fixtures_load_clean(fixture):
    b = DeploymentBundle.load(DATA / fixture)
    assert b.devices and not b.load_errors


@pytest.mark.parametrize("fixture", FIXTURES)
def test_truncated_fixture_raises_structured_error(tmp_path, fixture):
    """A blob cut off mid-write fails as BundleFormatError with the byte
    offset where decoding stopped — never a bare JSONDecodeError."""
    text = (DATA / fixture).read_text()
    for frac in (0.3, 0.8):
        p = tmp_path / f"t{int(frac * 100)}_{fixture}"
        p.write_text(text[: int(len(text) * frac)])
        with pytest.raises(BundleFormatError) as ei:
            DeploymentBundle.load(p)
        assert ei.value.offset is not None
        assert isinstance(ei.value, ValueError)  # callers catching ValueError keep working


def test_garbage_blob_raises_structured_error(tmp_path):
    p = tmp_path / "garbage.json"
    p.write_text("definitely not a bundle {{{")
    with pytest.raises(BundleFormatError) as ei:
        DeploymentBundle.load(p)
    assert ei.value.offset == 0
    # valid JSON of the wrong shape is a format error too, not a TypeError
    p2 = tmp_path / "list.json"
    p2.write_text("[1, 2, 3]")
    with pytest.raises(BundleFormatError, match="JSON object"):
        DeploymentBundle.load(p2)


@pytest.mark.parametrize("fixture", ["bundle_v3.json", "bundle_v4.json",
                                     "bundle_v5.json"])
def test_mangled_bundle_sections_name_the_section(fixture):
    blob = json.loads((DATA / fixture).read_text())
    with pytest.raises(BundleFormatError) as ei:
        DeploymentBundle.from_blob(dict(blob, version="vX"))
    assert ei.value.section == "version"
    with pytest.raises(BundleFormatError) as ei:
        DeploymentBundle.from_blob(dict(blob, deployments=7))
    assert ei.value.section == "deployments"
    # a structurally gutted device blob names the device it belongs to
    bad = json.loads((DATA / fixture).read_text())
    name = sorted(bad["deployments"])[0]
    bad["deployments"][name] = {"version": 5}
    with pytest.raises(BundleFormatError) as ei:
        DeploymentBundle.from_blob(bad)
    assert ei.value.section == f"deployments.{name}"


@pytest.mark.parametrize("fixture", ["dep_v1.json", "dep_v2.json"])
def test_mangled_single_device_fixture(fixture):
    blob = json.loads((DATA / fixture).read_text())
    blob.pop("configs", None)
    with pytest.raises(BundleFormatError) as ei:
        DeploymentBundle.from_blob(blob)
    assert ei.value.section == "deployment"


def test_v6_corrupt_device_core_recovers_via_fallbacks(bundle2):
    blob = bundle2.to_blob()
    blob["deployments"]["tpu_v5e"]["classifier_name"] = "tampered"
    back = DeploymentBundle.from_blob(blob)
    assert back.devices == ["tpu_v4"]  # corrupt device dropped, not fatal
    assert [e["section"] for e in back.load_errors] == ["deployments.tpu_v5e"]
    # lookups for the dropped device recover through devices.FALLBACKS
    dep, resolved = back.deployment_for("tpu_v5e")
    assert resolved == "tpu_v4" and dep is back.deployments["tpu_v4"]


def test_v6_corrupt_family_section_drops_family_only(bundle_fam):
    clean = DeploymentBundle.from_blob(bundle_fam.to_blob())
    assert not clean.load_errors and "wkv" in clean.deployments["tpu_v5e"].families
    blob = bundle_fam.to_blob()
    blob["deployments"]["tpu_v5e"]["families"]["wkv"]["configs"] = ["garbage"]
    back = DeploymentBundle.from_blob(blob)
    # the device survives minus the corrupt family (its op serves the ref path)
    assert "tpu_v5e" in back.deployments
    assert "wkv" not in back.deployments["tpu_v5e"].families
    secs = [e["section"] for e in back.load_errors]
    assert secs == ["deployments.tpu_v5e.families.wkv"]
    assert back.deployments["tpu_v4"].configs == bundle_fam.deployments["tpu_v4"].configs


def test_v6_missing_checksummed_family_is_recorded(bundle_fam):
    blob = bundle_fam.to_blob()
    del blob["deployments"]["tpu_v5e"]["families"]["wkv"]
    back = DeploymentBundle.from_blob(blob)
    assert any(e["section"].endswith("families.wkv") and "missing" in e["error"]
               for e in back.load_errors)


def test_v6_all_devices_corrupt_raises_integrity_error(bundle2):
    blob = bundle2.to_blob()
    for name in blob["deployments"]:
        blob["deployments"][name]["device"] = "tampered"
    with pytest.raises(BundleIntegrityError, match="no deployment"):
        DeploymentBundle.from_blob(blob)


def test_v6_provenance_mismatch_dropped_not_fatal(bundle2):
    blob = bundle2.to_blob()
    blob["provenance"] = {"tpu_v5e": {"seed": 1}}
    blob["checksums"]["provenance"] = "00000000"
    back = DeploymentBundle.from_blob(blob)
    assert back.devices == ["tpu_v4", "tpu_v5e"]  # deployments unaffected
    assert any(e["section"] == "provenance" for e in back.load_errors)
    assert "seed" not in back.deployments["tpu_v5e"].meta


# ---------------------------------------------------------------------------
# codegen
# ---------------------------------------------------------------------------
def test_bundle_to_python_routes_by_device(bundle2):
    src = bundle_to_python(bundle2)
    ns = {}
    exec(src, ns)  # noqa: S102 — generated launcher code, the paper's embedding
    assert set(ns["DEVICE_SELECTORS"]) == {"tpu_v4", "tpu_v5e"}
    feats = build_model_dataset(synthetic_problems(30)).features
    for device in ("tpu_v5e", "tpu_v4"):
        want = list(bundle2.deployments[device].classifier.predict(feats))
        got = [ns["select_kernel"](device, *row) for row in feats]
        assert got == want
    # untuned device routes through the baked-in fallback chain
    want = list(bundle2.deployments["tpu_v4"].classifier.predict(feats))
    got = [ns["select_kernel"]("tpu_v5p", *row) for row in feats]
    assert got == want
    # raw jax device_kind strings canonicalize inside the generated launcher
    row = feats[0]
    assert ns["select_kernel"]("TPU v4", *row) == ns["select_kernel"]("tpu_v4", *row)
    assert ns["select_kernel"]("TPU v5 lite", *row) == ns["select_kernel"]("tpu_v5e", *row)
