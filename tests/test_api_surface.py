"""Public-API snapshot: accidental surface breaks fail CI, deliberate ones
update the frozen lists below (and the README migration map if a legacy name
moves).

The snapshot covers the four entry layers of the redesigned API:
``repro`` (the facade), ``repro.core`` (the tuning pipeline),
``repro.kernels.ops`` (dispatch + the deprecated global shims),
``repro.core.faults`` (the failure-containment layer, which also absorbed
the former ``repro.ft.runtime`` training-side fault-tolerance helpers),
``repro.serve`` (the fleet serving tier: paged KV pool, scheduler, router),
and ``repro.control`` (the tuning control plane: job service, artifact
registry, telemetry federation).
"""
import importlib

import pytest

REPRO_ALL = [
    "ArtifactRegistry",
    "ControlPlane",
    "ControlPlaneClient",
    "Deployment",
    "DeploymentBundle",
    "EngineStatus",
    "FaultPlan",
    "KernelRuntime",
    "PolicySubscriber",
    "Request",
    "Router",
    "ServingEngine",
    "TelemetrySnapshot",
    "Ticket",
    "__version__",
    "current_runtime",
    "default_runtime",
    "install_bundle",
    "load_bundle",
    "reset_default_runtime",
    "tune",
]

CORE_ALL = [
    "CLASSIFIERS",
    "CLUSTER_METHODS",
    "NORMALIZATIONS",
    "PCA",
    "Deployment",
    "DeploymentBundle",
    "FamilyPipelineResult",
    "FamilyTuning",
    "FaultError",
    "FaultPlan",
    "FaultSpec",
    "FlatTree",
    "FleetTuneResult",
    "KernelFamily",
    "KernelRuntime",
    "TelemetrySnapshot",
    "TransferPrior",
    "TuneResult",
    "TuningDataset",
    "achievable_fraction",
    "build_family_dataset",
    "build_model_dataset",
    "canonical_device_name",
    "classifier_fraction",
    "current_runtime",
    "default_runtime",
    "detect_device",
    "evaluate_methods",
    "families",
    "family_names",
    "get_family",
    "harvest_problems",
    "install_bundle",
    "make_classifier",
    "normalize",
    "problem_features",
    "register_family",
    "reset_default_runtime",
    "resolve_device",
    "run_family_pipeline",
    "save_fleet",
    "select_configs",
    "select_from_dataset",
    "synthetic_problems",
    "train_deployment",
    "tune",
    "tune_dataset",
    "tune_family",
    "tune_fleet",
    "tune_for_archs",
]

OPS_ALL = [
    "KernelPolicy",
    "FixedPolicy",
    "attention",
    "expert_gemm",
    "matmul",
    "ssm_scan",
    "wkv",
    "select_kernel_config",
    "select_matmul_config",
    "select_ssm_config",
    "select_wkv_config",
    "active_device",
    "device_policies",
    "device_resolution",
    "get_kernel_policy",
    "policy_epoch",
    "selection_log",
    "selection_logging_enabled",
    "shape_cache_stats",
    "activate_device",
    "clear_device_policies",
    "clear_selection_log",
    "clear_shape_cache",
    "set_kernel_policy",
    "set_kernel_policy_for_device",
    "set_pallas_enabled",
    "set_selection_logging",
    "set_shape_cache_cap",
]

SERVE_ALL = [
    "EngineStatus",
    "KVPool",
    "Objective",
    "Request",
    "RetuneEvent",
    "Router",
    "Scheduler",
    "SchedulerConfig",
    "ServingEngine",
    "Ticket",
]

CONTROL_ALL = [
    "ArtifactRegistry",
    "ArtifactVersion",
    "ControlPlane",
    "ControlPlaneClient",
    "ControlPlaneError",
    "Job",
    "PolicySubscriber",
    "content_version",
]

FAULTS_ALL = [
    "FAULT_KINDS",
    "ElasticPlan",
    "FaultError",
    "FaultEvent",
    "FaultPlan",
    "FaultSpec",
    "GUARDED_EXCEPTIONS",
    "InjectedCompileError",
    "InjectedOOMError",
    "NonFiniteOutputError",
    "PreemptionGuard",
    "StragglerDetector",
    "elastic_plan",
    "incident",
]


@pytest.mark.parametrize(
    "module,snapshot",
    [
        ("repro", REPRO_ALL),
        ("repro.core", CORE_ALL),
        ("repro.kernels.ops", OPS_ALL),
        ("repro.core.faults", FAULTS_ALL),
        ("repro.serve", SERVE_ALL),
        ("repro.control", CONTROL_ALL),
    ],
    ids=["repro", "repro.core", "repro.kernels.ops", "repro.core.faults",
         "repro.serve", "repro.control"],
)
def test_public_surface_frozen(module, snapshot):
    mod = importlib.import_module(module)
    assert sorted(mod.__all__) == sorted(snapshot), (
        f"{module}.__all__ changed — if deliberate, update tests/test_api_surface.py "
        f"(and the README migration map for legacy names)"
    )
    assert len(set(snapshot)) == len(snapshot), "snapshot has duplicates"


@pytest.mark.parametrize(
    "module", ["repro", "repro.core", "repro.kernels.ops", "repro.serve",
               "repro.control"],
)
def test_all_names_resolve(module):
    mod = importlib.import_module(module)
    for name in mod.__all__:
        assert getattr(mod, name, None) is not None, f"{module}.{name} does not resolve"


def test_facade_version_matches_package_metadata():
    import repro

    assert isinstance(repro.__version__, str) and repro.__version__.count(".") == 2


def test_facade_lazy_names_complete():
    """Every __all__ name is either defined eagerly or wired into _LAZY."""
    import repro

    eager = {"__version__", "tune", "load_bundle"}
    assert set(repro.__all__) == eager | set(repro._LAZY)
