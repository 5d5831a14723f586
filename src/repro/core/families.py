"""Kernel-family registry: every tunable op as a first-class pipeline citizen.

The paper's pitch is that clustering + classification makes kernel selection
work for *general-purpose* libraries — any routine, any input.  This module
is the piece that makes that true here: a :class:`KernelFamily` declares
everything the tune -> deploy -> dispatch -> retune pipeline needs to know
about one op, and every layer iterates the registry instead of special-casing
matmul/attention:

  * ``tuner.tune`` / ``tune_fleet``     loop ``families()`` to tune each op;
  * ``dispatch.Deployment``             stores per-family ``(configs, tree)``
                                        and answers ``select(family, problem)``;
  * ``kernels.ops``                     resolves the policy hook and memoizes
                                        by family-qualified shape key;
  * ``core.retune``                     buckets telemetry and drift per
                                        ``(device, family, shape)``;
  * ``core.codegen``                    emits launcher routing per family.

Adding a new op to the whole pipeline is one ``register_family`` call (see
DESIGN.md §9 for the recipe); ``wkv`` and ``ssm_scan`` are registered below
exactly that way.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Sequence

import numpy as np

from repro.kernels.attention import DEFAULT_ATTN_CONFIG, AttentionConfig, attention_config_space
from repro.kernels.gmm import DEFAULT_GMM_CONFIG, GmmConfig, gmm_config_space
from repro.kernels.matmul import DEFAULT_CONFIG, MatmulConfig, config_space
from repro.kernels.ssm import DEFAULT_SSM_CONFIG, SsmConfig, ssm_config_space
from repro.kernels.wkv import DEFAULT_WKV_CONFIG, WkvConfig, wkv_config_space


class FamilyTuning(NamedTuple):
    """One family's shipped artifact: deployed configs + runtime classifier."""

    configs: list
    tree: object | None


@dataclasses.dataclass(frozen=True)
class KernelFamily:
    """Everything the pipeline needs to know about one tunable op.

    ``perf_matrix(problems, configs, device_name)`` is the benchmark-data
    source (analytic model on TPU-less hosts, a measure hook on hardware);
    ``harvest(arch_ids)`` yields the problems the assigned architectures
    actually launch; ``features`` is the trace-time featurization shared by
    tuning and dispatch.  ``policy_attr`` names the ``KernelPolicy`` method
    (``select_matmul``, ``select_wkv``, ...) so the ops layer can resolve the
    hook generically; ``name`` doubles as the dispatch-op / telemetry key.
    """

    name: str
    config_cls: type
    config_space: Callable[[], Sequence]
    default_config: object
    feature_names: tuple[str, ...]
    features: Callable[[list[tuple]], np.ndarray]
    harvest: Callable[[list[str] | None], list[tuple]]
    perf_matrix: Callable[[list[tuple], Sequence, str | None], np.ndarray]
    policy_attr: str
    problem_arity: int
    reference: str  # where the numerically-identical fallback lives
    default_n_kernels: int = 4
    # True: the perf surface differs per device, so tune_fleet re-tunes this
    # family per device; False: one tuning is shared across the fleet.
    device_sensitive: bool = False
    # Decision-tree hyperparameters for this family's runtime classifier —
    # shared by tune_family and incremental_retune so a retuned artifact
    # refits with the same capacity the offline tuning shipped.
    tree_max_depth: int = 6
    tree_min_samples_leaf: int = 1
    # Model-side (measurement-free) perf predictor with the same signature as
    # ``perf_matrix``.  The staged pipeline (repro.core.pipeline) prunes the
    # config space and allocates its measurement budget from this table; a
    # family without one tunes full-harvest only.  For the analytic-model
    # families this is the untextured roofline (``texture=False``).
    model_matrix: Callable[[list[tuple], Sequence, str | None], np.ndarray] | None = None
    # The TPU compiler's refusal of this family's Pallas kernel, or None when
    # it lowers.  A refused family runs its reference on a TPU
    # (``KernelRuntime.kernel_path``); the interpreter still runs the kernel.
    tpu_refusal: str | None = None

    def make_tree(self, seed: int = 0):
        """A fresh (unfit) runtime classifier for this family."""
        from .classify import DecisionTreeClassifier

        return DecisionTreeClassifier(
            max_depth=self.tree_max_depth, min_samples_leaf=self.tree_min_samples_leaf,
            seed=seed,
        )


_REGISTRY: dict[str, KernelFamily] = {}


def register_family(family: KernelFamily) -> KernelFamily:
    """Add (or replace) one family; returns it for decorator-style use."""
    if not family.name or any(ch in family.name for ch in " ,/"):
        raise ValueError(f"bad family name {family.name!r}")
    _REGISTRY[family.name] = family
    return family


def unregister_family(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_family(name: str) -> KernelFamily:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel family {name!r}; registered: {family_names()}") from None


def family_names() -> list[str]:
    """Registered family names, matmul first (it anchors the Deployment)."""
    return sorted(_REGISTRY, key=lambda n: (n != "matmul", n))


def families() -> list[KernelFamily]:
    return [_REGISTRY[n] for n in family_names()]


def is_registered(name: str) -> bool:
    return name in _REGISTRY


# ---------------------------------------------------------------------------
# built-in families
# ---------------------------------------------------------------------------
def _matmul_features(problems):
    from .dataset import problem_features

    return problem_features(problems)


def _matmul_harvest(arch_ids):
    from .dataset import harvest_problems

    return harvest_problems(arch_ids)


def _matmul_perf(problems, configs, device_name):
    from .perfmodel import DEVICES, build_perf_matrix

    if device_name not in DEVICES:
        raise ValueError(
            f"no analytic matmul perf model for device {device_name!r}; "
            f"use a measured dataset (repro.core.cpubench) instead"
        )
    return build_perf_matrix(problems, list(configs), DEVICES[device_name])


def _matmul_model(problems, configs, device_name):
    from .perfmodel import DEVICES, TPU_V5E, build_perf_matrix

    dev = DEVICES.get(device_name, TPU_V5E) if device_name else TPU_V5E
    return build_perf_matrix(problems, list(configs), dev, texture=False)


def _attn_features(problems):
    from .attnmodel import attn_problem_features

    return attn_problem_features(problems)


def _attn_harvest(arch_ids):
    from .attnmodel import harvest_attn_problems

    return harvest_attn_problems(arch_ids)


def _attn_perf(problems, configs, device_name):
    from .attnmodel import build_attn_matrix
    from .perfmodel import DEVICES, TPU_V5E

    return build_attn_matrix(problems, list(configs), DEVICES.get(device_name, TPU_V5E))


def _attn_model(problems, configs, device_name):
    from .attnmodel import build_attn_matrix
    from .perfmodel import DEVICES, TPU_V5E

    dev = DEVICES.get(device_name, TPU_V5E)
    return build_attn_matrix(problems, list(configs), dev, texture=False)


def _wkv_perf(problems, configs, device_name):
    from .recmodel import build_wkv_matrix

    return build_wkv_matrix(problems, list(configs), device_name)


def _wkv_model(problems, configs, device_name):
    from .recmodel import build_wkv_matrix

    return build_wkv_matrix(problems, list(configs), device_name, texture=False)


def _wkv_features(problems):
    from .recmodel import wkv_problem_features

    return wkv_problem_features(problems)


def _wkv_harvest(arch_ids):
    from .recmodel import harvest_wkv_problems

    return harvest_wkv_problems(arch_ids)


def _ssm_perf(problems, configs, device_name):
    from .recmodel import build_ssm_matrix

    return build_ssm_matrix(problems, list(configs), device_name)


def _ssm_model(problems, configs, device_name):
    from .recmodel import build_ssm_matrix

    return build_ssm_matrix(problems, list(configs), device_name, texture=False)


def _ssm_features(problems):
    from .recmodel import ssm_problem_features

    return ssm_problem_features(problems)


def _ssm_harvest(arch_ids):
    from .recmodel import harvest_ssm_problems

    return harvest_ssm_problems(arch_ids)


def _gmm_perf(problems, configs, device_name):
    from .gmmmodel import build_gmm_matrix

    return build_gmm_matrix(problems, list(configs), device_name)


def _gmm_model(problems, configs, device_name):
    from .gmmmodel import build_gmm_matrix

    return build_gmm_matrix(problems, list(configs), device_name, texture=False)


def _gmm_features(problems):
    from .gmmmodel import gmm_problem_features

    return gmm_problem_features(problems)


def _gmm_harvest(arch_ids):
    from .gmmmodel import harvest_gmm_problems

    return harvest_gmm_problems(arch_ids)


from .attnmodel import ATTN_FEATURE_NAMES  # noqa: E402
from .dataset import FEATURE_NAMES as MATMUL_FEATURE_NAMES  # noqa: E402
from .gmmmodel import GMM_FEATURE_NAMES  # noqa: E402
from .recmodel import SSM_FEATURE_NAMES, WKV_FEATURE_NAMES  # noqa: E402

MATMUL = register_family(
    KernelFamily(
        name="matmul",
        config_cls=MatmulConfig,
        config_space=config_space,
        default_config=DEFAULT_CONFIG,
        feature_names=tuple(MATMUL_FEATURE_NAMES),
        features=_matmul_features,
        harvest=_matmul_harvest,
        perf_matrix=_matmul_perf,
        policy_attr="select_matmul",
        problem_arity=4,
        reference="jnp.dot (XLA)",
        default_n_kernels=8,
        device_sensitive=True,
        model_matrix=_matmul_model,
    )
)

ATTENTION = register_family(
    KernelFamily(
        name="attention",
        config_cls=AttentionConfig,
        config_space=attention_config_space,
        default_config=DEFAULT_ATTN_CONFIG,
        feature_names=tuple(ATTN_FEATURE_NAMES),
        features=_attn_features,
        harvest=_attn_harvest,
        perf_matrix=_attn_perf,
        policy_attr="select_attention",
        problem_arity=3,
        reference="repro.kernels.ref.flash_attention_ref",
        default_n_kernels=4,
        model_matrix=_attn_model,
    )
)

WKV = register_family(
    KernelFamily(
        name="wkv",
        config_cls=WkvConfig,
        config_space=wkv_config_space,
        default_config=DEFAULT_WKV_CONFIG,
        feature_names=tuple(WKV_FEATURE_NAMES),
        features=_wkv_features,
        harvest=_wkv_harvest,
        perf_matrix=_wkv_perf,
        policy_attr="select_wkv",
        problem_arity=2,
        reference="repro.kernels.ref.wkv_ref",
        default_n_kernels=3,
        model_matrix=_wkv_model,
        tpu_refusal="NotImplementedError: Unimplemented primitive in Pallas TPU "
                    "lowering for KernelType.TC: cumsum",
    )
)

SSM_SCAN = register_family(
    KernelFamily(
        name="ssm_scan",
        config_cls=SsmConfig,
        config_space=ssm_config_space,
        default_config=DEFAULT_SSM_CONFIG,
        feature_names=tuple(SSM_FEATURE_NAMES),
        features=_ssm_features,
        harvest=_ssm_harvest,
        perf_matrix=_ssm_perf,
        policy_attr="select_ssm",
        problem_arity=2,
        reference="repro.kernels.ref.ssm_scan_ref",
        default_n_kernels=4,
        model_matrix=_ssm_model,
        tpu_refusal="NotImplementedError: Unimplemented primitive in Pallas TPU "
                    "lowering for KernelType.TC: dynamic_slice (and block_d=64 "
                    "blocks are not 128-lane aligned)",
    )
)

EXPERT_GEMM = register_family(
    KernelFamily(
        name="expert_gemm",
        config_cls=GmmConfig,
        config_space=gmm_config_space,
        default_config=DEFAULT_GMM_CONFIG,
        feature_names=GMM_FEATURE_NAMES,
        features=_gmm_features,
        harvest=_gmm_harvest,
        perf_matrix=_gmm_perf,
        policy_attr="select_expert_gemm",
        problem_arity=4,
        reference="repro.kernels.ref.gmm_ref (jax.lax.ragged_dot)",
        default_n_kernels=4,
        model_matrix=_gmm_model,
    )
)


def build_family_dataset(
    family: str | KernelFamily,
    problems: list[tuple] | None = None,
    device_name: str = "tpu_v5e",
):
    """Benchmark table for any registered family as a ``TuningDataset``."""
    from .dataset import TuningDataset

    fam = family if isinstance(family, KernelFamily) else get_family(family)
    problems = problems if problems is not None else fam.harvest(None)
    configs = list(fam.config_space())
    perf = fam.perf_matrix(problems, configs, device_name)
    return TuningDataset(
        device=device_name, problems=list(problems), configs=configs, perf=perf,
        source="model", family=fam.name,
    )
