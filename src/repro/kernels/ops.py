"""Jit'd kernel entry points + the runtime kernel-selection hook (paper §5).

This is the "kernel launcher" of the paper: every matmul in the framework
routes through :func:`matmul`, which consults the installed
:class:`KernelPolicy` to pick one of the *deployed* kernel configurations for
the problem size at trace time (JAX shapes are static, so trace time is the
TPU-native "runtime" — see DESIGN.md §2).

Selection state lives on an explicit :class:`~repro.core.runtime.KernelRuntime`
(DESIGN.md §10): dispatch consults the innermost runtime activated on the
calling thread (``with rt.activate(): ...``), falling back to the process-wide
default runtime.  The module-level mutators below
(``set_kernel_policy`` & co.) are **deprecated** thin shims over that default
runtime — byte-identical selections, kept for migration; see README's
old→new map.  New code should hold a ``KernelRuntime`` and call its methods.

A policy is produced by ``repro.core.tuner`` from benchmark data.  The kernel
path comes from the platform (``KernelRuntime.kernel_path``): compiled Pallas
on a TPU, XLA's ``jnp.dot`` / the jnp references elsewhere — numerically
identical (same f32 accumulation), which the kernel tests assert.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Protocol

import jax
import jax.numpy as jnp

from repro.core.faults import (
    GUARDED_EXCEPTIONS,
    FaultPlan,
    NonFiniteOutputError,
    incident,
)
from repro.core.runtime import (
    DEFAULT_LOG_CAP,
    DEFAULT_SHAPE_CACHE_CAP,
    current_runtime,
)

from .attention import DEFAULT_ATTN_CONFIG, AttentionConfig, flash_attention_pallas
from .matmul import DEFAULT_CONFIG, MatmulConfig, matmul_pallas
from .ref import flash_attention_ref
from .ssm import DEFAULT_SSM_CONFIG, SsmConfig, ssm_scan_pallas
from .wkv import DEFAULT_WKV_CONFIG, WkvConfig, wkv_pallas

__all__ = [
    # dispatch entry points (the real ops API)
    "KernelPolicy",
    "FixedPolicy",
    "attention",
    "matmul",
    "ssm_scan",
    "wkv",
    # launcher-side selection helpers (route through the current runtime)
    "select_kernel_config",
    "select_matmul_config",
    "select_ssm_config",
    "select_wkv_config",
    # runtime-state readers (current-runtime passthroughs)
    "active_device",
    "device_policies",
    "device_resolution",
    "get_kernel_policy",
    "policy_epoch",
    "selection_log",
    "selection_logging_enabled",
    "shape_cache_stats",
    # deprecated global mutators (shims over the default runtime)
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


class KernelPolicy(Protocol):
    """Maps a kernel-family problem to the deployed config that should run it.

    One ``select_<family>`` hook per registered family
    (``repro.core.families``); the runtime resolves the hook generically via
    the registry's ``policy_attr``, so a policy implementing only a subset
    keeps working — unimplemented families fall back to their default config
    (unless the policy exposes a generic ``select(family, problem)``).

    A policy may additionally expose ``select_for_objective(family, problem,
    objective)``; when the runtime carries an active
    :class:`~repro.core.runtime.Objective` (SLO mode — a latency target
    and/or a ``prefill_chunk_tokens`` work-granularity hint from the serving
    tier's chunked prefill), that hook is consulted first so latency-biased
    configs can override the throughput-tuned default for the same shape.
    """

    def select_matmul(self, m: int, k: int, n: int, batch: int) -> MatmulConfig: ...

    def select_attention(self, sq: int, skv: int, d: int) -> AttentionConfig: ...

    def select_wkv(self, s: int, hd: int) -> WkvConfig: ...

    def select_ssm(self, s: int, d: int) -> SsmConfig: ...


@dataclasses.dataclass
class FixedPolicy:
    """Single-kernel-per-family baseline (what an untuned library ships)."""

    matmul_config: MatmulConfig = DEFAULT_CONFIG
    attention_config: AttentionConfig = DEFAULT_ATTN_CONFIG
    wkv_config: WkvConfig = DEFAULT_WKV_CONFIG
    ssm_config: SsmConfig = DEFAULT_SSM_CONFIG

    def select_matmul(self, m, k, n, batch):
        return self.matmul_config

    def select_attention(self, sq, skv, d):
        return self.attention_config

    def select_wkv(self, s, hd):
        return self.wkv_config

    def select_ssm(self, s, d):
        return self.ssm_config


# ---------------------------------------------------------------------------
# deprecated module-level API: thin shims over the current (default) runtime
# ---------------------------------------------------------------------------
def _warn_global(old: str, new: str) -> None:
    warnings.warn(
        f"repro.kernels.ops.{old}() mutates shared global runtime state and is "
        f"deprecated; hold a repro.KernelRuntime and call {new} instead "
        f"(see the migration map in README.md)",
        DeprecationWarning,
        stacklevel=3,
    )


def set_kernel_policy(policy: KernelPolicy | None) -> None:
    """Deprecated shim: ``KernelRuntime.install(policy)`` on the current runtime."""
    _warn_global("set_kernel_policy", "KernelRuntime.install(policy)")
    current_runtime().install(policy)


def get_kernel_policy() -> KernelPolicy | None:
    return current_runtime().policy()


def set_kernel_policy_for_device(device: str, policy: KernelPolicy | None) -> None:
    """Deprecated shim: ``KernelRuntime.install_for_device(device, policy)``."""
    _warn_global(
        "set_kernel_policy_for_device", "KernelRuntime.install_for_device(device, policy)"
    )
    current_runtime().install_for_device(device, policy)


def device_policies() -> dict[str, KernelPolicy]:
    """Registered per-device policies of the current runtime (name -> policy)."""
    return current_runtime().device_policies()


def active_device() -> str | None:
    """Canonical name of the current runtime's live registered device."""
    return current_runtime().active_device()


def device_resolution() -> tuple[str | None, str | None]:
    """(requested, resolved) device names from the last device activation."""
    return current_runtime().device_resolution()


def activate_device(device: str | None = None, *, strict: bool = False) -> str:
    """Deprecated shim: ``KernelRuntime.activate_device(device)``."""
    _warn_global("activate_device", "KernelRuntime.activate_device(device)")
    return current_runtime().activate_device(device, strict=strict)


def clear_device_policies() -> None:
    """Deprecated shim: ``KernelRuntime.clear_device_policies()``."""
    _warn_global("clear_device_policies", "KernelRuntime.clear_device_policies()")
    current_runtime().clear_device_policies()


def set_pallas_enabled(enabled: bool, *, interpret: bool = False) -> None:
    """Deprecated shim: ``KernelRuntime.set_pallas_enabled(enabled)``."""
    _warn_global("set_pallas_enabled", "KernelRuntime.set_pallas_enabled(enabled)")
    current_runtime().set_pallas_enabled(enabled, interpret=interpret)


def set_selection_logging(enabled: bool, *, cap: int | None = None) -> None:
    """Deprecated shim: ``KernelRuntime.set_selection_logging(enabled)``."""
    _warn_global("set_selection_logging", "KernelRuntime.set_selection_logging(enabled)")
    current_runtime().set_selection_logging(enabled, cap=cap)


def selection_logging_enabled() -> bool:
    return current_runtime().selection_logging_enabled()


def selection_log() -> list[tuple]:
    """Trace-time dispatch decisions of the current runtime (op, problem, config)."""
    return current_runtime().selection_log()


def clear_selection_log() -> None:
    """Deprecated shim: ``KernelRuntime.clear_selection_log()``."""
    _warn_global("clear_selection_log", "KernelRuntime.clear_selection_log()")
    current_runtime().clear_selection_log()


def clear_shape_cache() -> None:
    """Deprecated shim: ``KernelRuntime.clear_shape_cache()``."""
    _warn_global("clear_shape_cache", "KernelRuntime.clear_shape_cache()")
    current_runtime().clear_shape_cache()


def set_shape_cache_cap(cap: int) -> None:
    """Deprecated shim: ``KernelRuntime.set_shape_cache_cap(cap)``."""
    _warn_global("set_shape_cache_cap", "KernelRuntime.set_shape_cache_cap(cap)")
    current_runtime().set_shape_cache_cap(cap)


def shape_cache_stats() -> dict:
    """Dispatch shape-cache counters of the current runtime (this thread)."""
    return current_runtime().shape_cache_stats()


def policy_epoch() -> int:
    """Policy epoch of the current runtime (swap observability)."""
    return current_runtime().policy_epoch()


# ---------------------------------------------------------------------------
# launcher-side selection (used by the ops below; also callable directly)
# ---------------------------------------------------------------------------
def select_kernel_config(family: str, problem: tuple):
    """Generic launcher-side selection against the current runtime.

    Shape-memoized under the family-qualified key, logged to the runtime's
    selection log as ``(family, problem, config)``; ``None`` when no policy
    is installed or the policy does not cover this family.
    """
    return current_runtime().select_config(family, problem)


def select_matmul_config(m: int, k: int, n: int, batch: int = 1) -> MatmulConfig | None:
    """The launcher-side selection path on its own (what ``matmul`` runs at
    trace time); ``None`` when no policy is installed."""
    return current_runtime().select_matmul_config(m, k, n, batch)


def select_wkv_config(s: int, hd: int) -> WkvConfig | None:
    """Launcher-side WKV selection (what ``wkv`` runs at trace time)."""
    return current_runtime().select_wkv_config(s, hd)


def select_ssm_config(s: int, d: int) -> SsmConfig | None:
    """Launcher-side selective-scan selection (what ``ssm_scan`` runs)."""
    return current_runtime().select_ssm_config(s, d)


# ---------------------------------------------------------------------------
# guarded execution (DESIGN.md §11: fault containment at the dispatch site)
# ---------------------------------------------------------------------------
_TRACER = getattr(jax.core, "Tracer", None)


def _raise_non_finite(family: str, out) -> None:
    """Raise :class:`NonFiniteOutputError` if a concrete output leaf has NaN/Inf.

    Tracer leaves are skipped — inside a ``jit`` trace there is no value to
    inspect (validation then happens on the eager/chaos path, which is where
    fault plans run).
    """
    leaves = out if isinstance(out, tuple) else (out,)
    for leaf in leaves:
        if _TRACER is not None and isinstance(leaf, _TRACER):
            return
        if not hasattr(leaf, "dtype") or not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        if not bool(jnp.isfinite(leaf).all()):
            raise NonFiniteOutputError(f"{family} kernel call produced non-finite output")


def _guarded_call(rt, family: str, config, run_tuned, run_ref):
    """Execute one kernel call under the fault guard.

    Happy-path cost is one try frame plus two attribute checks (the perf gate
    bounds it at <5%, ``guarded_dispatch_overhead`` in bench_selection).  On
    an injected or real compile/lowering/runtime failure — or a non-finite
    concrete output while validation is armed — the guard records the
    incident, quarantines ``(device, family, config)`` behind the runtime's
    circuit breaker, and re-runs the reference path (which a second failure
    would escape from: a broken oracle is a caller-visible bug, not a
    containment case).  A successful run of a half-open breaker's probe
    config closes the breaker (absolve).
    """
    plan = rt.fault_plan
    try:
        spec = None
        if plan is not None:
            key = config.name() if config is not None and hasattr(config, "name") else ""
            spec = plan.raise_if(f"dispatch.{family}", key)
        out = run_tuned()
        if spec is not None and spec.kind in ("nan", "inf"):
            out = FaultPlan.corrupt_array(spec, out)
        if plan is not None or rt._validate_outputs:
            _raise_non_finite(family, out)
        if spec is not None and spec.kind == "latency":
            rt.record_incident(incident(
                f"dispatch.{family}", family, config, "injected latency spike",
                "latency_spike", device=rt.active_device()))
        if config is not None and rt._quarantine and rt.probing(family, config):
            rt.absolve(family, config)
            rt.record_incident(incident(
                f"dispatch.{family}", family, config, "re-probe succeeded",
                "absolved", device=rt.active_device()))
        return out
    except GUARDED_EXCEPTIONS as e:
        if config is not None:
            rt.quarantine_config(family, config, e)
        rt.record_incident(incident(
            f"dispatch.{family}", family, config, e,
            "quarantined" if config is not None else "fallback_ref",
            device=rt.active_device()))
        return run_ref()


def _scoped(family: str):
    """Trace a dispatch entry under ``ops.<family>``: the ops it emits carry
    that scope in their metadata, so a device trace tells the families apart."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(f"ops.{family}"):
                return fn(*args, **kwargs)

        return scoped

    return wrap


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------
@_scoped("matmul")
def matmul(lhs: jax.Array, rhs: jax.Array, *, out_dtype=None, config: MatmulConfig | None = None) -> jax.Array:
    """``lhs @ rhs`` with ML-guided kernel selection.

    ``lhs``: (..., k) — leading dims are flattened into the GEMM M dimension.
    ``rhs``: (k, n).
    """
    if rhs.ndim != 2:
        raise ValueError(f"rhs must be 2-D, got {rhs.shape}")
    rt = current_runtime()
    *lead, k = lhs.shape
    n = rhs.shape[1]
    # Featurize with the tuning dataset's (m, k, n, batch) convention: the
    # trailing lead dim is the GEMM M, everything before it is the repeated
    # batch — a (B, S, D) activation is B GEMMs of (S, D), not one (B*S, D).
    m = lead[-1] if lead else 1
    batch = 1
    for d in lead[:-1]:
        batch *= d
    if config is None:
        config = rt.select_matmul_config(m, k, n, batch)
    run_ref = lambda: jnp.dot(lhs, rhs, preferred_element_type=jnp.float32).astype(
        out_dtype or lhs.dtype
    )
    if rt.kernel_path("matmul") == "xla":
        return _guarded_call(rt, "matmul", config, run_ref, run_ref)
    lhs2 = lhs.reshape(m * batch, k)
    run_tuned = lambda: matmul_pallas(
        lhs2, rhs, config or DEFAULT_CONFIG, out_dtype=out_dtype, interpret=rt.interpret
    ).reshape(*lead, n)
    return _guarded_call(rt, "matmul", config, run_tuned, run_ref)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
@_scoped("attention")
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    config: AttentionConfig | None = None,
) -> jax.Array:
    """Multi-head attention: q (..., sq, d), k/v (..., skv, d).

    Leading dims (batch, heads) are vmapped over the single-head kernel.
    """
    sq, d = q.shape[-2:]
    skv = k.shape[-2]
    rt = current_runtime()
    if config is None:
        config = rt.select_attention_config(sq, skv, d)

    def _apply(fn):
        for _ in range(q.ndim - 2):
            fn = jax.vmap(fn)
        return fn(q, k, v)

    ref_fn = lambda q_, k_, v_: flash_attention_ref(q_, k_, v_, causal=causal, scale=scale)
    if rt.kernel_path("attention") == "xla":
        run_ref = lambda: _apply(ref_fn)
        return _guarded_call(rt, "attention", config, run_ref, run_ref)
    cfg = config or DEFAULT_ATTN_CONFIG
    tuned_fn = lambda q_, k_, v_: flash_attention_pallas(
        q_, k_, v_, cfg, causal=causal, scale=scale, interpret=rt.interpret
    )
    return _guarded_call(
        rt, "attention", config, lambda: _apply(tuned_fn), lambda: _apply(ref_fn)
    )


# ---------------------------------------------------------------------------
# wkv (RWKV6 recurrence)
# ---------------------------------------------------------------------------
@_scoped("wkv")
def wkv(r, k, v, logw, u, state=None, *, config: WkvConfig | None = None):
    """Chunked WKV: r/k/v/logw (B, S, H, hd); u (H, hd); state (B, H, hd, hd).

    Returns (o (B, S, H, hd) f32, final_state).  Dispatches to the Pallas
    kernel where it runs (``KernelRuntime.kernel_path``); otherwise the jnp
    reference (identical math).
    """
    b, s, h, hd = r.shape
    rt = current_runtime()
    if config is None:
        config = rt.select_wkv_config(s, hd)
    from .ref import wkv_ref

    run_ref = lambda: wkv_ref(r, k, v, logw, u, state)
    if rt.kernel_path("wkv") == "xla":
        return _guarded_call(rt, "wkv", config, run_ref, run_ref)
    if state is None:
        import jax.numpy as _jnp

        state = _jnp.zeros((b, h, hd, hd), _jnp.float32)
    cfg = config or DEFAULT_WKV_CONFIG

    def run_tuned():
        one = lambda rr, kk, vv, ww, uu, ss: wkv_pallas(
            rr, kk, vv, ww, uu, ss, cfg, interpret=rt.interpret
        )
        fn = jax.vmap(jax.vmap(one, in_axes=(1, 1, 1, 1, 0, 0)), in_axes=(0, 0, 0, 0, None, 0))
        o, s_out = fn(r, k, v, logw, u, state)
        return o.transpose(0, 2, 1, 3), s_out  # (B,H,S,hd) -> (B,S,H,hd)

    return _guarded_call(rt, "wkv", config, run_tuned, run_ref)


# ---------------------------------------------------------------------------
# selective-SSM scan (Mamba / Hymba recurrence)
# ---------------------------------------------------------------------------
@_scoped("ssm_scan")
def ssm_scan(dtx, dta, b, v_c, state=None, *, config: SsmConfig | None = None):
    """Fused selective-SSM scan: dtx (B,S,d); dta (B,S,d,N); b/v_c (B,S,N).

    Returns (y (B,S,d) f32, final_state (B,d,N) f32).  Pallas path keeps the
    (d, N) state in VMEM (no (B,S,d,N) HBM materialization); jnp path is the
    associative-scan oracle.
    """
    rt = current_runtime()
    if config is None:
        config = rt.select_ssm_config(dtx.shape[1], dtx.shape[2])
    from .ref import ssm_scan_ref

    run_ref = lambda: ssm_scan_ref(dtx, dta, b, v_c, state)
    if rt.kernel_path("ssm_scan") == "xla":
        return _guarded_call(rt, "ssm_scan", config, run_ref, run_ref)
    cfg = config or DEFAULT_SSM_CONFIG
    if state is None:
        import jax.numpy as _jnp

        bsz, _, d = dtx.shape
        state = _jnp.zeros((bsz, d, b.shape[-1]), _jnp.float32)

    def run_tuned():
        one = lambda x_, a_, b_, c_, s_: ssm_scan_pallas(
            x_, a_, b_, c_, s_, cfg, interpret=rt.interpret
        )
        return jax.vmap(one)(dtx, dta, b, v_c, state)

    return _guarded_call(rt, "ssm_scan", config, run_tuned, run_ref)
