"""`KernelRuntime`: an explicit, multi-tenant runtime handle (DESIGN.md §10).

The paper's pipeline assumes one library instance per process, and until this
module the reproduction inherited that: a process-global registry in
``repro.kernels.ops`` mutated by ~20 module-level functions.  Production
serving needs *isolated, concurrently-active tunings* — A/B shadow policies,
per-tenant deployments, test isolation without ``clear_*`` teardown
choreography.  Following the model-driven-adaptive-libraries framing
(selection state as a first-class library object, not ambient process state),
everything that used to be global now lives on a :class:`KernelRuntime`:

  * the per-device policy registry + activation/epoch state (hot-swap unit);
  * per-thread shape-memoization caches and their counters;
  * the selection log (telemetry source of the continuous tuning loop);
  * the Pallas dispatch path (taken from the platform; tests may override).

Scoping: ``with rt.activate(): ...`` makes ``rt`` the innermost active
runtime for the *current thread*; ``repro.kernels.ops`` dispatch
(``matmul`` / ``attention`` / ``wkv`` / ``ssm_scan`` and the
``select_*_config`` helpers) consults :func:`current_runtime`.  With nothing
activated, the process-wide :func:`default_runtime` serves — which is exactly
what the legacy module-level API in ``repro.kernels.ops`` now shims over, so
old code keeps producing byte-identical selections.

The whole lifecycle reads as four lines through the facade::

    bundle = repro.tune(["granite-8b"], devices=("tpu_v5e",))
    rt = bundle.runtime(device="tpu_v5e")
    engine = rt.serve(model, params)
    engine.run(requests)

Thread model: one runtime may serve many threads (its registry mutations are
lock+epoch protected and its dispatch caches are per-thread, exactly like the
old global state), and many runtimes may serve one process (each thread picks
its runtime via activation).  Two engines with different runtimes on
different threads share nothing: no policy, shape-cache, or selection-log
cross-talk.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
from collections import OrderedDict, deque

DEFAULT_LOG_CAP = 4096
DEFAULT_SHAPE_CACHE_CAP = 1024
DEFAULT_INCIDENT_CAP = 256
DEFAULT_SWAP_HISTORY = 4
# Circuit breaker re-probe cadence, counted in selections of the quarantined
# config: first re-probe after QUARANTINE_BACKOFF skipped selections, doubling
# per failed probe up to the cap.
QUARANTINE_BACKOFF = 4
QUARANTINE_MAX_BACKOFF = 256

_MISS = object()


@dataclasses.dataclass(frozen=True)
class Objective:
    """A serving objective consulted at kernel-selection time.

    ``latency_target_ms`` is a per-token SLO: while an objective with a
    target is installed (:meth:`KernelRuntime.set_objective`), selection
    routes through the policy's ``select_for_objective(family, problem,
    objective)`` — typically trading peak throughput for predicted latency
    (e.g. the analytic-model-fastest deployed config instead of the
    classifier's throughput pick, or pausing online exploration).  Policies
    without ``select_for_objective`` are unaffected.

    ``prefill_chunk_tokens`` is a work-granularity hint set alongside the
    latency target by SLO-mode serving engines: it caps how many prompt
    tokens one prefill chunk may cover, so deadline pressure shrinks the
    unit of prefill work interleaved between decode rounds (DESIGN.md §13).
    Kernel policies may consult it to prefer configs tuned at the chunk's
    GEMM shapes; the serving scheduler enforces it as the admission budget.
    """

    latency_target_ms: float | None = None
    prefill_chunk_tokens: int | None = None

    def __bool__(self) -> bool:
        return (self.latency_target_ms is not None
                or self.prefill_chunk_tokens is not None)


class _RuntimeLocal(threading.local):
    """One thread's dispatch fast path *within one runtime*.

    ``family_stats`` tracks hit/miss per kernel family — cache keys are
    family-qualified (``(op, *problem)``) so an ssm ``(s, d)`` problem can
    never alias a matmul ``(m, k)`` tuple.  ``hook_cache`` memoizes the
    resolved policy hook per family; it depends only on the live policy, so
    it lives and dies with the shape cache (epoch sync).
    """

    def __init__(self):
        self.epoch: int = -1  # never matches: first dispatch syncs
        self.policy = None
        self.shape_cache: OrderedDict[tuple, object] = OrderedDict()
        self.shape_cache_cap: int = DEFAULT_SHAPE_CACHE_CAP
        self.cache_hits: int = 0
        self.cache_misses: int = 0
        self.family_stats: dict[str, list] = {}  # op -> [hits, misses]
        self.hook_cache: dict[str, object] = {}


_runtime_ids = itertools.count(1)


class KernelRuntime:
    """Explicit owner of kernel-selection state (policies, caches, telemetry).

    Construct one per tenant / deployment / test; or use
    :func:`default_runtime` (what the legacy ``repro.kernels.ops`` module
    functions mutate).  All registry mutations are atomic under the runtime's
    lock with an epoch bump; dispatching threads re-sync lazily on their next
    selection, so a cached config from an old policy can never be served as
    if the new policy had chosen it (the DESIGN.md §8 hot-swap contract,
    unchanged — just per-runtime now).
    """

    def __init__(self, name: str | None = None):
        self.name = name or f"runtime-{next(_runtime_ids)}"
        self._lock = threading.RLock()
        self._epoch: int = 0
        self._policy = None
        self._device_policies: dict[str, object] = {}
        self._active_device: str | None = None
        self._requested_device: str | None = None
        self._use_pallas: bool | None = None  # None: ask the platform on first use
        self.interpret: bool = False
        self._log_enabled: bool = False
        self._selection_log: deque[tuple] = deque(maxlen=DEFAULT_LOG_CAP)
        self._shape_cache_cap: int = DEFAULT_SHAPE_CACHE_CAP
        self._local = _RuntimeLocal()
        # -- failure containment (DESIGN.md §11) --
        self.fault_plan = None  # repro.core.faults.FaultPlan, or None
        self._validate_outputs: bool = False
        self._quarantine: dict[tuple[str, str], dict] = {}
        self._incidents: deque[dict] = deque(maxlen=DEFAULT_INCIDENT_CAP)
        self._incident_count: int = 0
        self._swap_history: deque[tuple[str, object, int]] = deque(
            maxlen=DEFAULT_SWAP_HISTORY
        )
        # -- SLO-aware selection (serving tier) --
        self._objective: Objective | None = None

    def __repr__(self) -> str:
        with self._lock:
            return (
                f"KernelRuntime({self.name!r}, active_device={self._active_device!r}, "
                f"devices={sorted(self._device_policies)}, epoch={self._epoch})"
            )

    # -- scoping --------------------------------------------------------------
    def activate(self) -> "_Activation":
        """Context manager making this the innermost active runtime.

        Per-thread and reentrant: ``with rt.activate():`` pushes ``rt`` onto
        the calling thread's activation stack, so ops-layer dispatch inside
        the block consults ``rt`` — other threads are unaffected.  (Not to be
        confused with :meth:`activate_device`, which picks which *registered
        per-device policy* is live inside this runtime.)
        """
        return _Activation(self)

    # -- policy installation ---------------------------------------------------
    def install(self, policy) -> None:
        """Install ``policy`` directly (manual single-device path).

        Clears the active-device marker: a manually installed policy is not
        tied to the registry, so later :meth:`install_for_device` calls won't
        silently replace it.
        """
        with self._lock:
            self._policy = policy
            self._active_device = None
            self._requested_device = None
            self._epoch += 1
        self.clear_shape_cache()

    def policy(self):
        """The live policy, syncing this thread's view of a hot swap."""
        return self._sync()

    def install_for_device(self, device: str, policy) -> None:
        """Register (or with ``None``, drop) the policy tuned for one device.

        Registration alone activates nothing; :meth:`activate_device` picks
        which registered policy serves.  If ``device`` is the currently
        active one, the live policy is refreshed in place — the zero-downtime
        hot-swap primitive the retune loop uses.
        """
        from .devices import canonical_device_name

        name = canonical_device_name(device)
        with self._lock:
            if policy is None:
                self._device_policies.pop(name, None)
                if name == self._active_device:
                    # Dropping the live policy deactivates it — a stale marker
                    # would report an active device while dispatch runs unpoliced.
                    self._policy = None
                    self._active_device = None
                    self._requested_device = None
                    self._epoch += 1
            else:
                prev = self._device_policies.get(name)
                if prev is not None and prev is not policy:
                    # Bounded swap history: rollback_device() restores the
                    # most recent predecessor after a regressing hot-swap.
                    self._swap_history.append((name, prev, self._epoch))
                self._device_policies[name] = policy
                if name == self._active_device:
                    self._policy = policy
                    self._epoch += 1
        # No explicit cache clear: the epoch bump (live-device cases only)
        # makes every dispatching thread drop its shape cache on its next
        # selection; registering an inactive device leaves warm caches alone.

    def device_policies(self) -> dict[str, object]:
        """Snapshot of the registered per-device policies (name -> policy)."""
        with self._lock:
            return dict(self._device_policies)

    # -- serving objective ----------------------------------------------------
    def set_objective(self, objective: Objective | None) -> None:
        """Install (or with ``None``/empty, clear) the serving objective.

        Epoch-bumped like a policy swap: every dispatching thread drops its
        shape and hook caches on its next selection, so objective-aware
        selections never serve from (or pollute) the unconstrained cache.
        The serving engine drives this from its SLO pressure loop; the
        objective applies runtime-wide — one engine per runtime (the router
        layout) keeps tenants isolated.
        """
        if objective is not None and not objective:
            objective = None
        with self._lock:
            if objective == self._objective:
                return
            self._objective = objective
            self._epoch += 1
        self.clear_shape_cache()

    def objective(self) -> Objective | None:
        """The live serving objective (``None`` when unconstrained)."""
        return self._objective

    def active_device(self) -> str | None:
        """Canonical name of the device whose registered policy is live."""
        return self._active_device

    def device_resolution(self) -> tuple[str | None, str | None]:
        """(requested, resolved) device names from the last activation."""
        with self._lock:
            return (self._requested_device, self._active_device)

    def activate_device(self, device: str | None = None, *, strict: bool = False) -> str:
        """Make the registered policy for ``device`` the live one.

        ``device=None`` detects the host (``REPRO_DEVICE`` override first).
        An unregistered device resolves to the nearest registered sibling via
        ``repro.core.devices.resolve_device``; ``strict=True`` raises instead
        of crossing platform families.  Returns the resolved canonical name.
        """
        from .devices import canonical_device_name, detect_device, resolve_device

        requested = canonical_device_name(device) if device is not None else detect_device()
        with self._lock:
            resolved = resolve_device(requested, list(self._device_policies), strict=strict)
            if resolved is None:
                raise KeyError(
                    f"no kernel policy registered for device {requested!r} "
                    f"(registered: {sorted(self._device_policies)})"
                )
            self._policy = self._device_policies[resolved]
            self._active_device = resolved
            self._requested_device = requested
            self._epoch += 1
        self.clear_shape_cache()
        return resolved

    def clear_device_policies(self) -> None:
        """Drop every registered per-device policy, deactivating the live one.

        A policy activated from the registry is uninstalled with it (the
        marker and the live policy must never disagree); a policy installed
        manually via :meth:`install` is not registry-owned and survives.
        """
        with self._lock:
            self._device_policies.clear()
            if self._active_device is not None:
                self._policy = None
            self._active_device = None
            self._requested_device = None
            self._epoch += 1
        self.clear_shape_cache()

    def install_bundle(self, bundle, device: str | None = None, *, strict: bool = False):
        """Install a :class:`~repro.core.bundle.DeploymentBundle` (or path).

        The bundle's policies become this runtime's registry (replacing any
        previous registrations — installing a bundle is authoritative) and
        the one resolved for ``device`` (default: detected host) activates.
        Returns the activated ``Deployment``.
        """
        from .bundle import DeploymentBundle
        from .devices import canonical_device_name, detect_device

        if not isinstance(bundle, DeploymentBundle):
            bundle = DeploymentBundle.load(bundle)
        requested = canonical_device_name(device) if device else detect_device()
        # Resolve (and raise under strict) before touching the live registry.
        bundle.deployment_for(requested, strict=strict)
        self.clear_device_policies()
        for name, dep in bundle.deployments.items():
            self.install_for_device(name, dep)
        resolved = self.activate_device(requested, strict=strict)
        return bundle.deployments[resolved]

    def apply_policy_update(self, deployment, device: str | None = None) -> str | None:
        """Adopt a control-plane-pushed deployment (subscription client path).

        The engine-less counterpart of ``ServingEngine.adopt_deployment``: a
        :class:`repro.control.PolicySubscriber` attached directly to a
        runtime (a trainer, a batch job — anything dispatching without a
        serving engine) lands pushed artifacts here.  ``device=None`` targets
        the currently active device; the update goes through
        :meth:`install_for_device`, so when the target is live this is the
        same lock+epoch hot-swap the retune loop uses (every dispatching
        thread drops its shape cache on its next selection).  With no target
        device at all the policy installs directly.  Returns the canonical
        device name the update landed on (``None`` for a direct install).
        """
        from .devices import canonical_device_name

        target = canonical_device_name(device) if device is not None else self.active_device()
        if target is None:
            self.install(deployment)
            return None
        self.install_for_device(target, deployment)
        if self.active_device() is None:
            self.activate_device(target)
        return target

    # -- pallas dispatch ---------------------------------------------------------
    @property
    def use_pallas(self) -> bool:
        """Whether ops dispatch to the Pallas kernels.

        Taken from the platform: compiled Pallas on a TPU backend, the XLA
        reference everywhere else.  :meth:`set_pallas_enabled` overrides it
        (kernel tests run the interpreter on CPU; a reference runtime on a
        TPU turns Pallas off).
        """
        if self._use_pallas is None:
            import jax

            self._use_pallas = jax.default_backend() == "tpu"
        return self._use_pallas

    def set_pallas_enabled(self, enabled: bool, *, interpret: bool = False) -> None:
        """Override the platform's kernel path (``interpret=True`` on CPU)."""
        self._use_pallas = enabled
        self.interpret = interpret

    def kernel_path(self, family: str) -> str:
        """``"pallas"`` if ``family`` dispatches to its Pallas kernel here,
        else ``"xla"`` (its reference).

        A family whose kernel the TPU compiler refuses
        (``KernelFamily.tpu_refusal``) runs its reference when compiled;
        the interpreter still runs its kernel.
        """
        if not self.use_pallas:
            return "xla"
        if self.interpret:
            return "pallas"
        from .families import get_family

        return "xla" if get_family(family).tpu_refusal else "pallas"

    # -- failure containment (DESIGN.md §11) -----------------------------------
    def set_fault_plan(self, plan) -> None:
        """Attach (or with ``None``, detach) a chaos-injection plan.

        An attached plan arms the ops-layer guard's injection sites *and* its
        non-finite output validation — injected NaN/Inf must be caught, and a
        chaos run should exercise the same validation a hardened production
        deployment would enable via :meth:`set_output_validation`.
        """
        self.fault_plan = plan

    def set_output_validation(self, enabled: bool) -> None:
        """Opt dispatch into checking kernel outputs for NaN/Inf.

        Only concrete (non-tracer) outputs are checked — inside a ``jit``
        trace there is nothing to inspect.  Always on while a fault plan is
        attached.
        """
        self._validate_outputs = bool(enabled)

    def output_validation_enabled(self) -> bool:
        return self._validate_outputs or self.fault_plan is not None

    def record_incident(self, rec: dict) -> dict:
        """Append one structured incident (see ``repro.core.faults.incident``).

        Stamps the monotonic incident sequence number; the bounded deque
        keeps the newest :data:`DEFAULT_INCIDENT_CAP` records while
        :meth:`incident_count` keeps counting — the engine's health watchdog
        compares counts, not buffer lengths.
        """
        with self._lock:
            self._incident_count += 1
            rec = dict(rec, seq=self._incident_count)
            self._incidents.append(rec)
        return rec

    def incidents(self) -> list[dict]:
        """Newest-last snapshot of recorded dispatch/serving incidents."""
        with self._lock:
            return list(self._incidents)

    def incident_count(self) -> int:
        """Monotonic count of incidents ever recorded on this runtime."""
        return self._incident_count

    def quarantine_config(self, family: str, config, error=None) -> dict:
        """Open (or re-open) the circuit breaker for ``(device, family, config)``.

        While open, selections that would serve ``config`` are redirected to
        the family default; every :data:`QUARANTINE_BACKOFF` (doubling per
        failed re-probe, capped at :data:`QUARANTINE_MAX_BACKOFF`) redirected
        selections the breaker goes half-open and serves the quarantined
        config once so the guard can re-probe it.  The epoch bump makes every
        dispatching thread drop its shape cache on its next selection — a
        cached entry from before the quarantine can never be served after it.
        """
        name = config.name() if hasattr(config, "name") and callable(config.name) else str(config)
        with self._lock:
            entry = self._quarantine.get((family, name))
            if entry is None:
                entry = {
                    "family": family,
                    "config": config,
                    "name": name,
                    "device": self._active_device,
                    "failures": 0,
                    "backoff": QUARANTINE_BACKOFF,
                    "countdown": QUARANTINE_BACKOFF,
                    "skipped": 0,
                    "probes": 0,
                    "state": "open",
                    "error": None,
                }
                self._quarantine[(family, name)] = entry
            else:
                entry["backoff"] = min(entry["backoff"] * 2, QUARANTINE_MAX_BACKOFF)
                entry["countdown"] = entry["backoff"]
                entry["state"] = "open"
            entry["failures"] += 1
            if error is not None:
                entry["error"] = f"{type(error).__name__}: {error}" if isinstance(
                    error, BaseException) else str(error)
            self._epoch += 1
        self.clear_shape_cache()
        return dict(entry)

    def absolve(self, family: str, config) -> bool:
        """Close the breaker after a successful re-probe (config healthy again)."""
        name = config.name() if hasattr(config, "name") and callable(config.name) else str(config)
        with self._lock:
            entry = self._quarantine.pop((family, name), None)
            if entry is not None:
                self._epoch += 1
        if entry is not None:
            self.clear_shape_cache()
        return entry is not None

    def quarantined(self) -> list[dict]:
        """Snapshot of open/half-open breaker entries (shallow copies)."""
        with self._lock:
            return [dict(e) for e in self._quarantine.values()]

    def _apply_quarantine(self, family: str, cfg):
        """Selection-time breaker: redirect a quarantined config, or probe it.

        Called only when the quarantine table is non-empty (the happy path
        pays one falsy-dict check).  Counting happens per *selection*, so a
        shape-cache hit still advances the re-probe countdown — the breaker
        sits after the cache, on the served value.
        """
        if cfg is None:
            return cfg
        name = cfg.name() if hasattr(cfg, "name") and callable(cfg.name) else str(cfg)
        with self._lock:
            entry = self._quarantine.get((family, name))
            if entry is None:
                return cfg
            if entry["device"] not in (None, self._active_device):
                return cfg
            entry["countdown"] -= 1
            if entry["countdown"] <= 0:
                # Half-open: serve the quarantined config once as a probe.
                # The countdown resets immediately so an unexecuted selection
                # (launcher-side select_* with no kernel run) cannot wedge
                # the breaker in half-open.
                entry["countdown"] = entry["backoff"]
                entry["probes"] += 1
                entry["state"] = "half_open"
                return cfg
            entry["skipped"] += 1
            entry["state"] = "open"
        from .families import get_family

        fallback = get_family(family).default_config
        return fallback if fallback is not None else cfg

    def probing(self, family: str, config) -> bool:
        """True when ``config`` is a half-open breaker's live probe."""
        name = config.name() if hasattr(config, "name") and callable(config.name) else str(config)
        with self._lock:
            entry = self._quarantine.get((family, name))
            return entry is not None and entry["state"] == "half_open"

    def swap_history(self) -> list[tuple[str, object, int]]:
        """Bounded (device, previous_policy, epoch) history of hot-swaps."""
        with self._lock:
            return list(self._swap_history)

    def rollback_device(self, device: str | None = None):
        """Reinstall the most recent pre-swap policy for ``device``.

        The auto-rollback path for an installed-but-regressing retune: pops
        the newest swap-history entry for the device (default: the active
        one) and restores it, with the usual epoch bump when the device is
        live.  Returns the restored policy, or ``None`` with no history.
        """
        from .devices import canonical_device_name

        name = canonical_device_name(device) if device else self._active_device
        if name is None:
            return None
        with self._lock:
            prev = None
            for i in range(len(self._swap_history) - 1, -1, -1):
                if self._swap_history[i][0] == name:
                    prev = self._swap_history[i][1]
                    del self._swap_history[i]
                    break
            if prev is None:
                return None
            self._device_policies[name] = prev
            if name == self._active_device:
                self._policy = prev
                self._epoch += 1
        return prev

    # -- selection log (telemetry) ---------------------------------------------
    def set_selection_logging(self, enabled: bool, *, cap: int | None = None) -> None:
        """Opt in/out of recording dispatch decisions; ``cap`` bounds the buffer."""
        with self._lock:
            self._log_enabled = enabled
            if cap is not None:
                self._selection_log = deque(self._selection_log, maxlen=max(int(cap), 1))

    def selection_logging_enabled(self) -> bool:
        return self._log_enabled

    def selection_log(self) -> list[tuple]:
        """Trace-time dispatch decisions (op, problem, chosen config).

        Empty unless :meth:`set_selection_logging` opted in; at most the
        newest ``cap`` entries are retained.  The log is runtime-global (not
        per-thread): the retune loop's telemetry reader may run on a
        different thread than the dispatches it observes.
        """
        return list(self._selection_log)

    def clear_selection_log(self) -> None:
        self._selection_log.clear()

    def telemetry(self, online=None):
        """Aggregate this runtime's selection log into a `TelemetrySnapshot`.

        Handle-side spelling of ``TelemetrySnapshot.from_runtime(rt)`` — what
        ``ServingEngine.maybe_retune`` reads each drift-check window.
        """
        from .retune import TelemetrySnapshot

        return TelemetrySnapshot.from_runtime(self, online=online)

    # -- dispatch shape cache --------------------------------------------------
    def policy_epoch(self) -> int:
        """Monotonic counter bumped by every policy mutation (swap observability)."""
        return self._epoch

    def clear_shape_cache(self) -> None:
        """Drop this thread's shape cache (other threads re-sync on epoch bump)."""
        loc = self._local
        loc.shape_cache.clear()
        loc.cache_hits = 0
        loc.cache_misses = 0
        loc.family_stats = {}
        loc.hook_cache = {}

    def set_shape_cache_cap(self, cap: int) -> None:
        """Bound the dispatch cache; oldest (LRU) shape keys are evicted.

        Runtime-level: the calling thread adopts the cap immediately, every
        other thread dispatching against this runtime adopts it at its next
        policy sync (a fresh thread's first selection, or the first selection
        after any epoch bump).
        """
        cap = max(int(cap), 1)
        self._shape_cache_cap = cap
        loc = self._local
        loc.shape_cache_cap = cap
        while len(loc.shape_cache) > cap:
            loc.shape_cache.popitem(last=False)

    def shape_cache_stats(self) -> dict:
        """Hit/miss counters for this thread's dispatch cache (reset on swap).

        ``per_family`` breaks the counters (and resident cache entries) down
        by kernel family — keys are the family-qualified ``op`` names of the
        selection log.
        """
        loc = self._local
        sizes: dict[str, int] = {}
        for key in loc.shape_cache:
            sizes[key[0]] = sizes.get(key[0], 0) + 1
        per_family = {
            op: {"hits": hm[0], "misses": hm[1], "size": sizes.get(op, 0)}
            for op, hm in sorted(loc.family_stats.items())
        }
        for op, size in sorted(sizes.items()):  # entries inherited before any stat
            per_family.setdefault(op, {"hits": 0, "misses": 0, "size": size})
        return {
            "hits": loc.cache_hits,
            "misses": loc.cache_misses,
            "size": len(loc.shape_cache),
            "cap": loc.shape_cache_cap,
            "per_family": per_family,
        }

    # -- selection -------------------------------------------------------------
    def _sync(self):
        """The live policy, after syncing this thread's view of a hot swap.

        The epoch check makes the swap atomic from the dispatcher's side: the
        policy reference and the shape-cache invalidation are taken together
        under the registry lock, so a selection either runs fully against the
        old policy (an in-flight request — fine) or fully against the new one.
        """
        loc = self._local
        if loc.epoch != self._epoch:
            with self._lock:
                loc.policy = self._policy
                loc.epoch = self._epoch
                loc.shape_cache_cap = self._shape_cache_cap
            loc.shape_cache.clear()
            loc.cache_hits = 0
            loc.cache_misses = 0
            loc.family_stats = {}
            loc.hook_cache = {}
        return loc.policy

    def _select(self, op: str, problem: tuple, policy, select_fn):
        """Policy consultation with LRU shape memoization.

        Repeated traces of the same problem shape (the serving engine's
        prefill/decode retraces) hit a dict lookup instead of
        featurize+predict.  Policies whose selections are not a pure function
        of the shape (e.g. the exploring ``OnlinePolicy``) opt out via
        ``cacheable = False``.  ``policy`` is the reference the caller already
        synced — passing it through keeps one selection pinned to one policy
        even if a hot swap lands mid-call.
        """
        loc = self._local
        cacheable = bool(getattr(policy, "cacheable", True))
        key = (op, *problem)
        if cacheable:
            cfg = loc.shape_cache.get(key, _MISS)
            if cfg is not _MISS:
                loc.cache_hits += 1
                loc.family_stats.setdefault(op, [0, 0])[0] += 1
                loc.shape_cache.move_to_end(key)
                if self._quarantine:
                    # Breaker sits after the cache (cache holds the policy's
                    # raw choice): counting per served selection keeps the
                    # re-probe countdown advancing on cache hits too.
                    cfg = self._apply_quarantine(op, cfg)
                if self._log_enabled:
                    self._selection_log.append((op, problem, cfg))
                return cfg
        cfg = select_fn()
        if cacheable:
            loc.cache_misses += 1
            loc.family_stats.setdefault(op, [0, 0])[1] += 1
            loc.shape_cache[key] = cfg
            if len(loc.shape_cache) > loc.shape_cache_cap:
                loc.shape_cache.popitem(last=False)
        if self._quarantine:
            cfg = self._apply_quarantine(op, cfg)
        if self._log_enabled:
            self._selection_log.append((op, problem, cfg))
        return cfg

    def _policy_hook(self, pol, family: str):
        """Resolve the policy's selection callable for ``family``.

        With a serving :class:`Objective` installed and a policy exposing
        ``select_for_objective``, the hook routes through it (SLO-aware
        selection); otherwise the method name comes from the family's
        registry-declared ``policy_attr``, and a policy may instead expose a
        generic ``select(family, problem)``.  Returns a ``hook(problem)``
        callable, or ``None`` when the policy covers none of these (the op
        runs its default config).  Resolution depends only on (policy,
        family, objective) — and an objective change bumps the epoch, which
        drops the per-thread hook cache — so :meth:`select_config` memoizes
        it per thread and the shape-cache fast path never pays registry
        lookup or ``getattr``.
        """
        from .families import get_family

        hook = self._objective_hook(pol, family)
        if hook is not None:
            return hook
        meth = getattr(pol, get_family(family).policy_attr, None)
        if meth is not None:
            return lambda problem: meth(*problem)
        generic = getattr(pol, "select", None)
        if generic is not None:
            return lambda problem: generic(family, problem)
        return None

    def _objective_hook(self, pol, family: str):
        """The SLO-aware selection callable, or None when unconstrained."""
        obj = self._objective
        if obj is None:
            return None
        slo = getattr(pol, "select_for_objective", None)
        if slo is None:
            return None
        return lambda problem: slo(family, problem, obj)

    def select_config(self, family: str, problem: tuple):
        """Generic launcher-side selection for any registered family.

        Shape-memoized under the family-qualified key, recorded in the
        selection log as ``(family, problem, config)``; ``None`` when no
        policy is installed or the policy does not cover this family.
        """
        pol = self._sync()  # drops stale hook/shape caches
        if pol is None:
            return None
        loc = self._local
        hook = loc.hook_cache.get(family, _MISS)
        if hook is _MISS:
            hook = self._policy_hook(pol, family)
            loc.hook_cache[family] = hook
        if hook is None:
            return None
        problem = tuple(problem)
        return self._select(family, problem, pol, lambda: hook(problem))

    def select_matmul_config(self, m: int, k: int, n: int, batch: int = 1):
        """The launcher-side matmul selection path on its own (what
        ``ops.matmul`` runs at trace time); ``None`` with no policy."""
        pol = self._sync()
        if pol is None:
            return None
        hook = self._objective_hook(pol, "matmul")
        if hook is not None:
            return self._select(
                "matmul", (m, k, n, batch), pol, lambda: hook((m, k, n, batch))
            )
        return self._select(
            "matmul", (m, k, n, batch), pol, lambda: pol.select_matmul(m, k, n, batch)
        )

    def select_attention_config(self, sq: int, skv: int, d: int):
        """Launcher-side flash-attention selection (what ``ops.attention`` runs)."""
        pol = self._sync()
        if pol is None:
            return None
        hook = self._objective_hook(pol, "attention")
        if hook is not None:
            return self._select(
                "attention", (sq, skv, d), pol, lambda: hook((sq, skv, d))
            )
        return self._select(
            "attention", (sq, skv, d), pol, lambda: pol.select_attention(sq, skv, d)
        )

    def select_wkv_config(self, s: int, hd: int):
        """Launcher-side WKV selection (what ``ops.wkv`` runs at trace time)."""
        return self.select_config("wkv", (s, hd))

    def select_ssm_config(self, s: int, d: int):
        """Launcher-side selective-scan selection (what ``ops.ssm_scan`` runs)."""
        return self.select_config("ssm_scan", (s, d))

    # -- serving ---------------------------------------------------------------
    def serve(self, model, params, **kwargs):
        """Build a :class:`~repro.serve.engine.ServingEngine` owned by this
        runtime (all its trace-time kernel selections dispatch here)."""
        from repro.serve.engine import ServingEngine

        return ServingEngine(model, params, runtime=self, **kwargs)


class _Activation:
    """``with rt.activate():`` — push/pop on the thread's activation stack."""

    __slots__ = ("runtime",)

    def __init__(self, runtime: KernelRuntime):
        self.runtime = runtime

    def __enter__(self) -> KernelRuntime:
        _active.stack.append(self.runtime)
        return self.runtime

    def __exit__(self, *exc) -> None:
        popped = _active.stack.pop()
        assert popped is self.runtime, "unbalanced KernelRuntime activation"


class _ActiveStack(threading.local):
    def __init__(self):
        self.stack: list[KernelRuntime] = []


_active = _ActiveStack()
_default_lock = threading.Lock()
_default_runtime: KernelRuntime | None = None


def default_runtime() -> KernelRuntime:
    """The process-wide runtime legacy ``repro.kernels.ops`` functions target.

    Created lazily on first use; survives for the process lifetime (or until
    :func:`reset_default_runtime`).
    """
    global _default_runtime
    rt = _default_runtime
    if rt is None:
        with _default_lock:
            rt = _default_runtime
            if rt is None:
                rt = _default_runtime = KernelRuntime(name="default")
    return rt


def reset_default_runtime() -> KernelRuntime:
    """Replace the default runtime with a fresh one (test isolation).

    Threads still dispatching against the old default keep their reference's
    state; new legacy-API calls see the fresh runtime.
    """
    global _default_runtime
    with _default_lock:
        _default_runtime = KernelRuntime(name="default")
        return _default_runtime


def current_runtime() -> KernelRuntime:
    """The innermost runtime activated on this thread, else the default."""
    stack = _active.stack
    return stack[-1] if stack else default_runtime()
