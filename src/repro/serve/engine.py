"""Continuous-batching serving engine over a paged KV cache.

The engine owns ``max_batch`` decode **lanes** backed by a block-allocated
:class:`~repro.serve.kvpool.KVPool` (cache memory scales with live tokens,
not slots), a priority/deadline :class:`~repro.serve.scheduler.Scheduler`
with starvation aging and preemption, and per-width compiled decode programs
(static shapes => a small set of compiled programs reused for the whole
serving lifetime — the same "few deployed kernels" economics as the paper's
library setting; ML-guided kernel selection runs once at trace time per
program).

Serving surface (new code):

    ticket = engine.submit(prompt, max_new_tokens=32,
                           priority=1, latency_target_ms=8.0)
    for tok in ticket.tokens():   # streams; drives engine.step() as needed
        ...
    status = engine.drain()       # run everything submitted to completion

One ``engine.step()`` is one scheduling round: admit waiting requests into
free lanes (prefill, bucketed by padded length), grow each active lane's
block table by one block when decode crosses a block boundary (preempting
the lowest-priority resident back to the wait queue — with block reclaim —
when the pool runs dry), then one batched decode advances all active lanes
at the smallest compiled width bucket that fits.

``latency_target_ms`` threads an SLO into kernel selection: when a targeted
request's recent per-token latency overruns its target, the engine installs
an :class:`~repro.core.runtime.Objective` on its runtime (selection policies
answer ``select_for_objective`` — e.g. a lower-latency kernel config instead
of the throughput pick), caps admission below the current width bucket, and
invalidates compiled programs so the next trace re-selects; it backs off
with hysteresis once targeted lanes run comfortably under target.

``engine.run(requests)`` — the seed batch API — remains as a deprecated
shim over submit/drain with byte-identical outputs.  Per-lane bookkeeping
lives in numpy on the host; tokens and caches stay on device.

**Streaming admission (prefix sharing + chunked prefill, DESIGN.md §13).**
When the pool is paged and the model supports ``prefill_chunk``, admission
switches from the legacy regime (left-padded monolithic prefill, first token
from the prefill's last-position logits) to a *streaming* regime: the
sequence is left-aligned so block content is position-stable, the prompt
body is prefilled in scheduler-budgeted chunks (``prefill_chunk_tokens`` /
``SchedulerConfig.prefill_token_budget``) that interleave with decode
rounds, and the final chunk also covers the last prompt token so its
logits at the last real row yield the first output token (no separate
first-token program).  Left alignment is what makes prefix sharing possible: a new
request whose prompt starts with tokens another lane already cached aliases
those blocks (refcounted, copy-on-write at the first partial block) and
skips prefill for the shared span.  Dense pools (``block_size=None``)
without an explicit chunk budget keep the legacy regime bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import warnings
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core.retune import DEFAULT_DRIFT_THRESHOLD, DEFAULT_MIN_EVENTS

from .kvpool import KVPool
from .scheduler import Scheduler, SchedulerConfig


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_id: int | None = None
    priority: int = 0  # higher admits sooner (scheduler ages waiters up)
    latency_target_ms: float | None = None  # per-token SLO -> kernel selection
    # filled by the engine
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    state: str = "queued"  # queued | active | preempted | done | starved
    truncated_tokens: int = 0  # prompt tokens dropped by sliding-window admit
    retries: int = 0  # kernel-fault retries this request survived
    preemptions: int = 0  # times evicted back to the wait queue
    token_ms: list[float] = dataclasses.field(default_factory=list)  # per-token latency
    routed_to: str | None = None  # engine key a Router dispatched this to


@dataclasses.dataclass(frozen=True)
class RetuneEvent:
    """One firing of the continuous tuning loop (DESIGN.md §8).

    ``swapped`` distinguishes a drift check that triggered a retune + policy
    hot-swap from one that merely looked; ``epoch`` is the engine runtime's
    policy epoch after the swap (monotonic within that runtime).  Drift is checked
    per kernel family: ``families`` names the families whose tunings were
    refreshed by this event (empty when nothing triggered), and
    ``drift_score`` / ``unseen_fraction`` report the worst family observed.
    ``rejected`` names families whose retune candidate failed the canary and
    was never installed; ``rolled_back`` marks the auto-rollback event of a
    previously installed policy that regressed in service (DESIGN.md §11).
    ``source`` records who produced the swapped-in deployment: ``"drift"``
    for the engine's own loop, ``"control-plane"`` (or any caller-supplied
    label) for an externally pushed artifact adopted via
    :meth:`ServingEngine.adopt_deployment`.
    """

    step: int
    drift_score: float
    unseen_fraction: float
    swapped: bool
    triggered: bool  # False + high score means the min-events floor blocked it
    n_events: int
    n_configs: int
    epoch: int
    families: tuple[str, ...] = ()
    rejected: tuple[str, ...] = ()
    rolled_back: bool = False
    source: str = "drift"


@dataclasses.dataclass(frozen=True)
class EngineStatus:
    """What a drain/status snapshot finished (and what it didn't).

    ``exhausted`` means the step budget ran out with work left: ``in_flight``
    requests hold lanes mid-decode, ``queued`` never got one (or lost one and
    were never re-admitted).  Both carry ``done=False`` and a non-``"done"``
    per-request ``state`` — checking ``output`` alone cannot distinguish them
    once prefill has emitted tokens.  A request evicted back to the wait
    queue counts **once**: in live snapshots it moves from ``in_flight`` to
    ``preempted`` (state ``"preempted"``, excluded from ``queued``) and back
    on re-admission, so ``completed + in_flight + queued + preempted``
    partitions the epoch; a drain report instead uses ``preempted`` for how
    many requests were evicted at least once while it served.  ``health`` is
    the engine's serving-health
    state (``"healthy"`` / ``"degraded"``): degraded while dispatch incidents
    are arriving or configs sit in quarantine, healthy again once the window
    is clean.
    """

    completed: int
    in_flight: int
    queued: int
    steps: int
    exhausted: bool
    health: str = "healthy"
    preempted: int = 0
    # -- pool health (observability for the paged/prefix-reuse path) --------
    pool_utilization: float = 0.0  # used / total blocks
    pool_fragmentation: float = 0.0  # 1 - used token slots / allocated slots
    shared_blocks: int = 0  # blocks aliased by more than one lane
    prefix_hits: int = 0  # admissions that reused a cached prefix
    prefix_lookups: int = 0  # admissions that probed the prefix index

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / self.prefix_lookups if self.prefix_lookups else 0.0


@dataclasses.dataclass
class Ticket:
    """Streaming handle for one submitted request.

    ``tokens()`` yields generated tokens as they land, driving
    ``source.step()`` (the engine or router it was submitted to) whenever it
    runs out of buffered output; it stops at EOS/completion, starvation, or
    when the source reports no further progress is possible.
    """

    request: Request
    source: object  # anything with .step() -> bool

    @property
    def done(self) -> bool:
        return self.request.done

    def tokens(self):
        sent = 0
        while True:
            out = self.request.output
            while sent < len(out):
                yield out[sent]
                sent += 1
            if self.request.done or self.request.state == "starved":
                return
            if not self.source.step():
                return

    def result(self) -> list[int]:
        """Block (stepping the source) until done; return the full output."""
        for _ in self.tokens():
            pass
        return list(self.request.output)


def _bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _extend_ladder(buckets: tuple[int, ...], cache_len: int) -> tuple[int, ...]:
    """Extend the prefill bucket ladder geometrically, capped below cache_len.

    A prompt longer than the largest configured bucket used to truncate to
    that bucket even when the cache had room; doubling the ladder up to (but
    excluding) ``cache_len`` keeps long prompts intact while bounding the
    number of compiled prefill programs at O(log cache_len).  ``cache_len``
    itself is excluded so an admitted prompt always leaves decode room.
    """
    out = [int(b) for b in buckets]
    last = out[-1]
    while last * 2 < cache_len:
        last *= 2
        out.append(last)
    return tuple(out)


def _recent_ms(req: Request, k: int = 3) -> float | None:
    if not req.token_ms:
        return None
    xs = req.token_ms[-k:]
    return sum(xs) / len(xs)


class ServingEngine:
    def __init__(
        self,
        model,
        params,
        *,
        max_batch: int = 4,
        cache_len: int = 256,
        prefill_buckets: tuple[int, ...] = (32, 64, 128),
        extra_inputs: dict | None = None,
        bundle=None,
        device: str | None = None,
        runtime=None,
        block_size: int | None = None,
        n_blocks: int | None = None,
        scheduler: SchedulerConfig | None = None,
        prefill_chunk_tokens: int | None = None,
        prefix_sharing: bool = True,
        slo_aware: bool = True,
        slo_patience: int = 4,
        clock=None,
        on_prefill=None,
        on_decode=None,
        on_first_token=None,
        retune_interval: int | None = None,
        drift_threshold: float = DEFAULT_DRIFT_THRESHOLD,
        retune_min_events: int = DEFAULT_MIN_EVENTS,
        canary: bool = True,
        rollback_threshold: int = 3,
        swap_history: int = 4,
    ):
        from repro.core.runtime import current_runtime

        # The engine dispatches against ONE explicit KernelRuntime for its
        # whole lifetime: every prefill/decode trace runs inside
        # ``runtime.activate()``, so two engines with different runtimes (two
        # tenants, an A/B shadow pair) share no policy, shape-cache, or
        # selection-log state even on the same thread.  ``runtime=None``
        # adopts the caller's current runtime (the process default unless the
        # ctor runs inside an activation) — the legacy behavior.
        self.runtime = runtime if runtime is not None else current_runtime()
        # A serving host consumes the multi-device artifact directly: install
        # the Deployment resolved for this host (nearest tuned sibling when
        # untuned) before the first trace-time kernel selection runs.
        self.deployment = None
        self.device = device
        if bundle is not None:
            self.deployment = self.runtime.install_bundle(bundle, device)
            self.device = self.runtime.active_device()
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.prefill_buckets = prefill_buckets
        # Geometric ladder extension (satellite fix): prompts longer than the
        # largest configured bucket bucket into doubled sizes up to cache_len
        # instead of truncating while cache room remains.
        self._ladder = _extend_ladder(tuple(prefill_buckets), cache_len)
        self.extra_inputs = extra_inputs or {}

        # Paged KV storage.  block_size=None keeps the dense layout (one
        # cache_len-sized block per lane) — byte-identical to the seed
        # engine's init_cache(max_batch, cache_len) pool.
        self.pool = KVPool(
            model, lanes=max_batch, cache_len=cache_len,
            block_size=block_size, n_blocks=n_blocks,
        )
        # Streaming admission regime (chunked prefill + prefix sharing):
        # requires a chunk-capable model, no extra prefill inputs, and either
        # an explicit chunk budget or a paged pool with sharing enabled.
        # Everything else keeps the legacy (byte-identical) admission path.
        chunk_capable = (
            hasattr(model, "prefill_chunk")
            and getattr(model, "supports_chunked_prefill", lambda: True)()
            and not self.extra_inputs
        )
        paged = self.pool.block_size < self.cache_len
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self._sharing = bool(prefix_sharing) and paged and chunk_capable
        self._streaming = chunk_capable and (
            prefill_chunk_tokens is not None or self._sharing
        )
        if (
            self._streaming
            and prefill_chunk_tokens is not None
            and (scheduler is None or scheduler.prefill_token_budget is None)
        ):
            # The chunk cap doubles as the default per-step prefill budget.
            scheduler = dataclasses.replace(
                scheduler or SchedulerConfig(),
                prefill_token_budget=int(prefill_chunk_tokens),
            )
        self.scheduler = Scheduler(scheduler)
        self.positions = np.zeros(max_batch, dtype=np.int32)  # next position to write
        self.slots: list[Request | None] = [None] * max_batch
        self.steps = 0
        self._uid = itertools.count()
        self._epoch_requests: list[Request] = []

        # Compiled decode programs, one per width bucket (powers of two up
        # to max_batch): a lone straggler decodes at width 1, a full house
        # at max_batch, without retracing in between.
        buckets, w = [], 1
        while w < max_batch:
            buckets.append(w)
            w *= 2
        buckets.append(max_batch)
        self._width_buckets = tuple(buckets)
        self._decode_cache: dict[int, object] = {}
        self._prefill_cache = {}
        self._chunk_cache: dict[int, object] = {}  # chunk width -> jitted program

        # -- SLO-aware selection ---------------------------------------------
        self.slo_aware = slo_aware
        self.slo_patience = max(int(slo_patience), 1)
        self._prefix_reused_tokens = 0  # prefill tokens skipped via aliasing
        self.slo_events: list[tuple[int, str, float | None]] = []
        self._slo_mode = False
        self._slo_cap: int | None = None
        self._slo_ok = 0
        self._step_ms: deque = deque(maxlen=8)  # recent per-step wall times
        # Injectable clock + hooks let the serving benchmark drive a
        # deterministic simulated timeline; production uses the wall clock.
        self._clock = clock if clock is not None else time.perf_counter
        self.on_prefill = on_prefill
        self.on_decode = on_decode
        # on_first_token(request, logits): the (1, 1, vocab) logits the first
        # output token was taken from (what a correctness check compares).
        self.on_first_token = on_first_token

        # -- continuous tuning loop (DESIGN.md §8) ---------------------------
        self.retune_interval = retune_interval
        self.drift_threshold = drift_threshold
        self.retune_min_events = retune_min_events
        self.retune_events: list[RetuneEvent] = []
        self._last_retune_check = 0
        # -- failure containment (DESIGN.md §11) -----------------------------
        self.canary = canary
        self.rollback_threshold = max(int(rollback_threshold), 1)
        self.health = "healthy"
        self.health_events: list[tuple[int, str]] = []  # (step, new state)
        self._incidents_seen = self.runtime.incident_count()
        # Previous deployments, newest last; maybe_retune pushes the incumbent
        # before installing a candidate, the rollback watchdog pops it.
        self._swap_history: deque = deque(maxlen=max(int(swap_history), 1))
        self._incidents_at_swap: int | None = None
        # Externally offered deployment (control-plane push): staged from any
        # thread via offer_deployment, adopted at the next step boundary so
        # the swap never lands mid-decode.
        self._offer_lock = threading.Lock()
        self._offered: tuple[object, str] | None = None
        if retune_interval is not None:
            # Telemetry source: the runtime's selection log (cache hits
            # included, so the histogram reflects real traffic frequencies).
            self.runtime.set_selection_logging(True)

    @property
    def cache(self):
        """Dense read view of all lanes (seed-engine layout), for inspection."""
        return self.pool.gather(range(self.max_batch))

    def dispatch_stats(self) -> dict:
        """Kernel-selection shape-cache counters (convenience passthrough).

        Each prefill bucket and decode width bucket retrace the model, so
        repeated admissions re-run trace-time kernel selection; the runtime's
        shape cache (DESIGN.md §6) turns those repeats into dict hits.  Note
        the counters are per *thread within the runtime*: call from the
        thread that drives this engine, and expect other engines sharing the
        same runtime on this thread to contribute to the same numbers.
        """
        return self.runtime.shape_cache_stats()

    # -- lane admission -------------------------------------------------------
    def _free_lane(self) -> int | None:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def _prefill_fn(self, plen: int):
        if plen not in self._prefill_cache:
            prefill, cache_len = self.model.prefill, self.cache_len

            def program(params, batch):
                return prefill(params, batch, cache_len)

            self._prefill_cache[plen] = jax.jit(_named(program, f"serve_prefill_{plen}"))
        return self._prefill_cache[plen]

    def _seq_tokens(self, req: Request) -> np.ndarray:
        """Tokens a (re-)admission must prefill: prompt plus anything already
        generated (a preempted request resumes by re-prefilling both — the
        last position's argmax is then exactly the next token it needed)."""
        prompt = np.asarray(req.prompt, dtype=np.int32)
        if req.output:
            return np.concatenate([prompt, np.asarray(req.output, dtype=np.int32)])
        return prompt

    def _fits(self, req: Request) -> bool:
        if self._streaming:
            kept = min(len(self._seq_tokens(req)), self._ladder[-1])
            return self.pool.can_fit(kept)
        plen = _bucket(len(self._seq_tokens(req)), self._ladder)
        return self.pool.can_fit(plen)

    def _admit(self, req: Request, slot: int) -> bool:
        """Admit ``req`` into ``slot``; True if a first token was emitted
        (legacy monolithic prefill), False if the lane entered the
        ``"prefilling"`` state (streaming regime — chunks run via
        :meth:`_advance_prefills` under the scheduler's budget)."""
        if self._streaming:
            self._admit_streaming(req, slot)
            return False
        plen = _bucket(len(self._seq_tokens(req)), self._ladder)
        with trace.span("serve.prefill", request=req.uid, plen=plen):
            tail = self._seq_tokens(req)
            if len(tail) > plen:
                # Sliding-window truncation: a prompt longer than the largest
                # prefill bucket keeps its most recent plen tokens (causal decode
                # conditions on the suffix) instead of raising on the left-pad.
                req.truncated_tokens = len(tail) - plen
                tail = tail[-plen:]
            prompt = np.zeros(plen, dtype=np.int32)
            if len(tail):
                prompt[-len(tail) :] = tail  # left-pad (causal end-aligned)
            batch = {"tokens": jnp.asarray(prompt[None, :])}
            for k, v in self.extra_inputs.items():
                batch[k] = _batch_extra(k, v)
            with self.runtime.activate():  # trace-time selections hit OUR runtime
                logits, cache1 = self._run_program(
                    "engine.prefill", self._prefill_cache, plen,
                    lambda: self._prefill_fn(plen)(self.params, batch),
                    request=req,
                )
            # Back the lane with blocks and scatter the single-sequence prefill
            # cache into them (the lane's previous tenant, if any, is reclaimed).
            self.pool.release(slot)
            if not self.pool.ensure(slot, plen):
                raise RuntimeError(
                    f"admitted request {req.uid} with no blocks for plen={plen}"
                )
            self.pool.admit(slot, cache1)
            self.pool.note_tokens(slot, min(len(tail), plen))
            if self.on_prefill is not None:
                self.on_prefill(plen)
            if self.on_first_token is not None:
                self.on_first_token(req, logits)
            first = int(jnp.argmax(logits[0, -1]))
            req.output.append(first)
            req.state = "active"
            self.slots[slot] = req
            self.positions[slot] = plen
        return True

    # -- streaming admission (chunked prefill + prefix sharing) ---------------
    def _chunk_cap(self) -> int:
        """Largest chunk width one prefill program may cover right now.

        The base cap is the biggest ladder value at or under
        ``prefill_chunk_tokens`` (whole ladder if unset); SLO mode shrinks it
        one ladder rung so deadline pressure reduces the unit of prefill work
        interleaved between decode rounds.
        """
        limit = self.prefill_chunk_tokens
        cap = self._ladder[0]
        for b in self._ladder:
            if limit is None or b <= limit:
                cap = max(cap, b)
        if self._slo_mode:
            below = [b for b in self._ladder if b < cap]
            cap = max(below) if below else self._ladder[0]
        return cap

    def _chunk_fn(self, width: int):
        if width not in self._chunk_cache:
            # jit a fresh closure, not the bound method: jax's trace cache
            # keys on the callable, and equal bound methods would share one
            # trace across engines (and across pop+re-jit after a hot-swap),
            # skipping the trace-time kernel selection that must run under
            # THIS engine's runtime and policy.
            chunk = self.model.prefill_chunk

            def program(params, cache, tokens, start, last):
                return chunk(params, cache, tokens, start, last)

            self._chunk_cache[width] = jax.jit(
                _named(program, f"serve_chunk_w{width}"), donate_argnums=(1,)
            )
        return self._chunk_cache[width]

    def _admit_streaming(self, req: Request, slot: int) -> None:
        """Left-aligned admission: alias any cached prefix, allocate the rest,
        and queue the sequence for budgeted chunked prefill.

        No model program runs here — chunks run in
        :meth:`_advance_prefills` (the final chunk emits the first token),
        so one step's prefill work is bounded by the scheduler's token
        budget no matter how many admissions land.
        """
        seq = self._seq_tokens(req)
        keep = self._ladder[-1]
        if len(seq) > keep:
            # Sliding-window truncation, as in the legacy regime: keep the
            # most recent tokens (causal decode conditions on the suffix).
            req.truncated_tokens = len(seq) - keep
            seq = seq[-keep:]
        body = seq[:-1]
        shared_tokens = 0
        if self._sharing:
            # Atomic match-then-release: the lane's outgoing tenant may itself
            # own the matched blocks (same system prompt re-admitted into its
            # old lane), so the pool reserves them before reclaiming.
            shared_tokens = self.pool.admit_prefix(slot, body)
            self._prefix_reused_tokens += shared_tokens
        else:
            self.pool.release(slot)  # reclaim the lane's previous tenant
        # Lane-kind leaves (recurrent state, rings) must start from zeros:
        # nothing below ever rewrites them wholesale the way pool.admit does.
        self.pool.reset_lane_state(slot)
        if not self.pool.ensure(slot, len(seq)):
            raise RuntimeError(
                f"admitted request {req.uid} with no blocks for {len(seq)} tokens"
            )
        self.pool.note_tokens(slot, len(seq))
        # Chunks cover the FULL sequence: the final chunk's logits at its
        # last real row predict the first output token (legacy parity — no
        # separate first-token program).  Sharing still matches/registers on
        # the body only, so the decode-frontier block stays private (COW).
        req._chunk_tokens = np.asarray(seq, dtype=np.int32)
        req._chunk_pos = shared_tokens
        req._first_logits = None
        req.state = "prefilling"
        self.slots[slot] = req
        self.positions[slot] = len(seq) - 1  # overwritten by the final chunk

    def _run_chunk(self, lane: int, req: Request, width: int) -> None:
        """One chunk-append prefill program over ``[chunk_pos, chunk_pos+width)``."""
        toks = req._chunk_tokens
        s0 = req._chunk_pos
        chunk = np.zeros(width, dtype=np.int32)
        real = toks[s0 : s0 + width]
        chunk[: len(real)] = real
        with trace.span("serve.chunk", request=req.uid, lane=lane, start=int(s0),
                        n_real=len(real), width=width, final=s0 + len(real) >= len(toks)):
            with self.runtime.activate():
                logits, cache = self._run_program(
                    "engine.prefill", self._chunk_cache, width,
                    lambda: self._chunk_fn(width)(
                        self.params,
                        self.pool.gather([lane]),  # re-gathered on retry: donation-safe
                        jnp.asarray(chunk[None, :]),
                        jnp.int32(s0),
                        jnp.int32(len(real) - 1),
                    ),
                    request=req,
                )
            self.pool.scatter([lane], cache)
            if self.on_prefill is not None:
                self.on_prefill(width)
            req._chunk_pos = min(s0 + width, len(toks))
            if req._chunk_pos >= len(toks):
                # Final chunk: its last real row predicts the first output token.
                req._first_logits = logits
            if self._sharing:
                # Index the blocks this chunk completed right away, so siblings
                # admitted while a long prompt is still prefilling can alias the
                # finished span instead of waiting for activation.  Only the
                # body (all but the last token) is ever indexed — the block
                # holding the decode frontier stays private (COW rule).
                self.pool.register_prefix(
                    lane, toks[: min(req._chunk_pos, len(toks) - 1)]
                )

    def _activate_lane(self, lane: int, req: Request) -> None:
        """Sequence fully cached: emit the first token from the final
        chunk's logits and join the batched decode.  No program runs here —
        activation costs nothing beyond the chunks themselves, matching the
        legacy prefill's first-token-from-last-position-logits economics."""
        with trace.span("serve.activate", request=req.uid):
            seq = req._chunk_tokens
            if self._sharing:
                # Index the lane's fully-covered body blocks for future reuse.
                # The block holding the decode frontier is never indexed (COW
                # rule), so shared content is immutable by construction.
                self.pool.register_prefix(lane, seq[:-1])
            if self.on_first_token is not None:
                self.on_first_token(req, req._first_logits)
            first = int(jnp.argmax(req._first_logits[0, -1]))
            req._first_logits = None  # free the device buffer
            req.output.append(first)
            req.state = "active"
            self.positions[lane] = len(seq)
            self.pool.note_tokens(lane, len(seq))

    def _advance_prefills(self) -> tuple[list[Request], list[Request]]:
        """Run chunk programs for ``"prefilling"`` lanes within this step's
        prefill-token budget; activate lanes whose body is done.

        Returns ``(progressed, activated)``: requests that did chunk work and
        requests whose final chunk landed (first token emitted, lane joins
        the decode batch).  At least one chunk runs per
        step when any lane is prefilling (the budget is a soft cap, never a
        stall), so streaming callers always observe progress.
        """
        progressed: list[Request] = []
        activated: list[Request] = []
        for lane, req in enumerate(self.slots):
            if req is None or req.state != "prefilling":
                continue
            did = False
            while req._chunk_pos < len(req._chunk_tokens):
                remaining = len(req._chunk_tokens) - req._chunk_pos
                width = min(self._chunk_cap(), _bucket(remaining, self._ladder))
                left = self.scheduler.prefill_budget_left()
                if width > left and self.scheduler._prefill_spent > 0:
                    break  # budget spent; resume next step
                self._run_chunk(lane, req, width)
                self.scheduler.charge_prefill(width)
                did = True
            if did:
                progressed.append(req)
            if req._chunk_pos >= len(req._chunk_tokens):
                self._activate_lane(lane, req)
                activated.append(req)
        return progressed, activated

    def _preempt(self, lane: int) -> Request:
        """Evict the lane's resident back to the wait queue, reclaiming its
        blocks; it keeps its output and re-admits via prompt+output prefill."""
        req = self.slots[lane]
        self.slots[lane] = None
        self.pool.release(lane)
        req.state = "preempted"
        req.preemptions += 1
        self.scheduler.submit(req, step=self.steps)
        return req

    def _pick_victim(self, running: list) -> Request | None:
        """Victim selection that prefers lanes holding no shared blocks.

        Evicting a refcount>1 holder never corrupts a sibling (release only
        decrements), but it throws away blocks other lanes ride on — so
        shared-prefix holders are passed to the scheduler as ``protect``ed
        and only become candidates when no unprotected victim exists.
        """
        protect = [
            r
            for lane, r in enumerate(self.slots)
            if r is not None and self.pool.lane_holds_shared(lane)
        ]
        victim = self.scheduler.pick_victim(running, self.steps, protect=protect)
        if victim is None and protect:
            victim = self.scheduler.pick_victim(running, self.steps)
        return victim

    def _preempt_for_admission(self) -> Request | None:
        """Admission-time preemption: a waiter that outranks the weakest
        active resident by the configured gap may take its blocks."""
        best = self.scheduler.peek_best(self.steps)
        if best is None:
            return None
        running = [r for r in self.slots if r is not None]
        victim = self._pick_victim(running)
        if victim is None:
            return None
        gap = self.scheduler.config.preempt_priority_gap
        if self.scheduler.effective_priority(best, self.steps) < victim.priority + gap:
            return None
        self._preempt(self.slots.index(victim))
        if self._fits(best):
            self.scheduler.remove(best)
            return best
        return None

    def _grow_active(self) -> None:
        """Every active lane must own the block its next token writes into;
        under pool pressure the scheduler's victim (lowest priority, most
        emitted tokens) is preempted until the allocation fits."""
        for lane, req in enumerate(self.slots):
            if req is None or req.state == "prefilling":
                continue  # prefilling lanes allocated fully at admission
            need = int(self.positions[lane]) + 1
            while not self.pool.ensure(lane, need):
                running = [r for r in self.slots if r is not None]
                victim = self._pick_victim(running)
                if victim is None:
                    break
                vlane = self.slots.index(victim)
                self._preempt(vlane)
                if vlane == lane:
                    break  # preempted ourselves; the lane is empty now
            else:
                self.pool.note_tokens(lane, need)

    # -- decode ---------------------------------------------------------------
    def _width(self, n_active: int) -> int:
        for b in self._width_buckets:
            if n_active <= b:
                return b
        return self._width_buckets[-1]

    def _decode_fn(self, width: int):
        if width not in self._decode_cache:
            # Fresh closure per jit — see _chunk_fn for why the bound method
            # must not be jitted directly.
            step = self.model.decode_step

            def program(params, cache, tokens, pos):
                return step(params, cache, tokens, pos)

            self._decode_cache[width] = jax.jit(
                _named(program, f"serve_decode_w{width}"), donate_argnums=(1,)
            )
        return self._decode_cache[width]

    def _decode_active(self) -> list[Request]:
        """One batched decode over the compacted active lanes, at the
        smallest compiled width bucket that fits; returns the requests that
        received a token."""
        active = [
            i for i, r in enumerate(self.slots)
            if r is not None and r.state == "active"
        ]
        if not active:
            return []
        width = self._width(len(active))
        with trace.span("serve.decode", width=width, rows=len(active)):
            # Pad the batch to the bucket with idle lanes (their block tables are
            # empty or retired, so their writes land in scratch / reclaimed rows
            # — same as the seed engine decoding its idle slots; a retired lane's
            # *registered* prefix blocks are safe because the stale write position
            # is at/beyond the old decode frontier, outside every indexed block).
            # When mid-prefill lanes leave too few idle lanes, they serve as
            # padding too: the pad write lands at the last prompt position (at
            # or past every finished chunk), which the lane's final chunk
            # overwrites with the real last-token k/v before activation.
            idle = [i for i, r in enumerate(self.slots) if r is None]
            idle += [
                i for i, r in enumerate(self.slots)
                if r is not None and r.state == "prefilling"
            ]
            sel = active + idle[: width - len(active)]
            tokens = np.zeros((width, 1), dtype=np.int32)
            for row, lane in enumerate(active):
                tokens[row, 0] = self.slots[lane].output[-1]
            pos = self.positions[sel]
            with self.runtime.activate():  # trace-time selections hit OUR runtime
                logits, new_cache = self._run_program(
                    "engine.decode", self._decode_cache, width,
                    lambda: self._decode_fn(width)(
                        self.params,
                        self.pool.gather(sel),  # re-gathered on retry: donation-safe
                        jnp.asarray(tokens),
                        jnp.asarray(pos),
                    ),
                )
            self.pool.scatter(sel, new_cache)
            if self.on_decode is not None:
                self.on_decode(width)
            with trace.span("serve.sample", width=width):
                nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            got = []
            for row, lane in enumerate(active):
                r = self.slots[lane]
                self.positions[lane] += 1
                tok = int(nxt[row])
                r.output.append(tok)
                got.append(r)
                if (
                    len(r.output) >= r.max_new_tokens
                    or (r.eos_id is not None and tok == r.eos_id)
                    or self.positions[lane] >= self.cache_len - 1
                ):
                    r.done = True
                    r.state = "done"
                    self.slots[lane] = None
                    # Lazy reclaim: blocks stay readable (pool.retire) until a
                    # later admission actually needs them.
                    self.pool.retire(lane)
            self.steps += 1
        return got

    # -- SLO pressure (objective-aware selection) -----------------------------
    def _admit_blocked(self) -> bool:
        if not self._slo_mode or self._slo_cap is None:
            return False
        return sum(s is not None for s in self.slots) >= self._slo_cap

    def _enter_slo(self, target: float) -> None:
        from repro.core.runtime import Objective

        self._slo_mode = True
        self._slo_ok = 0
        # Cap admissions below the current width bucket so the batch shrinks
        # as residents finish instead of refilling.
        cur = self._width(sum(s is not None for s in self.slots))
        cap = 1
        for b in self._width_buckets:
            if b < cur:
                cap = b
        self._slo_cap = cap
        self.slo_events.append((self.steps, "enter", target))
        # SLO mode also shrinks the prefill chunk cap one ladder rung (the
        # _chunk_cap() consults _slo_mode, already set above); publishing it
        # on the Objective lets selection policies prefer configs tuned at
        # the chunk's GEMM shapes.
        self.runtime.set_objective(Objective(
            latency_target_ms=float(target),
            prefill_chunk_tokens=self._chunk_cap() if self._streaming else None,
        ))
        # Invalidate compiled programs: the next trace re-runs kernel
        # selection under the objective (select_for_objective).
        self._prefill_cache.clear()
        self._decode_cache.clear()
        self._chunk_cache.clear()

    def _exit_slo(self) -> None:
        self._slo_mode = False
        self._slo_cap = None
        self._slo_ok = 0
        self.slo_events.append((self.steps, "exit", None))
        self.runtime.set_objective(None)
        self._prefill_cache.clear()
        self._decode_cache.clear()
        self._chunk_cache.clear()

    def _update_slo(self) -> None:
        """Hysteresis loop around the latency objective.

        Enter SLO mode when the engine's recent per-step time (or a targeted
        resident's own recent per-token latency) overruns the target of any
        latency-targeted request — *resident or queued*: a queued target
        about to be admitted into an over-budget batch would blow its SLO on
        its very first token, so the constraint lands before admission, not
        after the damage.  Exit when no targeted work remains anywhere, or
        after ``slo_patience`` consecutive comfortable (<0.7x target) steps.
        """
        if not self.slo_aware:
            return
        resident = [
            r for r in self.slots if r is not None and r.latency_target_ms is not None
        ]
        queued = [
            r for r in self.scheduler.waiting() if r.latency_target_ms is not None
        ]
        if not self._slo_mode:
            recent = list(self._step_ms)[-3:]
            step_ms = sum(recent) / len(recent) if recent else None
            at_risk = [
                r for r in resident
                if _recent_ms(r) is not None and _recent_ms(r) > r.latency_target_ms
            ]
            if step_ms is not None:
                at_risk += [
                    r for r in resident + queued if r.latency_target_ms < step_ms
                ]
            if at_risk:
                self._enter_slo(min(r.latency_target_ms for r in at_risk))
            return
        if not resident and not queued:
            self._exit_slo()
            return
        # A targeted request still waiting admission holds the mode: dropping
        # the cap now would re-widen the batch right before it lands.
        calm = bool(resident) and all(
            _recent_ms(r) is None or _recent_ms(r) < 0.7 * r.latency_target_ms
            for r in resident
        )
        if calm:
            self._slo_ok += 1
            if self._slo_ok >= self.slo_patience:
                self._exit_slo()
        else:
            self._slo_ok = 0

    # -- failure containment (DESIGN.md §11) -----------------------------------
    def _rejit_decode(self) -> None:
        self._decode_cache.clear()
        self._chunk_cache.clear()

    def _run_program(self, site: str, programs: dict, key: int, fn, *,
                     request: Request | None = None):
        """Run one compiled program with per-request retry-on-kernel-fault.

        ``fn`` calls the program ``programs[key]``, building it on a miss.
        The engine-level safety net above the ops-layer guard: an injected
        fault at ``site`` (fired *before* execution, so donated buffers are
        never half-consumed) or a real failure escaping the compiled program
        gets one retry after the compiled artifact is dropped — the re-trace
        re-runs kernel selection, picking up any quarantine the ops guard
        installed meanwhile.  A second failure propagates: zero silent
        drops, but also no infinite retry loop.
        """
        from repro.core.faults import GUARDED_EXCEPTIONS, incident

        rt = self.runtime
        plan = rt.fault_plan
        try:
            if plan is not None:
                plan.raise_if(site)
            return _launch(site, programs, key, fn)
        except GUARDED_EXCEPTIONS as e:
            rt.record_incident(incident(
                site, "engine", None, e, "retry", device=rt.active_device()))
            if request is not None:
                request.retries += 1
            programs.pop(key, None)
            return _launch(site, programs, key, fn)

    def _update_health(self) -> str:
        """Advance the healthy/degraded state machine; record transitions.

        Degraded while new incidents arrived since the last check or any
        config sits in quarantine; healthy once a full check window passes
        clean with an empty quarantine table.
        """
        rt = self.runtime
        count = rt.incident_count()
        fresh = count > self._incidents_seen
        self._incidents_seen = count
        state = "degraded" if (fresh or rt.quarantined()) else "healthy"
        if state != self.health:
            self.health = state
            self.health_events.append((self.steps, state))
        return state

    def maybe_rollback(self) -> RetuneEvent | None:
        """Auto-rollback watchdog for an installed-but-regressing policy.

        If :data:`rollback_threshold` incidents accumulate after a hot-swap,
        the most recent pre-swap deployment is reinstalled from the bounded
        swap history (one rollback per swap: the counter re-arms only on the
        next swap).  Compiled programs are invalidated the same way a swap
        does; in-flight requests keep their lanes.
        """
        from repro.core.faults import incident

        rt = self.runtime
        if self._incidents_at_swap is None or not self._swap_history:
            return None
        if rt.incident_count() - self._incidents_at_swap < self.rollback_threshold:
            return None
        prev = self._swap_history.pop()
        if self.device is not None and rt.active_device() == self.device:
            rt.install_for_device(self.device, prev)
        else:
            rt.install(prev)
        self.deployment = prev
        self._incidents_at_swap = None  # one rollback per swap
        rt.record_incident(incident(
            "engine.retune", "engine", None,
            f"{self.rollback_threshold}+ incidents since hot-swap",
            "rollback", device=rt.active_device()))
        rt.clear_selection_log()
        self._prefill_cache.clear()
        self._rejit_decode()
        ev = RetuneEvent(self.steps, 0.0, 0.0, True, True, 0,
                         len(prev.configs) if hasattr(prev, "configs") else 0,
                         rt.policy_epoch(), rolled_back=True)
        self.retune_events.append(ev)
        return ev

    # -- continuous tuning -----------------------------------------------------
    def maybe_retune(self, *, force: bool = False, online=None) -> RetuneEvent | None:
        """Telemetry -> drift check -> incremental retune -> policy hot-swap.

        Called between decode steps when ``retune_interval`` is set, or
        directly from an operator's background hook (the runtime's policy
        registry is lock+epoch protected, so a swap from another thread
        reaches the serving thread atomically — and only threads dispatching
        against *this engine's runtime*; other tenants' runtimes never see
        it).  Returns the :class:`RetuneEvent` when a
        drift check actually ran (``swapped=False`` if it didn't trigger),
        ``None`` when there is no deployment or not enough telemetry yet.
        ``online`` optionally names a hybrid-mode ``OnlinePolicy``: its arm
        measurements ride into the snapshot, and after a swap it adopts the
        retuned deployment as its prior (``set_prior``).

        The hot swap is zero-downtime: KV blocks, lanes, and in-flight
        requests are untouched; compiled programs for *already-traced* shapes
        keep their old kernels until natural retrace, while the cleared
        prefill/decode jit wrappers make every subsequent trace consult the
        new policy.
        """
        from repro.core.dispatch import Deployment
        from repro.core.faults import FaultError, incident
        from repro.core.retune import (
            canary_deployment,
            detect_drift_all,
            incremental_retune,
        )

        rt = self.runtime
        dep = self.deployment
        if dep is None:
            pol = rt.policy()
            dep = pol if isinstance(pol, Deployment) else None
        if dep is None:
            return None
        snap = rt.telemetry(online=online)
        if snap.n_events == 0:
            return None
        # Drift is detected per (device, family, shape): every family with
        # live traffic gets its own report against its own provenance, so an
        # ssm-only traffic shift retunes the ssm family without touching the
        # (undrifted) matmul artifact.
        reports = detect_drift_all(
            snap, dep, threshold=self.drift_threshold, min_events=self.retune_min_events
        )
        worst = max(reports.values(), key=lambda r: r.score)
        to_retune = [f for f, r in reports.items() if r.triggered]
        if force and not to_retune:
            to_retune = list(reports)
        if not to_retune:
            # n_events is the worst family's own event count: the "below
            # event floor" verdict must be judged against the per-family
            # floor drift detection actually applied, not the cross-family
            # aggregate.
            ev = RetuneEvent(self.steps, worst.score, worst.unseen_fraction,
                             False, any(r.triggered for r in reports.values()),
                             worst.n_events, len(dep.configs), rt.policy_epoch())
            self.retune_events.append(ev)
            return ev
        # Canary-gated adoption: each family's candidate must pass the
        # holdout validation (selection quality + numeric agreement with
        # ref) before it is allowed anywhere near install_for_device.  A
        # rejected candidate leaves the incumbent family tuning in place.
        new_dep = dep
        adopted: list[str] = []
        rejected: list[str] = []
        for fam in to_retune:
            try:
                if rt.fault_plan is not None:
                    rt.fault_plan.raise_if("retune.candidate", fam)
                cand = incremental_retune(
                    new_dep, snap, family=fam, report=reports[fam],
                    threshold=self.drift_threshold, min_events=self.retune_min_events,
                ).deployment
            except (FaultError, ValueError) as e:
                rejected.append(fam)
                rt.record_incident(incident(
                    "retune.candidate", fam, None, e, "candidate_failed",
                    device=rt.active_device()))
                continue
            if self.canary:
                verdict = canary_deployment(new_dep, cand, snap, family=fam, runtime=rt)
                if not verdict.ok:
                    rejected.append(fam)
                    rt.record_incident(incident(
                        f"canary.{fam}", fam, None, verdict.reason,
                        "candidate_rejected", device=rt.active_device()))
                    continue
            new_dep = cand
            adopted.append(fam)
        if not adopted:
            ev = RetuneEvent(self.steps, worst.score, worst.unseen_fraction,
                             False, any(r.triggered for r in reports.values()),
                             worst.n_events, len(dep.configs), rt.policy_epoch(),
                             rejected=tuple(rejected))
            self.retune_events.append(ev)
            return ev
        to_retune = adopted
        # Keep the incumbent in the bounded swap history and re-arm the
        # rollback watchdog: incidents from here on count against this swap.
        self._swap_history.append(dep)
        self._incidents_at_swap = rt.incident_count()
        if self.device is not None and rt.active_device() == self.device:
            rt.install_for_device(self.device, new_dep)  # registry hot-swap
        else:
            rt.install(new_dep)
        if online is not None and hasattr(online, "set_prior"):
            # A hybrid-mode OnlinePolicy must adopt the retuned deployment as
            # its prior (and drop its prior-derived attention cache with it).
            online.set_prior(new_dep)
        self.deployment = new_dep
        rt.clear_selection_log()  # fresh telemetry window for the new policy
        # Invalidate this engine's compiled programs so the next admission /
        # decode trace re-runs kernel selection under the swapped-in policy.
        # Engine state (block pool, lanes, positions) survives: in-flight
        # requests continue without a drop, paying only a retrace.
        self._prefill_cache.clear()
        self._rejit_decode()
        worst_retuned = max((reports[f] for f in to_retune), key=lambda r: r.score)
        ev = RetuneEvent(self.steps, worst_retuned.score, worst_retuned.unseen_fraction,
                         True, any(r.triggered for r in reports.values()),
                         worst_retuned.n_events, len(new_dep.configs), rt.policy_epoch(),
                         tuple(to_retune), rejected=tuple(rejected))
        self.retune_events.append(ev)
        return ev

    # -- control-plane adoption (DESIGN.md §14) --------------------------------
    def offer_deployment(self, candidate, *, source: str = "control-plane") -> None:
        """Stage an externally produced deployment for adoption.

        Thread-safe: a :class:`repro.control.PolicySubscriber` (or any other
        background delivery) calls this from its own thread; the engine
        adopts the candidate at the top of its next :meth:`step`, so the
        hot-swap always lands on a step boundary, never mid-decode.  A newer
        offer replaces an unclaimed older one (last writer wins — the
        control plane's latest artifact is the one that matters).
        """
        with self._offer_lock:
            self._offered = (candidate, source)

    def _take_offer(self):
        with self._offer_lock:
            offer, self._offered = self._offered, None
        return offer

    def adopt_deployment(
        self, candidate, *, source: str = "external"
    ) -> RetuneEvent:
        """Canary-gate and hot-swap an externally produced deployment.

        The adoption path for artifacts this engine did *not* tune itself —
        a control-plane retune pushed over the policy long-poll, an operator
        hand-off, an A/B promotion.  Every family with live traffic in the
        current telemetry window is canaried (selection quality + numeric
        ref agreement, exactly the gate :meth:`maybe_retune` applies to its
        own candidates); one failing family rejects the whole artifact — an
        external bundle swaps atomically or not at all.  On adoption the
        incumbent joins the bounded swap history and the rollback watchdog
        re-arms, so a pushed artifact that regresses in service rolls back
        the same way a local retune would.  In-flight requests are untouched
        (compiled programs re-trace lazily under the new policy).
        """
        from repro.core.faults import incident
        from repro.core.retune import canary_deployment

        rt = self.runtime
        incumbent = self.deployment
        snap = rt.telemetry()
        gated: list[str] = []
        rejected: list[str] = []
        if self.canary and incumbent is not None:
            for fam in snap.families():
                verdict = canary_deployment(
                    incumbent, candidate, snap, family=fam, runtime=rt
                )
                gated.append(fam)
                if not verdict.ok:
                    rejected.append(fam)
                    rt.record_incident(incident(
                        f"canary.{fam}", fam, None, verdict.reason,
                        "candidate_rejected", device=rt.active_device()))
        if rejected:
            ev = RetuneEvent(self.steps, 0.0, 0.0, False, False,
                             snap.n_events, len(incumbent.configs),
                             rt.policy_epoch(), rejected=tuple(rejected),
                             source=source)
            self.retune_events.append(ev)
            return ev
        if incumbent is not None:
            self._swap_history.append(incumbent)
            self._incidents_at_swap = rt.incident_count()
        if self.device is not None and rt.active_device() == self.device:
            rt.install_for_device(self.device, candidate)  # registry hot-swap
        else:
            rt.install(candidate)
        self.deployment = candidate
        rt.clear_selection_log()  # fresh telemetry window for the new policy
        self._prefill_cache.clear()
        self._rejit_decode()
        ev = RetuneEvent(self.steps, 0.0, 0.0, True, True, snap.n_events,
                         len(candidate.configs) if hasattr(candidate, "configs") else 0,
                         rt.policy_epoch(), families=tuple(gated), source=source)
        self.retune_events.append(ev)
        return ev

    # -- public ---------------------------------------------------------------
    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int = 16,
        eos_id: int | None = None,
        priority: int = 0,
        latency_target_ms: float | None = None,
        uid: int | None = None,
    ) -> Ticket:
        """Enqueue one prompt; returns a streaming :class:`Ticket`."""
        req = Request(
            uid=uid if uid is not None else next(self._uid),
            prompt=np.asarray(prompt, dtype=np.int32),
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
            priority=priority,
            latency_target_ms=latency_target_ms,
        )
        return self.submit_request(req)

    def submit_request(self, req: Request) -> Ticket:
        """Enqueue a pre-built :class:`Request` (advanced / legacy path)."""
        self.scheduler.submit(req, step=self.steps)
        self._epoch_requests.append(req)
        return Ticket(req, self)

    def pending(self) -> bool:
        """Work remains: requests waiting or lanes mid-decode."""
        return bool(len(self.scheduler) or any(s is not None for s in self.slots))

    def step(self) -> bool:
        """One scheduling round (admit -> grow/preempt -> decode -> watchdogs).

        Returns False when no progress was possible (nothing admitted and no
        active lane decoded) — callers looping on ``step()`` should stop.
        """
        with trace.span("serve.step", step=self.steps):
            t0 = self._clock()
            # A control-plane offer adopts on the step boundary: before any
            # admission or decode of this round, so the whole step runs under one
            # policy and no in-flight request straddles the swap mid-trace.
            offer = self._take_offer()
            if offer is not None:
                self.adopt_deployment(offer[0], source=offer[1])
            # SLO check runs BEFORE admission: it sees the same step-time history
            # it would at the end of the previous step, but entering now means
            # this step's admissions and traces already run under the cap and the
            # latency objective (no full-width burst right as a target lands).
            self._update_slo()
            self.scheduler.begin_step()  # fresh prefill-token budget
            emitted: list[Request] = []
            with trace.span("serve.schedule", queued=len(self.scheduler)) as sp:
                admitted = 0
                preempted_once = False
                while len(self.scheduler):
                    lane = self._free_lane()
                    if lane is None or self._admit_blocked():
                        break
                    req = self.scheduler.pop_next(self.steps, fits=self._fits)
                    if req is None:
                        if preempted_once:
                            break
                        preempted_once = True
                        req = self._preempt_for_admission()
                        if req is None:
                            break
                        lane = self._free_lane()
                    admitted += 1
                    if self._admit(req, lane):
                        emitted.append(req)  # legacy prefill emitted the first token
                sp.set(admitted=admitted)
            # Budgeted chunk work for prefilling lanes; a lane whose final chunk
            # landed this step emits its first token here and joins this step's
            # batched decode below (streaming parity with legacy: a small prompt
            # admitted this step still answers this step).
            progressed, activated = self._advance_prefills()
            emitted.extend(activated)
            with trace.span("serve.schedule", queued=len(self.scheduler)):
                self._grow_active()
            decoded = self._decode_active()
            emitted.extend(decoded)
            with trace.span("serve.watchdogs"):
                self._update_health()
                self.maybe_rollback()
                if (
                    self.retune_interval is not None
                    and self.steps - self._last_retune_check >= self.retune_interval
                ):
                    self._last_retune_check = self.steps
                    self.maybe_retune()
            dt_ms = (self._clock() - t0) * 1e3
            self._step_ms.append(dt_ms)
            for r in emitted:
                r.token_ms.append(dt_ms)
            # Chunk work without a token is still progress: streaming callers
            # (Ticket.tokens) must keep stepping while a long prompt prefills.
            return bool(emitted or progressed)

    def status(self) -> EngineStatus:
        """Live snapshot over this serving epoch (since the last drain).

        Every outstanding request is counted exactly once:
        ``completed + in_flight + queued + preempted`` partitions the epoch.
        Evicted waiters show up in ``preempted`` (state ``"preempted"``), not
        in ``queued``; once re-admitted they move back to ``in_flight``.
        """
        reqs = self._epoch_requests
        waiting = self.scheduler.waiting()
        preempted_now = sum(1 for r in waiting if r.state == "preempted")
        in_flight = sum(s is not None for s in self.slots)
        return EngineStatus(
            completed=sum(r.done for r in reqs),
            in_flight=in_flight,
            queued=len(waiting) - preempted_now,
            steps=self.steps,
            exhausted=bool(waiting or in_flight),
            health=self.health,
            preempted=preempted_now,
            **self._pool_health(),
        )

    def _pool_health(self) -> dict:
        ps = self.pool.stats()
        return {
            "pool_utilization": ps["utilization"],
            "pool_fragmentation": ps["fragmentation"],
            "shared_blocks": ps["shared_blocks"],
            "prefix_hits": ps["prefix_hits"],
            "prefix_lookups": ps["prefix_lookups"],
        }

    def prefix_overlap(self, prompt) -> int:
        """Tokens of ``prompt`` this engine could serve from cached blocks.

        A read-only probe (hit-rate counters untouched) used by the Router's
        prefix-affinity dispatch; 0 when sharing is inactive here.
        """
        if not self._sharing:
            return 0
        body = np.asarray(prompt, dtype=np.int32)[:-1]
        return len(self.pool.match_prefix(body, peek=True)) * self.pool.block_size

    def drain(self, *, max_steps: int = 10_000) -> EngineStatus:
        """Serve everything submitted until done or the step budget runs out.

        When the ``max_steps`` budget is exhausted, unfinished requests are
        NOT silently returned as results: in-flight ones keep
        ``state="active"`` and waiting ones (queued or preempted) are marked
        ``state="starved"`` and dropped from the queue (both stay
        ``done=False``), so callers can retry or surface them even though
        partial ``output`` tokens exist.  Closes the serving epoch: the next
        drain reports only requests submitted after this one (in-flight
        survivors carry over).  The terminal ``preempted`` field reports how
        many of the epoch's requests were evicted at least once (live
        :meth:`status` snapshots instead count requests *currently* awaiting
        re-admission).
        """
        while self.pending() and self.steps < max_steps:
            if not self.step():
                break
        exhausted = self.pending()
        starved = self.scheduler.clear()
        for r in starved:
            r.state = "starved"
        self._update_health()
        reqs = self._epoch_requests
        status = EngineStatus(
            completed=sum(r.done for r in reqs),
            in_flight=sum(s is not None for s in self.slots),
            queued=len(starved),
            steps=self.steps,
            exhausted=exhausted,
            health=self.health,
            preempted=sum(1 for r in reqs if r.preemptions),
            **self._pool_health(),
        )
        self._epoch_requests = [r for r in reqs if r.state == "active"]
        return status

    def run(self, requests: list[Request], *, max_steps: int = 10_000) -> EngineStatus:
        """Deprecated batch API: submit every request, then drain.

        Byte-identical to the seed engine's loop (admission order, bucketing,
        decode semantics); new code should use :meth:`submit` / :meth:`step` /
        :meth:`drain` (or a :class:`repro.serve.Router` across devices).
        """
        warnings.warn(
            "ServingEngine.run(requests) is deprecated; use "
            "engine.submit(...) -> Ticket plus engine.drain() "
            "(repro.serve submit/stream API)",
            DeprecationWarning,
            stacklevel=2,
        )
        for r in requests:
            self.submit_request(r)
        return self.drain(max_steps=max_steps)


def _named(fn, name: str):
    """Name a step program: jit names its module ``jit_<name>``, which is how
    a device trace tells the engine's programs from eager ops."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _launch(site: str, programs: dict, key: int, fn):
    # The first call of a program the engine has just built traces and
    # compiles it: a span of its own, apart from the launches.
    name = "serve.launch" if key in programs else "serve.compile"
    with trace.span(name, site=site, width=key):
        return fn()


def _batch_extra(key: str, v) -> jax.Array:
    """Shape one extra input for the batch-1 prefill, explicitly per rank.

    Extras come in two layouts: already batched with a leading batch-1 axis
    (``(1, n, d)``) which pass through, or per-sequence without a batch axis
    (``(n, d)``, or a scalar) which gain one.  A leading axis > 1 that is not
    batch-1 is treated as per-sequence data; an explicit batch > 1 cannot be
    meant for a single-sequence prefill, so there is nothing to guess.
    """
    v = jnp.asarray(v)
    if v.ndim == 0:
        return v[None]  # scalar -> (1,)
    if v.shape[0] == 1:
        return v  # already batched (batch-1 leading axis)
    return v[None]  # per-sequence -> add the batch axis


def _scatter_slot(full: jax.Array, one: jax.Array, slot: int, max_batch: int) -> jax.Array:
    """Write a batch-1 cache entry into batch slot ``slot`` of a dense pool.

    Cache leaves carry batch either at axis 0 (B, ...) or axis 1 (L, B, ...);
    the batch axis is the one sized ``max_batch`` in the pool and 1 in the
    prefill output.  Matching against the *pool size* (not shape inequality)
    keeps the write live when ``max_batch == 1``, where pool and prefill
    shapes coincide and an inequality guard silently drops the cache.

    (The engine itself now scatters through :class:`KVPool`, whose probe
    classification generalises this axis guessing; kept as the dense
    reference semantics — tests assert KVPool parity against it.)
    """
    if one.ndim != full.ndim:
        raise ValueError(f"cache rank mismatch {one.shape} vs {full.shape}")
    for axis in (0, 1):
        if one.ndim > axis and one.shape[axis] == 1 and full.shape[axis] == max_batch:
            idx = [slice(None)] * full.ndim
            idx[axis] = slice(slot, slot + 1)
            return full.at[tuple(idx)].set(one)
    # replicated leaf (e.g. shared encoder memory broadcast across slots): keep.
    return full
