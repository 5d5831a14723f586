"""Serving-engine regression tests: slot scatter, extras, truncation, starvation.

A deterministic toy model (echo+1 language model with an inspectable cache)
isolates the engine's bookkeeping from real model math; one real-model test
pins the max_batch=1 prefill-cache regression end to end.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.engine import EngineStatus, Request, ServingEngine, _scatter_slot


class ToyModel:
    """Echo+1 LM: next token = (last token + 1) % vocab; cache records tokens.

    Cache has both batch-leading ("k": (B, L)) and layer-leading
    ("mem": (2, B, 4)) leaves, matching the real models' two layouts.
    """

    vocab = 17

    def __init__(self):
        self.seen_extras: dict[str, tuple] = {}

    def init_cache(self, b, cache_len):
        return {
            "k": jnp.zeros((b, cache_len), jnp.float32),
            "mem": jnp.zeros((2, b, 4), jnp.float32),
        }

    def prefill(self, params, batch, cache_len):
        tokens = batch["tokens"]
        for k, v in batch.items():
            if k != "tokens":
                self.seen_extras[k] = tuple(v.shape)
        b, s = tokens.shape
        cache = self.init_cache(b, cache_len)
        cache["k"] = cache["k"].at[:, :s].set(tokens.astype(jnp.float32))
        cache["mem"] = cache["mem"] + 1.0
        logits = jax.nn.one_hot((tokens[:, -1:] + 1) % self.vocab, self.vocab)
        return logits, cache

    def decode_step(self, params, cache, tokens, positions):
        b = tokens.shape[0]
        cache = dict(cache)
        cache["k"] = cache["k"].at[jnp.arange(b), positions].set(
            tokens[:, 0].astype(jnp.float32)
        )
        logits = jax.nn.one_hot((tokens + 1) % self.vocab, self.vocab)
        return logits, cache


def _engine(**kw):
    kw.setdefault("max_batch", 1)
    kw.setdefault("cache_len", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    model = ToyModel()
    return ServingEngine(model, params={}, **kw), model


# ---------------------------------------------------------------------------
# _scatter_slot
# ---------------------------------------------------------------------------
def test_scatter_slot_writes_when_pool_is_batch_one():
    """max_batch == 1: pool and prefill shapes coincide; the write must land."""
    full = jnp.zeros((1, 8))
    one = jnp.arange(8.0).reshape(1, 8)
    out = _scatter_slot(full, one, slot=0, max_batch=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))
    # layer-leading (L, B, ...) layout too
    full2 = jnp.zeros((2, 1, 4))
    one2 = jnp.ones((2, 1, 4))
    out2 = _scatter_slot(full2, one2, slot=0, max_batch=1)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(one2))


def test_scatter_slot_multi_batch_and_replicated():
    full = jnp.zeros((4, 6))
    one = jnp.ones((1, 6))
    out = np.asarray(_scatter_slot(full, one, slot=2, max_batch=4))
    assert out[2].sum() == 6 and out[[0, 1, 3]].sum() == 0
    # replicated leaf (no batch-1 axis in the prefill output): kept as-is
    rep_full = jnp.full((3, 5), 7.0)
    rep_one = jnp.zeros((3, 5))
    out = _scatter_slot(rep_full, rep_one, slot=1, max_batch=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(rep_full))


def test_prefill_cache_lands_in_slot_when_max_batch_is_one():
    """Regression: the admit write used to be silently dropped at max_batch=1."""
    eng, _ = _engine(max_batch=1)
    prompt = np.array([3, 1, 4, 1, 5], dtype=np.int32)
    eng._admit(Request(uid=0, prompt=prompt), slot=0)
    got = np.asarray(eng.cache["k"])[0, :8]
    want = np.zeros(8)
    want[-5:] = prompt  # left-padded into the 8-bucket
    np.testing.assert_array_equal(got, want)
    assert np.asarray(eng.cache["mem"]).sum() > 0  # layer-leading leaf written too


def test_max_batch_one_matches_larger_pool_real_model():
    """Same request must decode identically in a 1-slot and a 2-slot pool."""
    from repro.configs import registry
    from repro.models.model import build_model

    cfg = registry.get("granite-8b").reduced()
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    outputs = []
    for max_batch in (1, 2):
        eng = ServingEngine(model, params, max_batch=max_batch, cache_len=64)
        req = Request(uid=0, prompt=prompt.copy(), max_new_tokens=6)
        status = eng.run([req])
        assert status.completed == 1 and req.done
        outputs.append(req.output)
    assert outputs[0] == outputs[1]


# ---------------------------------------------------------------------------
# extra inputs
# ---------------------------------------------------------------------------
def test_admit_extras_batched_and_unbatched():
    extras = {
        "batched": jnp.ones((1, 5, 3)),   # leading batch-1 axis: pass through
        "unbatched": jnp.ones((5, 3)),    # per-sequence: gains the batch axis
        "scalar": jnp.asarray(2.0),       # scalar: becomes (1,)
    }
    eng, model = _engine(extra_inputs=extras)
    eng._admit(Request(uid=0, prompt=np.array([1, 2], dtype=np.int32)), slot=0)
    assert model.seen_extras["batched"] == (1, 5, 3)
    assert model.seen_extras["unbatched"] == (1, 5, 3)
    assert model.seen_extras["scalar"] == (1,)


# ---------------------------------------------------------------------------
# over-long prompts
# ---------------------------------------------------------------------------
def test_overlong_prompt_truncates_sliding_window():
    eng, _ = _engine()  # largest bucket = 16
    prompt = np.arange(1, 41, dtype=np.int32)  # 40 tokens, no zeros
    req = Request(uid=0, prompt=prompt, max_new_tokens=2)
    status = eng.run([req])  # must not raise
    assert status.completed == 1 and req.done
    assert req.truncated_tokens == 40 - 16
    # the last 16 prompt tokens were prefilled (sliding window keeps the tail)
    np.testing.assert_array_equal(np.asarray(eng.cache["k"])[0, :16], prompt[-16:])
    # echo+1 model: first generated token continues from the *last* prompt token
    assert req.output[0] == (int(prompt[-1]) + 1) % ToyModel.vocab


def test_fitting_prompt_not_marked_truncated():
    eng, _ = _engine()
    req = Request(uid=0, prompt=np.array([1, 2, 3], dtype=np.int32), max_new_tokens=2)
    eng.run([req])
    assert req.truncated_tokens == 0


# ---------------------------------------------------------------------------
# starvation / step budget
# ---------------------------------------------------------------------------
def test_run_marks_starved_and_in_flight_on_budget_exhaustion():
    eng, _ = _engine(max_batch=1)
    reqs = [
        Request(uid=i, prompt=np.array([1, 2], dtype=np.int32), max_new_tokens=50)
        for i in range(3)
    ]
    status = eng.run(reqs, max_steps=3)
    assert isinstance(status, EngineStatus) and status.exhausted
    assert status.completed == 0 and status.in_flight == 1 and status.queued == 2
    # the in-flight request has partial output but is NOT a completed result
    assert reqs[0].state == "active" and not reqs[0].done and reqs[0].output
    # queued requests are distinguishable from both active and done
    assert all(r.state == "starved" and not r.done for r in reqs[1:])


def test_run_completion_status():
    eng, _ = _engine(max_batch=1)
    reqs = [
        Request(uid=i, prompt=np.array([1, 2, 3], dtype=np.int32), max_new_tokens=3)
        for i in range(2)
    ]
    status = eng.run(reqs)
    assert not status.exhausted
    assert status.completed == 2 and status.in_flight == 0 and status.queued == 0
    assert all(r.done and r.state == "done" for r in reqs)
    # echo+1 chain: each new token is prev+1
    for r in reqs:
        assert r.output == [4, 5, 6]


# ---------------------------------------------------------------------------
# preemption accounting (block pressure)
# ---------------------------------------------------------------------------
def test_preemption_accounting_counts_each_request_once():
    """A preempted waiter shows up in ``preempted`` only — never double-counted
    in ``queued``/``in_flight`` — and the drain report counts the request once
    no matter how many times it was evicted."""
    # 5 blocks of 8 tokens for two lanes that each want 3: guaranteed pressure.
    eng, _ = _engine(max_batch=2, cache_len=32, block_size=8, n_blocks=5)
    a = Request(uid=0, prompt=np.arange(8, dtype=np.int32), max_new_tokens=16,
                priority=1)
    b = Request(uid=1, prompt=np.arange(8, dtype=np.int32), max_new_tokens=16,
                priority=0)
    eng.submit_request(a)
    eng.submit_request(b)
    saw_preempted = False
    for _ in range(200):
        if not eng.step():
            break
        st = eng.status()
        # partition invariant: every outstanding request counted exactly once
        assert st.completed + st.in_flight + st.queued + st.preempted == 2
        if b.state == "preempted":
            saw_preempted = True
            assert st.preempted >= 1 and st.queued == 0  # not double-counted
        if a.done and b.done:
            break
    assert saw_preempted, "pool pressure never evicted the low-priority request"
    status = eng.drain()
    assert a.done and b.done and status.completed == 2
    # the higher-priority request kept its lane; the victim re-admitted and
    # finished, counted ONCE in the terminal report however often it was hit
    assert a.preemptions == 0 and b.preemptions >= 1
    assert status.preempted == 1


# ---------------------------------------------------------------------------
# run() shim vs submit/drain
# ---------------------------------------------------------------------------
def test_run_shim_is_byte_identical_to_submit_drain():
    import pytest

    prompts = [np.array([1, 2, 3], dtype=np.int32),
               np.array([5, 6], dtype=np.int32),
               np.array([9], dtype=np.int32)]

    def make_requests():
        return [Request(uid=i, prompt=p.copy(), max_new_tokens=5)
                for i, p in enumerate(prompts)]

    legacy_eng, _ = _engine(max_batch=2)
    legacy = make_requests()
    with pytest.warns(DeprecationWarning, match="submit"):
        legacy_status = legacy_eng.run(legacy)

    new_eng, _ = _engine(max_batch=2)
    new = make_requests()
    for r in new:
        new_eng.submit_request(r)
    new_status = new_eng.drain()

    assert [r.output for r in new] == [r.output for r in legacy]
    assert [r.state for r in new] == [r.state for r in legacy]
    assert new_status == legacy_status  # same steps, counts, health
    np.testing.assert_array_equal(
        np.asarray(new_eng.cache["k"]), np.asarray(legacy_eng.cache["k"])
    )


# ---------------------------------------------------------------------------
# streaming regime: chunked prefill + prefix sharing
# ---------------------------------------------------------------------------
class ChunkToyModel(ToyModel):
    """Echo+1 toy that also speaks the chunked-prefill protocol."""

    def supports_chunked_prefill(self):
        return True

    def prefill_chunk(self, params, cache, tokens, start, last_row=None):
        cache = dict(cache)
        pos = start + jnp.arange(tokens.shape[1])
        cache["k"] = cache["k"].at[:, pos].set(
            tokens.astype(jnp.float32), mode="drop"
        )
        if last_row is None:
            last = tokens[:, -1:]
        else:
            last = jax.lax.dynamic_slice_in_dim(
                tokens, jnp.asarray(last_row, jnp.int32), 1, axis=1
            )
        logits = jax.nn.one_hot((last + 1) % self.vocab, self.vocab)
        return logits, cache


def _chunk_engine(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_buckets", (8, 16))
    return ServingEngine(ChunkToyModel(), params={}, **kw)


def test_chunked_prefill_output_invariant_to_chunk_size():
    """Echo+1 semantics must hold whatever the chunk granularity."""
    prompt = list(range(1, 11))
    want = [(prompt[-1] + 1 + i) % 17 for i in range(4)]
    for chunk in (8, 16, None):
        eng = _chunk_engine(prefill_chunk_tokens=chunk)
        assert eng._streaming
        t = eng.submit(prompt, max_new_tokens=4)
        status = eng.drain()
        assert status.completed == 1 and t.request.output == want
        # cache records the whole sequence, left-aligned (no pad offset)
        k = np.asarray(eng.pool.gather([0])["k"])[0]
        np.testing.assert_array_equal(k[: len(prompt)], prompt)


def test_prefill_budget_spreads_chunks_over_steps():
    eng = _chunk_engine(prefill_chunk_tokens=8, cache_len=64, max_batch=1)
    widths = []
    eng.on_prefill = widths.append
    t = eng.submit(list(range(1, 34)), max_new_tokens=2)  # 32-token body
    eng.step()
    assert widths == [8]  # one budgeted chunk per step, not the whole body
    assert t.request.state == "prefilling"
    eng.drain()
    assert t.done and sum(widths) == 32


def test_on_first_token_sees_the_logits_of_the_first_token():
    """Both admission regimes hand the hook the logits the first output
    token is taken from (what chip_smoke compares with its reference)."""
    for eng in (_engine()[0], _chunk_engine(prefill_chunk_tokens=8)):
        seen = {}
        eng.on_first_token = lambda req, logits: seen.__setitem__(req.uid, logits)
        t = eng.submit([3, 4, 5], max_new_tokens=2, uid=7)
        eng.drain()
        assert list(seen) == [7]
        assert seen[7].shape == (1, 1, ToyModel.vocab)
        assert int(jnp.argmax(seen[7][0, -1])) == t.request.output[0] == 6


def test_prefix_sharing_aliases_blocks_and_counts_hits():
    eng = _chunk_engine(prefill_chunk_tokens=16, cache_len=64)
    sys_prompt = list(range(1, 17))  # two full 8-token blocks once admitted
    t1 = eng.submit(sys_prompt + [3], max_new_tokens=2)
    eng.drain()
    assert eng.status().prefix_lookups == 1 and eng.status().prefix_hits == 0
    t2 = eng.submit(sys_prompt + [5], max_new_tokens=2)
    eng.drain()
    st = eng.status()
    assert st.prefix_hits == 1 and st.prefix_lookups == 2
    assert st.prefix_hit_rate == 0.5
    assert eng._prefix_reused_tokens == 16  # both shared blocks skipped prefill
    assert t1.request.output == [4, 5] and t2.request.output == [6, 7]


def test_prefix_sharing_disabled_is_inert():
    eng = _chunk_engine(prefill_chunk_tokens=16, cache_len=64,
                        prefix_sharing=False)
    sys_prompt = list(range(1, 17))
    for tail in ([3], [5]):
        eng.submit(sys_prompt + tail, max_new_tokens=2)
    eng.drain()
    st = eng.status()
    assert st.prefix_lookups == 0 and st.shared_blocks == 0
    assert eng.prefix_overlap(sys_prompt + [9]) == 0


def test_engine_status_reports_pool_health():
    eng = _chunk_engine(prefill_chunk_tokens=16, cache_len=64)
    sys_prompt = list(range(1, 17))
    eng.submit(sys_prompt + [3], max_new_tokens=8)
    eng.step()  # first request activates and registers its prefix
    eng.submit(sys_prompt + [5], max_new_tokens=8)
    eng.step()  # sibling aliases the live lane's blocks: refcount > 1
    st = eng.status()
    assert 0.0 < st.pool_utilization <= 1.0
    assert 0.0 <= st.pool_fragmentation < 1.0
    assert st.shared_blocks == 2 and st.prefix_hits == 1


def test_pick_victim_protects_shared_prefix_holders():
    eng = _chunk_engine(max_batch=3, cache_len=64, prefill_chunk_tokens=16,
                        n_blocks=12)
    sys_prompt = list(range(1, 18))  # body = 16 tokens = 2 shareable blocks
    a = eng.submit(sys_prompt + [3], max_new_tokens=12).request
    eng.step()  # a activates and registers its prefix
    b = eng.submit(sys_prompt + [5], max_new_tokens=12).request
    c = eng.submit(list(range(60, 70)), max_new_tokens=12).request
    while b.state != "active" or c.state != "active":
        assert eng.step()
    lanes = {r.uid: lane for lane, r in enumerate(eng.slots) if r is not None}
    assert eng.pool.lane_holds_shared(lanes[a.uid])
    assert eng.pool.lane_holds_shared(lanes[b.uid])
    assert not eng.pool.lane_holds_shared(lanes[c.uid])
    # a has emitted most (admitted earliest) so unprotected ranking would
    # pick it; protection must steer eviction to the private lane instead
    running = [r for r in eng.slots if r is not None]
    assert eng._pick_victim(running) is c
    # with only shared holders running, the fallback still yields a victim
    assert eng._pick_victim([b]) is b


def test_preempted_shared_prefix_request_readmits():
    """Preemption of a lane whose prefix blocks are aliased by a live sibling
    must re-admit cleanly (refcounts make the release safe), and the evicted
    request's output must stay a seamless continuation."""
    eng = _chunk_engine(max_batch=2, cache_len=64, prefill_chunk_tokens=16,
                        n_blocks=8)
    sys_prompt = list(range(1, 18))  # 2 shared blocks once registered
    a = eng.submit(sys_prompt + [3], max_new_tokens=30, priority=0).request
    eng.step()  # a activates and registers its prefix
    b = eng.submit(sys_prompt + [5], max_new_tokens=30, priority=0).request
    while b.state != "active":
        assert eng.step()
    assert eng.pool.shared_blocks == 2  # b rides on a's blocks
    # Both decodes grow past what 8 blocks can hold.  Every lane holds shared
    # blocks, so the protected pick falls back and evicts one holder anyway —
    # release just drops the refcount, the sibling's alias stays intact.
    status = eng.drain()
    assert a.done and b.done and not status.exhausted
    assert status.preempted >= 1 and a.preemptions + b.preemptions >= 1
    # echo+1 ramps survive eviction + re-admission unbroken
    assert a.output == [(4 + i) % 17 for i in range(30)]
    assert b.output == [(6 + i) % 17 for i in range(30)]


# ---------------------------------------------------------------------------
# geometric bucket ladder (prompts longer than the largest configured bucket)
# ---------------------------------------------------------------------------
def test_extend_ladder_doubles_to_cache_len():
    from repro.serve.engine import _extend_ladder

    assert _extend_ladder((8, 16), 256) == (8, 16, 32, 64, 128)
    assert _extend_ladder((8, 16), 32) == (8, 16)  # seed geometry: unchanged
    assert _extend_ladder((8,), 64) == (8, 16, 32)


def test_long_prompts_share_one_extended_bucket():
    """Prompts past the configured ladder must not truncate, and near-length
    prompts must share one extended bucket (one retrace, not one per length)."""
    eng, _ = _engine(max_batch=2, cache_len=256)
    t1 = eng.submit(list(range(1, 101)), max_new_tokens=2)
    t2 = eng.submit(list(range(1, 121)), max_new_tokens=2)
    eng.drain()
    assert t1.request.truncated_tokens == 0
    assert t2.request.truncated_tokens == 0
    assert t1.done and t2.done
    assert list(eng._prefill_cache) == [128]  # both hit the same 128 bucket


# ---------------------------------------------------------------------------
# paged pool against the dense layout, on a real model
# ---------------------------------------------------------------------------
def test_paged_engine_matches_dense_and_stops_building_pool_programs():
    """Prefix-shared prompts through chunked prefill, preemption and decode
    widths 1/2/4 give the dense ``block_size=None`` engine's tokens, and a
    second pass over the same traffic builds no further pool program."""
    from repro.configs import registry
    from repro.models.model import build_model

    cfg = registry.get("granite-8b").reduced()
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    system = rng.integers(0, cfg.vocab, size=20).tolist()
    prompts = [system + rng.integers(0, cfg.vocab, size=n).tolist() for n in (3, 9, 5, 14)]
    prompts += [rng.integers(0, cfg.vocab, size=7).tolist()]
    budgets = (20, 10, 18, 8, 20)

    def serve(eng):
        widths = []
        eng.on_decode = widths.append
        tickets = [eng.submit(prompts[0], max_new_tokens=budgets[0])]
        while tickets[0].request.state != "active":
            assert eng.step()  # its prefix is indexed before the others arrive
        tickets += [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[1:], budgets[1:])]
        eng.drain()
        assert all(t.done for t in tickets)
        preempted = sum(t.request.preemptions for t in tickets)
        return [t.request.output for t in tickets], preempted, set(widths)

    common = dict(max_batch=4, cache_len=64, prefill_buckets=(8, 16, 48), prefill_chunk_tokens=16)
    dense, _, _ = serve(ServingEngine(model, params, block_size=None, **common))
    paged = ServingEngine(model, params, block_size=4, n_blocks=20, **common)
    first, preempted, widths = serve(paged)
    assert first == dense
    assert preempted >= 1 and widths == {1, 2, 4}
    assert paged.pool.stats()["prefix_hits"] >= 1
    built = paged.pool.stats()["programs_built"]
    again, _, _ = serve(paged)
    assert again == dense
    assert paged.pool.stats()["programs_built"] == built
