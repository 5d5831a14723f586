"""Paged KV pool regression tests: probing, block accounting, gather/scatter.

The pool is exercised standalone against the serving toy-model cache layout
(batch-leading "k", layer-leading "mem") plus a replicated-leaf variant, and
its gather/scatter round-trip is pinned against the dense ``_scatter_slot``
path it replaced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.engine import _scatter_slot
from repro.serve.kvpool import KVPool, probe_cache_layout


class ToyModel:
    """Echo cache: "k" is (B, L) batch-leading, "mem" is (2, B, 4) layer-leading."""

    def init_cache(self, b, cache_len):
        return {
            "k": jnp.zeros((b, cache_len), jnp.float32),
            "mem": jnp.zeros((2, b, 4), jnp.float32),
        }


class ReplicatedModel:
    """Adds a leaf with neither a batch nor a length axis (shared rotary table)."""

    def init_cache(self, b, cache_len):
        return {
            "k": jnp.zeros((b, cache_len, 2), jnp.float32),
            "rope": jnp.zeros((cache_len, 8), jnp.float32)[:16],  # fixed (16, 8)
        }


# ---------------------------------------------------------------------------
# layout probing
# ---------------------------------------------------------------------------
def _spec(specs, name):
    return next(s for s in specs if f"'{name}'" in s.path)


def test_probe_classifies_paged_and_lane_leaves():
    specs, _ = probe_cache_layout(ToyModel().init_cache, cache_len=32, block_size=8)
    k, mem = _spec(specs, "k"), _spec(specs, "mem")
    assert k.kind == "paged" and (k.batch_axis, k.length_axis) == (0, 1)
    assert mem.kind == "lane"  # no length axis: lives per-lane, dense
    assert mem.batch_axis == 1


def test_probe_classifies_replicated_leaf():
    specs, _ = probe_cache_layout(
        ReplicatedModel().init_cache, cache_len=32, block_size=8
    )
    assert _spec(specs, "k").kind == "paged"
    assert _spec(specs, "rope").kind == "replicated"


def test_probe_rejects_structure_changes():
    def shifty(b, cache_len):
        if b == 3:  # structure depends on batch: not a poolable cache
            return {"k": jnp.zeros((b, cache_len))}
        return {"k": jnp.zeros((b, cache_len)), "extra": jnp.zeros((b, 2))}

    with pytest.raises(ValueError):
        probe_cache_layout(shifty, cache_len=32, block_size=8)


# ---------------------------------------------------------------------------
# block accounting
# ---------------------------------------------------------------------------
def _pool(**kw):
    kw.setdefault("lanes", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("block_size", 8)
    return KVPool(ToyModel(), **kw)


def test_pool_defaults_and_invariants():
    pool = _pool()  # 4 lanes x 4 blocks/lane = 16 blocks by default
    assert pool.n_blocks == 16 and pool.block_size == 8
    assert pool.free_blocks == 16 and pool.used_blocks == 0
    assert pool.blocks_needed(1) == 1 and pool.blocks_needed(8) == 1
    assert pool.blocks_needed(9) == 2 and pool.blocks_needed(32) == 4
    st = pool.stats()
    assert st["n_blocks"] == 16 and st["lanes"] == 4 and st["lanes_used"] == 0


def test_pool_rejects_bad_geometry():
    with pytest.raises(ValueError):
        _pool(block_size=7)  # does not divide cache_len
    with pytest.raises(ValueError):
        _pool(n_blocks=3)  # fewer blocks than one full lane needs


def test_ensure_release_and_fragmentation():
    pool = _pool(lanes=2, n_blocks=6)
    assert pool.ensure(0, 20)  # 3 blocks
    assert pool.ensure(1, 10)  # 2 blocks
    assert pool.used_blocks == 5 and pool.free_blocks == 1
    assert pool.free_blocks + pool.used_blocks == pool.n_blocks
    # tables are disjoint and never reference the scratch block 0
    t0, t1 = pool.block_table(0), pool.block_table(1)
    assert not (set(t0) & set(t1)) and 0 not in t0 + t1
    # growth under pressure: one more block fits, the next does not
    assert pool.ensure(0, 28) and pool.used_blocks == 6
    assert not pool.ensure(1, 24)  # pool dry: caller must preempt
    assert pool.block_table(1) == t1  # failed ensure leaves the table intact
    freed = pool.release(0)
    assert freed == 4 and pool.free_blocks == 4 and pool.block_table(0) == ()
    # released blocks are reusable immediately, fragmentation notwithstanding
    assert pool.ensure(1, 24) and pool.used_blocks == 3  # grew 2 -> 3 blocks


def test_can_fit_tracks_free_and_retired():
    pool = _pool(lanes=2, n_blocks=4)
    assert pool.can_fit(32)
    pool.ensure(0, 24)  # 3 of 4 blocks
    assert pool.can_fit(8) and not pool.can_fit(9)
    pool.retire(0)  # lazily reclaimable: counts toward can_fit again
    assert pool.retired_blocks == 3 and pool.can_fit(32)


def test_retire_is_lazy_until_pressure():
    pool = _pool(lanes=2, n_blocks=4)
    cache1 = ToyModel().init_cache(1, 32)
    cache1 = {**cache1, "k": cache1["k"].at[0, :8].set(5.0)}
    pool.ensure(0, 8)
    pool.admit(0, cache1)
    pool.retire(0)
    # retired content is still readable (used for completed-request inspection)
    k = np.asarray(pool.gather([0])["k"])
    assert k[0, :8].sum() == 40.0
    # allocation pressure harvests the retired lane's blocks
    assert pool.ensure(1, 32)  # needs all 4 blocks; only 3 were free
    assert pool.retired_blocks == 0 and pool.block_table(0) == ()


# ---------------------------------------------------------------------------
# gather / scatter semantics
# ---------------------------------------------------------------------------
def _prefill_cache(tokens, cache_len=32):
    """Single-sequence cache the way ToyModel's prefill would build it."""
    cache = ToyModel().init_cache(1, cache_len)
    cache["k"] = cache["k"].at[0, : len(tokens)].set(jnp.asarray(tokens, jnp.float32))
    cache["mem"] = cache["mem"] + 1.0
    return cache


def test_admit_gather_round_trip():
    pool = _pool()
    toks = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0, 5.0]  # spans two blocks
    cache1 = _prefill_cache(toks)
    pool.ensure(2, len(toks))
    pool.admit(2, cache1)
    dense = pool.gather([0, 1, 2, 3])
    np.testing.assert_array_equal(
        np.asarray(dense["k"])[2, : len(toks)], np.asarray(toks)
    )
    assert np.asarray(dense["k"])[[0, 1, 3]].sum() == 0  # other lanes untouched
    np.testing.assert_array_equal(np.asarray(dense["mem"])[:, 2], 1.0)


def test_scatter_gather_matches_dense_scatter_slot():
    """Paged admit+gather must reproduce the dense ``_scatter_slot`` layout."""
    lanes, cache_len = 4, 32
    pool = _pool(lanes=lanes, cache_len=cache_len)
    dense = ToyModel().init_cache(lanes, cache_len)
    rng = np.random.default_rng(0)
    for lane in (0, 2, 3):
        toks = rng.integers(1, 9, size=int(rng.integers(3, 17)))
        cache1 = _prefill_cache(toks, cache_len)
        pool.ensure(lane, len(toks))
        pool.admit(lane, cache1)
        dense = {
            k: _scatter_slot(dense[k], cache1[k], slot=lane, max_batch=lanes)
            for k in dense
        }
    got = pool.gather(range(lanes))
    for key in dense:
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(dense[key]))


def test_scatter_writes_back_and_scratch_stays_zero():
    pool = _pool(lanes=2, cache_len=16, block_size=8, n_blocks=4)
    pool.ensure(0, 16)
    pool.ensure(1, 8)
    view = pool.gather([0, 1])
    view["k"] = view["k"].at[0, 11].set(7.0)  # lane 0, second block
    view["k"] = view["k"].at[1, 3].set(2.0)
    view["mem"] = view["mem"] + 0.5
    pool.scatter([0, 1], view)
    back = pool.gather([0, 1])
    assert np.asarray(back["k"])[0, 11] == 7.0
    assert np.asarray(back["k"])[1, 3] == 2.0
    assert np.asarray(back["mem"]).min() == 0.5
    # lanes with short tables read zeros past their allocation (scratch block)
    pool2 = _pool(lanes=2, cache_len=16, block_size=8, n_blocks=4)
    pool2.ensure(0, 8)  # one block only
    v = pool2.gather([0, 1])
    v["k"] = v["k"] + 1.0  # writes into the unallocated tail land in scratch
    pool2.scatter([0, 1], v)
    after = np.asarray(pool2.gather([0, 1])["k"])
    assert after[0, :8].min() == 1.0
    assert after[0, 8:].sum() == 0  # scratch block re-zeroed, tail reads clean
    assert after[1].sum() == 0


def test_dense_degenerate_mode_matches_seed_layout():
    """block_size=None keeps one dense block per lane: gather == init_cache."""
    pool = KVPool(ToyModel(), lanes=3, cache_len=16, block_size=None)
    assert pool.block_size == 16 and pool.n_blocks == 3
    base = ToyModel().init_cache(3, 16)
    for lane in range(3):
        pool.ensure(lane, 16)
    got = pool.gather(range(3))
    for key in base:
        assert got[key].shape == base[key].shape
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(base[key]))
    toks = [4.0, 2.0]
    pool.ensure(1, len(toks))
    pool.admit(1, _prefill_cache(toks, 16))
    np.testing.assert_array_equal(np.asarray(pool.gather(range(3))["k"])[1, :2], toks)


# ---------------------------------------------------------------------------
# prefix sharing (refcounts, index lifecycle)
# ---------------------------------------------------------------------------
def test_refcount_lifecycle_and_shared_release():
    pool = _pool(lanes=3, n_blocks=6)  # block_size=8
    toks = list(range(100, 120))  # 20 tokens: 2 full blocks + a partial third
    pool.ensure(0, 21)  # 3 blocks
    assert pool.register_prefix(0, toks) == 2  # only fully-covered blocks
    matched = pool.match_prefix(toks + [1, 2])
    assert matched == list(pool.block_table(0))[:2]
    pool.alias(1, matched)
    assert pool.block_refcount(matched[0]) == 2
    assert pool.lane_holds_shared(0) and pool.lane_holds_shared(1)
    assert pool.shared_blocks == 2
    # releasing the original owner frees only its private frontier block
    assert pool.release(0) == 1
    assert pool.match_prefix(toks) == matched  # index intact: lane 1 holds
    # last holder gone: blocks free and their index entries die with them
    assert pool.release(1) == 2
    assert pool.match_prefix(toks, peek=True) == []
    assert pool.shared_blocks == 0 and pool.free_blocks == 6


def test_match_prefix_stops_at_content_divergence():
    pool = _pool(lanes=2, n_blocks=6)
    toks = list(range(24))  # 3 full blocks
    pool.ensure(0, 24)
    assert pool.register_prefix(0, toks) == 3
    diverged = toks[:8] + [99] + toks[9:]
    assert pool.match_prefix(diverged, peek=True) == [pool.block_table(0)[0]]
    assert pool.match_prefix([7] + toks[1:], peek=True) == []


def test_admit_prefix_survives_reclaiming_its_own_lane():
    """A follow-up landing in the lane that owns its prefix must keep it:
    the match is reserved before the lane's previous tenant is released."""
    pool = _pool(lanes=2, n_blocks=4)
    toks = list(range(16))
    pool.ensure(0, 17)  # 2 full-body blocks + the decode frontier
    pool.register_prefix(0, toks)
    shared = list(pool.block_table(0))[:2]
    pool.retire(0)
    assert pool.admit_prefix(0, toks + [5]) == 16
    assert list(pool.block_table(0)) == shared
    assert pool.block_refcount(shared[0]) == 1  # reserved, then released once


def test_retired_lane_keeps_prefix_until_harvested():
    pool = _pool(lanes=2, n_blocks=4)
    toks = list(range(16))
    pool.ensure(0, 16)
    pool.register_prefix(0, toks)
    pool.retire(0)
    assert pool.match_prefix(toks, peek=True) == list(pool.block_table(0))
    # block pressure harvests the retired lane: the cached prefix dies
    assert pool.ensure(1, 32)
    assert pool.match_prefix(toks, peek=True) == []


def test_alias_rejects_bad_targets():
    pool = _pool(lanes=2, n_blocks=4)
    pool.ensure(0, 8)
    with pytest.raises(ValueError):
        pool.alias(1, [3])  # unallocated block
    pool.ensure(1, 8)
    with pytest.raises(ValueError):
        pool.alias(1, list(pool.block_table(0)))  # non-empty table


def test_stats_track_sharing_and_fragmentation():
    pool = _pool(lanes=2, n_blocks=6)
    pool.ensure(0, 9)  # 2 blocks = 16 slots
    pool.note_tokens(0, 9)
    st = pool.stats()
    assert st["fragmentation"] == pytest.approx(1 - 9 / 16)
    pool.match_prefix(range(8))  # miss
    toks = list(range(8))
    pool.register_prefix(0, toks)
    hit = pool.match_prefix(toks)
    pool.match_prefix(toks, peek=True)  # router probe: not counted
    pool.alias(1, hit)
    st = pool.stats()
    assert st["prefix_lookups"] == 2 and st["prefix_hits"] == 1
    assert st["prefix_hit_rate"] == 0.5 and st["prefix_hit_tokens"] == 8
    assert st["shared_blocks"] == 1


def test_replicated_leaf_passes_through_unpooled():
    pool = KVPool(ReplicatedModel(), lanes=2, cache_len=32, block_size=8)
    pool.ensure(0, 8)
    view = pool.gather([0, 1])
    assert view["rope"].shape == (16, 8)
    view["rope"] = view["rope"] + 3.0
    view["k"] = view["k"].at[0, 1, :].set(9.0)
    pool.scatter([0, 1], view)
    back = pool.gather([0, 1])
    assert np.asarray(back["rope"]).min() == 3.0  # adopted wholesale
    assert np.asarray(back["k"])[0, 1].min() == 9.0


# ---------------------------------------------------------------------------
# compiled data movement against a plain numpy model of the same writes
# ---------------------------------------------------------------------------
class LayoutModel:
    """A cache of the given leaves: name -> (shape with "B" and "T" standing
    for batch and length, dtype)."""

    def __init__(self, leaves):
        self.leaves = leaves

    def init_cache(self, b, cache_len):
        sizes = {"B": b, "T": cache_len}
        return {name: jnp.zeros(tuple(sizes.get(d, d) for d in shape), dtype)
                for name, (shape, dtype) in self.leaves.items()}


KV = {"k": ((2, "B", "T", 2, 3), jnp.float32), "v": ((2, "B", "T", 2, 3), jnp.float32)}
LAYOUTS = {
    "paged_kv": (KV, 8),
    "kv_quant": ({"k": ((2, "B", "T", 2, 3), jnp.int8), "v": ((2, "B", "T", 2, 3), jnp.int8),
                  "k_scale": ((2, "B", "T", 2), jnp.float32),
                  "v_scale": ((2, "B", "T", 2), jnp.float32)}, 8),
    "lane_state": ({**KV, "ssm": (("B", 6, 2), jnp.float32),
                    "mem": ((2, "B", 4), jnp.float32)}, 8),
    "replicated": ({**KV, "rope": ((16, 8), jnp.float32)}, 8),
    "dense": ({**KV, "ssm": (("B", 6, 2), jnp.float32)}, None),
    "split_axes": ({"k": (("B", 3, "T"), jnp.float32), "v": ((2, "T", "B"), jnp.float32)}, 8),
}


class NumpyPool:
    """The pool's bytes as plain numpy, with the batch (block or lane) axis
    first and, for paged leaves, the in-block axis second."""

    def __init__(self, pool):
        self.pool = pool
        self.leaves = [self._canon(spec, np.asarray(a)) for spec, a in zip(pool.specs, pool._store)]

    @staticmethod
    def _canon(spec, a):
        if spec.kind == "paged":
            return np.moveaxis(a, (spec.batch_axis, spec.length_axis), (0, 1)).copy()
        if spec.kind == "lane":
            return np.moveaxis(a, spec.batch_axis, 0).copy()
        return a.copy()

    def view_leaves(self, cache):
        return [self._canon(s, np.asarray(x)) for s, x in
                zip(self.pool.specs, self.pool.treedef.flatten_up_to(cache))]

    def table(self, lane):
        t = list(self.pool.block_table(lane))
        return t + [0] * (self.pool.blocks_per_lane - len(t))

    def zero(self, blk):
        for spec, a in zip(self.pool.specs, self.leaves):
            if spec.kind == "paged":
                a[blk] = 0

    def write(self, lanes, cache, *, admit=False):
        bs = self.pool.block_size
        for i, (spec, v) in enumerate(zip(self.pool.specs, self.view_leaves(cache))):
            a = self.leaves[i]
            for row, lane in enumerate(lanes):
                if spec.kind == "lane":
                    a[lane] = v[row]
                elif spec.kind == "paged":
                    blocks = self.pool.block_table(lane) if admit else self.table(lane)
                    for j, blk in enumerate(blocks):
                        a[blk] = v[row, j * bs:(j + 1) * bs]
            if spec.kind == "replicated" and not admit:
                self.leaves[i] = v.copy()
        if not admit:
            self.zero(0)

    def gather(self, lanes):
        bs, out = self.pool.block_size, []
        for spec, a in zip(self.pool.specs, self.leaves):
            if spec.kind == "paged":
                out.append(np.stack([np.concatenate([a[b] for b in self.table(lane)])
                                     for lane in lanes]))
            elif spec.kind == "lane":
                out.append(a[list(lanes)])
            else:
                out.append(a)
        return out

    def check(self):
        for spec, a, got in zip(self.pool.specs, self.leaves, self.pool._store):
            np.testing.assert_array_equal(self._canon(spec, np.asarray(got)), a, err_msg=spec.path)
            if spec.kind == "paged":
                assert not np.asarray(self._canon(spec, np.asarray(got)))[0].any()  # scratch


def _random_cache(model, rng, b, cache_len):
    def fill(x):
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-100, 100, size=x.shape), jnp.int8)
        return jnp.asarray(rng.integers(1, 50, size=x.shape), x.dtype)

    return jax.tree_util.tree_map(fill, model.init_cache(b, cache_len))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_compiled_programs_match_a_numpy_model_of_the_writes(layout):
    leaves, block_size = LAYOUTS[layout]
    model, rng, cache_len = LayoutModel(leaves), np.random.default_rng(7), 32
    pool = KVPool(model, lanes=3, cache_len=cache_len, block_size=block_size)
    ref = NumpyPool(pool)

    def ensure(lane, n):
        before = set(pool.block_table(lane))
        assert pool.ensure(lane, n)
        for blk in set(pool.block_table(lane)) - before:
            ref.zero(blk)

    def gathered_equal(lanes):
        for got, want in zip(ref.view_leaves(pool.gather(lanes)), ref.gather(lanes)):
            np.testing.assert_array_equal(got, want)

    ensure(0, 20)
    ensure(2, 9)
    cache1 = _random_cache(model, rng, 1, cache_len)
    pool.admit(0, cache1)
    ref.write([0], cache1, admit=True)
    ref.check()
    view = _random_cache(model, rng, 3, cache_len)  # lane 1 has no blocks: scratch
    pool.scatter([0, 1, 2], view)
    ref.write([0, 1, 2], view)
    ref.check()
    gathered_equal([2, 0])
    pool.release(0)
    ensure(1, cache_len)  # recycles lane 0's written blocks: zeroed again
    pool.reset_lane_state(2)
    for spec, a in zip(pool.specs, ref.leaves):
        if spec.kind == "lane":
            a[2] = 0
    ref.check()
    view = _random_cache(model, rng, 2, cache_len)
    pool.scatter([1, 2], view)
    ref.write([1, 2], view)
    ref.check()
    gathered_equal([0, 1, 2])
    assert pool.stats()["programs_built"] > 0


def test_pool_programs_are_built_once_per_lane_count():
    pool = KVPool(LayoutModel(LAYOUTS["lane_state"][0]), lanes=4, cache_len=32, block_size=8)
    for lane in range(4):
        pool.ensure(lane, 9)
    lanes = [0, 1, 2, 3]
    view = pool.gather(lanes)
    pool.scatter(lanes, view)
    built = pool.stats()["programs_built"]
    for _ in range(9):  # the tenth scatter at this width builds nothing
        view = pool.gather(lanes)
        pool.scatter(lanes, view)
    assert pool.stats()["programs_built"] == built
    assert pool.ensure(3, 30)  # new block ids: zeroed by the program built above
    pool.reset_lane_state(1)
    pool.reset_lane_state(2)
    reset = pool.stats()["programs_built"]
    assert reset == built + 1
    pool.release(0)
    assert pool.ensure(0, 32) and pool.ensure(1, 17)
    pool.reset_lane_state(0)
    pool.scatter(lanes, pool.gather(lanes))
    assert pool.stats()["programs_built"] == reset
    pool.gather([2, 3])  # a new lane count is one more program
    assert pool.stats()["programs_built"] == reset + 1


def test_leaves_sharing_one_buffer_get_their_own():
    """An init_cache may return one zeros array for two leaves; the donating
    programs need a buffer per leaf."""

    class SharedZeros:
        def init_cache(self, b, cache_len):
            z = jnp.zeros((b, cache_len, 2), jnp.float32)
            return {"k": z, "v": z}

    pool = KVPool(SharedZeros(), lanes=2, cache_len=16, block_size=8)
    pool.ensure(0, 16)
    view = pool.gather([0, 1])
    view["k"] = view["k"] + 1.0
    pool.scatter([0, 1], view)
    back = pool.gather([0, 1])
    assert np.asarray(back["k"])[0].min() == 1.0
    assert not np.asarray(back["v"]).any()
