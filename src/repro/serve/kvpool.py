"""Block-allocated paged KV cache for the serving tier.

The seed engine owned one dense ``model.init_cache(max_batch, cache_len)``
pytree: every slot paid for ``cache_len`` tokens of cache whether it held a
7-token prompt or none at all.  ``KVPool`` replaces that with a classic paged
layout (vLLM-style, adapted to "models consume dense caches"):

* cache storage is a pool of ``n_blocks`` fixed-size **blocks** of
  ``block_size`` tokens each, plus one permanently-zero **scratch block**
  (id 0) used to pad partially-filled lanes;
* every live request (a **lane**) owns an ordered **block table** — the
  blocks that back its tokens, allocated on admit and grown one block at a
  time as decode advances;
* models never see blocks: ``gather(lane_ids)`` materialises a dense
  ``(len(lane_ids), cache_len, ...)`` decode view from the tables, and
  ``scatter(lane_ids, cache)`` writes the updated view back into the pool.

Cache pytrees are classified *structurally*, with no per-model knowledge, by
probing ``model.init_cache`` at two (batch, length) points and watching which
axes scale:

* **paged** leaves have both a batch axis and a length axis that tracks
  ``cache_len`` exactly (k/v token caches) — these live in the block pool;
* **lane** leaves have a batch axis but no scaling length axis (recurrent
  WKV/SSM state, sliding-window rings shorter than ``cache_len``, cross-
  attention caches) — these live in a per-lane array, one row per lane;
* **replicated** leaves have neither (shared constants) — stored once.

The exact-scaling test is what makes sliding-window leaves safe: a Hymba SWA
ring of ``min(window, cache_len)`` tokens only classifies as paged when it
tracks *both* probe lengths, i.e. when it genuinely is a full-length cache.

Invariant relied on for byte-identity with the dense engine: models write
cache content only at positions ``< position`` and mask reads beyond it, and
freshly-initialised cache content is zero — so zero-filled growth blocks are
indistinguishable from a dense slot's untouched tail.

**Data movement.**  Every write or copy of pool storage is one compiled
program, built on first use and kept: ``kvpool_gather_w<n>`` and
``kvpool_scatter_w<n>`` per lane count, ``kvpool_zero`` (re-zero a block),
``kvpool_admit`` and ``kvpool_reset_lane``.  Block and lane ids are traced
arguments, so a new id never builds a program (``stats()["programs_built"]``
counts them), and the programs that write the store donate the leaves they
rewrite, so the pool is updated in place: a step costs the bytes of its
dense view twice (gather, scatter), never a copy of the whole pool.  Blocks
move one at a time along a paged leaf's batch axis; only a leaf whose length
axis does not follow its batch axis moves axes, and then only in the view.

``block_size=None`` degenerates to one ``cache_len``-sized block per lane —
the dense layout, byte-identical to the seed engine (and the default for the
``ServingEngine`` constructor, so existing callers see no change).

**Prefix sharing.**  Blocks are refcounted and indexed by a *chain hash* of
the token ids they cache: ``h_i = hash((h_{i-1}, tokens[i*bs:(i+1)*bs]))``.
A lane that finished prefilling a prompt registers its fully-covered blocks
(:meth:`register_prefix`); a later submit with the same leading tokens finds
the longest indexed run (:meth:`match_prefix`) and aliases those blocks into
its own table (:meth:`alias`), skipping prefill for the shared span.  Shared
blocks are copy-on-write at block granularity: only *full* blocks whose
content can never be rewritten are ever indexed (the block holding a lane's
last/decode position stays private), so siblings only ever re-write shared
blocks with byte-identical content.  ``release`` decrements refcounts and
reclaims a block only at zero — index entries die with the block, so a
recycled block id can never serve a stale prefix.  Retired-but-unreclaimed
lanes keep their blocks indexed, so a popular system prompt survives its
original request (until block pressure harvests it).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import trace

__all__ = ["KVPool", "LeafSpec"]

_PROBE_BATCHES = (3, 5, 7, 11, 13)


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Structural classification of one cache leaf."""

    path: str
    kind: str  # "paged" | "lane" | "replicated"
    batch_axis: int | None
    length_axis: int | None


def _flatten_with_paths(tree):
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [jax.tree_util.keystr(p) for p, _ in leaves_with_paths]
    leaves = [leaf for _, leaf in leaves_with_paths]
    return paths, leaves, treedef


def probe_cache_layout(init_cache, cache_len: int, block_size: int):
    """Classify every leaf of ``init_cache(batch, length)`` by axis scaling.

    Returns ``(specs, treedef)`` where ``specs[i]`` classifies the i-th leaf
    in flatten order and ``treedef`` rebuilds the pytree from a leaf list.
    """
    length_b = block_size if block_size != cache_len else max(1, cache_len // 2)
    if length_b == cache_len:
        raise ValueError(f"cache_len={cache_len} too small to probe a paged layout")
    batches = [b for b in _PROBE_BATCHES if b not in (cache_len, length_b)]
    pb_a, pb_b = batches[0], batches[1]

    paths_a, leaves_a, treedef = _flatten_with_paths(init_cache(pb_a, cache_len))
    _, leaves_b, treedef_b = _flatten_with_paths(init_cache(pb_b, length_b))
    if treedef != treedef_b:
        raise ValueError(
            "init_cache structure changes with (batch, length); cannot page it"
        )

    specs = []
    for path, la, lb in zip(paths_a, leaves_a, leaves_b):
        sa, sb = np.shape(la), np.shape(lb)
        if len(sa) != len(sb):
            raise ValueError(f"cache leaf {path} changes rank with (batch, length)")
        batch_axis = next(
            (i for i in range(len(sa)) if sa[i] == pb_a and sb[i] == pb_b), None
        )
        length_axis = None
        if batch_axis is not None:
            length_axis = next(
                (
                    i
                    for i in range(len(sa))
                    if i != batch_axis and sa[i] == cache_len and sb[i] == length_b
                ),
                None,
            )
        if batch_axis is None:
            kind = "replicated"
        elif length_axis is None:
            kind = "lane"
        else:
            kind = "paged"
        specs.append(LeafSpec(path, kind, batch_axis, length_axis))
    return tuple(specs), treedef


class KVPool:
    """Paged KV storage: block pool + per-lane block tables + lane state.

    ``lanes`` bounds concurrent decode residents (the engine's ``max_batch``);
    ``n_blocks`` bounds total live cache tokens (``n_blocks * block_size``).
    With the defaults (``block_size=None``) the pool is layout- and
    byte-identical to the seed engine's dense ``init_cache(lanes, cache_len)``.
    """

    def __init__(self, model, *, lanes: int, cache_len: int,
                 block_size: int | None = None, n_blocks: int | None = None):
        if lanes < 1:
            raise ValueError("lanes must be >= 1")
        self.lanes = int(lanes)
        self.cache_len = int(cache_len)
        self.block_size = int(block_size) if block_size else self.cache_len
        if self.cache_len % self.block_size:
            raise ValueError(
                f"cache_len={cache_len} not divisible by block_size={self.block_size}"
            )
        self.blocks_per_lane = self.cache_len // self.block_size
        self.n_blocks = int(n_blocks) if n_blocks else self.lanes * self.blocks_per_lane
        if self.n_blocks < self.blocks_per_lane:
            raise ValueError(
                f"n_blocks={self.n_blocks} cannot back even one full lane "
                f"({self.blocks_per_lane} blocks)"
            )

        self.specs, self.treedef = probe_cache_layout(
            model.init_cache, self.cache_len, self.block_size
        )
        # Block pool: batch axis indexes blocks; id 0 is the always-zero
        # scratch block that pads unallocated table rows in gathered views.
        _, pool_leaves, _ = _flatten_with_paths(
            model.init_cache(self.n_blocks + 1, self.block_size)
        )
        # Lane state (and replicated leaves) at the engine's dense shape.
        _, lane_leaves, _ = _flatten_with_paths(
            model.init_cache(self.lanes, self.cache_len)
        )
        self._store = [
            pool_leaves[i] if spec.kind == "paged" else lane_leaves[i]
            for i, spec in enumerate(self.specs)
        ]
        # The programs below donate store leaves, so no two may share a
        # buffer (an init_cache may hand out one zeros array twice).
        seen: set[int] = set()
        for i, leaf in enumerate(self._store):
            if id(leaf) in seen:
                self._store[i] = jnp.copy(leaf)
            seen.add(id(self._store[i]))
        # Free list popped from the tail: ids come out ascending (1, 2, ...).
        self._free = list(range(self.n_blocks, 0, -1))
        self._tables: list[list[int]] = [[] for _ in range(self.lanes)]
        # Lanes whose resident finished but whose blocks haven't been
        # reclaimed yet: content stays readable (dense-engine parity for
        # post-run cache inspection) until an allocation actually needs it.
        self._retired: set[int] = set()
        # Per-block refcounts (index 0 = scratch, never allocated).  A fresh
        # allocation starts at 1; aliasing a shared prefix increments; release
        # decrements and only refcount 0 returns a block to the free list.
        self._rc = [0] * (self.n_blocks + 1)
        # Tokens actually resident per lane (for fragmentation accounting).
        self._lane_tokens = [0] * self.lanes
        # Prefix index: chain hash over block token content -> block id, plus
        # the reverse map so freeing a block drops its index entry.
        self._prefix_index: dict[int, int] = {}
        self._block_hash: dict[int, int] = {}
        self._prefix_lookups = 0
        self._prefix_hits = 0
        self._prefix_hit_tokens = 0
        # Store positions by leaf kind, and the compiled programs that move
        # pool data, by name (see "data movement" below).
        self._by_kind = {
            kind: tuple(i for i, s in enumerate(self.specs) if s.kind == kind)
            for kind in ("paged", "lane", "replicated")
        }
        self._programs: dict[str, object] = {}
        self._programs_built = 0

    # -- block accounting ---------------------------------------------------
    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def block_table(self, lane: int) -> tuple[int, ...]:
        return tuple(self._tables[lane])

    def lane_capacity(self, lane: int) -> int:
        return len(self._tables[lane]) * self.block_size

    def blocks_needed(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.block_size))

    def block_refcount(self, blk: int) -> int:
        return self._rc[blk]

    def lane_holds_shared(self, lane: int) -> bool:
        """True if any block in ``lane``'s table is aliased by another lane."""
        return any(self._rc[blk] > 1 for blk in self._tables[lane])

    @property
    def retired_blocks(self) -> int:
        # Only blocks a harvest would actually free: refcount-1 residents of
        # retired lanes.  Shared blocks survive their retired owner.
        return sum(
            1
            for lane in self._retired
            for blk in self._tables[lane]
            if self._rc[blk] == 1
        )

    def retire(self, lane: int) -> None:
        """Mark a finished lane reclaimable without scrubbing it yet."""
        if self._tables[lane]:
            self._retired.add(lane)

    def _harvest(self, need: int) -> None:
        """Reclaim retired lanes (lowest lane id first) until ``need`` free
        blocks exist or no retired lane remains."""
        while len(self._free) < need and self._retired:
            lane = min(self._retired)
            self.release(lane)

    def can_fit(self, n_tokens: int) -> bool:
        """Could a fresh lane for ``n_tokens`` be admitted right now?"""
        return self.blocks_needed(n_tokens) <= len(self._free) + self.retired_blocks

    def ensure(self, lane: int, n_tokens: int) -> bool:
        """Grow ``lane``'s table to cover ``n_tokens``; False if pool is dry.

        Newly-allocated blocks are zeroed so the gathered view of the lane's
        unwritten tail matches a dense slot's untouched (zero) tail.
        """
        table = self._tables[lane]
        need = self.blocks_needed(n_tokens) - len(table)
        if need <= 0:
            return True
        if need > len(self._free):
            self._harvest(need)
        if need > len(self._free):
            return False
        for _ in range(need):
            blk = self._free.pop()
            self._rc[blk] = 1
            self._zero_block(blk)
            table.append(blk)
        return True

    def note_tokens(self, lane: int, n_tokens: int) -> None:
        """Record how many token slots ``lane`` actually uses (monotone)."""
        cap = len(self._tables[lane]) * self.block_size
        self._lane_tokens[lane] = min(max(self._lane_tokens[lane], n_tokens), cap)

    def release(self, lane: int) -> int:
        """Drop ``lane``'s claim on its blocks (finish or preemption).

        Each block's refcount is decremented; only blocks reaching zero are
        returned to the free list (a sibling aliasing a shared prefix keeps
        it alive).  Returns the number of blocks actually freed.
        """
        self._retired.discard(lane)
        table = self._tables[lane]
        dropped = []
        for blk in table:
            self._rc[blk] -= 1
            if self._rc[blk] == 0:
                dropped.append(blk)
                h = self._block_hash.pop(blk, None)
                if h is not None and self._prefix_index.get(h) == blk:
                    del self._prefix_index[h]
        # Reverse so pop() reuses the lane's lowest block id first.
        self._free.extend(reversed(dropped))
        self._tables[lane] = []
        self._lane_tokens[lane] = 0
        return len(dropped)

    # -- prefix sharing -----------------------------------------------------
    def _chain_hashes(self, tokens) -> list[int]:
        """Chain hash per *fully covered* block of ``tokens`` (token ids)."""
        bs = self.block_size
        toks = [int(t) for t in tokens]
        out: list[int] = []
        h = 0x9E3779B9  # fixed chain seed
        for i in range(len(toks) // bs):
            h = hash((h, tuple(toks[i * bs : (i + 1) * bs])))
            out.append(h)
        return out

    def match_prefix(self, tokens, *, peek: bool = False) -> list[int]:
        """Longest indexed block run caching a prefix of ``tokens``.

        Returns the block ids, in prefix order.  ``peek=True`` skips the
        hit-rate counters (used by router affinity probes so observability
        reflects actual admissions only).
        """
        run: list[int] = []
        for h in self._chain_hashes(tokens):
            blk = self._prefix_index.get(h)
            if blk is None:
                break
            run.append(blk)
        if not peek:
            self._prefix_lookups += 1
            if run:
                self._prefix_hits += 1
                self._prefix_hit_tokens += len(run) * self.block_size
        return run

    def register_prefix(self, lane: int, tokens) -> int:
        """Index ``lane``'s blocks that fully cover a prefix of ``tokens``.

        Only blocks whose ``block_size`` tokens are all real (never to be
        rewritten) are indexed — the copy-on-write rule: the block holding
        the lane's decode frontier stays private.  First registration of a
        chain hash wins; re-registering identical content is a no-op.
        Returns the number of shareable blocks.
        """
        with trace.span("kvpool.index", tokens=len(tokens)):
            table = self._tables[lane]
            n = 0
            for h, blk in zip(self._chain_hashes(tokens), table):
                if h not in self._prefix_index:
                    self._prefix_index[h] = blk
                    self._block_hash[blk] = h
                n += 1
            return n

    def admit_prefix(self, lane: int, tokens) -> int:
        """Release ``lane``'s previous tenant and seed it with the longest
        cached prefix of ``tokens``, atomically.

        The outgoing (retired) tenant may itself own the matched blocks — a
        follow-up request with the same system prompt admitted into its old
        lane — so the match is reserved (incref) *before* the release that
        would otherwise free it.  Returns the number of prefix tokens served
        from cache.
        """
        with trace.span("kvpool.index", tokens=len(tokens)):
            matched = self.match_prefix(tokens)
            for blk in matched:
                self._rc[blk] += 1  # reserve against the release below
            self.release(lane)
            self._tables[lane] = list(matched)
            self._lane_tokens[lane] = len(matched) * self.block_size
            return len(matched) * self.block_size

    def alias(self, lane: int, blocks) -> None:
        """Seed a fresh lane's table with shared ``blocks`` (incref each)."""
        table = self._tables[lane]
        if table:
            raise ValueError(f"alias() requires an empty table (lane {lane})")
        for blk in blocks:
            if self._rc[blk] < 1:
                raise ValueError(f"alias() of unallocated block {blk}")
            self._rc[blk] += 1
            table.append(blk)
        self._lane_tokens[lane] = len(table) * self.block_size

    def reset_lane_state(self, lane: int) -> None:
        """Zero ``lane``'s row of every lane-kind leaf (fresh-cache state).

        Used by aliased admissions: paged content arrives via shared blocks,
        but recurrent/lane state must start from ``init_cache`` zeros.
        """
        if not self._by_kind["lane"]:
            return
        prog = self._program("kvpool_reset_lane", self._reset_body, donate=(0,))
        self._put("lane", prog(self._leaves("lane"), np.int32(lane)))

    def _reset_body(self, lanes, lane):
        return tuple(
            arr.at[_on_axis(spec.batch_axis, lane)].set(0)
            for spec, arr in zip(self._specs("lane"), lanes)
        )

    @property
    def shared_blocks(self) -> int:
        """Blocks currently aliased by more than one lane table."""
        return sum(1 for blk in range(1, self.n_blocks + 1) if self._rc[blk] > 1)

    def stats(self) -> dict:
        alloc_slots = sum(len(t) for t in self._tables) * self.block_size
        used_slots = min(sum(self._lane_tokens), alloc_slots)
        return {
            "n_blocks": self.n_blocks,
            "block_size": self.block_size,
            "free_blocks": self.free_blocks,
            "retired_blocks": self.retired_blocks,
            "used_blocks": self.used_blocks,
            "utilization": self.used_blocks / self.n_blocks,
            "fragmentation": (
                1.0 - used_slots / alloc_slots if alloc_slots else 0.0
            ),
            "shared_blocks": self.shared_blocks,
            "prefix_lookups": self._prefix_lookups,
            "prefix_hits": self._prefix_hits,
            "prefix_hit_tokens": self._prefix_hit_tokens,
            "prefix_hit_rate": (
                self._prefix_hits / self._prefix_lookups
                if self._prefix_lookups
                else 0.0
            ),
            "lanes": self.lanes,
            "lanes_used": sum(1 for t in self._tables if t),
            # Pool programs compiled so far: constant once every lane count
            # has been seen, since ids are traced, never static.
            "programs_built": self._programs_built,
        }

    def counters(self) -> dict:
        """The model's program counters, to the host: the leaves of the
        cache's ``counters`` group (replicated, summed on the device by the
        decode and chunk programs), by name.  Read once, not per step: it
        waits for the device."""
        tree = jax.tree_util.tree_unflatten(self.treedef, self._store)
        group = tree.get("counters", {}) if isinstance(tree, dict) else {}
        return {name: np.asarray(leaf) for name, leaf in group.items()}

    # -- data movement ------------------------------------------------------
    # Every write or copy of pool storage is one compiled program, built on
    # first use and kept.  Programs that write the store donate the leaves
    # they update, so XLA updates them in place; block and lane ids are
    # traced arguments, so a new id never builds a new program.
    def _program(self, name: str, body, donate: tuple[int, ...] = ()):
        prog = self._programs.get(name)
        if prog is None:
            def program(*args):
                self._programs_built += 1  # the body runs once per trace
                return body(*args)

            # jit names the module ``jit_<name>``: how a device trace tells
            # the pool's programs apart.
            program.__name__ = program.__qualname__ = name
            prog = self._programs[name] = jax.jit(program, donate_argnums=donate)
        return prog

    def _leaves(self, kind: str) -> tuple:
        return tuple(self._store[i] for i in self._by_kind[kind])

    def _put(self, kind: str, leaves) -> None:
        for i, leaf in zip(self._by_kind[kind], leaves):
            self._store[i] = leaf

    def _specs(self, kind: str) -> list[LeafSpec]:
        return [self.specs[i] for i in self._by_kind[kind]]

    def _zero_block(self, blk: int) -> None:
        if not self._by_kind["paged"]:
            return
        prog = self._program("kvpool_zero", self._zero_body, donate=(0,))
        self._put("paged", prog(self._leaves("paged"), np.int32(blk)))

    def _zero_body(self, paged, blk):
        return tuple(
            arr.at[_on_axis(spec.batch_axis, blk)].set(0)
            for spec, arr in zip(self._specs("paged"), paged)
        )

    def _padded_tables(self, lane_ids) -> np.ndarray:
        """(W, blocks_per_lane) block ids, scratch-0 padded."""
        bt = np.zeros((len(lane_ids), self.blocks_per_lane), dtype=np.int32)
        for row, lane in enumerate(lane_ids):
            table = self._tables[lane]
            bt[row, : len(table)] = table
        return bt

    def admit(self, lane: int, cache1) -> None:
        """Write a batch-1 prefill cache (full ``cache_len`` length) into
        ``lane``'s allocated blocks and lane-state row.

        Only the lane's allocated blocks are written; content beyond them is
        zero in ``cache1`` by the masking invariant (see module docstring).
        The table is padded to ``blocks_per_lane`` and its length is a traced
        argument, so one program serves every length.
        """
        with trace.span("kvpool.admit", lanes=1):
            leaves = self.treedef.flatten_up_to(cache1)
            table = self._tables[lane]
            ids = np.zeros(self.blocks_per_lane, dtype=np.int32)
            ids[: len(table)] = table
            prog = self._program("kvpool_admit", self._admit_body, donate=(0, 1))
            paged, lanes = prog(
                self._leaves("paged"), self._leaves("lane"),
                tuple(leaves[i] for i in self._by_kind["paged"]),
                tuple(leaves[i] for i in self._by_kind["lane"]),
                ids, np.int32(len(table)), np.int32(lane),
            )
            self._put("paged", paged)
            self._put("lane", lanes)
            # Replicated leaves are not written: dense-engine parity,
            # _scatter_slot kept the pool's value.

    def _admit_body(self, paged, lanes, new_paged, new_lanes, ids, count, lane):
        blocks = [
            _regroup(spec, leaf, self.blocks_per_lane, self.block_size)
            for spec, leaf in zip(self._specs("paged"), new_paged)
        ]
        return (
            tuple(self._copy_in(paged, blocks, ids, count)),
            tuple(
                lax.dynamic_update_slice_in_dim(
                    arr, leaf.astype(arr.dtype), lane, axis=spec.batch_axis
                )
                for spec, arr, leaf in zip(self._specs("lane"), lanes, new_lanes)
            ),
        )

    def gather(self, lane_ids) -> object:
        """Materialise the dense decode view for ``lane_ids``.

        Paged leaves are assembled from block tables (scratch-padded rows
        read as zero); lane leaves are row-gathered; replicated leaves pass
        through untouched.  One program per lane count; the store is read,
        not donated.
        """
        lane_ids = list(lane_ids)
        with trace.span("kvpool.gather", lanes=len(lane_ids)):
            prog = self._program(f"kvpool_gather_w{len(lane_ids)}", self._gather_body)
            paged, lanes = prog(
                self._leaves("paged"), self._leaves("lane"),
                self._padded_tables(lane_ids).reshape(-1),
                np.asarray(lane_ids, dtype=np.int32),
            )
            out = list(self._store)  # replicated leaves pass through
            for kind, got in (("paged", paged), ("lane", lanes)):
                for i, leaf in zip(self._by_kind[kind], got):
                    out[i] = leaf
            return jax.tree_util.tree_unflatten(self.treedef, out)

    def _gather_body(self, paged, lanes, idx, rows):
        return (
            tuple(
                _regroup(spec, got, rows.shape[0], self.cache_len)
                for spec, got in zip(self._specs("paged"), self._copy_out(paged, idx))
            ),
            tuple(
                jnp.take(arr, rows, axis=spec.batch_axis, mode="clip")
                for spec, arr in zip(self._specs("lane"), lanes)
            ),
        )

    # A loop of one-block slices reads and writes each block once.  XLA's
    # own gather and scatter on a block axis behind the layer axis
    # (jnp.take, .at[].set) go through a block-major copy of the whole view
    # and a transpose: 11 and 10 ms against 6 and 6 (gather, scatter at the
    # chat cell's size, timed on one v5e).
    def _copy_out(self, paged, idx) -> list:
        """Blocks ``idx`` of each paged leaf, on its batch axis."""
        specs = self._specs("paged")
        out = [
            jnp.zeros(a.shape[:s.batch_axis] + idx.shape + a.shape[s.batch_axis + 1:], a.dtype)
            for s, a in zip(specs, paged)
        ]

        def block(i, out):
            return [
                lax.dynamic_update_slice_in_dim(
                    o, lax.dynamic_slice_in_dim(a, idx[i], 1, s.batch_axis), i, s.batch_axis
                )
                for s, a, o in zip(specs, paged, out)
            ]

        return lax.fori_loop(0, idx.shape[0], block, out)

    def _copy_in(self, paged, blocks, idx, count) -> list:
        """Each paged leaf with block ``idx[i]`` set to block ``i`` of its
        ``blocks``, for ``i < count``; in place where the leaf is donated."""
        specs = self._specs("paged")

        def block(i, out):
            return [
                lax.dynamic_update_slice_in_dim(
                    o, lax.dynamic_slice_in_dim(b, i, 1, s.batch_axis), idx[i], s.batch_axis
                )
                for s, o, b in zip(specs, out, blocks)
            ]

        return lax.fori_loop(0, count, block, list(paged))

    def scatter(self, lane_ids, cache) -> None:
        """Write an updated dense view back into the pool.

        The scratch block absorbs writes from unallocated table rows and is
        re-zeroed in the same program, so later gathers still read zeros
        there.  One program per lane count, donating the paged and lane
        leaves it rewrites.
        """
        lane_ids = list(lane_ids)
        with trace.span("kvpool.scatter", lanes=len(lane_ids)):
            leaves = self.treedef.flatten_up_to(cache)
            prog = self._program(
                f"kvpool_scatter_w{len(lane_ids)}", self._scatter_body, donate=(0, 1)
            )
            paged, lanes = prog(
                self._leaves("paged"), self._leaves("lane"),
                tuple(leaves[i] for i in self._by_kind["paged"]),
                tuple(leaves[i] for i in self._by_kind["lane"]),
                self._padded_tables(lane_ids).reshape(-1),
                np.asarray(lane_ids, dtype=np.int32),
            )
            self._put("paged", paged)
            self._put("lane", lanes)
            # Dense-engine parity: the decode output's replicated leaves
            # become the pool wholesale.
            for i in self._by_kind["replicated"]:
                self._store[i] = leaves[i]

    def _scatter_body(self, paged, lanes, view_paged, view_lanes, idx, rows):
        specs = self._specs("paged")
        blocks = [
            _regroup(spec, leaf, idx.shape[0], self.block_size)
            for spec, leaf in zip(specs, view_paged)
        ]
        # Re-zero scratch block 0, which absorbed the padded rows' writes.
        return tuple(
            a.at[_on_axis(spec.batch_axis, 0)].set(0)
            for spec, a in zip(specs, self._copy_in(paged, blocks, idx, idx.shape[0]))
        ), tuple(
            arr.at[_on_axis(spec.batch_axis, rows)].set(leaf)
            for spec, arr, leaf in zip(self._specs("lane"), lanes, view_lanes)
        )


def _on_axis(axis: int, index) -> tuple:
    """Index tuple selecting ``index`` on ``axis`` (every other axis whole)."""
    return (slice(None),) * axis + (index,)


def _regroup(spec: LeafSpec, x, outer: int, inner: int):
    """Reshape a paged leaf's (batch, length) axis pair to ``(outer, inner)``:
    a dense view's ``(rows, cache_len)`` to ``(blocks, block_size)`` and back."""
    b, l = spec.batch_axis, spec.length_axis
    if l == b + 1:  # every model's k/v: a reshape, no data movement
        return x.reshape(x.shape[:b] + (outer, inner) + x.shape[b + 2:])
    moved = jnp.moveaxis(x, (b, l), (0, 1))
    moved = moved.reshape((outer, inner) + moved.shape[2:])
    return jnp.moveaxis(moved, (0, 1), (b, l))
