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
        for i, spec in enumerate(self.specs):
            if spec.kind != "lane":
                continue
            arr = self._store[i]
            idx = [slice(None)] * arr.ndim
            idx[spec.batch_axis] = lane
            self._store[i] = arr.at[tuple(idx)].set(0)

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
        }

    # -- data movement ------------------------------------------------------
    def _zero_block(self, blk: int) -> None:
        for i, spec in enumerate(self.specs):
            if spec.kind != "paged":
                continue
            arr = self._store[i]
            idx = [slice(None)] * arr.ndim
            idx[spec.batch_axis] = blk
            self._store[i] = arr.at[tuple(idx)].set(0)

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
        """
        with trace.span("kvpool.admit", lanes=1):
            leaves = self.treedef.flatten_up_to(cache1)
            table = self._tables[lane]
            ids = np.asarray(table, dtype=np.int32)
            for i, (spec, leaf) in enumerate(zip(self.specs, leaves)):
                if spec.kind == "replicated":
                    # Dense-engine parity: _scatter_slot kept the pool's value.
                    continue
                arr = self._store[i]
                if spec.kind == "lane":
                    idx = [slice(None)] * arr.ndim
                    idx[spec.batch_axis] = slice(lane, lane + 1)
                    self._store[i] = arr.at[tuple(idx)].set(leaf)
                    continue
                # paged: (…,1,…,cache_len,…) -> (blocks_per_lane, block_size, rest)
                canon = jnp.moveaxis(leaf, (spec.batch_axis, spec.length_axis), (0, 1))[0]
                chunks = canon.reshape(
                    (self.blocks_per_lane, self.block_size) + canon.shape[1:]
                )
                pooled = jnp.moveaxis(arr, (spec.batch_axis, spec.length_axis), (0, 1))
                pooled = pooled.at[ids].set(chunks[: len(table)])
                self._store[i] = jnp.moveaxis(
                    pooled, (0, 1), (spec.batch_axis, spec.length_axis)
                )

    def gather(self, lane_ids) -> object:
        """Materialise the dense decode view for ``lane_ids``.

        Paged leaves are assembled from block tables (scratch-padded rows
        read as zero); lane leaves are row-gathered; replicated leaves pass
        through untouched.
        """
        lane_ids = list(lane_ids)
        with trace.span("kvpool.gather", lanes=len(lane_ids)):
            idx = jnp.asarray(self._padded_tables(lane_ids).reshape(-1))
            rows = jnp.asarray(np.asarray(lane_ids, dtype=np.int32))
            out = []
            for spec, arr in zip(self.specs, self._store):
                if spec.kind == "replicated":
                    out.append(arr)
                elif spec.kind == "lane":
                    out.append(jnp.take(arr, rows, axis=spec.batch_axis))
                else:
                    pooled = jnp.moveaxis(
                        arr, (spec.batch_axis, spec.length_axis), (0, 1)
                    )
                    got = jnp.take(pooled, idx, axis=0)  # (W*bpl, block, rest)
                    got = got.reshape(
                        (len(lane_ids), self.blocks_per_lane * self.block_size)
                        + got.shape[2:]
                    )
                    out.append(
                        jnp.moveaxis(got, (0, 1), (spec.batch_axis, spec.length_axis))
                    )
            return jax.tree_util.tree_unflatten(self.treedef, out)

    def scatter(self, lane_ids, cache) -> None:
        """Write an updated dense view back into the pool.

        The scratch block absorbs writes from unallocated table rows and is
        re-zeroed afterwards so later gathers still read zeros there.
        """
        lane_ids = list(lane_ids)
        with trace.span("kvpool.scatter", lanes=len(lane_ids)):
            idx = jnp.asarray(self._padded_tables(lane_ids).reshape(-1))
            rows = jnp.asarray(np.asarray(lane_ids, dtype=np.int32))
            leaves = self.treedef.flatten_up_to(cache)
            touched_scratch = False
            for i, (spec, leaf) in enumerate(zip(self.specs, leaves)):
                arr = self._store[i]
                if spec.kind == "replicated":
                    # Dense-engine parity: the decode output's replicated leaves
                    # became the pool wholesale.
                    self._store[i] = leaf
                elif spec.kind == "lane":
                    moved = jnp.moveaxis(arr, spec.batch_axis, 0)
                    new = jnp.moveaxis(leaf, spec.batch_axis, 0)
                    moved = moved.at[rows].set(new)
                    self._store[i] = jnp.moveaxis(moved, 0, spec.batch_axis)
                else:
                    pooled = jnp.moveaxis(
                        arr, (spec.batch_axis, spec.length_axis), (0, 1)
                    )
                    canon = jnp.moveaxis(
                        leaf, (spec.batch_axis, spec.length_axis), (0, 1)
                    )
                    chunks = canon.reshape(
                        (len(lane_ids) * self.blocks_per_lane, self.block_size)
                        + canon.shape[2:]
                    )
                    pooled = pooled.at[idx].set(chunks)
                    self._store[i] = jnp.moveaxis(
                        pooled, (0, 1), (spec.batch_axis, spec.length_axis)
                    )
                    touched_scratch = True
            if touched_scratch:
                self._zero_block(0)
