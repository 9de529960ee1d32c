"""Paged KV cache: a block-table pool shared by the serving stack.

This is the memory half of the serving architecture (SERVING.md §2): KV
state lives in fixed-size *blocks* drawn from one pool; a request owns a
*block table* — an ordered list of block ids covering positions
``[i*block_size, (i+1)*block_size)``. The pool tracks three disjoint
populations over the same id space:

* **free** blocks — unowned, immediately allocatable;
* **pinned** blocks — referenced by ≥1 live request (refcounted, never
  evicted); full prefix blocks may be pinned by several requests at once
  (copy-free prefix sharing — the KV of a token depends only on the token
  and its absolute position, so identical prefixes have identical blocks);
* **cached** blocks — refcount 0 but retained under a ``(prefix_id,
  block_idx)`` key in LRU order; the prefix cache proper. Allocation
  evicts from the LRU head when the free list is empty.

The same class serves two clients with two views of the same bookkeeping:

* the **model engine** (`serve/engine.py`) uses the id-level API
  (``alloc`` / ``share`` / ``release``) and keeps the actual device
  arrays, indexed by block id, next to the jitted decode step
  (``models/decode.py::paged_decode_step`` gathers by block table). Each
  array is shaped ``models/decode.py::pool_shape``: (blocks, L,
  block*KV*hd/128, 128), a block's (block, KV, hd) values in lane-wide
  rows (SERVING.md §3);
* the **discrete-time simulator** (`serve/scheduler.py`) uses the
  occupancy API (``insert`` / ``hit_fraction`` / ``touch_decode``) to
  model residency decay without any arrays — subsuming the old
  ``PrefixCachePool``. Because both views mutate one LRU, the residency
  numbers the sim reports are claims about this code, not a look-alike.

Block id 0 can be reserved as a *null block* (``reserve_null=True``): the
engine points empty batch slots' tables at it so a fixed-shape jitted
decode step has somewhere harmless to scatter garbage (SERVING.md §3).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass


class KVPoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied even after evicting
    every unpinned cached block."""


@dataclass
class _BlockMeta:
    refcount: int = 0
    key: tuple | None = None        # (prefix_id, block_idx) if cached


@dataclass
class PoolStats:
    allocs: int = 0
    evictions: int = 0
    shared_hits: int = 0            # blocks served from the prefix cache
    exhausted: int = 0

    def to_dict(self) -> dict:
        return {"allocs": self.allocs, "evictions": self.evictions,
                "shared_hits": self.shared_hits,
                "exhausted": self.exhausted}


class PagedKVPool:
    """LRU block pool keyed by ``(prefix_id, block_idx)`` (SERVING.md §2)."""

    def __init__(self, capacity_blocks: int, reserve_null: bool = False,
                 evict_callback=None):
        if capacity_blocks < 1 + int(reserve_null):
            raise ValueError("pool needs at least one allocatable block")
        self.cap = capacity_blocks
        self.null_block: int | None = 0 if reserve_null else None
        first = 1 if reserve_null else 0
        self._free: list = list(range(capacity_blocks - 1, first - 1, -1))
        self._meta: dict = {}                   # block_id -> _BlockMeta
        self._cached: OrderedDict = OrderedDict()   # key -> block_id (LRU)
        self._owned: dict = {}                  # owner -> [block_id, ...]
        #: called with the ``(prefix_id, block_idx)`` key whenever a
        #: cached prefix block is dropped from the pool (LRU eviction) —
        #: the hook a fleet router uses to keep its global prefix index
        #: coherent with per-replica residency (SERVING.md §8). Fires
        #: mid-allocation: the callback must not re-enter the pool.
        self.evict_callback = evict_callback
        self.stats = PoolStats()

    # -- capacity accounting --------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_cached(self) -> int:
        """Blocks retained only by the prefix cache (evictable)."""
        return sum(1 for bid in self._cached.values()
                   if self._meta[bid].refcount == 0)

    @property
    def n_pinned(self) -> int:
        return sum(1 for m in self._meta.values() if m.refcount > 0)

    def check(self) -> None:
        """Internal invariants (exercised by tests/test_serve.py)."""
        live = set(self._free)
        assert len(live) == len(self._free), "double-free"
        for bid, m in self._meta.items():
            assert bid not in live, f"block {bid} both free and live"
            assert m.refcount >= 0
            if m.key is not None:
                assert self._cached.get(m.key) == bid
        for key, bid in self._cached.items():
            assert self._meta[bid].key == key
        n_meta = len([m for m in self._meta.values()
                      if m.refcount > 0 or m.key is not None])
        n_null = 1 if self.null_block is not None else 0
        assert len(self._free) + n_meta + n_null <= self.cap

    # -- id plumbing ----------------------------------------------------------
    def _evict_one(self) -> int:
        for key, bid in self._cached.items():      # head = LRU
            if self._meta[bid].refcount == 0:
                del self._cached[key]
                del self._meta[bid]
                self.stats.evictions += 1
                if self.evict_callback is not None:
                    self.evict_callback(key)
                return bid
        self.stats.exhausted += 1
        raise KVPoolExhausted(
            f"all {self.cap} blocks pinned; cannot allocate")

    def _take(self) -> int:
        bid = self._free.pop() if self._free else self._evict_one()
        self.stats.allocs += 1
        return bid

    def _reclaim(self, bid: int) -> None:
        m = self._meta[bid]
        if m.refcount == 0 and m.key is None:
            del self._meta[bid]
            self._free.append(bid)

    # -- engine-side API (block ids + pinning) --------------------------------
    def alloc(self, owner, n: int) -> list:
        """Pin ``n`` fresh blocks to ``owner``; evicts LRU cached blocks as
        needed. Raises ``KVPoolExhausted`` (allocating nothing) if the pool
        cannot cover the request."""
        evictable = self.n_free + self.n_cached
        if n > evictable:
            self.stats.exhausted += 1
            raise KVPoolExhausted(
                f"need {n} blocks, only {evictable} free+evictable of "
                f"{self.cap}")
        ids = []
        for _ in range(n):
            bid = self._take()
            self._meta[bid] = _BlockMeta(refcount=1)
            ids.append(bid)
        self._owned.setdefault(owner, []).extend(ids)
        return ids

    def lookup(self, prefix_id, n_blocks: int) -> list:
        """Longest resident *run* ``(prefix_id, 0..k-1)``, ``k <=
        n_blocks``; touches LRU recency. Returns block ids (not pinned)."""
        ids = []
        for j in range(n_blocks):
            key = (prefix_id, j)
            bid = self._cached.get(key)
            if bid is None:
                break
            self._cached.move_to_end(key)
            ids.append(bid)
        return ids

    def share(self, owner, prefix_id, n_blocks: int) -> list:
        """Pin the longest resident prefix run for ``owner`` (copy-free
        sharing). Returns the shared block ids, possibly empty."""
        ids = self.lookup(prefix_id, n_blocks)
        for bid in ids:
            self._meta[bid].refcount += 1
        self._owned.setdefault(owner, []).extend(ids)
        self.stats.shared_hits += len(ids)
        return ids

    def release(self, owner, prefix_id=None, keep_blocks: int = 0) -> None:
        """Unpin everything ``owner`` holds. The first ``keep_blocks``
        blocks (the full prompt-prefix blocks, in table order) are retained
        in the prefix cache under ``(prefix_id, j)``; the rest are freed
        once their refcount drops to zero."""
        ids = self._owned.pop(owner, [])
        for j, bid in enumerate(ids):
            m = self._meta[bid]
            m.refcount -= 1
            if prefix_id is not None and j < keep_blocks:
                key = (prefix_id, j)
                prev = self._cached.get(key)
                if prev is None or prev == bid:
                    if m.key is None:
                        m.key = key
                    self._cached[key] = bid
                    self._cached.move_to_end(key)
                # else: another request already cached this prefix block;
                # ours is a duplicate and falls through to reclaim.
            self._reclaim(bid)

    def table_of(self, owner) -> list:
        return list(self._owned.get(owner, ()))

    # -- sim-side API (occupancy only; subsumes PrefixCachePool) --------------
    def hit_fraction(self, prefix_id, n_blocks: int) -> float:
        """Fraction of ``(prefix_id, 0..n_blocks-1)`` resident (touches
        recency per hit) — the old ``PrefixCachePool`` probe."""
        if n_blocks == 0:
            return 0.0
        hits = 0
        for j in range(n_blocks):
            key = (prefix_id, j)
            if key in self._cached:
                hits += 1
                self._cached.move_to_end(key)
        return hits / n_blocks

    def insert(self, prefix_id, n_blocks: int) -> None:
        """Mark ``(prefix_id, 0..n_blocks-1)`` resident (MRU), allocating
        backing ids and evicting LRU unpinned blocks as needed."""
        for j in range(n_blocks):
            key = (prefix_id, j)
            bid = self._cached.get(key)
            if bid is not None:
                self._cached.move_to_end(key)
                continue
            bid = self._take()
            self._meta[bid] = _BlockMeta(refcount=0, key=key)
            self._cached[key] = bid

    def touch_decode(self, rid, blocks: int) -> None:
        """Decode working set churns the pool (residency decay, App. C):
        keyed on a per-request pseudo-prefix so it competes in the LRU."""
        self.insert(("decode", rid), blocks)


# Backwards-compatible name: the old dense prefix pool is now a view of the
# paged pool (same LRU, same probe semantics).
PrefixCachePool = PagedKVPool
