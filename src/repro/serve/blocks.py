"""The host half of the block-paged KV pool: refcounts, free lists and
the prefix index, with no device state and no jax.

:class:`BlockAllocator` is what the scheduler
(:mod:`repro.serve.scheduler`) allocates from;
:class:`~repro.serve.paged_cache.PagedKVCache` is this allocator plus
the device pools.  Physical block 0 is the reserved null block: never
allocated, the target of idle table rows.

Block sharing (copy-on-write)
-----------------------------
Blocks carry reference counts, so several requests may name the same
physical block in their tables.  A *prefix index* maps a chained
content hash of each page-aligned token run (``sha1(parent_digest ||
page_tokens)``, vLLM-style) to the block holding its K/V: a new request
whose prompt starts with an already-cached prefix takes the matching
blocks for free — :meth:`match_prefix` + :meth:`acquire` are pure
host-side bookkeeping, no prefill compute.

Freeing a *registered* block (one the index knows) does not scrub it:
at refcount zero it parks on a revival list, still matchable, and is
only evicted — deregistered and handed out as writable — when the
allocator runs out of never-written blocks (oldest-parked first, and
only ever at refcount zero).
:meth:`~repro.serve.paged_cache.PagedKVCache.fork` is the copy-on-write
escape hatch: give a writer its own copy of a shared block.  The serve engine
never needs it in steady state — prefix matches are capped so writes
land past every shared page — but the cache keeps the operation (and
its tests) so the invariant is enforceable, not incidental.

The free structures are O(1) end to end: a fresh-block stack, an
insertion-ordered dict for parked revivable blocks (O(1) membership,
removal, and oldest-first eviction), and refcounts make the
double-free check a single array lookup instead of the old free-list
scan.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["BlockAllocator", "prefix_digests"]


def prefix_digests(tokens: np.ndarray, page: int) -> List[bytes]:
    """Chained content hashes of ``tokens``' full pages.

    ``digest[i] = sha1(digest[i-1] || tokens[i*page:(i+1)*page])`` — the
    chain means a digest identifies the whole prefix up to and including
    its page, so equal digests imply bitwise-equal cache contents (K/V
    of a causal model depend only on the tokens at and before a row).
    """
    toks = np.asarray(tokens, np.int32).reshape(-1)
    out: List[bytes] = []
    h = b""
    for i in range(toks.shape[0] // page):
        h = hashlib.sha1(h + toks[i * page:(i + 1) * page].tobytes()).digest()
        out.append(h)
    return out


class BlockAllocator:
    """The host half of the pool: refcounts, free lists and the prefix
    index over ``n_blocks`` physical blocks of ``page`` token rows.

    ``n_blocks`` counts physical blocks *including* the reserved null
    block 0, so ``n_blocks - 1`` are allocatable.  Holds no device
    state, so a host-only replica of the scheduler runs the same
    allocator the engine does.
    """

    def __init__(self, n_blocks: int, page: int):
        if n_blocks < 2:
            raise ValueError(f"n_blocks={n_blocks}: need at least the null "
                             "block plus one allocatable block")
        self.page = int(page)
        self.n_blocks = int(n_blocks)
        self._refs: List[int] = [0] * self.n_blocks
        # LIFO stack of never-registered writable blocks
        self._fresh: List[int] = list(range(self.n_blocks - 1, 0, -1))
        # refcount-0 blocks still in the prefix index, oldest-parked
        # first (dict preserves insertion order: O(1) park/revive/evict)
        self._parked: Dict[int, None] = {}
        self._index: Dict[bytes, int] = {}      # digest -> block
        self._digest: Dict[int, bytes] = {}     # block  -> digest

    # -- allocator ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable blocks (the null block excluded)."""
        return self.n_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._fresh) + len(self._parked)

    @property
    def used_blocks(self) -> int:
        return self.capacity - self.free_blocks

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 blocks parked in the prefix index (revivable)."""
        return len(self._parked)

    def occupancy(self) -> float:
        """Fraction of allocatable blocks currently held by requests."""
        return self.used_blocks / max(1, self.capacity)

    def ref_count(self, b: int) -> int:
        return self._refs[b]

    def _check_range(self, b: int, op: str) -> None:
        if not 1 <= b < self.n_blocks:
            raise ValueError(f"{op}: block id {b} outside the "
                             f"allocatable range [1, {self.n_blocks})")

    def _deregister(self, b: int) -> None:
        d = self._digest.pop(b, None)
        if d is not None:
            del self._index[d]

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` writable blocks at refcount 1, or ``None`` if the
        pool cannot cover them (all-or-nothing keeps admission atomic).
        Never-written blocks go first; then parked index entries are
        evicted oldest-first (deregistered — only refcount-0 blocks are
        ever reclaimed, so no live request ever loses a block)."""
        if n > self.free_blocks:
            return None
        ids: List[int] = []
        for _ in range(n):
            if self._fresh:
                b = self._fresh.pop()
            else:
                b = next(iter(self._parked))
                del self._parked[b]
                self._deregister(b)
            self._refs[b] = 1
            ids.append(b)
        return ids

    def free(self, ids: Sequence[int]) -> None:
        """Drop one reference per listed block.  At refcount 0 a block
        returns to the fresh stack, or — if the prefix index knows it —
        parks for revival.  Raises before touching anything if any id is
        out of range or would go below zero (double free)."""
        counts: Dict[int, int] = {}
        for b in ids:
            self._check_range(b, "free")
            counts[b] = counts.get(b, 0) + 1
        for b, c in counts.items():
            if self._refs[b] < c:
                raise ValueError(f"free: block {b} double-freed")
        for b, c in counts.items():
            self._refs[b] -= c
            if self._refs[b] == 0:
                if b in self._digest:
                    self._parked[b] = None
                else:
                    self._fresh.append(b)

    def acquire(self, ids: Sequence[int]) -> None:
        """Add one reference per listed block (a prefix-cache hit taking
        shared ownership).  Parked blocks revive; a block that is neither
        live nor parked is not acquirable — that would hand out a fresh
        block without initialising it."""
        for b in ids:
            self._check_range(b, "acquire")
            if self._refs[b] == 0 and b not in self._parked:
                raise ValueError(f"acquire: block {b} is not live or "
                                 "cached (alloc writable blocks instead)")
        for b in ids:
            self._parked.pop(b, None)
            self._refs[b] += 1

    # -- prefix index ------------------------------------------------------

    def match_prefix(self, tokens: np.ndarray) -> List[int]:
        """Longest run of cached blocks covering ``tokens``' page-aligned
        prefix.  Pure lookup — call :meth:`acquire` on the result before
        the next alloc/free, or the blocks may be evicted under you."""
        out: List[int] = []
        for d in prefix_digests(tokens, self.page):
            b = self._index.get(d)
            if b is None:
                break
            out.append(b)
        return out

    def register_prefix(self, tokens: np.ndarray, ids: Sequence[int]) -> None:
        """Enter ``tokens``' full pages — held in ``ids`` in order — into
        the prefix index.  Already-indexed digests are skipped (first
        writer wins; duplicate content in another block stays private),
        as are blocks already registered under some digest (a fork)."""
        ds = prefix_digests(tokens, self.page)
        if len(ds) > len(ids):
            raise ValueError(
                f"register_prefix: {len(ds)} full pages but only "
                f"{len(ids)} blocks")
        for d, b in zip(ds, ids):
            self._check_range(b, "register_prefix")
            if d in self._index or b in self._digest:
                continue
            if self._refs[b] == 0 and b not in self._parked:
                raise ValueError(f"register_prefix: block {b} is not live")
            self._index[d] = b
            self._digest[b] = d

    def check_invariants(self) -> None:
        """Assert the allocator's conservation and bookkeeping invariants.

        The chaos suite calls this after **every scheduler tick** under
        fault injection; any violation raises :class:`AssertionError`
        naming the broken invariant.  The contract:

        * **conservation** — every allocatable block is in exactly one
          of three states: *fresh* (never-registered free list), *parked*
          (refcount 0 but still in the prefix index), or *live*
          (refcount > 0): ``fresh + parked + live == n_blocks - 1`` with
          the three sets disjoint — no leaked and no double-owned block;
        * the **null block** (0) is never fresh, parked, live, or
          indexed;
        * refcounts are non-negative; parked blocks sit at exactly 0;
        * the **prefix index** is an exact bijection with the reverse
          map and only names live or parked blocks (an indexed fresh
          block would serve stale K/V to a future prefix match).
        """
        P = self.n_blocks
        fresh = list(self._fresh)
        fresh_set = set(fresh)
        parked = set(self._parked)
        if len(fresh) != len(fresh_set):
            raise AssertionError(f"fresh list holds duplicates: {fresh}")
        for name, ids in (("fresh", fresh_set), ("parked", parked)):
            bad = [b for b in ids if not 1 <= b < P]
            if bad:
                raise AssertionError(f"{name} holds out-of-range or null "
                                     f"blocks: {bad}")
        neg = [b for b in range(P) if self._refs[b] < 0]
        if neg:
            raise AssertionError(f"negative refcounts on blocks {neg}")
        if self._refs[0] != 0:
            raise AssertionError(f"null block has refcount {self._refs[0]}")
        live = {b for b in range(1, P) if self._refs[b] > 0}
        if fresh_set & parked:
            raise AssertionError("blocks both fresh and parked: "
                                 f"{sorted(fresh_set & parked)}")
        if live & fresh_set:
            raise AssertionError("live blocks on the fresh list: "
                                 f"{sorted(live & fresh_set)}")
        if live & parked:
            raise AssertionError("live blocks parked: "
                                 f"{sorted(live & parked)}")
        if len(fresh_set) + len(parked) + len(live) != P - 1:
            missing = (set(range(1, P)) - fresh_set - parked - live)
            raise AssertionError(
                f"block conservation broken: fresh {len(fresh_set)} + "
                f"parked {len(parked)} + live {len(live)} != {P - 1} "
                f"(leaked blocks: {sorted(missing)})")
        bad_parked = [b for b in parked if self._refs[b] != 0]
        if bad_parked:
            raise AssertionError(f"parked blocks with nonzero refcount: "
                                 f"{bad_parked}")
        unindexed = [b for b in parked if b not in self._digest]
        if unindexed:
            raise AssertionError(f"parked blocks missing from the prefix "
                                 f"index: {unindexed}")
        if len(self._index) != len(self._digest):
            raise AssertionError(
                f"prefix index ({len(self._index)}) and reverse map "
                f"({len(self._digest)}) disagree")
        for d, b in self._index.items():
            if self._digest.get(b) != d:
                raise AssertionError(
                    f"prefix index names block {b} but the reverse map "
                    f"holds {self._digest.get(b)!r} != {d!r}")
            if b not in live and b not in parked:
                raise AssertionError(
                    f"prefix index names block {b}, which is neither "
                    "live nor parked (stale K/V would be served)")
