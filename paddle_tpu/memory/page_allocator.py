"""Refcounted page allocator over a fixed device-resident pool.

The framework's first real device-memory manager (PAPER.md L1:
`paddle/fluid/memory/` keeps a strategy-selectable allocator stack for
exactly this job). The allocator itself never touches device memory —
it hands out integer *page ids* into a pool whose storage the caller
owns (for decode: one `[pages, page_tokens, row]` array a layer, the
page axis first). That keeps it decode-agnostic: any subsystem that wants paged
device buffers (KV caches today, remat/offload spill later) can reuse
the same alloc/retain/release/refcount discipline.

Conventions:

  * page 0 is reserved as the **null page** when ``reserve_null`` —
    a scratch sink for block-table padding and padded-batch writes, so
    garbage writes land somewhere harmless instead of clobbering live
    data. It is never allocated and never freed.
  * every page has a refcount. `alloc` returns pages at refcount 1;
    `retain` increments (copy-on-write sharing: a prefix cache maps the
    same page into many sequences); `release` decrements and returns
    the page to the free list at zero.
  * `alloc` raises :class:`PageExhausted` (typed, catchable) instead of
    over-committing — callers turn that into backpressure. The error
    carries the pool label, the denied owner tag, and the
    requested/free counts so the resulting ``RESOURCE_EXHAUSTED``
    frame says *who* was denied *what*.
  * thread-safe behind one leaf lock; no callback, device work, or I/O
    ever runs under it (tsan-lite TPR102 clean by construction).

Owner attribution (observability/memz.py): every alloc/retain/release
accepts an optional lightweight ``owner`` tag — a small tuple such as
``("slot", req_id, tenant)``, ``("trie", node)``, ``("tier", handle)``,
``("draft", req_id)`` or ``("handoff", stream)`` — kept in a side table
under the same leaf lock. Rollups attribute each used page to its
**primary owner** (the first still-holding tagger), so the per-owner
page counts always sum to exactly ``pages_used`` even when a page is
shared between a slot and the prefix trie. Untagged calls fall into a
distinguished ``("untagged",)`` bucket and a mismatched release
degrades gracefully — attribution can never turn a correct refcount
operation into an error. Each operation also lands one event on the
bounded memz allocation ring (recorded *after* the leaf lock is
dropped, so no lock ever nests inside the allocator's).

The free list is kept sorted by insertion (`bisect.insort` on release)
rather than re-sorted on every alloc, so `alloc` stays O(n) in the
pages granted, not O(free · log free).

`write_pages` / `copy_page` are the pure-jax pool ops that pair with
the bookkeeping: both are shape-stable (jit/AOT-cacheable) updates over
a pool whose axis 0 is the page axis.
"""
from __future__ import annotations

import threading
from bisect import insort
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

#: Attribution bucket for alloc/retain/release calls with no owner tag.
UNTAGGED: Tuple[str, ...] = ("untagged",)


class PageExhausted(RuntimeError):
    """Raised by `PageAllocator.alloc` when the free list cannot cover
    the request — the caller's cue for eviction or backpressure.
    Attributes ``pool`` / ``owner`` / ``requested`` / ``free`` identify
    the denied pool, the requester's owner tag, and the shortfall."""

    def __init__(self, message: str, *, pool: str = "",
                 owner: Tuple = UNTAGGED, requested: int = 0,
                 free: int = 0):
        super().__init__(message)
        self.pool = pool
        self.owner = owner
        self.requested = requested
        self.free = free


def owner_str(owner) -> str:
    """Stable printable form of an owner tag (JSON-safe dict key)."""
    return ":".join(str(x) for x in owner)


_RING = None


def _ring_record(op: str, pool: str, owner, n: int, free: int) -> None:
    """Land one event on the memz allocation ring (lazily bound so
    `memory` never imports `observability` at module load). Called only
    outside the allocator lock."""
    global _RING
    ring = _RING
    if ring is None:
        from ..observability import memz as _memz
        ring = _RING = _memz.RING
    ring.record(op, pool, owner, n, free)


class PageAllocator:
    """Bookkeeping for a pool of `num_pages` fixed-size device pages."""

    def __init__(self, num_pages: int, *, reserve_null: bool = True,
                 label: str = "kv"):
        if num_pages < (2 if reserve_null else 1):
            raise ValueError(f"page pool needs >= 2 pages, got {num_pages}")
        self.num_pages = int(num_pages)
        self.null_page = 0 if reserve_null else -1
        self.label = str(label)
        self._lock = threading.Lock()
        first = 1 if reserve_null else 0
        # kept sorted ascending at all times: alloc slices the head,
        # release bisect-inserts — never a full sort on the hot path
        self._free: List[int] = list(range(first, self.num_pages))
        self._refs: Dict[int, int] = {}
        # page -> {owner tag -> refs held under that tag}; insertion
        # order makes the first surviving key the page's primary owner
        self._owners: Dict[int, Dict[Tuple, int]] = {}
        self._allocs = 0
        self._failures = 0
        self._high_water = 0
        self._shared = 0           # pages at refcount > 1, kept as refs move

    # ------------------------------------------------- owner side table

    def _owner_add(self, page: int, owner: Tuple, n: int = 1) -> None:
        d = self._owners.get(page)
        if d is None:
            d = self._owners[page] = {}
        d[owner] = d.get(owner, 0) + n

    def _owner_drop(self, page: int, owner: Tuple) -> None:
        """Drop one owner ref for `page`: the given tag if it holds one,
        else the untagged bucket, else the newest holder — a mismatched
        tag degrades attribution, never correctness."""
        d = self._owners.get(page)
        if not d:
            return
        key = owner if owner in d else (
            UNTAGGED if UNTAGGED in d else next(reversed(d)))
        left = d[key] - 1
        if left > 0:
            d[key] = left
        else:
            del d[key]

    # ------------------------------------------------------------- ops

    def alloc(self, n: int = 1, owner: Optional[Tuple] = None) -> List[int]:
        """Hand out `n` pages at refcount 1 (lowest ids first — keeps
        the pool dense so fragmentation stays measurable and low),
        attributed to `owner` (or the untagged bucket)."""
        if n <= 0:
            return []
        tag = owner if owner is not None else UNTAGGED
        with self._lock:
            free = len(self._free)
            if n > free:
                self._failures += 1
                pages = None
            else:
                pages = self._free[:n]
                del self._free[:n]
                for p in pages:
                    self._refs[p] = 1
                    self._owners[p] = {tag: 1}
                self._allocs += n
                self._high_water = max(self._high_water, len(self._refs))
        if pages is None:
            _ring_record("exhausted", self.label, tag, n, free)
            raise PageExhausted(
                f"pool '{self.label}': requested {n} pages for "
                f"{owner_str(tag)}, {free} free of {self.num_pages}",
                pool=self.label, owner=tag, requested=n, free=free)
        _ring_record("alloc", self.label, tag, n, free - n)
        return pages

    def retain(self, page: int, owner: Optional[Tuple] = None) -> int:
        """Add a reference to an allocated page (sharing); returns the
        new refcount."""
        tag = owner if owner is not None else UNTAGGED
        with self._lock:
            if page not in self._refs:
                raise ValueError(f"retain of unallocated page {page}")
            self._refs[page] += 1
            refs = self._refs[page]
            self._shared += refs == 2
            self._owner_add(page, tag)
            free = len(self._free)
        _ring_record("retain", self.label, tag, 1, free)
        return refs

    def release(self, page: int, owner: Optional[Tuple] = None) -> int:
        """Drop a reference; the page rejoins the free list at zero.
        Returns the remaining refcount."""
        tag = owner if owner is not None else UNTAGGED
        with self._lock:
            refs = self._refs.get(page)
            if refs is None:
                raise ValueError(f"release of unallocated page {page}")
            if refs > 1:
                self._refs[page] = refs - 1
                self._shared -= refs == 2
                self._owner_drop(page, tag)
                left = refs - 1
            else:
                del self._refs[page]
                self._owners.pop(page, None)
                insort(self._free, page)
                left = 0
            free = len(self._free)
        _ring_record("release", self.label, tag, 1, free)
        return left

    def release_range(self, ids, from_idx: int,
                      owner: Optional[Tuple] = None) -> int:
        """Drop one reference on every page in ``ids[from_idx:]`` under a
        single lock acquisition — the speculative-decode rollback path,
        which strands a tail of a block table past the last accepted
        token. Returns the number of references dropped. Any unallocated
        id raises ValueError before *any* refcount changes, so a bad
        call never half-applies."""
        tag = owner if owner is not None else UNTAGGED
        tail = [int(p) for p in list(ids)[max(int(from_idx), 0):]]
        with self._lock:
            for p in tail:
                if p not in self._refs:
                    raise ValueError(f"release of unallocated page {p}")
            for p in tail:
                refs = self._refs[p]
                if refs > 1:
                    self._refs[p] = refs - 1
                    self._shared -= refs == 2
                    self._owner_drop(p, tag)
                else:
                    del self._refs[p]
                    self._owners.pop(p, None)
                    insort(self._free, p)
            free = len(self._free)
        if tail:
            _ring_record("release", self.label, tag, len(tail), free)
        return len(tail)

    def retag(self, page: int, old: Tuple, new: Tuple) -> None:
        """Move one owner ref of `page` from tag `old` to tag `new`
        without touching the refcount — used when a reference changes
        hands (e.g. a tier refetch lands and the trie becomes the
        holder). No-op on an unallocated page."""
        with self._lock:
            if page not in self._refs:
                return
            self._owner_drop(page, old)
            self._owner_add(page, new)

    def refcount(self, page: int) -> int:
        with self._lock:
            return self._refs.get(page, 0)

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    # ----------------------------------------------------------- stats

    def owner_rollups(self) -> Tuple[Dict, Dict, Dict]:
        """(by_owner, by_kind, by_tenant) page counts under primary-owner
        attribution: each used page counts once, toward the first owner
        tag still holding it — so every rollup sums to ``pages_used``
        exactly. Tenants come from ``("slot", req, tenant)`` tags; pages
        not held by any slot count toward tenant ``"-"``."""
        by_owner: Dict[Tuple, int] = {}
        by_kind: Dict[str, int] = {}
        by_tenant: Dict[str, int] = {}
        with self._lock:
            primaries = [next(iter(d)) for d in self._owners.values() if d]
        for owner in primaries:
            by_owner[owner] = by_owner.get(owner, 0) + 1
            kind = str(owner[0])
            by_kind[kind] = by_kind.get(kind, 0) + 1
            tenant = str(owner[2]) if kind == "slot" and len(owner) > 2 \
                else "-"
            by_tenant[tenant] = by_tenant.get(tenant, 0) + 1
        return by_owner, by_kind, by_tenant

    def owned_pages(self) -> List[Tuple[int, Tuple, int]]:
        """Snapshot of ``(page, primary_owner, refcount)`` for every
        allocated page — the memz ghost-page audit's raw material."""
        with self._lock:
            return [(p, next(iter(self._owners.get(p) or [UNTAGGED])),
                     r) for p, r in self._refs.items()]

    def occupancy(self) -> Dict:
        """What a gauge refresh reads, several times a scheduler tick:
        the page counts (kept as pages change hands) and the free
        space's fragmentation (one pass over the free list, which is
        short while a prefix cache holds what requests left behind).
        Nothing here walks the allocated pages or their owners."""
        with self._lock:
            free = np.asarray(self._free)      # already sorted ascending
            used, shared = len(self._refs), self._shared
        frag = 0.0
        if len(free):
            # run ends: where the next free page is not the next page
            ends = np.flatnonzero(np.diff(free) != 1)
            runs = np.diff(np.concatenate(([-1], ends, [len(free) - 1])))
            frag = 1.0 - int(runs.max()) / len(free)
        return {
            "pages_total": self.num_pages - (1 if self.null_page == 0 else 0),
            "pages_free": len(free),
            "pages_used": used,
            "pages_shared": shared,
            "fragmentation": round(frag, 4),
        }

    def stats(self) -> Dict:
        """Occupancy + fragmentation snapshot (all counts exclude the
        reserved null page). Fragmentation is 1 − largest contiguous
        free run / free pages: 0.0 when the free space is one block
        (or empty), approaching 1.0 as it shatters. ``owners`` /
        ``owner_kinds`` / ``tenants`` are the primary-owner page
        rollups (each sums to ``pages_used``)."""
        with self._lock:
            refs_total = sum(self._refs.values())
            allocs, failures = self._allocs, self._failures
            high = self._high_water
        by_owner, by_kind, by_tenant = self.owner_rollups()
        return {
            **self.occupancy(),
            "refs_total": refs_total,
            "allocs_total": allocs,
            "alloc_failures_total": failures,
            "high_watermark": high,
            "owners": {owner_str(o): c for o, c in sorted(
                by_owner.items(), key=lambda kv: -kv[1])},
            "owner_kinds": by_kind,
            "tenants": by_tenant,
        }

    def fragmentation_map(self) -> List[List[int]]:
        """Free-space layout as ``[start, length]`` runs over the sorted
        free list — the OOM forensic dump's picture of *where* the holes
        are, not just how many."""
        with self._lock:
            free = list(self._free)
        runs: List[List[int]] = []
        for p in free:
            if runs and p == runs[-1][0] + runs[-1][1]:
                runs[-1][1] += 1
            else:
                runs.append([p, 1])
        return runs


# ----------------------------------------------------------- pool ops

def write_pages(pool, rows, page_ids):
    """Scatter whole pages into the pool.

    pool      [P, page_tokens, ...]  (page axis = 0 on every leaf)
    rows      [W, page_tokens, ...]  page-shaped rows to write
    page_ids  [W] int32              destination pages (traced ok)

    `pool` may be a bare array or a pytree (one array a layer, the int8
    pool's ``(data, scale)`` pair from `quant.kv`, a model kind's whole
    pools); `rows` must mirror its structure. Duplicate destinations (e.g. several padding rows aimed
    at the null page) resolve arbitrarily — by convention only
    don't-care data is ever aimed at a duplicated id.
    """
    return jax.tree.map(lambda p, r: p.at[page_ids].set(r), pool, rows)


def copy_page(pool, src, dst):
    """Copy one page (copy-on-write): pool[dst] = pool[src] on every
    pool leaf. `src`/`dst` may be traced scalars, so one
    executable serves every (src, dst) pair."""
    return jax.tree.map(lambda p: p.at[dst].set(p[src]), pool)


def gather_pages(pool, page_ids):
    """Gather whole pages out of the pool into a fresh buffer — the
    shape-stable read twin of `write_pages`.

    pool      [P, page_tokens, ...]  (page axis = 0 on every leaf)
    page_ids  [W] int32              source pages (traced ok)

    The result is an *independent* `[W, page_tokens, ...]` buffer
    per leaf, so the caller may release (and even donate) the pool right
    after dispatch — jax orders the in-flight read before any later
    donation. This is the spill-side primitive of host tiering: gather
    cold pages, hand the chunk to the migration engine, free the pages.
    """
    return jax.tree.map(lambda p: p[page_ids], pool)


__all__ = ["PageAllocator", "PageExhausted", "UNTAGGED", "owner_str",
           "write_pages", "copy_page", "gather_pages"]


if __name__ == "__main__":  # pragma: no cover - smoke
    a = PageAllocator(8)
    pages = a.alloc(3, owner=("slot", "r0", "tenant-a"))
    a.retain(pages[0], owner=("trie", "n0"))
    print(pages, a.stats())
    for p in pages:
        a.release(p, owner=("slot", "r0", "tenant-a"))
    a.release(pages[0], owner=("trie", "n0"))
    print(jnp.asarray(0), a.stats())
