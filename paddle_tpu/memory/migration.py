"""Async host<->device page migration: the two-tier KV memory manager.

`memory.page_allocator` made device pages a first-class resource; this
module makes HBM a *cache* over a much larger host-RAM page store
(PAPER.md L1: `paddle/fluid/memory/` spills beyond device memory via
MmapAllocator — same idea, paged). Three pieces:

* :class:`TieredPageAllocator` grows :class:`PageAllocator` into a
  two-tier manager. Device pages keep the inherited id space
  (non-negative ints); spilled page *contents* live in a bounded host
  tier addressed by negative **handles**, each with a residency state —
  ``HOST`` (payload landed, refetchable), ``IN_FLIGHT`` (a migration is
  moving it in either direction). Device-resident pages are simply
  allocator pages (residency ``DEVICE``). Pure bookkeeping behind one
  leaf lock, like the base class — it never touches device memory.
* :class:`HostPageStore` owns the payload bytes: per-pool-leaf arenas
  preallocated at construction (the pinned-buffer discipline — spills
  copy into a fixed arena slot, never allocate per page), indexed by
  the same handles.
* :class:`MigrationEngine` is the async transport: a background worker
  with per-direction queues and a bounded in-flight window that
  double-buffers transfers — it *dispatches* up to ``window`` device
  copies (``copy_to_host_async`` / ``jax.device_put``, both async under
  jax's dispatch model) before *retiring* the oldest (the blocking
  host-side copy into / out of the arena), so transfer k+1 overlaps the
  host copy of transfer k. Spills are drained before refetches, which
  (with submission order: a handle is always spilled before it can be
  refetched) makes a refetch of an in-flight spill naturally wait for
  the payload to land.

The engine is deliberately consumer-agnostic: callers hand it opaque
device chunks / handle lists plus an ``on_done`` callback, so the same
transport serves KV tiering and — via :func:`serialize_pages` /
:func:`deserialize_pages` below — the cross-process prefill/decode KV
handoff (docs/serving.md). Failure never raises out of the
worker — the callback reports it and the *caller* decides (the decode
engine degrades to a re-prefill, which is always correct).

Chaos: every migration batch passes the ``page.migrate`` site before
its device work. A ``Fail`` kills that batch (callback with the error);
``Hang@s`` sleeps the worker — both stall or fail only streams waiting
on those specific pages, because no scheduler thread ever blocks on
this worker.

Observability: the ``paddle_tpu_kv_tier_*`` families (resident pages
per tier, spill/refetch counters, per-direction migration latency,
in-flight depth) plus ``page.spill`` / ``page.refetch`` tracez spans.
"""
from __future__ import annotations

import threading
import time
import zlib
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .page_allocator import (PageAllocator, _ring_record,  # noqa: F401
                             gather_pages)

__all__ = ["Residency", "TieredPageAllocator", "HostPageStore",
           "MigrationEngine", "MigrationTicket", "gather_pages",
           "serialize_pages", "deserialize_pages", "tier_metrics"]


class Residency:
    """Residency states for a logical KV page in the two-tier manager."""
    DEVICE = "DEVICE"
    HOST = "HOST"
    IN_FLIGHT = "IN_FLIGHT"


_METRICS = None


def tier_metrics():
    """Register (idempotently) and return the paddle_tpu_kv_tier_* family."""
    global _METRICS
    if _METRICS is None:
        from ..observability import counter, gauge, histogram
        _METRICS = {
            "resident": gauge(
                "paddle_tpu_kv_tier_resident_pages",
                "KV pages resident per tier: device = allocator pages in "
                "use, host = spilled page payloads held in the host "
                "arena (in-flight pages count toward host)",
                labelnames=("tier",)),
            "spills": counter(
                "paddle_tpu_kv_tier_spills_total",
                "KV pages spilled device -> host by the migration "
                "engine"),
            "refetches": counter(
                "paddle_tpu_kv_tier_refetches_total",
                "KV pages refetched host -> device by the migration "
                "engine"),
            "migration_seconds": histogram(
                "paddle_tpu_kv_tier_migration_seconds",
                "Wall time of one migration batch by direction "
                "(out = device->host spill, in = host->device refetch)",
                labelnames=("direction",)),
            "inflight": gauge(
                "paddle_tpu_kv_tier_inflight",
                "Migration jobs submitted but not yet retired "
                "(queued + dispatched)"),
        }
    return _METRICS


class TieredPageAllocator(PageAllocator):
    """`PageAllocator` plus a bounded host tier of spilled page contents.

    Host **handles** are negative ints (``-(slot + 1)`` for arena slot
    ``slot``) so they can never collide with device page ids; callers
    that store "a page or its spilled handle" branch on the sign. The
    handle lifecycle is
    ``spill_begin (IN_FLIGHT) -> spill_commit (HOST) ->
    refetch_begin (IN_FLIGHT) -> host_drop`` with ``host_drop`` also
    serving every abort path. All transitions are O(1) bookkeeping
    under the inherited leaf lock."""

    def __init__(self, num_pages: int, *, host_pages: int,
                 reserve_null: bool = True, label: str = "kv"):
        super().__init__(num_pages, reserve_null=reserve_null,
                         label=label)
        if host_pages < 1:
            raise ValueError(f"host tier needs >= 1 page, got {host_pages}")
        self.host_pages = int(host_pages)
        self._host_free: List[int] = list(range(self.host_pages))
        self._residency: Dict[int, str] = {}     # handle -> Residency
        self._spilled = 0
        self._refetched = 0

    @staticmethod
    def handle_slot(handle: int) -> int:
        """Arena slot index a (negative) host handle addresses."""
        return -int(handle) - 1

    # ---------------------------------------------------------- spills

    def spill_begin(self, n: int) -> List[int]:
        """Reserve up to `n` host slots; returns their handles at
        residency IN_FLIGHT (the payload is still moving). Returns
        fewer — possibly none — when the host tier is near capacity;
        the caller falls back to destructive eviction for the rest."""
        with self._lock:
            take = min(max(n, 0), len(self._host_free))
            handles = [-(self._host_free.pop() + 1) for _ in range(take)]
            for h in handles:
                self._residency[h] = Residency.IN_FLIGHT
            return handles

    def spill_commit(self, handle: int) -> None:
        """The payload landed in the host arena: IN_FLIGHT -> HOST."""
        with self._lock:
            if self._residency.get(handle) != Residency.IN_FLIGHT:
                raise ValueError(f"spill_commit of handle {handle} not "
                                 f"in flight")
            self._residency[handle] = Residency.HOST
            self._spilled += 1
            host_free = len(self._host_free)
        # ring event after the lock, same discipline as the base class
        _ring_record("spill", self.label, ("tier", handle), 1, host_free)

    # -------------------------------------------------------- refetches

    def refetch_begin(self, handle: int) -> None:
        """Pin a HOST handle for refetch: HOST -> IN_FLIGHT (a pinned
        handle can neither be refetched again nor dropped under it)."""
        with self._lock:
            if self._residency.get(handle) != Residency.HOST:
                raise ValueError(f"refetch_begin of handle {handle} not "
                                 f"host-resident")
            self._residency[handle] = Residency.IN_FLIGHT

    def refetch_commit(self, handle: int) -> None:
        """The payload is back on device: count it and free the slot."""
        with self._lock:
            self._refetched += 1
        self.host_drop(handle)
        with self._lock:
            host_free = len(self._host_free)
        _ring_record("refetch", self.label, ("tier", handle), 1,
                     host_free)

    def host_drop(self, handle: int) -> None:
        """Free a host slot (restore landed, spill failed, refetch
        failed, or the entry was evicted). Idempotent."""
        with self._lock:
            if self._residency.pop(handle, None) is not None:
                self._host_free.append(self.handle_slot(handle))

    def residency(self, handle: int) -> Optional[str]:
        """Residency of a host handle (None when unknown/dropped);
        non-negative ids are device pages and report DEVICE while
        allocated."""
        if handle >= 0:
            return Residency.DEVICE if self.refcount(handle) else None
        with self._lock:
            return self._residency.get(handle)

    def host_used(self) -> int:
        with self._lock:
            return self.host_pages - len(self._host_free)

    def occupancy(self) -> Dict:
        occ = super().occupancy()
        occ["host_pages_used"] = self.host_used()
        return occ

    def stats(self) -> Dict:
        st = super().stats()
        with self._lock:
            st["host_pages_total"] = self.host_pages
            st["host_inflight"] = sum(
                1 for r in self._residency.values()
                if r == Residency.IN_FLIGHT)
            st["spilled_total"] = self._spilled
            st["refetched_total"] = self._refetched
        return st


class HostPageStore:
    """Preallocated host arenas for spilled page payloads.

    ``template`` is a pytree whose leaves carry the *pool* shape
    ``[P, page_tokens, ...]`` (page axis 0) — concrete arrays or
    ShapeDtypeStructs both work; only ``.shape``/``.dtype`` are read.
    One numpy arena of shape ``(capacity, *leaf_shape_without_P)`` is
    allocated per leaf up front, so a spill is a bounded copy into a
    fixed slot (the pinned-buffer discipline) and the store's footprint
    is visible at construction, never a surprise mid-serve."""

    def __init__(self, template, capacity: int):
        import jax

        self.capacity = int(capacity)
        leaves = jax.tree_util.tree_flatten(template)[0]
        self._treedef = jax.tree_util.tree_structure(template)
        self._arenas = []
        for leaf in leaves:
            self._arenas.append(np.zeros(
                (self.capacity,) + tuple(leaf.shape)[1:],   # page axis 0
                dtype=np.dtype(leaf.dtype)))

    def nbytes(self) -> int:
        return sum(a.nbytes for a in self._arenas)

    def put(self, slot: int, chunk_leaves: Sequence[np.ndarray],
            index: int) -> None:
        """Store page `index` of a gathered chunk (leaf list, each
        ``[W, page_tokens, ...]``) into arena slot `slot`."""
        for arena, leaf in zip(self._arenas, chunk_leaves):
            arena[slot] = leaf[index]

    def assemble(self, slots: Sequence[int], rung: int):
        """Build page rows for `slots`, zero-padded to `rung` pages —
        the host-side half of a refetch, shaped for the AOT'd
        `write_pages` executable. Returns a pytree mirroring the
        template."""
        import jax

        rows = []
        for arena in self._arenas:
            out = np.zeros((int(rung),) + arena.shape[1:], dtype=arena.dtype)
            out[:len(slots)] = arena[list(slots)]
            rows.append(out)
        return jax.tree_util.tree_unflatten(self._treedef, rows)


# ---------------------------------------------------- wire serialization
#
# The prefill/decode KV handoff ships gathered page chunks between
# processes over the serve wire protocol. Same leaf discipline as
# `HostPageStore`: a chunk is a pytree of ``[W, page_tokens, ...]``
# leaves (page axis 0), possibly rung-padded past the real page count.
# Serialization slices each leaf to the real count, records per-leaf
# dtype/shape metadata plus a per-page crc32 chained across leaves, and
# re-views int8 leaves as uint8 (the wire tensor codec carries no int8
# code); deserialization restores the dtypes and refuses any structural
# or checksum mismatch — a torn or mis-routed handoff must degrade to a
# re-prefill, never land garbage in a pool.

def _page_crc(leaves: Sequence[np.ndarray], index: int) -> int:
    c = 0
    for a in leaves:
        c = zlib.crc32(np.ascontiguousarray(a[index]).tobytes(), c)
    return c


def serialize_pages(chunk, count: int) -> Tuple[List[np.ndarray], Dict]:
    """Flatten a gathered page chunk into wire-safe arrays + metadata.

    Returns ``(arrays, meta)``: one contiguous numpy array per leaf,
    sliced to `count` real pages (int8 leaves ride as a uint8 view),
    and ``meta`` = ``{"n_pages", "leaves": [{"dtype", "shape"}, ...],
    "crcs": [per-page crc32]}``."""
    import jax

    count = int(count)
    leaves = [np.ascontiguousarray(np.asarray(x)[:count])
              for x in jax.tree_util.tree_flatten(chunk)[0]]
    arrays, leaf_meta = [], []
    for a in leaves:
        leaf_meta.append({"dtype": str(a.dtype), "shape": list(a.shape)})
        arrays.append(a.view(np.uint8) if a.dtype == np.int8 else a)
    meta = {"n_pages": count,
            "leaves": leaf_meta,
            "crcs": [_page_crc(leaves, j) for j in range(count)]}
    return arrays, meta


def deserialize_pages(arrays: Sequence[np.ndarray],
                      meta: Dict) -> List[np.ndarray]:
    """Inverse of :func:`serialize_pages`: restore leaf dtypes from the
    metadata and validate every page's crc32 chain. Returns the per-leaf
    arrays (``[n_pages, page_tokens, ...]``, page axis 0). Raises ``ValueError``
    on any structural or checksum mismatch."""
    leaf_meta = meta.get("leaves") or []
    crcs = list(meta.get("crcs") or [])
    n = int(meta.get("n_pages") or 0)
    if len(arrays) != len(leaf_meta):
        raise ValueError(
            f"kv payload structure mismatch: {len(arrays)} arrays for "
            f"{len(leaf_meta)} leaf descriptors")
    if len(crcs) != n:
        raise ValueError(
            f"kv payload structure mismatch: {len(crcs)} checksums for "
            f"{n} pages")
    leaves = []
    for i, (a, lm) in enumerate(zip(arrays, leaf_meta)):
        dt = np.dtype(lm.get("dtype", ""))
        shape = tuple(int(s) for s in lm.get("shape") or ())
        a = np.asarray(a)
        if dt == np.int8 and a.dtype == np.uint8:
            a = a.view(np.int8)
        if a.dtype != dt or a.shape != shape:
            raise ValueError(
                f"kv payload structure mismatch: leaf {i} is "
                f"{a.dtype}{list(a.shape)}, descriptor says "
                f"{dt}{list(shape)}")
        if not shape or shape[0] != n:
            raise ValueError(
                f"kv payload structure mismatch: leaf {i} holds "
                f"{shape[0] if shape else 0} pages, "
                f"metadata says {n}")
        leaves.append(a)
    for j in range(n):
        if _page_crc(leaves, j) != int(crcs[j]):
            raise ValueError(f"kv page {j} checksum mismatch")
    return leaves


class MigrationTicket:
    """Async handle on one migration batch. ``poll()`` is non-blocking
    ("pending" | "ok" | "failed"); ``rows`` carries the device-resident
    page rows after a successful refetch."""

    __slots__ = ("direction", "handles", "count", "rung", "chunk",
                 "rows", "error", "duration_s", "_done", "_on_done")

    def __init__(self, direction: str, handles: List[int], count: int,
                 rung: int = 0, chunk=None,
                 on_done: Optional[Callable] = None):
        self.direction = direction        # "out" (spill) | "in" (refetch)
        self.handles = handles
        self.count = count
        self.rung = rung
        self.chunk = chunk                # device chunk to land (spill)
        self.rows = None                  # device rows to write (refetch)
        self.error: Optional[BaseException] = None
        self.duration_s = 0.0
        self._done = threading.Event()
        self._on_done = on_done

    def poll(self) -> str:
        if not self._done.is_set():
            return "pending"
        return "failed" if self.error is not None else "ok"

    def wait(self, timeout: Optional[float] = None) -> str:
        self._done.wait(timeout)
        return self.poll()

    def _finish(self, error: Optional[BaseException] = None):
        self.error = error
        self.chunk = None                 # drop the device reference
        self._done.set()
        if self._on_done is not None:
            try:
                self._on_done(self)
            except Exception:             # pragma: no cover - callback bug
                pass                      # must never kill the worker


class MigrationEngine:
    """Background double-buffered host<->device page transport.

    One daemon worker thread; per-direction submission queues (spills
    drain first); an in-flight window of `window` dispatched-but-
    unretired transfers. Submission never blocks — the decode scheduler
    hands work off and keeps stepping, so a chaos-hung migration stalls
    only the streams waiting on those pages."""

    def __init__(self, store: HostPageStore, *, window: int = 2,
                 name: str = "kv-migrate",
                 wake: Optional[Callable[[], None]] = None):
        if window < 1:
            raise ValueError(f"in-flight window must be >= 1, got {window}")
        self._store = store
        self._window = int(window)
        self._wake = wake                 # poked after every retirement
        self._m = tier_metrics()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._out_q: deque = deque()      # spills (device -> host)
        self._in_q: deque = deque()       # refetches (host -> device)
        self._inflight = 0                # submitted - retired
        self._spill_s: deque = deque(maxlen=256)
        self._refetch_s: deque = deque(maxlen=256)
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True)
        self._thread.start()

    # ------------------------------------------------------ submission

    def spill(self, chunk, handles: List[int], count: int,
              on_done: Optional[Callable] = None) -> MigrationTicket:
        """Queue a device->host spill. `chunk` is an already-gathered
        device pytree of `count` pages (plus rung padding); page j
        lands in `handles[j]`'s arena slot. The gather copied the
        content, so the caller releases the device pages immediately —
        the ticket only tracks when the host copy is durable."""
        t = MigrationTicket("out", list(handles), int(count),
                            chunk=chunk, on_done=on_done)
        self._submit(self._out_q, t)
        return t

    def refetch(self, handles: List[int], rung: int,
                on_done: Optional[Callable] = None) -> MigrationTicket:
        """Queue a host->device refetch of `handles`, padded to `rung`
        pages. On success ``ticket.rows`` holds the device page rows,
        shaped for the AOT'd `write_pages` executable."""
        t = MigrationTicket("in", list(handles), len(handles),
                            rung=int(rung), on_done=on_done)
        self._submit(self._in_q, t)
        return t

    def _submit(self, q: deque, t: MigrationTicket):
        with self._cond:
            if self._stop:
                raise RuntimeError("migration engine stopped")
            q.append(t)
            self._inflight += 1
            self._m["inflight"].set(self._inflight)
            self._cond.notify_all()

    # ---------------------------------------------------------- worker

    def _next(self, block: bool) -> Optional[MigrationTicket]:
        with self._cond:
            while True:
                if self._out_q:            # spills first: a refetch of an
                    return self._out_q.popleft()   # in-flight spill must
                if self._in_q:                     # see its payload land
                    return self._in_q.popleft()
                if self._stop or not block:
                    return None
                self._cond.wait(timeout=0.1)

    def _loop(self):
        from ..testing import chaos

        inflight: deque = deque()          # (ticket, t0) dispatched
        while True:
            t = self._next(block=not inflight)
            if t is None and not inflight:
                if self._stop:
                    return
                continue
            if t is not None:
                t0 = time.perf_counter()
                try:
                    chaos.maybe_fail(
                        "page.migrate",
                        detail=f"{t.direction}:{t.count}")
                    self._dispatch(t)
                except BaseException as exc:
                    self._retire_err(t, exc, t0)
                else:
                    inflight.append((t, t0))
            # retire the oldest once the window is full, or when the
            # queues are momentarily empty (nothing to overlap with)
            while inflight and (len(inflight) >= self._window
                                or not self._queued()):
                self._retire(*inflight.popleft())

    def _queued(self) -> bool:
        with self._lock:
            return bool(self._out_q or self._in_q)

    def _dispatch(self, t: MigrationTicket):
        """Start the device half of a transfer (async under jax)."""
        import jax

        if t.direction == "out":
            for leaf in jax.tree_util.tree_flatten(t.chunk)[0]:
                start = getattr(leaf, "copy_to_host_async", None)
                if start is not None:
                    start()
        else:
            rows = self._store.assemble(
                [TieredPageAllocator.handle_slot(h) for h in t.handles],
                t.rung)
            t.rows = jax.device_put(rows)

    def _retire(self, t: MigrationTicket, t0: float):
        """Block on the transfer, land payloads, finish the ticket."""
        import jax

        try:
            if t.direction == "out":
                leaves = [np.asarray(x) for x in
                          jax.tree_util.tree_flatten(t.chunk)[0]]
                for j, h in enumerate(t.handles):
                    self._store.put(
                        TieredPageAllocator.handle_slot(h), leaves, j)
                self._m["spills"].inc(t.count)
            else:
                jax.block_until_ready(t.rows)
                self._m["refetches"].inc(t.count)
        except BaseException as exc:
            self._retire_err(t, exc, t0)
            return
        t.duration_s = time.perf_counter() - t0
        from ..observability.tracez import RING as _RING

        span = "page.spill" if t.direction == "out" else "page.refetch"
        _RING.complete(span, t0, time.perf_counter(),
                       {"pages": t.count})
        self._m["migration_seconds"].labels(
            direction=t.direction).observe(t.duration_s)
        (self._spill_s if t.direction == "out"
         else self._refetch_s).append(t.duration_s)
        self._done(t, None)

    def _retire_err(self, t: MigrationTicket, exc: BaseException,
                    t0: float):
        t.duration_s = time.perf_counter() - t0
        self._done(t, exc)

    def _done(self, t: MigrationTicket, exc: Optional[BaseException]):
        with self._cond:
            self._inflight -= 1
            self._m["inflight"].set(self._inflight)
        t._finish(exc)
        if self._wake is not None:
            try:
                self._wake()
            except Exception:              # pragma: no cover
                pass

    # ------------------------------------------------------------ misc

    def stats(self) -> Dict:
        with self._lock:
            spill_s = sorted(self._spill_s)
            refetch_s = sorted(self._refetch_s)
            inflight = self._inflight
        def _p(vals, q):
            if not vals:
                return 0.0
            return vals[min(len(vals) - 1, int(q * len(vals)))]
        return {
            "window": self._window,
            "inflight": inflight,
            "host_arena_bytes": self._store.nbytes(),
            "spill_p50_ms": round(_p(spill_s, 0.50) * 1e3, 3),
            "spill_p95_ms": round(_p(spill_s, 0.95) * 1e3, 3),
            "refetch_p50_ms": round(_p(refetch_s, 0.50) * 1e3, 3),
            "refetch_p95_ms": round(_p(refetch_s, 0.95) * 1e3, 3),
        }

    def stop(self, timeout: float = 30.0):
        """Drain queued work and join the worker. Idempotent."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
