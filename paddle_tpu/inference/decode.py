"""Continuous-batching autoregressive decode engine (paged KV cache).

The DynamicBatcher serves stateless one-shot requests; LLM traffic is
iterative — every request is a prefill followed by many single-token
steps, and requests arrive and finish mid-flight. This engine is the
token-level analog of the batcher's shape-bucket design, over a PAGED
KV cache instead of per-slot contiguous panels:

  * the KV store is one device-resident page pool plus a
    per-sequence int32 block table; `memory.page_allocator` hands out
    refcounted page ids. What a page holds is the model kind's to say
    (`inference.model_kinds`: K and V, each one array a layer `[pages,
    page_tokens, heads * head_dim]`, for a GPT, one latent row a token
    a layer for `axk1`; page axis 0 on every leaf, a token's row whole,
    so a step writes rows into the arrays it was given); the engine
    threads the pools as one pytree. A kind may also keep state BY
    SLOT beside its pages (`kimi_linear`'s recurrent KDA state): the
    engine then hands the step its rows' slots and the prefill its
    slot, an admission overwrites its slot's state, and no prefix
    trie is built (`model_kinds`' docstring has the contract).
    Admission allocates pages,
    eviction releases them — capacity growth is a wider block table, never a cache copy
    (the contiguous engine re-packed the whole pool on every rung
    change);
  * the compute core is the model kind's prefill-into-pages — one
    dispatch builds a request's cache rows and writes them into the
    request's pool pages, so nothing of the cache crosses to the host
    — and its paged step, which advances EVERY active request one
    token, writing through the block table and attending over the
    pages (for a GPT both come from the one builder,
    `models.gpt.gpt_paged_fns`);
  * all device entry points run through an `AotCache` — the fused
    prefill per prompt rung, the step per (batch-rung x page-rung)
    bucket, plus one traced-scalar copy-on-write executable — so after
    `warmup()` a steady-state token stream compiles nothing, across
    any admission/eviction churn;
  * **prefix sharing**: a hash trie caches page-aligned prompt
    prefixes. A second request with the same system prompt maps the
    cached pages (refcount++) and only prefills its tail — the tail
    tokens ride the normal batched decode step, so a hit admission does
    zero extra device work. A slot's first write into a shared page
    triggers copy-on-write through the allocator's refcounts;
  * pool exhaustion is typed RESOURCE_EXHAUSTED backpressure on the
    victim stream (after LRU-evicting cold prefix-cache pages), never
    an engine crash — batch-mates keep streaming;
  * a greedy row's next token is picked on the device and stays there
    (one int32 a slot, the next step's input), so **a tick runs ahead
    of the host**: step k+1 is dispatched before step k's tokens are
    read, and the push, EOS test, page release and the next tables are
    made beside the device (`_step_once`); a row that ends by EOS is
    found out one step late and that step's result for it dropped.
    Sampling with temperature (optional top-k) is host-side numpy over
    the pulled logits, and a tick that holds such a row reads before it
    dispatches; the device graph stays deterministic per shape.

Streams: `submit()` returns a `DecodeStream`; tokens are pushed as they
are sampled (serve.py forwards them as incremental PDI2 frames), and a
failed request gets a typed error while its batch-mates keep streaming.
Chaos sites: `decode.stream` fires per token delivery,
`decode.page_alloc` per page allocation, `decode.preempt` per
preemption attempt, `page.migrate` per host-tier migration batch.

**Host-RAM KV tiering** (docs/serving.md "KV tiering", opt-in via
``host_pages=`` / PADDLE_TPU_DECODE_HOST_PAGES): with a
`memory.migration.TieredPageAllocator` + `MigrationEngine` behind the
pool, HBM becomes a cache over a much larger host-RAM page store.
Under pool pressure the engine *spills* cold trie-only pages (cold
shared prefixes, preempted streams' stashed state, finished
conversations) to pinned host arenas instead of destructively evicting
them — the trie entry swaps its device page for a negative host
handle. An admission whose prefix continues in the host tier parks on
an async *refetch* (only that stream waits; its slot stays free) and
then resumes with a full device hit, byte-identical content. QoS
preemption composes: the stash-to-trie pages ride the same
spill/restore path, so preempt-resume becomes a page copy instead of a
recompute.

Multi-tenant QoS (docs/serving.md "Multi-tenant QoS"): every request
carries a ``tenant`` (default ``"default"``) and an integer
``priority``. Admission is weighted-fair — the scheduler picks the
most-underserved tenant by weighted virtual time (tokens served /
weight, PADDLE_TPU_TENANT_WEIGHTS) — and per-tenant token-rate quotas
(PADDLE_TPU_TENANT_QUOTA, a token bucket per tenant) defer a tenant's
queued requests instead of running them. When a strictly
higher-priority request cannot be admitted, the lowest-priority active
slot is *preempted to host*: its pages go back to the allocator (full
pages are stashed in the prefix cache so a quick resume re-maps them),
prompt + tokens-so-far + seed stay host-side, and the request re-enters
admission when pressure drops. Resume is a fresh admission over
``prompt + generated``; the per-(seed, position) counter RNG makes the
resumed stream token-identical to an unpreempted run, and the live
`DecodeStream` survives preemption so the client-facing seq stream is
gapless.

`SpecDecodeEngine` layers draft-and-verify speculative decoding on the
same machinery: a small draft GPT runs k greedy steps per tick over its
own page pool (same allocator, same block tables), the target scores
all k+1 positions in one verify forward (the kind's `verify_fn`), and a
rejection rolls back by truncating `cache_len` and releasing the
stranded block-table tail (`PageAllocator.release_range`). Enabled via
PADDLE_TPU_DECODE_SPECULATE / PADDLE_TPU_DECODE_DRAFT_MODEL or serve's
--speculate-k/--draft-model; default off.
"""
from __future__ import annotations

import hashlib
import heapq
import json
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiler
from ..core import flags as _flags
from ..core import monitor
from ..jit.compile_cache import AotCache, aot_compile
from ..memory.migration import (HostPageStore, MigrationEngine,
                                TieredPageAllocator, deserialize_pages,
                                serialize_pages, tier_metrics)
from ..memory.page_allocator import (PageAllocator, PageExhausted,
                                     gather_pages, write_pages)
from ..models.gpt import GPTConfig
from ..observability import counter, gauge, histogram
from ..observability import memz as _memz
from ..observability.spans import SpanRecorder, next_request_id
from ..observability.tracez import RING as _RING
from ..quant.ptq import is_quantized as _params_quantized
from ..quant.ptq import quantize_params
from ..testing import chaos
from .batching import (_WARMUP_SIG_CAP, bucket_ladder, next_bucket,
                       tenant_quotas as _tenant_quotas,
                       tenant_weights as _tenant_weights)
from . import model_kinds
from .errors import (ERR_FAILED_PRECONDITION, ERR_INVALID_ARGUMENT,
                     ERR_RESOURCE_EXHAUSTED, ERR_UNAVAILABLE,
                     TypedServeError)
from .model_kinds import (GPTKind,  # noqa: F401
                          kv_fingerprint, kv_page_bytes)

DEFAULT_MAX_SLOTS = 8          # CPU fallback when HBM stats are absent
DEFAULT_MAX_NEW_TOKENS = 64
DEFAULT_PAGE_TOKENS = 16       # mirrors PADDLE_TPU_DECODE_PAGE_TOKENS

_METRICS = None


def _decode_metrics():
    """Register (idempotently) and return the paddle_tpu_decode_* family."""
    global _METRICS
    if _METRICS is None:
        _METRICS = {
            "tokens": counter(
                "paddle_tpu_decode_tokens_total",
                "Tokens sampled by the decode engine (prefill + steps)"),
            "steps": counter(
                "paddle_tpu_decode_steps_total",
                "Batched decode steps executed (one per token column)"),
            "ahead_steps": counter(
                "paddle_tpu_decode_ahead_steps_total",
                "Decode steps dispatched before the tokens of the step "
                "ahead of them were read on the host"),
            "prefills": counter(
                "paddle_tpu_decode_prefills_total",
                "Fused prefill-into-pages dispatches: one per miss "
                "admission (none if its page allocation fails first) "
                "or KV-handoff export"),
            "evictions": counter(
                "paddle_tpu_decode_cache_evictions_total",
                "KV-cache slot evictions by reason",
                labelnames=("reason",)),
            "occupancy": gauge(
                "paddle_tpu_decode_slot_occupancy",
                "Active sequences / slot-pool capacity (0..1)"),
            "active": gauge(
                "paddle_tpu_decode_active_requests",
                "Sequences currently holding a KV slot"),
            "prefill_latency": histogram(
                "paddle_tpu_decode_prefill_latency_seconds",
                "Fused prefill-into-pages latency per dispatch: prefill "
                "plus the K/V page write until the pools are ready, "
                "until the first token is read on an admission"),
            "step_latency": histogram(
                "paddle_tpu_decode_step_latency_seconds",
                "Batched decode-step latency, dispatch until its tokens "
                "are on the device (or, of a tick that samples on the "
                "host, until its logits are pulled)"),
            "ttft": histogram(
                "paddle_tpu_decode_ttft_seconds",
                "Submit-to-first-token latency per request"),
            # paged KV pool
            "page_pool_size": gauge(
                "paddle_tpu_decode_page_pool_pages",
                "Allocatable KV pages in the decode page pool"),
            "page_in_use": gauge(
                "paddle_tpu_decode_page_in_use",
                "KV pages currently allocated (refcount >= 1)"),
            "page_shared": gauge(
                "paddle_tpu_decode_page_shared",
                "KV pages mapped by more than one owner (refcount > 1)"),
            "page_fragmentation": gauge(
                "paddle_tpu_decode_page_fragmentation",
                "Free-list fragmentation of the KV page pool (0..1)"),
            "page_allocs": counter(
                "paddle_tpu_decode_page_allocs_total",
                "KV pages handed out by the decode page allocator"),
            "page_alloc_failures": counter(
                "paddle_tpu_decode_page_alloc_failures_total",
                "Page allocations refused (pool exhausted or chaos)"),
            "cow": counter(
                "paddle_tpu_decode_page_cow_copies_total",
                "Copy-on-write page copies (first write into a shared "
                "page)"),
            # prefix cache
            "prefix_hits": counter(
                "paddle_tpu_decode_prefix_hits_total",
                "Admissions that mapped at least one cached prefix page"),
            "prefix_misses": counter(
                "paddle_tpu_decode_prefix_misses_total",
                "Admissions that found no cached prefix page"),
            "prefix_hit_tokens": counter(
                "paddle_tpu_decode_prefix_hit_tokens_total",
                "Prompt tokens served from cached prefix pages"),
            "prefix_lookup_tokens": counter(
                "paddle_tpu_decode_prefix_lookup_tokens_total",
                "Prompt tokens offered to prefix-cache lookup"),
            "prefix_cached_pages": gauge(
                "paddle_tpu_decode_prefix_cached_pages",
                "Pages pinned by the prefix-cache trie"),
            "prefix_evictions": counter(
                "paddle_tpu_decode_prefix_evictions_total",
                "Prefix-cache entries LRU-evicted under pool pressure"),
            # speculative decoding
            "spec_draft_steps": counter(
                "paddle_tpu_decode_spec_draft_steps_total",
                "Batched draft-model decode steps executed"),
            "spec_accepted": counter(
                "paddle_tpu_decode_spec_accepted_tokens_total",
                "Drafted tokens accepted by target verification"),
            "spec_rejected": counter(
                "paddle_tpu_decode_spec_rejected_tokens_total",
                "Drafted tokens rejected by target verification"),
            "spec_acceptance": gauge(
                "paddle_tpu_decode_spec_acceptance_rate",
                "Cumulative accepted/drafted token ratio (0..1)"),
            "page_rollback_released": counter(
                "paddle_tpu_decode_page_rollback_released_total",
                "Page references released by speculative rollback "
                "(pages stranded past the last accepted token)"),
            # multi-tenant QoS
            "tenant_tokens": counter(
                "paddle_tpu_tenant_decode_tokens_total",
                "Tokens sampled by the decode engine per tenant",
                labelnames=("tenant",)),
            "tenant_admissions": counter(
                "paddle_tpu_tenant_admissions_total",
                "Requests admitted into a decode slot per tenant "
                "(resumes after preemption count again)",
                labelnames=("tenant",)),
            "tenant_shed": counter(
                "paddle_tpu_tenant_shed_total",
                "Requests refused at decode admission because the "
                "tenant was past its weighted share of the pending "
                "queue (typed RESOURCE_EXHAUSTED)",
                labelnames=("tenant",)),
            "tenant_quota_deferred": counter(
                "paddle_tpu_tenant_quota_deferred_total",
                "Requests deferred in the pending queue because the "
                "tenant's token-rate quota bucket was empty "
                "(PADDLE_TPU_TENANT_QUOTA)",
                labelnames=("tenant",)),
            "preemptions": counter(
                "paddle_tpu_decode_preemptions_total",
                "Active decode slots evicted to host so a "
                "higher-priority request could run"),
            "preempt_resumes": counter(
                "paddle_tpu_decode_preempt_resumes_total",
                "Preempted requests re-admitted into a decode slot"),
            "preempted_tokens": counter(
                "paddle_tpu_decode_preempted_tokens_total",
                "Generated tokens stashed host-side at preemption "
                "(re-prefilled or prefix-cache-mapped at resume)"),
            "preempted_waiting": gauge(
                "paddle_tpu_decode_preempted_waiting",
                "Preempted requests currently parked host-side "
                "awaiting re-admission"),
            # quantized serving
            "state_pool_bytes": gauge(
                "paddle_tpu_decode_state_pool_bytes",
                "Bytes of state that lives by slot beside the KV pages "
                "(a recurrent model kind's state pool; 0 for a kind "
                "whose streams keep pages only)"),
            "kv_page_bytes": gauge(
                "paddle_tpu_decode_kv_page_bytes",
                "HBM bytes one K+V page occupies at the engine's pool "
                "dtype (int8 pools: payload + per-row scales)"),
            "kv_quantized": gauge(
                "paddle_tpu_decode_kv_quantized",
                "1 when the engine's KV page pool is int8, 0 for fp32"),
            # routed expert layers (model kinds that have them)
            "routed_assignments": counter(
                "paddle_tpu_decode_routed_assignments_total",
                "Routed (token, expert) assignments computed here, by "
                "expert layer and held expert; accumulated on the "
                "device, read when stats() is",
                labelnames=("layer", "expert")),
            "routed_tokens": counter(
                "paddle_tpu_decode_routed_tokens_total",
                "Live tokens that went through the routers (prefill "
                "positions and decode rows), read when stats() is"),
        }
    return _METRICS


_HANDOFF_METRICS = None


def _handoff_metrics():
    """Register (idempotently) and return the paddle_tpu_handoff_*
    family — the engine-side half of disaggregated prefill/decode
    serving (docs/observability.md). Router-side orchestration counters
    live in `router.py` under paddle_tpu_router_*."""
    global _HANDOFF_METRICS
    if _HANDOFF_METRICS is None:
        _HANDOFF_METRICS = {
            "exports": counter(
                "paddle_tpu_handoff_exports_total",
                "KV-page handoffs exported by a prefill worker"),
            "imports": counter(
                "paddle_tpu_handoff_imports_total",
                "KV-page handoffs landed by a decode worker"),
            "rejects": counter(
                "paddle_tpu_handoff_rejects_total",
                "KV handoffs the receiving engine refused, by reason "
                "(compat, structure, checksum, exhausted, disabled)",
                labelnames=("reason",)),
            "pages": counter(
                "paddle_tpu_handoff_pages_total",
                "KV pages moved by handoffs, by direction "
                "(export, import)", labelnames=("direction",)),
            "bytes": counter(
                "paddle_tpu_handoff_bytes_total",
                "Serialized KV payload bytes moved by handoffs, by "
                "direction (export, import)", labelnames=("direction",)),
            "latency": histogram(
                "paddle_tpu_handoff_seconds",
                "Engine-side handoff latency by stage (export = "
                "prefill-if-miss + gather + serialize, import = "
                "validate + scatter + trie insert)",
                labelnames=("stage",)),
        }
    return _HANDOFF_METRICS


class _HandoffJob:
    """Pseudo-request for allocator accounting inside a KV handoff —
    `_alloc_pages` only reads `.id` (chaos detail, error messages) and
    `_owner_for` stamps its pages ``("handoff", id)``."""
    __slots__ = ("id",)

    def __init__(self):
        self.id = next_request_id()


_POOL_SEQ = [0]
_POOL_SEQ_LOCK = threading.Lock()


def _next_pool_label() -> str:
    """Unique page-pool label per engine in this process ("kv", "kv2",
    ...) so /memz and the mem gauges keep concurrent engines apart."""
    with _POOL_SEQ_LOCK:
        _POOL_SEQ[0] += 1
        n = _POOL_SEQ[0]
    return "kv" if n == 1 else f"kv{n}"


def greedy_picks(logits):
    """Every row's first best id, [B] int32: what `np.argmax` reads
    from the pulled rows, computed where the logits are."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# The last token of every slot stays on the device between steps, one
# int32 a slot. A step's packed rows [3, B] say, per row, whose slot it
# is (the slot count for a padding row: no such entry), the input token
# where the host knows it (-1: the slot's entry), and the cache length.

def step_inputs(last, rows):
    """(last_tok [B], cache_len [B]) of a step: the host's token for
    the rows that have one, the slot's entry of `last` for the rest."""
    slot, tok, clen = rows[0], rows[1], rows[2]
    return jnp.where(tok >= 0, tok, last.at[slot].get(mode="clip")), clen


def step_inputs_by_slot(last, rows):
    """`step_inputs` and the rows' slots [B], for a kind whose streams
    keep state by slot (a padding row: the slot count, its null slot)."""
    return step_inputs(last, rows) + (rows[0],)


def store_picks(logits, last, rows):
    """`last` with every row's greedy pick in its slot's entry."""
    return last.at[rows[0]].set(greedy_picks(logits), mode="drop")


def store_first(logits, last, slot):
    """`last` with a prefill's greedy pick ([1, V] logits) at `slot`."""
    return last.at[slot].set(greedy_picks(logits)[0])


def _launch(exe, *args):
    """Call `exe` without waiting for its outputs where it can be
    (`AotCache` hands out executables with a `dispatch`); anything else
    that stands in an executable's place is plainly called."""
    return getattr(exe, "dispatch", exe)(*args)


def _trie_owner(digest: bytes) -> tuple:
    """Allocator owner tag for a prefix-trie node (short digest hex)."""
    return ("trie", digest.hex()[:12])


def _slot_state(kind) -> bool:
    """Whether `kind`'s streams keep state by slot beside their pages
    (`model_kinds`' module docstring has the contract)."""
    return bool(getattr(kind, "slot_state", False))


def _slots_kw(kind, slots: int) -> Dict:
    """What such a kind's pools are told beside the page count (how
    many slots the state pool serves); nothing for the other kinds."""
    return {"slots": int(slots)} if _slot_state(kind) else {}


def fit_slot_count(step_bytes, budget: int, upper: int,
                   start: int = DEFAULT_MAX_SLOTS) -> int:
    """Largest slot count <= `upper` whose compiled step fits `budget`
    bytes of HBM. `step_bytes(n)` is the footprint of the largest step
    of an n-slot engine, or None when the compiler itself ran out of
    HBM. The footprint is close to linear in n (pools and gather
    temporaries both grow with it), so proportional moves inside the
    (largest fitting, smallest failing) bracket settle in two or three
    compiles."""
    fits, fails = 0, upper + 1
    n = min(upper, start)
    while True:
        need = step_bytes(n)
        if need is not None and need <= budget:
            fits = n
        else:
            fails = n
        nxt = n // 2 if need is None else n * budget // need
        n = min(max(nxt, 1), fails - 1)
        if n <= fits:
            break
    if fits:
        return fits
    if need is None:
        raise RuntimeError(
            "decode engine: the paged step does not fit this device's "
            "HBM even with one KV slot")
    return 1        # compiles, above the budget: one slot is the floor


def default_slot_count(step_jit, params, kind, page_tokens: int,
                       kv_dtype: str = "float32",
                       hbm_fraction: float = 0.5,
                       fallback: int = DEFAULT_MAX_SLOTS) -> int:
    """Size the slot pool from live HBM stats and the COMPILED step: the
    largest slot count whose biggest step executable (full batch rung x
    full block-table width) needs no more than what is in use now plus
    `hbm_fraction` of the free bytes. The compiler's own
    `memory_analysis()` is the cost model — it sees what arithmetic on
    logical shapes cannot: tile padding of the pools' minor dims and the
    step's gather temporaries, together several times the pools'
    logical bytes. `kind` is the model kind (`model_kinds`): it
    describes the pools of an n-slot engine. A device without memory stats (CPU)
    gets the fixed fallback so tests and benches behave identically."""
    used, limit = monitor.hbm_usage()
    if limit <= 0:
        return fallback
    budget = used + int(max(limit - used, 0) * hbm_fraction)
    pages_per_seq = -(-kind.max_seq_len // page_tokens)
    i32 = jnp.int32
    by_slot = _slot_state(kind)

    def step_bytes(n):
        # a kind with state by slot grows its state pool with n too
        pools = kind.pools_sds(n * pages_per_seq + 1, page_tokens, kv_dtype,
                               **_slots_kw(kind, n))
        rows = jax.ShapeDtypeStruct((n,), i32)
        try:
            exe, _ = aot_compile(
                step_jit, params, pools,
                jax.ShapeDtypeStruct((n, pages_per_seq), i32),
                *(rows,) * (3 if by_slot else 2),
                label=f"decode.sizing:{n}")
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return None
        m = exe.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)

    # logical cache bytes per slot bound the count from above, and say
    # where the probe starts: the step's footprint is the weights plus
    # the pools (its temporaries are a fraction of them), so from what
    # the pools' own bytes allow it settles in two or three compiles
    slot = kind.slot_bytes()
    upper = max(1, min(int((limit - used) // slot), 256))
    return fit_slot_count(step_bytes, budget, upper,
                          max(int((budget - used) // slot), 1))


def kv_capacity_ladder(max_seq_len: int,
                       floor: Optional[int] = None) -> List[int]:
    """Powers of two (times the floor) from the floor up to — and
    including — max_seq_len. The floor defaults to the page size so
    every rung is a formable page-granular capacity (no warmup
    signature the pool cannot realize)."""
    lo = int(floor) if floor else DEFAULT_PAGE_TOKENS
    if max_seq_len <= lo:
        return [int(max_seq_len)]
    vals, v = [], lo
    while v < max_seq_len:
        vals.append(v)
        v *= 2
    vals.append(int(max_seq_len))
    return sorted(set(vals))


class DecodeStream:
    """Consumer handle for one request's token stream.

    Events arrive in order: zero or more ``("token", tok, eos)`` then
    exactly one ``("done", tokens)`` — or a `TypedServeError` raised out
    of `next_event` / `result` if the stream died (engine stop, chaos,
    per-request failure)."""

    def __init__(self, req_id: int, prompt: List[int]):
        self.request_id = req_id
        self.prompt = list(prompt)
        self.tokens: List[int] = []      # generated so far (mirror)
        self.spec_drafted = 0            # speculative-decode stats
        self.spec_accepted = 0           # (stay 0 on the plain engine)
        self._q: queue.Queue = queue.Queue()
        self._pending: deque = deque()   # consumer-side unbatch buffer
        self._closed = False             # producer-side latch

    # -- producer (engine thread) ------------------------------------
    def _push_token(self, tok: int, eos: bool):
        if not self._closed:
            self.tokens.append(int(tok))
            self._q.put(("token", int(tok), bool(eos)))

    def _push_tokens(self, toks: List[int], eos: bool):
        # One queue put for a whole burst of committed tokens (the
        # speculative engine lands several per tick); `eos` applies to
        # the final token only — commits stop at the first eos, so an
        # earlier one can't occur. Consumers still see per-token
        # events: `_unbatch` expands the burst on their side.
        if not self._closed:
            toks = [int(t) for t in toks]
            self.tokens.extend(toks)
            self._q.put(("tokens", toks, bool(eos)))

    def _push_done(self):
        if not self._closed:
            self._closed = True
            self._q.put(("done", list(self.tokens)))

    def _push_error(self, err: TypedServeError):
        if not self._closed:
            self._closed = True
            self._q.put(("error", err))

    # -- consumer ----------------------------------------------------
    def _unbatch(self, ev):
        if ev[0] == "tokens":
            toks, eos = ev[1], ev[2]
            last = len(toks) - 1
            for i, t in enumerate(toks):
                self._pending.append(("token", t, eos and i == last))
            return self._pending.popleft()
        return ev

    def next_event(self, timeout: Optional[float] = None):
        if self._pending:
            return self._pending.popleft()
        try:
            ev = self._q.get(timeout=timeout)
        except queue.Empty:
            raise TypedServeError(
                ERR_UNAVAILABLE,
                f"decode stream {self.request_id}: no event within "
                f"{timeout}s") from None
        if ev[0] == "error":
            raise ev[1]
        return self._unbatch(ev)

    def poll(self):
        """Non-blocking `next_event`: the next pending event, or None
        when the queue is momentarily empty. Raises the stream's typed
        error like `next_event` if the stream died. Lets a single
        collector sweep many streams without parking one blocked
        thread per stream."""
        if self._pending:
            return self._pending.popleft()
        # An empty stream is the common answer and has to be cheap: a
        # collector that sweeps a stream a slot once a millisecond holds
        # the interpreter lock while it asks, and the scheduler thread
        # waits for it. Peeking the queue's deque takes no lock and
        # raises nothing (0.04 us against 1.7 for `queue.Empty`); an
        # event that lands right after the peek is read by the next poll.
        if not self._q.queue:
            return None
        try:
            ev = self._q.get_nowait()
        except queue.Empty:
            return None
        if ev[0] == "error":
            raise ev[1]
        return self._unbatch(ev)

    def events(self, timeout: Optional[float] = None):
        """Yield ("token", tok, eos) events until done; raises on error."""
        while True:
            ev = self.next_event(timeout=timeout)
            if ev[0] == "done":
                return
            yield ev

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream completes; returns generated tokens."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            left = None if deadline is None \
                else max(deadline - time.monotonic(), 0.0)
            ev = self.next_event(timeout=left)
            if ev[0] == "done":
                return ev[1]


DEFAULT_TENANT = "default"


class _Req:
    __slots__ = ("id", "prompt", "max_new", "temperature", "top_k",
                 "eos_id", "seed", "stream", "cache_len", "last_tok",
                 "generated", "pages", "input_tail", "feeding",
                 "t_submit", "t_admit", "prefill_s", "tenant", "priority",
                 "preempts", "deferred", "slot", "pending", "closing")

    def __init__(self, prompt, max_new, temperature, top_k, eos_id,
                 seed=None, tenant=DEFAULT_TENANT, priority=0):
        self.id = next_request_id()
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.seed = seed         # per-stream sampling seed (None -> engine RNG)
        self.stream = DecodeStream(self.id, prompt)
        self.cache_len = 0
        self.last_tok = 0
        self.generated: List[int] = []
        self.pages: List[int] = []       # block table (page ids, in order)
        self.input_tail: deque = deque() # prompt tokens still to feed
        self.feeding = False             # consuming prompt via the step
        self.t_submit = time.monotonic()
        self.t_admit = 0.0
        self.prefill_s = 0.0
        self.tenant = tenant
        self.priority = priority         # higher wins; may preempt lower
        self.preempts = 0                # times evicted to host
        self.deferred = False            # quota deferral counted once
        self.slot: Optional[int] = None  # its entry of the last tokens
        self.pending = 0                 # tokens on the device, unread
        self.closing = False             # its last token is dispatched


class _Batch:
    """The next step's inputs as the host prepares them, from counts:
    the requests in row order, the block tables [b_rung, w_rung] and
    the packed rows [3, b_rung] of `step_inputs`, both numpy until the
    step is dispatched (a request admitted meanwhile takes a row)."""
    __slots__ = ("reqs", "b_rung", "w_rung", "tables", "rows")

    def __init__(self, reqs, b_rung, w_rung, tables, rows):
        self.reqs = reqs
        self.b_rung = b_rung
        self.w_rung = w_rung
        self.tables = tables
        self.rows = rows


class _Flight:
    """A dispatched step whose tokens the host has not read: its rows
    as (request, whether the row's output is a token of the request: a
    row that feeds a prompt tail has none), the logits where the host
    is to sample from them, and when it was dispatched."""
    __slots__ = ("rows", "logits", "t0")

    def __init__(self, rows, logits, t0):
        self.rows = rows
        self.logits = logits
        self.t0 = t0


class _SpecReq(_Req):
    """_Req plus speculative-decode state: how far the draft pool has
    been written, the slot's adaptive speculation depth, and acceptance
    accounting for the adaptive-k policy."""
    __slots__ = ("draft_len", "spec_k", "accept_ema", "drafted",
                 "accepted")

    def __init__(self, prompt, max_new, temperature, top_k, eos_id,
                 seed=None, tenant=DEFAULT_TENANT, priority=0):
        super().__init__(prompt, max_new, temperature, top_k, eos_id,
                         seed=seed, tenant=tenant, priority=priority)
        self.draft_len = 0       # draft-pool rows written (positions)
        self.spec_k = 1          # per-slot adaptive k (set at admission)
        self.accept_ema = 1.0    # EMA of per-tick acceptance rate
        self.drafted = 0
        self.accepted = 0


class _PrefixCache:
    """Hash trie of page-aligned prompt prefixes -> pool pages.

    Keys are a SHA-1 hash *chain* over full pages of prompt tokens —
    entry i's digest commits to pages 0..i, so one dict lookup per page
    walks the trie without storing token arrays. Every device-resident
    entry holds one allocator reference; `lookup` retains matched pages
    on the caller's behalf (so an entry evicted a microsecond later
    cannot free a page the caller is about to map).

    Eviction is **leaf-first LRU**: among evictable entries, ones with
    no live child go first (ordered by last-touch tick), and only when
    every candidate is mid-chain does the oldest interior entry go —
    so surviving entries stay reachable instead of silently orphaned.
    Each entry tracks its parent digest and a live-child count to make
    leaf status O(1); forced mid-chain removals bump the `orphaned`
    stat (the children remain cached but can never be looked up again).
    The order is kept in one heap for the trie's life: an entry is
    pushed under its new key whenever the key changes (inserted,
    touched, a child gained or the last one lost, back from the host)
    and a copy whose key is no longer the entry's is skipped when it
    surfaces, so an eviction costs the pages it frees, not the trie.

    With a :class:`~paddle_tpu.memory.TieredPageAllocator` behind it,
    an entry's location may also be a negative **host handle**: the
    page content was spilled to the host tier. `lookup` stops at a
    spilled entry (the device chain ends there); the engine's tier path
    reads the continuation via `host_chain` and swaps locations back
    with `restore_entry` once the migration engine lands the pages.
    Single leaf lock, no device work or blocking calls under it; lock
    order is trie -> allocator everywhere."""

    def __init__(self, alloc: PageAllocator, page_tokens: int):
        self._alloc = alloc
        self._pt = int(page_tokens)
        self._lock = threading.Lock()
        # digest -> [loc, tick, parent_digest|None]; loc >= 0 is a
        # device page (one ref held), loc < 0 a host-tier handle
        self._entries: Dict[bytes, List] = {}
        self._kids: Dict[bytes, int] = {}     # digest -> live children
        # (leaf key, digest) of every device-resident entry under its
        # current key, among stale copies: see `_push` and `evict`
        self._heap: List[Tuple[tuple, bytes]] = []
        self._tick = 0
        self._evictions = 0
        self._orphaned = 0

    def _digests(self, prompt: Sequence[int]) -> List[bytes]:
        h, out = b"", []
        for i in range(len(prompt) // self._pt):
            chunk = np.asarray(prompt[i * self._pt:(i + 1) * self._pt],
                               np.int64).tobytes()
            h = hashlib.sha1(h + chunk).digest()
            out.append(h)
        return out

    def _remove(self, d: bytes, ent: List):
        """Drop one entry (lock held): release its device ref or host
        slot, unlink from its parent, count stranded descendants."""
        del self._entries[d]
        parent = ent[2]
        if parent is not None and parent in self._kids:
            self._kids[parent] -= 1
            if self._kids[parent] <= 0:
                del self._kids[parent]
                self._push(parent)            # a leaf now
        self._orphaned += self._kids.pop(d, 0)
        if ent[0] >= 0:
            self._alloc.release(ent[0], owner=_trie_owner(d))
        else:
            self._alloc.host_drop(ent[0])

    def lookup(self, prompt: Sequence[int],
               owner: Optional[tuple] = None) -> Tuple[List[int], int]:
        """Longest *device-resident* cached page-aligned prefix of
        `prompt`. Returns (pages, hit_tokens); each returned page has
        been retained for the caller — attributed to the caller's
        `owner` tag — who owns releasing every one."""
        pages: List[int] = []
        with self._lock:
            self._tick += 1
            for d in self._digests(prompt):
                ent = self._entries.get(d)
                if ent is None or ent[0] < 0:
                    break
                self._alloc.retain(ent[0], owner=owner)
                ent[1] = self._tick
                self._push(d)
                pages.append(ent[0])
        return pages, len(pages) * self._pt

    def host_chain(self, prompt: Sequence[int],
                   start: int) -> List[Tuple[bytes, int]]:
        """The contiguous run of HOST-resident entries continuing the
        device hit (`start` = device pages matched). Returns
        [(digest, handle)]; an IN_FLIGHT or missing entry ends the run
        — the caller just gets a shorter refetch, which is always
        correct."""
        from ..memory.migration import Residency

        out: List[Tuple[bytes, int]] = []
        with self._lock:
            for d in self._digests(prompt)[max(start, 0):]:
                ent = self._entries.get(d)
                if ent is None or ent[0] >= 0:
                    break
                if self._alloc.residency(ent[0]) != Residency.HOST:
                    break
                out.append((d, ent[0]))
        return out

    def insert(self, prompt: Sequence[int], pages: Sequence[int]):
        """Cache `prompt`'s full pages (pages[i] holds prompt rows
        [i*pt, (i+1)*pt)); already-cached prefixes are left in place.
        A spilled (host) entry whose content is being re-inserted live
        is upgraded back to the device page — the host copy is
        redundant from that moment."""
        from ..memory.migration import Residency

        with self._lock:
            self._tick += 1
            prev = None
            for d, p in zip(self._digests(prompt), pages):
                ent = self._entries.get(d)
                if ent is None:
                    self._alloc.retain(p, owner=_trie_owner(d))
                    self._entries[d] = [int(p), self._tick, prev]
                    self._push(d)
                    if prev is not None and prev in self._entries:
                        self._kids[prev] = self._kids.get(prev, 0) + 1
                        if self._kids[prev] == 1:
                            self._push(prev)  # mid-chain now
                elif ent[0] < 0 and \
                        self._alloc.residency(ent[0]) == Residency.HOST:
                    self._alloc.retain(p, owner=_trie_owner(d))
                    self._alloc.host_drop(ent[0])
                    ent[0] = int(p)
                    ent[1] = self._tick
                    self._push(d)
                prev = d

    def _leaf_key(self, d: bytes, ent: List):
        return (1 if self._kids.get(d) else 0, ent[1])

    def _push(self, d: bytes):
        """Enter `d` under its current key (lock held). Called wherever
        a device-resident entry's key changes; whatever copies it left
        behind are skipped by `evict`."""
        ent = self._entries.get(d)
        if ent is not None and ent[0] >= 0:
            heapq.heappush(self._heap, (self._leaf_key(d, ent), d))

    def evict(self, n: int) -> int:
        """Release up to `n` device-resident entries' pages, leaf-first
        LRU, re-deriving leaf status after every removal (so evicting a
        whole chain walks it tip-to-root instead of orphaning it)."""
        removed = 0
        with self._lock:
            # the heap outlives the call: a tick at a hundred slots
            # evicts at half a dozen page boundaries and two admissions,
            # a page or a few each, out of thousands of entries (a heap
            # built per call was 14 ms of such a tick, PR 29; a scan of
            # the trie per page 52 ms of a long admission, PR 28).
            heap = self._heap
            if len(heap) > 4 * len(self._entries) + 64:
                heap[:] = [(self._leaf_key(d, e), d)
                           for d, e in self._entries.items() if e[0] >= 0]
                heapq.heapify(heap)
            while removed < max(n, 0) and heap:
                key, d = heapq.heappop(heap)
                e = self._entries.get(d)
                if e is None or e[0] < 0 or key != self._leaf_key(d, e):
                    continue              # a copy the entry moved on from
                self._remove(d, e)        # pushes a parent that became a leaf
                removed += 1
            self._evictions += removed
        return removed

    # ------------------------------------------------- host-tier hooks

    def spill_victims(self, n: int) -> List[Tuple[bytes, int]]:
        """Up to `n` spillable entries, coldest leaves first: device-
        resident and trie-only (refcount 1 — nothing active maps the
        page, so its content is immutable and nobody stalls on it)."""
        with self._lock:
            cands = [(d, e) for d, e in self._entries.items()
                     if e[0] >= 0 and self._alloc.refcount(e[0]) == 1]
            cands.sort(key=lambda x: self._leaf_key(*x))
            return [(d, e[0]) for d, e in cands[:max(n, 0)]]

    def mark_spilled(self, d: bytes, page: int, handle: int) -> bool:
        """Swap an entry's location to its host handle and release the
        trie's device ref (this is what actually frees the page)."""
        with self._lock:
            ent = self._entries.get(d)
            if ent is None or ent[0] != page:
                return False
            ent[0] = int(handle)
            self._alloc.release(page, owner=_trie_owner(d))
            return True

    def restore_entry(self, d: bytes, handle: int, page: int) -> bool:
        """A refetch landed: point the entry back at a device page. The
        caller transfers its allocator reference to the trie. False if
        the entry moved on meanwhile (caller keeps the ref)."""
        with self._lock:
            ent = self._entries.get(d)
            if ent is None or ent[0] != handle:
                return False
            ent[0] = int(page)
            ent[1] = self._tick
            self._push(d)
            # the caller's allocator ref changes hands: attribution
            # follows it from the tier to this trie node
            self._alloc.retag(page, ("tier", handle), _trie_owner(d))
            return True

    def drop_by_handle(self, handle: int) -> bool:
        """Remove the entry parked on `handle` (failed migration): the
        cached content is gone, the stream degrades to a re-prefill."""
        with self._lock:
            for d, ent in self._entries.items():
                if ent[0] == handle:
                    self._remove(d, ent)
                    return True
        return False

    def drop_host_lru(self, n: int) -> int:
        """Drop up to `n` coldest HOST-resident entries to make room in
        the host tier (never IN_FLIGHT ones — a migration owns those
        slots)."""
        from ..memory.migration import Residency

        dropped = 0
        with self._lock:
            cands = sorted(
                ((d, e) for d, e in self._entries.items()
                 if e[0] < 0
                 and self._alloc.residency(e[0]) == Residency.HOST),
                key=lambda x: x[1][1])
            for d, e in cands[:max(n, 0)]:
                self._remove(d, e)
                dropped += 1
        return dropped

    def clear(self):
        with self._lock:
            for d, ent in self._entries.items():
                if ent[0] >= 0:
                    self._alloc.release(ent[0], owner=_trie_owner(d))
                else:
                    self._alloc.host_drop(ent[0])
            self._entries.clear()
            self._kids.clear()
            self._heap.clear()

    def cached_pages(self) -> int:
        """Entries held, device and host: one length, for the gauges."""
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict:
        with self._lock:
            host = sum(1 for e in self._entries.values() if e[0] < 0)
            return {"cached_pages": len(self._entries),
                    "host_entries": host,
                    "evictions": self._evictions,
                    "orphaned": self._orphaned}


class DecodeEngine:
    """Slot-pool continuous batcher over a model kind's paged
    incremental forward (`model_kinds`: a GPT, `axk1`, or
    `kimi_linear`, whose streams also keep state by slot): fixed device
    page pool + per-slot block tables, prefix sharing with
    copy-on-write, typed backpressure on exhaustion. `model` (a layer)
    or `cfg` + `params` say which model; the kind follows from their
    type."""

    _req_cls = _Req       # SpecDecodeEngine swaps in _SpecReq
    _speculative = False

    def __init__(self, model=None, *, cfg=None,
                 params: Optional[Dict] = None, eps: Optional[float] = None,
                 max_slots: Optional[int] = None,
                 max_new_tokens: int = DEFAULT_MAX_NEW_TOKENS,
                 eos_id: Optional[int] = None,
                 hbm_fraction: float = 0.5, seed: int = 0,
                 max_pending: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 tenant_weights=None, tenant_quota=None,
                 preempt: Optional[bool] = None,
                 kv_dtype: Optional[str] = None,
                 host_pages: Optional[int] = None,
                 handoff: Optional[bool] = None):
        if model is not None:
            from .. import framework
            kind = model_kinds.for_model(model, eps)
            params = framework.param_arrays(model)
        elif cfg is None or params is None:
            raise ValueError("DecodeEngine needs a model or (cfg, params)")
        else:
            kind = model_kinds.for_config(cfg, eps)
        self._kind = kind
        # state that lives by slot (a recurrent kind): the step takes
        # its rows' slots, the prefill its slot, the pools a slot count
        self._by_slot = _slot_state(kind)
        self.cfg = kind.cfg
        self.eps = kind.eps
        self.params = {k: jnp.asarray(v) for k, v in params.items()}
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.page_tokens = int(page_tokens or kind.default_page_tokens())
        if self.page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, "
                             f"got {self.page_tokens}")
        hp = int(host_pages) if host_pages is not None \
            else int(_flags.env_value("PADDLE_TPU_DECODE_HOST_PAGES"))
        self.host_pages = max(hp, 0)
        # disaggregated prefill/decode KV handoff (docs/serving.md):
        # export gathers a prompt's full pages through `pgather`, import
        # lands them through `ptier` + a prefix-trie insert so the
        # follow-up stream admits as a prefix hit
        self.handoff = bool(_flags.env_value("PADDLE_TPU_DECODE_HANDOFF")) \
            if handoff is None else bool(handoff)
        # the kind says which pool dtype it runs, and refuses (typed)
        # the optional features it does not have
        self.kv_dtype = kind.pool_dtype(
            kv_dtype, host_pages=self.host_pages, handoff=self.handoff,
            speculative=self._speculative)
        step_fn = kind.step_fn(self.page_tokens)
        # `jit_prefill` in a device trace, beside its ring label
        # `exec:decode.prefill` (the draft's is `jit_paged_prefill` /
        # `exec:decode.dprefill`): a reader of both pairs them by name
        prefill_fn = kind.prefill_fn(self.page_tokens, name="prefill")
        # The pools are donated: every call site rebinds them from the
        # result, so XLA updates the multi-MB pool buffers in place
        # instead of copying them per dispatch (the copy dominated
        # step/verify cost on CPU).
        step_jit = jax.jit(step_fn, donate_argnums=(1,))
        self.max_slots = int(max_slots) if max_slots \
            else default_slot_count(step_jit, self.params, kind,
                                    self.page_tokens, self.kv_dtype,
                                    hbm_fraction)
        self.max_pending = int(max_pending) if max_pending is not None \
            else 4 * self.max_slots
        self.batch_ladder = bucket_ladder(
            self.max_slots, env=_flags.env_value("PADDLE_TPU_DECODE_BUCKETS"))
        self.kv_ladder = kv_capacity_ladder(kind.max_seq_len,
                                            floor=self.page_tokens)
        # block-table width rungs: pages needed to hold each kv rung
        self.page_ladder = sorted(
            {-(-r // self.page_tokens) for r in self.kv_ladder})
        self.pages_per_seq = -(-kind.max_seq_len // self.page_tokens)
        # +1: page 0 is the reserved null/scratch page (table padding
        # and padded-batch writes land there, never on live data)
        self.num_pages = int(num_pages) if num_pages \
            else self.max_slots * self.pages_per_seq + 1
        pool_label = _next_pool_label()
        self._alloc = TieredPageAllocator(
            self.num_pages, host_pages=self.host_pages,
            label=pool_label) \
            if self.host_pages \
            else PageAllocator(self.num_pages, label=pool_label)
        use_prefix = prefix_cache if prefix_cache is not None \
            else bool(_flags.env_value("PADDLE_TPU_DECODE_PREFIX_CACHE"))
        # tiering spills and refetches *through* the trie — its entries
        # are the spill candidates and the resume index — and a handoff
        # import lands as a trie entry, so either mode implies the
        # prefix cache
        if self.host_pages or self.handoff:
            use_prefix = True
        # a page hit without the slot's state at that boundary would
        # serve wrong tokens: no trie for a kind with state by slot
        # (so nothing is shared, copied on write or stashed at a
        # preemption; a resume prefills prompt + generated anew)
        if self._by_slot:
            use_prefix = False
        self._prefix = _PrefixCache(self._alloc, self.page_tokens) \
            if use_prefix else None

        self._prefill_aot = AotCache(
            jax.jit(prefill_fn, donate_argnums=(1,)), "decode.prefill",
            donate_argnums=(1,))
        self._step_aot = AotCache(step_jit, "decode.pstep",
                                  donate_argnums=(1,))
        self._copy_aot = AotCache(
            jax.jit(kind.copy_page, donate_argnums=(0,)), "decode.pcow",
            donate_argnums=(0,))
        # the slots' last tokens stay on the device (`step_inputs`):
        # a greedy tick stores its picks there and the host pulls one
        # id a slot, not [B, V] logits (17 MB at 84 rows of a 50k
        # vocabulary, every tick), after the next step is dispatched
        self._tok_aot = AotCache(
            jax.jit(step_inputs_by_slot if self._by_slot else step_inputs),
            "decode.ptok")
        self._pick_aot = AotCache(jax.jit(store_picks), "decode.ppick")
        self._first_aot = AotCache(jax.jit(store_first), "decode.pfirst")
        # host-tier / handoff executables: `pgather` snapshots pages
        # into an independent buffer (pools NOT donated — the engine
        # keeps stepping on them), `ptier` scatters rows back in. The
        # KV handoff rides the same two executables — export gathers,
        # import scatters — so disaggregation adds zero new
        # pool-threading executables
        self._gather_aot = self._tier_write_aot = None
        if self.host_pages or self.handoff:
            self._gather_aot = AotCache(jax.jit(gather_pages),
                                        "decode.pgather")
            self._tier_write_aot = AotCache(
                jax.jit(write_pages, donate_argnums=(0,)), "decode.ptier",
                donate_argnums=(0,))

        self.fingerprint = kind.fingerprint(self.params)
        self._hm = _handoff_metrics() if self.handoff else None
        self._handoff_counts = {"exports": 0, "imports": 0, "rejects": 0}

        self._m = _decode_metrics()
        self._m["kv_page_bytes"].set(
            kind.page_bytes(self.page_tokens, self.kv_dtype))
        self._m["kv_quantized"].set(1 if self.kv_dtype == "int8" else 0)
        self._m["state_pool_bytes"].set(self._state_pool_bytes())
        self._spans = SpanRecorder(
            component="decode", metric="paddle_tpu_decode_span_seconds",
            help="Decode request stage latency (queue/prefill/decode)")
        self._rng = np.random.default_rng(seed)

        self._pending: deque = deque()
        self._paused: deque = deque()    # preempted-to-host requests
        self._active: List[_Req] = []
        # multi-tenant QoS: fair-share weights, token-rate quota buckets,
        # weighted virtual time per tenant (tokens served / weight)
        self._weights = _tenant_weights(tenant_weights)
        self._quota = _tenant_quotas(tenant_quota)
        self._vtokens: Dict[str, float] = {}
        self._quota_tokens: Dict[str, float] = {}
        self._quota_ts = time.monotonic()
        self._preempt_on = bool(
            _flags.env_value("PADDLE_TPU_DECODE_PREEMPT")) \
            if preempt is None else bool(preempt)
        self._pool_tree = None       # the kind's pools pytree, lazy
        # run-ahead state, the scheduler thread's alone: the slots'
        # last tokens on the device (lazy with the pools) and each
        # slot's index as a device scalar, the slots no request holds,
        # the step in flight, the admissions whose first token the
        # device picked and the host has not read, as (request, when
        # its prefill was dispatched), and the next step as prepared
        self._last = None
        self._slot_ids: List = []
        self._free_slots = list(range(self.max_slots))[::-1]
        self._flight: Optional[_Flight] = None
        self._firsts: List = []
        self._batch: Optional[_Batch] = None
        self._ahead_steps = 0
        self._routed_seen = None     # routed counters last exported
        # host tier (lazy with the pools): arena store + migration
        # worker + requests parked on an in-flight refetch
        self._store = None
        self._migrate: Optional[MigrationEngine] = None
        self._migrating: List = []   # [ticket, req, [(digest, handle)]]
        # KV-handoff jobs parked for the scheduler thread (pools are
        # donated on every step — only that thread may touch them);
        # each entry is (closure, reply Queue(1))
        self._handoff_q: deque = deque()
        self._handoff_live: set = set()   # handoff job ids holding pages
        # requests popped by _schedule but not yet in _active: they hold
        # pages during _admit, so the ghost audit must see them as live
        self._admitting: List = []
        self._tm = tier_metrics() if self.host_pages else None
        self._last_b_rung = self.batch_ladder[0]
        self._last_w_rung = self.page_ladder[0]
        self._steps = 0
        self._tokens = 0
        self._stop = False
        self._cond = threading.Condition()
        # memory plane: /memz renders this pool's owner attribution,
        # and the context callback feeds the ghost-page audit the set
        # of stream ids still alive (registered after _cond exists —
        # _memz_context reads the queues under it)
        _memz.register_pool(self._alloc, context_fn=self._memz_context)
        self._thread = threading.Thread(
            target=self._loop, name="decode-scheduler", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ API

    def submit(self, prompt: Sequence[int], max_new_tokens=None,
               temperature: float = 0.0, top_k: int = 0,
               eos_id=None, seed=None, tenant=None,
               priority=None) -> DecodeStream:
        toks = [int(t) for t in np.asarray(prompt, dtype=np.int64).reshape(-1)]
        if not toks:
            raise TypedServeError(ERR_INVALID_ARGUMENT, "empty prompt")
        if any(t < 0 or t >= self._kind.vocab_size for t in toks):
            raise TypedServeError(
                ERR_INVALID_ARGUMENT,
                f"prompt token out of range [0, {self._kind.vocab_size})")
        if len(toks) >= self._kind.max_seq_len:
            raise TypedServeError(
                ERR_INVALID_ARGUMENT,
                f"prompt length {len(toks)} leaves no room to generate "
                f"(max_seq_len={self._kind.max_seq_len})")
        tenant = str(tenant).strip() if tenant else DEFAULT_TENANT
        req = self._req_cls(toks,
                            int(max_new_tokens or self.max_new_tokens),
                            float(temperature), int(top_k),
                            self.eos_id if eos_id is None else int(eos_id),
                            seed=None if seed is None else int(seed),
                            tenant=tenant,
                            priority=0 if priority is None else int(priority))
        with self._cond:
            if self._stop:
                raise TypedServeError(ERR_UNAVAILABLE,
                                      "decode engine stopped")
            # each tenant gets a weighted share of the pending queue, so
            # a flood tenant saturates its own share while others keep
            # a clear path to admission. A single tenant's share is the
            # whole queue — the pre-QoS backpressure behavior. With
            # several tenants queued the per-tenant share IS the
            # watermark (a flood filling the global queue must not shed
            # everyone else); 2x the watermark is the hard backstop.
            mine = sum(1 for r in self._pending if r.tenant == tenant)
            tset = {r.tenant for r in self._pending}
            tset.add(tenant)
            if len(tset) <= 1:
                share = self.max_pending
                over = len(self._pending) >= self.max_pending
            else:
                wsum = sum(self._weight(t) for t in tset)
                share = max(1, int(round(
                    self.max_pending * self._weight(tenant) / wsum)))
                over = (mine >= share
                        or len(self._pending) >= 2 * self.max_pending)
            if over:
                self._m["tenant_shed"].labels(tenant=tenant).inc()
                raise TypedServeError(
                    ERR_RESOURCE_EXHAUSTED,
                    f"decode queue full ({self.max_pending} pending): "
                    f"tenant {tenant!r} holds {mine} of its "
                    f"{share}-slot share")
            self._pending.append(req)
            self._cond.notify_all()
        return req.stream

    def _weight(self, tenant: str) -> float:
        return self._weights.get(tenant, self._weights["*"])

    def _quota_rate(self, tenant: str) -> float:
        return self._quota.get(tenant, self._quota["*"])

    def _state_pool_bytes(self) -> int:
        return int(self._kind.state_bytes(self.max_slots)) \
            if self._by_slot else 0

    def _model_pools_sds(self):
        """The model kind's pools, described: what step, prefill and
        copy-on-write take."""
        return self._kind.pools_sds(self.num_pages, self.page_tokens,
                                    self.kv_dtype, **_slots_kw(self._kind, self.max_slots))

    # The tier moves every pool an engine owns as ONE pytree — the base
    # engine's model pools, the speculative engine's plus its draft's —
    # so one gather/scatter executable per page rung migrates a page's
    # full footprint. Subclasses that add pools override these three
    # hooks.

    def _pools(self):
        return self._pool_tree

    def _set_pools(self, pools):
        self._pool_tree = pools

    def _pools_sds(self):
        return self._model_pools_sds()

    def _ensure_pool(self):
        if self._pool_tree is None:
            self._pool_tree = self._kind.pools_zeros(
                self.num_pages, self.page_tokens, self.kv_dtype,
                **_slots_kw(self._kind, self.max_slots))
            self._last = jnp.zeros((self.max_slots,), jnp.int32)
            self._slot_ids = [jnp.asarray(i, jnp.int32)
                              for i in range(self.max_slots)]
        if self.host_pages and self._migrate is None:
            self._store = HostPageStore(self._pools_sds(), self.host_pages)
            self._migrate = MigrationEngine(
                self._store, window=2, name="kv-migrate",
                wake=self._tier_wake)

    def _tier_wake(self):
        """Migration-worker completion callback: poke the scheduler so
        `_tier_poll` runs promptly (no other lock is ever held here)."""
        with self._cond:
            self._cond.notify_all()

    # One prefill path (the kind's prefill-into-pages): the target
    # model's admission, the KV-handoff export and the speculative
    # engine's draft all dispatch it through these two.

    def _prefill_exe(self, aot, params, pools, rung):
        """`aot`'s fused prefill-into-pages executable for one kv rung
        (the pools may be arrays or their ShapeDtypeStructs)."""
        i32 = jnp.int32
        slot = (jax.ShapeDtypeStruct((), i32),) if self._by_slot else ()
        return aot.get_or_compile(
            params, pools,
            jax.ShapeDtypeStruct((1, rung), i32),
            jax.ShapeDtypeStruct((1, -(-rung // self.page_tokens)), i32),
            jax.ShapeDtypeStruct((1,), i32), *slot,
            key=("prefill", 1, rung))

    def _prefill_into_pages(self, aot, params, pools, toks, pages,
                            wait: bool = True, slot: Optional[int] = None):
        """One dispatch: `toks` prefilled at their kv rung and their
        cache rows written into `pages` of the (donated) pools; table
        padding aims at the null page. A kind with state by slot also
        overwrites the state of `slot` with the sequence's. Returns
        (logits [1, V], pools), all on the device: ready, or with
        `wait` off as soon as the program is enqueued."""
        plen = len(toks)
        rung = next_bucket(plen, self.kv_ladder)
        inp = np.zeros((1, rung), np.int32)
        inp[0, :plen] = toks
        table = np.zeros((1, -(-rung // self.page_tokens)), np.int32)
        table[0, :len(pages)] = pages
        exe = self._prefill_exe(aot, params, pools, rung)
        # numpy arrays go up as they are; a list would cost a program
        # to cast it (`convert_element_type`) every admission
        args = (params, pools, jnp.asarray(inp), jnp.asarray(table),
                jnp.asarray(np.asarray([plen], np.int32)))
        if self._by_slot:
            args += (self._slot_ids[slot],)
        return exe(*args) if wait else _launch(exe, *args)

    def _token_exes(self, b_rung):
        """The two tiny programs around a step of `b_rung` rows:
        `step_inputs` (with the rows' slots for a kind with state by
        slot) before it, `store_picks` after it."""
        i32 = jnp.int32
        last = jax.ShapeDtypeStruct((self.max_slots,), i32)
        rows = jax.ShapeDtypeStruct((3, b_rung), i32)
        logits = jax.ShapeDtypeStruct((b_rung, self._kind.vocab_size),
                                      jnp.float32)
        return (self._tok_aot.get_or_compile(last, rows,
                                             key=("ptok", b_rung)),
                self._pick_aot.get_or_compile(logits, last, rows,
                                              key=("ppick", b_rung)))

    def _step_exe(self, pools, b_rung, w_rung):
        """The step's executable at one (batch rung, page rung); the
        pools may be arrays or their ShapeDtypeStructs."""
        rows = jax.ShapeDtypeStruct((b_rung,), jnp.int32)
        return self._step_aot.get_or_compile(
            self.params, pools,
            jax.ShapeDtypeStruct((b_rung, w_rung), jnp.int32),
            *(rows,) * (3 if self._by_slot else 2),
            key=("pstep", b_rung, w_rung))

    def _first_exe(self):
        i32 = jnp.int32
        return self._first_aot.get_or_compile(
            jax.ShapeDtypeStruct((1, self._kind.vocab_size), jnp.float32),
            jax.ShapeDtypeStruct((self.max_slots,), i32),
            jax.ShapeDtypeStruct((), i32), key=("pfirst",))

    def warmup(self, verbose: bool = False) -> int:
        """AOT-compile the fused prefill-into-pages prompt rungs, the
        copy-on-write executable, the three token programs (a step's
        inputs and its picks per batch rung, a prefill's pick), and the
        decode (batch-rung x page-rung) cross product (capped, largest
        rungs first dropped last). Returns the number of fresh
        compiles."""
        before = len(profiler.compile_events())
        i32 = jnp.int32
        pool = self._model_pools_sds()
        for r in self.kv_ladder:
            self._prefill_exe(self._prefill_aot, self.params, pool, r)
        self._copy_aot.get_or_compile(
            pool,
            jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32),
            key=("pcow",))
        if self.host_pages or self.handoff:
            # tier/handoff executables per page rung: gather (spill or
            # handoff export) + scatter (refetch or handoff import)
            # over the full pool tuple, so steady-state migration AND
            # steady-state handoff — like steady-state decode —
            # compile nothing
            pools = self._pools_sds()
            for w in self.page_ladder:
                ids = jax.ShapeDtypeStruct((w,), i32)
                rows = jax.tree.map(
                    lambda s, _w=w: jax.ShapeDtypeStruct(
                        (_w,) + s.shape[1:], s.dtype), pools)
                self._gather_aot.get_or_compile(
                    pools, ids, key=("pgather", w))
                self._tier_write_aot.get_or_compile(
                    pools, rows, ids, key=("ptier", w))
        sigs = [(b, w) for b in self.batch_ladder for w in self.page_ladder]
        if len(sigs) > _WARMUP_SIG_CAP:
            sigs = sigs[:_WARMUP_SIG_CAP]
        for b, w in sigs:
            self._step_exe(pool, b, w)
        for b in self.batch_ladder:
            self._token_exes(b)
        self._first_exe()
        n = len(profiler.compile_events()) - before
        if verbose:
            print(f"DECODE WARMUP compiles={n} "
                  f"prefill_rungs={self.kv_ladder} "
                  f"page_rungs={self.page_ladder} "
                  f"step_sigs={len(sigs)}", flush=True)
        return n

    def stats(self) -> Dict:
        st = {
            "active": len(self._active),
            "pending": len(self._pending),
            "paused": len(self._paused),
            "max_slots": self.max_slots,
            "steps": self._steps,
            "ahead_steps": self._ahead_steps,
            "tokens": self._tokens,
            # rung of the most recent dispatch; the smallest formable
            # rung before the first one (never a bogus 0)
            "batch_rung": int(self._last_b_rung),
            "kv_rung": int(self._last_w_rung * self.page_tokens),
            "batch_ladder": list(self.batch_ladder),
            "kv_ladder": list(self.kv_ladder),
            "page_tokens": self.page_tokens,
            "kv_dtype": self.kv_dtype,
            "fingerprint": self.fingerprint,
            "kv_page_bytes": self._kind.page_bytes(self.page_tokens,
                                                   self.kv_dtype),
            "model_kind": self._kind.name,
            # state that lives by slot beside the pages (0, 0 for a
            # kind whose streams keep pages only)
            "state_pool_bytes": self._state_pool_bytes(),
            "state_slots": self.max_slots if self._by_slot else 0,
            "pages": self._alloc.stats(),
            "tenants": {t: round(v, 4)
                        for t, v in sorted(dict(self._vtokens).items())},
        }
        st.update(self._routed_stats())
        if self._prefix is not None:
            st["prefix_cache"] = self._prefix.stats()
        if self.handoff:
            st["handoff"] = dict(self._handoff_counts)
        if self.host_pages:
            ps = st["pages"]
            tier = {
                "host_pages_total": ps.get("host_pages_total",
                                           self.host_pages),
                "host_pages_used": ps.get("host_pages_used", 0),
                "spilled_total": ps.get("spilled_total", 0),
                "refetched_total": ps.get("refetched_total", 0),
                "parked_refetches": len(self._migrating),
            }
            if self._migrate is not None:
                tier.update(self._migrate.stats())
            st["kv_tier"] = tier
        return st

    def _routed_stats(self) -> Dict:
        """The kind's device-side counters (routed assignments by expert
        layer and held expert, tokens routed), read here and nowhere on
        the tick; what is new since the last read goes to the
        `paddle_tpu_decode_routed_*` counters. The scheduler thread
        donates the pools to every dispatch and rebinds them when it
        returns (a prefill of 8k tokens holds them for 0.4 s), so a read
        that finds them gone waits for the next tree."""
        deadline = time.monotonic() + 5.0
        while True:
            try:
                now = self._kind.counters(self._pool_tree)
                break
            except RuntimeError:        # donated under us
                if time.monotonic() > deadline:
                    return {}
                time.sleep(0.001)
        if not now:
            return {}
        seen = self._routed_seen or {"routed": [[0] * len(r)
                                                for r in now["routed"]],
                                     "routed_tokens": 0}
        for li, (row, old) in enumerate(zip(now["routed"], seen["routed"])):
            for e, (n, o) in enumerate(zip(row, old)):
                if n > o:
                    self._m["routed_assignments"].labels(
                        layer=str(li), expert=str(e)).inc(n - o)
        if now["routed_tokens"] > seen["routed_tokens"]:
            self._m["routed_tokens"].inc(
                now["routed_tokens"] - seen["routed_tokens"])
        self._routed_seen = now
        return now

    def stop(self):
        """Stop the scheduler; open streams get typed UNAVAILABLE."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout=30)
        if self._migrate is not None:
            self._migrate.stop()
        leftovers = (list(self._active) + list(self._pending)
                     + list(self._paused)
                     + [item[1] for item in self._migrating])
        self._active, self._pending = [], deque()
        self._paused = deque()
        self._migrating = []
        while self._handoff_q:
            _, box = self._handoff_q.popleft()
            box.put(("err", TypedServeError(
                ERR_UNAVAILABLE, "decode engine stopped")))
        for req in leftovers:
            req.stream._push_error(TypedServeError(
                ERR_UNAVAILABLE, "decode engine stopped"))
            self._release_pages(req)
        if self._prefix is not None:
            self._prefix.clear()
        self._m["active"].set(0)
        self._m["occupancy"].set(0.0)
        self._spans.close()

    # ------------------------------------------------------- scheduler

    def _loop(self):
        while True:
            with _RING.span("decode.loop", {"admits": 0, "active": 0}) as it:
                if not self._loop_once(it.args):
                    it.drop()            # the stop is no iteration
                    break
        # stop() fails every open stream: what the device still runs
        # for them is waited for and not read
        self._drain(read=False)

    def _loop_once(self, counts: dict) -> bool:
        """One scheduler iteration: wait for work, schedule, admit, step.
        False once the engine is stopped. `counts` is the `decode.loop`
        span's args. Every phase is a ring span on this thread
        (docs/observability.md lists them), so an iteration is tiled:
        what no span covers is the loop's own overhead.

        A tick (`_step_once`) leaves its step on the device, its tokens
        unread. The iteration that follows admits what is pending
        beside it (each prefill dispatched, not awaited: it runs as
        soon as the step ends), waits until the device is through
        (`decode.step.wait`), admits whoever arrived meanwhile, and
        dispatches the next step: between "ready" and that dispatch
        the host does nothing but look at the queue, admit and
        dispatch, so no step is queued ahead of a late admission."""
        round_ = self._schedule_round(idle=True)
        if round_ is None:
            return False
        try:
            self._admit_round(round_, counts)
            if self._flight is not None:
                with _RING.span("decode.step.wait"):
                    # the interpreter lock is free here: callers submit
                    jax.block_until_ready(self._last)
                self._m["step_latency"].observe(
                    time.perf_counter() - self._flight.t0)
                if self._pending or self._paused:
                    round_ = self._schedule_round(idle=False)
                    if round_ is None:
                        return False
                    self._admit_round(round_, counts)
            counts["active"] = len(self._active)
            if self._active:
                self._step_once()
            else:
                self._drain()    # rows that failed left a step behind
        except Exception as exc:  # engine-level failure: fail the
            # batch (typed), free its pages, keep serving newcomers
            err = exc if isinstance(exc, TypedServeError) else \
                TypedServeError(ERR_UNAVAILABLE,
                                f"decode scheduler failure: {exc}")
            self._drain(read=False)
            for req in self._active:
                req.stream._push_error(err)
                self._m["evictions"].labels(reason="error").inc()
                self._release_pages(req)
            self._active = []
            self._update_gauges()
        return True

    def _schedule_round(self, idle: bool):
        """Look at the queue under the scheduler lock (`decode.schedule`):
        (admissions, preemption victims), or None once the engine is
        stopped. With `idle` it first waits for there to be work at
        all; the round after a step's wait never waits."""
        with _RING.span("decode.schedule", {}) as sched:
            with self._cond:
                while (idle and not self._stop and not self._pending
                       and not self._paused and not self._active
                       and not self._migrating and not self._handoff_q):
                    with _RING.span("decode.idle"):
                        self._cond.wait(timeout=0.1)
                if self._stop:
                    sched.drop()
                    return None
                sched.args["pending"] = len(self._pending)
                sched.args["paused"] = len(self._paused)
                self._refill_quota()
                newly, victims = self._schedule()
                self._admitting = list(newly) + list(victims)
                if idle and not newly and not victims \
                        and not self._active and not self._handoff_q:
                    # everything queued is quota-blocked (or parked on
                    # an in-flight refetch): wait for the bucket refill
                    # / migration wake instead of spinning
                    with _RING.span("decode.idle"):
                        self._cond.wait(timeout=0.02)
        return newly, victims

    def _admit_round(self, round_, counts: dict):
        """Act on what `_schedule_round` picked: parked handoff jobs and
        landed refetches, the preemptions, then the admissions."""
        newly, victims = round_
        if self._handoff_q:
            self._handoff_drain()
        if self._migrating:
            self._tier_poll()
        for vic in victims:
            self._preempt(vic)
        for req in newly:
            if len(self._active) >= self.max_slots:
                # a preemption was abandoned (chaos) and its
                # candidate has no slot: requeue at the front
                with self._cond:
                    if req.preempts:
                        self._paused.appendleft(req)
                    else:
                        self._pending.appendleft(req)
                continue
            with _RING.span("decode.admit", {"req": req.id}) as adm:
                admitted = self._admit(req, adm.args)
                adm.args["ok"] = admitted
                if admitted:
                    self._active.append(req)
                    self._join_batch(req)
            counts["admits"] += 1
            if admitted:
                self._m["tenant_admissions"].labels(
                    tenant=req.tenant).inc()
                if req.preempts:
                    self._m["preempt_resumes"].inc()
        if self._admitting:
            with self._cond:
                self._admitting = []
        if newly or victims:
            self._update_gauges()

    # ------------------------------------------------- QoS scheduling

    def _schedule(self):
        """Pick this tick's admissions — and preemption victims — under
        `_cond`.

        Weighted fair queuing over tenants: a tenant's virtual time
        advances by tokens_served / weight, and each free slot goes to
        the quota-eligible tenant head with the smallest virtual time
        (preempted requests queue ahead of their tenant's fresh ones).
        A tenant whose quota bucket is in debt is skipped — its requests
        wait, they are never dropped. When no slot is free and
        preemption is enabled, a head with strictly higher priority than
        the lowest-priority active slot evicts it and takes the slot."""
        newly: List[_Req] = []
        victims: List[_Req] = []
        free = self.max_slots - len(self._active)
        preemptable = list(self._active)
        while True:
            heads: Dict[str, tuple] = {}
            for q in (self._paused, self._pending):
                for r in q:
                    heads.setdefault(r.tenant, (q, r))
            eligible: Dict[str, tuple] = {}
            for t, (q, r) in heads.items():
                if self._quota_ok(t):
                    eligible[t] = (q, r)
                elif not r.deferred:
                    r.deferred = True
                    self._m["tenant_quota_deferred"].labels(
                        tenant=t).inc()
            if not eligible:
                return newly, victims
            if free > 0:
                t = min(eligible,
                        key=lambda x: self._vtokens.get(x, 0.0))
                q, r = eligible[t]
                q.remove(r)
                free -= 1
            else:
                if not self._preempt_on or not preemptable:
                    return newly, victims
                # the highest-priority eligible head justifies evicting
                # the lowest-priority (most recently admitted) active
                # slot — and takes that slot itself, so a third tenant
                # cannot slip into the preempt-freed capacity
                t, (q, r) = max(eligible.items(),
                                key=lambda kv: kv[1][1].priority)
                vic = min(preemptable,
                          key=lambda a: (a.priority, -a.t_admit))
                if r.priority <= vic.priority:
                    return newly, victims
                q.remove(r)
                preemptable.remove(vic)
                victims.append(vic)
            newly.append(r)
            # an idle tenant re-entering service starts at the busy
            # tenants' floor, not at the ancient credit it banked
            floor = min((self._vtokens.get(a.tenant, 0.0)
                         for a in self._active), default=0.0)
            self._vtokens[r.tenant] = max(
                self._vtokens.get(r.tenant, 0.0), floor)

    def _preempt(self, req: _Req) -> bool:
        """Evict an active slot to host so a higher-priority request can
        run: stash resumable state, release every page, park the request
        in `_paused`. The live `DecodeStream` is untouched — the client
        just sees a pause. On chaos the preemption is abandoned and the
        victim keeps decoding."""
        try:
            chaos.maybe_fail("decode.preempt", detail=req.id)
        except Exception:
            return False
        # a resume replays prompt + generated: every token of the
        # victim comes home first (and may turn out to be its last)
        self._drain()
        if req.slot is None:
            return False
        self._preempt_stash(req)
        self._release_pages(req)
        req.cache_len = 0
        req.last_tok = 0
        req.input_tail = deque()
        req.feeding = False
        req.preempts += 1
        self._m["preemptions"].inc()
        self._m["preempted_tokens"].inc(len(req.generated))
        self._active = [r for r in self._active if r.id != req.id]
        self._batch = None
        with self._cond:
            self._paused.append(req)
        return True

    def _preempt_stash(self, req: _Req):
        """Keep a victim's FULL pages alive in the prefix cache, keyed
        by the tokens they hold, so a quick resume re-maps them instead
        of re-prefilling. The partial last page is excluded — its rows
        past the last page boundary were never written."""
        if self._prefix is None:
            return
        pt = self.page_tokens
        toks = (req.prompt + req.generated)[:req.cache_len]
        if len(toks) >= pt:
            self._prefix.insert(toks, req.pages[:len(toks) // pt])

    def _refill_quota(self):
        """Advance every tenant's token bucket by elapsed wall time
        (rate tokens/s, burst = max(rate, 1)). Loop thread only."""
        now = time.monotonic()
        dt = now - self._quota_ts
        if dt <= 0:
            return
        self._quota_ts = now
        for t in list(self._quota_tokens):
            rate = self._quota_rate(t)
            if rate > 0:
                self._quota_tokens[t] = min(
                    self._quota_tokens[t] + dt * rate, max(rate, 1.0))

    def _quota_ok(self, tenant: str) -> bool:
        rate = self._quota_rate(tenant)
        if rate <= 0:
            return True
        if tenant not in self._quota_tokens:
            self._quota_tokens[tenant] = max(rate, 1.0)
        return self._quota_tokens[tenant] > 0.0

    def _note_token(self, req: _Req, n: int = 1):
        """Charge `n` sampled tokens to the request's tenant: advances
        its weighted virtual time and drains its quota bucket (which may
        go negative — the debt defers the tenant's next admission)."""
        t = req.tenant
        self._vtokens[t] = self._vtokens.get(t, 0.0) + n / self._weight(t)
        rate = self._quota_rate(t)
        if rate > 0:
            self._quota_tokens[t] = self._quota_tokens.get(
                t, max(rate, 1.0)) - n
        self._m["tenant_tokens"].labels(tenant=t).inc(n)

    # ---------------------------------------------------- page plumbing

    def _owner_for(self, req) -> tuple:
        """The memz owner tag stamped on pages `req` holds: handoff
        jobs own as ``("handoff", id)``, decode slots as
        ``("slot", id, tenant)`` (SpecDecodeEngine retags its streams
        ``("draft", id)`` so spec pages roll up separately)."""
        if isinstance(req, _HandoffJob):
            return ("handoff", req.id)
        return ("slot", req.id, getattr(req, "tenant", DEFAULT_TENANT))

    def _memz_context(self) -> Dict:
        """Engine context for /memz snapshots and OOM dumps: the ids of
        every stream legitimately holding pages (the ghost-page audit's
        live set) plus the ladder state that shapes allocations."""
        with self._cond:
            live = [r.id for r in self._active]
            live += [r.id for r in self._pending]
            live += [r.id for r in self._paused]
            live += [r.id for r in self._admitting]
            live += [item[1].id for item in self._migrating]
            live += list(self._handoff_live)
        return {"live_owner_ids": [str(i) for i in live],
                "kv_ladder": list(self.kv_ladder),
                "page_ladder": list(self.page_ladder),
                "page_tokens": self.page_tokens,
                "prefix_cache": self._prefix is not None}

    def _release_pages(self, req: _Req):
        """Drop the slot's reference on every page it maps (exactly one
        ref per block-table entry), and its slot. Idempotent via the
        resets. A step in flight may still write the request's row:
        whoever takes the pages or the slot next writes them later on
        the device, and reads no row it has not written."""
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None
        owner = self._owner_for(req)
        pages, req.pages = req.pages, []
        for p in pages:
            try:
                self._alloc.release(p, owner=owner)
            except ValueError:       # never expected; don't mask the
                pass                 # caller's error path if it happens
        self._update_gauges()

    def _alloc_pages(self, n: int, req: _Req,
                     owner: Optional[tuple] = None) -> List[int]:
        """Allocate `n` pages for `req`: chaos site, then the pool, then
        — under pressure — LRU-evict cold prefix-cache pages and retry
        once. Failure is typed RESOURCE_EXHAUSTED for THIS request.
        `owner` overrides the request-derived memz tag (tier restores
        allocate on behalf of the tier, not the parked slot)."""
        owner = owner or self._owner_for(req)
        try:
            chaos.maybe_fail("decode.page_alloc", detail=req.id)
        except Exception as exc:
            self._m["page_alloc_failures"].inc()
            raise TypedServeError(
                ERR_RESOURCE_EXHAUSTED,
                f"decode request {req.id}: page allocation failed: "
                f"{exc}") from exc
        retried = False
        while True:
            try:
                pages = self._alloc.alloc(n, owner=owner)
            except PageExhausted as exc:
                if not retried and self._prefix is not None:
                    shortfall = max(n - self._alloc.free_count(), 1)
                    # host tier first: spilling parks the content in
                    # host RAM (a later resume is a page copy, not a
                    # re-prefill); destructive LRU eviction only covers
                    # whatever the tier could not take
                    freed = self._tier_spill(shortfall) \
                        if self._migrate is not None else 0
                    evicted = 0
                    if freed < shortfall:
                        evicted = self._prefix.evict(shortfall - freed)
                        if evicted:
                            self._m["prefix_evictions"].inc(evicted)
                    if freed or evicted:
                        retried = True
                        continue
                self._m["page_alloc_failures"].inc()
                try:
                    # the OOM forensic dump: who held every page when
                    # this RESOURCE_EXHAUSTED fired (served /memz?oom=1)
                    _memz.capture_oom(self._alloc, owner=owner,
                                      requested=n,
                                      context=self._memz_context())
                except Exception:    # forensics must not mask the error
                    pass
                raise TypedServeError(
                    ERR_RESOURCE_EXHAUSTED,
                    f"decode request {req.id}: KV page pool exhausted "
                    f"({exc})") from exc
            self._m["page_allocs"].inc(n)
            return pages

    def _cow(self, req: _Req, slot: int):
        """First write into a shared page: copy it to a fresh page and
        repoint this slot's block table (the other owners keep the
        original — that's the isolation)."""
        old = req.pages[slot]
        (new,) = self._alloc_pages(1, req)
        exe = self._copy_aot.get_or_compile(
            self._pool_tree,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            key=("pcow",))
        self._pool_tree = exe(
            self._pool_tree,
            jnp.asarray(old, jnp.int32), jnp.asarray(new, jnp.int32))
        req.pages[slot] = new
        self._alloc.release(old, owner=self._owner_for(req))
        self._m["cow"].inc()

    # ---------------------------------------------------- host KV tier
    #
    # All tier work below runs on the scheduler thread (pool buffers
    # are DONATED on every step — only this thread may touch them); the
    # migration worker only ever sees independent buffers (the gather
    # snapshot, the device_put result) plus allocator/trie bookkeeping
    # behind their own leaf locks. Requests that need a refetch are
    # PARKED in `_migrating` — their slot stays free for other streams,
    # so a slow or chaos-hung migration stalls only the touching
    # stream.

    def _tier_spill(self, n: int) -> int:
        """Spill up to `n` cold trie-only pages to the host tier.
        Returns how many device pages were freed. The gather snapshot
        happens BEFORE the trie refs drop, so the pages being copied
        out are still allocated at gather dispatch; after
        `mark_spilled` they are free for the allocation that triggered
        the pressure."""
        victims = self._prefix.spill_victims(n)
        if not victims:
            return 0
        handles = self._alloc.spill_begin(len(victims))
        if len(handles) < len(victims):
            # host tier full: age out its coldest entries and retry —
            # anything still short of `n` falls to destructive evict
            if self._prefix.drop_host_lru(len(victims) - len(handles)):
                handles += self._alloc.spill_begin(
                    len(victims) - len(handles))
        victims = victims[:len(handles)]
        if not victims:
            return 0
        w = next_bucket(len(victims), self.page_ladder)
        ids = np.zeros(w, np.int32)
        ids[:len(victims)] = [p for _, p in victims]
        exe = self._gather_aot.get_or_compile(
            self._pools(), jax.ShapeDtypeStruct((w,), jnp.int32),
            key=("pgather", w))
        chunk = exe(self._pools(), jnp.asarray(ids))
        for (d, p), h in zip(victims, handles):
            self._prefix.mark_spilled(d, p, h)
        prefix, alloc = self._prefix, self._alloc

        def on_done(t):
            # migration-worker thread: pure bookkeeping. Failure drops
            # the trie entries — the content degrades to a re-prefill,
            # which is always token-identical, never wrong.
            for h in t.handles:
                try:
                    if t.error is not None:
                        raise ValueError
                    alloc.spill_commit(h)
                except ValueError:
                    prefix.drop_by_handle(h)
                    alloc.host_drop(h)

        self._migrate.spill(chunk, handles, len(victims), on_done=on_done)
        return len(victims)

    def _tier_fetch(self, req: _Req, chain) -> bool:
        """Launch an async refetch of `chain` ([(digest, handle)]) and
        park `req` until it lands. False when nothing could be pinned
        (the caller proceeds with its partial device hit)."""
        pinned = []
        for d, h in chain:
            try:
                self._alloc.refetch_begin(h)
            except ValueError:
                break
            pinned.append((d, h))
        if not pinned:
            return False
        w = next_bucket(len(pinned), self.page_ladder)
        t = self._migrate.refetch([h for _, h in pinned], rung=w)
        self._migrating.append([t, req, pinned])
        return True

    def _tier_poll(self):
        """Non-blocking sweep over parked refetches (scheduler thread,
        outside `_cond`): a landed ticket gets its pages written back
        into the pool and its request reinjected at the head of its
        queue; a failed one drops the spilled entries and the request
        degrades to the ordinary prefill path."""
        still, done = [], []
        for item in self._migrating:
            (done if item[0].poll() != "pending" else still).append(item)
        if not done:
            return
        self._migrating = still
        for t, req, pinned in done:
            ok = t.poll() == "ok" and self._tier_restore(t, req, pinned)
            if not ok:
                for d, h in pinned:
                    self._prefix.drop_by_handle(h)
                    self._alloc.host_drop(h)
            with self._cond:
                if req.preempts:
                    self._paused.appendleft(req)
                else:
                    self._pending.appendleft(req)
                self._cond.notify_all()
        self._update_gauges()

    def _tier_restore(self, t, req: _Req, pinned) -> bool:
        """Scatter a landed refetch into fresh pool pages and point the
        trie back at them; the request's next admission then sees a
        full device hit. False on allocation pressure — the entries
        drop and the request re-prefills instead."""
        self._drain()
        try:
            # the tier (not the parked slot) owns these pages until
            # restore_entry retags each one to its trie node
            pages = self._alloc_pages(len(pinned), req,
                                      owner=("tier", req.id))
        except TypedServeError:
            return False
        w = t.rung
        ids = np.zeros(w, np.int32)
        ids[:len(pages)] = pages
        exe = self._tier_write_aot.get_or_compile(
            self._pools(), t.rows,
            jax.ShapeDtypeStruct((w,), jnp.int32), key=("ptier", w))
        self._set_pools(exe(self._pools(), t.rows, jnp.asarray(ids)))
        for (d, h), p in zip(pinned, pages):
            if self._prefix.restore_entry(d, h, p):
                self._alloc.refetch_commit(h)
            else:                 # entry moved on: keep nothing
                self._alloc.release(p, owner=("tier", req.id))
                self._alloc.host_drop(h)
        return True

    # ----------------------------------------- prefill/decode KV handoff
    #
    # Disaggregated serving (docs/serving.md "Disaggregated
    # prefill/decode"): a prefill worker calls `export_kv` — run the
    # prompt forward if its full pages are not already cached, gather
    # them through the non-donating `pgather` snapshot, serialize with
    # per-page crc32 — and the router ships the payload to a decode
    # worker, whose `import_kv` validates compat, scatters the pages in
    # through `ptier`, and seeds the prefix trie so the follow-up
    # decode stream admits as an ordinary prefix hit. Both halves run
    # ON THE SCHEDULER THREAD (pool buffers are donated on every step)
    # via a parked-work queue the loop drains; the calling connection
    # thread waits on a one-slot reply box. Only the prompt's FULL
    # pages travel — the decode side re-feeds the tail and samples
    # every token itself, so token identity with a unified engine falls
    # out of the per-(seed, position) RNG, and a failed or refused
    # handoff degrades to a plain re-prefill (token-identical, same
    # contract as a failed tier refetch).

    def kv_compat(self) -> Dict:
        """The engine-identity facts a KV handoff must match to land
        here (the compat contract; docs/serving.md)."""
        return {"page_tokens": self.page_tokens,
                "kv_dtype": self.kv_dtype,
                "fingerprint": self.fingerprint}

    def _handoff_call(self, fn, timeout: float):
        """Park `fn` for the scheduler thread; wait for its reply."""
        if not self.handoff:
            raise TypedServeError(
                ERR_FAILED_PRECONDITION,
                "KV handoff is disabled on this engine (enable with "
                "handoff= / PADDLE_TPU_DECODE_HANDOFF)")
        box: queue.Queue = queue.Queue(1)
        with self._cond:
            if self._stop:
                raise TypedServeError(ERR_UNAVAILABLE,
                                      "decode engine stopped")
            self._handoff_q.append((fn, box))
            self._cond.notify_all()
        try:
            status, val = box.get(timeout=timeout)
        except queue.Empty:
            raise TypedServeError(
                ERR_UNAVAILABLE,
                f"KV handoff did not complete within {timeout}s") \
                from None
        if status == "err":
            raise val
        return val

    def _handoff_drain(self):
        """Run parked handoff jobs (scheduler thread, outside `_cond`).
        A job's failure goes back through its reply box — it must never
        poison the active batch the way a step failure does. The step
        in flight comes home first: a job's calls wait for the device."""
        self._drain()
        while True:
            with self._cond:
                if not self._handoff_q:
                    return
                fn, box = self._handoff_q.popleft()
            try:
                box.put(("ok", fn()))
            except BaseException as exc:
                self._handoff_counts["rejects"] += 1
                box.put(("err", exc))

    def export_kv(self, prompt: Sequence[int],
                  timeout: float = 30.0) -> Dict:
        """Prefill-side half of a KV handoff: ensure the prompt's full
        pages exist (prefix-cache hit, else one prefill), snapshot and
        serialize them. Returns the wire payload — compat metadata,
        the prompt tokens, per-leaf page arrays (int8 as uint8 views)
        and per-page checksums. ``n_pages`` may be 0 for a sub-page
        prompt; the importer then just seeds nothing and the decode
        worker re-prefills, which is still token-identical."""
        toks = [int(t)
                for t in np.asarray(prompt, np.int64).reshape(-1)]
        if not toks:
            raise TypedServeError(ERR_INVALID_ARGUMENT, "empty prompt")
        if any(t < 0 or t >= self._kind.vocab_size for t in toks):
            raise TypedServeError(
                ERR_INVALID_ARGUMENT,
                f"prompt token out of range [0, {self._kind.vocab_size})")
        if len(toks) >= self._kind.max_seq_len:
            raise TypedServeError(
                ERR_INVALID_ARGUMENT,
                f"prompt length {len(toks)} exceeds "
                f"max_seq_len={self._kind.max_seq_len}")
        return self._handoff_call(lambda: self._export_kv(toks), timeout)

    def import_kv(self, payload: Dict, timeout: float = 30.0) -> int:
        """Decode-side half of a KV handoff: validate the compat
        contract and the payload integrity, scatter the pages into the
        pool, and seed the prefix trie so the follow-up stream admits
        as a prefix hit. Returns the number of pages landed. Raises
        typed FAILED_PRECONDITION on any compat / structure / checksum
        mismatch — never a silent garbage admission."""
        return self._handoff_call(lambda: self._import_kv(payload),
                                  timeout)

    def _export_kv(self, toks: List[int]) -> Dict:
        t0 = time.perf_counter()
        pt = self.page_tokens
        n_full = len(toks) // pt
        self._ensure_pool()
        payload = self.kv_compat()
        payload["prompt"] = list(toks)
        if n_full == 0:
            payload.update(n_pages=0, leaves=[], crcs=[], arrays=[])
        else:
            job = _HandoffJob()
            owner = self._owner_for(job)
            with self._cond:
                self._handoff_live.add(job.id)
            try:
                pages = self._handoff_pages(toks, n_full, job)
                try:
                    w = next_bucket(n_full, self.page_ladder)
                    ids = np.zeros(w, np.int32)
                    ids[:n_full] = pages
                    exe = self._gather_aot.get_or_compile(
                        self._pools(),
                        jax.ShapeDtypeStruct((w,), jnp.int32),
                        key=("pgather", w))
                    chunk = exe(self._pools(), jnp.asarray(ids))
                    arrays, meta = serialize_pages(chunk, n_full)
                finally:
                    for p in pages:
                        self._alloc.release(p, owner=owner)
            finally:
                with self._cond:
                    self._handoff_live.discard(job.id)
            payload.update(meta)
            payload["arrays"] = arrays
        nbytes = sum(a.nbytes for a in payload["arrays"])
        self._handoff_counts["exports"] += 1
        self._hm["exports"].inc()
        self._hm["pages"].labels(direction="export").inc(n_full)
        self._hm["bytes"].labels(direction="export").inc(nbytes)
        self._hm["latency"].labels(stage="export").observe(
            time.perf_counter() - t0)
        _RING.complete("handoff.export", t0, time.perf_counter(),
                       {"pages": n_full, "bytes": nbytes})
        return payload

    def _handoff_pages(self, toks: List[int], n_full: int,
                       job: _HandoffJob) -> List[int]:
        """Device pages holding `toks`' first `n_full` full pages, one
        reference each held for the caller (attributed to `job`'s
        ``("handoff", id)`` tag): the cached chain when the trie
        already covers them, else one fused prefill-into-pages (which
        also seeds the trie — the next export of this prompt is pure
        gather)."""
        pt = self.page_tokens
        owner = self._owner_for(job)
        hit_pages, _ = self._prefix.lookup(toks, owner=owner)
        if len(hit_pages) >= n_full:
            for p in hit_pages[n_full:]:
                self._alloc.release(p, owner=owner)
            return hit_pages[:n_full]
        for p in hit_pages:
            self._alloc.release(p, owner=owner)
        pages = self._alloc_pages(n_full, job)
        t0 = time.perf_counter()
        # the partial last page's rows fall on the null page: only full
        # pages travel. (An AotCache call returns once its outputs are
        # ready, so the latency below is prefill + page write.)
        _, self._pool_tree = self._prefill_into_pages(
            self._prefill_aot, self.params, self._pool_tree, toks, pages)
        self._m["prefills"].inc()
        self._m["prefill_latency"].observe(time.perf_counter() - t0)
        self._prefix.insert(toks[:n_full * pt], pages)
        return pages

    def _handoff_reject(self, reason: str, detail: str):
        self._hm["rejects"].labels(reason=reason).inc()
        raise TypedServeError(ERR_FAILED_PRECONDITION,
                              f"kv_handoff refused: {detail}")

    def _import_kv(self, payload: Dict) -> int:
        t0 = time.perf_counter()
        mine = self.kv_compat()
        for key in ("page_tokens", "kv_dtype", "fingerprint"):
            theirs = payload.get(key)
            if theirs != mine[key]:
                self._handoff_reject(
                    "compat",
                    f"{key} mismatch (sender {theirs!r}, receiver "
                    f"{mine[key]!r})")
        toks = [int(t) for t in payload.get("prompt") or []]
        n = int(payload.get("n_pages") or 0)
        pt = self.page_tokens
        if not toks or n != len(toks) // pt:
            self._handoff_reject(
                "structure",
                f"page count {n} does not cover prompt length "
                f"{len(toks)} at page_tokens={pt}")
        self._ensure_pool()
        if n > 0:
            self._import_pages(payload, toks, n)
        self._handoff_counts["imports"] += 1
        self._hm["imports"].inc()
        self._hm["pages"].labels(direction="import").inc(n)
        self._hm["bytes"].labels(direction="import").inc(
            sum(np.asarray(a).nbytes for a in payload.get("arrays") or []))
        self._hm["latency"].labels(stage="import").observe(
            time.perf_counter() - t0)
        _RING.complete("handoff.import", t0, time.perf_counter(),
                       {"pages": n})
        return n

    def _import_pages(self, payload: Dict, toks: List[int], n: int):
        try:
            leaves = deserialize_pages(
                payload.get("arrays") or [],
                {"n_pages": n, "leaves": payload.get("leaves"),
                 "crcs": payload.get("crcs")})
        except ValueError as e:
            self._handoff_reject(
                "checksum" if "checksum" in str(e) else "structure",
                str(e))
        # the payload's leaf structure must be THIS engine's pool
        # structure — a speculative engine's footprint (target and
        # draft pools) can never land in a plain engine's (the
        # target's alone), nor across draft shapes
        sds = jax.tree_util.tree_flatten(self._pools_sds())[0]
        if len(leaves) != len(sds):
            self._handoff_reject(
                "structure",
                f"pool structure mismatch ({len(leaves)} payload "
                f"leaves, engine has {len(sds)})")
        for i, (a, s) in enumerate(zip(leaves, sds)):
            want = (n,) + tuple(s.shape[1:])
            if tuple(a.shape) != want \
                    or np.dtype(a.dtype) != np.dtype(s.dtype):
                self._handoff_reject(
                    "structure",
                    f"leaf {i} is {np.dtype(a.dtype)}{list(a.shape)}, "
                    f"engine pool wants "
                    f"{np.dtype(s.dtype)}{list(want)}")
        job = _HandoffJob()
        with self._cond:
            self._handoff_live.add(job.id)
        try:
            self._land_pages(leaves, toks, n, job)
        finally:
            with self._cond:
                self._handoff_live.discard(job.id)

    def _land_pages(self, leaves, toks: List[int], n: int,
                    job: _HandoffJob):
        """Scatter validated handoff leaves into fresh pool pages and
        seed the trie; pages are attributed to `job` while held."""
        try:
            pages = self._alloc_pages(n, job)
        except TypedServeError:
            self._hm["rejects"].labels(reason="exhausted").inc()
            raise
        w = next_bucket(n, self.page_ladder)
        padded = []
        for a in leaves:
            out = np.zeros((w,) + a.shape[1:], a.dtype)
            out[:n] = a
            padded.append(out)
        rows = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._pools_sds()), padded)
        ids = np.zeros(w, np.int32)
        ids[:n] = pages
        exe = self._tier_write_aot.get_or_compile(
            self._pools(), rows,
            jax.ShapeDtypeStruct((w,), jnp.int32), key=("ptier", w))
        self._set_pools(exe(self._pools(), rows, jnp.asarray(ids)))
        # the trie takes its own reference per inserted page; dropping
        # ours makes it the sole owner — imported pages age out (or
        # spill to the host tier) exactly like any cached prefix
        self._prefix.insert(toks[:n * self.page_tokens], pages)
        owner = self._owner_for(job)
        for p in pages:
            self._alloc.release(p, owner=owner)

    # ------------------------------------------------------- admission

    def _admit(self, req: _Req, note: dict) -> bool:
        """Give the request KV pages, a slot and a first token source.
        `note` is the `decode.admit` span's args, filled as they are
        learnt.

        Prefix hit: map the cached pages (refcount++), queue the
        uncached prompt tail to be fed through the batched decode step
        — no prefill, no device work here at all. Miss: fresh pages,
        one fused B=1 prefill-into-pages dispatch at the prompt rung,
        the first token picked where the logits are and read with the
        tokens of the step in flight (`_admit_prefill`). True if the
        request now occupies a decode slot.

        A preempted request resumes through this same path over
        ``prompt + generated`` (for a fresh request that IS the prompt):
        replayed tokens are teacher-forced — prefix-mapped or prefilled,
        then tail-fed without sampling — and the per-(seed, position)
        RNG picks up sampling at the first unseen position, so the
        resumed stream is token-identical to an unpreempted run."""
        toks = req.prompt + req.generated
        plen = len(toks)
        self._ensure_pool()
        req.t_admit = time.monotonic()
        note["plen"] = plen
        note["queued_ms"] = 1e3 * (req.t_admit - req.t_submit)
        with _RING.span("decode.admit.lookup"):
            usable, hit_pages = self._prefix_map(req, toks)
        if usable is None:
            return False         # parked in _migrating, no slot held
        note["hit_tokens"] = usable
        if usable:
            req.pages = hit_pages
            req.slot = self._free_slots.pop()
            req.cache_len = usable
            req.last_tok = toks[usable]
            req.input_tail = deque(toks[usable + 1:])
            req.feeding = True
            return True
        return self._admit_prefill(req, toks, note)

    def _prefix_map(self, req: _Req, toks: List[int]):
        """Prefix-cache lookup for an admission: (usable tokens, the
        pages mapped for them), (0, []) on a miss or with the cache
        off, (None, []) when the request was parked on a host-tier
        refetch instead."""
        plen = len(toks)
        pt = self.page_tokens
        usable, hit_pages = 0, []
        owner = self._owner_for(req)
        if self._prefix is not None:
            hit_pages, hit_tokens = self._prefix.lookup(toks, owner=owner)
            self._m["prefix_lookup_tokens"].inc(plen)
            if self._migrate is not None:
                # the device hit may continue in the host tier (spilled
                # cold prefixes, a preempted stream's stashed pages):
                # when refetching would lengthen the usable prefix,
                # park the request on an async refetch instead of
                # re-prefilling content that already exists host-side
                chain = self._prefix.host_chain(toks, len(hit_pages))
                gain = min((len(hit_pages) + len(chain)) * pt, plen - 1)
                if chain and gain > min(hit_tokens, plen - 1) \
                        and self._tier_fetch(req, chain):
                    for p in hit_pages:
                        self._alloc.release(p, owner=owner)
                    return None, []
            # at least one prompt token is always re-fed so the step
            # has logits to sample the first generated token from
            usable = min(hit_tokens, plen - 1)
            n_map = min(len(hit_pages), -(-(usable + 1) // pt)) \
                if usable else 0
            for p in hit_pages[n_map:]:
                self._alloc.release(p, owner=owner)
            hit_pages = hit_pages[:n_map]
            self._m["prefix_hits" if usable else "prefix_misses"].inc()
            if usable:
                self._m["prefix_hit_tokens"].inc(usable)
        return usable, hit_pages

    def _admit_prefill(self, req: _Req, toks: List[int],
                       note: dict) -> bool:
        """The miss path of `_admit`: fresh pages, then ONE dispatch
        that prefills at the prompt's kv rung and writes the K/V panel
        into those pages where it was computed, not awaited; its
        greedy pick goes into the slot's entry of the last tokens
        (`exec:decode.pfirst`) and the host reads it with the tokens of
        the step in flight, once the next step is on the device. A
        request that samples needs its logits on the host: that
        admission waits, pulls the [1, V] row and emits here
        (`decode.admit.logits_pull`, `decode.admit.emit`), as an engine
        that does not run ahead does for every request."""
        plen = len(toks)
        note["rung"] = next_bucket(plen, self.kv_ladder)
        n_pages = -(-plen // self.page_tokens)
        with _RING.span("decode.admit.alloc", {"pages": n_pages}):
            try:
                req.pages = self._alloc_pages(n_pages, req)
            except TypedServeError as err:
                req.stream._push_error(err)
                self._m["evictions"].labels(reason="exhausted").inc()
                return False
        req.slot = self._free_slots.pop()
        if self._by_slot:
            note["state_slot"] = req.slot    # whose state it overwrites
        ahead = self._run_ahead and req.temperature <= 0.0
        t0 = time.perf_counter()
        logits, self._pool_tree = self._prefill_into_pages(
            self._prefill_aot, self.params, self._pool_tree, toks,
            req.pages, wait=not ahead, slot=req.slot)
        self._m["prefills"].inc()
        req.cache_len = plen
        self._expect(req)
        # the pages hold the prompt for whatever is dispatched from here
        if self._prefix is not None:
            self._prefix.insert(
                toks, req.pages[:plen // self.page_tokens])
        if ahead:
            self._last = _launch(self._first_exe(), logits, self._last,
                                 self._slot_ids[req.slot])
            self._firsts.append((req, t0))
            return True
        with _RING.span("decode.admit.logits_pull"):
            row = np.asarray(logits)[0]
        self._note_prefill(req, t0)
        with _RING.span("decode.admit.emit"):
            return not self._deliver(req, row)

    def _note_prefill(self, req: _Req, t0: float):
        req.prefill_s = time.perf_counter() - t0
        self._m["prefill_latency"].observe(req.prefill_s)

    # ------------------------------------------------------------ step
    #
    # One algorithm, two orders. A step is dispatched from counts
    # (pages, cache lengths, who leaves at `max_new`) and from the last
    # tokens where they are: on the device for a greedy row, on the host
    # for a row that feeds a prompt tail or whose token the host
    # sampled. Running ahead, a tick dispatches step k+1, then reads
    # step k's tokens, then prepares step k+2; the loop admits beside
    # step k+1 and waits for it (`_loop_once`). A tick that holds a
    # sampling row (or `_run_ahead` off) reads each step before it
    # dispatches the next, as the host's sampler needs.

    _run_ahead = True     # the speculative engine reads every tick

    def _step_once(self):
        """One tick: every active slot advances one position. The tick
        is a `decode.step` ring span; running ahead it is tiled by the
        dispatch (`decode.step.build`, the uploads, and
        `exec:decode.ptok`, `exec:decode.pstep`, `exec:decode.ppick`,
        each the dispatch call alone, `decode.step.advance`, the rows'
        counts), `decode.step.pull` and
        `decode.sample` for the step BEFORE, and
        `decode.step.provision` and `decode.step.build` for the step
        AFTER; the step it dispatched is still running when it ends. A
        tick that dispatches nothing writes no `decode.step`."""
        sampling = any(r.temperature > 0.0 for r in self._active)
        ahead = self._run_ahead and not sampling
        if not ahead:
            self._drain()
        with _RING.span("decode.step", {}) as tick:
            batch = self._batch or self._build_batch()
            self._batch = None
            if not batch.reqs:           # every slot awaits its last token
                tick.drop()
                self._drain()
                return
            unread = self._flight, self._firsts, self._last
            self._dispatch(batch, sampling)
            t0 = self._flight.t0
            tick.args.update(batch=len(batch.reqs), b_rung=batch.b_rung,
                             w_rung=batch.w_rung,
                             ahead=unread[0] is not None)
            if unread[0] is not None:
                self._ahead_steps += 1
                self._m["ahead_steps"].inc()
            if ahead:
                self._read(*unread)
                self._batch = self._build_batch()
            else:
                self._drain()
                self._m["step_latency"].observe(time.perf_counter() - t0)

    def _build_batch(self) -> _Batch:
        """The next step from counts: a write target for every row
        (`_provision_rows`), then tables and packed rows."""
        with _RING.span("decode.step.provision", {}) as prov:
            prov.args["new_pages"] = self._provision_rows(self._active)
        reqs = [r for r in self._active if not r.closing]
        if not reqs:
            return _Batch(reqs, 0, 0, None, None)
        with _RING.span("decode.step.build"):
            b_rung = next_bucket(len(reqs), self.batch_ladder)
            w_rung = next_bucket(max(len(r.pages) for r in reqs),
                                 self.page_ladder)
            tables = np.zeros((b_rung, w_rung), np.int32)   # pad -> null
            rows = np.zeros((3, b_rung), np.int32)
            rows[0] = self.max_slots        # a padding row has no slot
            batch = _Batch([], b_rung, w_rung, tables, rows)
            for req in reqs:
                self._fill_row(batch, req)
        return batch

    @staticmethod
    def _fill_row(batch: _Batch, req: _Req):
        j = len(batch.reqs)
        batch.tables[j, :len(req.pages)] = req.pages
        batch.rows[:, j] = req.slot, req.last_tok, req.cache_len
        batch.reqs.append(req)

    def _join_batch(self, req: _Req):
        """A request admitted after the next step was prepared takes a
        row of it where the rungs hold it; else the step is built anew."""
        batch = self._batch
        if batch is None or req.closing:
            return
        self._provision_rows([req])
        if req.slot is None:
            return                       # the pool had no page for it
        if len(batch.reqs) < batch.b_rung and len(req.pages) <= batch.w_rung:
            self._fill_row(batch, req)
        else:
            self._batch = None

    def _dispatch(self, batch: _Batch, sampling: bool):
        """Put `batch` on the device: the uploads, the step's inputs
        from the last tokens, the step and, unless the host is to
        sample from the logits, its picks into the last tokens. The
        rows' counts advance here; the tokens are read later (`_read`)."""
        b_rung, w_rung = batch.b_rung, batch.w_rung
        with _RING.span("decode.step.build"):
            exe = self._step_exe(self._pool_tree, b_rung, w_rung)
            inputs, picks = self._token_exes(b_rung)
            tables, rows = jnp.asarray(batch.tables), jnp.asarray(batch.rows)
        t0 = time.perf_counter()
        # (last_tok, cache_len), and the rows' slots where state lives
        # by slot
        logits, self._pool_tree = _launch(
            exe, self.params, self._pool_tree, tables,
            *_launch(inputs, self._last, rows))
        if not sampling:
            self._last = _launch(picks, logits, self._last, rows)
        self._last_b_rung, self._last_w_rung = b_rung, w_rung
        self._steps += 1
        self._m["steps"].inc()
        pt = self.page_tokens
        out = []
        with _RING.span("decode.step.advance"):
            for req in batch.reqs:
                req.cache_len += 1
                if req.input_tail:       # still consuming prompt tail:
                    req.last_tok = req.input_tail.popleft()
                    out.append((req, False))    # mid-prompt logits: none
                    continue
                if req.feeding:
                    # the step consumes the final prompt token — its
                    # pages then hold the whole prompt: cache them; the
                    # row's output is this request's FIRST token
                    req.feeding = False
                    if self._prefix is not None:
                        self._prefix.insert(
                            req.prompt, req.pages[:len(req.prompt) // pt])
                self._expect(req)
                out.append((req, True))
        self._flight = _Flight(out, logits if sampling else None, t0)
        self._firsts = []

    def _expect(self, req: _Req):
        """One more token of `req` is being computed: counted now,
        delivered when read. Its next input is then the slot's entry
        on the device; from the count the host knows whether it is the
        request's last."""
        req.pending += 1
        req.last_tok = -1
        if len(req.generated) + req.pending >= req.max_new \
                or req.cache_len >= self._kind.max_seq_len:
            req.closing = True

    def _drain(self, read: bool = True):
        """Bring home whatever the device still owes the host: the step
        in flight and the first tokens of the admissions since. With
        `read` off (the error path, the stop) it is waited for and
        dropped."""
        unread = self._flight, self._firsts, self._last
        self._flight, self._firsts = None, []
        if unread[0] is None and not unread[1]:
            return
        if read:
            self._read(*unread)
            return
        self._batch = None
        try:
            jax.block_until_ready((self._last, self._pool_tree))
        except Exception:                # a poisoned step: nothing to keep
            pass

    def _read(self, flight: Optional[_Flight], firsts: List, last):
        """Read a step's tokens and the first tokens of the admissions
        dispatched after it, deliver them, and let go of the requests
        that ended. `last` is the slots' last tokens from before any
        later step stored into them: one [slots] pull for all of them,
        or the step's [B, V] logits where the host samples."""
        with _RING.span("decode.step.pull", {}) as pull:
            # greedy rows need their best id and nothing else of the
            # logits: the device picked it and one id a slot crosses;
            # one sampling row and the tick pulls the logits
            logits = ids = None
            if flight is not None and flight.logits is not None:
                logits = np.asarray(flight.logits)
            if firsts or (flight is not None and logits is None):
                ids = np.asarray(last)
            pull.args["bytes"] = sum(a.nbytes for a in (logits, ids)
                                     if a is not None)
        ended = False
        rows = flight.rows if flight is not None else []
        with _RING.span("decode.sample", {"reqs": len(rows)}):
            for j, (req, sampled) in enumerate(rows):
                # a row of a request that ended meanwhile (EOS read
                # while this step ran, a failed page) is dropped
                if sampled and req.slot is not None:
                    ended |= self._deliver(
                        req, ids[req.slot] if logits is None else logits[j])
            for req, t0 in firsts:
                if req.slot is not None:
                    self._note_prefill(req, t0)
                    ended |= self._deliver(req, ids[req.slot])
        if ended:
            self._active = [r for r in self._active if r.slot is not None]
            self._batch = None
            self._update_gauges()

    def _provision_rows(self, reqs: List[_Req]) -> int:
        """Provision each request's write target for row cache_len: a
        fresh page at a page boundary, a copy-on-write if the target
        page is shared. A slot the pool cannot serve fails alone.
        Returns the pages taken."""
        pt = self.page_tokens
        taken = 0
        victims = []
        for req in reqs:
            if req.closing or req.slot is None:
                continue
            slot = req.cache_len // pt
            try:
                if slot >= len(req.pages):
                    req.pages.extend(self._alloc_pages(1, req))
                    taken += 1
                elif self._alloc.refcount(req.pages[slot]) > 1:
                    with _RING.span("decode.cow", {"req": req.id}):
                        self._cow(req, slot)
                    taken += 1
            except TypedServeError as err:
                if req.pending:
                    # the tokens it is owed reach its stream before the
                    # error does (and may end it: then there is none)
                    self._drain()
                    if req.slot is None:
                        continue
                req.stream._push_error(err)
                self._m["evictions"].labels(reason="exhausted").inc()
                self._release_pages(req)
                victims.append(req)
        if victims:
            dead = {r.id for r in victims}
            self._active = [r for r in self._active if r.id not in dead]
            self._update_gauges()
        return taken

    def _deliver(self, req: _Req, value) -> bool:
        """One token of `req` is on the host: push and account it.
        `value` is the device's pick, or the logits row [V] the host
        samples from (its next input is then the host's to give). True
        if the request ended with it: EOS, its count, or a killed
        stream."""
        req.pending -= 1
        first = not req.generated        # resumes already saw first-token
        try:
            chaos.maybe_fail("decode.stream", detail=req.id)
            if np.ndim(value):
                tok = req.last_tok = self._sample(value, req)
            else:
                tok = int(value)
        except Exception as exc:
            req.stream._push_error(TypedServeError(
                ERR_UNAVAILABLE, f"decode stream killed: {exc}"))
            self._m["evictions"].labels(reason="error").inc()
            self._release_pages(req)
            return True
        req.generated.append(tok)
        self._tokens += 1
        self._m["tokens"].inc()
        self._note_token(req)
        if first:
            self._m["ttft"].observe(time.monotonic() - req.t_submit)
        eos = req.eos_id is not None and tok == req.eos_id
        req.stream._push_token(tok, eos)
        _RING.instant("decode.emit", {"req": req.id})
        if eos or (req.closing and not req.pending):
            self._finish(req, "eos" if eos else "length")
            self._release_pages(req)
            return True
        return False

    def _finish(self, req: _Req, reason: str):
        req.stream._push_done()
        self._m["evictions"].labels(reason=reason).inc()
        now = time.monotonic()
        self._spans.record(req.id, {
            "queue": req.t_admit - req.t_submit,
            "prefill": req.prefill_s,
            "decode": now - req.t_admit,
        }, extra={"tokens": len(req.generated),
                  "prompt_len": len(req.prompt)})

    def _dist(self, row: np.ndarray, req: _Req) -> np.ndarray:
        """The request's sampling distribution over the vocab (its
        temperature/top-k transform of one logit row) — shared by
        `_sample` and speculative rejection sampling."""
        logits = row.astype(np.float64) / max(req.temperature, 1e-6)
        if 0 < req.top_k < logits.shape[0]:
            kth = np.partition(logits, -req.top_k)[-req.top_k]
            logits = np.where(logits >= kth, logits, -np.inf)
        logits -= logits.max()
        p = np.exp(logits)
        p /= p.sum()
        return p

    def _req_rng(self, req: _Req, pos: int):
        """Sampling generator for the token at absolute sequence
        position `pos`. Seeded streams draw from a counter-based RNG
        keyed on (seed, position), so a resumed stream — resubmitted as
        `prompt + tokens_emitted_so_far` with the same seed — samples
        the remaining positions draw-for-draw identically to the
        uninterrupted run, regardless of engine history or batch mates.
        Unseeded requests share the engine RNG."""
        if req.seed is None:
            return self._rng
        return np.random.default_rng((req.seed, pos))

    def _sample(self, row: np.ndarray, req: _Req, pos=None) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(row))
        p = self._dist(row, req)
        if pos is None:
            pos = len(req.prompt) + len(req.generated)
        return int(self._req_rng(req, pos).choice(p.shape[0], p=p))

    def _update_gauges(self):
        with _RING.span("decode.gauges"):
            n = len(self._active)
            self._m["active"].set(n)
            self._m["occupancy"].set(n / max(self.max_slots, 1))
            self._m["preempted_waiting"].set(len(self._paused))
            # counts the allocator and the trie keep as pages change
            # hands: nothing here walks the pool (a refresh follows
            # every admission and every finishing tick)
            ps = self._alloc.occupancy()
            self._m["page_pool_size"].set(ps["pages_total"])
            self._m["page_in_use"].set(ps["pages_used"])
            self._m["page_shared"].set(ps["pages_shared"])
            self._m["page_fragmentation"].set(ps["fragmentation"])
            if self._prefix is not None:
                self._m["prefix_cached_pages"].set(
                    self._prefix.cached_pages())
            if self._tm is not None:
                self._tm["resident"].labels(tier="device").set(
                    ps["pages_used"])
                self._tm["resident"].labels(tier="host").set(
                    ps.get("host_pages_used", 0))


# ------------------------------------------------- speculative decoding

def spec_k_ladder(k_max: int) -> List[int]:
    """Powers of two from 1 up to — and including — `k_max`: the
    adaptive speculation-depth rungs. Every rung's verify width (k+1)
    is AOT-warmed, so per-slot k moves along the ladder without a
    steady-state compile."""
    k_max = int(k_max)
    if k_max <= 1:
        return [1]
    vals, v = [], 1
    while v < k_max:
        vals.append(v)
        v *= 2
    vals.append(k_max)
    return sorted(set(vals))


class SpecDecodeEngine(DecodeEngine):
    """Draft-and-verify speculative decoding over the paged KV pool.

    A small draft GPT (same vocab) runs up to k greedy steps per
    scheduler tick over its OWN page pool — same shape discipline, same
    `PageAllocator`, same per-slot block tables, so one page id names
    one target page AND one draft page. The target then scores all
    drafted positions in a single verify forward (which
    also writes their target K/V rows); acceptance is
    sample-then-compare — the committed token at each position is the
    target's own (argmax, or the per-(seed, position) sampler over the
    verify logits) and a draft is accepted iff it guessed it, so
    speculative output is token-for-token the plain engine's for greedy
    AND seeded-sampled decode. A rejection is pure host bookkeeping:
    truncate
    `cache_len`, drop the block-table tail through
    `PageAllocator.release_range` (stale rows inside kept pages are
    masked by `lengths` and overwritten next tick — no contiguous-rung
    copy to unwind, which is what makes speculation cheap on pages).

    Everything else — admission, prefix sharing, eviction, streaming,
    typed backpressure — is inherited. Copy-on-write copies BOTH pools
    so divergent continuations stay isolated in draft space too, and
    `warmup()` extends the AOT surface with draft-prefill, draft-step,
    draft-write/COW and the (batch-rung x page-rung x k-rung) verify
    cross product, keeping the zero-steady-state-compile invariant
    across churn including rejections and rollbacks.

    Per-slot adaptive k: each slot starts at `speculate_k` and walks a
    power-of-two ladder by an EMA of its acceptance rate — repetitive
    continuations earn deep speculation, adversarial streams degrade
    toward plain decode instead of burning draft steps.
    """

    _req_cls = _SpecReq
    _speculative = True
    _run_ahead = False    # its tick reads drafts and verdicts as it goes

    def __init__(self, model=None, *, draft_model=None,
                 draft_cfg: Optional[GPTConfig] = None,
                 draft_params: Optional[Dict] = None,
                 draft_eps: Optional[float] = None,
                 speculate_k: Optional[int] = None, **kw):
        tcfg = model.cfg if model is not None else kw.get("cfg")
        for c in (tcfg, draft_cfg if draft_model is None
                  else draft_model.cfg):
            if c is not None and not isinstance(c, GPTConfig):
                # the typed refusal of a kind that has no speculation yet
                model_kinds.for_config(c).pool_dtype(
                    kw.get("kv_dtype"), speculative=True)
        if draft_model is not None:
            from .. import framework
            draft_cfg = draft_model.cfg
            draft_params = framework.param_arrays(draft_model)
            draft_eps = draft_model.ln_f._epsilon \
                if draft_eps is None else draft_eps
        if draft_cfg is None or draft_params is None:
            raise ValueError(
                "SpecDecodeEngine needs a draft model or "
                "(draft_cfg, draft_params)")
        k = int(speculate_k) if speculate_k is not None \
            else int(_flags.env_value("PADDLE_TPU_DECODE_SPECULATE"))
        if k < 1:
            raise ValueError(f"speculate_k must be >= 1, got {k}")
        # validate against the target BEFORE the scheduler thread starts
        if tcfg is not None:
            if draft_cfg.vocab_size != tcfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target "
                    f"vocab {tcfg.vocab_size}")
            if draft_cfg.max_seq_len < tcfg.max_seq_len:
                raise ValueError(
                    f"draft max_seq_len {draft_cfg.max_seq_len} < target "
                    f"max_seq_len {tcfg.max_seq_len}")
        super().__init__(model, **kw)
        self.draft_cfg = draft_cfg
        self.draft_eps = 1e-5 if draft_eps is None else float(draft_eps)
        self._draft_params = {n: jnp.asarray(v)
                              for n, v in draft_params.items()}
        self.k_ladder = spec_k_ladder(k)
        draft = self._draft_kind = model_kinds.for_config(
            draft_cfg, self.draft_eps)
        # Draft/target pools donated for the same in-place-update
        # reason as the base engine's executables.
        self._dprefill_aot = AotCache(
            jax.jit(draft.prefill_fn(self.page_tokens, name="paged_prefill"),
                    donate_argnums=(1,)), "decode.dprefill",
            donate_argnums=(1,))
        self._droll_aot = AotCache(
            jax.jit(draft.rollout_fn(self.page_tokens),
                    donate_argnums=(1,)), "decode.droll",
            donate_argnums=(1,))
        self._dcopy_aot = AotCache(
            jax.jit(draft.copy_page, donate_argnums=(0,)), "decode.dcow",
            donate_argnums=(0,))
        self._verify_aot = AotCache(
            jax.jit(self._kind.verify_fn(self.page_tokens),
                    donate_argnums=(1,)), "decode.verify",
            donate_argnums=(1,))
        self._dpool_tree = None      # draft pools, lazy like the target's
        self._drafted_total = 0
        self._accepted_total = 0

    # ----------------------------------------------------- pool plumbing

    def _owner_for(self, req) -> tuple:
        """Speculative streams own their pages as ``("draft", id)`` —
        one page id names a target AND a draft page, so the draft kind
        keeps the spec footprint distinct in /memz rollups. Handoff
        jobs keep the base tag."""
        if isinstance(req, _HandoffJob):
            return super()._owner_for(req)
        return ("draft", req.id)

    def _dpools_sds(self):
        return self._draft_kind.pools_sds(self.num_pages, self.page_tokens,
                                          self.kv_dtype)

    # Host tiering migrates the draft pools with the target pools: one
    # page id names a page in both, so a spilled page's full
    # footprint moves as one chunk and a restore brings the draft rows
    # back warm. (Even when restored draft rows are stale, acceptance
    # is sample-then-compare — draft content can only cost acceptance
    # rate, never change emitted tokens.)

    def _pools(self):
        return (self._pool_tree, self._dpool_tree)

    def _set_pools(self, pools):
        self._pool_tree, self._dpool_tree = pools

    def _pools_sds(self):
        return (self._model_pools_sds(), self._dpools_sds())

    def _ensure_pool(self):
        super()._ensure_pool()
        if self._dpool_tree is None:
            self._dpool_tree = self._draft_kind.pools_zeros(
                self.num_pages, self.page_tokens, self.kv_dtype)

    def _cow(self, req: _Req, slot: int):
        """Copy-on-write for speculation copies the page in BOTH pools —
        one page id names a target page and a draft page."""
        old = req.pages[slot]
        super()._cow(req, slot)     # the target's copy; repoints the slot
        i32 = jnp.int32
        dexe = self._dcopy_aot.get_or_compile(
            self._dpool_tree,
            jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32),
            key=("dcow",))
        # the other owners still hold `old`, so its draft rows stand
        self._dpool_tree = dexe(
            self._dpool_tree, jnp.asarray(old, i32),
            jnp.asarray(req.pages[slot], i32))

    # ---------------------------------------------------------- warmup

    def warmup(self, verbose: bool = False) -> int:
        """Base warmup plus the draft/verify surface: fused draft
        prefill-into-pages per prompt rung, draft COW, the fused draft
        rollout (batch-rung x page-rung x k-rung) grid and the verify
        (batch-rung x page-rung x k-rung) cross product — each grid
        capped like the base step's."""
        before = len(profiler.compile_events())
        super().warmup(verbose=False)
        i32 = jnp.int32
        pools, dpools = self._model_pools_sds(), self._dpools_sds()
        for r in self.kv_ladder:
            self._prefill_exe(self._dprefill_aot, self._draft_params,
                              dpools, r)
        self._dcopy_aot.get_or_compile(
            dpools,
            jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32),
            key=("dcow",))
        # When the full (batch x page x k) cross product overflows the
        # warmup cap, shrink the k ladder itself — dropping middle rungs,
        # keeping k=1 and k_max — instead of silently truncating tail
        # signatures. Adaptive k then only walks warmed rungs, so the
        # no-steady-state-compiles invariant survives large k_max.
        grid = len(self.batch_ladder) * len(self.page_ladder)
        while len(self.k_ladder) > 1 \
                and grid * len(self.k_ladder) > _WARMUP_SIG_CAP:
            self.k_ladder.pop(len(self.k_ladder) // 2)
        sigs = [(b, w, kk) for b in self.batch_ladder
                for w in self.page_ladder for kk in self.k_ladder]
        if len(sigs) > _WARMUP_SIG_CAP:
            sigs = sigs[:_WARMUP_SIG_CAP]
        for b, w, kk in sigs:
            self._droll_aot.get_or_compile(
                self._draft_params, dpools,
                jax.ShapeDtypeStruct((b, w), i32),
                jax.ShapeDtypeStruct((b, kk), i32),
                jax.ShapeDtypeStruct((b,), i32),
                key=("droll", b, w, kk))
        vsigs = [(b, w, kk + 1) for b in self.batch_ladder
                 for w in self.page_ladder for kk in self.k_ladder]
        if len(vsigs) > _WARMUP_SIG_CAP:
            vsigs = vsigs[:_WARMUP_SIG_CAP]
        for b, w, k1 in vsigs:
            self._verify_aot.get_or_compile(
                self.params, pools,
                jax.ShapeDtypeStruct((b, w), i32),
                jax.ShapeDtypeStruct((b, k1), i32),
                jax.ShapeDtypeStruct((b,), i32),
                key=("verify", b, w, k1))
        n = len(profiler.compile_events()) - before
        if verbose:
            print(f"SPEC DECODE WARMUP compiles={n} "
                  f"k_ladder={self.k_ladder} "
                  f"rollout_sigs={len(sigs)} verify_sigs={len(vsigs)}",
                  flush=True)
        return n

    # ------------------------------------------------------- admission

    def _admit(self, req: _Req, note: dict) -> bool:
        req.spec_k = self.k_ladder[-1]      # start optimistic, adapt down
        if not super()._admit(req, note):
            return False
        if not req.feeding:
            # prefill miss: the target panel is in the pages; mirror the
            # prompt into the draft pool so drafting starts warm
            self._draft_prefill(req)
        # prefix hit: the mapped pages already carry the draft rows the
        # original (speculative) prefill wrote — nothing to do
        req.draft_len = req.cache_len
        return True

    def _draft_prefill(self, req: _Req):
        """One fused B=1 draft prefill-into-pages dispatch over the
        committed sequence (the prompt — or prompt + replayed tokens on
        a preempt resume), scattered into the SAME page ids the target
        panel landed in. These writes deliberately skip the COW check:
        the rows hold committed K/V — the one thing every mapper of a
        shared prefix page agrees on."""
        seq = (req.prompt + req.generated)[:req.cache_len]
        _, self._dpool_tree = self._prefill_into_pages(
            self._dprefill_aot, self._draft_params, self._dpool_tree, seq,
            req.pages)

    def _preempt_stash(self, req: _Req):
        """Stash only PROMPT-region pages at preemption. Generated-region
        pages may carry draft rows past the commit point (speculation in
        flight); a resume that prefix-mapped them would skip the draft
        re-prefill and let stale draft rows steer the greedy draft chain
        — diverging the rejection-sampling draw sequence from an
        unpreempted run. The prompt resume path re-drafts the generated
        region instead. Rows the draft catch-up has not reached yet
        (`draft_len` lagging `cache_len`) are excluded the same way."""
        if self._prefix is not None:
            full = min(req.cache_len, req.draft_len,
                       len(req.prompt)) // self.page_tokens
            if full:
                self._prefix.insert(req.prompt, req.pages[:full])
        req.draft_len = 0

    # ------------------------------------------------------------ tick

    def _step_once(self):
        t_tick = time.perf_counter()
        pt = self.page_tokens
        cap = self.cfg.max_seq_len
        tick_k = max(r.spec_k for r in self._active)
        K1 = tick_k + 1
        # 1. provision every page this tick can write: draft rows
        # [draft_len, draft_len+k) and verify rows [cache_len,
        # cache_len+k]; COW any shared page in that window (both pools)
        victims = []
        for req in self._active:
            lo = min(req.cache_len, req.draft_len) // pt
            hi_row = min(max(req.cache_len + tick_k,
                             req.draft_len + tick_k - 1), cap - 1)
            need = hi_row // pt + 1
            try:
                if need > len(req.pages):
                    req.pages.extend(
                        self._alloc_pages(need - len(req.pages), req))
                for s in range(lo, need):
                    if self._alloc.refcount(req.pages[s]) > 1:
                        t_cow = time.perf_counter()
                        self._cow(req, s)
                        _RING.complete("decode.cow", t_cow,
                                       time.perf_counter(),
                                       {"req": req.id})
            except TypedServeError as err:
                req.stream._push_error(err)
                self._m["evictions"].labels(reason="exhausted").inc()
                self._release_pages(req)
                victims.append(req)
        if victims:
            dead = {r.id for r in victims}
            self._active = [r for r in self._active if r.id not in dead]
            self._update_gauges()
        reqs = self._active
        if not reqs:
            return
        b_rung = next_bucket(len(reqs), self.batch_ladder)
        w_rung = next_bucket(max(len(r.pages) for r in reqs),
                             self.page_ladder)
        tables = np.zeros((b_rung, w_rung), np.int32)   # pad -> null page
        for j, req in enumerate(reqs):
            tables[j, :len(req.pages)] = req.pages
        tables_j = jnp.asarray(tables)
        # 2. draft phase: tick_k greedy draft steps fused into ONE
        # rollout dispatch. Step i consumes one token per slot — a
        # committed token the draft has not seen yet (catch-up, passed
        # via `forced`; its output is discarded) or the slot's own
        # previous draft (forced = -1: the rollout chains its argmax).
        t_draft = time.perf_counter()
        seqs = [req.prompt + req.generated for req in reqs]
        forced = np.zeros((b_rung, tick_k), np.int32)
        forced[len(reqs):] = 0              # padded rows: null-page writes
        dlen = np.zeros(b_rung, np.int32)
        for j, req in enumerate(reqs):
            dl, seq = req.draft_len, seqs[j]
            dlen[j] = dl
            for i in range(tick_k):
                forced[j, i] = seq[dl + i] if dl + i < len(seq) else -1
        dexe = self._droll_aot.get_or_compile(
            self._draft_params, self._dpool_tree,
            jax.ShapeDtypeStruct((b_rung, w_rung), jnp.int32),
            jax.ShapeDtypeStruct((b_rung, tick_k), jnp.int32),
            jax.ShapeDtypeStruct((b_rung,), jnp.int32),
            key=("droll", b_rung, w_rung, tick_k))
        dout, self._dpool_tree = dexe(
            self._draft_params, self._dpool_tree,
            tables_j, jnp.asarray(forced), jnp.asarray(dlen))
        dnp = np.asarray(dout)
        self._m["spec_draft_steps"].inc(tick_k)
        chains: List[List[int]] = [[] for _ in reqs]
        for j, req in enumerate(reqs):
            for i in range(tick_k):
                if req.draft_len >= len(seqs[j]) - 1:
                    chains[j].append(int(dnp[j, i]))
                req.draft_len += 1
        t_verify = time.perf_counter()
        _RING.complete("decode.draft", t_draft, t_verify, {"k": tick_k})
        # 3. verify: one multi-token target forward scores (and writes
        # the K/V of) up to K1 positions per slot — the un-consumed
        # committed tokens first, then this tick's drafts
        vtoks = np.zeros((b_rung, K1), np.int32)
        clen = np.zeros(b_rung, np.int32)
        meta = []
        for j, req in enumerate(reqs):
            known = seqs[j][req.cache_len:]
            n_known = min(len(known), K1, cap - req.cache_len)
            nd = min(len(chains[j]), req.spec_k, K1 - n_known)
            row = known[:n_known] + chains[j][:nd]
            vtoks[j, :len(row)] = row
            vtoks[j, len(row):] = row[-1]   # padding rows roll back
            clen[j] = req.cache_len
            meta.append((n_known, nd))
        vexe = self._verify_aot.get_or_compile(
            self.params, self._pool_tree,
            jax.ShapeDtypeStruct((b_rung, w_rung), jnp.int32),
            jax.ShapeDtypeStruct((b_rung, K1), jnp.int32),
            jax.ShapeDtypeStruct((b_rung,), jnp.int32),
            key=("verify", b_rung, w_rung, K1))
        t0 = time.perf_counter()
        logits, amax, self._pool_tree = vexe(
            self.params, self._pool_tree,
            tables_j, jnp.asarray(vtoks), jnp.asarray(clen))
        amaxnp = np.asarray(amax)
        lognp = None   # full logits only cross to host when sampling
        self._m["step_latency"].observe(time.perf_counter() - t0)
        self._last_b_rung, self._last_w_rung = b_rung, w_rung
        self._steps += 1
        self._m["steps"].inc()
        t_accept = time.perf_counter()
        _RING.complete("decode.verify", t_verify, t_accept, {"k1": K1})
        # 4. acceptance + rollback, per slot on the host
        finished = []
        for j, req in enumerate(reqs):
            n_known, nd = meta[j]
            drafts = chains[j][:nd]
            seq_len_old = len(seqs[j])
            if req.feeding and req.cache_len + n_known >= len(req.prompt):
                # the verify just consumed the last prompt-tail token:
                # the pages now hold the whole prompt
                req.feeding = False
                req.input_tail.clear()
                if self._prefix is not None:
                    self._prefix.insert(
                        req.prompt, req.pages[:len(req.prompt) // pt])
            emitted, a, i = [], 0, n_known - 1
            while True:
                if req.temperature > 0.0 and lognp is None:
                    lognp = np.asarray(logits)
                # Sample-then-compare verification: the committed token
                # at every position comes straight from the target —
                # greedy argmax, or the plain engine's per-(seed, pos)
                # sampler over the verify logits — and a draft is
                # accepted iff it guessed that token. A draft d is
                # accepted with probability p[d], exactly classic
                # rejection sampling's, but the OUTPUT never depends on
                # the draft chain: a speculative stream is draw-for-draw
                # the plain engine's across any k, batch composition, or
                # preempt/resume history.
                if req.temperature <= 0.0:
                    tok = int(amaxnp[j, i])
                else:
                    pos = len(req.prompt) + len(req.generated) \
                        + len(emitted)
                    tok = self._sample(lognp[j, i], req, pos=pos)
                accept = a < nd and tok == drafts[a]
                emitted.append(tok)
                if accept:
                    a += 1
                    i += 1
                hit_eos = req.eos_id is not None and tok == req.eos_id
                if (not accept) or hit_eos \
                        or len(req.generated) + len(emitted) >= req.max_new \
                        or req.cache_len + n_known + a >= cap:
                    break
            new_c = req.cache_len + n_known + a
            # rollback: keep pages covering the committed rows and the
            # still-valid draft rows, release the stranded tail
            dl_valid = min(req.draft_len, seq_len_old + a)
            req.draft_len = dl_valid
            keep = -(-max(new_c, dl_valid) // pt)
            if keep < len(req.pages):
                released = self._alloc.release_range(
                    req.pages, keep, owner=self._owner_for(req))
                del req.pages[keep:]
                if released:
                    self._m["page_rollback_released"].inc(released)
            req.cache_len = new_c
            req.last_tok = emitted[-1]
            # acceptance accounting + adaptive k
            req.drafted += nd
            req.accepted += a
            req.stream.spec_drafted = req.drafted
            req.stream.spec_accepted = req.accepted
            self._drafted_total += nd
            self._accepted_total += a
            if nd:
                self._m["spec_accepted"].inc(a)
                self._m["spec_rejected"].inc(nd - a)
                req.accept_ema = 0.5 * req.accept_ema + 0.5 * (a / nd)
                ki = self.k_ladder.index(req.spec_k)
                if req.accept_ema < 0.35 and ki > 0:
                    req.spec_k = self.k_ladder[ki - 1]
                elif req.accept_ema > 0.8 and ki < len(self.k_ladder) - 1:
                    req.spec_k = self.k_ladder[ki + 1]
            if self._drafted_total:
                self._m["spec_acceptance"].set(
                    self._accepted_total / self._drafted_total)
            # stream the newly committed tokens
            first = not req.generated
            try:
                chaos.maybe_fail("decode.stream", detail=req.id)
            except Exception as exc:
                req.stream._push_error(TypedServeError(
                    ERR_UNAVAILABLE, f"decode stream killed: {exc}"))
                self._m["evictions"].labels(reason="error").inc()
                self._release_pages(req)
                finished.append(req)
                continue
            req.generated.extend(emitted)
            self._tokens += len(emitted)
            self._m["tokens"].inc(len(emitted))
            self._note_token(req, len(emitted))
            req.stream._push_tokens(
                emitted,
                req.eos_id is not None and emitted[-1] == req.eos_id)
            _RING.instant("decode.emit", {"req": req.id, "n": len(emitted)})
            if first:
                self._m["ttft"].observe(time.monotonic() - req.t_submit)
            done_eos = req.eos_id is not None \
                and emitted[-1] == req.eos_id
            if done_eos or len(req.generated) >= req.max_new \
                    or req.cache_len >= cap:
                self._finish(req, "eos" if done_eos else "length")
                self._release_pages(req)
                finished.append(req)
        now = time.perf_counter()
        _RING.complete("decode.accept", t_accept, now, {"reqs": len(reqs)})
        _RING.complete("decode.step", t_tick, now,
                       {"batch": len(reqs), "k": tick_k})
        if finished:
            done = {r.id for r in finished}
            self._active = [r for r in reqs if r.id not in done]
            self._update_gauges()

    def stats(self) -> Dict:
        st = super().stats()
        drafted, accepted = self._drafted_total, self._accepted_total
        st["speculate"] = {
            "k_max": self.k_ladder[-1],
            "k_ladder": list(self.k_ladder),
            "drafted": drafted,
            "accepted": accepted,
            "acceptance_rate": round(accepted / drafted, 4)
            if drafted else 0.0,
        }
        return st


# ------------------------------------------------------------ artifact

def save_for_decode(model, prefix: str, quant: Optional[str] = None):
    """Persist a model for the decode daemon: config JSON + params npz
    (the jit.save one-shot artifact has no incremental entry points).

    The manifest names the model kind (`model_kinds`) under
    `"model_kind"` for every kind but a GPT: a GPT's artifact is
    byte-identical to those written before the key existed, and an
    artifact without the key loads as a GPT. bfloat16 parameters, which
    npz cannot hold, are stored as their uint16 bit patterns and listed
    under `"bfloat16"`.

    `quant="int8"` applies `quant.ptq.quantize_params` before writing —
    int8 weights under their original keys plus fp32 `::scale` siblings
    — and records `"quant": "int8"` in the manifest. The default fp32
    artifact is byte-identical to pre-quantization versions (no extra
    manifest key, same npz keys), so old artifacts load unchanged."""
    from .. import framework
    kind = model_kinds.for_model(model)
    meta = dict(kind.manifest(), format="paddle_tpu.decode.v1")
    if kind.name != GPTKind.name:
        meta["model_kind"] = kind.name
    params = {k: np.asarray(v)
              for k, v in framework.param_arrays(model).items()}
    if quant is not None:
        if quant != "int8" or kind.name != GPTKind.name:
            raise ValueError(f"quant={quant!r} for model kind "
                             f"{kind.name!r}: expected None, or 'int8' "
                             f"for a GPT")
        params = quantize_params(params)
        meta["quant"] = "int8"
    bf16 = sorted(k for k, v in params.items() if v.dtype == jnp.bfloat16)
    if bf16:
        meta["bfloat16"] = bf16
        params = {k: v.view(np.uint16) if k in bf16 else v
                  for k, v in params.items()}
    with open(prefix + ".decode.json", "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
    np.savez(prefix + ".decode.npz", **params)


def _load_decode_artifact(prefix: str):
    """(model kind, params) of a `save_for_decode` artifact."""
    with open(prefix + ".decode.json") as f:
        meta = json.load(f)
    if meta.get("format") != "paddle_tpu.decode.v1":
        raise ValueError(f"{prefix}.decode.json: not a decode artifact")
    kind = model_kinds.from_manifest(meta)
    bf16 = set(meta.get("bfloat16", ()))
    with np.load(prefix + ".decode.npz") as z:
        params = {k: z[k].view(jnp.bfloat16) if k in bf16 else z[k]
                  for k in z.files}
    return kind, params


def load_for_decode(prefix: str, draft_prefix: Optional[str] = None,
                    speculate_k: Optional[int] = None,
                    draft_quant: Optional[bool] = None,
                    **engine_kw) -> DecodeEngine:
    """Load a `save_for_decode` artifact into a ready DecodeEngine.

    With a draft artifact (`draft_prefix`, or
    PADDLE_TPU_DECODE_DRAFT_MODEL) and a speculation depth
    (`speculate_k`, or PADDLE_TPU_DECODE_SPECULATE >= 1) the result is
    a `SpecDecodeEngine`; otherwise the plain engine — speculation is
    strictly opt-in.

    `draft_quant` (or PADDLE_TPU_DECODE_DRAFT_QUANT) int8-quantizes the
    DRAFT weights at load when the draft artifact is still fp32 — draft
    numerics only move the acceptance rate, never the target stream, so
    this is the cheapest quantization on-ramp. Already-quantized
    artifacts (manifest `"quant": "int8"`) pass through untouched."""
    kind, params = _load_decode_artifact(prefix)
    if draft_prefix is None:
        draft_prefix = _flags.env_value(
            "PADDLE_TPU_DECODE_DRAFT_MODEL") or None
    if speculate_k is None:
        speculate_k = int(_flags.env_value("PADDLE_TPU_DECODE_SPECULATE"))
    if draft_quant is None:
        draft_quant = bool(
            _flags.env_value("PADDLE_TPU_DECODE_DRAFT_QUANT"))
    if draft_prefix and int(speculate_k) >= 1:
        draft, dparams = _load_decode_artifact(draft_prefix)
        if draft_quant and not _params_quantized(dparams):
            dparams = quantize_params(dparams)
        return SpecDecodeEngine(cfg=kind.cfg, params=params, eps=kind.eps,
                                draft_cfg=draft.cfg, draft_params=dparams,
                                draft_eps=draft.eps,
                                speculate_k=int(speculate_k), **engine_kw)
    return DecodeEngine(cfg=kind.cfg, params=params, eps=kind.eps,
                        **engine_kw)
