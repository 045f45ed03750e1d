"""Paged KV-cache decode: allocator, paged-vs-contiguous equivalence,
prefix sharing with copy-on-write isolation, and exhaustion backpressure.

The contiguous reference for every equivalence claim is the FULL
forward pass (`_full_logits` greedy loop) — the same oracle
tests/test_decode.py holds the engine to — so "paged == contiguous"
is enforced token-for-token through real admission/eviction churn,
EOS mid-page, page-boundary crossings, and shared-prefix admissions.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.decode import (DecodeEngine, kv_capacity_ladder,
                                         kv_page_bytes)
from paddle_tpu.inference.errors import (ERR_RESOURCE_EXHAUSTED,
                                         ERR_UNAVAILABLE, TypedServeError)
from paddle_tpu.memory.page_allocator import (PageAllocator, PageExhausted,
                                              copy_page, write_pages)
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_tiny
from paddle_tpu.testing import chaos

_CFGS = [
    ("tiny-scan", gpt_tiny()),                       # scan-stacked params
    ("small-unrolled", GPTConfig(vocab_size=256, max_seq_len=64, hidden=32,
                                 layers=3, heads=2, scan_layers=False)),
]


@pytest.fixture(scope="module")
def gpt_models():
    paddle.seed(7)
    return {name: GPT(cfg) for name, cfg in _CFGS}


def _full_logits(model, toks):
    idx = paddle.to_tensor(np.asarray([toks], np.int64))
    return model(idx).numpy()[0, -1].astype(np.float32)


def _ref_greedy(model, prompt, n, eos_id=None):
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        t = int(_full_logits(model, toks).argmax())
        out.append(t)
        toks.append(t)
        if eos_id is not None and t == eos_id:
            break
    return out


# ----------------------------------------------------------- allocator

def test_page_allocator_basics():
    a = PageAllocator(9)                 # 8 allocatable + null page 0
    assert a.null_page == 0
    p = a.alloc(3)
    assert p == [1, 2, 3] and all(a.refcount(x) == 1 for x in p)
    assert 0 not in a.alloc(5)           # null page never handed out
    with pytest.raises(PageExhausted):
        a.alloc(1)
    a.release(p[0])
    assert a.alloc(1) == [p[0]]          # freed page recycles
    with pytest.raises(ValueError):
        a.retain(0)                      # null page is not allocated
    with pytest.raises(ValueError):
        a.release(0)


def test_page_allocator_refcounts_and_stats():
    a = PageAllocator(9)
    p = a.alloc(4)
    assert a.retain(p[0]) == 2
    st = a.stats()
    assert st["pages_total"] == 8 and st["pages_used"] == 4
    assert st["pages_shared"] == 1 and st["refs_total"] == 5
    assert a.release(p[0]) == 1          # still held by the other owner
    assert a.refcount(p[0]) == 1
    # fragmentation: free pages {5..8} contiguous -> 0.0; poke a hole
    assert a.stats()["fragmentation"] == 0.0
    a.release(p[1])                      # free set {2, 5, 6, 7, 8}
    st = a.stats()
    assert 0.0 < st["fragmentation"] <= 1.0
    assert st["allocs_total"] == 4 and st["alloc_failures_total"] == 0


def _kind_pools(which, pages, pt):
    """Zeroed page-holding pools of a model kind at a tiny size."""
    from paddle_tpu.inference import model_kinds
    from paddle_tpu.models.axk1 import axk1_tiny

    if which == "axk1":
        kind = model_kinds.for_config(axk1_tiny())
        return kind.pools_zeros(pages, pt, None)["latent"]
    kind = model_kinds.for_config(
        GPTConfig(vocab_size=64, max_seq_len=16, hidden=24, layers=2,
                  heads=3))
    return kind.pools_zeros(pages, pt, which)


@pytest.mark.parametrize("which", ["float32", "int8", "axk1"])
def test_page_ops_round_trip_on_a_kinds_pools(which):
    """`write_pages`, `gather_pages`, `copy_page`, the wire codec and
    the host arena move whole pages of either kind's pools: one
    convention, page axis 0 on every leaf, a leaf a layer."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.memory.migration import (HostPageStore,
                                             deserialize_pages,
                                             serialize_pages)
    from paddle_tpu.memory.page_allocator import gather_pages

    P, pt, W = 6, 4, 3
    pools = _kind_pools(which, P, pt)
    leaves, treedef = jax.tree.flatten(pools)
    assert all(x.shape[:2] == (P, pt) for x in leaves)
    assert len(leaves) == {"float32": 4, "int8": 8, "axk1": 3}[which]
    rs = np.random.RandomState(3)
    rows = jax.tree.unflatten(treedef, [
        jnp.asarray(rs.randint(-9, 9, size=(W,) + x.shape[1:]), x.dtype)
        for x in leaves])
    ids = jnp.asarray([4, 1, 2], jnp.int32)
    pools = write_pages(pools, rows, ids)
    for got, want in zip(jax.tree.leaves(pools), jax.tree.leaves(rows)):
        np.testing.assert_array_equal(np.asarray(got[ids]), np.asarray(want))
        assert not np.asarray(got[jnp.asarray([0, 3, 5])]).any()
    chunk = gather_pages(pools, ids)
    for got, want in zip(jax.tree.leaves(chunk), jax.tree.leaves(rows)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    pools = copy_page(pools, jnp.int32(4), jnp.int32(5))
    for x in jax.tree.leaves(pools):
        np.testing.assert_array_equal(np.asarray(x[5]), np.asarray(x[4]))
    # the wire: two real pages of a rung-padded chunk of three
    arrays, meta = serialize_pages(chunk, 2)
    back = deserialize_pages(arrays, meta)
    for got, want in zip(back, jax.tree.leaves(rows)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, np.asarray(want)[:2])
    # the host arena: put pages 2 and 0 of the chunk, assemble at rung 4
    store = HostPageStore(pools, capacity=2)
    host = [np.asarray(x) for x in jax.tree.leaves(chunk)]
    store.put(0, host, 2)
    store.put(1, host, 0)
    out = store.assemble([1, 0], rung=4)
    assert jax.tree.structure(out) == treedef
    for got, want in zip(jax.tree.leaves(out), host):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[2])
        assert got.shape[0] == 4 and not got[2:].any()


def test_kv_capacity_ladder_floor_follows_page_size():
    assert kv_capacity_ladder(128)[0] == 16          # default floor
    assert kv_capacity_ladder(128, floor=4) == [4, 8, 16, 32, 64, 128]
    assert kv_capacity_ladder(128, floor=32) == [32, 64, 128]
    assert kv_capacity_ladder(8, floor=16) == [8]


# ------------------------------------------ fused prefill-into-pages

_PT = 4
_FUSED_CFG = GPTConfig(vocab_size=256, max_seq_len=30, hidden=32, layers=3,
                       heads=2, scan_layers=False)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize(
    "plen", [1, _PT - 1, _PT, _PT + 1, 16, 29],
    ids=["one", "pt-1", "pt", "pt+1", "rung-last", "rung-not-page-multiple"])
def test_fused_prefill_writes_what_the_three_hops_wrote(plen, kv_dtype):
    """`gpt_paged_fns`' prefill against the path it replaced: the logits
    are `gpt_dense_prefill`'s, the request's pages hold
    `write_pages` of the zero-padded panel (quantized per (row, head)
    for an int8 pool) bit for bit, and every other page but the null
    page is untouched."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu import framework
    from paddle_tpu.inference.batching import next_bucket
    from paddle_tpu.models.gpt import gpt_dense_prefill, gpt_paged_fns
    from paddle_tpu.quant.kv import quantize_kv

    cfg, pt = _FUSED_CFG, _PT
    paddle.seed(11)
    params = {k: jnp.asarray(v)
              for k, v in framework.param_arrays(GPT(cfg)).items()}
    rung = next_bucket(plen, kv_capacity_ladder(cfg.max_seq_len, floor=pt))
    assert rung == {16: 16, 29: 30}.get(plen, rung)
    w = -(-rung // pt)
    n_pages = -(-plen // pt)
    rs = np.random.RandomState(plen)
    toks = np.zeros((1, rung), np.int32)
    toks[0, :plen] = rs.randint(0, cfg.vocab_size, size=plen)
    # garbage in the rung's padding must not reach the pool
    toks[0, plen:] = rs.randint(0, cfg.vocab_size, size=rung - plen)
    P = w + 4
    pages = list(rs.permutation(np.arange(1, P))[:n_pages])
    table = np.zeros((1, w), np.int32)
    table[0, :n_pages] = pages
    row = cfg.heads * cfg.head_dim

    def dirty_pool(seed):              # one array a layer, rows whole
        r = np.random.RandomState(seed)

        def layer():
            if kv_dtype == "int8":
                return (jnp.asarray(r.randint(-127, 128, size=(P, pt, row)),
                                    jnp.int8),
                        jnp.asarray(r.rand(P, pt, cfg.heads), jnp.float32))
            return jnp.asarray(r.randn(P, pt, row), jnp.float32)

        return tuple(layer() for _ in range(cfg.layers))

    k0, v0 = dirty_pool(1), dirty_pool(2)
    n = jnp.asarray([plen], jnp.int32)
    fused = jax.jit(gpt_paged_fns(cfg, page_tokens=pt)[0])
    logits, (k1, v1) = fused(params, (k0, v0), jnp.asarray(toks),
                             jnp.asarray(table), n)

    want_logits, k, v = jax.jit(functools.partial(gpt_dense_prefill, cfg))(
        params, jnp.asarray(toks), n)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    others = [p for p in range(1, P) if p not in pages]
    for got, before, panel in ((k1, k0, k), (v1, v0, v)):
        assert jax.tree.structure(got) == jax.tree.structure(before)
        rows = np.zeros((cfg.layers, w * pt, cfg.heads, cfg.head_dim),
                        np.float32)
        rows[:, :plen] = np.asarray(panel)[:, 0, :plen]
        for li in range(cfg.layers):
            page_rows = jnp.asarray(rows[li])
            if kv_dtype == "int8":
                q, sc = jax.jit(quantize_kv)(page_rows)
                page_rows = (q.reshape(w, pt, row),
                             sc.reshape(w, pt, cfg.heads))
            else:
                page_rows = page_rows.reshape(w, pt, row)
            want = jax.jit(write_pages)(before[li], page_rows,
                                        jnp.asarray(table[0]))
            for g, wnt, b in zip(jax.tree.leaves(got[li]),
                                 jax.tree.leaves(want),
                                 jax.tree.leaves(before[li])):
                g, wnt, b = np.asarray(g), np.asarray(wnt), np.asarray(b)
                assert g.dtype == b.dtype and g.shape == b.shape
                np.testing.assert_array_equal(g[pages], wnt[pages])
                np.testing.assert_array_equal(g[others], b[others])
            # the tail of a partial page is zero, not what the page held
            data = np.asarray(jax.tree.leaves(got[li])[0])
            tail = data[pages[-1], plen - (n_pages - 1) * pt:]
            assert not tail.any()


def test_admission_is_one_dispatch_and_no_host_trip(gpt_models):
    """A miss admission's ring: `exec:decode.prefill` once, the first
    token's pick beside it on the device (`exec:decode.pfirst`: a
    greedy admission pulls nothing), and nothing of the K/V panel's old
    trip through numpy or its second dispatch."""
    from paddle_tpu.observability.tracez import RING

    eng = DecodeEngine(gpt_models["tiny-scan"], max_slots=2,
                       max_new_tokens=4, page_tokens=4)
    try:
        tid = eng._thread.ident
        assert len(eng.submit(np.arange(1, 8),
                              max_new_tokens=3).result(timeout=120)) == 3
    finally:
        eng.stop()
    events = [(name, ts, ts + dur) for ph, name, ts, dur, etid, _ in
              RING.snapshot()[0] if ph == "X" and etid == tid]
    (adm,) = [e for e in events if e[0] == "decode.admit"][-1:]
    inner = [name for name, t0, t1 in events
             if adm[1] <= t0 and t1 <= adm[2] and name != "decode.admit"
             and not name.startswith("compile:")]      # it was not warmed
    assert inner.count("exec:decode.prefill") == 1
    assert sorted(inner) == sorted(
        ["decode.admit.lookup", "decode.admit.alloc", "exec:decode.prefill",
         "exec:decode.pfirst"])
    for gone in ("decode.admit.kv_pull", "decode.admit.repack",
                 "decode.admit.upload", "exec:decode.pwrite"):
        assert not any(name == gone for name, _, _ in events), gone


def test_prefill_program_is_named_for_the_trace(gpt_models):
    """A device trace calls a program `jit_<function name>`; readers of
    the trace pair the target's fused prefill with its ring label
    `exec:decode.prefill` as `jit_prefill`, and the step as
    `jit_paged_step`. Any other `name=` goes through the same way."""
    import jax

    from paddle_tpu.models.gpt import gpt_paged_fns

    eng = DecodeEngine(gpt_models["tiny-scan"], max_slots=2,
                       max_new_tokens=4, page_tokens=4)
    try:
        exe = eng._prefill_exe(eng._prefill_aot, eng.params,
                               eng._model_pools_sds(), eng.kv_ladder[0])
        assert exe.as_text().startswith("HloModule jit_prefill,")
        assert eng._prefill_aot._label == "decode.prefill"
        assert eng._step_aot._jitted.__name__ == "paged_step"
    finally:
        eng.stop()
    assert gpt_paged_fns(eng.cfg)[0].__name__ == "prefill"
    draft = jax.jit(gpt_paged_fns(eng.cfg, prefill_name="draft_prefill")[0])
    assert draft.__name__ == "draft_prefill"


# ------------------------------------- paged == contiguous equivalence

@pytest.mark.parametrize("name", [n for n, _ in _CFGS])
def test_paged_engine_matches_full_forward_under_churn(gpt_models, name):
    """Property test on both param layouts: random prompt lengths,
    ragged admission/eviction churn, EOS mid-page, page-boundary
    crossings (page_tokens=4 stresses them) — every stream must equal
    the full-forward greedy reference, with ZERO steady-state compiles
    after warmup."""
    model = gpt_models[name]
    cfg = model.cfg
    rng = np.random.RandomState(hash(name) % 2**31)
    eng = DecodeEngine(model, max_slots=3, max_new_tokens=32,
                       page_tokens=4)
    try:
        eng.warmup()
        c0 = len(profiler.compile_events())
        # wave 1: ragged lengths around page boundaries (3..9 tokens at
        # pt=4 covers sub-page, exact-page, and page+1 prompts)
        prompts = [rng.randint(0, cfg.vocab_size, size=int(p))
                   for p in rng.randint(3, 10, size=5)]
        gens = [int(g) for g in rng.randint(2, 14, size=5)]
        streams = [eng.submit(p, max_new_tokens=g)
                   for p, g in zip(prompts, gens)]
        for p, g, s in zip(prompts, gens, streams):
            assert s.result(timeout=180) == _ref_greedy(model, p, g)
        # wave 2: EOS mid-page — pick each prompt's 2nd reference token
        # as its eos so the stream dies with a partially filled page
        for p in prompts[:3]:
            ref_full = _ref_greedy(model, p, 8)
            eos = ref_full[1]
            ref = ref_full[:ref_full.index(eos) + 1]
            got = eng.submit(p, max_new_tokens=8,
                             eos_id=eos).result(timeout=180)
            assert got == ref
        assert len(profiler.compile_events()) == c0, \
            "paged engine compiled during a warmed-up churn run"
        st = eng.stats()
        assert st["active"] == 0 and st["pending"] == 0
    finally:
        eng.stop()


# ------------------------------------------- prefix sharing + COW

def test_prefix_sharing_and_cow_isolation(gpt_models):
    """Shared system prompt: the second admission maps the cached pages
    (no second prefill) and only feeds its unique tail; divergent tails
    and a same-prompt overlap stream stay token-for-token correct —
    i.e. copy-on-write isolates every writer from the shared pages."""
    from paddle_tpu.observability import REGISTRY
    model = gpt_models["tiny-scan"]
    cfg = model.cfg
    rng = np.random.RandomState(97)
    pt = 4
    head = rng.randint(0, cfg.vocab_size, size=3 * pt)   # page-aligned
    tails = [rng.randint(0, cfg.vocab_size, size=t) for t in (2, 3, 5)]
    prompts = [np.concatenate([head, t]) for t in tails]
    refs = [_ref_greedy(model, p, 10) for p in prompts]
    aligned = head                        # exact-multiple prompt: its
    ref_aligned = _ref_greedy(model, aligned, 12)   # first write is COW

    eng = DecodeEngine(model, max_slots=4, max_new_tokens=16,
                       page_tokens=pt)
    try:
        flat0 = REGISTRY.flat()
        # seed the cache, then admit the divergent tails concurrently
        assert eng.submit(prompts[0],
                          max_new_tokens=10).result(timeout=180) == refs[0]
        streams = [eng.submit(p, max_new_tokens=10) for p in prompts[1:]]
        # overlap: the aligned prompt maps ALL its pages shared; its
        # first decode write hits a shared page -> copy-on-write, while
        # the other streams keep attending the originals
        s_aligned = eng.submit(aligned, max_new_tokens=12)
        for s, ref in zip(streams, refs[1:]):
            assert s.result(timeout=180) == ref
        assert s_aligned.result(timeout=180) == ref_aligned
        # replay every prompt against a now-warm cache: still exact
        for p, ref in zip(prompts, refs):
            assert eng.submit(p,
                              max_new_tokens=10).result(timeout=180) == ref
        flat = REGISTRY.flat()

        def delta(name):
            return flat.get(name, 0) - flat0.get(name, 0)

        assert delta("paddle_tpu_decode_prefix_hits_total") >= 6
        assert delta("paddle_tpu_decode_prefix_hit_tokens_total") \
            >= 6 * len(head)
        assert delta("paddle_tpu_decode_page_cow_copies_total") >= 1
        st = eng.stats()
        assert st["prefix_cache"]["cached_pages"] >= 3
        assert st["pages"]["pages_used"] >= 3     # trie keeps them warm
    finally:
        eng.stop()


def test_prefix_cache_off_still_correct(gpt_models):
    """PADDLE_TPU_DECODE_PREFIX_CACHE=0 equivalent: identical prompts
    each prefill from scratch and still match the reference."""
    model = gpt_models["small-unrolled"]
    rng = np.random.RandomState(5)
    p = rng.randint(0, model.cfg.vocab_size, size=9)
    ref = _ref_greedy(model, p, 6)
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8,
                       page_tokens=4, prefix_cache=False)
    try:
        assert eng.submit(p, max_new_tokens=6).result(timeout=120) == ref
        assert eng.submit(p, max_new_tokens=6).result(timeout=120) == ref
        assert "prefix_cache" not in eng.stats()
        assert eng.stats()["pages"]["pages_used"] == 0   # all released
    finally:
        eng.stop()


# ------------------------------------------------ backpressure + chaos

def test_page_exhaustion_fails_only_victim(gpt_models):
    """A pool too small for a second sequence: the victim gets typed
    RESOURCE_EXHAUSTED (not a crash), the survivor keeps streaming, and
    the freed capacity serves the next request."""
    model = gpt_models["tiny-scan"]
    rng = np.random.RandomState(13)
    p1 = rng.randint(0, 512, size=8)
    p2 = rng.randint(0, 512, size=8)
    ref1 = _ref_greedy(model, p1, 6)
    # 4 allocatable pages at pt=4: p1 needs 2 + 1 mid-decode; p2's
    # admission (2 pages) cannot fit alongside -> typed backpressure
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8,
                       page_tokens=4, num_pages=5, prefix_cache=False)
    try:
        # p1's second token ends its first decode step, which took its
        # third page (row 8): from there until p1 finishes four ticks
        # later, one page is free. Wait for that token (a sleep raced
        # the engine's first compile), and slow p1's later ticks so
        # that p2 is scheduled well before p1 gives its pages back.
        with chaos.inject("decode.stream:3+:Hang@0.05"):
            s1 = eng.submit(p1, max_new_tokens=6)
            for _ in range(2):
                assert s1.next_event(timeout=120)[0] == "token"
            s2 = eng.submit(p2, max_new_tokens=6)
            with pytest.raises(TypedServeError) as ei:
                s2.result(timeout=120)
        assert ei.value.code == ERR_RESOURCE_EXHAUSTED
        # the denial carries its forensics: pool label, the denied
        # owner tag (this slot, default tenant), and requested/free
        detail = str(ei.value)
        assert "pool '" in detail, detail
        assert "slot:" in detail and ":default" in detail, detail
        assert "requested 2 pages" in detail, detail
        assert "free of" in detail, detail
        assert s1.result(timeout=120) == ref1     # survivor unharmed
        # pool drained -> the next identical request now succeeds
        assert eng.submit(p2,
                          max_new_tokens=6).result(timeout=120) \
            == _ref_greedy(model, p2, 6)
    finally:
        eng.stop()


def test_ring_spans_tile_the_scheduler_loop():
    """The engine thread's ring spans after a short run: every
    `decode.loop` contains its schedule, admissions and tick and counts
    them; a miss admission is tiled by its four phases, in order, and
    carries how long the request queued; the tick's phases (the
    dispatch, the read of the step before, the preparation of the step
    after) and the wait for its step, which the iteration that
    follows does, cover at least 95% of the two."""
    import time

    from paddle_tpu.observability.tracez import RING

    # deep enough that a tick takes 10 ms on a CPU: the dispatch hook's
    # own bookkeeping between the phases is some 0.1 ms of every tick
    paddle.seed(7)
    model = GPT(GPTConfig(vocab_size=4096, max_seq_len=64, hidden=256,
                          layers=16, heads=4))
    rng = np.random.RandomState(5)
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8, page_tokens=4)
    try:
        tid = eng._thread.ident
        t_start = time.perf_counter()
        streams = [eng.submit(rng.randint(0, 512, size=n), max_new_tokens=8)
                   for n in (5, 9, 6)]
        for st in streams:
            assert len(st.result(timeout=120)) == 8
    finally:
        eng.stop()
    spans = {}
    for ph, name, ts, dur, etid, args in RING.snapshot()[0]:
        if ph == "X" and etid == tid and ts + dur >= t_start:
            spans.setdefault(name, []).append((ts, ts + dur, args or {}))

    def inside(name, outer):
        return [c for c in spans.get(name, [])
                if outer[0] <= c[0] and c[1] <= outer[1]]

    loops = spans["decode.loop"]
    admits, ticks = spans["decode.admit"], spans["decode.step"]
    assert len(admits) == 3 and len(ticks) >= 8
    for name in ("decode.admit", "decode.step"):
        assert sum(len(inside(name, lp)) for lp in loops) \
            == len(spans[name]), name        # none outside an iteration
    for lp in loops:
        # one look at the queue, and one more after a step's wait if
        # somebody arrived meanwhile
        scheds = inside("decode.schedule", lp)
        assert 1 <= len(scheds) <= 2 and all(
            set(s[2]) == {"pending", "paused"} for s in scheds)
        assert len(inside("decode.step.wait", lp)) <= 1
        assert lp[2]["admits"] == len(inside("decode.admit", lp))
        # a tick, unless every slot only awaits its last token: then
        # the iteration reads them and dispatches nothing
        n_ticks = len(inside("decode.step", lp))
        assert n_ticks == (lp[2]["active"] > 0) or (
            n_ticks == 0 and inside("decode.step.pull", lp))
    assert sum(s[2]["pending"] for s in spans["decode.schedule"]) >= 3
    assert spans["decode.idle"]              # it waited for the first

    phases = ["decode.admit.lookup", "decode.admit.alloc",
              "exec:decode.prefill", "exec:decode.pfirst"]
    for adm, plen in zip(admits, (5, 9, 6)):
        args = adm[2]
        assert args["plen"] == plen and args["ok"] is True
        assert args["queued_ms"] >= 0 and args["hit_tokens"] == 0
        assert args["rung"] >= plen
        inner = [inside(name, adm) for name in phases]
        assert all(len(c) == 1 for c in inner), inner
        starts = [c[0][0] for c in inner]
        assert starts == sorted(starts)
        assert inner[1][0][2] == {"pages": -(-plen // 4)}

    tiles = ["decode.step.build", "decode.step.provision",
             "exec:decode.ptok", "exec:decode.pstep", "exec:decode.ppick",
             "decode.step.pull", "decode.sample", "decode.step.advance"]
    shares = []
    waits = sorted(spans["decode.step.wait"])
    ticks = sorted(ticks)
    for tick, nxt in zip(ticks, ticks[1:] + [(float("inf"),)]):
        assert set(tick[2]) == {"batch", "b_rung", "w_rung", "ahead"}
        inner = [inside(name, tick) for name in tiles]
        # a tick builds the step it dispatches only where the tick
        # before could not prepare it, and the step after where one is
        # due; the rest it does once
        assert all(len(c) == 1 for c in inner[2:]), inner
        assert 1 <= len(inner[0]) <= 3 and 1 <= len(inner[1]) <= 2, inner
        # the step is waited for by the iteration that follows: the
        # tick's phases and that wait, over the tick and that wait
        (wait,) = [w for w in waits if tick[1] <= w[0] < nxt[0]]
        covered = sum(c[1] - c[0] for cs in inner + [[wait]] for c in cs)
        whole = tick[1] - tick[0] + wait[1] - wait[0]
        shares.append((covered / whole, covered, whole))
        assert inner[5][0][2]["bytes"] > 0
        # the step goes out before the step before it is read
        assert inner[3][0][0] < inner[5][0][0]
    # every tick, but for the odd one in which a loaded machine took
    # the thread off the CPU between two spans (six test workers share
    # these cores): the median tick and the ticks taken together
    shares.sort()
    assert shares[len(shares) // 2][0] >= 0.95, shares
    assert sum(c for _, c, _ in shares) \
        >= 0.95 * sum(d for _, _, d in shares), shares
    assert shares[0][0] >= 0.5, shares
    assert sum(t[2]["new_pages"]
               for t in spans["decode.step.provision"]) >= 3


def test_chaos_page_alloc_mid_decode(gpt_models):
    """Chaos site decode.page_alloc: an injected allocation fault as a
    page boundary is crossed mid-decode kills ONLY the victim stream —
    typed RESOURCE_EXHAUSTED, delivered AFTER it already streamed
    tokens — and the engine serves the next request unharmed."""
    from paddle_tpu.observability import REGISTRY
    model = gpt_models["tiny-scan"]
    rng = np.random.RandomState(41)
    p1 = rng.randint(0, 512, size=8)     # exactly one page at pt=8
    p2 = rng.randint(0, 512, size=5)
    ref2 = _ref_greedy(model, p2, 4)
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8,
                       page_tokens=8, prefix_cache=False)
    try:
        # alloc call 1 is p1's admission (1 page); call 2 is the row-8
        # page-boundary alloc inside the FIRST decode step — so the
        # fault deterministically lands mid-decode, mid-stream
        with chaos.inject("decode.page_alloc:2:RuntimeError") as inj:
            s1 = eng.submit(p1, max_new_tokens=6)
            with pytest.raises(TypedServeError) as ei:
                s1.result(timeout=120)
            assert ei.value.code == ERR_RESOURCE_EXHAUSTED
            assert len(s1.tokens) >= 1   # died streaming, not at admit
            assert inj.fired
        # victim's pages are back; the engine keeps serving correctly
        assert eng.stats()["pages"]["pages_used"] == 0
        assert eng.submit(p2, max_new_tokens=4).result(timeout=120) == ref2
        flat = REGISTRY.flat()
        assert flat.get(
            "paddle_tpu_decode_page_alloc_failures_total", 0) >= 1
        assert flat.get(
            'paddle_tpu_decode_cache_evictions_total{reason="exhausted"}',
            0) >= 1
    finally:
        eng.stop()


# ------------------------------------------------------ stats surface

def test_stats_report_rungs_and_pages_before_first_admission(gpt_models):
    """The pre-admission stats bug: batch_rung/kv_rung must report the
    smallest formable rung (not 0), and the page-pool occupancy block
    is present from construction."""
    model = gpt_models["tiny-scan"]
    eng = DecodeEngine(model, max_slots=4, max_new_tokens=4,
                       page_tokens=8)
    try:
        st = eng.stats()
        assert st["batch_rung"] >= 1            # was 0 before admission
        assert st["kv_rung"] >= st["page_tokens"] == 8
        assert st["pages"]["pages_total"] == 4 * (128 // 8)
        assert st["pages"]["pages_used"] == 0
        assert st["pages"]["fragmentation"] == 0.0
        assert st["prefix_cache"]["cached_pages"] == 0
        assert kv_page_bytes(model.cfg, 8) == \
            model.cfg.layers * 2 * 8 * model.cfg.heads * \
            model.cfg.head_dim * 4
        # after traffic the rungs reflect the last dispatch
        p = np.random.RandomState(3).randint(0, 512, size=5)
        eng.submit(p, max_new_tokens=3).result(timeout=120)
        st = eng.stats()
        assert st["batch_rung"] >= 1 and st["kv_rung"] >= 8
        assert st["pages"]["pages_used"] == 0   # prefix off: 5 < 8 page
    finally:
        eng.stop()


@pytest.mark.parametrize("n_evict", [1, 3, 7, 100])
def test_trie_eviction_is_leaf_first_lru_in_one_pass(n_evict):
    """`_PrefixCache.evict` builds one heap a call; what it removes is
    what the rule says, one removal at a time: the least recently used
    of the entries that are leaves at that moment (a chain goes tip to
    root, never orphaned)."""
    from paddle_tpu.inference.decode import _PrefixCache
    from paddle_tpu.memory.page_allocator import PageAllocator

    alloc = PageAllocator(64, label="evict-test")
    trie = _PrefixCache(alloc, page_tokens=2)
    rng = np.random.RandomState(5)
    head = rng.randint(0, 50, size=6).tolist()
    prompts = [head + rng.randint(0, 50, size=4).tolist() for _ in range(3)] \
        + [rng.randint(0, 50, size=8).tolist() for _ in range(3)]
    for p in prompts:
        pages = alloc.alloc(len(p) // 2, owner=("slot", 0, "t"))
        trie.insert(p, pages)
        for page in pages:
            alloc.release(page, owner=("slot", 0, "t"))
    for p in (prompts[4], prompts[1]):          # touch two chains
        for page in trie.lookup(p, owner=("slot", 1, "t"))[0]:
            alloc.release(page, owner=("slot", 1, "t"))

    entries = {d: list(e) for d, e in trie._entries.items()}
    kids = dict(trie._kids)
    want = []
    for _ in range(min(n_evict, len(entries))):     # the rule, by scans
        d = min(entries, key=lambda d: (1 if kids.get(d) else 0,
                                        entries[d][1]))
        parent = entries.pop(d)[2]
        want.append(d)
        if parent is not None:
            kids[parent] -= 1
    before = set(trie._entries)
    assert trie.evict(n_evict) == len(want)
    assert before - set(trie._entries) == set(want)
    assert trie.stats()["orphaned"] == 0
    assert alloc.stats()["pages_used"] == len(trie._entries)


# ------------------- one serving block: the programs agree with each other

def _programs_rig(kv_dtype, lens=(6, 9), W=5):
    """A GPT, its four programs, and pools with two prompts of `lens`
    tokens prefilled, a table of W pages a row:
    (params, kind, fns, pools, tables, cache_len)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import framework
    from paddle_tpu.inference import model_kinds
    from paddle_tpu.inference.batching import next_bucket

    cfg, pt = _FUSED_CFG, _PT
    paddle.seed(5)
    params = {k: jnp.asarray(v)
              for k, v in framework.param_arrays(GPT(cfg)).items()}
    kind = model_kinds.for_config(cfg)
    fns = {"prefill": jax.jit(kind.prefill_fn(pt)),
           "step": jax.jit(kind.step_fn(pt)),
           "verify": jax.jit(kind.verify_fn(pt)),
           "rollout": jax.jit(kind.rollout_fn(pt))}
    pools = kind.pools_zeros(2 * W + 1, pt, kv_dtype)
    tables = np.arange(1, 2 * W + 1, dtype=np.int32).reshape(2, W)
    rs = np.random.RandomState(17)
    ladder = kv_capacity_ladder(cfg.max_seq_len, floor=pt)
    for b, n in enumerate(lens):
        rung = next_bucket(n, ladder)
        toks = np.zeros((1, rung), np.int32)
        toks[0, :n] = rs.randint(0, cfg.vocab_size, size=n)
        _, pools = fns["prefill"](params, pools, jnp.asarray(toks),
                                  jnp.asarray(tables[b:b + 1, :-(-rung // pt)]),
                                  jnp.asarray([n], jnp.int32))
    return (params, kind, fns, pools, jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32))


def _live_rows(pools):
    """Every pool leaf as float32 rows, the null page left out (padding
    and overruns write there)."""
    from paddle_tpu.ops.pallas.decode_attention import dequantize_rows

    def rows(layer):
        if isinstance(layer, tuple):
            layer = dequantize_rows(*layer)
        return np.asarray(layer)[1:]

    return [rows(layer) for pool in pools for layer in pool]


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize(
    "lens,W,row0", [
        ((6, 9), 5, [7]),                   # 20 of max_seq_len 30 positions
        # row 0 runs past the cap, a new token at every step
        ((27, 9), 8, [7, 19, 23, 29, 31])],
    ids=["mid-sequence", "overrun"])
def test_rollout_is_k_paged_steps(lens, W, row0, kv_dtype):
    """`paged_rollout` is `fori_loop` over the step's own body: K steps
    of it leave the tokens and the pools that K calls of `paged_step`
    leave, forced (catch-up) tokens and chained argmaxes alike. A row
    that runs past max_seq_len writes to the null page from there on
    (the step has no such rule: it is given the null tables a padding
    row carries), so its last live row keeps what position
    max_seq_len - 1 wrote; its drafts past the cap are nobody's."""
    import jax.numpy as jnp

    params, kind, fns, pools, tables, cache_len = _programs_rig(
        kv_dtype, lens, W)
    K = 5                                   # crosses a page boundary
    forced = np.full((2, K), -1, np.int32)
    forced[0, :len(row0)] = row0
    forced[1, :2] = [11, 13]                # row 1 catches up two tokens
    drafts, rolled = fns["rollout"](params, pools, tables,
                                    jnp.asarray(forced), cache_len)
    stepped, prev = pools, None
    for i in range(K):
        tok = forced[:, i] if prev is None \
            else np.where(forced[:, i] >= 0, forced[:, i], prev)
        live = np.asarray(cache_len) + i < _FUSED_CFG.max_seq_len
        logits, stepped = fns["step"](params, stepped,
                                      jnp.where(live[:, None], tables, 0),
                                      jnp.asarray(tok, jnp.int32),
                                      cache_len + i)
        prev = np.asarray(logits).argmax(-1).astype(np.int32)
        np.testing.assert_array_equal(np.asarray(drafts)[live, i],
                                      prev[live])
    assert live.all() == (max(lens) + K <= _FUSED_CFG.max_seq_len)
    # int8 rows may round a float's last bit to the next quantum
    tol = 1e-5 if kv_dtype == "float32" else 2e-2
    for got, ref in zip(_live_rows(rolled), _live_rows(stepped)):
        np.testing.assert_allclose(got, ref, atol=tol, rtol=0)


@pytest.mark.parametrize("kv_dtype,tol", [("float32", 1e-4), ("int8", 5e-2)])
def test_verify_is_the_same_tokens_through_paged_steps(kv_dtype, tol):
    """`paged_verify` over K1 tokens scores and writes what feeding the
    same tokens through `paged_step` one by one scores and writes (an
    int8 pool within its quantization: verify's window attends the
    fresh rows before they are quantized)."""
    import jax.numpy as jnp

    params, kind, fns, pools, tables, cache_len = _programs_rig(kv_dtype)
    K1 = 4
    toks = np.random.RandomState(23).randint(0, kind.vocab_size,
                                             size=(2, K1)).astype(np.int32)
    logits, amax, verified = fns["verify"](params, pools, tables,
                                           jnp.asarray(toks), cache_len)
    np.testing.assert_array_equal(np.asarray(amax),
                                  np.asarray(logits).argmax(-1))
    stepped = pools
    for i in range(K1):
        want, stepped = fns["step"](params, stepped, tables,
                                    jnp.asarray(toks[:, i]), cache_len + i)
        np.testing.assert_allclose(np.asarray(logits)[:, i],
                                   np.asarray(want), atol=tol, rtol=0)
    for got, ref in zip(_live_rows(verified), _live_rows(stepped)):
        np.testing.assert_allclose(got, ref, atol=tol, rtol=0)


def test_moe_config_is_refused_once_by_the_builder():
    """A GPT with expert blocks has no KV-decode path: the one builder
    says so before any of the four programs exists, whichever of them
    the kind was asked for."""
    from paddle_tpu.inference import model_kinds
    from paddle_tpu.models.gpt import gpt_paged_fns

    cfg = GPTConfig(vocab_size=64, max_seq_len=16, hidden=16, layers=1,
                    heads=2, moe_experts=2)
    with pytest.raises(NotImplementedError, match="no KV-decode path"):
        gpt_paged_fns(cfg)
    kind = model_kinds.for_config(cfg)
    for ask in (kind.prefill_fn, kind.step_fn, kind.verify_fn,
                kind.rollout_fn):
        with pytest.raises(NotImplementedError, match="gpt_paged_fns"):
            ask(4)


@pytest.mark.parametrize("cfg,want", [
    (GPTConfig(vocab_size=50304, max_seq_len=1024, hidden=768, layers=12,
               heads=12), 75_497_472),          # gpt2-124m: the chat cell
    (gpt_tiny(), None)], ids=["gpt2-124m", "gpt-tiny"])
def test_gpt_slot_bytes_is_a_full_sequence_of_pages(cfg, want):
    """The slot probe starts from what one sequence's pages hold at
    float32, said the paged way (as `AXK1Kind.slot_bytes` says it)."""
    from paddle_tpu.inference import model_kinds

    kind = model_kinds.for_config(cfg)
    assert kind.slot_bytes() == kind.page_bytes(cfg.max_seq_len, "float32") \
        == cfg.max_seq_len // 16 * kv_page_bytes(cfg, 16)
    assert kind.slot_bytes() == cfg.layers * 2 * cfg.max_seq_len \
        * cfg.heads * cfg.head_dim * 4
    if want is not None:
        assert kind.slot_bytes() == want


# --------------------- attention over rows that hold every head whole

@pytest.mark.parametrize("heads,dim", [(3, 8), (2, 64), (12, 64)],
                         ids=["3x8", "2x64", "12x64"])
def test_paged_attention_matches_per_head_einsum(heads, dim):
    """A layer's pool keeps a token's heads side by side in one row
    `[P, pt, heads * head_dim]`; the one reader of a page (the gather
    that keeps the panel in that layout) gives what the per-head einsum
    over `[.., heads, head_dim]` gives."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.decode_attention import paged_decode_attention

    rs = np.random.RandomState(heads * dim)
    P, pt, B, W = 9, 4, 3, 3
    k = rs.randn(P, pt, heads, dim).astype(np.float32)
    v = rs.randn(P, pt, heads, dim).astype(np.float32)
    q = rs.randn(B, heads, dim).astype(np.float32)
    tables = rs.randint(0, P, size=(B, W)).astype(np.int32)
    lengths = np.asarray([1, 7, 12], np.int32)
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k.reshape(P, pt, heads * dim)),
        jnp.asarray(v.reshape(P, pt, heads * dim)), jnp.asarray(tables),
        jnp.asarray(lengths))
    kk = k[tables].reshape(B, W * pt, heads, dim)
    vv = v[tables].reshape(B, W * pt, heads, dim)
    s = np.einsum("bhd,bkhd->bhk", q, kk) / np.sqrt(dim)
    s = np.where(np.arange(W * pt)[None, None] < lengths[:, None, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhk,bkhd->bhd", p, vv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


# ------------------------------------- what a tick pulls from the device

def test_greedy_tick_pulls_ids_and_a_sampling_row_pulls_logits(gpt_models):
    """A tick whose rows are all greedy pulls the device's picks, [B]
    int32, and one sampling row makes it pull the [B, V] logits; the
    tokens are the same either way (`top_k=1` leaves the best id)."""
    from paddle_tpu.observability.tracez import RING

    model = gpt_models["tiny-scan"]
    prompts = [np.arange(1, 6), np.arange(3, 12)]

    def run(**sampling):
        eng = DecodeEngine(model, max_slots=2, max_new_tokens=6,
                           page_tokens=4)
        try:
            eng.warmup()
            tid = eng._thread.ident
            RING.clear()
            streams = [eng.submit(prompts[0], max_new_tokens=6),
                       eng.submit(prompts[1], max_new_tokens=6, **sampling)]
            toks = [s.result(timeout=120) for s in streams]
        finally:
            eng.stop()
        pulls = {args["bytes"] for ph, name, _, _, etid, args in
                 RING.snapshot()[0]
                 if name == "decode.step.pull" and etid == tid}
        return toks, pulls

    greedy, pulled_ids = run()
    mixed, pulled_rows = run(temperature=1.0, top_k=1)
    assert greedy == mixed
    v = model.cfg.vocab_size
    assert pulled_ids and all(b % 4 == 0 and b < v for b in pulled_ids)
    assert any(b % (4 * v) == 0 for b in pulled_rows)


# ------------------------- counts kept where pages change hands (S8)

def test_allocator_occupancy_keeps_its_counts_under_churn():
    """`occupancy()` (what a gauge refresh reads) walks no allocated
    page: the shared count moves with retain / release /
    release_range, and agrees with a recount after every operation."""
    rs = np.random.RandomState(5)
    a = PageAllocator(40)
    held = []
    for _ in range(600):
        op = rs.randint(4)
        if op == 0 and a.free_count() >= 3:
            held += a.alloc(int(rs.randint(1, 4)))
        elif op == 1 and held:
            p = held[rs.randint(len(held))]
            a.retain(p)
            held.append(p)
        elif op == 2 and held:
            a.release(held.pop(rs.randint(len(held))))
        elif op == 3 and len(held) > 2:
            tail = [held.pop() for _ in range(2)]
            a.release_range(tail, 0)
        occ, st = a.occupancy(), a.stats()
        refs = {}
        for p in held:
            refs[p] = refs.get(p, 0) + 1
        assert occ["pages_used"] == len(refs)
        assert occ["pages_shared"] == sum(r > 1 for r in refs.values())
        assert occ["pages_free"] == 39 - len(refs)
        assert {k: st[k] for k in occ} == occ
        free = sorted(set(range(1, 40)) - set(refs))
        runs, run = [0], 0
        for i, p in enumerate(free):
            run = run + 1 if i and p == free[i - 1] + 1 else 1
            runs.append(run)
        want = 1.0 - max(runs) / len(free) if free else 0.0
        assert occ["fragmentation"] == round(want, 4)


def test_prefix_eviction_order_is_the_scan_s_under_churn():
    """The trie keeps one heap for its life; what it evicts is what a
    scan of every entry would pick (leaf-first, then least recently
    touched, then by digest), through inserts, hits and evictions."""
    from paddle_tpu.inference.decode import _PrefixCache

    rs = np.random.RandomState(9)
    alloc = PageAllocator(400)
    pc = _PrefixCache(alloc, page_tokens=2)
    prompts = []
    for step in range(300):
        op = rs.randint(3)
        if op == 0 or not prompts:
            base = prompts[rs.randint(len(prompts))][:2 * rs.randint(0, 4)] \
                if prompts and rs.randint(2) else []
            prompt = list(base) + rs.randint(0, 50, size=2 * rs.randint(
                1, 5)).tolist()
            pages = alloc.alloc(len(prompt) // 2)
            pc.insert(prompt, pages)
            for p in pages:
                alloc.release(p)
            prompts.append(prompt)
        elif op == 1:
            hit, _ = pc.lookup(prompts[rs.randint(len(prompts))])
            for p in hit:
                alloc.release(p)
        else:
            with pc._lock:
                order = sorted((pc._leaf_key(d, e), d)
                               for d, e in pc._entries.items())
            before = set(pc._entries)
            if order and pc.evict(1):
                (gone,) = before - set(pc._entries)
                assert gone == order[0][1], step
    assert pc.stats()["evictions"] > 20
    assert len(pc._heap) <= 4 * len(pc._entries) + 64 + 300
