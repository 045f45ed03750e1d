"""memz: page-level owner attribution, the allocation event ring, OOM
forensics, and the fleet memory plane (ISSUE 20).

The load-bearing claims: (1) per-owner rollups are conservation-exact —
every used page counts toward exactly one owner, so Σ owners ==
pages_used always; (2) the allocation ring stays under the tracez-style
2 µs/event budget and attribution adds < 2 µs on top of an untagged op;
(3) a forced exhaustion on a REAL engine produces an OOM forensic dump
whose rollup accounts for every used page, retrievable via a live HTTP
``/memz?oom=1`` scrape; (4) the router-side merge sums per-backend
bodies without losing any."""
import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.memory.page_allocator import (PageAllocator, PageExhausted,
                                              UNTAGGED, owner_str)
from paddle_tpu.models.gpt import GPT, gpt_tiny
from paddle_tpu.observability import AdminServer, memz
from paddle_tpu.observability.memz import MemRing


# -- MemRing ---------------------------------------------------------------

def test_ring_records_and_wraps():
    ring = MemRing(capacity=4)
    for i in range(6):
        ring.record("alloc", "kv", ("slot", f"r{i}", "t"), 1, 10 - i)
    assert ring.total == 6 and ring.dropped == 2
    events, total = ring.snapshot()
    assert total == 6 and len(events) == 4
    # oldest two were overwritten; survivors are r2..r5 in order
    assert [e[2][1] for e in events] == ["r2", "r3", "r4", "r5"]
    tail = ring.tail(2)
    assert [t["owner"] for t in tail] == ["slot:r4:t", "slot:r5:t"]
    assert tail[-1]["op"] == "alloc" and tail[-1]["free"] == 5
    # wall anchor: tail timestamps are wall-clock-ish
    assert abs(tail[-1]["t"] - time.time()) < 60
    ring.clear()
    assert ring.total == 0 and ring.snapshot() == ([], 0)


def test_ring_capacity_zero_disables():
    ring = MemRing(capacity=0)
    ring.record("alloc", "kv", UNTAGGED, 1, 1)
    assert ring.total == 0 and ring.snapshot() == ([], 0)


def test_ring_record_overhead_under_2us():
    """The always-on budget, same as tracez: one tuple + one slot
    assignment under one lock, < 2 µs/event on CPU, min-of-repeats."""
    ring = MemRing(capacity=1 << 14)
    n = 20000
    best = float("inf")
    for _ in range(5):
        ring.clear()
        t0 = time.perf_counter()
        for _i in range(n):
            ring.record("alloc", "kv", ("slot", "r1", "t"), 1, 3)
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 2e-6, f"{best * 1e6:.3f} µs/event"


def test_attribution_overhead_under_2us():
    """Owner attribution must ride the existing leaf lock for free-ish:
    a tagged retain/release costs < 2 µs more than an untagged one
    (min-of-repeats on both sides to squeeze out scheduler noise)."""
    a = PageAllocator(8, label="memz-bench")
    (p,) = a.alloc(1, owner=("slot", "r1", "t"))
    n = 20000

    def bench(tag):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _i in range(n):
                a.retain(p, owner=tag)
                a.release(p, owner=tag)
            best = min(best, (time.perf_counter() - t0) / (2 * n))
        return best

    tagged = bench(("trie", "abcdef012345"))
    untagged = bench(None)
    assert tagged - untagged < 2e-6, \
        f"attribution adds {(tagged - untagged) * 1e9:.0f} ns/op"
    assert tagged < 10e-6, f"{tagged * 1e6:.3f} µs/op absolute"


# -- owner rollups ---------------------------------------------------------

def test_owner_rollups_conservation_and_primary_owner():
    a = PageAllocator(17, label="roll")
    s1 = a.alloc(4, owner=("slot", "r1", "acme"))
    s2 = a.alloc(3, owner=("slot", "r2", "blue"))
    tr = a.alloc(2, owner=("trie", "aa11"))
    a.alloc(1)                                    # untagged bucket
    # sharing: the trie retains two of r1's pages — primary owner stays
    # the slot (first still-holding tagger), so nothing double-counts
    a.retain(s1[0], owner=("trie", "bb22"))
    a.retain(s1[1], owner=("trie", "bb22"))
    st = a.stats()
    assert st["pages_used"] == 10
    assert sum(st["owners"].values()) == 10
    assert st["owner_kinds"] == {"slot": 7, "trie": 2, "untagged": 1}
    assert st["tenants"] == {"acme": 4, "blue": 3, "-": 3}
    # the slot releases its pages: the trie's retained refs survive and
    # attribution shifts to the surviving holder
    for p in s1:
        a.release(p, owner=("slot", "r1", "acme"))
    st = a.stats()
    assert st["pages_used"] == 8                  # 2 shared survive
    assert st["owner_kinds"] == {"slot": 3, "trie": 4, "untagged": 1}
    assert sum(st["owners"].values()) == 8
    # mismatched release tag degrades attribution, never correctness
    a.release(s2[0], owner=("draft", "nope"))
    assert a.refcount(s2[0]) == 0
    assert owner_str(("slot", "r1", "acme")) == "slot:r1:acme"
    assert a.fragmentation_map()[0][0] >= 1
    for p in [s1[0], s1[1]]:
        a.release(p, owner=("trie", "bb22"))
    for p in s2[1:] + tr:
        a.release(p)
    assert a.stats()["owner_kinds"] == {"untagged": 1}


def test_retag_moves_attribution():
    a = PageAllocator(5, label="retag")
    (p,) = a.alloc(1, owner=("tier", "job-9"))
    a.retag(p, ("tier", "job-9"), ("trie", "cc33"))
    assert a.stats()["owner_kinds"] == {"trie": 1}
    a.retag(999, ("x",), ("y",))                  # unallocated: no-op
    a.release(p, owner=("trie", "cc33"))
    assert a.stats()["pages_used"] == 0


# -- pool registry + ghost audit ------------------------------------------

class _FakeEngine:
    def __init__(self, alloc, live):
        self.alloc = alloc
        self.live = live

    def context(self):
        return {"live_owner_ids": list(self.live), "kv_ladder": [16]}


def test_register_pool_snapshot_and_ghost_audit():
    a = PageAllocator(9, label="ghosty")
    eng = _FakeEngine(a, {"r-alive"})
    memz.register_pool(a, context_fn=eng.context)
    a.alloc(2, owner=("slot", "r-alive", "t"))
    a.alloc(1, owner=("slot", "r-dead", "t"))     # finished stream
    a.alloc(1, owner=("trie", "aa"))              # trie is never a ghost
    snap = memz.snapshot()
    pool = snap["pools"]["ghosty"]
    assert pool["stats"]["pages_used"] == 4
    assert pool["ghost_pages"] == 1
    assert pool["ghosts"][0]["owner"] == "slot:r-dead:t"
    assert pool["context"]["kv_ladder"] == [16]
    assert "live_owner_ids" not in pool.get("context", {})
    assert snap["ring"]["capacity"] == memz.RING.capacity
    blk = memz.status_block()
    assert blk["pools"]["ghosty"]["ghost_pages"] == 1
    assert blk["pools"]["ghosty"]["pages_used"] == 4
    # the registry gauges refresh from the pool on scrape
    from paddle_tpu.observability import REGISTRY
    flat = REGISTRY.flat()
    assert flat['paddle_tpu_mem_pages{pool="ghosty",owner_kind="slot"}'] \
        == 3
    assert flat['paddle_tpu_mem_ghost_pages{pool="ghosty"}'] == 1
    # a dead engine's pool unregisters itself via the weakref
    del a, eng
    assert "ghosty" not in memz.snapshot()["pools"]


def test_ghost_audit_without_live_set_reports_nothing():
    a = PageAllocator(5, label="nolive")
    a.alloc(1, owner=("slot", "r-gone", "t"))
    assert memz.ghost_audit(a, None) == []
    assert memz.ghost_audit(a, {"other": 1}) == []


# -- OOM forensics on a real engine + live /memz?oom=1 ---------------------

def test_engine_oom_dump_accounts_for_every_page():
    """Force exhaustion on a real DecodeEngine: the captured forensic
    dump's per-owner rollup must account for every used page exactly,
    and the dump must be retrievable over live HTTP at /memz?oom=1
    (plus merged through the router-side merge helper)."""
    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.inference.errors import TypedServeError

    memz.clear_oom_dumps()
    paddle.seed(7)
    model = GPT(gpt_tiny())
    rng = np.random.RandomState(13)
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8,
                       page_tokens=4, num_pages=5, prefix_cache=False)
    try:
        # the first request holds its pages once it has stepped: its
        # second token means a third page (row 8) is mapped, so the
        # second request's two pages cannot both be had. (A sleep stood
        # here for that, and under six workers it was too short.)
        s1 = eng.submit(rng.randint(0, 512, size=8), max_new_tokens=8)
        for _ in range(2):
            assert s1.next_event(timeout=120)[0] == "token"
        s2 = eng.submit(rng.randint(0, 512, size=8), max_new_tokens=6)
        with pytest.raises(TypedServeError):
            s2.result(timeout=120)
        dumps = memz.oom_dumps()
        assert dumps, "exhaustion did not capture an OOM dump"
        d = dumps[-1]
        label = eng._alloc.label
        assert d["pool"] == label
        assert d["requested"] == 2
        assert d["denied_owner"].startswith("slot:")
        assert d["denied_owner"].endswith(":default")
        # conservation: the rollup accounts for EVERY used page
        assert sum(d["top_owners"].values()) == d["pages_used"]
        assert sum(d["owner_kinds"].values()) == d["pages_used"]
        assert sum(d["tenants"].values()) == d["pages_used"]
        assert d["pages_used"] + d["pages_free"] == 4  # 5 minus null
        assert d["ring_tail"], "dump must embed the allocation ring"
        ops = {e["op"] for e in d["ring_tail"]}
        assert "exhausted" in ops and "alloc" in ops
        assert isinstance(d["fragmentation_map"], list)
        assert d["context"]["page_tokens"] == 4
        s1.result(timeout=120)

        # live scrape: the engine's registered pool serves /memz and
        # the ?oom=1 view returns the retained dumps
        with AdminServer(port=0) as adm:
            base = f"http://127.0.0.1:{adm.port}"
            with urllib.request.urlopen(base + "/memz", timeout=10) as r:
                body = json.loads(r.read())
            assert label in body["pools"]
            st = body["pools"][label]["stats"]
            assert sum(st["owner_kinds"].values()) == st["pages_used"]
            with urllib.request.urlopen(base + "/memz?oom=1",
                                        timeout=10) as r:
                oom_body = json.loads(r.read())
            assert oom_body["oom_dumps"]
            assert oom_body["oom_dumps"][-1]["seq"] == d["seq"]
            with urllib.request.urlopen(base + "/", timeout=10) as r:
                assert 'href="/memz"' in r.read().decode()
            # the router-side merge over this live body keeps the dump
            merged = memz.merge_memz([oom_body], keys=["b0"])
            assert merged["merged"] == 1
            assert any(x["seq"] == d["seq"] for x in merged["oom_dumps"])
    finally:
        eng.stop()
        memz.clear_oom_dumps()


def test_oom_dump_retention_limit(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MEMZ_OOM_DUMPS", "2")
    memz.clear_oom_dumps()
    a = PageAllocator(4, label="lim")
    for _ in range(4):
        memz.capture_oom(a, owner=("slot", "r", "t"), requested=9)
    dumps = memz.oom_dumps()
    assert len(dumps) == 2
    assert [d["seq"] for d in dumps] == sorted(d["seq"] for d in dumps)
    memz.clear_oom_dumps()


# -- fleet merge -----------------------------------------------------------

def test_merge_memz_sums_rollups():
    def body(label, kinds, tenants, used, free):
        return {"pools": {label: {
            "stats": {"pages_total": used + free, "pages_used": used,
                      "pages_free": free, "owner_kinds": kinds,
                      "tenants": tenants},
            "ghost_pages": 1}}, "oom_dumps": 2}

    m = memz.merge_memz(
        [body("kv", {"slot": 3, "trie": 1}, {"acme": 3, "-": 1}, 4, 4),
         body("kv", {"slot": 2}, {"acme": 2}, 2, 6),
         None],                                   # unreachable backend
        keys=["b0", "b1", "b2"])
    assert m["merged"] == 2
    assert m["owner_kinds"] == {"slot": 5, "trie": 1}
    assert m["tenants"] == {"acme": 5, "-": 1}
    assert m["pages_used"] == 6 and m["pages_total"] == 16
    assert m["ghost_pages"] == 2 and m["oom_dumps"] == 4
    assert set(m["backends"]) == {"b0", "b1"}
    # oom-mode bodies merge into one time-sorted dump list
    mo = memz.merge_memz(
        [{"oom_dumps": [{"time": 2.0, "seq": 5}]},
         {"oom_dumps": [{"time": 1.0, "seq": 9}]}], keys=["a", "b"])
    assert [d["seq"] for d in mo["oom_dumps"]] == [9, 5]


# -- satellites ------------------------------------------------------------

def test_stall_dump_embeds_memz_block(tmp_path):
    from paddle_tpu.observability.flight_recorder import FlightRecorder

    a = PageAllocator(6, label="stally")
    memz.register_pool(a)
    a.alloc(2, owner=("slot", "rq", "t"))
    rec = FlightRecorder("memz_dump_test", busy_fn=lambda: True,
                         dump_dir=str(tmp_path), threshold_s=60.0)
    try:
        path = rec.dump(reason="manual")
    finally:
        rec.stop()
    payload = json.loads(open(path).read())
    assert "memz" in payload
    blk = payload["memz"]["pools"]["stally"]
    assert blk["pages_used"] == 2
    assert blk["owner_kinds"] == {"slot": 2}
    assert "slot:rq:t" in blk["top_owners"]


def test_exhausted_error_carries_context():
    a = PageAllocator(4, label="ctx")
    a.alloc(2, owner=("slot", "r1", "t"))
    with pytest.raises(PageExhausted) as ei:
        a.alloc(3, owner=("slot", "r2", "t"))
    e = ei.value
    assert e.pool == "ctx" and e.requested == 3 and e.free == 1
    assert e.owner == ("slot", "r2", "t")
    msg = str(e)
    assert "pool 'ctx'" in msg and "requested 3 pages" in msg
    assert "slot:r2:t" in msg and "1 free of 4" in msg
