"""Disaggregated prefill/decode serving: KV-page handoff over the wire
(inference/decode.py export_kv/import_kv, serve.py kv_export/kv_handoff
frames, router.py topology-aware orchestration; docs/serving.md
"Disaggregated prefill/decode").

The contract under test is the ISSUE-19 tentpole: a prefill worker runs
the prompt forward and ships the full KV pages to a decode worker,
which admits the stream as a prefix-cache hit — token-identical to a
unified engine for greedy, seeded and speculative decoding, with zero
steady-state compiles on either worker. Every failure mode (chaos-cut
handoff, compat mismatch, checksum corruption, missing prefill pool)
degrades to a plain re-prefill, never a garbage admission."""
import os
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed.store import FileStore
from paddle_tpu.distributed.store.membership import MembershipPublisher
from paddle_tpu.inference.decode import (DecodeEngine, SpecDecodeEngine,
                                         kv_fingerprint, save_for_decode)
from paddle_tpu.inference.errors import (ERR_FAILED_PRECONDITION,
                                         TypedServeError)
from paddle_tpu.inference.router import Backend, ServeRouter
from paddle_tpu.inference.serve import InferenceServer, decode_request
from paddle_tpu.memory.migration import deserialize_pages, serialize_pages
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_tiny
from paddle_tpu.observability import REGISTRY
from paddle_tpu.testing import chaos

MAX_NEW = 8

_DRAFT_CFG = GPTConfig(vocab_size=512, max_seq_len=128, hidden=32,
                       layers=1, heads=2, scan_layers=False)


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    """Tiny GPT + draft, a decode artifact, and a unified-engine oracle."""
    paddle.seed(7)
    model = GPT(gpt_tiny())
    draft = GPT(_DRAFT_CFG)
    prefix = str(tmp_path_factory.mktemp("disagg") / "gpt")
    save_for_decode(model, prefix)

    refs = {}
    eng = DecodeEngine(model, max_slots=4, max_new_tokens=32)

    def ref(prompt, max_new=MAX_NEW, **opts):
        key = (tuple(int(t) for t in prompt), max_new,
               tuple(sorted(opts.items())))
        if key not in refs:
            refs[key] = eng.submit(prompt, max_new_tokens=max_new,
                                   **opts).result(timeout=300)
        return refs[key]

    yield {"model": model, "draft": draft, "prefix": prefix, "ref": ref}
    eng.stop()


def _prompt(seed, size):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=size)]


def _delta(flat0, key):
    return REGISTRY.flat().get(key, 0) - flat0.get(key, 0)


# ------------------------------------------------ serialization units

def test_serialize_roundtrip_and_checksum():
    """Page serialization is lossless, detects per-page corruption, and
    rides int8 leaves as uint8 views (the wire dtype table has no
    int8)."""
    rng = np.random.RandomState(0)
    chunk = (rng.randn(3, 2, 4).astype(np.float32),     # page axis 0
             rng.randint(-128, 127, size=(3, 2, 4), dtype=np.int8))
    arrays, meta = serialize_pages(chunk, 3)
    assert meta["n_pages"] == 3 and len(meta["crcs"]) == 3
    assert arrays[1].dtype == np.uint8          # int8 rides as a view
    leaves = deserialize_pages(arrays, meta)
    np.testing.assert_array_equal(leaves[0], chunk[0])
    np.testing.assert_array_equal(leaves[1], chunk[1])
    assert leaves[1].dtype == np.int8

    bad = [a.copy() for a in arrays]
    bad[0].view(np.uint8).reshape(-1)[1] ^= 0xFF
    with pytest.raises(ValueError, match="checksum"):
        deserialize_pages(bad, meta)
    with pytest.raises(ValueError, match="structure"):
        deserialize_pages(arrays[:1], meta)


def test_fingerprint_tracks_model_identity(rig):
    """Same artifact -> same fingerprint; a different model -> a
    different one (the compat fact that blocks cross-model handoffs)."""
    from paddle_tpu.framework import param_arrays
    m, d = rig["model"], rig["draft"]
    a = kv_fingerprint(m.cfg, 1e-5, param_arrays(m))
    b = kv_fingerprint(m.cfg, 1e-5, param_arrays(m))
    c = kv_fingerprint(d.cfg, 1e-5, param_arrays(d))
    assert a == b != c


# ----------------------------------------- in-process engine handoff

def test_engine_handoff_byte_identity_zero_compiles(rig):
    """The tentpole, in-process: export on one engine, import on
    another, and the decode stream is byte-identical to the unified
    oracle for greedy AND seeded sampling — with zero compiles past
    warmup on both workers."""
    model = rig["model"]
    pre = DecodeEngine(model, max_slots=4, max_new_tokens=MAX_NEW,
                       handoff=True)
    dec = DecodeEngine(model, max_slots=4, max_new_tokens=MAX_NEW,
                       handoff=True)
    cases = [
        (_prompt(3, 37), {}),
        (_prompt(4, 21), {"temperature": 0.8, "seed": 42}),
    ]
    # oracle runs (and their compiles) land before the compile snapshot
    wants = [rig["ref"](p, **o) for p, o in cases]
    try:
        pre.warmup()
        dec.warmup()
        c0 = len(profiler.compile_events())
        for (prompt, opts), want in zip(cases, wants):
            payload = pre.export_kv(prompt)
            assert payload["n_pages"] == len(prompt) // pre.page_tokens
            assert dec.import_kv(payload) == payload["n_pages"]
            got = dec.submit(prompt, max_new_tokens=MAX_NEW,
                             **opts).result(timeout=300)
            assert got == want, f"diverged under opts={opts}"
        assert len(profiler.compile_events()) == c0, \
            "handoff compiled after warmup"
        assert pre.stats()["handoff"]["exports"] == 2
        assert dec.stats()["handoff"]["imports"] == 2
        # re-export hits the prefill worker's own trie: same checksums
        assert pre.export_kv(_prompt(3, 37))["crcs"] == \
            pre.export_kv(_prompt(3, 37))["crcs"]
    finally:
        pre.stop()
        dec.stop()


def test_engine_handoff_speculative_identity(rig):
    """Speculative pair: handoff ships target K/V only (draft rows ride
    along but may be cold) — the sample-then-compare loop keeps the
    decode-side stream byte-identical to a unified spec engine."""
    model, draft = rig["model"], rig["draft"]

    def spec(**kw):
        return SpecDecodeEngine(model, draft_model=draft, speculate_k=4,
                                max_slots=2, max_new_tokens=24,
                                page_tokens=4, prefix_cache=True, **kw)

    prompt = _prompt(11, 19)
    uni = spec()
    try:
        want = uni.submit(prompt, max_new_tokens=12).result(timeout=300)
    finally:
        uni.stop()
    pre, dec = spec(handoff=True), spec(handoff=True)
    try:
        payload = pre.export_kv(prompt)
        assert dec.import_kv(payload) == len(prompt) // 4
        got = dec.submit(prompt, max_new_tokens=12).result(timeout=300)
        assert got == want
    finally:
        pre.stop()
        dec.stop()


def test_engine_handoff_zero_page_prompt(rig):
    """A prompt shorter than one page exports n_pages=0; the import is
    a no-op and the decode worker's plain prefill still matches."""
    model = rig["model"]
    pre = DecodeEngine(model, max_slots=2, max_new_tokens=MAX_NEW,
                       handoff=True)
    dec = DecodeEngine(model, max_slots=2, max_new_tokens=MAX_NEW,
                       handoff=True)
    try:
        prompt = _prompt(6, 7)
        payload = pre.export_kv(prompt)
        assert payload["n_pages"] == 0 and payload["arrays"] == []
        assert dec.import_kv(payload) == 0
        got = dec.submit(prompt,
                         max_new_tokens=MAX_NEW).result(timeout=300)
        assert got == rig["ref"](prompt)
    finally:
        pre.stop()
        dec.stop()


def test_engine_handoff_compat_and_integrity_rejects(rig):
    """Every refusal class is a typed FAILED_PRECONDITION, counted by
    reason — never a silent garbage admission: page-geometry mismatch,
    model-fingerprint mismatch, payload corruption, and a speculative
    payload landing in a plain engine (same fingerprint, different pool
    structure)."""
    model, draft = rig["model"], rig["draft"]
    pre = DecodeEngine(model, max_slots=2, max_new_tokens=MAX_NEW,
                       handoff=True)
    dec = DecodeEngine(model, max_slots=2, max_new_tokens=MAX_NEW,
                       handoff=True)
    mism = DecodeEngine(model, max_slots=2, max_new_tokens=MAX_NEW,
                        page_tokens=8, handoff=True)
    spre = SpecDecodeEngine(model, draft_model=draft, speculate_k=2,
                            max_slots=2, max_new_tokens=MAX_NEW,
                            prefix_cache=True, handoff=True)
    try:
        prompt = _prompt(9, 33)
        payload = pre.export_kv(prompt)

        # deliberately mismatched pair: page_tokens 16 -> 8
        with pytest.raises(TypedServeError,
                           match="page_tokens mismatch") as ei:
            mism.import_kv(payload)
        assert ei.value.code == ERR_FAILED_PRECONDITION

        bad = dict(payload, fingerprint="0" * 16)
        with pytest.raises(TypedServeError, match="fingerprint"):
            dec.import_kv(bad)

        corrupt = dict(payload)
        arrs = [a.copy() for a in payload["arrays"]]
        arrs[0].view(np.uint8).reshape(-1)[0] ^= 0xFF
        corrupt["arrays"] = arrs
        with pytest.raises(TypedServeError, match="checksum"):
            dec.import_kv(corrupt)

        # spec export into a plain engine: fingerprint matches (same
        # target) but the pool structure cannot — structural reject
        spayload = spre.export_kv(prompt)
        with pytest.raises(TypedServeError, match="structure"):
            dec.import_kv(spayload)

        assert dec.stats()["handoff"]["rejects"] == 3
        assert dec.stats()["handoff"]["imports"] == 0
        # the good payload still lands after all the refusals
        assert dec.import_kv(payload) == payload["n_pages"]
    finally:
        pre.stop()
        dec.stop()
        mism.stop()
        spre.stop()


def test_handoff_disabled_is_typed_refusal(rig):
    """A unified engine (handoff off) refuses export AND import with
    FAILED_PRECONDITION — the router's fallback contract."""
    eng = DecodeEngine(rig["model"], max_slots=2,
                       max_new_tokens=MAX_NEW)
    try:
        with pytest.raises(TypedServeError, match="disabled") as ei:
            eng.export_kv(_prompt(2, 20))
        assert ei.value.code == ERR_FAILED_PRECONDITION
        with pytest.raises(TypedServeError, match="disabled"):
            eng.import_kv({"page_tokens": 16})
    finally:
        eng.stop()


# ------------------------------------------------- routed fleet tests

def _disagg_fleet(prefix, store_dir, roles, **router_kw):
    """Role-tagged servers publishing into a FileStore membership
    registry + a watching router. Returns (servers, publishers, router)
    once every member is routed and trace-capable."""
    srvs, pubs = [], []
    for role in roles:
        srv = InferenceServer(prefix, port=0, decode=True,
                              decode_slots=4, decode_max_new=MAX_NEW,
                              metrics_port=0, role=role)
        meta = {"role": srv.role}
        meta.update(srv._engine.kv_compat())
        pubs.append(MembershipPublisher(
            FileStore(store_dir), f"127.0.0.1:{srv.port}",
            admin_port=srv.metrics_port, interval=0.2,
            meta=meta).start())
        srvs.append(srv)
    router = ServeRouter([], port=0, poll_interval=0.1, **router_kw)
    router.watch_membership(FileStore(store_dir), ttl=5.0, interval=0.1)
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        bs = router.backends()
        if len(bs) == len(roles) and all(b.trace_wire for b in bs):
            break
        time.sleep(0.05)
    assert len(router.backends()) == len(roles), "fleet never formed"
    return srvs, pubs, router


def _stop_fleet(srvs, pubs, router):
    for p in pubs:
        p.leave()
    router.stop()
    for s in srvs:
        s.stop()


def _stream(port, prompt, opts=None, timeout=120):
    with socket.create_connection(("127.0.0.1", port)) as s:
        s.settimeout(timeout)
        return decode_request(s, prompt, opts=opts)


def test_router_disagg_stream_token_identical(rig, tmp_path):
    """Prefill worker + decode worker through the router: the stream is
    token-identical to the unified oracle (greedy and seeded, plus a
    sub-page prompt whose handoff ships zero pages), the handoff
    counters fire, and /statusz renders the topology."""
    srvs, pubs, router = _disagg_fleet(
        rig["prefix"], str(tmp_path / "members"), ["prefill", "decode"])
    try:
        flat0 = REGISTRY.flat()
        cases = [
            (_prompt(3, 21), {"max_new_tokens": MAX_NEW}),
            (_prompt(4, 18), {"max_new_tokens": MAX_NEW,
                              "temperature": 0.7, "seed": 99}),
            (_prompt(5, 5), {"max_new_tokens": MAX_NEW}),   # 0 pages
        ]
        for prompt, opts in cases:
            ropts = {k: v for k, v in opts.items()
                     if k != "max_new_tokens"}
            want = rig["ref"](prompt, **ropts)
            assert _stream(router.port, prompt, opts) == want
        ok = _delta(flat0,
                    'paddle_tpu_router_handoffs_total{outcome="ok"}')
        assert ok == len(cases)
        pre = next(s for s in srvs if s.role == "prefill")
        dec = next(s for s in srvs if s.role == "decode")
        assert pre._engine.stats()["handoff"]["exports"] == len(cases)
        assert dec._engine.stats()["handoff"]["imports"] == len(cases)
        st = router._status()
        assert st["topology"]["roles"] == {"unified": 0, "prefill": 1,
                                           "decode": 1}
        roles = {v["role"] for v in st["membership"]["roles"].values()}
        assert roles == {"prefill", "decode"}
        for v in st["membership"]["roles"].values():
            assert v["fingerprint"] and v["page_tokens"]
    finally:
        _stop_fleet(srvs, pubs, router)


def test_router_chaos_cut_degrades_token_identical(rig, tmp_path):
    """Chaos-cut mid-handoff (the `handoff.send` site): the stream
    degrades to a plain re-prefill on the decode worker and completes
    token-identically; the fallback outcome is counted."""
    srvs, pubs, router = _disagg_fleet(
        rig["prefix"], str(tmp_path / "members"), ["prefill", "decode"])
    try:
        prompt = _prompt(8, 23)
        want = rig["ref"](prompt)
        flat0 = REGISTRY.flat()
        with chaos.inject("handoff.send:1:ConnectionError") as inj:
            got = _stream(router.port, prompt,
                          {"max_new_tokens": MAX_NEW})
        assert inj.fired
        assert got == want
        assert _delta(
            flat0,
            'paddle_tpu_router_handoffs_total{outcome="fallback"}') == 1
        assert _delta(
            flat0,
            'paddle_tpu_router_handoffs_total{outcome="ok"}') == 0
        dec = next(s for s in srvs if s.role == "decode")
        assert dec._engine.stats()["handoff"]["imports"] == 0
    finally:
        _stop_fleet(srvs, pubs, router)


def test_router_compat_mismatch_falls_back(rig, tmp_path):
    """Regression: a deliberately mismatched pair (decode worker at
    page_tokens=8 vs the prefill worker's 16). The decode worker
    refuses the handoff with a typed FAILED_PRECONDITION frame, the
    router degrades to re-prefill, and the stream still completes
    token-identically."""
    store_dir = str(tmp_path / "members")
    pre = InferenceServer(rig["prefix"], port=0, decode=True,
                          decode_slots=4, decode_max_new=MAX_NEW,
                          metrics_port=0, role="prefill")
    import paddle_tpu.inference.decode as decode_mod
    dec = InferenceServer(rig["prefix"], port=0, decode=True,
                          decode_slots=4, decode_max_new=MAX_NEW,
                          metrics_port=0, role="decode")
    dec._engine.stop()
    dec._engine = decode_mod.load_for_decode(
        rig["prefix"], max_slots=4, max_new_tokens=MAX_NEW,
        page_tokens=8, handoff=True)
    pubs = []
    for srv in (pre, dec):
        meta = {"role": srv.role}
        meta.update(srv._engine.kv_compat())
        pubs.append(MembershipPublisher(
            FileStore(store_dir), f"127.0.0.1:{srv.port}",
            admin_port=srv.metrics_port, interval=0.2,
            meta=meta).start())
    router = ServeRouter([], port=0, poll_interval=0.1)
    router.watch_membership(FileStore(store_dir), ttl=5.0, interval=0.1)
    try:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            bs = router.backends()
            if len(bs) == 2 and all(b.trace_wire for b in bs):
                break
            time.sleep(0.05)
        prompt = _prompt(13, 25)
        want = rig["ref"](prompt)
        flat0 = REGISTRY.flat()
        got = _stream(router.port, prompt, {"max_new_tokens": MAX_NEW})
        assert got == want
        assert _delta(
            flat0,
            'paddle_tpu_router_handoffs_total{outcome="fallback"}') == 1
        assert dec._engine.stats()["handoff"]["rejects"] >= 1
        assert dec._engine.stats()["handoff"]["imports"] == 0
    finally:
        for p in pubs:
            p.leave()
        router.stop()
        pre.stop()
        dec.stop()


def test_membership_role_join_leave_rerouting(rig, tmp_path):
    """Role-aware membership: with only a decode worker, streams run
    without handoff; a prefill worker joining starts handoffs; its
    clean leave stops them — streams keep completing token-identically
    throughout, and prefill workers never take direct traffic."""
    store_dir = str(tmp_path / "members")
    srvs, pubs, router = _disagg_fleet(rig["prefix"], store_dir,
                                       ["decode"])
    prompt = _prompt(17, 21)
    want = rig["ref"](prompt)
    pre = pub2 = None
    try:
        flat0 = REGISTRY.flat()
        assert _stream(router.port, prompt,
                       {"max_new_tokens": MAX_NEW}) == want
        assert _delta(
            flat0,
            'paddle_tpu_router_handoffs_total{outcome="ok"}') == 0

        pre = InferenceServer(rig["prefix"], port=0, decode=True,
                              decode_slots=4, decode_max_new=MAX_NEW,
                              metrics_port=0, role="prefill")
        meta = {"role": "prefill"}
        meta.update(pre._engine.kv_compat())
        pub2 = MembershipPublisher(
            FileStore(store_dir), f"127.0.0.1:{pre.port}",
            admin_port=pre.metrics_port, interval=0.2,
            meta=meta).start()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if any(b.role == "prefill" for b in router.backends()):
                break
            time.sleep(0.05)
        assert any(b.role == "prefill" for b in router.backends())

        flat0 = REGISTRY.flat()
        assert _stream(router.port, _prompt(18, 22),
                       {"max_new_tokens": MAX_NEW}) \
            == rig["ref"](_prompt(18, 22))
        assert _delta(
            flat0,
            'paddle_tpu_router_handoffs_total{outcome="ok"}') == 1
        # prefill workers take exports, never direct client streams
        assert all(b.role != "prefill" for b in router._routable())

        pub2.leave()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if all(b.role != "prefill" for b in router.backends()):
                break
            time.sleep(0.05)
        assert all(b.role != "prefill" for b in router.backends())
        flat0 = REGISTRY.flat()
        assert _stream(router.port, prompt,
                       {"max_new_tokens": MAX_NEW}) == want
        assert _delta(
            flat0,
            'paddle_tpu_router_handoffs_total{outcome="ok"}') == 0
    finally:
        if pub2 is not None:
            pub2.leave()
        if pre is not None:
            pre.stop()
        _stop_fleet(srvs, pubs, router)


def test_unified_fleet_unchanged(rig):
    """Purely additive: a role-less (unified) fleet never attempts a
    handoff, routes exactly as before, and stays token-identical."""
    srvs = [InferenceServer(rig["prefix"], port=0, decode=True,
                            decode_slots=4, decode_max_new=MAX_NEW,
                            metrics_port=0)
            for _ in range(2)]
    router = ServeRouter(
        [Backend("127.0.0.1", s.port, s.metrics_port) for s in srvs],
        port=0, poll_interval=0.1)
    try:
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            bs = router.backends()
            if bs and all(b.trace_wire for b in bs):
                break
            time.sleep(0.05)
        assert all(b.role == "unified" for b in router.backends())
        flat0 = REGISTRY.flat()
        prompt = _prompt(21, 15)
        assert _stream(router.port, prompt,
                       {"max_new_tokens": MAX_NEW}) == rig["ref"](prompt)
        for outcome in ("ok", "fallback"):
            assert _delta(
                flat0, f'paddle_tpu_router_handoffs_total'
                       f'{{outcome="{outcome}"}}') == 0
        for s in srvs:
            assert "handoff" not in s._engine.stats()
    finally:
        router.stop()
        for s in srvs:
            s.stop()


@pytest.mark.slow
def test_multiprocess_disagg_drill(rig, tmp_path):
    """The drill with real process boundaries: 1 prefill + 2 decode
    workers spawned as `--role`-tagged subprocesses publishing into a
    FileStore registry; concurrent routed streams all complete
    token-identical to the unified oracle with handoffs landing."""
    import subprocess
    import sys

    store_dir = str(tmp_path / "members")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_TSAN", None)     # children run unsanitized
    procs = []
    try:
        for role in ("prefill", "decode", "decode"):
            p = subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.inference.serve",
                 rig["prefix"], "--port", "0", "--metrics-port", "0",
                 "--decode", "--decode-slots", "4",
                 "--decode-max-new", str(MAX_NEW),
                 "--role", role, "--membership-store", store_dir],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                env=env, text=True)
            procs.append(p)
        for p in procs:
            deadline = time.monotonic() + 120.0
            serving = False
            while time.monotonic() < deadline:
                line = p.stdout.readline()
                if line.startswith("MEMBERSHIP "):
                    serving = True
                    break
                if not line and p.poll() is not None:
                    break
            assert serving, "worker never published membership"

        router = ServeRouter([], port=0, poll_interval=0.1)
        router.watch_membership(FileStore(store_dir), ttl=5.0,
                                interval=0.1)
        try:
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                bs = router.backends()
                if len(bs) == 3 and all(b.trace_wire for b in bs) \
                        and sum(b.role == "prefill" for b in bs) == 1:
                    break
                time.sleep(0.05)
            bs = router.backends()
            assert sorted(b.role for b in bs) \
                == ["decode", "decode", "prefill"]

            n_streams = 6
            prompts = [_prompt(40 + i, 17 + i) for i in range(n_streams)]
            want = [rig["ref"](p) for p in prompts]
            flat0 = REGISTRY.flat()
            outs = [None] * n_streams
            errs = []

            def client(i):
                try:
                    outs[i] = _stream(router.port, prompts[i],
                                      {"max_new_tokens": MAX_NEW},
                                      timeout=300)
                except Exception as e:
                    errs.append(f"stream {i}: {e!r}")

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True)
                       for i in range(n_streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            assert not errs, f"lost streams: {errs[:3]}"
            assert outs == want
            assert _delta(
                flat0,
                'paddle_tpu_router_handoffs_total{outcome="ok"}') \
                == n_streams
        finally:
            router.stop()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
