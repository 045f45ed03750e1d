"""tracez + profilez contract: the bounded event ring (overwrite
semantics, exact counts under concurrent writers, < 2 µs/event), the
Chrome trace-event exporter (schema, wall-clock skew correction on
merge), the per-executable continuous profiler over the AOT dispatch
hook, the admin surface (/tracez, /profilez, the / index), and the
offline merge CLI — including a slow 3-process router + 2-backend run
assembled into one Perfetto-loadable timeline."""
import collections
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.observability import (PROFILER, REGISTRY, RING,
                                     AdminServer, ExecProfiler,
                                     MetricsRegistry, SpanRecorder,
                                     TraceRing, merge_traces)
from paddle_tpu.observability.tracez import main as tracez_main
from paddle_tpu.static import InputSpec


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 4)

    def forward(self, x):
        return self.fc(x)


# -- ring semantics --------------------------------------------------------

def test_ring_bound_and_overwrite():
    ring = TraceRing(capacity=16)
    for i in range(40):
        ring.record("i", f"e{i}", float(i))
    events, total = ring.snapshot()
    assert total == 40 and ring.total == 40
    assert ring.dropped == 24
    assert len(events) == 16            # the ring never grows
    # oldest -> newest, and exactly the LAST 16: overwrite, not refuse
    assert [e[1] for e in events] == [f"e{i}" for i in range(24, 40)]
    ring.clear()
    assert ring.snapshot() == ([], 0)


def test_ring_capacity_zero_disables_recording(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACEZ_CAPACITY", "0")
    ring = TraceRing()
    assert ring.capacity == 0
    ring.complete("x", 0.0, 1.0)
    ring.instant("y")
    assert ring.snapshot() == ([], 0)
    doc = ring.chrome_trace()
    assert [e for e in doc["traceEvents"] if e["ph"] != "M"] == []


def test_ring_concurrent_writers_exact_counts():
    """N threads x M events with no drops: every event lands exactly
    once, per-thread order is preserved, tids are distinct."""
    ring = TraceRing(capacity=8192)
    N, M = 8, 500
    barrier = threading.Barrier(N)

    def worker(k):
        barrier.wait()
        for i in range(M):
            ring.complete(f"t{k}", float(i), float(i) + 0.5, {"i": i})

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events, total = ring.snapshot()
    assert total == N * M == len(events)
    counts = collections.Counter(e[1] for e in events)
    assert counts == {f"t{k}": M for k in range(N)}
    for k in range(N):
        seq = [e[5]["i"] for e in events if e[1] == f"t{k}"]
        assert seq == list(range(M))    # per-thread order survives
    tids = {e[1]: e[4] for e in events}
    assert len(set(tids.values())) == N


def test_ring_record_overhead_under_2us():
    """The always-on budget: one instant() (clock read + tuple + one
    lock) must stay under 2 µs/event on CPU, min-of-repeats."""
    ring = TraceRing(capacity=1 << 14)
    n = 20000
    best = float("inf")
    for _ in range(5):
        ring.clear()
        t0 = time.perf_counter()
        for _i in range(n):
            ring.instant("bench")
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 2e-6, f"{best * 1e6:.3f} µs/event"


# -- Chrome trace-event export ---------------------------------------------

def test_chrome_trace_schema():
    ring = TraceRing(capacity=32, component="testcomp", pid=77)
    with ring.span("work", {"k": 1}) as work:
        time.sleep(0.002)
        work.args["n"] = 3              # a count known only at the end
    ring.instant("mark", {"m": 2})
    with ring.span("dropped") as gone:
        gone.drop()
    ring.complete("after", work.t0, work.t1)
    doc = ring.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs[0] == {"ph": "M", "pid": 77, "tid": 0,
                      "name": "process_name",
                      "args": {"name": "testcomp/77"}}
    tnames = [e for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"]
    assert len(tnames) == 1             # single-threaded test
    rows = [e for e in evs if e["ph"] != "M"]
    assert [(e["ph"], e["name"]) for e in rows] == [
        ("X", "work"), ("i", "mark"), ("X", "after")]
    x = rows[0]
    assert x["name"] == "work" and x["cat"] == "testcomp"
    assert x["pid"] == 77 and x["dur"] >= 2000      # µs
    assert x["args"] == {"k": 1, "n": 3}
    i = rows[1]
    assert i["s"] == "t" and i["args"]["m"] == 2
    assert rows[2]["dur"] == x["dur"] and "args" not in rows[2]
    # timestamps are anchored wall-clock µs: inside this test's window
    now_us = time.time() * 1e6
    for e in rows:
        assert now_us - 60e6 < e["ts"] < now_us + 60e6
    md = doc["metadata"]
    assert md["events_recorded"] == 3 and md["events_dropped"] == 0
    json.dumps(doc)                     # fully serializable
    assert not {"begin", "end", "counter"} & set(dir(TraceRing))


def test_span_is_a_profiler_annotation_too(tmp_path):
    """One primitive on both clocks: under a jax.profiler session a
    `span()` leaves an event of its name in the host plane of the same
    xplane file that holds the device rows; a ring of capacity 0 leaves
    neither."""
    import glob

    import jax
    from jax.profiler import ProfileData

    ring, off = TraceRing(capacity=8), TraceRing(capacity=0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # what the benchmark traces with
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with ring.span("tracez.test.outer", {"k": 1}):
            with ring.span("tracez.test.inner"):
                time.sleep(0.002)
        with off.span("tracez.test.off"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("tracez.test."):
                        host[ev.name] = (ev.start_ns, ev.duration_ns)
    assert set(host) == {"tracez.test.outer", "tracez.test.inner"}
    (o0, od), (i0, idur) = host["tracez.test.outer"], \
        host["tracez.test.inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od and idur >= 2e6    # ns
    events, _ = ring.snapshot()
    assert [e[1] for e in events] == ["tracez.test.inner",
                                      "tracez.test.outer"]
    # the ring's span lies inside the profiler's: same extent, two clocks
    assert events[1][3] * 1e9 <= od + 1e3
    assert off.snapshot() == ([], 0)


def test_span_overhead_under_5us_without_a_session():
    """`span()` with no profiler session on: two clock reads, one
    annotation that records nothing, one ring write."""
    ring = TraceRing(capacity=1 << 14)
    n = 20000
    best = float("inf")
    for _ in range(5):
        ring.clear()
        t0 = time.perf_counter()
        for _i in range(n):
            with ring.span("bench"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert ring.total == n
    assert best < 5e-6, f"{best * 1e6:.3f} µs/span"


def test_merge_skew_corrected_timeline():
    """Two rings whose monotonic epochs are 1234.5 s apart (different
    process boots) merge into one monotonic timeline: the backend's
    span nests inside the router's forward span, and the router's stage
    spans sum exactly to the client-observed request span."""
    wall = time.time()
    rr = TraceRing(capacity=64, component="router", pid=1)
    rb = TraceRing(capacity=64, component="serve", pid=2)
    rr.anchor_wall = rb.anchor_wall = wall
    rr.anchor_mono, rb.anchor_mono = 100.0, 100.0 + 1234.5
    t0, skew = 105.0, 1234.5            # router clock / backend offset
    rr.record("X", "router.request", t0, 0.100, {"rid": 1})
    rr.record("X", "router.pick", t0, 0.010)
    rr.record("X", "router.forward", t0 + 0.010, 0.080)
    rr.record("X", "router.reply", t0 + 0.090, 0.010)
    rb.record("X", "serve.request", t0 + 0.020 + skew, 0.060)
    merged = merge_traces([rr.chrome_trace(), rb.chrome_trace()])
    rows = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    ts = [e["ts"] for e in rows]
    assert ts == sorted(ts)             # monotonic after skew correction
    by = {e["name"]: e for e in rows}
    req, fwd, srv = (by["router.request"], by["router.forward"],
                     by["serve.request"])
    # the backend span sits strictly inside the forward span
    assert fwd["ts"] <= srv["ts"]
    assert srv["ts"] + srv["dur"] <= fwd["ts"] + fwd["dur"] + 1e-3
    # span-sum == client-observed latency (pick + forward + reply)
    assert by["router.pick"]["dur"] + fwd["dur"] + by["router.reply"]["dur"] \
        == pytest.approx(req["dur"], rel=1e-9)
    # and the absolute position is the shared wall anchor
    assert req["ts"] == pytest.approx((wall + 5.0) * 1e6, abs=1.0)
    assert merged["metadata"]["merged"] == 2
    assert {p["pid"] for p in merged["metadata"]["processes"]} == {1, 2}


def test_merge_cli_files(tmp_path):
    r1 = TraceRing(capacity=16, component="a", pid=11)
    r2 = TraceRing(capacity=16, component="b", pid=22)
    r1.instant("one")
    r2.instant("two")
    f1, f2 = tmp_path / "a.json", tmp_path / "b.json"
    f1.write_text(json.dumps(r1.chrome_trace()))
    f2.write_text(json.dumps(r2.chrome_trace()))
    out = tmp_path / "merged.json"
    assert tracez_main(["merge", str(f1), str(f2), "-o", str(out)]) == 0
    merged = json.loads(out.read_text())
    names = [e["name"] for e in merged["traceEvents"] if e["ph"] != "M"]
    assert sorted(names) == ["one", "two"]
    assert merged["metadata"]["merged"] == 2
    # all sources unreadable -> rc 1
    assert tracez_main(["merge", str(tmp_path / "missing.json"),
                        "-o", str(tmp_path / "m2.json")]) == 1


def test_ring_gauges_in_registry():
    RING.instant("gauge.marker")
    flat = REGISTRY.flat()
    assert flat["paddle_tpu_tracez_events"] == RING.total
    assert flat["paddle_tpu_tracez_dropped"] == RING.dropped
    assert flat["paddle_tpu_tracez_capacity"] == RING.capacity


# -- continuous profiler over the dispatch hook ----------------------------

def test_exec_profiler_counts_scripted_dispatches_exactly():
    """The AotCache dispatch hook: 13 scripted dispatches of one
    executable produce exactly 13 call observations, 1 compile, and
    matching ring events."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.compile_cache import AotCache

    label = "tracez_churn"
    cache = AotCache(jax.jit(lambda x: x * 2.0), label)
    before = PROFILER.snapshot().get(
        label, {"calls": 0, "compiles": 0})
    x = jnp.ones((8,), jnp.float32)
    exe = cache.get_or_compile(x)
    for _ in range(13):
        out = exe(x)
    assert np.allclose(np.asarray(out), 2.0)
    after = PROFILER.snapshot()[label]
    assert after["calls"] - before["calls"] == 13
    assert after["compiles"] - before["compiles"] == 1
    assert after["wall_s"] > 0.0 and after["block_s"] >= 0.0
    flat = REGISTRY.flat()
    assert flat[f'paddle_tpu_exec_calls_total{{exe="{label}"}}'] \
        >= after["calls"]
    names = [e[1] for e in RING.snapshot()[0]]
    assert names.count(f"exec:{label}") >= 13
    assert any(n.startswith(f"compile:{label}") for n in names)
    top = PROFILER.profilez()["top"]
    assert any(r["exe"] == label for r in top) or len(top) == 10


def test_exec_profiler_private_registry_top():
    reg = MetricsRegistry()
    prof = ExecProfiler(registry=reg)
    prof.observe("slow", 0.001, 0.050, 1024)
    prof.observe("fast", 0.001, 0.001)
    prof.observe("fast", 0.001, 0.001)
    prof.record_compile("slow", 0.5)
    top = prof.top(5)
    assert [r["exe"] for r in top] == ["slow", "fast"]   # by block time
    assert top[0]["donated_bytes"] == 1024
    assert top[0]["compiles"] == 1 and top[1]["calls"] == 2
    body = prof.profilez()
    assert body["executables"] == 2 and body["total_calls"] == 3
    assert body["total_block_s"] == pytest.approx(0.052)


def test_decode_churn_exact_dispatch_accounting():
    """A scripted decode churn: the per-executable call count advances
    by exactly the engine's step count, and the ring holds the tick
    phases."""
    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.models.gpt import GPT, gpt_tiny

    eng = DecodeEngine(GPT(gpt_tiny()), max_slots=2, max_new_tokens=8)
    try:
        eng.warmup()
        base = PROFILER.snapshot().get(
            "decode.pstep", {"calls": 0})["calls"]
        steps0 = eng.stats()["steps"]
        rng = np.random.default_rng(0)
        futs = [eng.submit(
            rng.integers(0, 64, size=5).astype(np.int32),
            max_new_tokens=8) for _ in range(3)]
        for f in futs:
            assert len(f.result(timeout=300)) == 8
    finally:
        eng.stop()
    steps1 = eng.stats()["steps"]
    calls1 = PROFILER.snapshot()["decode.pstep"]["calls"]
    assert steps1 > steps0
    assert calls1 - base == steps1 - steps0   # one dispatch per tick
    names = {e[1] for e in RING.snapshot()[0]}
    assert {"decode.step", "decode.sample", "decode.admit",
            "decode.emit", "exec:decode.pstep"} <= names


# -- admin surface ---------------------------------------------------------

def test_admin_serves_tracez_profilez_and_index():
    RING.instant("admin.test.marker")
    with AdminServer(port=0, registry=MetricsRegistry()) as adm:
        base = f"http://127.0.0.1:{adm.port}"
        with urllib.request.urlopen(base + "/tracez", timeout=10) as r:
            doc = json.loads(r.read())
        assert any(e.get("name") == "admin.test.marker"
                   for e in doc["traceEvents"])
        assert doc["metadata"]["capacity"] == RING.capacity

        with urllib.request.urlopen(base + "/profilez", timeout=10) as r:
            prof = json.loads(r.read())
        assert {"executables", "total_calls",
                "total_block_s", "top"} <= set(prof)

        with urllib.request.urlopen(base + "/", timeout=10) as r:
            assert r.headers.get_content_type() == "text/html"
            html = r.read().decode()
        for p in ("/metrics", "/healthz", "/statusz",
                  "/tracez", "/profilez"):
            assert f'href="{p}"' in html

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert ei.value.code == 404
        assert "/tracez" in json.loads(ei.value.read())["endpoints"]


# -- satellites ------------------------------------------------------------

def test_stall_dump_embeds_ring_tail(tmp_path):
    from paddle_tpu.observability.flight_recorder import FlightRecorder

    RING.instant("pre.stall.marker", {"x": 1})
    rec = FlightRecorder("tracez_dump_test", busy_fn=lambda: True,
                         dump_dir=str(tmp_path), threshold_s=60.0)
    try:
        path = rec.dump(reason="manual")
    finally:
        rec.stop()
    payload = json.loads(open(path).read())
    assert "events" in payload
    rows = [row for rows in payload["events"].values() for row in rows]
    assert any(row["name"] == "pre.stall.marker" for row in rows)
    # per-thread tail is bounded
    assert all(len(rows) <= 200 for rows in payload["events"].values())


def test_span_jsonl_rotation(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE_MAX_BYTES", "500")
    path = tmp_path / "t.jsonl"
    rec = SpanRecorder(component="rot", sample=1.0, path=str(path))
    assert rec.max_bytes == 500
    for i in range(40):
        rec.record(i, {"queue_wait": 0.001}, force=True)
    rec.close()
    rotated = tmp_path / "t.jsonl.1"
    assert path.exists() and rotated.exists()   # keep-last-2
    assert path.stat().st_size <= 500
    assert rotated.stat().st_size <= 500
    for p in (path, rotated):                   # no torn lines
        for ln in p.read_text().splitlines():
            json.loads(ln)
    assert not (tmp_path / "t.jsonl.2").exists()


def test_span_ts_is_wall_anchored(tmp_path):
    path = tmp_path / "w.jsonl"
    rec = SpanRecorder(component="anchor", sample=1.0, path=str(path))
    t0 = time.time()
    rec.record(1, {"queue_wait": 0.001}, force=True)
    rec.close()
    line = json.loads(path.read_text().splitlines()[0])
    assert t0 - 1.0 <= line["ts"] <= time.time() + 1.0


# -- slow: end-to-end artifacts --------------------------------------------

@pytest.mark.slow
def test_merge_cli_over_router_and_two_backends(tmp_path):
    """Router + 2 backends as real processes; one `tracez merge` over
    the router's fleet /tracez yields a single Perfetto-loadable file
    with all three processes and backend serve spans nested inside
    router forward spans."""
    from paddle_tpu.inference.serve import read_reply, write_tensors

    paddle.seed(5)
    prefix = str(tmp_path / "net")
    paddle.jit.save(SmallNet(), prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []

    def spawn(args):
        p = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.inference.serve"] + args,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        procs.append(p)
        return p

    def ports(p, timeout=180.0):
        serve = metrics = None
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            line = p.stdout.readline()
            if not line:
                break
            if line.startswith("METRICS "):
                metrics = int(line.split()[1])
            elif line.startswith("SERVING "):
                serve = int(line.split()[1])
                return serve, metrics
        raise AssertionError(f"no SERVING line (rc={p.poll()})")

    try:
        b1 = spawn([prefix, "--port", "0", "--metrics-port", "0",
                    "--stats-interval", "0"])
        b2 = spawn([prefix, "--port", "0", "--metrics-port", "0",
                    "--stats-interval", "0"])
        p1, a1 = ports(b1)
        p2, a2 = ports(b2)
        router = spawn(["--router",
                        "--backend", f"127.0.0.1:{p1}:{a1}",
                        "--backend", f"127.0.0.1:{p2}:{a2}",
                        "--port", "0", "--metrics-port", "0"])
        pr, ar = ports(router)

        x = np.ones((2, 8), np.float32)
        for _ in range(8):
            with socket.create_connection(("127.0.0.1", pr)) as s:
                s.settimeout(60)
                write_tensors(s, [x])
                out, err = read_reply(s)
                assert err is None and out[0].shape == (2, 4)

        merged_path = tmp_path / "fleet.json"
        res = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability.tracez",
             "merge", f"http://127.0.0.1:{ar}/tracez",
             "-o", str(merged_path)],
            capture_output=True, text=True, timeout=120, env=env)
        assert res.returncode == 0, res.stderr
        doc = json.loads(merged_path.read_text())

        # all three processes present, each with a process_name record
        pids = {p["pid"] for p in doc["metadata"]["processes"]}
        assert len(pids) == 3
        named = {e["pid"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert pids <= named
        rows = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        ts = [e["ts"] for e in rows]
        assert ts == sorted(ts)                 # one monotonic timeline
        forwards = [e for e in rows if e["name"] == "router.forward"]
        serves = [e for e in rows if e["name"] == "serve.request"]
        assert len(forwards) >= 8 and len(serves) >= 8
        assert len({e["pid"] for e in serves}) == 2   # both backends hit
        # nesting: every backend serve span sits inside some router
        # forward span (2 ms tolerance for the two processes' anchors)
        tol = 2000.0
        for s in serves:
            assert any(
                f["ts"] - tol <= s["ts"] and
                s["ts"] + s["dur"] <= f["ts"] + f["dur"] + tol
                for f in forwards), s
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5)
