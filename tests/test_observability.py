"""Unified observability layer (paddle_tpu/observability/): exposition
goldens (escaping, cumulative buckets, +Inf, label ordering), concurrency
of the registry, the admin endpoint over a live socket (/metrics /healthz
/statusz — healthz flips to 503 on a killed dispatcher, scrapes compile
nothing), request-scoped spans (histogram sums ≈ request latency, JSONL
sampling, ids in error frames), the stall flight recorder, the hardened
device-memory probes, the reqs/s t1==t0 fix, and a lint over every
registered metric name/help."""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import profiler
from paddle_tpu.core import monitor
from paddle_tpu.inference.batching import DynamicBatcher
from paddle_tpu.observability import (REGISTRY, AdminServer, FlightRecorder,
                                      MetricsRegistry, SpanRecorder,
                                      capture_thread_stacks)
from paddle_tpu.observability.admin import CONTENT_TYPE_METRICS
from paddle_tpu.static import InputSpec


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 4)

    def forward(self, x):
        return self.fc(x)


@pytest.fixture(scope="module")
def mlp_prefix(tmp_path_factory):
    paddle.seed(3)
    prefix = str(tmp_path_factory.mktemp("obs") / "mlp")
    paddle.jit.save(SmallNet(), prefix,
                    input_spec=[InputSpec([None, 8], "float32")])
    return prefix


class FakePredictor:
    """Spec-compatible stand-in so batcher tests need no jax dispatch.
    run_fn(stacked) -> outputs; default: rowwise zeros of width 4."""

    def __init__(self, run_fn=None):
        self.run_fn = run_fn

    def input_specs(self):
        return [(("batch", 8), np.float32)]

    def output_specs(self):
        return [(("batch", 4), np.float32)]

    def run_batch(self, arrays):
        if self.run_fn is not None:
            return self.run_fn(arrays)
        return [np.zeros((arrays[0].shape[0], 4), np.float32)]


# -- exposition goldens ---------------------------------------------------

def test_counter_exposition_escaping_and_label_order():
    reg = MetricsRegistry()
    c = reg.counter("paddle_tpu_t_total", 'help \\ with\nnewline',
                    labelnames=("zz", "aa"))
    # kwargs order must NOT matter: declaration order wins in the output
    c.labels(aa='x"y', zz="p\\q").inc(3)
    text = reg.render()
    assert "# HELP paddle_tpu_t_total help \\\\ with\\nnewline" in text
    assert "# TYPE paddle_tpu_t_total counter" in text
    assert 'paddle_tpu_t_total{zz="p\\\\q",aa="x\\"y"} 3' in text
    assert text.endswith("\n")


def test_histogram_exposition_cumulative_buckets_inf():
    reg = MetricsRegistry()
    h = reg.histogram("paddle_tpu_lat_seconds", "Latency.",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    lines = reg.render().splitlines()
    assert 'paddle_tpu_lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'paddle_tpu_lat_seconds_bucket{le="1"} 2' in lines
    # +Inf bucket == _count (cumulative contract)
    assert 'paddle_tpu_lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "paddle_tpu_lat_seconds_count 3" in lines
    s = [ln for ln in lines if ln.startswith("paddle_tpu_lat_seconds_sum")]
    assert len(s) == 1 and float(s[0].split()[1]) == pytest.approx(5.55)


def test_registry_registration_idempotent_and_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("paddle_tpu_x_total", "X.")
    assert reg.counter("paddle_tpu_x_total", "X.") is a
    with pytest.raises(ValueError):
        reg.gauge("paddle_tpu_x_total", "now a gauge")
    with pytest.raises(ValueError):
        reg.counter("paddle_tpu_x_total", "X.", labelnames=("k",))
    with pytest.raises(ValueError):
        reg.counter("Bad-Name", "nope")
    with pytest.raises(ValueError):
        reg.counter("paddle_tpu_y_total", "   ")


def test_counter_monotonic_and_label_validation():
    reg = MetricsRegistry()
    c = reg.counter("paddle_tpu_c_total", "C.", labelnames=("k",))
    with pytest.raises(ValueError):
        c.labels(k="a").inc(-1)
    with pytest.raises(ValueError):
        c.labels(wrong="a")
    with pytest.raises(ValueError):
        c.inc()          # labeled family has no direct sample
    assert c.value(k="never_created") is None


def test_gauge_ops_and_flat():
    reg = MetricsRegistry()
    g = reg.gauge("paddle_tpu_g", "G.", labelnames=("d",))
    g.labels(d="0").set(5)
    g.labels(d="0").dec(2)
    g.labels(d="1").set_max(7)
    g.labels(d="1").set_max(3)      # high-water mark: stays 7
    flat = reg.flat()
    assert flat['paddle_tpu_g{d="0"}'] == 3
    assert flat['paddle_tpu_g{d="1"}'] == 7


def test_histogram_percentile_ceil_rank():
    reg = MetricsRegistry()
    h = reg.histogram("paddle_tpu_p_seconds", "P.", sample_cap=1000)
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(0.50) == 50.0
    assert h.percentile(0.95) == 95.0
    assert h.percentile(0.99) == 99.0
    assert h.percentile(1.0) == 100.0


def test_registry_concurrent_increments_exact():
    reg = MetricsRegistry()
    c = reg.counter("paddle_tpu_cc_total", "CC.", labelnames=("t",))
    h = reg.histogram("paddle_tpu_hh_seconds", "HH.", buckets=(0.5,))
    n_threads, per = 8, 5000

    def hammer(i):
        child = c.labels(t=str(i % 2))
        for _ in range(per):
            child.inc()
            h.observe(0.1)

    ts = [threading.Thread(target=hammer, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = sum(child.get() for _, child in c.samples())
    assert total == n_threads * per
    assert h.count == n_threads * per
    assert h.sum == pytest.approx(n_threads * per * 0.1)


def test_collector_refreshes_and_broken_collector_is_isolated():
    reg = MetricsRegistry()
    g = reg.gauge("paddle_tpu_up", "Up.")
    reg.add_collector(lambda: g.set(42))
    reg.add_collector(lambda: 1 / 0)
    assert "paddle_tpu_up 42" in reg.render()


# -- metric-name lint over the real registry ------------------------------

def test_all_registered_metrics_lint():
    """Every family in the process-global registry follows the naming
    convention and carries a non-empty help string — including the
    router span/poll, SLO, and decode families, which are
    force-registered here so the lint covers them even when no
    router/decode test ran first."""
    from paddle_tpu.inference.decode import (_decode_metrics,
                                             _handoff_metrics)
    from paddle_tpu.inference.router import _router_metrics
    from paddle_tpu.observability import SLOEngine, TimeSeriesStore
    from paddle_tpu.observability import memz  # noqa: F401 - registers

    _router_metrics()
    _decode_metrics()
    _handoff_metrics()
    SpanRecorder(component="router",
                 metric="paddle_tpu_router_span_seconds",
                 help="Router-side per-request span breakdown by stage, "
                      "seconds.")
    SpanRecorder(component="decode",
                 metric="paddle_tpu_decode_span_seconds",
                 help="Decode-side per-request span breakdown by stage, "
                      "seconds.")
    SLOEngine(TimeSeriesStore(), [])

    # Per-family conventions live in ONE place: the tpulint TPL051
    # implementation. This runtime pass covers dynamically-built names
    # the static scan cannot see.
    from paddle_tpu.analysis.catalog_drift import lint_metric_family

    metrics = REGISTRY.metrics()
    assert len(metrics) >= 15, [m.name for m in metrics]
    problems = [p for m in metrics
                for p in lint_metric_family(m.typename, m.name, m.help,
                                            m.labelnames)]
    assert not problems, problems
    names = {m.name for m in metrics}
    assert {"paddle_tpu_router_span_seconds",
            "paddle_tpu_router_poll_latency_seconds",
            "paddle_tpu_router_poll_failures_total",
            "paddle_tpu_router_backend_requests_total",
            "paddle_tpu_slo_state",
            "paddle_tpu_slo_burn_rate",
            "paddle_tpu_decode_tokens_total",
            "paddle_tpu_decode_steps_total",
            "paddle_tpu_decode_prefills_total",
            "paddle_tpu_decode_cache_evictions_total",
            "paddle_tpu_decode_slot_occupancy",
            "paddle_tpu_decode_active_requests",
            "paddle_tpu_decode_prefill_latency_seconds",
            "paddle_tpu_decode_step_latency_seconds",
            "paddle_tpu_decode_ttft_seconds",
            "paddle_tpu_decode_span_seconds",
            "paddle_tpu_handoff_exports_total",
            "paddle_tpu_handoff_imports_total",
            "paddle_tpu_handoff_rejects_total",
            "paddle_tpu_handoff_pages_total",
            "paddle_tpu_handoff_bytes_total",
            "paddle_tpu_handoff_seconds",
            "paddle_tpu_router_role_backends",
            "paddle_tpu_router_handoffs_total",
            "paddle_tpu_router_handoff_seconds",
            "paddle_tpu_mem_pages",
            "paddle_tpu_mem_tenant_pages",
            "paddle_tpu_mem_fragmentation",
            "paddle_tpu_mem_ghost_pages",
            "paddle_tpu_mem_ring_events",
            "paddle_tpu_mem_oom_dumps_total"} <= names, sorted(names)


# -- monitor shims + hardened memory probes -------------------------------

def test_stat_shims_registry_backed():
    monitor.stat_reset()
    monitor.stat_inc("obs_steps", 5)
    monitor.stat_set("obs_epoch", 2)
    assert monitor.stat_get("obs_steps") == 5
    assert monitor.all_stats()["obs_epoch"] == 2
    assert 'paddle_tpu_monitor_stat{name="obs_steps"} 5' in REGISTRY.render()
    monitor.stat_reset("obs_steps")
    assert monitor.stat_get("obs_steps", default=-1) == -1
    monitor.stat_reset()


def test_device_memory_stats_surface_backend_failure(monkeypatch):
    """A backend that cannot initialise raises (it must not read as a
    CPU with no stats); a device that cannot report stays empty."""
    import jax

    def boom():
        raise RuntimeError("backend exploded")

    monkeypatch.setattr(jax, "devices", boom)
    for probe in (monitor.device_memory_stats,
                  monitor.all_device_memory_stats, monitor.hbm_usage):
        with pytest.raises(RuntimeError, match="backend exploded"):
            probe()

    class BadDevice:
        def memory_stats(self):
            raise RuntimeError("no stats on this backend")

    assert monitor.device_memory_stats(BadDevice()) == {}
    assert monitor.hbm_usage(BadDevice()) == (0, 0)

    class NoneDevice:
        def memory_stats(self):
            return None          # CPU devices report None

    assert monitor.device_memory_stats(NoneDevice()) == {}


# -- serve_stats fix: reqs/s with a single resolution instant -------------

def test_serve_stats_reqs_per_s_not_zero_for_single_burst():
    profiler.reset_serve_stats()
    profiler.record_serve_batch(1, 1, 8, 8, 0)
    profiler.record_serve_requests([0.001])   # one instant: t1 == t0
    stats = profiler.serve_stats()
    assert stats["requests"] == 1
    assert stats["reqs_per_s"] is not None and stats["reqs_per_s"] > 0
    profiler.reset_serve_stats()


def test_serve_stats_reqs_per_s_zero_when_no_requests():
    profiler.reset_serve_stats()
    assert profiler.serve_stats()["reqs_per_s"] == 0.0


# -- spans ----------------------------------------------------------------

def test_span_recorder_deterministic_sampling():
    r = SpanRecorder(component="t", sample=0.0)
    assert not r.sampled(1)
    r = SpanRecorder(component="t", sample=1.0)
    assert r.sampled(1)
    r = SpanRecorder(component="t", sample=0.5)
    picks = [r.sampled(i) for i in range(1000)]
    assert picks == [r.sampled(i) for i in range(1000)]   # deterministic
    assert 300 < sum(picks) < 700                          # roughly rated


def test_batcher_spans_sum_to_latency_and_jsonl(tmp_path, monkeypatch):
    trace = tmp_path / "trace.jsonl"
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("PADDLE_TPU_TRACE_FILE", str(trace))
    fam = REGISTRY.get("paddle_tpu_serve_span_seconds")
    if fam is not None:
        fam.clear()

    def slow_run(arrays):
        time.sleep(0.05)
        return [np.zeros((arrays[0].shape[0], 4), np.float32)]

    b = DynamicBatcher(FakePredictor(slow_run), max_batch_size=4,
                       batch_timeout_ms=1.0)
    t0 = time.perf_counter()
    fut = b.submit([np.ones((1, 8), np.float32)])
    fut.result(timeout=30)
    latency = time.perf_counter() - t0
    b.stop()

    fam = REGISTRY.get("paddle_tpu_serve_span_seconds")
    stage_sums = {labels["stage"]: child.sum
                  for labels, child in fam.samples()}
    assert set(stage_sums) == {"queue_wait", "pad", "execute", "unpad"}
    total = sum(stage_sums.values())
    # spans cover enqueue->slice-back; the future-resolution hop adds a
    # little on top, so the sum is a lower bound within a loose margin
    assert total <= latency + 0.02
    assert total >= 0.05                       # at least the execute sleep
    assert total >= 0.5 * latency

    lines = [json.loads(ln) for ln in trace.read_text().splitlines()]
    assert len(lines) == 1
    line = lines[0]
    assert line["request_id"] == fut.request_id
    assert line["component"] == "serve"
    for k in ("queue_wait_s", "pad_s", "execute_s", "unpad_s", "total_s"):
        assert k in line
    assert line["total_s"] == pytest.approx(total, abs=5e-3)


def test_request_id_on_error_paths(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "0")
    b = DynamicBatcher(FakePredictor(), max_batch_size=4,
                       batch_timeout_ms=1.0)
    # validation failure: wrong arity — still tagged with a request id
    fut = b.submit([np.ones((1, 8), np.float32)] * 2)
    with pytest.raises(ValueError) as ei:
        fut.result(timeout=10)
    assert ei.value.request_id == fut.request_id > 0

    # model failure through the execute path
    def boom(arrays):
        raise RuntimeError("kernel exploded")

    b2 = DynamicBatcher(FakePredictor(boom), max_batch_size=4,
                        batch_timeout_ms=1.0)
    fut2 = b2.submit([np.ones((1, 8), np.float32)])
    with pytest.raises(RuntimeError) as ei2:
        fut2.result(timeout=10)
    assert ei2.value.request_id == fut2.request_id
    assert fut2.request_id != fut.request_id    # process-global id stream
    b2.stop()
    b.stop()
    # post-stop submits are tagged too
    fut3 = b.submit([np.ones((1, 8), np.float32)])
    with pytest.raises(RuntimeError):
        fut3.result(timeout=10)
    assert getattr(fut3, "request_id", 0) > 0


# -- flight recorder ------------------------------------------------------

def test_capture_thread_stacks_sees_this_thread():
    stacks = capture_thread_stacks()
    me = threading.current_thread()
    mine = [v for k, v in stacks.items() if str(me.ident) in k]
    assert mine and any("capture_thread_stacks" in ln or
                        "test_capture_thread_stacks" in ln
                        for ln in mine[0])


def test_flight_recorder_disabled_without_dump_dir(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_STALL_DUMP", raising=False)
    fr = FlightRecorder("t", busy_fn=lambda: True)
    assert not fr.enabled and fr._thread is None
    fr.stop()


def test_flight_recorder_dumps_once_per_stall(tmp_path):
    fr = FlightRecorder("unit", busy_fn=lambda: True,
                        context_fn=lambda: {"queue_depth": 3},
                        threshold_s=0.2, dump_dir=str(tmp_path),
                        poll_s=0.05)
    time.sleep(1.0)          # several polls past the threshold
    fr.stop()
    assert len(fr.dumps) == 1          # armed-once: one dump per stall
    payload = json.loads(open(fr.dumps[0]).read())
    assert payload["kind"] == "paddle_tpu_stall_dump"
    assert payload["label"] == "unit"
    assert payload["context"] == {"queue_depth": 3}
    assert payload["stalled_for_s"] >= 0.2
    assert payload["threads"]          # every live thread's stack
    assert any("paddle_tpu_" in k for k in payload["metrics"])


def test_flight_recorder_idle_is_not_a_stall(tmp_path):
    fr = FlightRecorder("idle", busy_fn=lambda: False,
                        threshold_s=0.1, dump_dir=str(tmp_path),
                        poll_s=0.03)
    time.sleep(0.5)
    fr.stop()
    assert fr.dumps == []


def test_stalled_batcher_produces_dump_with_thread_stacks(
        tmp_path, monkeypatch):
    """A predictor wedged mid-batch must produce a flight-recorder file
    naming the stuck thread and the queued request."""
    monkeypatch.setenv("PADDLE_TPU_STALL_DUMP", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_STALL_TIMEOUT", "0.3")
    monkeypatch.delenv("PADDLE_TPU_TRACE_SAMPLE", raising=False)
    release = threading.Event()

    def wedged(arrays):
        release.wait(timeout=30)     # simulates a hung device call
        return [np.zeros((arrays[0].shape[0], 4), np.float32)]

    b = DynamicBatcher(FakePredictor(wedged), max_batch_size=4,
                       batch_timeout_ms=1.0)
    fut = b.submit([np.ones((1, 8), np.float32)])
    deadline = time.monotonic() + 10
    while not b._recorder.dumps and time.monotonic() < deadline:
        time.sleep(0.05)
    release.set()
    fut.result(timeout=30)
    b.stop()
    assert b._recorder.dumps, "no stall dump written"
    payload = json.loads(open(b._recorder.dumps[0]).read())
    assert payload["label"] == "serve_batcher"
    assert payload["context"]["busy_batches"] == 1
    assert payload["context"]["dispatcher_alive"] is True
    stacks = json.dumps(payload["threads"])
    assert "wedged" in stacks          # the hung frame is in the dump
    assert "serve-dispatcher" in stacks


# -- admin endpoint (live socket) -----------------------------------------

def test_admin_server_standalone_routes():
    reg = MetricsRegistry()
    reg.counter("paddle_tpu_one_total", "One.").inc(7)
    state = {"ok": True}
    with AdminServer(port=0, registry=reg,
                     health_fn=lambda: (state["ok"],
                                        [] if state["ok"] else ["broken"]),
                     status_fn=lambda: {"engine": "test"}) as adm:
        base = f"http://127.0.0.1:{adm.port}"
        code, ctype, body = _get(base + "/metrics")
        assert code == 200 and ctype == CONTENT_TYPE_METRICS
        assert "paddle_tpu_one_total 7" in body

        code, _, body = _get(base + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

        state["ok"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["reasons"] == ["broken"]

        code, _, body = _get(base + "/statusz")
        st = json.loads(body)
        assert st["engine"] == "test" and "uptime_s" in st

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/nope")
        assert ei.value.code == 404


def test_admin_server_degrades_on_raising_callbacks():
    with AdminServer(port=0, registry=MetricsRegistry(),
                     health_fn=lambda: 1 / 0,
                     status_fn=lambda: 1 / 0) as adm:
        base = f"http://127.0.0.1:{adm.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/healthz")
        assert ei.value.code == 503
        code, _, body = _get(base + "/statusz")
        assert code == 200 and "status_error" in json.loads(body)


def test_serve_daemon_admin_endpoint_end_to_end(mlp_prefix):
    """InferenceServer with metrics_port=0: a scrape returns >= 15
    families with ZERO additional compiles, /statusz reports the engine
    and ladder, /healthz flips to 503 once the dispatcher dies."""
    from paddle_tpu.inference.serve import InferenceServer

    srv = InferenceServer(mlp_prefix, port=0, max_batch_size=4,
                          metrics_port=0)
    try:
        assert srv.metrics_port and srv.metrics_port != srv.port
        base = f"http://127.0.0.1:{srv.metrics_port}"
        fut = srv._batcher.submit([np.ones((1, 8), np.float32)])
        fut.result(timeout=60)

        compiles_before = len(profiler.compile_events())
        code, ctype, body = _get(base + "/metrics")
        assert code == 200 and ctype == CONTENT_TYPE_METRICS
        families = {ln.split()[2] for ln in body.splitlines()
                    if ln.startswith("# TYPE")}
        assert len(families) >= 15, sorted(families)
        assert "paddle_tpu_serve_requests_total" in families
        assert "paddle_tpu_serve_span_seconds" in families
        assert len(profiler.compile_events()) == compiles_before

        code, _, body = _get(base + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"

        _, _, body = _get(base + "/statusz")
        st = json.loads(body)
        assert st["engine"] == "batched"
        assert st["batcher"]["ladder"] == [1, 2, 4]
        assert st["serve"]["requests"] >= 1
        assert "device_memory" in st and "uptime_s" in st

        line = srv.stats_line()
        assert line.startswith("SERVE_STATS ")
        parsed = json.loads(line[len("SERVE_STATS "):])
        assert "ts_monotonic" in parsed and "queue_depth" in parsed

        # kill the dispatcher: the admin plane must stay up and report it
        srv._batcher.stop()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/healthz")
        assert ei.value.code == 503
        reasons = json.loads(ei.value.read())["reasons"]
        assert any("dispatcher" in r for r in reasons)
    finally:
        srv.stop()
    # stopped server: admin socket down
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _get(f"http://127.0.0.1:{srv.metrics_port}/healthz", timeout=2)


def test_serve_daemon_metrics_off_by_default(mlp_prefix, monkeypatch):
    from paddle_tpu.inference.serve import InferenceServer

    monkeypatch.delenv("PADDLE_TPU_METRICS_PORT", raising=False)
    srv = InferenceServer(mlp_prefix, port=0, max_batch_size=4)
    try:
        assert srv.metrics_port is None and srv._admin is None
    finally:
        srv.stop()


# -- training-side MetricsLogger ------------------------------------------

def test_metrics_logger_jsonl(tmp_path):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.hapi import MetricsLogger, Model
    from paddle_tpu.io import TensorDataset

    paddle.seed(0)

    class Reg(nn.Layer):
        def __init__(self):
            super().__init__()
            self.net = nn.Linear(8, 1)

        def forward(self, x, y):
            return ((self.net(x) - y) ** 2).mean()

    model = Model(Reg(), inputs=[InputSpec([None, 8], "float32"),
                                 InputSpec([None, 1], "float32")])
    model.prepare(opt.SGD(learning_rate=1e-2,
                          parameters=model.parameters()))
    rng = np.random.default_rng(0)
    ds = TensorDataset([rng.normal(size=(16, 8)).astype(np.float32),
                        rng.normal(size=(16, 1)).astype(np.float32)])
    path = tmp_path / "train_metrics.jsonl"
    model.fit(ds, batch_size=4, epochs=2, verbose=0, shuffle=False,
              callbacks=[MetricsLogger(log_freq=2, path=str(path))])
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines, "no telemetry emitted"
    steps = [ln for ln in lines if ln["event"] == "step"]
    epochs = [ln for ln in lines if ln["event"] == "epoch_end"]
    assert len(epochs) == 2
    for ln in steps:
        assert {"ts_monotonic", "steps_per_s", "loss",
                "step", "epoch"} <= set(ln)
    # async pipeline stats ride along when the window is on
    pipe = model._async_pipeline
    if pipe is not None:
        assert "host_blocked_s" in lines[-1]
        assert "steps_submitted" in lines[-1]
        # fit() closed the stall watchdog on exit
        assert pipe._recorder._thread is None \
            or not pipe._recorder._thread.is_alive()


# -- trace wire interop (PDI1 <-> PDI2) -----------------------------------

def _dial(port):
    import socket
    s = socket.create_connection(("127.0.0.1", port))
    s.settimeout(60)
    return s


def test_wire_interop_legacy_and_traced_clients(mlp_prefix, monkeypatch):
    """One server, both dialects: a PDI1 client must get byte-exact
    legacy frames back (old clients never see PDI2), while a PDI2
    client's context comes back with the backend's ids and spans."""
    from paddle_tpu.inference.serve import (InferenceServer,
                                            read_reply_ctx, write_tensors)

    monkeypatch.delenv("PADDLE_TPU_TRACE_SAMPLE", raising=False)
    srv = InferenceServer(mlp_prefix, port=0, max_batch_size=4,
                          metrics_port=0)
    x = np.ones((2, 8), np.float32)
    try:
        # old client: no ctx out, no ctx back — reply is a PDI1 frame
        with _dial(srv.port) as s:
            write_tensors(s, [x])
            out, err, ctx = read_reply_ctx(s)
            assert err is None and ctx is None
            assert out[0].shape == (2, 4)

        # new client: trace id echoed, backend id + span breakdown attached
        with _dial(srv.port) as s:
            write_tensors(s, [x], ctx={"trace_id": 777})
            out, err, ctx = read_reply_ctx(s)
            assert err is None and out[0].shape == (2, 4)
            assert ctx["trace_id"] == 777
            assert ctx["request_id"] > 0
            assert {"queue_wait_s", "pad_s", "execute_s",
                    "unpad_s"} <= set(ctx["spans"])
            # the breakdown is wall time, not placeholders
            assert all(v >= 0.0 for v in ctx["spans"].values())

        # both dialects interleave on ONE connection: the reply dialect
        # follows each request, not the connection
        with _dial(srv.port) as s:
            write_tensors(s, [x], ctx={"trace_id": 1})
            _, _, ctx1 = read_reply_ctx(s)
            write_tensors(s, [x])
            _, _, ctx2 = read_reply_ctx(s)
            write_tensors(s, [x], ctx={"trace_id": 3})
            _, _, ctx3 = read_reply_ctx(s)
            assert ctx1["trace_id"] == 1 and ctx2 is None
            assert ctx3["trace_id"] == 3
            assert ctx3["request_id"] > ctx1["request_id"]

        # capability is advertised so routers know to forward contexts
        _, _, body = _get(f"http://127.0.0.1:{srv.metrics_port}/statusz")
        assert json.loads(body)["trace_wire"] is True
    finally:
        srv.stop()


def test_wire_error_frames_carry_trace_context(mlp_prefix, monkeypatch):
    """A traced request that fails must come back as a PDI2 ERROR frame
    with the context attached (trace id + the failing request's id), so
    the router can finish the trace; an untraced failure stays PDI1."""
    from paddle_tpu.inference.serve import (InferenceServer,
                                            read_reply_ctx, write_tensors)

    monkeypatch.delenv("PADDLE_TPU_TRACE_SAMPLE", raising=False)
    srv = InferenceServer(mlp_prefix, port=0, max_batch_size=4)
    x = np.ones((1, 8), np.float32)
    try:
        with _dial(srv.port) as s:       # wrong arity: typed error
            write_tensors(s, [x, x], ctx={"trace_id": 555})
            out, err, ctx = read_reply_ctx(s)
            assert out is None and err is not None
            assert ctx["trace_id"] == 555
            assert ctx.get("request_id", 0) > 0

        with _dial(srv.port) as s:       # legacy client, same failure
            write_tensors(s, [x, x])
            out, err, ctx = read_reply_ctx(s)
            assert out is None and err is not None and ctx is None
    finally:
        srv.stop()


def test_garbage_trace_context_does_not_fail_the_request(mlp_prefix):
    """A PDI2 frame whose ctx bytes are not JSON must degrade to an
    empty context, not kill the connection — trust the tensor payload,
    never the metadata."""
    import struct

    from paddle_tpu.inference.serve import (MAGIC_TRACE, InferenceServer,
                                            read_reply_ctx)

    srv = InferenceServer(mlp_prefix, port=0, max_batch_size=4)
    x = np.ones((1, 8), np.float32)
    try:
        with _dial(srv.port) as s:
            garbage = b"\xff\xfenot json at all"
            s.sendall(struct.pack("<II", MAGIC_TRACE, 1)
                      + struct.pack("<I", len(garbage)) + garbage
                      + struct.pack("<BB", 0, 2)
                      + struct.pack("<2q", 1, 8) + x.tobytes())
            out, err, ctx = read_reply_ctx(s)
            assert err is None and out[0].shape == (1, 4)
            assert ctx is not None       # still a PDI2 reply
    finally:
        srv.stop()


def test_trace_jsonl_schema_stable_across_ok_and_error(
        tmp_path, monkeypatch):
    """The JSONL trace schema is a contract: ok lines and error lines
    share the core keys (component, request_id, stage spans, total_s),
    errors add the exception name — and the stage sum stays within the
    observed wall latency on both paths."""
    trace = tmp_path / "schema.jsonl"
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1.0")
    monkeypatch.setenv("PADDLE_TPU_TRACE_FILE", str(trace))

    b = DynamicBatcher(FakePredictor(), max_batch_size=4,
                       batch_timeout_ms=1.0)
    t0 = time.perf_counter()
    fut = b.submit([np.ones((1, 8), np.float32)])
    fut.result(timeout=30)
    ok_wall = time.perf_counter() - t0
    b.stop()

    def boom(arrays):
        raise RuntimeError("kernel exploded")

    b2 = DynamicBatcher(FakePredictor(boom), max_batch_size=4,
                        batch_timeout_ms=1.0)
    fut2 = b2.submit([np.ones((1, 8), np.float32)])
    with pytest.raises(RuntimeError):
        fut2.result(timeout=30)
    b2.stop()

    lines = [json.loads(ln) for ln in trace.read_text().splitlines()]
    assert len(lines) == 2
    ok_line = next(ln for ln in lines if "error" not in ln)
    err_line = next(ln for ln in lines if "error" in ln)
    for line in (ok_line, err_line):
        assert line["component"] == "serve"
        assert line["request_id"] > 0
        assert "total_s" in line and line["total_s"] >= 0
        span_keys = [k for k in line
                     if k.endswith("_s") and k != "total_s"]
        assert span_keys, line
        assert sum(line[k] for k in span_keys) \
            == pytest.approx(line["total_s"], abs=5e-6)
    assert ok_line["request_id"] == fut.request_id
    assert ok_line["total_s"] <= ok_wall + 0.02
    assert {"queue_wait_s", "pad_s", "execute_s",
            "unpad_s"} <= set(ok_line)
    assert err_line["request_id"] == fut2.request_id
    assert err_line["error"] == "RuntimeError"
