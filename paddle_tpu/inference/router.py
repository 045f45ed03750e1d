"""Health-aware front router: one wire-protocol address over a serve fleet.

A single ``InferenceServer`` is one process on one host — restart it and
every client sees connection errors until it is back. The router is the
resilience layer on top: it speaks the same wire protocol as a backend
(``serve.py`` frames in, frames out), so C/Go clients point at the
router unchanged, and behind it N backend daemons come, go, drain and
crash without a client ever losing a request silently.

What the router does per request:

* **Health-weighted routing** — a poll thread hits each backend's admin
  plane (``/healthz`` for liveness + draining, ``/statusz`` for
  ``queue_depth`` / ``oldest_wait_s``) every ``poll_interval`` seconds;
  requests go to the routable backend with the lowest load score
  (router-side in-flight + reported queue depth + wedge penalty).
  Backends without an admin port degrade to a TCP dial probe.
* **Circuit breaking** — a :class:`~paddle_tpu.utils.retry.CircuitBreaker`
  per backend trips OPEN after consecutive wire failures, so a dead
  backend costs one connect timeout, not one per request; after
  ``reset_timeout`` one half-open probe request re-tests it.
* **Bounded failover** — inference requests are idempotent, so a wire
  failure (or a typed ``UNAVAILABLE`` frame from a dying backend) is
  retried on the next-best backend — but every failover spends from a
  shared :class:`~paddle_tpu.utils.retry.RetryBudget`, so fleet-wide
  outage cannot amplify into a retry storm: when the budget is empty
  the client gets a fast typed ``UNAVAILABLE`` frame instead.
* **Load shedding** — when every routable backend is past the
  ``shed_watermark`` queue depth (or the router's own per-backend
  in-flight cap), the request is refused immediately with a typed
  ``RESOURCE_EXHAUSTED`` frame. Deterministic model errors
  (``INVALID_ARGUMENT``, ``INTERNAL``, ``DEADLINE_EXCEEDED``) are
  relayed verbatim, never failed over.
* **Drain awareness** — a backend whose /healthz says "draining"
  (SIGTERM was delivered; it is finishing in-flight work) is routed
  around within one poll interval; the router itself drains the same
  way (``drain()`` / SIGTERM in ``main_router``).
* **Stream-aware decode proxy** — a PDI2 request whose context carries
  a ``decode`` field leaves the one-reply fast path: the router relays
  the backend's seq-numbered token frames while recording every emitted
  token, and a backend dying mid-stream is *resumed* on another backend
  as ``prompt + tokens_emitted_so_far`` — greedy decode is
  deterministic and sampled decode carries a per-stream seed, so the
  client sees one gapless, duplicate-free, token-identical stream
  (``_handle_stream``; chaos site ``router.stream_relay``). PDI2
  decode requests must carry the ``decode`` context field (the
  ``decode_request`` helper always does); a bare PDI2 decode frame
  would be mis-relayed as a one-reply exchange.
* **Dynamic membership** — ``watch_membership`` follows a
  ``distributed/store`` registry (TCPStore in production, FileStore in
  tests): backends publish TTL'd heartbeat keys at startup and a
  "left" record at drain, and the watcher calls ``add_backend`` /
  ``remove_backend`` live — fleet joins/leaves need no supervisor
  edits and no router restart.

``BackendSupervisor`` optionally owns the fleet: ``--fleet N`` spawns N
``serve.py`` daemons from the model prefix, restarts dead ones with
bounded backoff (all resolving the same persistent compile-cache
directory, so a restarted backend warms from it), and swaps them into
the routing table live. On a TPU host each backend is pinned to one
chip and the router process itself never initialises a JAX backend.

Chaos site ``router.forward`` fires once per backend attempt, so tests
inject wire failures between router and backend deterministically
(see tests/test_serve_chaos.py and docs/fault_tolerance.md).

    python -m paddle_tpu.inference.serve /path/prefix --router --fleet 3 \
        --port 9000 --warmup

All ``paddle_tpu_router_*`` metric families land in the shared registry
and are served from the router's own admin plane (``--metrics-port``),
which also mounts ``/varz`` (windowed time-series history) and
``/alertz`` (SLO burn-rate verdicts). Observability feeds back into
routing: the poll thread reads each backend's ``/alertz`` and a backend
whose SLOs are firing is demoted in the load score before it ever goes
unhealthy. Requests carrying a PDI2 trace context (or sampled by
``PADDLE_TPU_TRACE_SAMPLE``) are forwarded with the context to
trace-capable backends and assembled into one JSONL line per request:
router stages (pick / forward / reply) plus the backend's relayed
queue_wait / pad / execute / unpad breakdown (docs/observability.md).
"""
from __future__ import annotations

import collections
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time
from http.client import HTTPConnection

import numpy as np

from ..core import flags as _flags
from ..core.place import local_tpu_chip_count, single_chip_env
from ..observability import (FlightRecorder, SLOEngine, SpanRecorder,
                             TimeSeriesStore, next_request_id,
                             request_id_base, router_objectives)
from ..observability import tracez as _tracez
from ..testing import chaos
from ..utils.retry import CircuitBreaker, RetryBudget, backoff_delays
from .errors import (ERR_INVALID_ARGUMENT, ERR_RESOURCE_EXHAUSTED,
                     ERR_UNAVAILABLE, RETRYABLE_CODES, TypedServeError,
                     error_code)
from .serve import (read_reply_ctx, read_request, write_error,
                    write_tensors)

__all__ = ["Backend", "ServeRouter", "BackendSupervisor", "parse_backend",
           "main_router"]

_BREAKER_STATE_CODE = {CircuitBreaker.CLOSED: 0,
                       CircuitBreaker.HALF_OPEN: 1,
                       CircuitBreaker.OPEN: 2}


class _RerouteShed(Exception):
    """Internal: a backend answered RESOURCE_EXHAUSTED before any token
    was relayed — unwind the stream attempt and reroute to a sibling
    without counting a breaker failure (the backend answered; it is
    saturated, not broken)."""


class _HandoffFailed(Exception):
    """Internal: a prefill->decode KV handoff could not complete (typed
    refusal, malformed reply, wire failure). Never fatal — the stream
    degrades to a plain re-prefill on its decode worker, which is
    token-identical (docs/serving.md)."""


def _router_metrics():
    """Register (idempotently) and return the paddle_tpu_router_* metric
    families. Catalogued in docs/observability.md."""
    from ..observability import counter, gauge, histogram
    return {
        "requests": counter(
            "paddle_tpu_router_requests_total",
            "Requests answered by the router, by outcome (ok, "
            "relayed_error, shed, unavailable, malformed)", ("outcome",)),
        "failovers": counter(
            "paddle_tpu_router_failovers_total",
            "Requests retried on another backend after a wire failure "
            "or typed UNAVAILABLE frame"),
        "budget_denied": counter(
            "paddle_tpu_router_retry_budget_denied_total",
            "Failovers refused because the shared retry budget was "
            "empty (the anti-retry-storm valve)"),
        "shed": counter(
            "paddle_tpu_router_shed_total",
            "Requests refused with RESOURCE_EXHAUSTED because every "
            "routable backend was past the shed watermark"),
        "backend_up": gauge(
            "paddle_tpu_router_backend_up",
            "1 while the backend's last health poll was healthy",
            ("backend",)),
        "breaker_state": gauge(
            "paddle_tpu_router_breaker_state",
            "Per-backend circuit breaker state "
            "(0 closed, 1 half-open, 2 open)", ("backend",)),
        "backend_queue": gauge(
            "paddle_tpu_router_backend_queue_depth",
            "Backend queue depth from its last /statusz poll",
            ("backend",)),
        "inflight": gauge(
            "paddle_tpu_router_inflight",
            "Requests currently being routed (read off a client and "
            "not yet answered)"),
        "latency": histogram(
            "paddle_tpu_router_request_latency_seconds",
            "Router-side request latency (client read to reply write)",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0, 10.0, 30.0), sample_cap=2048),
        "failover_latency": histogram(
            "paddle_tpu_router_failover_latency_seconds",
            "Extra latency a failed-over request paid: first backend "
            "failure to final reply",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0, 10.0), sample_cap=2048),
        "backend_restarts": counter(
            "paddle_tpu_router_backend_restarts_total",
            "Dead fleet backends respawned by the supervisor"),
        "backend_requests": counter(
            "paddle_tpu_router_backend_requests_total",
            "Forward attempts per backend (failovers count once per "
            "backend tried)", ("backend",)),
        "poll_latency": histogram(
            "paddle_tpu_router_poll_latency_seconds",
            "Health-poll round-trip per backend (healthz + statusz + "
            "alertz, or the TCP dial fallback)",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5), sample_cap=1024),
        "poll_failures": counter(
            "paddle_tpu_router_poll_failures_total",
            "Health polls that failed outright (dial refused, admin "
            "unreachable, poll raised), per backend", ("backend",)),
        "stream_active": gauge(
            "paddle_tpu_router_stream_active",
            "Decode streams currently being relayed through the router"),
        "stream_failovers": counter(
            "paddle_tpu_router_stream_failovers_total",
            "Decode streams re-issued to another backend after a "
            "mid-stream wire failure or typed UNAVAILABLE frame"),
        "stream_resumed_tokens": counter(
            "paddle_tpu_router_stream_resumed_tokens_total",
            "Tokens already emitted that were carried into a resume "
            "re-issue (prompt + tokens so far) across stream failovers"),
        "stream_lost": counter(
            "paddle_tpu_router_stream_lost_total",
            "Decode streams the router could not complete or resume "
            "(client got a typed UNAVAILABLE instead of a done frame)"),
        "membership_backends": gauge(
            "paddle_tpu_router_membership_backends",
            "Live members in the membership registry at the last "
            "watcher poll"),
        "membership_events": counter(
            "paddle_tpu_router_membership_events_total",
            "Routing-table updates driven by the membership watcher, "
            "by event (join, leave)", ("event",)),
        "reroutes": counter(
            "paddle_tpu_router_reroutes_total",
            "Requests rerouted to a sibling after one backend answered "
            "RESOURCE_EXHAUSTED at its own admission watermark "
            "(one-shot, spends from the shared retry budget; shed is "
            "terminal only when every backend is saturated)"),
        "tenant_shed": counter(
            "paddle_tpu_router_tenant_shed_total",
            "Requests refused at the router because the tenant was at "
            "its PADDLE_TPU_ROUTER_TENANT_MAX_INFLIGHT cap; never "
            "counted against fleet availability", ("tenant",)),
        "tenant_inflight": gauge(
            "paddle_tpu_router_tenant_inflight",
            "Requests currently being routed, per tenant", ("tenant",)),
        "role_backends": gauge(
            "paddle_tpu_router_role_backends",
            "Routable backends by advertised serving-topology role "
            "(unified, prefill, decode; docs/serving.md)", ("role",)),
        "handoffs": counter(
            "paddle_tpu_router_handoffs_total",
            "Prefill->decode KV handoffs orchestrated for routed "
            "streams, by outcome: 'ok' landed the pages on the decode "
            "worker, 'fallback' degraded to a plain re-prefill there "
            "(compat refusal, wire failure, or chaos)", ("outcome",)),
        "handoff_latency": histogram(
            "paddle_tpu_router_handoff_seconds",
            "Wall time of one orchestrated KV handoff: prefill-worker "
            "export round-trip plus shipping the pages to the decode "
            "worker and its ack",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0, 10.0), sample_cap=2048),
    }


class Backend:
    """One backend daemon in the routing table: its address, the last
    health-poll verdict, a circuit breaker, and router-side in-flight
    accounting. Health fields are written by the poll thread and read by
    the routing path; all mutation goes through ``update_health`` /
    ``begin``/``end`` under the backend's lock."""

    def __init__(self, host: str, port: int, admin_port: int = None,
                 breaker: CircuitBreaker = None):
        self.host = host
        self.port = int(port)
        self.admin_port = int(admin_port) if admin_port is not None \
            else None
        self.key = f"{host}:{self.port}"
        self.breaker = breaker or CircuitBreaker(failure_threshold=3,
                                                 reset_timeout=2.0)
        self._lock = threading.Lock()
        # optimistic until the first poll: a just-added backend must be
        # routable immediately (the poll loop demotes it within one tick)
        self.healthy = True
        self.health_reasons = []
        self.draining = False
        self.queue_depth = 0
        self.oldest_wait_s = 0.0
        self.last_poll_s = None
        self.polls_failed = 0
        self.inflight = 0
        # does the backend speak the PDI2 trace-context frames? learned
        # from /statusz ("trace_wire": true); False until proven, so a
        # mixed fleet of old and new backends interops (old backends
        # simply never see a trace context)
        self.trace_wire = False
        # the backend's own /alertz verdict ("ok" / "warning" /
        # "firing"); a burning backend is demoted in score() so traffic
        # shifts away BEFORE it goes fully unhealthy
        self.alert_state = "ok"
        # serving-topology role + KV-compat facts from the membership
        # meta (docs/serving.md): "unified" until advertised otherwise,
        # so a meta-less fleet keeps today's routing byte-identical
        self.role = "unified"
        self.page_tokens = None
        self.kv_dtype = None
        self.fingerprint = None

    def set_meta(self, meta: dict):
        """Apply a membership meta dict (role + KV-compat facts)."""
        meta = meta or {}
        with self._lock:
            role = str(meta.get("role") or "unified").lower()
            self.role = role if role in ("unified", "prefill",
                                         "decode") else "unified"
            self.page_tokens = meta.get("page_tokens")
            self.kv_dtype = meta.get("kv_dtype")
            self.fingerprint = meta.get("fingerprint")

    def kv_compat(self) -> dict:
        with self._lock:
            return {"page_tokens": self.page_tokens,
                    "kv_dtype": self.kv_dtype,
                    "fingerprint": self.fingerprint}

    # score() demotion per /alertz state: warning nudges traffic away,
    # firing is worth ~50 queued requests — routed around unless every
    # other backend is worse
    _ALERT_PENALTY = {"ok": 0.0, "warning": 5.0, "firing": 50.0}

    def update_health(self, healthy: bool, reasons=(), draining=False,
                      queue_depth: int = None, oldest_wait_s: float = None,
                      trace_wire: bool = None, alert_state: str = None):
        with self._lock:
            self.healthy = bool(healthy)
            self.health_reasons = list(reasons)
            self.draining = bool(draining)
            if queue_depth is not None:
                self.queue_depth = int(queue_depth)
            if oldest_wait_s is not None:
                self.oldest_wait_s = float(oldest_wait_s)
            if trace_wire is not None:
                self.trace_wire = bool(trace_wire)
            if alert_state in self._ALERT_PENALTY:
                self.alert_state = alert_state
            self.last_poll_s = time.monotonic()
            self.polls_failed = 0 if healthy else self.polls_failed + 1

    def begin(self):
        with self._lock:
            self.inflight += 1

    def end(self):
        with self._lock:
            self.inflight -= 1

    def score(self) -> float:
        """Load score for least-loaded routing: cheap requests go where
        the combined router-side in-flight + backend queue is smallest;
        a wedging queue (old oldest_wait_s) is penalized hard, and a
        backend whose own SLOs are burning is demoted (warning +5,
        firing +50) so the alert feeds back into routing."""
        with self._lock:
            return (self.inflight + self.queue_depth
                    + 10.0 * self.oldest_wait_s
                    + self._ALERT_PENALTY.get(self.alert_state, 0.0))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "key": self.key,
                "admin_port": self.admin_port,
                "healthy": self.healthy,
                "reasons": list(self.health_reasons),
                "draining": self.draining,
                "queue_depth": self.queue_depth,
                "oldest_wait_s": round(self.oldest_wait_s, 3),
                "inflight": self.inflight,
                "breaker": self.breaker.state,
                "trace_wire": self.trace_wire,
                "alert_state": self.alert_state,
                "polls_failed": self.polls_failed,
                "role": self.role,
                "kv_compat": {"page_tokens": self.page_tokens,
                              "kv_dtype": self.kv_dtype,
                              "fingerprint": self.fingerprint},
            }


def parse_backend(spec: str) -> Backend:
    """``HOST:PORT`` or ``HOST:PORT:ADMIN_PORT`` -> :class:`Backend`."""
    parts = spec.rsplit(":", 2)
    try:
        if len(parts) == 3 and parts[0]:
            # HOST:PORT:ADMIN — but HOST:PORT alone also splits in two;
            # disambiguate by whether the first part parses as a port
            try:
                host, port, admin = parts[0], int(parts[1]), int(parts[2])
                return Backend(host, port, admin)
            except ValueError:
                pass
        host, port = spec.rsplit(":", 1)
        return Backend(host, int(port))
    except (ValueError, IndexError):
        raise ValueError(
            f"backend spec {spec!r}: want HOST:PORT[:ADMIN_PORT]")


class ServeRouter:
    """Wire-protocol front router over a set of :class:`Backend`\\ s.

    Accepts client connections exactly like ``InferenceServer`` (same
    framing, same keep-alive loop), but instead of running a model it
    picks a backend, relays the request, and relays the reply — with
    health-weighted selection, circuit-breaker failover, retry
    budgeting, load shedding and drain support (class docstring above,
    and docs/fault_tolerance.md for the full state machine).
    """

    def __init__(self, backends, port: int = 0, host: str = "127.0.0.1",
                 poll_interval: float = 0.5, shed_watermark: int = 64,
                 failover_retries: int = 2, forward_timeout: float = 130.0,
                 connect_timeout: float = 2.0, idle_timeout: float = None,
                 metrics_port: int = None, retry_budget: RetryBudget = None,
                 max_inflight_per_backend: int = 256,
                 stream_retries: int = None):
        self._backends = list(backends)
        self._block = threading.Lock()          # routing-table lock
        self._poll_interval = float(poll_interval)
        self._watermark = int(shed_watermark)
        self._failover_retries = max(int(failover_retries), 0)
        self._stream_retries = max(int(
            _flags.env_value("PADDLE_TPU_ROUTER_STREAM_RETRIES")
            if stream_retries is None else stream_retries), 0)
        self._forward_timeout = forward_timeout
        self._connect_timeout = float(connect_timeout)
        self._idle_timeout = float(idle_timeout) if idle_timeout else None
        self._budget = retry_budget or RetryBudget()
        self._max_inflight = max(int(max_inflight_per_backend), 1)
        # multi-tenant isolation: a per-tenant in-flight cap (0 = off)
        # and per-tenant retry budgets so one tenant's failure storm
        # cannot drain the shared budget or trip fleet-wide alerts
        self._tenant_max_inflight = max(int(_flags.env_value(
            "PADDLE_TPU_ROUTER_TENANT_MAX_INFLIGHT") or 0), 0)
        self._tenant_inflight = {}              # tenant -> in-flight
        self._tenant_budgets = {}               # tenant -> RetryBudget
        self._local = threading.local()         # per-thread conn cache
        # every thread's cache dict, so remove_backend can purge a dead
        # backend's sockets fleet-wide, not just the calling thread's
        self._conn_caches = {}                  # thread -> cache dict
        self._conn_caches_lock = threading.Lock()
        # dynamic membership (watch_membership): watcher + bookkeeping
        self._membership = None
        self._membership_thread = None
        self._membership_interval = None
        self._member_keys = set()
        self._rr = 0                            # tie-break rotation
        self._m = _router_metrics()
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._t0 = time.monotonic()
        self._ring = _tracez.RING
        # router-side trace assembly: its own stage histogram family
        # (pick / forward / reply + the backend_* breakdown relayed over
        # the wire), same JSONL sink and sampling gate as the backends
        self._spans = SpanRecorder(
            component="router",
            metric="paddle_tpu_router_span_seconds",
            help="Router-side per-request span breakdown by stage "
                 "(pick, forward, reply, plus relayed backend_* "
                 "stages), seconds.")
        # stall watchdog: busy while a client request is in flight; the
        # forward loop beats after every answered request
        self._recorder = FlightRecorder(
            "serve_router",
            busy_fn=lambda: self.inflight_requests > 0,
            context_fn=self._stall_context)

        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(128)
        self.port = self._srv.getsockname()[1]
        self.host = host
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               daemon=True,
                                               name="router-accept")
        self._accept_thread.start()
        self._poll_thread = threading.Thread(target=self._poll_loop,
                                             daemon=True,
                                             name="router-health-poll")
        self._poll_thread.start()

        self._admin = None
        self.metrics_port = None
        self._varz = None
        self._slo = None
        if metrics_port is not None and int(metrics_port) >= 0:
            from ..observability import (AdminServer,
                                         install_default_collectors)
            # no HBM collector: the router owns no device, and a scrape
            # that called jax.devices() here would take the chips its
            # backends need
            install_default_collectors(hbm=False)
            self._varz = TimeSeriesStore()
            self._varz.start()
            self._slo = SLOEngine(self._varz, router_objectives())
            self._admin = AdminServer(port=int(metrics_port), host=host,
                                      health_fn=self._health,
                                      status_fn=self._status,
                                      varz_fn=self._varz.varz,
                                      alertz_fn=self._slo.alertz,
                                      tracez_fn=self._fleet_tracez,
                                      memz_fn=self._fleet_memz)
            self.metrics_port = self._admin.port

    # -- routing table ---------------------------------------------------

    def backends(self):
        with self._block:
            return list(self._backends)

    def add_backend(self, backend: Backend) -> Backend:
        with self._block:
            self._backends.append(backend)
        return backend

    def remove_backend(self, key: str):
        with self._block:
            self._backends = [b for b in self._backends if b.key != key]
        # purge the removed backend's cached keep-alive sockets in EVERY
        # thread, not just this one — a backend re-added on the same
        # host:port must never inherit a half-dead socket from a thread
        # that had no request in between. dict.pop is atomic under the
        # GIL; the owning thread sees a miss and dials fresh, and a
        # socket closed mid-request surfaces as a wire failure the
        # failover loop already handles.
        dead = []
        with self._conn_caches_lock:
            for t in [t for t in self._conn_caches if not t.is_alive()]:
                dead.extend(self._conn_caches.pop(t).values())
            caches = list(self._conn_caches.values())
        for cache in caches:
            dead.append(cache.pop(key, None))
        for s in dead:
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        # drop the dead backend's per-backend samples so /metrics does
        # not advertise an address that no longer exists
        for fam in ("backend_up", "breaker_state", "backend_queue",
                    "poll_failures", "backend_requests"):
            self._m[fam].remove(backend=key)

    # -- dynamic membership ----------------------------------------------

    def watch_membership(self, store, group: str = "serve", ttl=None,
                         interval: float = None):
        """Follow a ``distributed/store`` membership registry: backends
        publishing into ``group`` (see ``membership.MembershipPublisher``)
        are added to the routing table on join and removed on clean
        leave or heartbeat expiry — no supervisor edits, no router
        restart. ``store`` is a :class:`Store` instance or an endpoint
        string (``HOST:PORT`` for TCPStore, else a FileStore path).
        Statically configured backends are never membership-removed."""
        from ..distributed.store.membership import MembershipWatcher
        from ..distributed.store.membership import connect as _store_connect
        if isinstance(store, str):
            store = _store_connect(store)
        ttl = float(_flags.env_value("PADDLE_TPU_MEMBERSHIP_TTL")
                    if ttl is None else ttl)
        self._membership = MembershipWatcher(store, group=group, ttl=ttl)
        self._membership_interval = float(interval or self._poll_interval)
        self._membership_thread = threading.Thread(
            target=self._membership_loop, daemon=True,
            name="router-membership")
        self._membership_thread.start()
        return self._membership

    def _membership_loop(self):
        while not self._stop.is_set():
            try:
                live = self._membership.poll()
            except Exception:
                live = None      # store unreachable: keep current table
            if live is not None:
                current = {b.key for b in self.backends()}
                for key, rec in live.items():
                    if key in current:
                        continue
                    host, port = key.rsplit(":", 1)
                    b = Backend(host, int(port), rec.get("admin_port"))
                    if rec.get("meta"):
                        # role + KV-compat facts ride the slot record
                        # (docs/serving.md): a prefill worker is pulled
                        # out of general rotation the moment it joins
                        b.set_meta(rec["meta"])
                    self.add_backend(b)
                    self._member_keys.add(key)
                    self._m["membership_events"].labels(event="join").inc()
                for key in list(self._member_keys):
                    if key not in live:
                        self.remove_backend(key)
                        self._member_keys.discard(key)
                        self._m["membership_events"].labels(
                            event="leave").inc()
                self._m["membership_backends"].set(len(live))
            self._stop.wait(self._membership_interval)

    # -- health polling --------------------------------------------------

    def _poll_loop(self):
        while not self._stop.is_set():
            for b in self.backends():
                t0 = time.perf_counter()
                try:
                    self._poll_backend(b)
                except Exception as e:   # a poll bug must not kill polls
                    b.update_health(False, [f"poll raised: {e!r}"])
                    self._m["poll_failures"].labels(backend=b.key).inc()
                self._m["poll_latency"].observe(time.perf_counter() - t0)
                self._m["backend_up"].labels(backend=b.key).set(
                    1 if b.healthy else 0)
                self._m["breaker_state"].labels(backend=b.key).set(
                    _BREAKER_STATE_CODE[b.breaker.state])
                self._m["backend_queue"].labels(backend=b.key).set(
                    b.queue_depth)
            counts = {"unified": 0, "prefill": 0, "decode": 0}
            for b in self.backends():
                counts[b.role] = counts.get(b.role, 0) + 1
            for role, n in counts.items():
                self._m["role_backends"].labels(role=role).set(n)
            self._stop.wait(self._poll_interval)

    def _poll_backend(self, b: Backend):
        if b.admin_port is None:
            # no admin plane: degrade to a TCP liveness dial
            try:
                socket.create_connection(
                    (b.host, b.port),
                    timeout=max(self._poll_interval, 0.5)).close()
                b.update_health(True)
            except OSError as e:
                b.update_health(False, [f"dial failed: {e}"])
                self._m["poll_failures"].labels(backend=b.key).inc()
            return
        conn = HTTPConnection(b.host, b.admin_port,
                              timeout=max(self._poll_interval, 0.5))
        try:
            conn.request("GET", "/healthz")
            r = conn.getresponse()
            hbody = json.loads(r.read().decode("utf-8", "replace") or "{}")
            healthy = r.status == 200
            reasons = hbody.get("reasons", [])
            draining = any("draining" in str(x) for x in reasons)
            queue_depth = oldest = None
            conn.request("GET", "/statusz")
            s = conn.getresponse()
            sbody = json.loads(s.read().decode("utf-8", "replace") or "{}")
            draining = bool(sbody.get("draining", draining))
            trace_wire = bool(sbody.get("trace_wire", False))
            batcher = sbody.get("batcher") or {}
            if "queue_depth" in batcher:
                queue_depth = batcher["queue_depth"]
            if "oldest_wait_s" in batcher:
                oldest = batcher["oldest_wait_s"]
            # the backend's own SLO verdict closes the loop into
            # routing: /alertz 404s on an old backend -> stays "ok"
            alert_state = None
            try:
                conn.request("GET", "/alertz")
                a = conn.getresponse()
                abody = json.loads(
                    a.read().decode("utf-8", "replace") or "{}")
                if a.status == 200:
                    alert_state = abody.get("state")
            except (OSError, ValueError):
                pass
            b.update_health(healthy, reasons, draining=draining,
                            queue_depth=queue_depth, oldest_wait_s=oldest,
                            trace_wire=trace_wire,
                            alert_state=alert_state)
        except (OSError, ValueError) as e:
            b.update_health(False, [f"admin poll failed: {e}"])
            self._m["poll_failures"].labels(backend=b.key).inc()
        finally:
            conn.close()

    # -- backend selection -----------------------------------------------

    def _routable(self, exclude=()):
        """Backends eligible for new traffic: last poll healthy, not
        draining, breaker not OPEN (HALF_OPEN stays in — its allow()
        gate hands one probe through)."""
        out = []
        for b in self.backends():
            if b.key in exclude or b.draining or not b.healthy:
                continue
            if b.breaker.state == CircuitBreaker.OPEN:
                continue
            if b.role == "prefill":
                # prefill workers take KV-export traffic from the
                # handoff orchestrator, never direct client requests
                continue
            out.append(b)
        return out

    def _choose_prefill(self, exclude=()):
        """Least-loaded routable prefill worker for a KV export, or
        ``None`` when the fleet has no usable prefill pool (the stream
        then just prefills on its decode worker — today's path). Compat
        is deliberately NOT pre-filtered here: the decode worker is the
        authority (typed FAILED_PRECONDITION refusal, docs/serving.md),
        so a misconfigured pairing is caught loudly on the wire instead
        of silently shadowed by the router."""
        cands = []
        for b in self.backends():
            if b.key in exclude or b.draining or not b.healthy:
                continue
            if b.role != "prefill":
                continue
            if b.breaker.state == CircuitBreaker.OPEN:
                continue
            cands.append(b)
        cands.sort(key=lambda b: b.score())
        for b in cands:
            if b.breaker.allow():
                return b
        return None

    def _choose(self, exclude=()):
        """Least-loaded routable backend, or ``None`` when nothing is
        routable. Raises RESOURCE_EXHAUSTED when backends ARE routable
        but every one is past the shed watermark / in-flight cap —
        queueing behind an overloaded fleet only converts overload into
        timeouts, so the router refuses fast instead."""
        cands = self._routable(exclude)
        if not cands:
            return None
        open_for_traffic = []
        for b in cands:
            if self._watermark > 0 and b.queue_depth >= self._watermark:
                continue
            if b.inflight >= self._max_inflight:
                continue
            open_for_traffic.append(b)
        if not open_for_traffic:
            self._m["shed"].inc()
            raise TypedServeError(
                ERR_RESOURCE_EXHAUSTED,
                f"all {len(cands)} routable backends past the shed "
                f"watermark (queue >= {self._watermark}); back off and "
                f"retry later")
        scored = [(b.score(), b) for b in open_for_traffic]
        scored.sort(key=lambda p: p[0])
        # equal-score leaders rotate round-robin — a stable sort alone
        # would pile every idle-fleet request onto the first backend
        leaders = [b for s, b in scored if s <= scored[0][0]]
        self._rr += 1
        rot = self._rr % len(leaders)
        ordered = leaders[rot:] + leaders[:rot] \
            + [b for _, b in scored if b not in leaders]
        for b in ordered:
            if b.breaker.allow():    # claims the half-open probe slot
                return b
        return None

    # -- forwarding ------------------------------------------------------

    def _conn_cache(self) -> dict:
        cache = getattr(self._local, "conns", None)
        if cache is None:
            cache = self._local.conns = {}
            with self._conn_caches_lock:
                self._conn_caches[threading.current_thread()] = cache
        return cache

    def _backend_conn(self, b: Backend) -> socket.socket:
        cache = self._conn_cache()
        s = cache.get(b.key)
        if s is None:
            s = socket.create_connection((b.host, b.port),
                                         timeout=self._connect_timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.settimeout(self._forward_timeout)
            cache[b.key] = s
        return s

    def _drop_conn(self, b: Backend):
        s = self._conn_cache().pop(b.key, None)
        if s is not None:
            try:
                s.close()
            except OSError:
                pass

    def _forward(self, b: Backend, arrays, ctx=None):
        """One attempt against one backend: write the request, read the
        reply. Returns ``(outputs, None, reply_ctx)`` or ``(None,
        error_message, reply_ctx)``; ``reply_ctx`` is the backend's
        trace context (span breakdown) or ``None``. The context is only
        put on the wire when the backend advertised ``trace_wire`` in
        its /statusz, so an old backend never sees a PDI2 frame. A
        stale keep-alive socket (backend restarted between requests)
        gets exactly one fresh-socket retry; every other wire failure
        propagates to the failover loop."""
        send_ctx = ctx if (ctx is not None and b.trace_wire) else None
        reused = b.key in self._conn_cache()
        b.begin()
        self._m["backend_requests"].labels(backend=b.key).inc()
        try:
            try:
                s = self._backend_conn(b)
                write_tensors(s, arrays, ctx=send_ctx)
                return read_reply_ctx(s)
            except ConnectionError:
                self._drop_conn(b)
                if not reused:
                    raise
            except (TimeoutError, OSError, struct.error):
                self._drop_conn(b)
                raise
            s = self._backend_conn(b)
            try:
                write_tensors(s, arrays, ctx=send_ctx)
                return read_reply_ctx(s)
            except (ConnectionError, TimeoutError, OSError, struct.error):
                self._drop_conn(b)
                raise
        finally:
            b.end()

    # -- multi-tenant isolation -------------------------------------------

    @staticmethod
    def _tenant_of(cctx) -> str:
        """Tenant identity off the wire ctx: the decode ctx field for
        streams, the top-level field for one-shot requests."""
        if not isinstance(cctx, dict):
            return "default"
        d = cctx.get("decode")
        t = d.get("tenant") if isinstance(d, dict) else None
        t = t or cctx.get("tenant")
        return str(t).strip() if t else "default"

    def _budget_for(self, tenant) -> RetryBudget:
        """Non-default tenants spend failover retries from their own
        budget: a flood tenant burning retries cannot starve everyone
        else's failovers."""
        if tenant == "default":
            return self._budget
        b = self._tenant_budgets.get(tenant)
        if b is None:
            b = self._tenant_budgets.setdefault(tenant, RetryBudget())
        return b

    def _tenant_admit(self, tenant) -> bool:
        """Claim an in-flight slot for the tenant; False when it is at
        its PADDLE_TPU_ROUTER_TENANT_MAX_INFLIGHT cap (0 disables)."""
        if self._tenant_max_inflight <= 0:
            return True
        with self._inflight_lock:
            n = self._tenant_inflight.get(tenant, 0)
            if n >= self._tenant_max_inflight:
                return False
            self._tenant_inflight[tenant] = n + 1
        self._m["tenant_inflight"].labels(tenant=tenant).inc()
        return True

    def _tenant_release(self, tenant):
        if self._tenant_max_inflight <= 0:
            return
        with self._inflight_lock:
            n = self._tenant_inflight.get(tenant, 1) - 1
            if n <= 0:
                self._tenant_inflight.pop(tenant, None)
            else:
                self._tenant_inflight[tenant] = n
        self._m["tenant_inflight"].labels(tenant=tenant).dec()

    def _handle(self, arrays, ctx=None, info=None, tenant="default"):
        """Route one decoded request. Returns ``("ok", outputs)`` or
        ``(outcome, error_message)`` with outcome one of
        ``relayed_error`` / ``shed`` / ``unavailable``. ``ctx`` is the
        trace context forwarded to trace-capable backends; ``info``
        (when given) is filled in-place with the trace assembly:
        ``pick_s`` / ``forward_s`` accumulated across attempts,
        ``backend`` (the answering backend's key), ``backend_ctx`` (its
        reply trace context) and ``attempts``."""
        info = info if info is not None else {}
        info.update(pick_s=0.0, forward_s=0.0, backend=None,
                    backend_ctx=None, attempts=0)
        budget = self._budget_for(tenant)
        budget.record_request()
        tried = set()
        attempts = 0
        first_failure_t = None
        last_err = None
        rerouted = False         # one-shot RESOURCE_EXHAUSTED reroute
        pending_reroute = False  # next attempt is the reroute, not a failover
        last_shed = None         # the shed errmsg, relayed if terminal
        max_attempts = 1 + self._failover_retries
        while attempts < max_attempts:
            t_pick = time.perf_counter()
            try:
                b = self._choose(exclude=tried)
            except TypedServeError as e:     # shed: every backend busy
                now = time.perf_counter()
                info["pick_s"] += now - t_pick
                self._ring.complete("router.pick", t_pick, now,
                                    {"outcome": "shed"})
                return ("shed", last_shed or str(e))
            now = time.perf_counter()
            info["pick_s"] += now - t_pick
            self._ring.complete("router.pick", t_pick, now,
                                {"backend": b.key if b else None})
            if b is None:
                break
            if attempts > 0:
                if not budget.try_spend():
                    self._m["budget_denied"].inc()
                    if last_shed is not None:
                        # the reroute could not be funded: the shed is
                        # terminal — relay it so the client backs off
                        return ("shed", last_shed)
                    return ("unavailable",
                            f"{ERR_UNAVAILABLE}: retry budget exhausted "
                            f"after backend failure ({last_err}); "
                            f"failing fast instead of retry-storming")
                if pending_reroute:
                    pending_reroute = False
                    self._m["reroutes"].inc()
                else:
                    self._m["failovers"].inc()
            attempts += 1
            info["attempts"] = attempts
            tried.add(b.key)
            t_fwd = time.perf_counter()
            try:
                chaos.maybe_fail("router.forward", b.key)
                outputs, errmsg, rctx = self._forward(b, arrays, ctx=ctx)
            except (ConnectionError, TimeoutError, OSError,
                    struct.error, ValueError, IndexError) as e:
                # wire failure or unparseable reply: the backend is
                # misbehaving — count it against the breaker, fail over
                now = time.perf_counter()
                info["forward_s"] += now - t_fwd
                self._ring.complete("router.forward", t_fwd, now,
                                    {"backend": b.key, "error":
                                     type(e).__name__})
                b.breaker.record_failure()
                self._drop_conn(b)
                last_err = f"{b.key}: {type(e).__name__}: {e}"
                last_shed = None   # freshest failure is no longer a shed
                if first_failure_t is None:
                    first_failure_t = time.monotonic()
                continue
            now = time.perf_counter()
            info["forward_s"] += now - t_fwd
            self._ring.complete("router.forward", t_fwd, now,
                                {"backend": b.key})
            if errmsg is not None:
                code = error_code(errmsg)
                if code in RETRYABLE_CODES:
                    # the backend itself says UNAVAILABLE (dispatcher
                    # died, worker crashed): failover-safe
                    b.breaker.record_failure()
                    last_err = f"{b.key}: {errmsg}"
                    last_shed = None
                    if first_failure_t is None:
                        first_failure_t = time.monotonic()
                    continue
                if code == ERR_RESOURCE_EXHAUSTED and not rerouted:
                    # this backend shed at its own admission watermark;
                    # a sibling may have free slots — one-shot reroute
                    # to the least-loaded non-shedding backend (spends
                    # from the shared retry budget at the top of the
                    # loop). Shed stays terminal only when every
                    # backend is saturated.
                    b.breaker.record_success()   # it answered; healthy
                    rerouted = pending_reroute = True
                    last_shed = errmsg
                    last_err = f"{b.key}: {errmsg}"
                    max_attempts += 1   # don't eat a failover retry
                    continue
                if code == ERR_RESOURCE_EXHAUSTED:
                    # the reroute target shed too: the fleet really is
                    # saturated — terminal shed (counts against the shed
                    # outcome, not as a relayed model error)
                    b.breaker.record_success()
                    info["backend"], info["backend_ctx"] = b.key, rctx
                    return ("shed", errmsg)
                # deterministic / non-retryable error: relay verbatim —
                # the backend answered, so its breaker heals
                b.breaker.record_success()
                info["backend"], info["backend_ctx"] = b.key, rctx
                return ("relayed_error", errmsg)
            b.breaker.record_success()
            if first_failure_t is not None:
                self._m["failover_latency"].observe(
                    time.monotonic() - first_failure_t)
            info["backend"], info["backend_ctx"] = b.key, rctx
            return ("ok", outputs)
        if last_shed is not None:
            # the only failure seen was a backend shed and no sibling
            # could take the reroute: terminal shed, not UNAVAILABLE
            return ("shed", last_shed)
        detail = last_err or ("no routable backend (all unhealthy, "
                              "draining, or circuit-broken)")
        return ("unavailable",
                f"{ERR_UNAVAILABLE}: no backend answered after "
                f"{attempts} attempt(s): {detail}")

    # -- decode stream relay ---------------------------------------------

    def _stream_conn(self, b: Backend) -> socket.socket:
        """A dedicated socket for one stream attempt — never the shared
        keep-alive cache: a stream holds its connection for seconds, and
        a failed one is poisoned mid-frame by definition."""
        s = socket.create_connection((b.host, b.port),
                                     timeout=self._connect_timeout)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(self._forward_timeout)
        return s

    def _stream_ctx(self, rid, trace_id, stream_fields):
        return {"trace_id": trace_id, "request_id": rid,
                "stream": stream_fields}

    def _finish_stream(self, conn, rid, trace_id, emitted) -> bool:
        """Write the client's done frame from the router's own record
        (used both after a relayed done frame and when the backend died
        with nothing left to generate). False when the client is gone."""
        try:
            write_tensors(conn, [np.asarray(emitted, np.int32)],
                          ctx=self._stream_ctx(
                              rid, trace_id,
                              {"done": True, "n_tokens": len(emitted)}))
            return True
        except (ConnectionError, TimeoutError, OSError):
            return False

    def _export_kv_from(self, pre: Backend, prompt, rid, trace_id):
        """One kv_export round-trip to a prefill worker on a dedicated
        socket: prompt in, (page leaf arrays, export metadata) out."""
        pre.begin()
        self._m["backend_requests"].labels(backend=pre.key).inc()
        s = None
        try:
            s = self._stream_conn(pre)
            write_tensors(s, [np.asarray(prompt, np.int32)],
                          ctx={"trace_id": trace_id, "request_id": rid,
                               "kv_export": {}})
            arrays, errmsg, rctx = read_reply_ctx(s)
            if errmsg is not None:
                pre.breaker.record_success()   # it answered; not broken
                raise _HandoffFailed(f"{pre.key}: {errmsg}")
            meta = (rctx or {}).get("kv_export")
            if not isinstance(meta, dict):
                raise _HandoffFailed(
                    f"{pre.key}: kv_export reply carries no metadata")
            pre.breaker.record_success()
            return arrays, meta
        except (ConnectionError, TimeoutError, OSError, struct.error,
                ValueError, IndexError) as e:
            pre.breaker.record_failure()
            raise _HandoffFailed(
                f"{pre.key}: {type(e).__name__}: {e}") from e
        finally:
            pre.end()
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass

    def _maybe_handoff(self, b: Backend, s, prompt, rid,
                       trace_id) -> bool:
        """Orchestrate one prefill->decode KV handoff for a fresh stream
        routed to decode worker ``b`` (docs/serving.md "Disaggregated
        prefill/decode"): export the prompt's full KV pages from a
        prefill worker, ship them to ``b`` on the stream's own socket
        ``s``, and wait for the ack — the ordering that makes the landed
        pages visible to the stream request sent next on ``s``. Returns
        True when pages landed. ANY failure degrades to False — the
        stream simply prefills on ``b`` (token-identical, the same
        contract as a failed tier refetch); a failure that poisoned
        ``s`` mid-frame surfaces at the stream request write and rides
        the normal failover path."""
        pre = self._choose_prefill()
        if pre is None:
            return False
        t0 = time.monotonic()
        try:
            chaos.maybe_fail("handoff.send", detail=b.key)
            arrays, meta = self._export_kv_from(pre, prompt, rid,
                                                trace_id)
            write_tensors(s, arrays,
                          ctx={"trace_id": trace_id, "request_id": rid,
                               "kv_handoff": meta})
            _, errmsg, _ = read_reply_ctx(s)
            if errmsg is not None:
                # typed refusal (compat / checksum / exhausted): the
                # frame was fully consumed, the socket stays clean
                raise _HandoffFailed(f"{b.key}: {errmsg}")
        except (_HandoffFailed, ConnectionError, TimeoutError, OSError,
                struct.error, ValueError, IndexError) as e:
            self._m["handoffs"].labels(outcome="fallback").inc()
            _tracez.RING.instant("router.handoff_fallback",
                                 {"backend": b.key, "err": str(e)[:200]})
            return False
        self._m["handoffs"].labels(outcome="ok").inc()
        self._m["handoff_latency"].observe(time.monotonic() - t0)
        return True

    def _handle_stream(self, conn, arrays, cctx, rid, trace_id):
        """Proxy one decode stream with mid-stream failover.

        The state machine (docs/fault_tolerance.md "Streaming
        failover"): relay the backend's seq-numbered token frames to the
        client while recording every emitted token; on a wire failure or
        typed ``UNAVAILABLE``, re-issue the request to another routable
        backend as ``prompt + tokens_emitted_so_far`` (a resume is just
        a longer prefill; greedy decode is argmax-deterministic and
        sampled decode carries a per-stream seed, so the continuation is
        token-identical). Backend seq restarts at 0 per attempt, so
        client seq = tokens-already-relayed + backend seq; frames that
        would rewind it are dropped — the client sees one gapless,
        duplicate-free stream. Each failover spends from the shared
        retry budget and from the per-stream ``stream_retries`` cap.
        The half-open breaker probe resolves at the FIRST relayed frame
        (stream established), not stream completion, so a minutes-long
        stream cannot pin a breaker in HALF_OPEN.

        Returns ``(outcome, conn_alive)``.
        """
        opts = dict(cctx.get("decode") or {})
        prompt = [int(t) for t in np.asarray(arrays[0]).reshape(-1)]
        max_new = opts.get("max_new_tokens")
        max_new = None if max_new is None else int(max_new)
        temperature = float(opts.get("temperature") or 0.0)
        if temperature > 0.0 and opts.get("seed") is None:
            # sampled decode only resumes token-identically with a
            # per-stream seed; mint one so every attempt samples the
            # same continuation
            opts["seed"] = int.from_bytes(os.urandom(4), "little")
        budget = self._budget_for(self._tenant_of(cctx))
        budget.record_request()
        emitted = []             # tokens relayed to the client, in order
        eos_seen = False
        tried = set()
        attempts = 0
        first_failure_t = None
        last_err = None
        rerouted = False         # one-shot RESOURCE_EXHAUSTED reroute
        last_shed = None         # the shed errmsg, relayed if terminal
        max_attempts = 1 + self._stream_retries
        while attempts < max_attempts:
            if emitted and (eos_seen or
                            (max_new is not None
                             and len(emitted) >= max_new)):
                # the backend died between its last token and the done
                # frame: nothing is left to generate — synthesize the
                # done frame from the router's record
                return (("ok", True)
                        if self._finish_stream(conn, rid, trace_id,
                                               emitted)
                        else ("ok", False))
            try:
                b = self._choose(exclude=tried)
            except TypedServeError as e:         # shed: every backend busy
                if not emitted:
                    try:
                        write_error(conn, last_shed or str(e),
                                    ctx=self._stream_ctx(
                            rid, trace_id, {"done": True, "error": True,
                                            "seq": 0}))
                    except OSError:
                        return ("shed", False)
                    return ("shed", True)
                # mid-stream shed is a lost stream, same as no backend
                break
            if b is None:
                break
            if attempts > 0:
                if not budget.try_spend():
                    self._m["budget_denied"].inc()
                    last_err = (f"retry budget exhausted after "
                                f"{last_err}")
                    break
                self._m["stream_failovers"].inc()
                if emitted:
                    self._m["stream_resumed_tokens"].inc(len(emitted))
            attempts += 1
            tried.add(b.key)
            seq_base = len(emitted)
            send_opts = dict(opts)
            if max_new is not None:
                send_opts["max_new_tokens"] = max_new - seq_base
            # the resume form: every emitted token becomes prompt (the
            # paged prefix cache makes the re-prefill cheap)
            req_toks = np.asarray(prompt + emitted, np.int32)
            send_ctx = {"trace_id": trace_id, "request_id": rid,
                        "decode": send_opts}
            b.begin()
            self._m["backend_requests"].labels(backend=b.key).inc()
            s = None
            established = False
            try:
                chaos.maybe_fail("router.stream_relay", b.key)
                s = self._stream_conn(b)
                if not emitted and b.role == "decode":
                    # disaggregated topology: land the prompt's KV
                    # pages from a prefill worker before the stream
                    # request, so admission sees a prefix-cache hit;
                    # failure degrades to a plain prefill on b
                    self._maybe_handoff(b, s, prompt, rid, trace_id)
                write_tensors(s, [req_toks], ctx=send_ctx)
                while True:
                    outputs, errmsg, rctx = read_reply_ctx(s)
                    stream = (rctx or {}).get("stream") or {}
                    if errmsg is not None:
                        code = error_code(errmsg)
                        if code in RETRYABLE_CODES:
                            raise TypedServeError(code, errmsg)
                        if (code == ERR_RESOURCE_EXHAUSTED
                                and not rerouted and not emitted):
                            # shed at decode admission before any token:
                            # one-shot reroute to a sibling with free
                            # slots (terminal only when all saturated)
                            rerouted = True
                            last_shed = errmsg
                            max_attempts += 1
                            raise _RerouteShed(errmsg)
                        # deterministic error: relay verbatim; the
                        # backend answered, so its breaker heals
                        b.breaker.record_success()
                        try:
                            write_error(conn, errmsg,
                                        ctx=self._stream_ctx(
                                            rid, trace_id,
                                            {"done": True, "error": True,
                                             "seq": len(emitted)}))
                        except OSError:
                            return ("relayed_error", False)
                        return ("relayed_error", True)
                    if not established:
                        # stream established: the half-open probe (and a
                        # failover's recovery clock) resolves NOW, not
                        # at stream completion
                        established = True
                        b.breaker.record_success()
                        if first_failure_t is not None:
                            self._m["failover_latency"].observe(
                                time.monotonic() - first_failure_t)
                            first_failure_t = None
                    if stream.get("done"):
                        # reconcile: the done payload is this attempt's
                        # authoritative token list — relay any trailing
                        # tokens the per-token frames missed
                        done_toks = ([int(t) for t in
                                      np.asarray(outputs[0]).reshape(-1)]
                                     if outputs else [])
                        full = emitted[:seq_base] + done_toks
                        for i in range(len(emitted), len(full)):
                            try:
                                write_tensors(
                                    conn,
                                    [np.asarray([full[i]], np.int32)],
                                    ctx=self._stream_ctx(
                                        rid, trace_id,
                                        {"seq": i, "eos": False,
                                         "done": False}))
                            except (ConnectionError, TimeoutError,
                                    OSError):
                                return ("client_gone", False)
                        emitted = full
                        return (("ok", True)
                                if self._finish_stream(conn, rid,
                                                       trace_id, emitted)
                                else ("ok", False))
                    gseq = seq_base + int(stream.get("seq", 0))
                    if gseq < len(emitted):
                        continue     # duplicate of an already-relayed seq
                    tok = int(np.asarray(outputs[0]).reshape(-1)[0])
                    emitted.append(tok)
                    eos_seen = bool(stream.get("eos")) or eos_seen
                    try:
                        write_tensors(
                            conn, [np.asarray([tok], np.int32)],
                            ctx=self._stream_ctx(
                                rid, trace_id,
                                {"seq": gseq,
                                 "eos": bool(stream.get("eos")),
                                 "done": False}))
                    except (ConnectionError, TimeoutError, OSError):
                        return ("client_gone", False)
            except _RerouteShed as e:
                # the backend answered (saturated, not broken): heal its
                # breaker and reroute without a failure mark
                b.breaker.record_success()
                self._m["reroutes"].inc()
                last_err = f"{b.key}: {e}"
                continue
            except (TypedServeError, ConnectionError, TimeoutError,
                    OSError, struct.error, ValueError, IndexError) as e:
                # mid-stream backend failure: count it, resume elsewhere
                b.breaker.record_failure()
                last_err = f"{b.key}: {type(e).__name__}: {e}"
                last_shed = None   # freshest failure is no longer a shed
                if first_failure_t is None:
                    first_failure_t = time.monotonic()
                continue
            finally:
                b.end()
                if s is not None:
                    try:
                        s.close()
                    except OSError:
                        pass
        if last_shed is not None and not emitted:
            # the only failure seen was an admission shed and no sibling
            # could take the reroute: relay it terminally — the client
            # backs off instead of treating the fleet as down
            try:
                write_error(conn, last_shed, ctx=self._stream_ctx(
                    rid, trace_id, {"done": True, "error": True,
                                    "seq": 0}))
            except OSError:
                return ("shed", False)
            return ("shed", True)
        # out of backends or budget: the stream is lost
        self._m["stream_lost"].inc()
        detail = last_err or ("no routable backend (all unhealthy, "
                              "draining, or circuit-broken)")
        msg = (f"{ERR_UNAVAILABLE}: decode stream lost after "
               f"{attempts} attempt(s), {len(emitted)} token(s) "
               f"relayed: {detail}")
        try:
            write_error(conn, msg, ctx=self._stream_ctx(
                rid, trace_id, {"done": True, "error": True,
                                "seq": len(emitted)}))
        except OSError:
            return ("unavailable", False)
        return ("unavailable", True)

    def _serve_stream(self, conn, arrays, cctx, rid, trace_id) -> bool:
        """Accounting shell around :meth:`_handle_stream`: in-flight and
        stream gauges, latency + outcome metrics, the event ring, and
        the stall-watchdog beat. Returns whether the client connection
        is still usable."""
        with self._inflight_lock:
            self._inflight += 1
        self._m["inflight"].inc()
        self._m["stream_active"].inc()
        t0 = time.monotonic()
        t_ring = time.perf_counter()
        try:
            outcome, alive = self._handle_stream(conn, arrays, cctx,
                                                 rid, trace_id)
        finally:
            with self._inflight_lock:
                self._inflight -= 1
            self._m["inflight"].dec()
            self._m["stream_active"].dec()
        wall = time.monotonic() - t0
        self._m["latency"].observe(wall)
        self._m["requests"].labels(outcome=outcome).inc()
        self._ring.complete("router.stream", t_ring, time.perf_counter(),
                            {"outcome": outcome, "rid": rid})
        self._recorder.beat()
        return alive

    # -- client plane ----------------------------------------------------

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                break
            threading.Thread(target=self._serve_client, args=(conn,),
                             daemon=True).start()

    def _serve_client(self, conn):
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        if self._idle_timeout and self._idle_timeout > 0:
            conn.settimeout(self._idle_timeout)
        try:
            while True:
                try:
                    arrays, cctx = read_request(conn)
                except (ConnectionError, TimeoutError, struct.error,
                        OSError):
                    return
                except (ValueError, IndexError) as e:
                    self._m["requests"].labels(outcome="malformed").inc()
                    try:
                        write_error(conn,
                                    f"{ERR_INVALID_ARGUMENT}: malformed "
                                    f"request: {e}")
                    except OSError:
                        pass
                    return
                # one router-minted id per request (globally unique via
                # the process prefix); the trace id is the client's if
                # it sent a context, else the router id — either way it
                # names the whole client->router->backend trace
                rid = next_request_id()
                trace_id = (cctx or {}).get("trace_id") or rid
                tenant = self._tenant_of(cctx)
                is_stream = (cctx is not None
                             and isinstance(cctx.get("decode"), dict))
                if not self._tenant_admit(tenant):
                    # router-side per-tenant cap: refuse THIS tenant
                    # without touching a backend; the dedicated outcome
                    # keeps one tenant's flood out of the fleet-wide
                    # availability objective
                    self._m["tenant_shed"].labels(tenant=tenant).inc()
                    self._m["requests"].labels(
                        outcome="tenant_shed").inc()
                    msg = (f"{ERR_RESOURCE_EXHAUSTED}: tenant "
                           f"{tenant!r} is at its router in-flight "
                           f"cap ({self._tenant_max_inflight}; "
                           "PADDLE_TPU_ROUTER_TENANT_MAX_INFLIGHT)")
                    ectx = (self._stream_ctx(
                        rid, trace_id,
                        {"done": True, "error": True, "seq": 0})
                        if is_stream else None)
                    try:
                        write_error(conn, msg, ctx=ectx)
                    except (ConnectionError, TimeoutError, OSError):
                        return
                    continue
                if is_stream:
                    # decode stream: leave the one-reply fast path for
                    # the seq-relaying proxy with mid-stream failover
                    try:
                        alive = self._serve_stream(conn, arrays, cctx,
                                                   rid, trace_id)
                    finally:
                        self._tenant_release(tenant)
                    if not alive or self._draining.is_set():
                        return
                    continue
                traced = cctx is not None or self._spans.sampled(rid)
                fwd_ctx = {"trace_id": trace_id, "request_id": rid} \
                    if traced else None
                with self._inflight_lock:
                    self._inflight += 1
                self._m["inflight"].inc()
                t0 = time.monotonic()
                info = {}
                try:
                    outcome, payload = self._handle(arrays, ctx=fwd_ctx,
                                                    info=info,
                                                    tenant=tenant)
                finally:
                    self._tenant_release(tenant)
                    with self._inflight_lock:
                        self._inflight -= 1
                    self._m["inflight"].dec()
                wall = time.monotonic() - t0
                self._m["latency"].observe(wall)
                self._m["requests"].labels(outcome=outcome).inc()
                reply_ctx = self._client_reply_ctx(cctx, rid, trace_id,
                                                   info)
                t_reply = time.perf_counter()
                try:
                    if outcome == "ok":
                        write_tensors(conn, payload, ctx=reply_ctx)
                    else:
                        write_error(conn, payload, ctx=reply_ctx)
                except (ConnectionError, TimeoutError, OSError):
                    return
                now = time.perf_counter()
                if traced:
                    # trace line first: the client already has its reply,
                    # and a test (or tail -f) watching the JSONL sink
                    # should see the line as soon as possible
                    self._record_trace(rid, trace_id, cctx is not None,
                                       wall, info, outcome)
                self._ring.complete("router.reply", t_reply, now,
                                    {"outcome": outcome})
                self._ring.complete("router.request", now - wall, now,
                                    {"outcome": outcome, "rid": rid})
                self._recorder.beat()
                if self._draining.is_set():
                    return
        finally:
            conn.close()

    # -- trace assembly --------------------------------------------------

    @staticmethod
    def _backend_spans(info) -> dict:
        """The answering backend's span breakdown (stage -> seconds,
        no ``_s`` suffix) out of its reply trace context, or ``{}``."""
        bctx = info.get("backend_ctx") or {}
        out = {}
        for k, v in (bctx.get("spans") or {}).items():
            try:
                v = float(v)
            except (TypeError, ValueError):
                continue
            out[k[:-2] if k.endswith("_s") else k] = v
        return out

    def _client_reply_ctx(self, cctx, rid, trace_id, info):
        """Trace context echoed to a PDI2 client: the router's ids plus
        the relayed backend breakdown. ``None`` for a PDI1 client (the
        reply frame must mirror the request's dialect)."""
        if cctx is None:
            return None
        ctx = {"trace_id": trace_id, "request_id": rid}
        spans = {"pick_s": round(info.get("pick_s", 0.0), 6),
                 "forward_s": round(info.get("forward_s", 0.0), 6)}
        for k, v in self._backend_spans(info).items():
            spans[f"backend_{k}_s"] = round(v, 6)
        ctx["spans"] = spans
        if info.get("backend"):
            ctx["backend"] = info["backend"]
        bctx = info.get("backend_ctx") or {}
        if bctx.get("request_id") is not None:
            ctx["backend_request_id"] = bctx["request_id"]
        return ctx

    def _record_trace(self, rid, trace_id, client_traced, wall, info,
                      outcome):
        """One assembled JSONL line per traced request: the router's
        own stages (pick / forward / reply, summing to the observed
        latency) plus the backend's relayed breakdown as ``backend_*``
        extras — kept out of ``total_s`` because the backend's time is
        inside ``forward_s`` already (double counting would make the
        epsilon check total_s - backend_total_s meaningless)."""
        pick = info.get("pick_s", 0.0)
        fwd = info.get("forward_s", 0.0)
        spans = {"pick": pick, "forward": fwd,
                 "reply": max(wall - pick - fwd, 0.0)}
        extra = {"trace_id": trace_id, "outcome": outcome,
                 "attempts": info.get("attempts", 0),
                 "client_traced": bool(client_traced)}
        if info.get("backend"):
            extra["backend"] = info["backend"]
        bctx = info.get("backend_ctx") or {}
        if bctx.get("request_id") is not None:
            extra["backend_request_id"] = bctx["request_id"]
        bspans = self._backend_spans(info)
        if bspans:
            for k, v in bspans.items():
                self._spans.observe_stage(f"backend_{k}", v)
                extra[f"backend_{k}_s"] = round(v, 6)
            extra["backend_total_s"] = round(sum(bspans.values()), 6)
        self._spans.record(rid, spans, extra=extra, force=True)

    # -- admin surface ---------------------------------------------------

    def _fleet_tracez(self) -> dict:
        """Router /tracez: the fleet's merged execution timeline — the
        router's own event ring plus every admin-reachable backend's
        /tracez, skew-corrected by each ring's wall-clock anchor
        (best-effort: an unreachable backend is simply absent)."""
        traces = [self._ring.chrome_trace()]
        for b in self.backends():
            if b.admin_port is None:
                continue
            try:
                traces.append(_tracez.fetch_trace(
                    f"http://{b.host}:{b.admin_port}/tracez",
                    timeout=2.0))
            except Exception:
                continue
        return _tracez.merge_traces(traces)

    def _fleet_memz(self, oom: bool = False) -> dict:
        """Router /memz: the fleet's merged memory plane — every
        admin-reachable backend's /memz body (owner rollups, ghost
        audits; with ``oom=1`` the retained OOM forensic dumps) summed
        into one view, each full body kept under ``backends``. Same
        best-effort contract as the tracez merge."""
        from ..observability import memz as _memz
        snaps, keys = [], []
        for b in self.backends():
            if b.admin_port is None:
                continue
            url = f"http://{b.host}:{b.admin_port}/memz" \
                  + ("?oom=1" if oom else "")
            try:
                snaps.append(_memz.fetch_memz(url, timeout=2.0))
                keys.append(b.key)
            except Exception:
                continue
        return _memz.merge_memz(snaps, keys=keys)

    def _health(self):
        """Router /healthz: healthy while >= 1 backend is routable."""
        reasons = []
        if self._stop.is_set():
            reasons.append("router stopped")
        elif self._draining.is_set():
            reasons.append("draining")
        routable = self._routable()
        if not routable:
            per = [f"{s['key']}: "
                   + ("draining" if s["draining"]
                      else f"breaker {s['breaker']}"
                      if s["breaker"] == CircuitBreaker.OPEN
                      else "; ".join(s["reasons"]) or "unhealthy")
                   for s in (b.snapshot() for b in self.backends())]
            reasons.append("no routable backend ("
                           + ("; ".join(per) or "no backends") + ")")
        return not reasons, reasons

    def _stall_context(self) -> dict:
        """Flight-recorder dump context: what the router was doing when
        it wedged (which backends looked routable, what was in flight)."""
        return {
            "inflight_requests": self.inflight_requests,
            "draining": self._draining.is_set(),
            "backends": [b.snapshot() for b in self.backends()],
        }

    def _status(self) -> dict:
        poll_lat = self._m["poll_latency"]
        return {
            "role": "router",
            "port": self.port,
            "metrics_port": self.metrics_port,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "draining": self._draining.is_set(),
            "inflight_requests": self.inflight_requests,
            "shed_watermark": self._watermark,
            "poll_interval_s": self._poll_interval,
            "trace_wire": True,
            "request_id_base": request_id_base(),
            "poll": {
                "interval_s": self._poll_interval,
                "polls": poll_lat.count,
                "latency_p50_s": round(poll_lat.percentile(0.50), 6),
                "latency_p99_s": round(poll_lat.percentile(0.99), 6),
                "failures": {
                    b.key: b.polls_failed for b in self.backends()},
            },
            "retry_budget": {
                "tokens": round(self._budget.tokens, 2),
                "spent": self._budget.spent,
                "denied": self._budget.denied,
            },
            "streams": {
                "retries": self._stream_retries,
            },
            "membership": None if self._membership is None else {
                "ttl_s": self._membership.ttl,
                "interval_s": self._membership_interval,
                "members": sorted(self._member_keys),
                # topology view (docs/serving.md): role + KV-compat
                # facts each member advertised in its slot meta
                "roles": {
                    b.key: dict(role=b.role, **b.kv_compat())
                    for b in self.backends()
                    if b.key in self._member_keys},
            },
            "topology": {
                "roles": {
                    role: sum(1 for b in self.backends()
                              if b.role == role)
                    for role in ("unified", "prefill", "decode")},
            },
            "backends": [b.snapshot() for b in self.backends()],
        }

    # -- lifecycle -------------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    @property
    def inflight_requests(self) -> int:
        with self._inflight_lock:
            return self._inflight

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop accepting, answer everything in flight, then stop."""
        self._draining.set()
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._srv.close()
        deadline = time.monotonic() + float(timeout)
        drained = False
        while time.monotonic() < deadline:
            if self.inflight_requests <= 0:
                drained = True
                break
            time.sleep(0.01)
        self.stop()
        return drained

    def stop(self):
        self._stop.set()
        if self._membership_thread is not None:
            self._membership_thread.join(timeout=2)
        if self._varz is not None:
            self._varz.stop()
        self._recorder.stop()
        self._spans.close()
        if self._admin is not None:
            self._admin.stop()
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._srv.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


class _ProcIO:
    """Stdout reader for one spawned backend: drains the pipe forever
    (a full pipe would wedge the child), remembers the announced ports,
    and keeps a tail of lines for crash diagnostics."""

    def __init__(self, proc: subprocess.Popen):
        self.proc = proc
        self.lines = collections.deque(maxlen=64)
        self.serve_port = None
        self.metrics_port = None
        self._serving = threading.Event()
        self._thread = threading.Thread(target=self._read, daemon=True,
                                        name=f"backend-io-{proc.pid}")
        self._thread.start()

    def _read(self):
        try:
            for line in self.proc.stdout:
                line = line.rstrip("\n")
                self.lines.append(line)
                if line.startswith("METRICS "):
                    try:
                        self.metrics_port = int(line.split()[1])
                    except (IndexError, ValueError):
                        pass
                elif line.startswith("SERVING "):
                    try:
                        self.serve_port = int(line.split()[1])
                    except (IndexError, ValueError):
                        pass
                    self._serving.set()
        except (OSError, ValueError):
            pass
        finally:
            self._serving.set()     # EOF: unblock any waiter

    def wait_serving(self, timeout: float):
        if not self._serving.wait(timeout) or self.serve_port is None:
            tail = "\n".join(self.lines)
            raise RuntimeError(
                f"backend pid {self.proc.pid} did not announce SERVING "
                f"within {timeout:g}s; last output:\n{tail}")
        return self.serve_port, self.metrics_port


class BackendSupervisor:
    """Owns a fleet of ``serve.py`` daemons for a router.

    Spawns ``count`` backends from one model prefix (each on an
    ephemeral data + admin port, announced on stdout), registers them
    with the router, and watches them: a backend that dies is removed
    from the routing table and respawned with bounded exponential
    backoff — up to ``max_restarts`` times per slot, after which the
    slot is abandoned (the router simply keeps routing around it). All
    backends resolve the same persistent compile-cache directory
    (jit/compile_cache.py), so a respawned backend warms its bucket
    ladder from it instead of recompiling from scratch. On a TPU host
    slot i is pinned to chip i, and more slots than chips is an error.

    ``terminate(key)`` SIGTERMs one backend (it drains via serve.py's
    handler) — the rolling-restart primitive: the watcher respawns it
    once it exits, one slot at a time.
    """

    def __init__(self, model_prefix: str, count: int, router: ServeRouter,
                 host: str = "127.0.0.1", serve_args=None, env=None,
                 max_restarts: int = 5, start_timeout: float = 180.0):
        self.model_prefix = model_prefix
        self.count = int(count)
        self.router = router
        self.host = host
        self.serve_args = list(serve_args or [])
        self.max_restarts = int(max_restarts)
        self.start_timeout = float(start_timeout)
        # children inherit the environment as it is — the compile cache
        # included: JAX_COMPILATION_CACHE_DIR if set, else every backend
        # resolves the same fixed in-checkout directory on its own
        self._env = dict(env if env is not None else os.environ)
        # one backend per TPU chip: slot i is pinned to chip i. This
        # process never initialises a backend (it would take the chips).
        self._chips = local_tpu_chip_count(self._env)
        if self._chips and self.count > self._chips:
            raise ValueError(
                f"--fleet {self.count}: this host has {self._chips} TPU "
                f"chip(s) and a chip serves one process at a time — ask "
                f"for at most {self._chips} backend(s)")
        self._m = _router_metrics()
        self._stop = threading.Event()
        self._lock = threading.Lock()
        # slot -> {"io": _ProcIO, "backend": Backend, "restarts": int}
        self._slots = {}
        self._watch_thread = None

    def _spawn(self, slot: int) -> _ProcIO:
        cmd = [sys.executable, "-m", "paddle_tpu.inference.serve",
               self.model_prefix, "--port", "0", "--metrics-port", "0",
               "--stats-interval", "0"] + self.serve_args
        env = self._env
        if self._chips:
            env = dict(env, **single_chip_env(slot))
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                env=env)
        return _ProcIO(proc)

    def start(self):
        for slot in range(self.count):
            io = self._spawn(slot)
            port, admin = io.wait_serving(self.start_timeout)
            backend = Backend(self.host, port, admin)
            self.router.add_backend(backend)
            with self._lock:
                self._slots[slot] = {"io": io, "backend": backend,
                                     "restarts": 0, "delays": None}
        self._watch_thread = threading.Thread(target=self._watch_loop,
                                              daemon=True,
                                              name="fleet-supervisor")
        self._watch_thread.start()
        return self

    def backends(self):
        with self._lock:
            return {slot: s["backend"] for slot, s in self._slots.items()}

    def terminate(self, key: str) -> bool:
        """SIGTERM the backend with this key (graceful drain); the
        watcher respawns the slot after it exits."""
        import signal as _signal
        with self._lock:
            for s in self._slots.values():
                if s["backend"] is not None and s["backend"].key == key:
                    s["io"].proc.send_signal(_signal.SIGTERM)
                    return True
        return False

    def _watch_loop(self):
        while not self._stop.wait(0.25):
            with self._lock:
                slots = list(self._slots.items())
            for slot, s in slots:
                io = s["io"]
                if io is None or io.proc.poll() is None:
                    continue
                if self._stop.is_set():
                    return
                self._restart_slot(slot, s)

    def _restart_slot(self, slot: int, s: dict):
        dead = s["backend"]
        if dead is not None:
            self.router.remove_backend(dead.key)
        tail = "\n".join(list(s["io"].lines)[-5:])
        if s["restarts"] >= self.max_restarts:
            # slot abandoned: the router routes around it for good
            print(f"FLEET slot {slot} exceeded {self.max_restarts} "
                  f"restarts; abandoning. last output:\n{tail}",
                  flush=True)
            with self._lock:
                s["io"], s["backend"] = None, None
            return
        if s["delays"] is None:
            s["delays"] = backoff_delays(self.max_restarts,
                                         base_delay=0.2, max_delay=5.0)
        try:
            delay = next(s["delays"])
        except StopIteration:
            delay = 5.0
        print(f"FLEET slot {slot} ({dead.key if dead else '?'}) exited "
              f"rc={s['io'].proc.returncode}; respawning in {delay:.2f}s",
              flush=True)
        if self._stop.wait(delay):
            return
        s["restarts"] += 1
        self._m["backend_restarts"].inc()
        try:
            io = self._spawn(slot)
        except OSError as e:
            print(f"FLEET slot {slot} respawn failed: {e}", flush=True)
            return                       # old dead io stays; retry next tick
        try:
            port, admin = io.wait_serving(self.start_timeout)
        except RuntimeError as e:
            print(f"FLEET slot {slot} respawn failed: {e}", flush=True)
            with self._lock:
                s["io"], s["backend"] = io, None
            return                       # watcher sees it dead, retries
        backend = Backend(self.host, port, admin)
        with self._lock:
            s["io"], s["backend"] = io, backend
        self.router.add_backend(backend)
        print(f"FLEET slot {slot} back as {backend.key} "
              f"(restart {s['restarts']})", flush=True)

    def stop(self, drain_timeout: float = 15.0):
        """SIGTERM every live backend (graceful drain), then reap; a
        backend that ignores SIGTERM past the timeout is killed."""
        import signal as _signal
        self._stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=2)
        with self._lock:
            ios = [s["io"] for s in self._slots.values()
                   if s["io"] is not None]
        for io in ios:
            if io.proc.poll() is None:
                try:
                    io.proc.send_signal(_signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + float(drain_timeout)
        for io in ios:
            left = max(deadline - time.monotonic(), 0.1)
            try:
                io.proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                io.proc.kill()
                io.proc.wait(timeout=5)


def main_router(args) -> int:
    """Entry point for ``python -m paddle_tpu.inference.serve --router``
    (serve.py delegates here after argparse)."""
    import signal as _signal

    backends = [parse_backend(s) for s in args.backend]
    if not backends and not args.fleet:
        print("router needs --backend HOST:PORT[:ADMIN] and/or --fleet N",
              flush=True)
        return 2
    if args.fleet and not args.model:
        print("--fleet needs the model prefix argument", flush=True)
        return 2

    # forward timeout: a shade over the backend request deadline, so the
    # backend's own typed DEADLINE_EXCEEDED frame wins the race against
    # the router's socket timeout
    req_t = args.request_timeout
    if req_t is None:
        from .serve import _request_timeout_default
        req_t = _request_timeout_default()
    forward_timeout = (req_t + 10.0) if req_t and req_t > 0 else None

    router = ServeRouter(
        backends, port=args.port, host=args.host,
        poll_interval=args.poll_interval,
        shed_watermark=args.shed_watermark,
        forward_timeout=forward_timeout,
        idle_timeout=args.idle_timeout,
        metrics_port=args.metrics_port)

    membership_store = args.membership_store \
        or _flags.env_value("PADDLE_TPU_MEMBERSHIP_STORE")
    if membership_store:
        router.watch_membership(membership_store,
                                group=args.membership_group,
                                ttl=args.membership_ttl)
        print(f"MEMBERSHIP store={membership_store} "
              f"group={args.membership_group}", flush=True)

    sup = None
    if args.fleet:
        serve_args = ["--max-batch", str(args.max_batch),
                      "--pool", str(args.pool),
                      "--batch-timeout-ms", str(args.batch_timeout_ms),
                      "--drain-timeout", str(args.drain_timeout)]
        if args.warmup:
            serve_args.append("--warmup")
        if args.trailing:
            serve_args += ["--trailing", args.trailing]
        if args.request_timeout is not None:
            serve_args += ["--request-timeout", str(args.request_timeout)]
        if args.max_queue is not None:
            serve_args += ["--max-queue", str(args.max_queue)]
        try:
            sup = BackendSupervisor(args.model, args.fleet, router,
                                    host=args.host, serve_args=serve_args)
        except (ValueError, RuntimeError) as e:   # more slots than chips
            print(f"FLEET start failed: {e}", flush=True)
            router.stop()
            return 2
        try:
            sup.start()
        except RuntimeError as e:
            print(f"FLEET start failed: {e}", flush=True)
            router.stop()
            sup.stop(drain_timeout=2.0)
            return 1

    keys = [b.key for b in router.backends()]
    print(f"ROUTER backends={','.join(keys)}", flush=True)
    if router.metrics_port is not None:
        print(f"METRICS {router.metrics_port}", flush=True)
    print(f"SERVING {router.port}", flush=True)

    term = threading.Event()
    try:
        _signal.signal(_signal.SIGTERM, lambda *a: term.set())
    except ValueError:                   # non-main thread (tests)
        pass
    try:
        term.wait()
        print("DRAINING", flush=True)
        ok = router.drain(timeout=args.drain_timeout)
        if sup is not None:
            sup.stop(drain_timeout=args.drain_timeout)
        print(f"DRAINED ok={ok}", flush=True)
    except KeyboardInterrupt:
        router.stop()
        if sup is not None:
            sup.stop(drain_timeout=2.0)
    return 0


if __name__ == "__main__":
    from .serve import main
    sys.exit(main(sys.argv[1:] + ["--router"]))
