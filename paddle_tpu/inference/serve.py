"""Inference serve daemon: a TCP front-end over Predictor, the transport
behind the C/Go client APIs.

Reference: the C API (/root/reference/paddle/fluid/inference/capi/) and Go
bindings (go/paddle/) link AnalysisPredictor into the client process. A
TPU predictor cannot be linked into a C program (the runtime is
XLA/PJRT + Python), so the native-client capability is delivered as a
daemon + thin C client (inference/capi/paddle_c_api.{h,c}): same
capability boundary, process-separated — the deployment shape TPU serving
uses in practice.

Wire protocol (little endian), one request per round trip:
  request : u32 magic 'PDI1' | u32 n_tensors | tensors
  tensor  : u8 dtype | u8 ndim | i64 shape[ndim] | raw data
  reply   : u32 magic | u32 n_tensors | tensors     (or n=0xFFFFFFFF +
            u32 len + utf8 error message)
dtype codes match utils/cpp_extension: 0 f32, 1 f64, 2 i32, 3 i64, 4 u8,
5 bool.

Trace-context extension (optional, backward compatible): a frame whose
magic is 'PDI2' carries a JSON *trace context* between the header and
the payload —
  request : u32 'PDI2' | u32 n_tensors | u32 ctx_len | ctx JSON | tensors
  reply   : u32 'PDI2' | u32 n_tensors | u32 ctx_len | ctx JSON | tensors
  error   : u32 'PDI2' | u32 0xFFFFFFFF | u32 ctx_len | ctx JSON |
            u32 len | utf8 message
The server replies 'PDI2' ONLY to a 'PDI2' request, echoing the trace id
and attaching its span breakdown, so a legacy client ('PDI1', including
the C client) never sees a frame it cannot parse; a new client talking
to a legacy server simply does not send a context (the router gates on
the backend's /statusz ``trace_wire`` capability flag). Contexts are
capped at 64 KiB and an unparseable context degrades to "no context" —
tracing must never fail a request.

Engine: with ``max_batch_size > 1`` (the CLI default) the daemon is a
batched, compile-bounded pipeline — reader threads enqueue decoded
tensors into a DynamicBatcher (inference/batching.py), a dispatcher
forms deadline-bounded batches padded to a shape-bucket ladder, and one
AOT-compiled executable per bucket answers them; ``--warmup``
pre-compiles the whole bucket set so steady-state traffic never
compiles. Trailing dynamic dims are only zero-padded when a startup
probe proves the model padding-invariant (``--trailing``), and every
batched request carries a server-side deadline (``--request-timeout``).
``max_batch_size in (0, 1)`` keeps the legacy one-request-at-a-time
lock. See docs/serving.md.

    python -m paddle_tpu.inference.serve /path/prefix --port 9000 --warmup
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import signal
import socket
import struct
import sys
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np

from ..core import flags as _flags
from ..observability.tracez import RING as _RING
from ..testing import chaos
from .errors import (ERR_DEADLINE_EXCEEDED, ERR_FAILED_PRECONDITION,
                     ERR_INTERNAL, ERR_INVALID_ARGUMENT, TypedServeError)

MAGIC = 0x31494450          # 'PDI1'
MAGIC_TRACE = 0x32494450    # 'PDI2': header is followed by a trace ctx
ERR = 0xFFFFFFFF
_DTYPES = [np.float32, np.float64, np.int32, np.int64, np.uint8, np.bool_]
_MAX_TENSORS = 256          # a request claiming more is malformed
_MAX_NDIM = 32
_MAX_CTX_BYTES = 1 << 16    # trace-context JSON cap
_SEND_COPY_MAX = 1 << 16    # payloads above this go out via memoryview


def _recv_exact(sock, n):
    from ..utils.net import recv_exact
    return recv_exact(sock, n, what="client")


_TENANT_METRICS = None


def _tenant_serve_metrics():
    """Per-tenant request/error counters — the key families the
    per-tenant SLO objectives (observability/slo.py) burn against."""
    global _TENANT_METRICS
    if _TENANT_METRICS is None:
        from ..observability import counter
        _TENANT_METRICS = {
            "requests": counter(
                "paddle_tpu_tenant_requests_total",
                "Decode requests served per tenant",
                labelnames=("tenant",)),
            "errors": counter(
                "paddle_tpu_tenant_errors_total",
                "Decode requests that ended in a typed error frame, "
                "per tenant", labelnames=("tenant",)),
        }
    return _TENANT_METRICS


def max_request_bytes() -> int:
    """Per-request payload budget (``PADDLE_TPU_MAX_REQUEST_BYTES``)."""
    return int(_flags.env_value("PADDLE_TPU_MAX_REQUEST_BYTES"))


def _encode_ctx(ctx: dict) -> bytes:
    raw = json.dumps(ctx, separators=(",", ":")).encode("utf-8")
    if len(raw) > _MAX_CTX_BYTES:
        # oversize context degrades to the trace id alone rather than
        # failing the frame
        raw = json.dumps({"trace_id": ctx.get("trace_id")},
                         separators=(",", ":")).encode("utf-8")
    return struct.pack("<I", len(raw)) + raw


def _read_ctx(sock) -> dict:
    (clen,) = struct.unpack("<I", _recv_exact(sock, 4))
    if clen > _MAX_CTX_BYTES:
        raise ValueError(f"trace context claims {clen} bytes "
                         f"(cap {_MAX_CTX_BYTES})")
    raw = _recv_exact(sock, clen)
    try:
        ctx = json.loads(raw.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        return {}               # garbage context must not fail the frame
    return ctx if isinstance(ctx, dict) else {}


def _read_tensor_list(sock, n, max_bytes, what):
    """The shared per-tensor loop: validates every size field BEFORE
    allocating or recv-ing — dtype code and ndim in range, no negative
    dims, and the total payload capped by PADDLE_TPU_MAX_REQUEST_BYTES,
    so a hostile header can never drive ``count * itemsize`` into a huge
    (or, via int64 overflow, negative) recv."""
    out, total = [], 0
    for _ in range(n):
        dt, nd = struct.unpack("<BB", _recv_exact(sock, 2))
        if dt >= len(_DTYPES):
            raise IndexError(f"bad dtype code {dt}")
        if nd > _MAX_NDIM:
            raise ValueError(f"tensor ndim {nd} exceeds cap {_MAX_NDIM}")
        shape = struct.unpack(f"<{nd}q", _recv_exact(sock, 8 * nd)) \
            if nd else ()
        if any(d < 0 for d in shape):
            raise ValueError(f"negative dim in shape {shape}")
        dtype = np.dtype(_DTYPES[dt])
        count = 1
        for d in shape:          # python ints: no int64 overflow
            count *= d
        nbytes = count * dtype.itemsize
        total += nbytes
        if total > max_bytes:
            raise ValueError(
                f"{what} exceeds PADDLE_TPU_MAX_REQUEST_BYTES="
                f"{max_bytes} ({total} bytes claimed)")
        data = _recv_exact(sock, nbytes)
        out.append(np.frombuffer(data, dtype, count).reshape(shape).copy())
    return out


def read_request(sock, max_bytes=None):
    """Decode one request frame -> ``(arrays, ctx)``. ``ctx`` is the
    trace-context dict for a 'PDI2' frame, ``None`` for a legacy 'PDI1'
    frame (every pre-trace client, including the C client)."""
    if max_bytes is None:
        max_bytes = max_request_bytes()
    magic, n = struct.unpack("<II", _recv_exact(sock, 8))
    if magic not in (MAGIC, MAGIC_TRACE):
        raise ValueError("bad magic")
    ctx = _read_ctx(sock) if magic == MAGIC_TRACE else None
    if n > _MAX_TENSORS:
        raise ValueError(f"request claims {n} tensors "
                         f"(cap {_MAX_TENSORS})")
    return _read_tensor_list(sock, n, max_bytes, "request"), ctx


def read_tensors(sock, max_bytes=None):
    """Decode one request frame (tensors only — the historical API; any
    trace context on the frame is read and discarded)."""
    arrays, _ = read_request(sock, max_bytes)
    return arrays


def write_tensors(sock, arrays, ctx=None):
    """Encode one reply frame. Small tensors are coalesced into one
    buffered send; large payloads go out as per-part ``sendall`` on a
    ``memoryview`` of the array — no ``tobytes()`` + ``b"".join`` double
    copy of multi-megabyte results. A ``ctx`` dict upgrades the frame to
    'PDI2' with the JSON trace context after the header — only send one
    to a peer known to speak it."""
    if ctx is None:
        small = [struct.pack("<II", MAGIC, len(arrays))]
    else:
        small = [struct.pack("<II", MAGIC_TRACE, len(arrays)),
                 _encode_ctx(ctx)]
    for a in arrays:
        a = np.ascontiguousarray(a)
        if a.dtype not in [np.dtype(d) for d in _DTYPES]:
            if np.issubdtype(a.dtype, np.floating) or \
                    a.dtype.name == "bfloat16":
                a = a.astype(np.float32)   # bf16/f16 outputs -> f32 wire
            else:
                raise ValueError(
                    f"unsupported output dtype {a.dtype} on the wire "
                    f"(supported: {[np.dtype(d).name for d in _DTYPES]})")
        dt = next(i for i, d in enumerate(_DTYPES) if np.dtype(d) == a.dtype)
        small.append(struct.pack("<BB", dt, a.ndim))
        small.append(struct.pack(f"<{a.ndim}q", *a.shape))
        if a.nbytes > _SEND_COPY_MAX:
            sock.sendall(b"".join(small))
            small = []
            sock.sendall(memoryview(a).cast("B"))
        else:
            small.append(a.tobytes())
    if small:
        sock.sendall(b"".join(small))


def write_error(sock, msg: str, ctx=None):
    m = msg.encode()[:65536]
    if ctx is None:
        sock.sendall(struct.pack("<III", MAGIC, ERR, len(m)) + m)
    else:
        sock.sendall(struct.pack("<II", MAGIC_TRACE, ERR)
                     + _encode_ctx(ctx)
                     + struct.pack("<I", len(m)) + m)


def read_reply_ctx(sock, max_bytes=None):
    """Decode one REPLY frame -> ``(arrays, errmsg, ctx)``: a tensor
    reply is ``(arrays, None, ctx)``, an error frame ``(None, message,
    ctx)``; ``ctx`` is ``None`` unless the peer sent a 'PDI2' frame
    (which it only does in answer to a 'PDI2' request)."""
    if max_bytes is None:
        max_bytes = max_request_bytes()
    magic, n = struct.unpack("<II", _recv_exact(sock, 8))
    if magic not in (MAGIC, MAGIC_TRACE):
        raise ValueError("bad magic in reply")
    ctx = _read_ctx(sock) if magic == MAGIC_TRACE else None
    if n == ERR:
        (mlen,) = struct.unpack("<I", _recv_exact(sock, 4))
        if mlen > 65536:
            raise ValueError(f"error frame claims {mlen} bytes")
        return None, _recv_exact(sock, mlen).decode("utf-8", "replace"), ctx
    if n > _MAX_TENSORS:
        raise ValueError(f"reply claims {n} tensors (cap {_MAX_TENSORS})")
    return _read_tensor_list(sock, n, max_bytes, "reply"), None, ctx


def read_reply(sock, max_bytes=None):
    """Decode one REPLY frame: ``(arrays, None)`` for a tensor reply,
    ``(None, message)`` for an error frame. The router (and any Python
    client) needs this because ``read_tensors`` treats the error marker
    as a hostile tensor count. Same size validation as ``read_tensors``.
    """
    arrays, err, _ = read_reply_ctx(sock, max_bytes)
    return arrays, err


def decode_request(sock, prompt, opts=None, trace=True,
                   on_token=None, max_bytes=None):
    """Client half of the decode wire exchange on an open socket.

    Sends the prompt (int32 [T]); with ``trace=True`` the request is a
    'PDI2' frame (``opts`` rides in its ``decode`` context field —
    including the multi-tenant QoS identity ``tenant``/``priority``,
    which server and router read from there) and
    the server streams per-token frames — ``on_token(tok, stream_ctx)``
    fires for each — before the final accumulated frame. ``trace=False``
    sends legacy 'PDI1' and blocks for the single accumulated reply.
    Returns the generated tokens as a list; raises TypedServeError on a
    typed error frame (mid-stream or otherwise). An error frame that
    arrives after token frames does NOT drop the prefix: the raised
    exception carries the tokens already received (in seq order) as
    ``.partial_tokens`` plus ``.last_seq``. Token frames are
    de-duplicated by ``seq`` (a failover relay may legally repeat one),
    and the final done frame's accumulated payload is authoritative
    regardless of token-frame arrival order."""
    from .errors import error_code
    arr = np.asarray(prompt, np.int32).reshape(-1)
    ctx = None
    if trace:
        # always carry the decode field: the router's stream detection
        # keys on its presence, not its contents
        ctx = {"trace_id": f"decode-{os.getpid()}-{id(arr):x}",
               "decode": dict(opts or {})}
    write_tensors(sock, [arr], ctx=ctx)
    by_seq = {}
    while True:
        arrays, err, rctx = read_reply_ctx(sock, max_bytes)
        if err is not None:
            code = error_code(err)
            detail = err.split(":", 1)[1].strip() if code else err
            exc = TypedServeError(code or ERR_INTERNAL, detail)
            exc.partial_tokens = [t for _, t in sorted(by_seq.items())]
            exc.last_seq = max(by_seq) if by_seq else -1
            raise exc
        stream = (rctx or {}).get("stream") or {}
        if not trace or stream.get("done"):
            return [int(t) for t in np.asarray(arrays[0]).reshape(-1)]
        tok = int(np.asarray(arrays[0]).reshape(-1)[0])
        seq = int(stream.get("seq", len(by_seq)))
        if seq in by_seq:
            continue                 # duplicate frame: already surfaced
        by_seq[seq] = tok
        if on_token is not None:
            on_token(tok, stream)


def _idle_timeout_default() -> float:
    return float(_flags.env_value("PADDLE_TPU_SERVE_IDLE_TIMEOUT"))


def _request_timeout_default() -> float:
    return float(_flags.env_value("PADDLE_TPU_SERVE_REQUEST_TIMEOUT"))


class InferenceServer:
    """Serves one loaded model over TCP.

    Two engines:
    * ``max_batch_size in (None, 0, 1)`` — legacy serialized mode: the
      predictor call runs under a global lock, one request at a time.
    * ``max_batch_size > 1`` — batched mode: connection threads only
      decode and enqueue; a DynamicBatcher forms deadline-bounded
      batches, pads them to the bucket ladder, and round-robins them
      across ``pool_size`` predictors pinned to distinct devices.
      ``warmup=True`` pre-compiles every bucket at startup so
      steady-state traffic never compiles.

    ``stats_interval > 0`` prints a periodic ``SERVE_STATS {json}`` line
    (queue depth, occupancy, padding waste, compile count, latency
    percentiles, reqs/s) from the metrics registry via
    ``profiler.serve_stats()``.

    ``metrics_port`` (or ``PADDLE_TPU_METRICS_PORT``) mounts the admin
    HTTP endpoint — ``/metrics`` (Prometheus exposition), ``/healthz``
    (503 once the dispatcher dies or the queue wedges past the request
    deadline) and ``/statusz`` (one JSON snapshot: serve stats, bucket
    ladder, warmup/compile state, per-device HBM, uptime, effective
    config). Off by default; ``0`` picks a free port
    (``srv.metrics_port``). See docs/observability.md.
    """

    def __init__(self, model_prefix: str, port: int = 0,
                 host: str = "127.0.0.1", max_batch_size: int = None,
                 batch_timeout_ms: float = 2.0, pool_size: int = 1,
                 warmup: bool = False, idle_timeout: float = None,
                 stats_interval: float = 0.0, request_timeout: float = None,
                 trailing: str = None, metrics_port: int = None,
                 max_queue: int = None, decode: bool = False,
                 decode_slots: int = None, decode_max_new: int = None,
                 draft_model: str = None, speculate_k: int = None,
                 kv_dtype: str = None, draft_quant: bool = None,
                 host_pages: int = None, role: str = None):
        # loopback by default: the daemon is unauthenticated — exposing a
        # model to the network segment must be an explicit --host choice
        if role is None:
            role = str(_flags.env_value("PADDLE_TPU_SERVE_ROLE"))
        role = str(role).lower()
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown serve role {role!r} (want "
                             f"'unified', 'prefill' or 'decode')")
        if role != "unified" and not decode:
            raise ValueError(
                f"role {role!r} requires decode mode: a disaggregated "
                f"worker exports or imports KV pages (docs/serving.md)")
        self.role = role
        if max_batch_size is None:
            max_batch_size = int(_flags.env_value("PADDLE_TPU_SERVE_BATCH"))
        self._batched = (not decode) and max_batch_size \
            and int(max_batch_size) > 1
        self._batcher = None
        self._engine = None          # continuous-batching decode engine
        self.warmup_compiles = 0
        if decode:
            # autoregressive decode mode: the token-level continuous
            # batcher (inference/decode.py) replaces the one-shot
            # predictor; requests are token prompts, replies are token
            # streams (PDI2) or one accumulated frame (PDI1)
            from .decode import load_for_decode
            kw = {}
            if decode_slots:
                kw["max_slots"] = int(decode_slots)
            if decode_max_new:
                kw["max_new_tokens"] = int(decode_max_new)
            if draft_model:
                kw["draft_prefix"] = draft_model
            if speculate_k is not None:
                kw["speculate_k"] = int(speculate_k)
            if kv_dtype:
                kw["kv_dtype"] = str(kv_dtype)
            if draft_quant:
                kw["draft_quant"] = True
            if host_pages is not None:
                kw["host_pages"] = int(host_pages)
            if role != "unified":
                # disaggregated worker: arm the engine's KV handoff
                # endpoints (export on prefill, import on decode);
                # unified workers keep today's path untouched
                kw["handoff"] = True
            self._engine = load_for_decode(model_prefix, **kw)
            self._predictor = None
            if warmup:
                self.warmup_compiles = self._engine.warmup(verbose=True)
        elif self._batched:
            from . import Config, PredictorPool
            from .batching import DynamicBatcher
            cfg = Config(model_prefix)
            pool = PredictorPool(cfg, size=max(int(pool_size), 1),
                                 devices="auto" if int(pool_size) > 1
                                 else None)
            self._pool = pool
            self._predictor = pool.retrieve(0)
            self._batcher = DynamicBatcher(
                pool, max_batch_size=int(max_batch_size),
                batch_timeout_ms=batch_timeout_ms, trailing=trailing,
                max_queue=max_queue)
            if warmup:
                self.warmup_compiles = self._batcher.warmup()
        else:
            from . import Config, create_predictor
            self._predictor = create_predictor(Config(model_prefix))
        self._lock = threading.Lock()
        self._idle_timeout = _idle_timeout_default() \
            if idle_timeout is None else float(idle_timeout)
        self._request_timeout = _request_timeout_default() \
            if request_timeout is None else float(request_timeout)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.port = self._srv.getsockname()[1]
        self._t0 = time.monotonic()
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._conn_inflight = 0      # requests read and not yet answered
        self._conn_lock = threading.Lock()
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True)
        self._thread.start()
        if stats_interval and stats_interval > 0:
            self._stats_thread = threading.Thread(
                target=self._stats_loop, args=(float(stats_interval),),
                daemon=True)
            self._stats_thread.start()
        # admin endpoint: off unless a port is given (env or argument);
        # 0 = ephemeral. Loopback only, like the data-plane default.
        self._admin = None
        self.metrics_port = None
        if metrics_port is None:
            metrics_port = _flags.env_value("PADDLE_TPU_METRICS_PORT")
        self._varz = None
        self._slo = None
        if metrics_port is not None and int(metrics_port) >= 0:
            from ..observability import (AdminServer, SLOEngine,
                                         TimeSeriesStore,
                                         install_default_collectors,
                                         serve_objectives)
            install_default_collectors()
            # windowed history + SLO verdicts ride the same admin plane:
            # /varz is the ring-buffer view, /alertz the burn-rate
            # judgment over it (docs/observability.md)
            self._varz = TimeSeriesStore()
            self._varz.start()
            self._slo = SLOEngine(self._varz, serve_objectives())
            self._admin = AdminServer(port=int(metrics_port), host=host,
                                      health_fn=self._health,
                                      status_fn=self._status,
                                      varz_fn=self._varz.varz,
                                      alertz_fn=self._slo.alertz)
            self.metrics_port = self._admin.port

    @property
    def batched(self) -> bool:
        return bool(self._batched)

    # -- admin surface ---------------------------------------------------

    def _health(self):
        """(healthy, reasons) for /healthz: the accept loop and (in
        batched mode) the dispatcher + workers must be alive, and the
        queue must not be wedged past the request deadline."""
        reasons = []
        if self._stop.is_set():
            reasons.append("server stopped")
        elif self._draining.is_set():
            # a draining backend finishes in-flight work but must take no
            # new traffic: the router reads this as "route around me"
            reasons.append("draining")
        elif not self._thread.is_alive():
            reasons.append("accept thread dead")
        if self._engine is not None \
                and not self._engine._thread.is_alive():
            reasons.append("decode scheduler thread dead")
        if self._batcher is not None:
            if not self._batcher.dispatcher_alive:
                reasons.append("dispatcher thread dead")
            if not self._batcher.workers_alive:
                reasons.append("predictor worker thread dead")
            wedge_after = self._request_timeout \
                if self._request_timeout and self._request_timeout > 0 \
                else 300.0
            oldest = self._batcher.oldest_wait_s
            if oldest > wedge_after:
                reasons.append(
                    f"queue wedged: oldest request waiting "
                    f"{oldest:.1f}s (> {wedge_after:g}s)")
        return not reasons, reasons

    def _status(self) -> dict:
        from .. import profiler
        from ..core import monitor

        compiles = profiler.compile_events()
        st = {
            "engine": "decode" if self._engine is not None
            else ("batched" if self._batched else "serialized"),
            "port": self.port,
            "metrics_port": self.metrics_port,
            # capability flag the router gates trace propagation on: a
            # backend advertising it accepts 'PDI2' request frames
            "trace_wire": True,
            # serving-topology role (docs/serving.md): what the worker
            # advertises into membership for topology-aware routing
            "role": self.role,
            "draining": self._draining.is_set(),
            "inflight_requests": self.inflight_requests,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            "config": {
                "idle_timeout_s": self._idle_timeout,
                "request_timeout_s": self._request_timeout,
                "max_request_bytes": max_request_bytes(),
            },
            "warmup_compiles": self.warmup_compiles,
            "compiles": len(compiles),
            # persistent-cache verdicts of those compiles: a warm
            # restart shows every one of them under "hit"
            "compile_cache": dict(collections.Counter(
                e["cache"] for e in compiles)),
            "compile_seconds": round(
                sum(e["compile_s"] for e in compiles), 3),
            "serve": profiler.serve_stats(),
            "device_memory": monitor.all_device_memory_stats(),
        }
        if self._engine is not None:
            st["decode"] = self._engine.stats()
        # the memory plane's compact block: per-pool owner rollups +
        # fragmentation + ghost count (full detail lives at /memz)
        try:
            from ..observability import memz as _memz
            st["memory"] = _memz.status_block()
        except Exception as e:
            st["memory"] = {"error": repr(e)}
        if self._batcher is not None:
            st["batcher"] = {
                "ladder": self._batcher.ladder,
                "trailing_bucketing": self._batcher.trailing_bucketing,
                "queue_depth": self._batcher.queue_depth,
                "oldest_wait_s": round(self._batcher.oldest_wait_s, 3),
                "dispatcher_alive": self._batcher.dispatcher_alive,
            }
        return st

    def stats_line(self) -> str:
        """One ``SERVE_STATS {json}`` line from the registry snapshot;
        ``ts_monotonic`` makes consecutive lines orderable and
        rate-computable without wall-clock trust."""
        from .. import profiler
        stats = profiler.serve_stats()
        stats["ts_monotonic"] = round(time.monotonic(), 3)
        if self._batcher is not None:
            stats["queue_depth"] = self._batcher.queue_depth
        return "SERVE_STATS " + json.dumps(stats)

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                break
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _run(self, inputs, ctx=None):
        """-> (outputs, future_or_None); the future carries the request
        id and (post-delivery) the span breakdown a traced reply echoes
        back to the caller. A ``tenant`` field in the request ctx tags
        the request for the batcher's weighted-fair QoS."""
        if self._batcher is not None:
            tenant = (ctx or {}).get("tenant")
            fut = self._batcher.submit(inputs, tenant=tenant)
            deadline = self._request_timeout
            if not deadline or deadline <= 0:
                return fut.result(), fut
            try:
                return fut.result(timeout=deadline), fut
            except FuturesTimeout:
                # a wedged predictor/worker must not pin the connection
                # thread forever; the future stays abandoned (the
                # batcher delivers into it defensively) and the client
                # gets a typed error frame instead of silence
                err = TypedServeError(
                    ERR_DEADLINE_EXCEEDED,
                    f"request deadline exceeded "
                    f"({deadline:g}s in queue+execute; "
                    f"PADDLE_TPU_SERVE_REQUEST_TIMEOUT)")
                err.request_id = getattr(fut, "request_id", None)
                raise err from None
        with self._lock:
            return self._predictor.run(inputs), None

    @staticmethod
    def _reply_ctx(ctx, fut, exc=None):
        """Reply trace context for a traced request: echo the trace id,
        attach this backend's request id and span breakdown (what the
        router joins into the end-to-end trace). None for untraced
        ('PDI1') requests — the reply then stays a legacy frame."""
        if ctx is None:
            return None
        out = {"trace_id": ctx.get("trace_id")}
        src = exc if exc is not None else fut
        rid = getattr(src, "request_id", None)
        if rid is None and fut is not None:
            rid = getattr(fut, "request_id", None)
        if rid is not None:
            out["request_id"] = int(rid)
        spans = getattr(src, "spans", None)
        if spans is None and fut is not None:
            spans = getattr(fut, "spans", None)
        if spans:
            out["spans"] = {f"{k}_s": round(float(v), 6)
                            for k, v in spans.items()}
        return out

    def _serve_decode(self, conn, inputs, ctx):
        """One decode request on an open connection.

        PDI2 clients get a PDI2 frame per sampled token — one int32 [1]
        tensor, ctx ``{"stream": {"seq": i, "eos": bool, "done": false}}``
        — then a final done frame carrying the full accumulated sequence
        (``{"stream": {"done": true, "n_tokens": n}}``). PDI1 clients
        get exactly one legacy frame with the accumulated tokens:
        byte-identical framing to a one-shot reply, so pre-decode
        clients (including the C client) work unchanged. A stream that
        dies mid-flight becomes a typed error frame on the same
        connection. Returns False when the socket is unusable."""
        opts = {}
        if ctx is not None and isinstance(ctx.get("decode"), dict):
            d = ctx["decode"]
            for key in ("max_new_tokens", "top_k", "eos_id", "seed"):
                if d.get(key) is not None:
                    opts[key] = int(d[key])
            if d.get("temperature") is not None:
                opts["temperature"] = float(d["temperature"])
            # multi-tenant QoS identity (docs/serving.md): who to bill
            # the tokens to, and how urgently to schedule them
            if d.get("tenant") is not None:
                opts["tenant"] = str(d["tenant"])
            if d.get("priority") is not None:
                opts["priority"] = int(d["priority"])
        tenant = opts.get("tenant") or "default"
        tm = _tenant_serve_metrics()
        tm["requests"].labels(tenant=tenant).inc()

        def _sctx(stream_fields, req_id=None):
            if ctx is None:
                return None
            out = {"stream": stream_fields}
            if ctx.get("trace_id") is not None:
                out["trace_id"] = ctx.get("trace_id")
            if req_id is not None:
                out["request_id"] = int(req_id)
            return out

        try:
            if len(inputs) != 1:
                raise TypedServeError(
                    ERR_INVALID_ARGUMENT,
                    f"decode request wants exactly one prompt tensor, "
                    f"got {len(inputs)}")
            prompt = np.asarray(inputs[0])
            if prompt.dtype not in (np.int32, np.int64) \
                    or prompt.ndim not in (1, 2) \
                    or (prompt.ndim == 2 and prompt.shape[0] != 1):
                raise TypedServeError(
                    ERR_INVALID_ARGUMENT,
                    "decode prompt must be int32/int64 [T] or [1, T]")
            stream = self._engine.submit(prompt.reshape(-1), **opts)
        except TypedServeError as e:
            tm["errors"].labels(tenant=tenant).inc()
            try:
                write_error(conn, str(e),
                            ctx=_sctx({"done": True, "error": True}))
            except OSError:
                pass
            return True          # frame fully consumed; keep the conn
        timeout = self._request_timeout \
            if self._request_timeout and self._request_timeout > 0 else None
        seq = 0
        try:
            while True:
                ev = stream.next_event(timeout=timeout)
                if ev[0] == "done":
                    chaos.maybe_fail("serve.stream_write", detail="done")
                    final = np.asarray(ev[1], np.int32)
                    write_tensors(conn, [final],
                                  ctx=_sctx({"done": True,
                                             "n_tokens": int(final.size)},
                                            stream.request_id))
                    return True
                _, tok, eos = ev
                if ctx is not None:
                    chaos.maybe_fail("serve.stream_write", detail=seq)
                    write_tensors(
                        conn, [np.asarray([tok], np.int32)],
                        ctx=_sctx({"seq": seq, "eos": bool(eos),
                                   "done": False}, stream.request_id))
                seq += 1
        except TypedServeError as e:
            tm["errors"].labels(tenant=tenant).inc()
            try:
                write_error(conn, str(e),
                            ctx=_sctx({"done": True, "error": True,
                                       "seq": seq}))
            except OSError:
                pass
            return True
        except (ConnectionError, TimeoutError, OSError):
            return False

    def _serve_handoff(self, conn, inputs, ctx) -> bool:
        """One KV-handoff control frame (docs/serving.md "Disaggregated
        prefill/decode").

        ``kv_export`` (prefill side): the prompt tensor comes in, the
        reply frame carries the prompt's full KV pages as leaf arrays
        plus the export metadata (compat contract, page count, per-page
        checksums) in the reply ctx. ``kv_handoff`` (decode side): the
        leaf arrays come in with the metadata in the request ctx, and
        the ack frame reports how many pages landed. Any refusal —
        disabled endpoint, compat mismatch, checksum failure, pool
        exhaustion — is a typed error frame the router degrades on.
        Returns False when the socket is unusable."""
        timeout = self._request_timeout \
            if self._request_timeout and self._request_timeout > 0 \
            else 30.0
        tctx = {"trace_id": ctx.get("trace_id")} \
            if ctx.get("trace_id") is not None else {}
        try:
            try:
                if ctx.get("kv_export") is not None:
                    if len(inputs) != 1:
                        raise TypedServeError(
                            ERR_INVALID_ARGUMENT,
                            f"kv_export wants exactly one prompt "
                            f"tensor, got {len(inputs)}")
                    prompt = np.asarray(inputs[0]).reshape(-1)
                    payload = self._engine.export_kv(prompt,
                                                     timeout=timeout)
                    arrays = payload.pop("arrays")
                    write_tensors(conn, arrays,
                                  ctx=dict(tctx, kv_export=payload))
                else:
                    meta = ctx.get("kv_handoff")
                    if not isinstance(meta, dict):
                        raise TypedServeError(
                            ERR_INVALID_ARGUMENT,
                            "kv_handoff ctx must be a metadata object")
                    payload = dict(meta)
                    payload["arrays"] = [np.asarray(a) for a in inputs]
                    n = self._engine.import_kv(payload, timeout=timeout)
                    write_tensors(conn, [np.asarray([n], np.int32)],
                                  ctx=dict(tctx,
                                           kv_handoff={"landed": n}))
            except TypedServeError as e:
                write_error(conn, str(e), ctx=tctx or None)
            except AttributeError:
                # a pre-handoff engine (or none): same contract as a
                # disabled endpoint — typed refusal, router re-prefills
                write_error(conn,
                            str(TypedServeError(
                                ERR_FAILED_PRECONDITION,
                                "backend has no KV handoff endpoint")),
                            ctx=tctx or None)
            return True
        except (ConnectionError, TimeoutError, OSError):
            return False

    def _serve_conn(self, conn):
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # per-connection idle timeout: a dead client must not pin a
        # daemon thread (and its socket buffers) forever
        timeout = self._idle_timeout
        if timeout and timeout > 0:
            conn.settimeout(timeout)
        try:
            while True:
                try:
                    chaos.maybe_fail("serve.conn.read")
                    inputs, ctx = read_request(conn)
                except (ConnectionError, TimeoutError, struct.error,
                        OSError):
                    return
                except (ValueError, IndexError) as e:
                    # unparseable request (bad magic / dtype code /
                    # hostile sizes): the stream is desynced —
                    # best-effort typed error frame, drop the connection
                    try:
                        write_error(conn,
                                    f"{ERR_INVALID_ARGUMENT}: malformed "
                                    f"request: {e}")
                    except OSError:
                        pass
                    return
                with self._conn_lock:
                    self._conn_inflight += 1
                t_req = time.perf_counter()
                try:
                    if ctx is not None \
                            and (ctx.get("kv_export") is not None
                                 or ctx.get("kv_handoff") is not None):
                        # KV-handoff control frames for disaggregated
                        # serving ride the same connection as decode
                        # streams (docs/serving.md)
                        if not self._serve_handoff(conn, inputs, ctx):
                            return
                    elif self._engine is not None:
                        if not self._serve_decode(conn, inputs, ctx):
                            return
                    else:
                        try:
                            outputs, fut = self._run(inputs, ctx)
                            chaos.maybe_fail("serve.conn.reply")
                            write_tensors(conn, outputs,
                                          ctx=self._reply_ctx(ctx, fut))
                        except (ConnectionError, TimeoutError):
                            return
                        except Exception as e:  # model-side error -> client
                            if getattr(e, "code", None):
                                msg = str(e)  # typed: frame leads with CODE
                            else:
                                msg = f"{type(e).__name__}: {e}"
                            rid = getattr(e, "request_id", None)
                            if rid:
                                # the id a sampled span trace / stall dump
                                # carries
                                msg += f" [request_id={rid}]"
                            write_error(conn, msg,
                                        ctx=self._reply_ctx(ctx, None,
                                                            exc=e))
                finally:
                    with self._conn_lock:
                        self._conn_inflight -= 1
                    _RING.complete("serve.request", t_req,
                                   time.perf_counter())
                if self._draining.is_set():
                    # drained: the in-flight request was answered; a
                    # keep-alive connection must not feed a retiring
                    # backend more work
                    return
        finally:
            conn.close()

    def _stats_loop(self, interval: float):
        while not self._stop.wait(interval):
            print(self.stats_line(), flush=True)

    # -- draining / lifecycle --------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    @property
    def inflight_requests(self) -> int:
        """Requests read off a connection and not yet answered."""
        with self._conn_lock:
            return self._conn_inflight

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful retirement (the SIGTERM path): stop accepting new
        connections, flip /healthz to "draining" so the router routes
        around this backend, answer every request already read off a
        connection (result or typed error), then stop. Returns True when
        everything in flight was answered inside ``timeout``.

        Idle keep-alive connections are closed as soon as their current
        request (if any) is answered; a client racing a request into the
        closing socket sees a connection error, which the front router
        converts into a failover, not a lost request."""
        self._draining.set()
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._srv.close()
        deadline = time.monotonic() + float(timeout)
        drained = False
        while time.monotonic() < deadline:
            busy = self.inflight_requests > 0 or (
                self._batcher is not None
                and self._batcher.inflight > 0) or (
                self._engine is not None
                and (self._engine.stats()["active"]
                     + self._engine.stats()["pending"]) > 0)
            if not busy:
                drained = True
                break
            time.sleep(0.01)
        self.stop()
        return drained

    def stop(self):
        self._stop.set()
        if self._varz is not None:
            self._varz.stop()
        if self._admin is not None:
            self._admin.stop()
        if self._batcher is not None:
            self._batcher.stop()
        if self._engine is not None:
            self._engine.stop()
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._srv.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description="paddle_tpu inference server")
    ap.add_argument("model", nargs="?", default=None,
                    help="jit.save artifact prefix (required unless "
                         "--router runs over pre-started --backend "
                         "daemons)")
    ap.add_argument("--port", type=int, default=9000)
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address (default loopback; 0.0.0.0 exposes "
                         "the unauthenticated daemon to the network)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="cross-request batch row budget (0/1 = legacy "
                         "serialized mode)")
    ap.add_argument("--trailing", choices=("auto", "on", "off"),
                    default=None,
                    help="trailing-dynamic-dim bucketing policy: 'auto' "
                         "(default) proves padding-invariance with a "
                         "startup probe and falls back to batch-dim-only "
                         "batching on mismatch; 'on' forces it; 'off' "
                         "merges only exact trailing shapes "
                         "(PADDLE_TPU_SERVE_TRAILING)")
    ap.add_argument("--request-timeout", type=float, default=None,
                    help="server-side deadline in seconds for one request "
                         "(queue wait + execution); on expiry the client "
                         "gets an error frame instead of blocking forever "
                         "(default PADDLE_TPU_SERVE_REQUEST_TIMEOUT or "
                         "120; 0 = off)")
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0,
                    help="max wait past the oldest queued request before "
                         "dispatching a partial batch")
    ap.add_argument("--pool", type=int, default=1,
                    help="predictor pool size; >1 pins each slot to a "
                         "distinct device and round-robins batches")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the whole shape-bucket ladder at "
                         "startup so steady-state traffic never compiles")
    ap.add_argument("--idle-timeout", type=float, default=None,
                    help="per-connection idle seconds before the daemon "
                         "drops it (default "
                         "PADDLE_TPU_SERVE_IDLE_TIMEOUT or 600; 0 = off)")
    ap.add_argument("--stats-interval", type=float, default=10.0,
                    help="seconds between SERVE_STATS lines (0 = off)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="mount /metrics + /healthz + /statusz + /varz "
                         "+ /alertz on this port (0 = ephemeral; "
                         "default off, or PADDLE_TPU_METRICS_PORT)")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="seconds SIGTERM waits for in-flight requests "
                         "before hard stop")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission watermark: queued requests past this "
                         "are shed with a RESOURCE_EXHAUSTED frame "
                         "instead of queueing unboundedly (default "
                         "PADDLE_TPU_SERVE_MAX_QUEUE or off)")
    ap.add_argument("--decode", action="store_true",
                    help="autoregressive decode mode: load a "
                         "decode.save_for_decode artifact and serve "
                         "token streams through the continuous-batching "
                         "KV-cache engine (PDI2 clients stream per-token "
                         "frames; PDI1 clients get one accumulated "
                         "reply). docs/serving.md#continuous-batching-"
                         "decode")
    ap.add_argument("--decode-slots", type=int, default=None,
                    help="(decode) KV-cache slot-pool size — concurrent "
                         "sequences; default: the most whose largest "
                         "compiled step fits half the free HBM "
                         "(decode.default_slot_count), 8 on CPU")
    ap.add_argument("--decode-max-new", type=int, default=None,
                    help="(decode) default max new tokens per request "
                         "when the client does not specify one")
    ap.add_argument("--draft-model", default=None, metavar="PREFIX",
                    help="(decode) draft-model save_for_decode artifact "
                         "prefix enabling speculative decoding; must "
                         "share the target's vocab (default "
                         "PADDLE_TPU_DECODE_DRAFT_MODEL)")
    ap.add_argument("--speculate-k", type=int, default=None,
                    help="(decode) speculation depth: draft steps per "
                         "scheduler tick, verified in one k+1-token "
                         "target forward (default "
                         "PADDLE_TPU_DECODE_SPECULATE; 0 disables)")
    ap.add_argument("--role", choices=("unified", "prefill", "decode"),
                    default=None,
                    help="(decode) serving-topology role for "
                         "disaggregated prefill/decode: 'prefill' runs "
                         "prompt forwards and exports KV pages, 'decode' "
                         "imports them and streams tokens, 'unified' "
                         "(default) does both locally. Non-unified roles "
                         "arm the engine's KV-handoff endpoints and are "
                         "advertised in the membership meta (default "
                         "PADDLE_TPU_SERVE_ROLE; docs/serving.md)")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host-RAM KV tier capacity in pages for decode "
                         "mode (memory/migration.py): cold pages spill "
                         "to host arenas under pool pressure and refetch "
                         "on demand; default PADDLE_TPU_DECODE_HOST_PAGES "
                         "(0 = tiering off)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("float32", "int8"),
                    help="(decode) KV page-pool dtype: int8 stores "
                         "quantized pages with per-row scales, cutting "
                         "page HBM ~4x (default "
                         "PADDLE_TPU_DECODE_KV_DTYPE)")
    ap.add_argument("--draft-quant", action="store_true", default=None,
                    help="(decode) int8-quantize the draft model's "
                         "weights at load — draft numerics only move "
                         "the speculation acceptance rate, never the "
                         "target stream (default "
                         "PADDLE_TPU_DECODE_DRAFT_QUANT)")
    ap.add_argument("--router", action="store_true",
                    help="run the health-aware front router instead of a "
                         "backend: load-balance the wire protocol across "
                         "--backend daemons (or a --fleet it spawns from "
                         "the model prefix) with circuit-breaker "
                         "failover, load shedding and drain-aware "
                         "routing (docs/fault_tolerance.md)")
    ap.add_argument("--backend", action="append", default=[],
                    metavar="HOST:PORT[:ADMIN_PORT]",
                    help="(router) one backend serve daemon; repeatable. "
                         "ADMIN_PORT enables /healthz-driven routing")
    ap.add_argument("--fleet", type=int, default=0,
                    help="(router) spawn this many backend daemons from "
                         "the model prefix and supervise them "
                         "(restart-with-backoff, warm compile cache)")
    ap.add_argument("--poll-interval", type=float, default=0.5,
                    help="(router) seconds between backend health polls")
    ap.add_argument("--shed-watermark", type=int, default=64,
                    help="(router) queue depth past which a backend "
                         "counts as overloaded; when EVERY routable "
                         "backend is past it, requests are shed with "
                         "RESOURCE_EXHAUSTED")
    ap.add_argument("--membership-store", default=None,
                    metavar="ENDPOINT",
                    help="membership registry endpoint (HOST:PORT for "
                         "TCPStore, else a FileStore directory). A "
                         "backend publishes TTL'd heartbeats into it; a "
                         "router watches it and adds/removes backends "
                         "live (default PADDLE_TPU_MEMBERSHIP_STORE)")
    ap.add_argument("--membership-group", default="serve",
                    help="membership registry group name")
    ap.add_argument("--membership-ttl", type=float, default=None,
                    help="seconds without heartbeat progress before a "
                         "member expires (default "
                         "PADDLE_TPU_MEMBERSHIP_TTL)")
    args = ap.parse_args(argv)
    if args.router:
        from .router import main_router
        return main_router(args)
    if not args.model:
        ap.error("model prefix is required (or pass --router)")
    srv = InferenceServer(args.model, port=args.port, host=args.host,
                          max_batch_size=args.max_batch,
                          batch_timeout_ms=args.batch_timeout_ms,
                          pool_size=args.pool, warmup=args.warmup,
                          idle_timeout=args.idle_timeout,
                          stats_interval=args.stats_interval,
                          request_timeout=args.request_timeout,
                          trailing=args.trailing,
                          metrics_port=args.metrics_port,
                          max_queue=args.max_queue, decode=args.decode,
                          decode_slots=args.decode_slots,
                          decode_max_new=args.decode_max_new,
                          draft_model=args.draft_model,
                          speculate_k=args.speculate_k,
                          kv_dtype=args.kv_dtype,
                          draft_quant=args.draft_quant,
                          host_pages=args.host_pages, role=args.role)
    if args.warmup:
        print(f"WARMUP compiles={srv.warmup_compiles}", flush=True)
    if srv.metrics_port is not None:
        print(f"METRICS {srv.metrics_port}", flush=True)
    print(f"SERVING {srv.port}", flush=True)
    # dynamic membership: publish this backend into the registry so a
    # watching router adds it to the fleet without supervisor edits;
    # leave() at drain so the router routes around it immediately
    # instead of waiting out the TTL
    publisher = None
    store_ep = args.membership_store \
        or _flags.env_value("PADDLE_TPU_MEMBERSHIP_STORE")
    if store_ep:
        from ..distributed.store.membership import (MembershipPublisher,
                                                    connect)
        ttl = float(args.membership_ttl
                    if args.membership_ttl is not None
                    else _flags.env_value("PADDLE_TPU_MEMBERSHIP_TTL"))
        # decode workers advertise their topology role and KV-compat
        # facts so a watching router can route prefill->handoff->decode
        # and refuse incompatible pairings up front (docs/serving.md)
        meta = None
        if srv._engine is not None:
            meta = {"role": srv.role}
            meta.update(srv._engine.kv_compat())
        publisher = MembershipPublisher(
            connect(store_ep), f"{args.host}:{srv.port}",
            group=args.membership_group, admin_port=srv.metrics_port,
            interval=max(ttl / 3.0, 0.05), meta=meta).start()
        print(f"MEMBERSHIP store={store_ep} group={args.membership_group} "
              f"slot={publisher.slot}", flush=True)
    # SIGTERM = graceful retirement: stop accepting, finish in-flight,
    # exit 0 — the rolling-restart contract the router drains against
    term = threading.Event()
    try:
        signal.signal(signal.SIGTERM, lambda *a: term.set())
    except ValueError:                   # non-main thread (tests)
        pass
    try:
        term.wait()
        print("DRAINING", flush=True)
        if publisher is not None:
            publisher.leave()
        ok = srv.drain(timeout=args.drain_timeout)
        print(f"DRAINED ok={ok}", flush=True)
    except KeyboardInterrupt:
        if publisher is not None:
            publisher.leave()
        srv.stop()


if __name__ == "__main__":
    sys.exit(main())
