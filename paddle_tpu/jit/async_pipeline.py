"""Asynchronous step pipeline: bounded in-flight dispatch + deferred fetch.

JAX dispatches device computations asynchronously: calling the compiled
train step returns futures immediately, and the host only stalls when it
*reads* a value (``jax.device_get`` / ``block_until_ready``).  A train
loop that fetches the loss every step therefore serializes host collate,
dispatch and device compute — the chip idles for a full host round-trip
per step.  The fix is pure
reordering of host reads: keep the loss on device, keep up to N steps in
flight, and resolve metrics only at log/callback boundaries.  Numerics
are bit-identical to the synchronous loop — nothing about the computation
changes, only *when* the host looks at it.

Backpressure: an unbounded in-flight window lets the host race ahead of
the device, queueing batches (and their donated buffers) until the device
OOMs.  ``AsyncStepPipeline`` bounds the window (default 2, env
``PADDLE_TPU_ASYNC_STEPS``) by calling ``jax.block_until_ready`` on the
*oldest* ticket before admitting a new one; the blocked wall-clock is
accounted as ``host_blocked_s`` — on an overlapped pipeline it should be
a small fraction of total step time.

Error semantics: with async dispatch a poisoned batch (runtime error in
the compiled step) surfaces at the *fetch* boundary, not the dispatch
site.  Tickets capture the originating step index and re-raise as
``AsyncStepError(step_index=...)`` so the failing step is identifiable.
"""
from __future__ import annotations

import time
from typing import Any, List, Optional

from ..core import flags as _flags

__all__ = [
    "AsyncStepError",
    "AsyncStepPipeline",
    "StepTicket",
    "async_steps",
    "DEFAULT_ASYNC_STEPS",
]

DEFAULT_ASYNC_STEPS = 2


def async_steps(default: int = DEFAULT_ASYNC_STEPS) -> int:
    """In-flight window from ``PADDLE_TPU_ASYNC_STEPS``.

    ``0`` (or ``off``/``sync``) disables async stepping — the train loop
    fetches the loss synchronously every step.  ``>=1`` is the maximum
    number of dispatched-but-unfetched steps."""
    raw = (_flags.env_raw("PADDLE_TPU_ASYNC_STEPS") or "").strip().lower()
    if raw in ("off", "sync", "false", "no"):
        return 0
    try:
        n = int(raw) if raw else int(default)
    except ValueError:
        return int(default)
    return max(n, 0)


class AsyncStepError(RuntimeError):
    """A dispatched step failed; raised at the fetch boundary.

    ``step_index`` is the loop index of the originating dispatch (the
    poisoned batch), which by the time the error surfaces is typically
    several steps behind the loop counter."""

    def __init__(self, step_index: int, cause: BaseException):
        super().__init__(
            f"async train step {step_index} failed at the fetch boundary "
            f"(dispatched {type(cause).__name__}: {cause}); the offending "
            f"batch is step {step_index}, not the step being dispatched "
            f"when this raised")
        self.step_index = step_index
        self.__cause__ = cause


class StepTicket:
    """Handle for one dispatched step: on-device value(s) + timestamps."""

    __slots__ = ("step_index", "value", "submit_t", "ready_t",
                 "collate_s", "dispatch_s", "fetch_s", "_blocked")

    def __init__(self, step_index: int, value: Any,
                 collate_s: float = 0.0, dispatch_s: float = 0.0):
        self.step_index = step_index
        self.value = value
        self.submit_t = time.perf_counter()
        self.ready_t: Optional[float] = None
        self.collate_s = collate_s
        self.dispatch_s = dispatch_s
        self.fetch_s = 0.0
        self._blocked = False

    @property
    def done(self) -> bool:
        return self._blocked

    def block(self) -> float:
        """Wait until the device value is ready; returns seconds blocked.

        Re-raises any deferred step failure as :class:`AsyncStepError`
        carrying this ticket's step index."""
        if self._blocked:
            return 0.0
        t0 = time.perf_counter()
        try:
            # _AsyncScalar keeps its device loss in ._arr (None once it
            # has been fetched); plain arrays / pytrees block directly
            arr = getattr(self.value, "_arr", self.value)
            if arr is not None:
                import jax
                jax.block_until_ready(arr)
        except AsyncStepError:
            raise
        except Exception as e:  # noqa: BLE001 — deferred device failure
            self._blocked = True
            self.ready_t = time.perf_counter()
            raise AsyncStepError(self.step_index, e) from e
        self._blocked = True
        self.ready_t = time.perf_counter()
        self.fetch_s = self.ready_t - t0
        return self.fetch_s


class AsyncStepPipeline:
    """Bounded window of in-flight step tickets.

    ``submit()`` after each dispatch; when the window is full the call
    blocks on the *oldest* ticket (FIFO backpressure).  ``drain()`` at
    epoch end / loop exit retires everything, so deferred errors cannot
    escape the fit call that dispatched them.
    """

    def __init__(self, max_in_flight: Optional[int] = None,
                 label: str = "train", record: bool = True):
        self.max_in_flight = (async_steps() if max_in_flight is None
                              else max(int(max_in_flight), 1))
        self.label = label
        self.record = record
        self._inflight: List[StepTicket] = []
        self.host_blocked_s = 0.0
        self.steps_in_flight = 0      # max concurrently in flight
        self.steps_submitted = 0
        # stall flight recorder (PADDLE_TPU_STALL_DUMP): dumps thread
        # stacks + the in-flight window when steps stop retiring — a
        # device hang shows up here as "busy, no heartbeat"
        from ..observability import FlightRecorder
        from ..observability import tracez as _tracez
        self._recorder = FlightRecorder(
            f"async_steps_{label}",
            busy_fn=lambda: bool(self._inflight),
            context_fn=self._stall_context)
        self._ring = _tracez.RING

    def _stall_context(self):
        now = time.perf_counter()
        return {
            "label": self.label,
            "window": self.max_in_flight,
            "steps_submitted": self.steps_submitted,
            "in_flight": [{"step_index": t.step_index,
                           "age_s": round(now - t.submit_t, 3)}
                          for t in list(self._inflight)],
        }

    def submit(self, value: Any, step_index: int,
               collate_s: float = 0.0, dispatch_s: float = 0.0) -> StepTicket:
        t = StepTicket(step_index, value, collate_s, dispatch_s)
        self._inflight.append(t)
        self.steps_submitted += 1
        # dispatch span ends at submit: collate + dispatch led up to it
        self._ring.complete(
            f"step.dispatch:{self.label}",
            t.submit_t - collate_s - dispatch_s, t.submit_t,
            {"step": step_index})
        self._recorder.beat()
        while len(self._inflight) > self.max_in_flight:
            self._retire(self._inflight[0])
        # high-water mark AFTER backpressure: what was actually left in
        # flight, never the transient submit overshoot
        self.steps_in_flight = max(self.steps_in_flight, len(self._inflight))
        return t

    def drain(self) -> None:
        """Block on every outstanding ticket (oldest first)."""
        while self._inflight:
            self._retire(self._inflight[0])

    def close(self) -> None:
        """Stop the stall watchdog (idempotent; drain() first if the
        window may still hold tickets)."""
        self._recorder.stop()

    def _retire(self, t: StepTicket) -> None:
        try:
            blocked = t.block()
        finally:
            try:
                self._inflight.remove(t)
            except ValueError:
                pass
            self._recorder.beat()
        self.host_blocked_s += blocked
        if t.ready_t is not None:
            self._ring.complete(f"step.block:{self.label}",
                                t.ready_t - blocked, t.ready_t,
                                {"step": t.step_index})
        if self.record:
            from .. import profiler
            profiler.record_step(
                t.step_index,
                collate_s=t.collate_s,
                dispatch_s=t.dispatch_s,
                compute_s=max((t.ready_t or t.submit_t) - t.submit_t, 0.0),
                fetch_s=blocked,
                in_flight=min(self.steps_in_flight, self.max_in_flight),
                label=self.label)

    def stats(self) -> dict:
        return {
            "steps_in_flight": self.steps_in_flight,
            "host_blocked_s": round(self.host_blocked_s, 6),
            "steps_submitted": self.steps_submitted,
            "window": self.max_in_flight,
        }
