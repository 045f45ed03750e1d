"""Seeded, replayable multi-tenant traffic scenarios for the serving QoS
layer.

A scenario is a deterministic list of :class:`Arrival` records (offset
seconds, tenant, priority, prompt tokens, decode budget) generated from
a single seed — replaying the same seed replays the same traffic, which
is what makes these usable as a standing regression harness (ISSUE 16).
Four generators cover the shapes a multi-tenant fleet actually sees:

* ``diurnal``      — a smooth sinusoidal wave over the run: the
  steady-state capacity-planning case.
* ``flash_crowd``  — a low baseline with a short burst window at many
  times the baseline rate: launch-day traffic.
* ``long_context`` — mostly short requests plus a straggler tenant
  submitting long prompts with large decode budgets: the head-of-line
  blocking probe.
* ``adversarial_flood`` — a well-behaved tenant at a sustainable rate
  beside a flood tenant submitting at >= 4x capacity: the QoS
  acceptance scenario (the flood must be degraded via quota/shed/
  preempt while the well-behaved tenant loses nothing).

:func:`replay` drives any DecodeEngine-shaped object (``submit(prompt,
tenant=..., priority=..., max_new_tokens=...)`` returning a pollable
stream) open-loop on the arrival clock and records one
:class:`Outcome` per request; :func:`score` folds outcomes into
per-tenant p50/p99 latency and goodput. Everything here is numpy +
stdlib so tests can import the generators without touching jax.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["Arrival", "Outcome", "SCENARIOS", "generate", "replay",
           "score", "diurnal", "flash_crowd", "long_context",
           "adversarial_flood"]


@dataclass(frozen=True)
class Arrival:
    """One scheduled request of a scenario."""
    t: float                  # offset from scenario start, seconds
    tenant: str
    priority: int
    prompt: tuple             # token ids
    max_new: int


@dataclass
class Outcome:
    """What happened to one replayed arrival."""
    tenant: str
    t_submit: float           # offsets from replay start, seconds
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    tokens: int = 0
    status: str = "pending"   # ok | shed | error | timeout
    error: str = ""


def _prompt(rng, vocab, lo, hi):
    n = int(rng.integers(lo, max(hi, lo + 1)))
    return tuple(int(t) for t in rng.integers(0, vocab, size=n))


def _poisson_times(rng, rate_fn, duration_s, cap=10000) -> List[float]:
    """Arrival offsets for an inhomogeneous Poisson process via
    thinning against the rate function's peak."""
    peak = max(rate_fn(duration_s * i / 64.0) for i in range(65))
    if peak <= 0:
        return []
    out, t = [], 0.0
    while len(out) < cap:
        t += float(rng.exponential(1.0 / peak))
        if t >= duration_s:
            break
        if rng.random() * peak <= rate_fn(t):
            out.append(t)
    return out


def diurnal(seed=0, duration_s=3.0, rate=12.0, vocab=512,
            tenants=("tenant-a", "tenant-b"), max_new=12) -> List[Arrival]:
    """A full sinusoidal day compressed into the run: rate swings
    between ~25% and ~175% of the mean, tenants interleaved evenly."""
    rng = np.random.default_rng((seed, 0xD1))
    wave = lambda t: rate * (1.0 + 0.75 * np.sin(
        2.0 * np.pi * t / duration_s))
    out = []
    for i, t in enumerate(_poisson_times(rng, wave, duration_s)):
        out.append(Arrival(t, tenants[i % len(tenants)], 0,
                           _prompt(rng, vocab, 4, 17), max_new))
    return out


def flash_crowd(seed=0, duration_s=3.0, base_rate=6.0, burst_rate=48.0,
                vocab=512, tenants=("tenant-a", "crowd"),
                max_new=12) -> List[Arrival]:
    """A steady baseline tenant plus a crowd tenant that goes from zero
    to ``burst_rate`` for the middle third of the run."""
    rng = np.random.default_rng((seed, 0xF1))
    out = [Arrival(t, tenants[0], 0, _prompt(rng, vocab, 4, 17), max_new)
           for t in _poisson_times(rng, lambda t: base_rate, duration_s)]
    lo, hi = duration_s / 3.0, 2.0 * duration_s / 3.0
    burst = lambda t: burst_rate if lo <= t < hi else 0.0
    out += [Arrival(t, tenants[1], 0, _prompt(rng, vocab, 4, 13), max_new)
            for t in _poisson_times(rng, burst, duration_s)]
    out.sort(key=lambda a: a.t)
    return out


def long_context(seed=0, duration_s=3.0, rate=10.0, vocab=512,
                 tenants=("tenant-a", "straggler"), max_new=10,
                 long_prompt=72, long_max_new=48) -> List[Arrival]:
    """Short interactive traffic beside a straggler tenant whose
    requests carry long prompts and large decode budgets — the
    head-of-line blocking / preemption-victim probe."""
    rng = np.random.default_rng((seed, 0x1C))
    out = [Arrival(t, tenants[0], 1, _prompt(rng, vocab, 4, 13), max_new)
           for t in _poisson_times(rng, lambda t: rate, duration_s)]
    out += [Arrival(t, tenants[1], 0,
                    _prompt(rng, vocab, long_prompt, long_prompt + 9),
                    long_max_new)
            for t in _poisson_times(rng, lambda t: rate / 5.0,
                                    duration_s)]
    out.sort(key=lambda a: a.t)
    return out


def adversarial_flood(seed=0, duration_s=3.0, capacity_rps=8.0,
                      flood_factor=4.0, vocab=512,
                      tenants=("tenant-a", "flood"),
                      max_new=12) -> List[Arrival]:
    """The QoS acceptance scenario: the well-behaved tenant submits at
    half of capacity; the flood tenant submits at ``flood_factor`` x
    capacity with low priority. The fleet must degrade the flood (via
    quota, shed, or preemption) while the well-behaved tenant loses
    nothing and keeps its latency."""
    rng = np.random.default_rng((seed, 0xAD))
    good = _poisson_times(rng, lambda t: capacity_rps / 2.0, duration_s)
    out = [Arrival(t, tenants[0], 1, _prompt(rng, vocab, 4, 13), max_new)
           for t in good]
    flood = _poisson_times(
        rng, lambda t: capacity_rps * flood_factor, duration_s)
    out += [Arrival(t, tenants[1], 0, _prompt(rng, vocab, 4, 13),
                    max_new)
            for t in flood]
    out.sort(key=lambda a: a.t)
    return out


SCENARIOS: Dict[str, Callable[..., List[Arrival]]] = {
    "diurnal": diurnal,
    "flash_crowd": flash_crowd,
    "long_context": long_context,
    "adversarial_flood": adversarial_flood,
}


def generate(name: str, seed: int = 0, **kw) -> List[Arrival]:
    """Build a named scenario's arrival list (same seed, same list)."""
    try:
        gen = SCENARIOS[name]
    except KeyError:
        raise ValueError(
            f"unknown scenario {name!r}; one of {sorted(SCENARIOS)}")
    return gen(seed=seed, **kw)


def replay(engine, arrivals: Sequence[Arrival], timeout_s: float = 120.0,
           speedup: float = 1.0) -> List[Outcome]:
    """Drive the engine open-loop on the arrival clock.

    Submits each arrival when its offset elapses (never gated on
    completions — floods really flood), sweeps every live stream from
    one collector loop (per-stream consumer threads would fight the
    scheduler thread for cycles), and returns one Outcome per arrival.
    A shed submit (typed RESOURCE_EXHAUSTED) is an outcome, not a crash.
    ``speedup`` > 1 compresses the arrival clock."""
    outcomes = [Outcome(a.tenant, a.t / speedup) for a in arrivals]
    streams: Dict[int, object] = {}
    t0 = time.perf_counter()
    nxt = 0
    deadline = t0 + timeout_s
    while (nxt < len(arrivals) or streams) \
            and time.perf_counter() < deadline:
        now = time.perf_counter() - t0
        while nxt < len(arrivals) and arrivals[nxt].t / speedup <= now:
            a, o = arrivals[nxt], outcomes[nxt]
            o.t_submit = now
            try:
                streams[nxt] = engine.submit(
                    np.asarray(a.prompt, np.int32), tenant=a.tenant,
                    priority=a.priority, max_new_tokens=a.max_new)
            except Exception as e:
                code = getattr(e, "code", "")
                o.status = ("shed" if code == "RESOURCE_EXHAUSTED"
                            else "error")
                o.error = str(e).split("\n")[0]
            nxt += 1
        moved = False
        for i in list(streams):
            o = outcomes[i]
            while True:
                try:
                    ev = streams[i].poll()
                except Exception as e:
                    o.status, o.error = "error", repr(e)
                    del streams[i]
                    break
                if ev is None:
                    break
                moved = True
                if ev[0] == "done":
                    o.t_done = time.perf_counter() - t0
                    o.status = "ok"
                    del streams[i]
                    break
                if o.t_first is None:
                    o.t_first = time.perf_counter() - t0
                o.tokens += 1
        if not moved:
            time.sleep(0.0005)
    for i in streams:       # replay deadline: anything still open
        outcomes[i].status = "timeout"
    return outcomes


def _pct(vals, q):
    if not vals:
        return 0.0
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(q * len(vals)))]


def score(outcomes: Sequence[Outcome],
          duration_s: Optional[float] = None) -> Dict[str, dict]:
    """Fold outcomes into per-tenant verdicts: request counts by
    status, p50/p99 completion latency (submit -> done, ms), and
    goodput (completed tokens per second of scenario wall)."""
    if duration_s is None:
        duration_s = max((o.t_done or o.t_submit for o in outcomes),
                         default=0.0) or 1.0
    per: Dict[str, dict] = {}
    for o in outcomes:
        d = per.setdefault(o.tenant, {
            "submitted": 0, "ok": 0, "shed": 0, "error": 0,
            "timeout": 0, "tokens": 0, "_lat": []})
        d["submitted"] += 1
        d[o.status] = d.get(o.status, 0) + 1
        d["tokens"] += o.tokens
        if o.status == "ok" and o.t_done is not None:
            d["_lat"].append((o.t_done - o.t_submit) * 1e3)
    out = {}
    for tenant, d in per.items():
        lat = d.pop("_lat")
        out[tenant] = {
            **d,
            "lost": d["submitted"] - d["ok"],
            "p50_ms": round(_pct(lat, 0.50), 3),
            "p99_ms": round(_pct(lat, 0.99), 3),
            "goodput_tps": round(d["tokens"] / duration_s, 3),
        }
    return out
