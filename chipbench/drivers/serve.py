"""Serving driver: an in-process ``DecodeEngine`` under a closed loop.

The engine is built as ``serve.py --decode`` builds it (default slot
sizing, default page size, prefix cache at its default) on weights made
on the device from the seed. One closed-loop client per slot
(``clients_per_slot`` of the traffic file) runs from ONE collector
thread, which sweeps the streams once a millisecond, stamps every token
as it reads it and submits a client's next request when it reads the
end of the last: few threads, and the engine keeps the interpreter.

Before the window a client starts when the one before it has its first
token, so the streams enter on successive ticks: every output length of
a grid is a multiple of 8 or 16, a closed-loop stream's period is its
length in ticks, and streams that start on one tick would end, and be
admitted again, on the same ticks for ever. The window opens once every
client has completed one request and closes ``--seconds`` later. Tokens,
gaps and first tokens are counted by their own timestamps between the
marks (``chipbench.stats``); requests in flight at the close are not
failures: the engine is stopped under them.

What decides ``correct`` is what the window served. Once it has closed,
the peak memory has been read and the engine is stopped and let go, a
seeded sample of the requests the window finished (`CHECK_REQUESTS`,
the longest among them) goes through the family's plain reference, one
full forward pass over each prompt with its served tokens, and the
mean, over those served tokens, of how far each one's logit lies below
the reference's best at its position is held to the family's limit
(`served_gap_mean`). That is off the set-up clock and outside the
window.
"""
import gc
import json
import time

import numpy as np

from chipbench import harness, stats, traffic, weights

SWEEP_S = 0.001
CHECK_REQUESTS = 128    # finished requests held against the reference


class Client:
    """One closed-loop caller: its open stream and what it has seen."""

    def __init__(self):
        self.stream = None
        self.record = None
        self.completed = 0


def _submit(engine, client, source, records):
    prompt, max_new = next(source)
    t = time.perf_counter()
    client.stream = engine.submit(prompt, max_new_tokens=max_new,
                                  temperature=0.0)
    client.record = {"prompt": prompt, "plen": len(prompt),
                     "max_new": max_new, "t_submit": t, "times": [],
                     "tokens": None, "done": False, "error": None}
    records.append(client.record)


def _sweep(engine, clients, source, records):
    """Read every event that is ready; resubmit for finished clients.
    Returns the number of events read."""
    from paddle_tpu.inference.errors import TypedServeError

    n = 0
    for c in clients:
        while True:
            try:
                ev = c.stream.poll()
            except TypedServeError as err:
                c.record["error"] = str(err)
                ev = ("done",)
            if ev is None:
                break
            n += 1
            if ev[0] == "token":
                c.record["times"].append(time.perf_counter())
                continue
            c.record["done"] = True           # "done" (or a typed error)
            c.record["tokens"] = ev[1] if len(ev) > 1 else None
            c.completed += 1
            _submit(engine, c, source, records)
    return n


def reachable(engine, mix):
    """The rungs this cell's traffic can reach, from the traffic file's
    lengths and the engine's public ladders: prefill (and page-write)
    rungs of the prompt grid, page-table rungs from the shortest prompt
    to the longest request, every batch rung."""
    from paddle_tpu.inference.batching import next_bucket

    pt = engine.page_tokens
    kv = sorted({next_bucket(p, engine.kv_ladder)
                 for p in mix["prompt_lens"]})
    lo = next_bucket(-(-min(mix["prompt_lens"]) // pt), engine.page_ladder)
    hi = next_bucket(-(-traffic.longest_request(mix) // pt),
                     engine.page_ladder)
    pages = sorted({w for w in engine.page_ladder if lo <= w <= hi}
                   | {-(-r // pt) for r in kv})
    return kv, pages, list(engine.batch_ladder)


def warm_reachable(engine, mix):
    """`engine.warmup()` over the reachable rungs only: its public
    ladders are narrowed for the call and put back. No request is in
    flight, so the scheduler reads none of them meanwhile."""
    kv, pages, batch = reachable(engine, mix)
    full = engine.kv_ladder, engine.page_ladder, engine.batch_ladder
    engine.kv_ladder, engine.page_ladder, engine.batch_ladder = \
        kv, pages, batch
    try:
        engine.warmup()
    finally:
        engine.kv_ladder, engine.page_ladder, engine.batch_ladder = full
    return {"prefill_rungs": kv, "page_rungs": pages, "batch_rungs": batch}


def finished_sample(records, t_open, t_close, seed, n):
    """At most `n` of the requests that were submitted and read to
    their end between the marks, drawn from the seed, the longest
    (prompt + served tokens) always among them."""
    done = [r for r in records
            if r["done"] and r["tokens"] and not _broken(r)
            and stats.in_window(r["t_submit"], t_open, t_close)]
    if len(done) <= n:
        return done
    longest = max(range(len(done)),
                  key=lambda i: done[i]["plen"] + len(done[i]["tokens"]))
    rng = np.random.default_rng(traffic.seed_words(seed) + [4])
    others = [int(i) for i in rng.permutation(len(done)) if i != longest]
    return [done[i] for i in sorted([longest] + others[:n - 1])]


def served_gaps(family, sizes, params, sample, pad_to, control=False):
    """For every served token of the sampled requests, how far its
    logit lies below the reference's best at its position (the
    family's measure), as one array: the reference runs once over each
    prompt with its served tokens, on `params`, the weights as the
    benchmark made them. `control` judges the family's control in the
    served tokens' place (`chipbench/control.py` and a test; a
    benchmark run never does)."""
    ref = family.to_reference(params)
    gaps = [family.served_gaps(
        ref, [int(t) for t in r["prompt"]] + list(r["tokens"]), sizes,
        pad_to, control=control)[r["plen"] - 1:] for r in sample]
    return np.concatenate(gaps) if gaps else np.zeros(0)


def largest_temp_bytes(engine):
    """The largest temporary of an executable the engine holds, by the
    compiler's own `memory_analysis()` (see `harness.device_json`)."""
    caches = [getattr(engine, name, None)
              for name in ("_step_aot", "_prefill_aot")]
    return harness.program_temp_bytes(
        c.get(k) for c in caches if c is not None for k in c.keys())


def serve_window(bench, cell, mix, seed, seconds, trace, t_process_start,
                 require_tpu=True, engine_kw=None, control=False):
    """Set-up, the window and the engine's end. Returns the result
    line without the served check, the sampled requests, and what the
    reference needs: (family, sizes, params). `control`: the engine is
    the family's control, the program's own path one precision down."""
    clock = harness.SetupClock(t_process_start)
    devs = harness.require_devices(cell["chips"], require_tpu)
    import jax

    from paddle_tpu import profiler
    from paddle_tpu.jit.compile_cache import setup_compilation_cache
    from paddle_tpu.observability import tracez

    setup_compilation_cache()
    clock.mark("import")
    _, sizes, family = harness.load_config(bench, cell["config"])
    params = weights.make_params(family.param_shapes(sizes), seed,
                                 family.fill)
    jax.block_until_ready(params)
    clock.mark("weights")
    engine = family.serving_engine(sizes, params, control=control,
                                   **(engine_kw or {}))
    clock.mark("slot_sizing")
    try:
        out, sample = _measure(bench, cell, mix, seed, seconds, trace, clock,
                               devs, engine, sizes, family, profiler, tracez)
    finally:
        engine.stop()
    # the window has closed and the peak memory is read: the engine's
    # pools and programs go before the reference runs
    del engine
    gc.collect()
    return out, sample, (family, sizes, params)


def run(bench, cell, mix, seed, seconds, trace, t_process_start,
        require_tpu=True, engine_kw=None, control=None):
    """`control`, never set by a benchmark run, puts a control in the
    program's place: "program", the program's own path one precision
    down (the engine built so); "reference", the family's reference
    one precision down, judged in the served tokens' place."""
    out, sample, (family, sizes, params) = serve_window(
        bench, cell, mix, seed, seconds, trace, t_process_start,
        require_tpu, engine_kw, control == "program")
    import jax

    held = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    t0 = time.perf_counter()
    gaps = served_gaps(family, sizes, params, sample,
                       traffic.longest_request(mix),
                       control=control == "reference")
    gap = float(gaps.mean()) if len(gaps) else None
    print("SERVED " + json.dumps(
        {"requests_compared": len(sample), "tokens_compared": len(gaps),
         "longest": max((r["plen"] + len(r["tokens"]) for r in sample),
                        default=None),
         "served_gap_mean": gap,
         "served_gap_widest": float(gaps.max()) if len(gaps) else None,
         "not_the_best_share": float((gaps > 0).mean()) if len(gaps)
         else None, "control": control,
         "bytes_in_use_before_reference": held,
         "reference_s": round(time.perf_counter() - t0, 3)}), flush=True)
    harness.add_check(out, "served_gap_mean", gap, family.GAP_TOL)
    return out


def _measure(bench, cell, mix, seed, seconds, trace, clock, devs, engine,
             sizes, family, profiler, tracez):
    warmed = warm_reachable(engine, mix)
    clock.mark("warm_up")

    n_clients = engine.max_slots * int(mix["clients_per_slot"])
    source = traffic.requests(mix, sizes["vocab_size"], seed)
    clients = [Client() for _ in range(n_clients)]
    records = []
    # pre-window: a client starts when the one before it has its first
    # token, so the streams enter on successive ticks and are out of
    # step from the start; then every client completes one request
    started = 0
    limit = time.perf_counter() + 600.0
    while started < n_clients or min(c.completed for c in clients) < 1:
        if started < n_clients and (
                started == 0 or clients[started - 1].record["times"]):
            _submit(engine, clients[started], source, records)
            started += 1
        if not _sweep(engine, clients[:started], source, records):
            time.sleep(SWEEP_S)
        if time.perf_counter() > limit:
            raise RuntimeError("pre-window did not finish in 600 s")
    # what set-up left behind is collected now: a full collection stops
    # every thread for 0.18 s (PERF.md section 6), and where the first
    # one falls would otherwise follow from what set-up happened to
    # allocate. The window's own garbage is collected as it comes.
    gc.collect()
    clock.mark("pre_window")
    compiles_pre = len(profiler.compile_events())
    tracez.RING.clear()
    stats0 = engine.stats()
    setup_s = clock.total()
    t_open = time.perf_counter()
    tracer = harness.MidWindowTrace(t_open, seconds,
                                    mix.get("trace_seconds", 3.0)) \
        if trace else None
    t_end = t_open + seconds
    gc_watch = harness.GcWatch()
    while time.perf_counter() < t_end:
        if not _sweep(engine, clients, source, records):
            time.sleep(SWEEP_S)
    t_close = time.perf_counter()
    collections = gc_watch.stop()
    compiles_in_window = len(profiler.compile_events()) - compiles_pre
    stats1 = engine.stats()
    ring = harness.ring_events(t_open, t_close)
    temp_bytes = largest_temp_bytes(engine)
    traced = tracer.result() if tracer else None

    print("SETUP " + json.dumps(
        {"parts": clock.parts, "setup_s": round(setup_s, 3),
         "slots": engine.max_slots, "warmed": warmed,
         "compiles_total": compiles_pre,
         "compiles_pre_window": [e["label"] for e in
                                 profiler.compile_events()][-8:]}),
          flush=True)

    # ---- end-to-end numbers, by token events between the marks
    token_times = [t for r in records for t in r["times"]]
    n_tokens = stats.count_in_window(token_times, t_open, t_close)
    gaps = stats.token_gaps((r["times"] for r in records), t_open, t_close)
    ttfts = stats.first_token_latencies(
        ((r["t_submit"], r["times"][0] if r["times"] else None)
         for r in records), t_open, t_close)
    attempted = [r for r in records
                 if stats.in_window(r["t_submit"], t_open, t_close)]
    failed = [r for r in attempted if _broken(r)]
    values = {
        "serve_tokens_per_s": stats.rate(n_tokens, t_open, t_close),
        "ttft_p50_ms": _ms(stats.percentile(ttfts, 50)),
        "itl_p95_ms": _ms(stats.percentile(gaps, 95)),
        "setup_s": setup_s,
    }
    broken = sum(_broken(r) for r in records)
    checks = {"compiles_in_window": (compiles_in_window, 0),
              "broken_streams": (broken, 0)}
    correct = compiles_in_window == 0 and not broken and n_tokens > 0
    print("WINDOW " + json.dumps(
        {"window_s": t_close - t_open, "tokens": n_tokens,
         "gaps": len(gaps), "first_tokens": len(ttfts),
         "attempted": len(attempted), "failed": len(failed),
         "compiles_in_window": compiles_in_window,
         "engine_steps": stats1["steps"] - stats0["steps"],
         "engine_tokens": stats1["tokens"] - stats0["tokens"],
         "itl_p50_ms": _ms(stats.percentile(gaps, 50)),
         "itl_max_ms": _ms(max(gaps, default=None)),
         "gc": collections,
         "ttft_p95_ms": _ms(stats.percentile(ttfts, 95)),
         "ring_events": None if ring is None else len(ring),
         "largest_temp_bytes": temp_bytes,
         "ticks": _tick_summary(ring),
         "end_to_end": values}), flush=True)

    out = harness.result_line(
        bench, cell, mix, sizes, family, (t_open, t_close), trace, values,
        correct, len(attempted), len(failed), devs, traced, temp_bytes,
        checks, ring=ring, records=records, slots=engine.max_slots,
        engine_stats=(stats0, stats1))
    return out, finished_sample(records, t_open, t_close, seed,
                                CHECK_REQUESTS)


def _tick_summary(ring):
    """Where a tick's time went, for the line a reader sees first: the
    step executable and the host around it, median and 95th percentile
    in ms, and the page rungs the steps ran at."""
    from chipbench import ringread

    step = [1e3 * d for _, d in ringread.spans(ring, "exec:decode.pstep")]
    host = ringread.self_ms(ring, "decode.step", ["exec:decode.pstep"])
    rungs = {}
    for a in ringread.span_args(ring, "decode.step"):
        rungs[a.get("w_rung")] = rungs.get(a.get("w_rung"), 0) + 1
    return {"step_ms": [stats.percentile(step, 50),
                        stats.percentile(step, 95)],
            "host_ms": [stats.percentile(host, 50),
                        stats.percentile(host, 95)],
            "page_rungs": rungs}


def _broken(r):
    """A stream that ended in a typed error, or emitted other than its
    `max_new` tokens (greedy, no EOS: exactly that many are due)."""
    return (r["error"] is not None or len(r["times"]) > r["max_new"]
            or (r["done"] and len(r["times"]) != r["max_new"]))


def _ms(seconds):
    return None if seconds is None else 1e3 * seconds
