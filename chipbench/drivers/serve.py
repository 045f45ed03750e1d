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
"""
import json
import time

import numpy as np

from chipbench import harness, stats, traffic, weights
from chipbench.reference import gpt as reference

SWEEP_S = 0.001
CHECK_PROMPT_LEN = 40
CHECK_STEPS = 8


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
    client.record = {"plen": len(prompt), "max_new": max_new, "t_submit": t,
                     "times": [], "done": False, "error": None}
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


def check_against_reference(engine, sizes, seed):
    """Criterion (a): the functions the engine itself jits, on the
    engine's parameters and page size -- prefill of one seeded prompt,
    its K/V written into pages, then eight decode steps through the
    paged cache -- against the plain reference's full forward pass over
    the same tokens. Returns the relative logit error."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.batching import next_bucket
    from paddle_tpu.models.gpt import gpt_paged_decode_fns

    cfg = engine.cfg
    pt = engine.page_tokens
    L, nh, D = cfg.layers, cfg.heads, cfg.head_dim
    prefill_fn, step_fn = gpt_paged_decode_fns(
        cfg, eps=engine.eps, page_tokens=pt)
    rng = np.random.default_rng(traffic.seed_words(seed) + [4])
    plen = CHECK_PROMPT_LEN
    prompt = rng.integers(0, cfg.vocab_size, plen)
    rung = next_bucket(plen, engine.kv_ladder)
    inp = np.zeros((1, rung), np.int32)
    inp[0, :plen] = prompt
    logits, k, v = jax.jit(prefill_fn)(
        engine.params, jnp.asarray(inp), jnp.asarray([plen], np.int32))
    # a private pool: pages 1..W hold the sequence, page 0 is the null
    # page, as in the engine
    W = -(-(plen + CHECK_STEPS) // pt)
    pool = jnp.zeros((L, W + 1, pt, nh, D), jnp.float32)
    rows = W * pt
    kr = jnp.zeros((L, rows, nh, D), jnp.float32).at[:, :plen].set(
        k[:, 0, :plen])
    vr = jnp.zeros((L, rows, nh, D), jnp.float32).at[:, :plen].set(
        v[:, 0, :plen])
    k_pool = pool.at[:, 1:].set(kr.reshape(L, W, pt, nh, D))
    v_pool = pool.at[:, 1:].set(vr.reshape(L, W, pt, nh, D))
    tables = jnp.asarray(np.arange(1, W + 1, dtype=np.int32)[None])
    step = jax.jit(step_fn)
    got = [np.asarray(logits)[0]]
    toks = list(int(t) for t in prompt)
    for i in range(CHECK_STEPS):
        toks.append(int(np.argmax(got[-1])))
        lg, k_pool, v_pool = step(
            engine.params, k_pool, v_pool, tables,
            jnp.asarray([toks[-1]], np.int32),
            jnp.asarray([plen + i], np.int32))
        got.append(np.asarray(lg)[0])
    ref = jax.jit(reference.forward, static_argnums=(2, 3))(
        weights.to_reference(engine.params), jnp.asarray(toks, jnp.int32),
        sizes["heads"], sizes["eps"])
    want = np.asarray(ref)[plen - 1:plen + CHECK_STEPS]
    return reference.relative_error(np.stack(got), want)


def largest_temp_bytes(engine):
    """The largest temporary of an executable the engine holds, by the
    compiler's own `memory_analysis()` (see `harness.device_json`)."""
    caches = [getattr(engine, name, None)
              for name in ("_step_aot", "_prefill_aot", "_write_aot")]
    return harness.program_temp_bytes(
        c.get(k) for c in caches if c is not None for k in c.keys())


def run(bench, cell, mix, seed, seconds, trace, t_process_start,
        require_tpu=True, engine_kw=None):
    clock = harness.SetupClock(t_process_start)
    devs = harness.require_devices(cell["chips"], require_tpu)
    import jax

    from paddle_tpu import framework, profiler
    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.jit.compile_cache import setup_compilation_cache
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.observability import tracez

    setup_compilation_cache()
    clock.mark("import")
    _, sizes = harness.load_config(bench, cell["config"])
    cfg = GPTConfig(vocab_size=sizes["vocab_size"],
                    max_seq_len=sizes["max_seq_len"],
                    hidden=sizes["hidden"], layers=sizes["layers"],
                    heads=sizes["heads"])
    shapes = jax.eval_shape(lambda: framework.param_arrays(GPT(cfg)))
    params = weights.make_params(shapes, seed)
    jax.block_until_ready(params)
    clock.mark("weights")
    engine = DecodeEngine(cfg=cfg, params=params, eps=sizes["eps"],
                          **(engine_kw or {}))
    clock.mark("slot_sizing")
    try:
        return _measure(bench, cell, mix, seed, seconds, trace, clock, devs,
                        engine, sizes, profiler, tracez)
    finally:
        engine.stop()


def _measure(bench, cell, mix, seed, seconds, trace, clock, devs, engine,
             sizes, profiler, tracez):
    warmed = warm_reachable(engine, mix)
    clock.mark("warm_up")
    rel_err = check_against_reference(engine, sizes, seed)
    clock.mark("reference_check")

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
    while time.perf_counter() < t_end:
        if not _sweep(engine, clients, source, records):
            time.sleep(SWEEP_S)
    t_close = time.perf_counter()
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
                                 profiler.compile_events()][-8:],
         "reference_rel_err": rel_err}), flush=True)

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
    correct = (rel_err <= reference.LOGIT_TOL and compiles_in_window == 0
               and not any(_broken(r) for r in records) and n_tokens > 0)
    print("WINDOW " + json.dumps(
        {"window_s": t_close - t_open, "tokens": n_tokens,
         "gaps": len(gaps), "first_tokens": len(ttfts),
         "attempted": len(attempted), "failed": len(failed),
         "compiles_in_window": compiles_in_window,
         "engine_steps": stats1["steps"] - stats0["steps"],
         "engine_tokens": stats1["tokens"] - stats0["tokens"],
         "itl_p50_ms": _ms(stats.percentile(gaps, 50)),
         "ttft_p95_ms": _ms(stats.percentile(ttfts, 95)),
         "ring_events": None if ring is None else len(ring),
         "largest_temp_bytes": temp_bytes,
         "ticks": _tick_summary(ring),
         "end_to_end": values}), flush=True)

    return harness.result_line(
        bench, cell, mix, sizes, (t_open, t_close), trace, values, correct,
        len(attempted), len(failed), devs, traced, temp_bytes,
        ring=ring, records=records, slots=engine.max_slots,
        engine_stats=(stats0, stats1))


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
