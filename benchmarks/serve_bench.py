"""Serving-engine benchmark: batched vs per-request-serialized inference.

Open-loop client over a synthetic MLP with MIXED request shapes (rows
1..4 of a [None, 64] f32 input): the serialized mode replays the legacy
daemon behavior (one ``Predictor.run`` per request, in order), the
batched mode drives the DynamicBatcher + per-bucket AOT engine
(inference/batching.py) with every request submitted up front —
arrivals are not gated on completions.

Prints ONE JSON line; the load-bearing fields:
  batched_reqs_per_s / serial_reqs_per_s / speedup  (target: >= 3x at
      max_batch_size >= 8)
  batch_occupancy, padding_waste, p50/p95/p99_latency_ms  (profiler
      serve stats for the batched run)
  warmup_compiles, compile_count  (compile_count = compiles observed
      AFTER warmup during the measured stream; the compile-bounded
      engine's contract is 0)

Runs on whatever backend JAX selects; ``JAX_PLATFORMS=cpu`` is how the
test suite runs it. A backend that fails to initialise, or any exception
in a mode, is a traceback and a non-zero exit.

    python benchmarks/serve_bench.py [--requests 400] [--max-batch 16]
    python benchmarks/serve_bench.py --decode   # continuous batching vs
                                                # sequential generation
    python benchmarks/serve_bench.py --decode --speculate-k 8
        # speculative decoding (draft-and-verify) vs the plain engine on
        # a repetitive-continuation workload; scored as accepted
        # tokens/s (target: >= 1.5x)
    python benchmarks/serve_bench.py --disagg --router 2
        # disaggregated 1-prefill + 2-decode fleet with KV-page handoff
        # vs a 3-unified colocated fleet; scored on decode-stream stall,
        # TTFT, handoff cost, output identity, zero compiles
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def run_bench(args):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import profiler
    from paddle_tpu.inference import Config, Predictor
    from paddle_tpu.inference.batching import DynamicBatcher
    from paddle_tpu.static import InputSpec

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(64, 256)
            self.fc2 = nn.Linear(256, 64)

        def forward(self, x):
            import paddle_tpu.nn.functional as F
            return self.fc2(F.relu(self.fc1(x)))

    paddle.seed(0)
    prefix = os.path.join(tempfile.mkdtemp(prefix="serve_bench_"), "mlp")
    paddle.jit.save(MLP(), prefix,
                    input_spec=[InputSpec([None, 64], "float32")])

    rng = np.random.default_rng(args.seed)
    row_mix = (1, 2, 1, 4)     # mixed request shapes, single-row-heavy
    requests = [rng.normal(size=(row_mix[i % len(row_mix)], 64))
                .astype(np.float32) for i in range(args.requests)]

    # --- serialized mode: the legacy daemon loop (one run per request,
    # global order). Warm each distinct shape first so the comparison is
    # steady-state dispatch, not compile time.
    serial_pred = Predictor(Config(prefix))
    for r in row_mix:
        serial_pred.run([np.zeros((r, 64), np.float32)])
    t0 = time.perf_counter()
    for x in requests:
        serial_pred.run([x])
    serial_s = time.perf_counter() - t0
    serial_rps = args.requests / serial_s

    # --- batched mode: fresh predictor + batcher, full warmup, then an
    # open-loop submit of the whole stream.
    profiler.reset_serve_stats()
    batched_pred = Predictor(Config(prefix))
    batcher = DynamicBatcher(batched_pred, max_batch_size=args.max_batch,
                             batch_timeout_ms=args.batch_timeout_ms)
    warmup_compiles = batcher.warmup()
    c0 = len(profiler.compile_events())
    t0 = time.perf_counter()
    futs = [batcher.submit([x]) for x in requests]
    for f in futs:
        f.result(timeout=300)
    batched_s = time.perf_counter() - t0
    batcher.stop()
    batched_rps = args.requests / batched_s
    steady_compiles = len(profiler.compile_events()) - c0

    from paddle_tpu.observability import REGISTRY
    stats = profiler.serve_stats()
    speedup = batched_rps / serial_rps if serial_rps > 0 else 0.0
    return {
        "metric": "serve_throughput",
        "value": round(batched_rps, 2),
        "unit": "reqs/s",
        # north star: >= 3x over the serialized daemon at max_batch >= 8
        "vs_baseline": round(speedup / 3.0, 3),
        "requests": args.requests,
        "max_batch_size": args.max_batch,
        "batch_timeout_ms": args.batch_timeout_ms,
        "serial_reqs_per_s": round(serial_rps, 2),
        "batched_reqs_per_s": round(batched_rps, 2),
        "speedup": round(speedup, 3),
        "batch_occupancy": stats["batch_occupancy"],
        "padding_waste": stats["padding_waste"],
        "queue_depth_max": stats["queue_depth_max"],
        "p50_latency_ms": stats["p50_latency_ms"],
        "p95_latency_ms": stats["p95_latency_ms"],
        "p99_latency_ms": stats["p99_latency_ms"],
        "warmup_compiles": warmup_compiles,
        "compile_count": steady_compiles,
        # raw registry samples behind the derived numbers above (the
        # serve_* families only — the bench result stays shape-stable)
        "metrics": {k: v for k, v in REGISTRY.flat().items()
                    if k.startswith("paddle_tpu_serve_")},
    }


def _pct(sorted_vals, q):
    """Nearest-rank percentile over an already-sorted list (ms units
    are the caller's problem); 0.0 on empty input."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def _drive_decode(eng, prompts, max_new):
    """Open-loop continuous phase: submit every prompt up front against
    an already-warm engine, then consume every stream from ONE sweeping
    collector (`stream.poll()`). A consumer thread per stream would
    wake on every token and fight the scheduler thread for cycles —
    distorting exactly the number this bench exists to measure — so the
    sweep drains whatever arrived, timestamps each burst, and naps
    briefly when nothing moved. Returns the aggregate wall clock plus
    per-stream detail: TTFT, steady-state ms/token (first -> last
    token, so queueing doesn't pollute the decode rate), generated
    tokens, and speculative acceptance when the engine reports it
    (``stream.spec_drafted`` stays 0 on the plain engine)."""
    n = len(prompts)
    outs = [[] for _ in range(n)]
    first = [None] * n
    last = [None] * n
    t_sub = [0.0] * n
    errors = []
    t0 = time.perf_counter()
    streams = []
    for i, p in enumerate(prompts):
        t_sub[i] = time.perf_counter()
        streams.append(eng.submit(p, max_new_tokens=max_new))
    open_idx = set(range(n))
    deadline = time.perf_counter() + 600
    while open_idx and time.perf_counter() < deadline:
        moved = False
        for i in list(open_idx):
            while True:
                try:
                    ev = streams[i].poll()
                except Exception as e:
                    errors.append(repr(e))
                    open_idx.discard(i)
                    break
                if ev is None:
                    break
                moved = True
                if ev[0] == "done":
                    open_idx.discard(i)
                    break
                now = time.perf_counter()
                if first[i] is None:
                    first[i] = now
                last[i] = now
                outs[i].append(int(ev[1]))
        if not moved:
            time.sleep(0.0005)
    wall_s = time.perf_counter() - t0
    ttfts, ms_per_tok, accept = [], [], []
    for i, s in enumerate(streams):
        got = len(outs[i])
        if first[i] is not None:
            ttfts.append(first[i] - t_sub[i])
            if got >= 2:
                ms_per_tok.append((last[i] - first[i]) / (got - 1) * 1e3)
            else:
                ms_per_tok.append((last[i] - t_sub[i]) * 1e3)
        if s.spec_drafted:
            accept.append(s.spec_accepted / s.spec_drafted)
    return {
        "wall_s": wall_s,
        "tokens": sum(len(o) for o in outs),
        "outs": outs,
        "ttfts": sorted(ttfts),
        "ms_per_tok": sorted(ms_per_tok),
        "accept": sorted(accept),
        "errors": errors,
    }


def _kv_quant_probe(cfg, model, prompt, page_tokens):
    """Max |logits_fp32 - logits_int8| across a paged prefill + one
    decode step on one prompt — the logit error of KV-page quantization
    alone (the weights stay fp32), measured on the bench model."""
    import jax.numpy as jnp
    from paddle_tpu import framework
    from paddle_tpu.models.gpt import (gpt_paged_decode_fns,
                                       gpt_paged_prefill_fns)
    from paddle_tpu.quant.kv import kv_pool_zeros

    params = {k: jnp.asarray(v)
              for k, v in framework.param_arrays(model).items()}
    pt = int(page_tokens)
    toks = np.asarray(prompt, np.int32)[None]
    plen = toks.shape[1]
    W = -(-(plen + 1) // pt)
    shape = (cfg.layers, W + 2, pt, cfg.heads, cfg.head_dim)
    paged_prefill = gpt_paged_prefill_fns(cfg, page_tokens=pt)
    _, paged_step = gpt_paged_decode_fns(cfg, page_tokens=pt)
    tables = jnp.asarray(np.arange(1, W + 1, dtype=np.int32)[None])
    nlen = jnp.asarray([plen], jnp.int32)
    out = {}
    last = None
    for dt in ("float32", "int8"):
        kp = kv_pool_zeros(shape, dt)
        vp = kv_pool_zeros(shape, dt)
        logits, kp, vp = paged_prefill(params, kp, vp,
                                       jnp.asarray(toks), tables, nlen)
        if last is None:      # both arms step on the fp32 arm's argmax
            last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        step_logits, kp, vp = paged_step(params, kp, vp, tables,
                                         last, nlen)
        out[dt] = np.asarray(step_logits)
    return float(np.max(np.abs(out["float32"] - out["int8"])))


def run_decode_bench(args):
    """Decode mode: continuous batching vs one-request-at-a-time
    autoregressive generation on a tiny GPT (inference/decode.py).

    Open loop: every prompt is submitted up front; the engine admits
    them into free KV slots between steps. The baseline runs the SAME
    engine code with max_slots=1 and gates each submit on the previous
    completion — i.e. the naive serving loop. Contract: >= 2x aggregate
    tokens/s at concurrency >= 8 with compile_count == 0 after warmup.

    With ``--speculate-k`` the bench instead scores draft-and-verify
    speculative decoding against the plain continuous engine (see
    run_spec_decode_bench)."""
    import threading

    if args.speculate_k:
        return run_spec_decode_bench(args)

    from paddle_tpu import profiler
    from paddle_tpu.inference.decode import (DecodeEngine, kv_page_bytes,
                                             kv_slot_bytes, next_bucket)
    from paddle_tpu.models.gpt import GPT, gpt_tiny
    from paddle_tpu.observability import REGISTRY

    cfg = gpt_tiny()
    model = GPT(cfg)
    kv_dtype = getattr(args, "kv_dtype", None) or "float32"
    rng = np.random.default_rng(args.seed)
    max_new = args.decode_tokens or 32
    if args.shared_prefix:
        # shared-system-prompt workload: N requests, one long common
        # head (page-aligned at the default 16-token pages) + a short
        # unique tail each — the prefix cache's target case
        n = args.shared_prefix
        head_len = 96
        max_new = min(max_new, cfg.max_seq_len - head_len - 8)
        head = rng.integers(0, cfg.vocab_size, size=head_len)
        prompts = [np.concatenate([
            head, rng.integers(0, cfg.vocab_size,
                               size=int(rng.integers(2, 7)))
        ]).astype(np.int32) for _ in range(n)]
    else:
        n = args.decode_requests
        prompts = [rng.integers(
            0, cfg.vocab_size,
            size=int(rng.integers(4, 25))).astype(np.int32)
            for _ in range(n)]

    # --- baseline: one request at a time (slot pool of 1, next submit
    # gated on the previous completion). Same kernels, same warmup.
    base = DecodeEngine(model, max_slots=1, max_new_tokens=max_new,
                        kv_dtype=kv_dtype)
    base_warmup = base.warmup()
    t0 = time.perf_counter()
    base_tokens = 0
    for p in prompts:
        base_tokens += len(
            base.submit(p, max_new_tokens=max_new).result(timeout=300))
    base_s = time.perf_counter() - t0
    base.stop()
    base_tps = base_tokens / base_s if base_s > 0 else 0.0

    # --- continuous batching: all prompts in flight at once, per-stream
    # TTFT measured from submit to first token event.
    eng = DecodeEngine(model, max_slots=args.decode_slots,
                       max_new_tokens=max_new, max_pending=n,
                       kv_dtype=kv_dtype)
    warmup_compiles = eng.warmup()
    c0 = len(profiler.compile_events())
    m0 = {k: float(v) for k, v in REGISTRY.flat().items()
          if k.startswith("paddle_tpu_decode_prefix_")}

    from paddle_tpu.observability import memz as _memz
    oom0 = len(_memz.oom_dumps())
    occupancy_samples = []
    frag_samples = []
    peak_pages = [0]
    tenant_peaks = {}
    run_done = threading.Event()

    def sample_occupancy():
        while not run_done.wait(0.005):
            st = eng.stats()
            pg = st["pages"]
            peak_pages[0] = max(peak_pages[0], pg["pages_used"])
            frag_samples.append(pg["fragmentation"])
            for t, pages in pg.get("tenants", {}).items():
                tenant_peaks[t] = max(tenant_peaks.get(t, 0), pages)
            if st["active"] or st["pending"]:
                occupancy_samples.append(st["active"] / st["max_slots"])

    sampler = threading.Thread(target=sample_occupancy, daemon=True)
    sampler.start()
    drive = _drive_decode(eng, prompts, max_new)
    run_done.set()
    sampler.join(timeout=10)
    steady_compiles = len(profiler.compile_events()) - c0
    st = eng.stats()
    eng.stop()

    wall_s = drive["wall_s"]
    errors = drive["errors"]
    cont_tokens = drive["tokens"]
    cont_tps = cont_tokens / wall_s if wall_s > 0 else 0.0
    speedup = cont_tps / base_tps if base_tps > 0 else 0.0
    ts = drive["ttfts"]

    def pct(q):
        return round(_pct(ts, q) * 1e3, 3)

    occ = round(sum(occupancy_samples) / len(occupancy_samples), 4) \
        if occupancy_samples else 0.0

    # paged-KV scorecard: prefix-cache efficiency and HBM per slot vs
    # what the old contiguous (batch-rung x kv-rung) pool would reserve
    m1 = {k: float(v) for k, v in REGISTRY.flat().items()
          if k.startswith("paddle_tpu_decode_prefix_")}
    hit_toks = m1.get("paddle_tpu_decode_prefix_hit_tokens_total", 0.0) \
        - m0.get("paddle_tpu_decode_prefix_hit_tokens_total", 0.0)
    lookup_toks = \
        m1.get("paddle_tpu_decode_prefix_lookup_tokens_total", 0.0) \
        - m0.get("paddle_tpu_decode_prefix_lookup_tokens_total", 0.0)
    hit_rate = hit_toks / lookup_toks if lookup_toks else 0.0
    pages_peak = max(peak_pages[0], st["pages"]["pages_used"])
    page_bytes = kv_page_bytes(cfg, st["page_tokens"], st["kv_dtype"])
    slots = max(args.decode_slots, 1)
    longest = min(max(len(p) for p in prompts) + max_new,
                  cfg.max_seq_len)
    contig_per_slot = kv_slot_bytes(
        cfg, next_bucket(longest, eng.kv_ladder))
    # --kv-dtype int8: an fp32 comparison arm over the SAME prompts,
    # reported side by side — throughput, HBM per slot, greedy stream
    # identity, and the one-step logit error of KV quantization alone
    quant_compare = None
    if kv_dtype == "int8":
        ref = DecodeEngine(model, max_slots=args.decode_slots,
                           max_new_tokens=max_new, max_pending=n)
        ref.warmup()
        ref_drive = _drive_decode(ref, prompts, max_new)
        ref_st = ref.stats()
        ref.stop()
        ref_tps = ref_drive["tokens"] / ref_drive["wall_s"] \
            if ref_drive["wall_s"] > 0 else 0.0
        fp32_page_bytes = kv_page_bytes(cfg, ref_st["page_tokens"])
        ref_peak = ref_st["pages"]["high_watermark"]
        int8_peak = st["pages"]["high_watermark"]
        quant_compare = {
            "tokens_per_s": {"float32": round(ref_tps, 2),
                             "int8": round(cont_tps, 2)},
            "hbm_bytes_per_slot": {
                "float32": int(ref_peak * fp32_page_bytes // slots),
                "int8": int(int8_peak * page_bytes // slots)},
            "hbm_reduction": round(fp32_page_bytes / page_bytes, 3),
            "outputs_match": drive["outs"] == ref_drive["outs"],
            "acceptance_rate": 1.0,
            "logits_max_abs_err": round(
                _kv_quant_probe(cfg, model, prompts[0],
                                st["page_tokens"]), 6),
        }
    # tracez artifact + continuous-profiler summary: the run's event
    # ring rendered as Chrome trace-event JSON (load in ui.perfetto.dev)
    # plus the per-executable top-5 by total host-blocked time
    from paddle_tpu.observability import PROFILER, RING
    trace_file = os.path.join(
        tempfile.mkdtemp(prefix="serve_bench_tracez_"),
        "decode_trace.json")
    with open(trace_file, "w") as f:
        json.dump(RING.chrome_trace(), f)
    return {
        "metric": "decode_throughput",
        "value": round(cont_tps, 2),
        "unit": "tokens/s",
        # north star: >= 2x over one-request-at-a-time at >= 8 slots
        "vs_baseline": round(speedup / 2.0, 3),
        "requests": n,
        "errors": errors[:5],
        "decode_slots": args.decode_slots,
        "max_new_tokens": max_new,
        "continuous_tokens_per_s": round(cont_tps, 2),
        "sequential_tokens_per_s": round(base_tps, 2),
        "speedup": round(speedup, 3),
        "tokens_per_s_per_request": round(cont_tps / n, 2) if n else 0.0,
        "total_tokens": cont_tokens,
        # shared scoring unit with the speculative bench: committed
        # output tokens/s. On the plain engine every emitted token is
        # trivially "accepted", so this equals the aggregate rate.
        "accepted_tokens_per_s": round(cont_tps, 2),
        "acceptance_rate": 1.0,
        "ms_per_token_p50": round(_pct(drive["ms_per_tok"], 0.50), 3),
        "ms_per_token_p95": round(_pct(drive["ms_per_tok"], 0.95), 3),
        "ttft_p50_ms": pct(0.50),
        "ttft_p95_ms": pct(0.95),
        "slot_occupancy": occ,
        "shared_prefix": args.shared_prefix,
        "prefix_hit_rate": round(hit_rate, 4),
        "pages_in_use": int(pages_peak),
        "page_tokens": st["page_tokens"],
        "kv_dtype": st["kv_dtype"],
        "kv_page_bytes": int(page_bytes),
        "hbm_bytes_per_slot": int(pages_peak * page_bytes // slots),
        "contiguous_hbm_bytes_per_slot": int(contig_per_slot),
        "quant_compare": quant_compare,
        "page_pool": st["pages"],
        # the memory plane's scorecard: peak footprint by tenant, how
        # shattered the free list got, and whether anything OOM'd
        "memory": {
            "peak_pages": int(pages_peak),
            "peak_pages_by_tenant": {
                t: int(v) for t, v in sorted(tenant_peaks.items())},
            "fragmentation_p95": round(_pct(frag_samples, 0.95), 4),
            "owner_kinds": st["pages"].get("owner_kinds", {}),
            "oom_dumps": len(_memz.oom_dumps()) - oom0,
            "ring_events": _memz.RING.total,
        },
        "engine_steps": st["steps"],
        "warmup_compiles": warmup_compiles,
        "baseline_warmup_compiles": base_warmup,
        "compile_count": steady_compiles,
        "trace_file": trace_file,
        "profilez_top": PROFILER.top(5),
        "metrics": {k: v for k, v in REGISTRY.flat().items()
                    if k.startswith("paddle_tpu_decode_")},
    }


def run_long_context_bench(args):
    """Long-context resident-streams mode (``--decode --long-context``):
    two-turn conversations whose cached KV chains collectively dwarf
    the device page pool, tiered (``--host-pages``, memory/migration.py)
    vs the same tight pool without a host tier.

    Turn 1 runs open-loop to build every conversation's chain; the
    device pool only holds ~2 of them, so the tier spills the rest to
    host RAM (the untiered arm destructively LRU-evicts instead). Turn
    2 then measures per-conversation resume latency: the tiered arm
    refetches spilled pages asynchronously and tail-feeds the few new
    tokens; the untiered arm re-prefills the whole conversation.
    Load-bearing fields: ``resident_streams`` (conversations whose KV
    survived the turn gap, vs ``device_chain_capacity``),
    ``spilled_pages`` / ``refetch_p95_ms`` (migration engine), and
    ``resume_vs_reprefill`` (>= 1.0 means a tiered resume is cheaper
    than the re-prefill it replaces). Both arms must emit identical
    greedy tokens — the tier is invisible in outputs."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.observability import REGISTRY

    # long-context regime: a deep model with 224-token conversation
    # heads, where re-prefilling a conversation costs real attention
    # compute (O(L^2)) and a page refetch is a bounded copy
    paddle.seed(args.seed)
    cfg = GPTConfig(vocab_size=512, max_seq_len=256, hidden=64,
                    layers=6, heads=4, scan_layers=False)
    model = GPT(cfg)
    rng = np.random.default_rng(args.seed)
    n = args.decode_requests
    # short turns over a long head: the resume path re-feeds only the
    # tokens past the cached chain, so most of turn 2's cost is the
    # refetch-vs-reprefill difference this bench scores
    gen = min(args.decode_tokens or 4, 8)
    head_len, follow_len = 224, 0
    pt = 16                              # page_tokens: 14 pages per chain
    chain_pages = head_len // pt
    # room for two concurrently active turn-2 sequences, nothing more
    slots = 2
    num_pages = slots * (-(-(head_len + gen + follow_len + gen) // pt)) + 1
    prompts = [rng.integers(0, cfg.vocab_size, size=head_len)
               .astype(np.int32) for _ in range(n)]
    follows = [rng.integers(0, cfg.vocab_size, size=follow_len)
               .astype(np.int32) for _ in range(n)]

    def run_arm(host_pages):
        eng = DecodeEngine(model, max_slots=slots, max_new_tokens=gen,
                           max_pending=n, page_tokens=pt,
                           num_pages=num_pages, prefix_cache=True,
                           host_pages=host_pages)
        warmup = eng.warmup()
        c0 = len(profiler.compile_events())
        turn1 = _drive_decode(eng, prompts, gen)
        # let in-flight spills land so turn 2 sees HOST residency
        deadline = time.perf_counter() + 30
        while host_pages and time.perf_counter() < deadline:
            tier = eng.stats().get("kv_tier", {})
            if not tier.get("inflight") and not tier.get("parked_refetches"):
                break
            time.sleep(0.01)
        st_gap = eng.stats()
        # turn 2, closed loop: per-conversation resume latency
        lat, outs2, errors = [], [], list(turn1["errors"])
        for p, o1, f in zip(prompts, turn1["outs"], follows):
            toks = np.concatenate([p, np.asarray(o1, np.int32), f])
            t0 = time.perf_counter()
            try:
                outs2.append(eng.submit(toks, max_new_tokens=gen)
                             .result(timeout=300))
            except Exception as e:
                errors.append(repr(e))
                outs2.append([])
            lat.append((time.perf_counter() - t0) * 1e3)
        st = eng.stats()
        compiles = len(profiler.compile_events()) - c0
        eng.stop()
        return {
            "turn1": turn1, "outs2": outs2, "errors": errors,
            "lat_ms": sorted(lat), "stats": st, "gap": st_gap,
            "warmup": warmup, "compiles": compiles,
        }

    tiered = run_arm(args.host_pages)
    untier = run_arm(0)

    # conversations whose chains were still addressable at the turn gap
    gap_cache = tiered["gap"].get("prefix_cache", {})
    resident = min(n, gap_cache.get("cached_pages", 0) // chain_pages)
    resident_untier = min(n, untier["gap"].get("prefix_cache", {})
                          .get("cached_pages", 0) // chain_pages)
    capacity = (num_pages - 1) // chain_pages
    tier = tiered["stats"].get("kv_tier", {})
    resume_p50 = round(_pct(tiered["lat_ms"], 0.50), 3)
    reprefill_p50 = round(_pct(untier["lat_ms"], 0.50), 3)
    outputs_match = (tiered["turn1"]["outs"] == untier["turn1"]["outs"]
                     and tiered["outs2"] == untier["outs2"])
    return {
        "metric": "decode_long_context_resident_streams",
        "value": resident,
        "unit": "conversations",
        # target: >= 4x the conversations the device pool alone holds
        "vs_baseline": round(resident / (4.0 * max(capacity, 1)), 3),
        "requests": n,
        "errors": (tiered["errors"] + untier["errors"])[:5],
        "decode_slots": slots,
        "max_new_tokens": gen,
        "prompt_tokens": head_len,
        "page_tokens": pt,
        "num_pages": num_pages,
        "host_pages": args.host_pages,
        "device_chain_capacity": capacity,
        "resident_streams": resident,
        "resident_streams_untiered": resident_untier,
        "spilled_pages": int(tier.get("spilled_total", 0)),
        "refetched_pages": int(tier.get("refetched_total", 0)),
        "spill_p95_ms": tier.get("spill_p95_ms", 0.0),
        "refetch_p50_ms": tier.get("refetch_p50_ms", 0.0),
        "refetch_p95_ms": tier.get("refetch_p95_ms", 0.0),
        "host_arena_bytes": int(tier.get("host_arena_bytes", 0)),
        "resume_turn2_p50_ms": resume_p50,
        "resume_turn2_p95_ms": round(_pct(tiered["lat_ms"], 0.95), 3),
        "reprefill_turn2_p50_ms": reprefill_p50,
        "reprefill_turn2_p95_ms": round(_pct(untier["lat_ms"], 0.95), 3),
        "resume_vs_reprefill": round(reprefill_p50 / resume_p50, 3)
        if resume_p50 > 0 else 0.0,
        "outputs_match": outputs_match,
        "shed_tiered": len(tiered["errors"]),
        "shed_untiered": len(untier["errors"]),
        "page_pool": tiered["stats"]["pages"],
        "warmup_compiles": tiered["warmup"],
        "compile_count": tiered["compiles"],
        "metrics": {k: v for k, v in REGISTRY.flat().items()
                    if k.startswith(("paddle_tpu_kv_tier_",
                                     "paddle_tpu_decode_prefix_"))},
    }


def run_spec_decode_bench(args):
    """Speculative-decode mode (``--decode --speculate-k K``): the
    draft-and-verify SpecDecodeEngine vs the plain continuous engine on
    the SAME target model, prompts, and slot count — scored as accepted
    tokens/s (committed output tokens per second; every speculative
    token is target-verified, so the two arms are directly comparable).

    Workload: repetitive continuation. The target is built
    embedding-dominated (block weights scaled down so the residual
    stream is carried by the token/position embeddings), which makes
    greedy continuations collapse into short cycles — the regime
    speculation is for (boilerplate, templated text, code completion).
    The draft is a 1-layer model sharing the target's embedding table
    and final norm, so it predicts the target's argmax cheaply and
    accurately. Contract: >= 1.5x accepted tokens/s over the plain
    engine with identical outputs and compile_count == 0."""
    import paddle_tpu as paddle
    from paddle_tpu import framework, profiler
    from paddle_tpu.inference.decode import DecodeEngine, SpecDecodeEngine
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.observability import REGISTRY

    paddle.seed(args.seed)
    tcfg = GPTConfig(vocab_size=512, max_seq_len=256, hidden=64,
                     layers=6, heads=4, scan_layers=False)
    dcfg = GPTConfig(vocab_size=512, max_seq_len=256, hidden=64,
                     layers=1, heads=4, scan_layers=False)
    tp = {k: np.asarray(v)
          for k, v in framework.param_arrays(GPT(tcfg)).items()}
    dp = {k: np.asarray(v)
          for k, v in framework.param_arrays(GPT(dcfg)).items()}
    for params in (tp, dp):
        for k in list(params):
            if k.startswith("blocks."):
                params[k] = params[k] * 0.1
    for k in ("wte.weight", "wpe.weight", "ln_f.weight", "ln_f.bias"):
        dp[k] = tp[k]
    # --draft-quant: the speculative arm runs on an int8-PTQ draft;
    # the fp32-draft comparison arm below scores the acceptance delta
    draft_quant = bool(getattr(args, "draft_quant", False))
    if draft_quant:
        from paddle_tpu.quant.ptq import quantize_params
        dp_used = quantize_params(dp)
    else:
        dp_used = dp

    rng = np.random.default_rng(args.seed)
    n = args.decode_requests
    max_new = min(args.decode_tokens or 64, tcfg.max_seq_len - 32)
    psets = [[rng.integers(0, tcfg.vocab_size,
                           size=int(rng.integers(4, 13))).astype(np.int32)
              for _ in range(n)] for _ in range(3)]
    # one untimed slot-pool-sized wave per arm before its measured
    # drives: first-touch costs (pool materialization, collector
    # spin-up) land outside the window. All prompts sit below one
    # 16-token page, so nothing here ever enters the prefix cache.
    spin = [rng.integers(0, tcfg.vocab_size,
                         size=int(rng.integers(4, 13))).astype(np.int32)
            for _ in range(args.decode_slots)]

    def _tps(d):
        return d["tokens"] / d["wall_s"] if d["wall_s"] > 0 else 0.0

    # Both engines are built up front and the measured drives are
    # interleaved (plain set-0, spec set-0, plain set-1, ...): machine
    # drift on a shared box then lands on both arms instead of
    # whichever ran second. Each arm is scored by its best drive — one
    # scheduler hiccup otherwise decides the whole comparison — while
    # outputs of EVERY drive feed the cross-arm identity check.
    plain = DecodeEngine(cfg=tcfg, params=tp,
                         max_slots=args.decode_slots,
                         max_new_tokens=max_new, max_pending=n)
    plain_warmup = plain.warmup()
    spec = SpecDecodeEngine(cfg=tcfg, params=tp,
                            draft_cfg=dcfg, draft_params=dp_used,
                            speculate_k=args.speculate_k,
                            max_slots=args.decode_slots,
                            max_new_tokens=max_new, max_pending=n)
    spec_warmup = spec.warmup()

    plain_compiles = spec_compiles = 0
    plain_runs, spec_runs = [], []

    def _timed(eng, runs, ps, new):
        c0 = len(profiler.compile_events())
        d = _drive_decode(eng, ps, new)
        if runs is not None:
            runs.append(d)
        return len(profiler.compile_events()) - c0

    plain_compiles += _timed(plain, None, spin, 8)
    spec_compiles += _timed(spec, None, spin, 8)
    for ps in psets:
        plain_compiles += _timed(plain, plain_runs, ps, max_new)
        spec_compiles += _timed(spec, spec_runs, ps, max_new)

    st = spec.stats()
    plain.stop()
    spec.stop()
    # --draft-quant: an fp32-draft speculative arm on the first prompt
    # set — the acceptance-rate delta IS the draft-quantization quality
    # gate (target streams are identical by construction either way)
    draft_compare = None
    if draft_quant:
        ref_spec = SpecDecodeEngine(cfg=tcfg, params=tp,
                                    draft_cfg=dcfg, draft_params=dp,
                                    speculate_k=args.speculate_k,
                                    max_slots=args.decode_slots,
                                    max_new_tokens=max_new, max_pending=n)
        ref_spec.warmup()
        _drive_decode(ref_spec, psets[0], max_new)
        rst = ref_spec.stats()
        ref_spec.stop()
        draft_compare = {
            "acceptance_rate": {
                "float32": rst["speculate"]["acceptance_rate"],
                "int8": st["speculate"]["acceptance_rate"]},
            "acceptance_delta": round(
                st["speculate"]["acceptance_rate"]
                - rst["speculate"]["acceptance_rate"], 4),
            "draft_weight_bytes": {
                "float32": int(sum(v.nbytes for v in dp.values())),
                "int8": int(sum(v.nbytes for v in dp_used.values()))},
        }
    plain_d = max(plain_runs, key=_tps)
    spec_d = max(spec_runs, key=_tps)
    plain_tps = _tps(plain_d)
    spec_tps = _tps(spec_d)

    speedup = spec_tps / plain_tps if plain_tps > 0 else 0.0
    acc = spec_d["accept"]
    return {
        "metric": "decode_spec_throughput",
        "value": round(spec_tps, 2),
        "unit": "tokens/s",
        # north star: >= 1.5x accepted tokens/s over the plain engine
        "vs_baseline": round(speedup / 1.5, 3),
        "requests": n,
        "errors": (spec_d["errors"] + plain_d["errors"])[:5],
        "decode_slots": args.decode_slots,
        "max_new_tokens": max_new,
        "speculate_k": args.speculate_k,
        "accepted_tokens_per_s": round(spec_tps, 2),
        "plain_accepted_tokens_per_s": round(plain_tps, 2),
        "speedup": round(speedup, 3),
        "total_tokens": spec_d["tokens"],
        # every output must match the plain engine token-for-token —
        # speculation is an optimization, never a sampling change
        "identical_outputs": all(
            p["outs"] == s["outs"]
            for p, s in zip(plain_runs, spec_runs)),
        "acceptance_rate": st["speculate"]["acceptance_rate"],
        "per_stream_acceptance": {
            "p50": round(_pct(acc, 0.50), 4),
            "min": round(acc[0], 4) if acc else 0.0,
            "max": round(acc[-1], 4) if acc else 0.0,
        },
        "drafted_tokens": st["speculate"]["drafted"],
        "accepted_tokens": st["speculate"]["accepted"],
        "k_ladder": st["speculate"]["k_ladder"],
        "draft_quant": draft_quant,
        "draft_compare": draft_compare,
        "ms_per_token_p50": round(_pct(spec_d["ms_per_tok"], 0.50), 3),
        "ms_per_token_p95": round(_pct(spec_d["ms_per_tok"], 0.95), 3),
        "plain_ms_per_token_p50":
            round(_pct(plain_d["ms_per_tok"], 0.50), 3),
        "plain_ms_per_token_p95":
            round(_pct(plain_d["ms_per_tok"], 0.95), 3),
        "ttft_p50_ms": round(_pct(spec_d["ttfts"], 0.50) * 1e3, 3),
        "ttft_p95_ms": round(_pct(spec_d["ttfts"], 0.95) * 1e3, 3),
        "engine_steps": st["steps"],
        "page_pool": st["pages"],
        "warmup_compiles": spec_warmup,
        "plain_warmup_compiles": plain_warmup,
        "compile_count": spec_compiles,
        "plain_compile_count": plain_compiles,
        "metrics": {k: v for k, v in REGISTRY.flat().items()
                    if k.startswith("paddle_tpu_decode_spec_")
                    or k.startswith("paddle_tpu_decode_page_rollback_")},
    }


def run_router_bench(args):
    """Fleet mode: N in-process backends behind the ServeRouter, driven
    over the wire by concurrent clients. With ``--kill-one`` a backend
    is stopped abruptly mid-run — the contract under test is ZERO lost
    requests (every client gets a tensor reply for every request) with
    the failover cost reported from the router's own histograms.

    With ``PADDLE_TPU_TRACE_SAMPLE`` set (e.g. 1), every routed request
    is assembled into a JSONL trace line (router pick/forward/reply +
    the backend's relayed breakdown); the bench captures them to a temp
    file (unless ``PADDLE_TPU_TRACE_FILE`` already points somewhere),
    and reports the assembled-trace count, the router-vs-backend
    latency epsilon, and the request-id collision count (contract: 0).
    A ``metrics_delta`` section shows exactly which router/serve
    counters the run moved."""
    import socket
    import threading

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.inference.router import Backend, ServeRouter
    from paddle_tpu.inference.serve import (InferenceServer, read_reply,
                                            write_tensors)
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.static import InputSpec

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(64, 256)
            self.fc2 = nn.Linear(256, 64)

        def forward(self, x):
            import paddle_tpu.nn.functional as F
            return self.fc2(F.relu(self.fc1(x)))

    paddle.seed(0)
    prefix = os.path.join(tempfile.mkdtemp(prefix="serve_bench_"), "mlp")
    paddle.jit.save(MLP(), prefix,
                    input_spec=[InputSpec([None, 64], "float32")])

    # trace capture: recorders read the env at construction, so the
    # sink must be decided before any server/router exists
    trace_path = os.environ.get("PADDLE_TPU_TRACE_FILE") or None
    if os.environ.get("PADDLE_TPU_TRACE_SAMPLE") and trace_path is None:
        trace_path = os.path.join(
            tempfile.mkdtemp(prefix="serve_bench_trace_"),
            "traces.jsonl")
        os.environ["PADDLE_TPU_TRACE_FILE"] = trace_path

    srvs = [InferenceServer(prefix, port=0, max_batch_size=args.max_batch,
                            batch_timeout_ms=args.batch_timeout_ms,
                            metrics_port=0)
            for _ in range(args.router)]
    router = ServeRouter(
        [Backend("127.0.0.1", s.port, s.metrics_port) for s in srvs],
        port=0, poll_interval=0.1)

    # traces need the poll loop to have learned each backend speaks
    # PDI2 (statusz trace_wire) before the first request goes out
    deadline = time.perf_counter() + 10.0
    while time.perf_counter() < deadline:
        bs = router.backends()
        if bs and all(b.trace_wire for b in bs):
            break
        time.sleep(0.05)

    rng = np.random.default_rng(args.seed)
    row_mix = (1, 2, 1, 4)
    n_clients = max(args.clients, 1)
    per_client = max(args.requests // n_clients, 1)
    total = per_client * n_clients

    done_lock = threading.Lock()
    completed = [0]
    latencies = []
    lost = []                  # (client, error-or-exception)
    kill_at = total // 3 if args.kill_one and args.router > 1 else None
    killed = {"key": None, "t": None}

    def maybe_kill():
        with done_lock:
            fire = (kill_at is not None and killed["key"] is None
                    and completed[0] >= kill_at)
            if fire:
                killed["key"] = f"127.0.0.1:{srvs[1].port}"
        if fire:
            killed["t"] = time.perf_counter()
            srvs[1].stop()     # abrupt: mid-batch, no drain

    def client(i):
        x = rng.normal(size=(row_mix[i % len(row_mix)], 64)) \
            .astype(np.float32)
        try:
            with socket.create_connection(
                    ("127.0.0.1", router.port)) as s:
                s.settimeout(120)
                for _ in range(per_client):
                    t0 = time.perf_counter()
                    write_tensors(s, [x])
                    out, err = read_reply(s)
                    dt = time.perf_counter() - t0
                    if err is not None:
                        lost.append((i, err))
                        return
                    with done_lock:
                        completed[0] += 1
                        latencies.append(dt)
                    maybe_kill()
        except Exception as e:
            lost.append((i, repr(e)))

    flat0 = REGISTRY.flat()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall_s = time.perf_counter() - t0

    flat = REGISTRY.flat()
    fo_hist = REGISTRY.get("paddle_tpu_router_failover_latency_seconds")
    lat_sorted = sorted(latencies)

    def pct(q):
        if not lat_sorted:
            return 0.0
        k = min(len(lat_sorted) - 1, int(q * len(lat_sorted)))
        return round(lat_sorted[k] * 1e3, 3)

    router.stop()
    for s in srvs:
        s.stop()
    rps = completed[0] / wall_s if wall_s > 0 else 0.0

    # what the run actually moved, not the process lifetime totals
    metrics_delta = {}
    for k, v in flat.items():
        if not (k.startswith("paddle_tpu_router_")
                or k.startswith("paddle_tpu_serve_")):
            continue
        try:
            d = round(float(v) - float(flat0.get(k, 0.0)), 6)
        except (TypeError, ValueError):
            continue
        if d:
            metrics_delta[k] = d

    # assembled traces: count them, prove ids never collide, and bound
    # the epsilon between the router's observed latency (total_s) and
    # the backend's own stage sum (backend_total_s)
    trace_summary = {"file": trace_path, "lines": 0,
                     "router_assembled": 0, "with_backend_breakdown": 0,
                     "id_collisions": 0, "epsilon_ms": None}
    if trace_path and os.path.exists(trace_path):
        ids, eps = [], []
        with open(trace_path) as f:
            for raw in f:
                try:
                    line = json.loads(raw)
                except ValueError:
                    continue
                trace_summary["lines"] += 1
                ids.append(line.get("request_id"))
                if line.get("component") != "router":
                    continue
                trace_summary["router_assembled"] += 1
                if "backend_total_s" in line:
                    trace_summary["with_backend_breakdown"] += 1
                    eps.append(line["total_s"]
                               - line["backend_total_s"])
        trace_summary["id_collisions"] = len(ids) - len(set(ids))
        if eps:
            trace_summary["epsilon_ms"] = {
                "mean": round(sum(eps) / len(eps) * 1e3, 3),
                "min": round(min(eps) * 1e3, 3),
                "max": round(max(eps) * 1e3, 3)}

    return {
        "metric": "serve_router_fleet",
        "value": round(rps, 2),
        "unit": "reqs/s",
        # the contract IS the baseline: 1.0 = zero lost requests
        "vs_baseline": 1.0 if not lost and completed[0] == total else 0.0,
        "fleet": args.router,
        "clients": n_clients,
        "requests": total,
        "completed": completed[0],
        "lost_requests": len(lost),
        "lost_detail": [f"client {i}: {e}" for i, e in lost[:5]],
        "killed_backend": killed["key"],
        "failovers": int(flat.get(
            "paddle_tpu_router_failovers_total", 0)),
        "failover_p95_ms": round(
            fo_hist.percentile(0.95) * 1e3, 3) if fo_hist else 0.0,
        "failover_max_ms": round(
            fo_hist.percentile(1.0) * 1e3, 3) if fo_hist else 0.0,
        "p50_latency_ms": pct(0.50),
        "p95_latency_ms": pct(0.95),
        "p99_latency_ms": pct(0.99),
        "reqs_per_s": round(rps, 2),
        "traces": trace_summary,
        "metrics_delta": metrics_delta,
        "router_metrics": {k: v for k, v in flat.items()
                           if k.startswith("paddle_tpu_router_")},
    }


def run_decode_router_bench(args):
    """Streaming fleet mode (``--decode --router N``): N decode backends
    behind the ServeRouter with >= 16 concurrent token streams driven
    over the wire. A no-kill pass is run first as the correctness
    baseline; with ``--kill-one`` the scored pass stops one backend
    abruptly mid-token. The contract: ``lost`` stays 0 (every stream
    completes), every greedy stream's tokens are byte-identical to the
    no-kill pass, and each client observes a gapless, duplicate-free
    ``seq`` run — failover cost reported from the router's histogram."""
    import socket
    import threading

    import paddle_tpu as paddle
    from paddle_tpu.inference.decode import save_for_decode
    from paddle_tpu.inference.router import Backend, ServeRouter
    from paddle_tpu.inference.serve import InferenceServer, decode_request
    from paddle_tpu.models.gpt import GPT, gpt_tiny
    from paddle_tpu.observability import REGISTRY

    paddle.seed(args.seed)
    cfg = gpt_tiny()
    prefix = os.path.join(tempfile.mkdtemp(prefix="serve_bench_dec_"),
                          "gpt")
    save_for_decode(GPT(cfg), prefix)

    fleet = max(args.router, 2)
    n_streams = max(args.decode_requests, 16)
    max_new = args.decode_tokens or 24
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(4, 17))).astype(np.int32)
               for _ in range(n_streams)]

    def run_pass(kill_after=None):
        srvs = [InferenceServer(prefix, port=0, decode=True,
                                decode_slots=max(args.decode_slots, 4),
                                decode_max_new=max_new, metrics_port=0)
                for _ in range(fleet)]
        router = ServeRouter(
            [Backend("127.0.0.1", s.port, s.metrics_port) for s in srvs],
            port=0, poll_interval=0.1)
        deadline = time.perf_counter() + 10.0
        while time.perf_counter() < deadline:
            bs = router.backends()
            if bs and all(b.trace_wire for b in bs):
                break
            time.sleep(0.05)

        lock = threading.Lock()
        token_count = [0]
        killed = {"key": None}
        outs = [None] * n_streams
        seq_ok = [True] * n_streams
        errs = []

        def on_token(seqs):
            def cb(tok, stream):
                seqs.append(int(stream.get("seq", -1)))
                with lock:
                    token_count[0] += 1
                    fire = (kill_after is not None
                            and killed["key"] is None
                            and token_count[0] >= kill_after)
                    if fire:
                        killed["key"] = f"127.0.0.1:{srvs[1].port}"
                if fire:
                    srvs[1].stop()   # abrupt: mid-token, no drain
            return cb

        def client(i):
            seqs = []
            try:
                with socket.create_connection(
                        ("127.0.0.1", router.port)) as s:
                    s.settimeout(120)
                    outs[i] = decode_request(
                        s, prompts[i], opts={"max_new_tokens": max_new},
                        on_token=on_token(seqs))
                seq_ok[i] = seqs == list(range(len(seqs)))
            except Exception as e:
                errs.append(f"stream {i}: {e!r}")

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall_s = time.perf_counter() - t0
        router.stop()
        for s in srvs:
            s.stop()
        return {"outs": outs, "errs": errs, "seq_ok": seq_ok,
                "wall_s": wall_s, "killed": killed["key"]}

    baseline = run_pass()
    if baseline["errs"]:
        raise RuntimeError(f"baseline pass lost streams: "
                           f"{baseline['errs'][:3]}")

    flat0 = REGISTRY.flat()
    kill_after = (n_streams * max_new) // 3 if args.kill_one else None
    scored = run_pass(kill_after=kill_after)
    flat = REGISTRY.flat()
    fo_hist = REGISTRY.get("paddle_tpu_router_failover_latency_seconds")

    lost = sum(1 for o in scored["outs"] if o is None)
    identical = all(
        a is not None and b is not None and list(a) == list(b)
        for a, b in zip(baseline["outs"], scored["outs"]))
    tokens = sum(len(o) for o in scored["outs"] if o is not None)
    tps = tokens / scored["wall_s"] if scored["wall_s"] > 0 else 0.0

    def delta(name):
        return int(float(flat.get(name, 0)) - float(flat0.get(name, 0)))

    return {
        "metric": "serve_decode_router_stream",
        "value": round(tps, 2),
        "unit": "tokens/s",
        # the contract IS the baseline: every stream survives,
        # byte-identical, gapless
        "vs_baseline": 1.0 if (lost == 0 and identical
                               and all(scored["seq_ok"])) else 0.0,
        "fleet": fleet,
        "streams": n_streams,
        "max_new_tokens": max_new,
        "lost": lost,
        "lost_detail": scored["errs"][:5],
        "byte_identical": identical,
        "seq_gapless": all(scored["seq_ok"]),
        "killed_backend": scored["killed"],
        "stream_failovers": delta(
            "paddle_tpu_router_stream_failovers_total"),
        "resumed_tokens": delta(
            "paddle_tpu_router_stream_resumed_tokens_total"),
        "streams_lost_metric": delta(
            "paddle_tpu_router_stream_lost_total"),
        "failover_p95_ms": round(
            fo_hist.percentile(0.95) * 1e3, 3) if fo_hist else 0.0,
        "failover_max_ms": round(
            fo_hist.percentile(1.0) * 1e3, 3) if fo_hist else 0.0,
        "tokens_per_s": round(tps, 2),
        "wall_s": round(scored["wall_s"], 3),
        "router_metrics": {k: v for k, v in flat.items()
                           if k.startswith("paddle_tpu_router_stream_")
                           or k.startswith(
                               "paddle_tpu_router_membership_")},
    }


def run_disagg_bench(args):
    """Disaggregated serving mode (``--disagg``): 1 prefill worker + N
    decode workers with KV-page handoff over the wire
    (inference/decode.py export_kv/import_kv, docs/serving.md) vs an
    (N+1)-unified colocated fleet — same total worker count, same
    prompts, same router code.

    The workload is built to expose the interference disaggregation
    removes: long prompts (prefill-dominated) submitted with a stagger,
    so late arrivals' prefills land while earlier streams are
    mid-decode. On the colocated fleet those prefills run on the same
    engines as the live decode streams and stall them between tokens;
    on the disagg fleet the prefill worker absorbs them and the decode
    workers admit each handoff as a prefix-cache hit. Load-bearing
    fields: ``decode_stall_p95_ms`` per arm and ``stall_reduction``
    (>= 1.0 means disagg reduced inter-token stall), ``ttft_p50_ms`` /
    ``ttft_p95_ms`` per arm, the ``handoff`` block (count, pages,
    bytes, router-observed latency p95), ``outputs_match`` (greedy
    streams must be token-identical across arms) and the
    ``compile_count`` contract of 0 for both arms after warmup."""
    import socket
    import threading

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.decode import save_for_decode
    from paddle_tpu.inference.router import Backend, ServeRouter
    from paddle_tpu.inference.serve import InferenceServer, decode_request
    from paddle_tpu.models.gpt import GPT, gpt_tiny
    from paddle_tpu.observability import REGISTRY

    paddle.seed(args.seed)
    cfg = gpt_tiny()
    prefix = os.path.join(tempfile.mkdtemp(prefix="serve_bench_dis_"),
                          "gpt")
    save_for_decode(GPT(cfg), prefix)

    n_dec = max(args.router, 2)          # decode workers in the disagg arm
    n_streams = max(args.decode_requests, 8)
    max_new = min(args.decode_tokens or 16, 32)
    stagger_s = 0.02
    rng = np.random.default_rng(args.seed)
    # prefill-dominated requests: long prompts, short generations
    prompts = [rng.integers(
        0, cfg.vocab_size,
        size=int(rng.integers(33, cfg.max_seq_len - max_new - 8))
    ).astype(np.int32) for _ in range(n_streams)]

    def run_arm(roles):
        srvs = [InferenceServer(prefix, port=0, decode=True,
                                decode_slots=args.decode_slots,
                                decode_max_new=max_new, metrics_port=0,
                                role=r)
                for r in roles]
        backends = []
        for r, s in zip(roles, srvs):
            b = Backend("127.0.0.1", s.port, s.metrics_port)
            # what a membership record would carry (docs/serving.md);
            # a static bench fleet applies it directly
            b.set_meta(dict({"role": r}, **s._engine.kv_compat()))
            backends.append(b)
        router = ServeRouter(backends, port=0, poll_interval=0.1)
        deadline = time.perf_counter() + 15.0
        while time.perf_counter() < deadline:
            bs = router.backends()
            if bs and all(b.trace_wire for b in bs):
                break
            time.sleep(0.05)
        for s in srvs:
            s._engine.warmup()
        c0 = len(profiler.compile_events())

        outs = [None] * n_streams
        ttfts = [None] * n_streams
        gaps = [[] for _ in range(n_streams)]
        errs = []

        def client(i):
            time.sleep(i * stagger_s)
            arrivals = []
            try:
                with socket.create_connection(
                        ("127.0.0.1", router.port)) as s:
                    s.settimeout(300)
                    t_sub = time.perf_counter()
                    outs[i] = decode_request(
                        s, prompts[i],
                        opts={"max_new_tokens": max_new},
                        on_token=lambda tok, sctx:
                            arrivals.append(time.perf_counter()))
                if arrivals:
                    ttfts[i] = arrivals[0] - t_sub
                    gaps[i] = [b - a for a, b in
                               zip(arrivals, arrivals[1:])]
            except Exception as e:
                errs.append(f"stream {i}: {e!r}")

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall_s = time.perf_counter() - t0
        compiles = len(profiler.compile_events()) - c0
        router.stop()
        for s in srvs:
            s.stop()
        return {
            "outs": outs, "errs": errs, "wall_s": wall_s,
            "ttfts": sorted(t for t in ttfts if t is not None),
            "gaps": sorted(g for gs in gaps for g in gs),
            "compiles": compiles,
        }

    # colocated baseline first, then the disagg arm, with the handoff
    # counter/histogram deltas bracketing only the disagg pass
    colo = run_arm(["unified"] * (n_dec + 1))
    flat0 = REGISTRY.flat()
    disagg = run_arm(["prefill"] + ["decode"] * n_dec)
    flat = REGISTRY.flat()
    hh = REGISTRY.get("paddle_tpu_router_handoff_seconds")

    def delta(name):
        return float(flat.get(name, 0)) - float(flat0.get(name, 0))

    tokens = sum(len(o) for o in disagg["outs"] if o is not None)
    tps = tokens / disagg["wall_s"] if disagg["wall_s"] > 0 else 0.0
    lost = sum(1 for o in disagg["outs"] if o is None) \
        + sum(1 for o in colo["outs"] if o is None)
    outputs_match = all(
        a is not None and b is not None and list(a) == list(b)
        for a, b in zip(colo["outs"], disagg["outs"]))
    handoffs_ok = int(delta(
        'paddle_tpu_router_handoffs_total{outcome="ok"}'))
    colo_stall = _pct(colo["gaps"], 0.95) * 1e3
    dis_stall = _pct(disagg["gaps"], 0.95) * 1e3
    contract = (lost == 0 and outputs_match and handoffs_ok > 0
                and colo["compiles"] == 0 and disagg["compiles"] == 0)
    return {
        "metric": "serve_disagg_handoff",
        "value": round(tps, 2),
        "unit": "tokens/s",
        # the contract IS the baseline: zero lost streams, greedy
        # outputs identical across arms, handoffs actually landing,
        # zero steady-state compiles on every worker
        "vs_baseline": 1.0 if contract else 0.0,
        "prefill_workers": 1,
        "decode_workers": n_dec,
        "colocated_workers": n_dec + 1,
        "streams": n_streams,
        "max_new_tokens": max_new,
        "stagger_ms": stagger_s * 1e3,
        "lost": lost,
        "lost_detail": (disagg["errs"] + colo["errs"])[:5],
        "outputs_match": outputs_match,
        "tokens_per_s": round(tps, 2),
        "colocated_tokens_per_s": round(
            sum(len(o) for o in colo["outs"] if o is not None)
            / colo["wall_s"], 2) if colo["wall_s"] > 0 else 0.0,
        "ttft_p50_ms": round(_pct(disagg["ttfts"], 0.50) * 1e3, 3),
        "ttft_p95_ms": round(_pct(disagg["ttfts"], 0.95) * 1e3, 3),
        "colocated_ttft_p50_ms": round(
            _pct(colo["ttfts"], 0.50) * 1e3, 3),
        "colocated_ttft_p95_ms": round(
            _pct(colo["ttfts"], 0.95) * 1e3, 3),
        # inter-token gap while other streams' prefills are in flight:
        # the number disaggregation exists to shrink
        "decode_stall_p95_ms": round(dis_stall, 3),
        "colocated_decode_stall_p95_ms": round(colo_stall, 3),
        "stall_reduction": round(colo_stall / dis_stall, 3)
        if dis_stall > 0 else 0.0,
        "handoff": {
            "ok": handoffs_ok,
            "fallback": int(delta(
                'paddle_tpu_router_handoffs_total{outcome="fallback"}')),
            "pages_exported": int(delta(
                'paddle_tpu_handoff_pages_total{direction="export"}')),
            "bytes_exported": int(delta(
                'paddle_tpu_handoff_bytes_total{direction="export"}')),
            "bytes_imported": int(delta(
                'paddle_tpu_handoff_bytes_total{direction="import"}')),
            "latency_p95_ms": round(
                hh.percentile(0.95) * 1e3, 3) if hh else 0.0,
        },
        "compile_count": disagg["compiles"],
        "colocated_compile_count": colo["compiles"],
        "metrics": {k: v for k, v in flat.items()
                    if k.startswith(("paddle_tpu_handoff_",
                                     "paddle_tpu_router_handoff",
                                     "paddle_tpu_router_role_"))},
    }


def run_scenario_bench(args):
    """Scenario mode: replay a seeded multi-tenant traffic scenario
    (benchmarks/scenarios.py) against one QoS-armed decode engine —
    weighted-fair scheduling, a flood-tenant quota, and preemption all
    on — and score it per tenant (p50/p99 completion latency, goodput).

    ``adversarial_flood`` doubles as the QoS acceptance check: the
    well-behaved tenant's arrivals replay alone first (the no-flood
    baseline), then the full scenario. Acceptance: zero well-behaved
    requests lost, well-behaved p99 within 2x its no-flood baseline,
    and the flood tenant visibly degraded (shed/deferred/preempted or
    lower goodput per submitted request than the well-behaved tenant).
    Reported as booleans in the JSON; rc stays 0 either way."""
    try:
        from benchmarks import scenarios as scen
    except ImportError:      # run as a script from benchmarks/
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import scenarios as scen

    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.models.gpt import GPT, gpt_tiny
    from paddle_tpu.observability import REGISTRY

    name = args.scenario
    cfg = gpt_tiny()
    model = GPT(cfg)
    rate = args.scenario_rate
    max_new = args.decode_tokens or 12
    dur = args.scenario_duration
    if name == "adversarial_flood":
        kw = {"capacity_rps": rate}
    elif name == "flash_crowd":
        kw = {"base_rate": rate / 2.0, "burst_rate": rate * 4.0}
    else:
        kw = {"rate": rate}
    arrivals = scen.generate(name, seed=args.seed, duration_s=dur,
                             vocab=cfg.vocab_size, max_new=max_new, **kw)
    tenants = sorted({a.tenant for a in arrivals})
    good = "tenant-a" if "tenant-a" in tenants else tenants[0]
    # QoS posture: the well-behaved tenant carries 4x weight; a flood
    # tenant is token-rate-capped at half the nominal capacity; the
    # engine may preempt low-priority slots for high-priority arrivals
    quota = (f"flood:{rate * max_new / 2.0}"
             if "flood" in tenants else "")
    eng = DecodeEngine(model, max_slots=args.decode_slots,
                       max_new_tokens=max_new,
                       tenant_weights=f"{good}:4",
                       tenant_quota=quota, preempt=True)
    warmup_compiles = eng.warmup()
    try:
        baseline = None
        if name == "adversarial_flood":
            base_arr = [a for a in arrivals if a.tenant == good]
            baseline = scen.score(scen.replay(eng, base_arr), dur)
        outcomes = scen.replay(eng, arrivals)
        per = scen.score(outcomes, dur)
        st = eng.stats()
    finally:
        eng.stop()
    m = REGISTRY.flat()
    total_tps = sum(d["goodput_tps"] for d in per.values())
    out = {
        "metric": f"serve_scenario_{name}",
        "value": round(total_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "scenario": name,
        "seed": args.seed,
        "duration_s": dur,
        "arrivals": len(arrivals),
        "decode_slots": args.decode_slots,
        "max_new_tokens": max_new,
        "tenants": per,
        "warmup_compiles": warmup_compiles,
        "engine": {
            "preemptions": m.get(
                "paddle_tpu_decode_preemptions_total", 0.0),
            "preempt_resumes": m.get(
                "paddle_tpu_decode_preempt_resumes_total", 0.0),
            "virtual_clocks": st.get("tenants", {}),
        },
        "metrics": {k: v for k, v in m.items()
                    if k.startswith(("paddle_tpu_tenant_",
                                     "paddle_tpu_decode_preempt"))},
    }
    if baseline is not None:
        flood = next((t for t in tenants if t != good), None)
        g, f = per.get(good, {}), per.get(flood, {}) if flood else {}
        base_p99 = baseline.get(good, {}).get("p99_ms", 0.0)
        flood_degraded = bool(f) and (
            f.get("lost", 0) > 0
            or f.get("p99_ms", 0.0) > g.get("p99_ms", 0.0)
            or (f.get("tokens", 0) / max(f.get("submitted", 1), 1))
            < (g.get("tokens", 0) / max(g.get("submitted", 1), 1)))
        out["baseline"] = baseline
        out["acceptance"] = {
            "well_behaved_lost": g.get("lost", 0),
            "well_behaved_p99_ms": g.get("p99_ms", 0.0),
            "baseline_p99_ms": base_p99,
            "p99_within_2x_baseline":
                g.get("p99_ms", 0.0) <= 2.0 * base_p99 + 1.0,
            "zero_well_behaved_lost": g.get("lost", 0) == 0,
            "flood_degraded": flood_degraded,
        }
        out["vs_baseline"] = round(
            base_p99 / g["p99_ms"], 3) if g.get("p99_ms") else 1.0
    return out


def main():
    ap = argparse.ArgumentParser(description="serving engine benchmark")
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode", action="store_true",
                    help="decode mode: continuous-batching token "
                         "generation vs one-request-at-a-time on the "
                         "KV-cache engine (tokens/s, TTFT, occupancy)")
    ap.add_argument("--decode-requests", type=int, default=24)
    ap.add_argument("--decode-slots", type=int, default=8)
    ap.add_argument("--decode-tokens", type=int, default=None,
                    help="(decode mode) new tokens per request "
                         "(default: 32, or 64 with --speculate-k)")
    ap.add_argument("--speculate-k", type=int, default=0, metavar="K",
                    help="(decode mode) draft-and-verify speculative "
                         "decoding with K draft tokens per tick vs the "
                         "plain continuous engine on a repetitive-"
                         "continuation workload (accepted_tokens_per_s, "
                         "acceptance rates, ms/token)")
    ap.add_argument("--long-context", action="store_true",
                    help="(decode mode) two-turn resident-streams "
                         "workload over a device pool too small for the "
                         "conversations it serves — scores the host-RAM "
                         "KV tier (memory/migration.py) vs destructive "
                         "eviction (resident_streams, spilled_pages, "
                         "refetch_p95_ms, resume_vs_reprefill)")
    ap.add_argument("--host-pages", type=int, default=256,
                    help="(decode --long-context) host-RAM KV tier "
                         "capacity in pages for the tiered arm "
                         "(PADDLE_TPU_DECODE_HOST_PAGES equivalent)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="(decode mode) N requests sharing one long "
                         "system prompt + short unique tails — scores "
                         "the paged-KV prefix cache (prefix_hit_rate, "
                         "pages_in_use, hbm_bytes_per_slot)")
    ap.add_argument("--kv-dtype", choices=("float32", "int8"),
                    default=None,
                    help="(decode mode) KV page-pool dtype; int8 also "
                         "emits a side-by-side quant_compare block vs "
                         "an fp32 reference engine (tokens/s, "
                         "hbm_bytes_per_slot, logits_max_abs_err)")
    ap.add_argument("--draft-quant", action="store_true",
                    help="(decode mode, with --speculate-k) quantize "
                         "the draft model weights to int8; emits a "
                         "draft_compare block with acceptance-rate "
                         "delta vs the fp32 draft")
    ap.add_argument("--scenario", default="", metavar="NAME",
                    help="multi-tenant QoS scenario replay over the "
                         "decode engine (benchmarks/scenarios.py): "
                         "diurnal, flash_crowd, long_context, or "
                         "adversarial_flood — scored per tenant "
                         "(p50/p99/goodput); adversarial_flood also "
                         "scores the flood-isolation acceptance checks "
                         "against a no-flood baseline")
    ap.add_argument("--scenario-duration", type=float, default=3.0,
                    help="(scenario mode) arrival-clock length, seconds")
    ap.add_argument("--scenario-rate", type=float, default=8.0,
                    help="(scenario mode) nominal capacity in "
                         "requests/s the generators scale from")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving mode: 1 prefill + N "
                         "decode workers (N = --router, min 2) with "
                         "KV-page handoff over the wire vs an "
                         "(N+1)-unified colocated fleet — scores "
                         "decode-stream stall, TTFT, handoff "
                         "bytes/latency, output identity and the "
                         "zero-compile contract (docs/serving.md)")
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="fleet mode: N backends behind the front "
                         "router, driven over the wire (0 = classic "
                         "batched-vs-serial bench)")
    ap.add_argument("--clients", type=int, default=8,
                    help="(fleet mode) concurrent wire clients")
    ap.add_argument("--kill-one", action="store_true",
                    help="(fleet mode) stop one backend abruptly a "
                         "third of the way through; lost_requests must "
                         "stay 0")
    args = ap.parse_args()
    if args.scenario:
        out = run_scenario_bench(args)
    elif args.disagg:
        out = run_disagg_bench(args)
    elif args.decode and args.router:
        out = run_decode_router_bench(args)
    elif args.decode and args.long_context:
        out = run_long_context_bench(args)
    elif args.decode:
        out = run_decode_bench(args)
    elif args.router:
        out = run_router_bench(args)
    else:
        out = run_bench(args)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
