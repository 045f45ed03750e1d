"""Family ``kimi_linear`` through the benchmark: its configuration file
states its cut as the harness wants it and keeps every published width,
its counts equal hand sums, its new readers read what the program adds
(and nothing from a program without it), and a toy cut of it
(``tests/chipbench/data/kimi-linear-tiny.json``) runs the serving
driver end to end on the CPU, plain, traced and under its controls."""
import json
import os
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-kimi-linear-share2-reasoning"
SEED = 2 ** 31 + 33
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
MIX = {"driver": "serve", "loop": "closed", "clients_per_slot": 1,
       "prompt_lens": [8, 16, 24], "output_lens": [4, 8],
       "sharing": "none", "trace_seconds": 0.5}
NEW = {"kda.decode_state_roofline", "kda.state_pool_gb"}


@pytest.fixture(scope="module")
def loaded():
    from chipbench import harness

    bench = harness.load_benchmark()
    return (bench,) + harness.load_config(bench, "kimi-linear-share2")


def _catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct")


def test_the_configuration_states_its_cut(loaded):
    from chipbench import harness

    bench, raw, sizes, family = loaded
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-share2")
    assert entry["reduced"] == REDUCED and entry["source"] == raw["source"]
    assert harness.cut_problems(entry["reduced"], raw, family.CUTS) == []
    assert raw["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert raw["deployment"]["chips_per_layer"] == 2
    assert raw["deployment"]["leading_dense_layers"] == 1
    # the router keeps its published width and picks; the share is held
    assert sizes["n_routed"] == 256 and sizes["top_k"] == 8
    assert sizes["held"] == (0, 128) and sizes["vocab_size"] == 81920
    assert sizes["max_seq_len"] == raw["assumed"]["serving_max_len"] == 4096
    # one whole period after the dense layer: KDA, KDA, KDA, MLA, KDA
    assert sizes["kda_layers"] == (1, 2, 3, 5)
    assert sizes["full_attn_layers"] == (4,) and sizes["dense_layers"] == 1
    # a width of the source that differed would be a different model
    for key, value in {"hidden_size": 2304, "intermediate_size": 9216,
                       "moe_intermediate_size": 1024, "q_lora_rank": None,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "num_attention_heads": 32, "num_experts_per_token": 8,
                       "num_shared_experts": 1, "num_expert_group": 1,
                       "topk_group": 1, "routed_scaling_factor": 2.446,
                       "rms_norm_eps": 1e-5, "mla_use_nope": True}.items():
        assert raw[key] == value, key
    linear = raw["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(linear["kda_layers"]) == 20          # the published lists
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    bad = dict(raw, num_experts=6)
    assert any("8 routed experts" in p for p in harness.cut_problems(
        entry["reduced"], bad, family.CUTS))
    for word in ("selection_bias", "state", "conv", "value_heads", "biases",
                 "weights", "serving_max_len"):
        assert word in raw["assumed"], word


def test_the_file_holds_the_catalog_entry_but_for_the_cut(loaded):
    """Every key of the catalog's `config` stands in the file with the
    catalog's value, nested groups whole, but the three reduced keys."""
    cat = _catalog_entry()
    if cat is None:
        pytest.skip("no catalog beside the guides on this machine")
    raw = loaded[1]
    assert raw["source"] == cat["source_url"]
    for key, value in cat["config"].items():
        if key in REDUCED:
            assert raw[key] < value and raw["published"][key] == value
        else:
            assert raw[key] == value, key


def test_the_cell_joins_the_serving_metrics(loaded):
    from chipbench import harness, traffic

    bench = loaded[0]
    cell = harness.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == "kimi-linear-share2"
    assert cell["traffic"] == "reasoning-closed-1x-slots"
    # not `ttft_p50_ms`: six runs of the cell spread 9.4% on it against
    # the 4% a new cell is admitted under (PERF.md sections 2 and 7)
    assert set(harness.cell_metrics(bench, CELL, "end_to_end")) == {
        "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    longturns = set(harness.cell_metrics(
        bench, "serve-axk1-share16-longturns", "per_layer"))
    mine = set(harness.cell_metrics(bench, CELL, "per_layer"))
    # the serving metrics that still print, the latent kernel's and the
    # expert layer's, and the two this family brings; not the three
    # `idle.*`, which print nothing since the tick runs ahead, nor the
    # three that move `ttft_p50_ms`, which this cell does not report
    moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert mine == {m for m in longturns if not m.startswith("idle.")
                    and moved[m] != "ttft_p50_ms"} | NEW
    assert {m for m in longturns if moved[m] == "ttft_p50_ms"} == {
        "admit.host_ms_p50", "step.prefill_ms_p50", "queue.wait_ms_p50"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:            # a new metric lists this cell only
            assert m["workloads"] == [CELL]
    for name in mine:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", name + ".py")), name
    mix = traffic.load(cell["traffic"])
    assert mix["prompt_lens"] == [384, 768, 1024, 1536, 2048]
    assert mix["output_lens"] == [384, 512, 640, 768, 896]
    assert mix["sharing"] == "none" and mix["clients_per_slot"] == 1
    assert mix["loop"] == "closed" and mix["trace_seconds"] == 3.0
    assert traffic.longest_request(mix) == 2944 < 4096


# Hand sums at the published widths. A KDA mixer: three projections and
# their convolutions, the decay's and the gate's low-rank pairs with
# dt_bias and A_log, beta, the output norm and projection.
KDA = 3 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) \
    + 4096 + 32 + 2304 * 32 + 128 + 4096 * 2304
MLA = 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 + 4096 * 2304
EXPERT = 3 * 2304 * 1024
MOE = 128 * EXPERT + EXPERT + 2304 * 256 + 256      # held, shared, router, b
LAYERS = 4 * KDA + MLA + 5 * 2 * 2304 + 3 * 2304 * 9216 + 4 * MOE


def test_program_shapes_at_the_published_widths(loaded):
    _, _, sizes, family = loaded
    assert (KDA, MLA, EXPERT) == (39_514_272, 29_114_880, 7_077_888)
    shapes = family.param_shapes(sizes)
    n = sum(int(v.size) for v in shapes.values())
    want = LAYERS + 2 * 81920 * 2304 + 2304      # + embedding, head, norm
    assert n == want and 4.28e9 < n < 4.29e9     # 8.57 GB in bfloat16
    assert family.kda_params(sizes) == KDA and family.mla_params(sizes) == MLA
    f32 = {k for k, v in shapes.items() if str(v.dtype) == "float32"}
    assert {k.rsplit(".", 1)[1] for k in f32} == {
        "A_log", "dt_bias", "e_score_correction_bias"}
    assert {str(v.dtype) for k, v in shapes.items() if k not in f32} == {
        "bfloat16"}
    assert shapes["layers.1.mlp.experts.router"].shape == (2304, 256)
    assert shapes["layers.1.mlp.experts.gate_proj"].shape == (128, 2304, 1024)
    assert shapes["layers.3.self_attn.q_proj"].shape == (2304, 32 * 192)
    assert shapes["layers.0.self_attn.q_conv1d"].shape == (4096, 4)
    assert "layers.3.self_attn.A_log" not in shapes     # layer 4 is MLA
    assert family.fill("layers.0.self_attn.o_norm") == "ones"
    assert family.fill("norm") == "ones"
    assert family.fill("layers.3.mlp.experts.down_proj") == 0.02
    assert family.fill("layers.1.mlp.experts.e_score_correction_bias") == 0.005
    assert family.fill("layers.0.self_attn.k_conv1d") == 0.3
    assert family.fill("layers.0.self_attn.A_log") == 1.0


def test_the_decays_are_drawn_in_their_ranges(loaded):
    """`weights.make_params` fills normals; the family maps the two
    decay vectors to exp(A_log) in [1, 16] and softplus(dt_bias) in
    [0.001, 0.1], for program and reference alike, and nothing else."""
    import jax.numpy as jnp

    family = loaded[3]
    z = np.random.default_rng(0).normal(size=4096).astype(np.float32)
    raw = {"layers.0.self_attn.A_log": jnp.asarray(z[:32]),
           "layers.0.self_attn.dt_bias": jnp.asarray(z),
           "layers.0.self_attn.q_proj": jnp.ones((2, 2))}
    out = family.decay_params(raw)
    a = np.exp(np.asarray(out["layers.0.self_attn.A_log"]))
    dt = np.log1p(np.exp(np.asarray(out["layers.0.self_attn.dt_bias"],
                                    np.float64)))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 2.0
    assert 0.001 <= dt.min() * 1.001 and dt.max() <= 0.1001
    assert np.median(dt) == pytest.approx(0.01, rel=0.2)   # log-uniform
    assert out["layers.0.self_attn.q_proj"] is raw["layers.0.self_attn.q_proj"]
    again = family.to_reference(raw)
    np.testing.assert_array_equal(
        np.asarray(again["layers.0.self_attn.dt_bias"]),
        np.asarray(out["layers.0.self_attn.dt_bias"]))


def test_counts_against_hand_sums(loaded):
    _, _, sizes, family = loaded
    resident = LAYERS + 2304 + 2304 * 81920     # + final norm, head
    # every held expert hit (rows unknown): all but the embedding, once
    assert family.decode_weight_bytes(sizes) == 2 * resident
    # one cached position: 576 values of the ONE latent layer, bfloat16
    assert family.latent_bytes_per_token(sizes) == 1152
    state = 32 * 128 * 128 * 4                  # 2 MiB a layer a stream
    assert family.kda_state_bytes(sizes) == state == 2 * 2 ** 20
    # 200 rows: weights + live latent rows + each row's state of the 4
    # KDA layers read once and written once
    hit = family.experts_hit(sizes, 200)
    assert hit == pytest.approx(128 * (1 - (31 / 32) ** 200))
    assert family.decode_step_bytes(sizes, 300_000, rows=200) == \
        pytest.approx(2 * (resident - 4 * (128 - hit) * EXPERT)
                      + 300_000 * 1152 + 200 * 4 * 2 * state)
    # at 256 rows the state is a third of the step's bytes
    step = family.decode_step_bytes(sizes, 256 * 1800, rows=256)
    assert 0.30 < 256 * 4 * 2 * state / step < 0.36
    flops, nbytes = family.kda_step_cost(sizes, 200)
    assert flops == 200 * 32 * 7 * 128 * 128
    assert nbytes == 200 * (2 * state + 32 * (5 * 128 + 1) * 4)
    flops, nbytes = family.latent_attention_cost(sizes, 200_000, 48)
    assert flops == 2 * 32 * 200_000 * (576 + 512)
    assert nbytes == 200_000 * 1152 + 48 * 32 * (512 + 64 + 512) * 2
    assert family.PROGRAMS["paged_step"][0] == "exec:decode.pstep"


def test_the_readers_find_the_kernel_and_the_counter(loaded):
    from chipbench import harness
    from paddle_tpu.ops.pallas import kda, latent_attention

    _, _, sizes, family = loaded
    assert family.KDA_STEP_OP == kda.KERNEL_NAME
    assert family.LATENT_ATTENTION_OP == latent_attention.KERNEL_NAME
    records = [{"plen": 10, "times": [0.1, 0.2, 0.3, 0.4]},
               {"plen": 20, "times": [0.5, 0.6]}]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%kda_decode_step.3 = custom-call", 0.0, 2.0e6],
            ["%fusion.1", 3.0e6, 1.0e6],
            ["%kda_decode_step.4 = custom-call", 5.0e6, 4.0e6]]}]}]}
    ctx = {"family": family, "sizes": sizes, "trace": trace,
           "records": records, "t_open": 0.0, "t_close": 1.0,
           "peak": {"flops": 197e12, "bytes_per_s": 819e9},
           "engine_stats": ({"steps": 0}, {"steps": 2,
                                           "state_pool_bytes": 3_000_000})}
    got = harness.read_metrics(sorted(NEW), ctx)
    # 4 rows (token events after a request's first) in 2 steps: 2 rows a
    # step against the two calls' mean of 3 ms
    _, nbytes = family.kda_step_cost(sizes, 2.0)
    assert got["kda.decode_state_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / 3.0e-3)
    assert got["kda.state_pool_gb"] == 0.003
    # a program with no such kernel and no counter (the parent commit
    # under this PR's benchmark files, or another family): nothing
    gpt = dict(ctx, family=harness.load_family("gpt"),
               engine_stats=({"steps": 0}, {"steps": 2}))
    assert harness.read_metrics(sorted(NEW), gpt) == {}
    assert harness.read_metrics(sorted(NEW), dict(
        ctx, trace=None, engine_stats=({"steps": 0}, {
            "steps": 2, "state_pool_bytes": 0}))) == {}


def _run(trace, control=None, which="operand", seconds=2.0):
    from chipbench import harness
    from chipbench.reference import kimi_linear as reference

    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": "kimi-linear-tiny",
         "file": "tests/chipbench/data/kimi-linear-tiny.json",
         "reduced": REDUCED}]
    cell = {"name": CELL, "config": "kimi-linear-tiny", "traffic": "x",
            "chips": 1}
    was, reference.CONTROL = reference.CONTROL, which
    try:
        out = harness.load_driver("serve").run(
            bench=bench, cell=cell, mix=MIX, seed=SEED, seconds=seconds,
            trace=trace, t_process_start=time.perf_counter(),
            require_tpu=False, control=control,
            engine_kw={"max_slots": 4, "page_tokens": 8})
    finally:
        reference.CONTROL = was
    return json.loads(json.dumps(out))


@pytest.fixture(scope="module")
def plain():
    return _run(False)


def test_rehearsal_end_to_end(plain):
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] > 5
    assert set(plain["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                     "setup_s"}
    # float32 at "highest" on the CPU: the served tokens are the
    # reference's first at every position, through pages AND state
    gap = plain["checks"]["served_gap_mean"]
    assert 0 <= gap["value"] < 1e-6 < gap["limit"]
    assert plain["checks"]["compiles_in_window"]["value"] == 0
    assert plain["checks"]["broken_streams"]["value"] == 0


def test_rehearsal_traced_reads_the_new_counters():
    out = _run(True)
    assert out["correct"] is True
    got = out["metrics"]
    assert {"slots.count", "engine.batch_rows_mean", "step.decode_ms_p50",
            "tick.sample_ms_p50", "moe.held_assignments_per_token",
            "moe.held_load_max_over_mean", "kda.state_pool_gb"} <= set(got)
    # 5 slots' entries (4 and the null slot) of 4 KDA layers: a state
    # [4, 16, 16] float32 and 3 x 3 x 64 convolution inputs each
    assert got["kda.state_pool_gb"]["value"] == pytest.approx(
        5 * 4 * (4 * 16 * 16 * 4 + 9 * 64 * 4) / 1e9)
    # 8 of 16 experts held, 4 picks a token: 2 a token when even
    assert 1.0 < got["moe.held_assignments_per_token"]["value"] < 3.0
    # no chip: nothing read from a device trace
    assert not set(got) & {"kda.decode_state_roofline",
                           "mla.decode_attn_roofline",
                           "step.decode_roofline", "serve.peak_hbm_gb"}


@pytest.mark.parametrize("control", ["program", "reference"])
def test_the_operand_control_reads_above_the_program(control, plain):
    """Float8 operands into every projection, through the program's own
    path and through the reference: each reads far above what the
    program reads (0 here), so a limit between them exists. (A window
    of 4 s: a loaded machine finishes few requests in 1 s.)"""
    out = _run(False, control=control, which="operand", seconds=4.0)
    gap = out["checks"]["served_gap_mean"]["value"]
    assert gap > 1e-4 > plain["checks"]["served_gap_mean"]["value"]


def _tiny():
    from chipbench import harness, weights

    with open(os.path.join(ROOT, "tests", "chipbench", "data",
                           "kimi-linear-tiny.json")) as f:
        raw = json.load(f)
    family = harness.load_family("kimi_linear")
    sizes = family.sizes(raw)
    params = weights.make_params(family.param_shapes(sizes), SEED,
                                 family.fill)
    return family, sizes, params


@pytest.mark.parametrize("control", ["program", "reference"])
def test_the_state_control_keeps_the_state_in_bfloat16(control):
    """The second control, the recurrent state one precision down. A
    toy model's short answers seldom flip on it, so no window here: the
    control engine the family builds keeps its state pool (and nothing
    else) in bfloat16 and its logits leave the program's; the
    reference's control rounds the state after every token and its
    logits leave the reference's."""
    from chipbench.reference import kimi_linear as reference

    family, sizes, params = _tiny()
    ids = np.random.default_rng(3).integers(0, sizes["vocab_size"], 40)
    if control == "reference":
        c = family._ref_sizes(sizes)
        ref = family.to_reference(params)
        want = np.asarray(reference.forward(ref, ids, c))
        low = np.asarray(reference.forward(
            ref, ids, c, state=reference.CONTROL_STATE_DTYPE))
        assert np.abs(low - want).max() > 1e-4 * want.std()
        return
    rows = {}
    was, reference.CONTROL = reference.CONTROL, "state"
    try:
        for name, ctl in (("program", False), ("control", True)):
            eng = family.serving_engine(sizes, params, control=ctl,
                                        max_slots=1, page_tokens=8)
            state = eng._model_pools_sds()["state"][0]
            assert eng.cfg.operand_dtype is None
            assert str(state.dtype) == ("bfloat16" if ctl else "float32")
            sample, got = eng._sample, rows.setdefault(name, [])
            eng._sample = lambda row, req, pos=None, s=sample, g=got: (
                g.append(np.array(row, np.float32)), s(row, req, pos))[1]
            try:
                eng.submit(ids.tolist(), max_new_tokens=8, temperature=1.0,
                           top_k=1).result(timeout=300)
            finally:
                eng.stop()
    finally:
        reference.CONTROL = was
    a, b = np.stack(rows["program"]), np.stack(rows["control"])
    # the prefill's logits come from the float32 chunk form on both
    # sides; every step after it reads the rounded state
    assert np.abs(a[1:] - b[1:]).max() > 1e-4 * a.std()
