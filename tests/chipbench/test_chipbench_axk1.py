"""Family ``axk1`` through the benchmark: its configuration file states
its cut as the harness wants it, its counts equal hand sums at the
published widths, and a toy cut of it (``tests/chipbench/data/
axk1-tiny.json``) runs the serving driver end to end on the CPU, plain,
traced and under both controls."""
import json
import os
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-axk1-share16-longturns"
SEED = 2 ** 31 + 28
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
MIX = {"driver": "serve", "loop": "closed", "clients_per_slot": 1,
       "prompt_lens": [8, 16, 24], "output_lens": [4, 8],
       "sharing": "none", "trace_seconds": 0.5}


@pytest.fixture(scope="module")
def loaded():
    from chipbench import harness

    bench = harness.load_benchmark()
    return (bench,) + harness.load_config(bench, "axk1-share16")


def test_the_configuration_states_its_cut(loaded):
    from chipbench import harness

    bench, raw, sizes, family = loaded
    entry = next(c for c in bench["configs"] if c["name"] == "axk1-share16")
    assert entry["reduced"] == REDUCED and entry["source"] == raw["source"]
    assert harness.cut_problems(entry["reduced"], raw, family.CUTS) == []
    assert raw["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    assert raw["deployment"]["chips_per_layer"] == 16
    # the router keeps its published width and picks; the share is held
    assert sizes["n_routed"] == 192 and sizes["top_k"] == 8
    assert sizes["held"] == (0, 12) and sizes["vocab_size"] == 20480
    assert sizes["max_seq_len"] == raw["assumed"]["serving_max_len"] == 8192
    # a width of the source that differed would be a different model
    for key, value in {"hidden_size": 7168, "intermediate_size": 18432,
                       "moe_intermediate_size": 2048, "q_lora_rank": 1536,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "num_attention_heads": 64, "num_experts_per_tok": 8,
                       "n_group": 8, "topk_group": 4,
                       "max_position_embeddings": 131072}.items():
        assert raw[key] == value, key
    bad = dict(raw, n_routed_experts=6)
    assert any("8 routed experts" in p for p in harness.cut_problems(
        entry["reduced"], bad, family.CUTS))


def test_the_cell_joins_the_serving_metrics(loaded):
    from chipbench import harness

    bench = loaded[0]
    cell = harness.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == \
        "longturns-closed-1x-slots"
    assert set(harness.cell_metrics(bench, CELL, "end_to_end")) == {
        "serve_tokens_per_s", "ttft_p50_ms", "itl_p95_ms", "setup_s"}
    chat = set(harness.cell_metrics(bench, "serve-gpt2-124m-chat",
                                    "per_layer"))
    mine = set(harness.cell_metrics(bench, CELL, "per_layer"))
    assert len(chat) == 19 and mine == chat | {
        "mla.decode_attn_roofline", "moe.held_assignments_per_token",
        "moe.held_load_max_over_mean"}
    for name in mine:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", name + ".py")), name


# Hand sums at the published widths: attention 101.1M a layer (five
# matrices and four norm gains), an expert (and the shared one) 44.0M,
# a router 1.4M, the dense FFN 396.4M.
ATT = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
    + 64 * 128 * 7168 + 1536 + 512 + 2 * 7168
EXPERT = 3 * 7168 * 2048
LAYERS = 6 * ATT + 3 * 7168 * 18432 + 5 * (7168 * 192 + 13 * EXPERT)


def test_program_shapes_at_the_published_widths(loaded):
    _, _, sizes, family = loaded
    shapes = family.param_shapes(sizes)
    n = sum(int(v.size) for v in shapes.values())
    want = LAYERS + 2 * 20480 * 7168 + 7168     # + embedding, head, norm
    assert n == want and 4.16e9 < n < 4.18e9
    assert {str(v.dtype) for v in shapes.values()} == {"bfloat16"}
    assert shapes["layers.1.mlp.experts.router"].shape == (7168, 192)
    assert shapes["layers.1.mlp.experts.gate_proj"].shape == (12, 7168, 2048)
    assert family.fill("layers.0.self_attn.kv_a_layernorm") == "ones"
    assert family.fill("norm") == "ones"
    assert family.fill("layers.3.mlp.experts.down_proj") == 0.02


def test_counts_against_hand_sums(loaded):
    _, _, sizes, family = loaded
    expert = EXPERT
    resident = LAYERS + 7168 + 7168 * 20480     # + final norm, head
    # every held expert hit (rows unknown): all but the embedding, once
    assert family.decode_weight_bytes(sizes) == 2 * resident
    # one cached position: 576 values a layer, 6 layers, bfloat16
    assert family.latent_bytes_per_token(sizes) == 6 * 1152
    assert family.decode_step_bytes(sizes, 1000) == \
        2 * resident + 1000 * 6 * 1152
    # 48 rows leave (23/24)^48 = 13% of the held experts idle
    hit = family.experts_hit(sizes, 48)
    assert hit == pytest.approx(12 * (1 - (23 / 24) ** 48))
    assert family.decode_step_bytes(sizes, 0, rows=48) == pytest.approx(
        2 * (resident - 5 * (12 - hit) * expert))
    # one layer's absorbed attention: per (head, live row) a 576-wide
    # score product and a 512-wide value product
    flops, nbytes = family.latent_attention_cost(sizes, 200_000, 48)
    assert flops == 2 * 64 * 200_000 * (576 + 512)
    assert nbytes == 200_000 * 1152 + 48 * 64 * (512 + 64 + 512) * 2
    assert family.PROGRAMS["paged_step"][0] == "exec:decode.pstep"


def test_the_reader_of_the_kernel_finds_it_by_its_name(loaded):
    from chipbench import harness
    from paddle_tpu.ops.pallas import latent_attention

    _, _, sizes, family = loaded
    assert family.LATENT_ATTENTION_OP == latent_attention.KERNEL_NAME
    # a program that has no such kernel, or no counters: nothing, not an
    # error (the parent commit under this PR's benchmark files)
    ctx = {"family": harness.load_family("gpt"), "peak": {"flops": 1.0,
                                                         "bytes_per_s": 1.0},
           "trace": None, "records": [], "engine_stats": ({"steps": 0},
                                                          {"steps": 3}),
           "sizes": sizes, "t_open": 0.0, "t_close": 1.0}
    got = harness.read_metrics(["mla.decode_attn_roofline",
                                "moe.held_assignments_per_token",
                                "moe.held_load_max_over_mean"], ctx)
    assert got == {}
    counted = dict(ctx, engine_stats=(
        {"routed": [[0, 0], [1, 1]], "routed_tokens": 10},
        {"routed": [[30, 10], [21, 21]], "routed_tokens": 50}))
    got = harness.read_metrics(["moe.held_assignments_per_token",
                                "moe.held_load_max_over_mean"], counted)
    assert got["moe.held_assignments_per_token"] == 80 / (40 * 2)
    assert got["moe.held_load_max_over_mean"] == 30 / 20


def test_traffic_file_is_what_the_issue_names():
    from chipbench import traffic

    mix = traffic.load("longturns-closed-1x-slots")
    assert mix["prompt_lens"] == [1536, 2560, 3072, 4096, 6144]
    assert mix["output_lens"] == [256, 320, 384, 448, 512]
    assert mix["sharing"] == "none" and mix["clients_per_slot"] == 1
    assert traffic.longest_request(mix) == 6656 < 8192


def _run(trace, control=None):
    from chipbench import harness

    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": "axk1-tiny", "file": "tests/chipbench/data/axk1-tiny.json",
         "reduced": REDUCED}]
    cell = {"name": CELL, "config": "axk1-tiny", "traffic": "x", "chips": 1}
    out = harness.load_driver("serve").run(
        bench=bench, cell=cell, mix=MIX, seed=SEED, seconds=1.0,
        trace=trace, t_process_start=time.perf_counter(),
        require_tpu=False, control=control,
        engine_kw={"max_slots": 4, "page_tokens": 8})
    return json.loads(json.dumps(out))


@pytest.fixture(scope="module")
def plain():
    return _run(False)


def test_rehearsal_end_to_end(plain):
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] > 10
    assert set(plain["metrics"]) == {"serve_tokens_per_s", "ttft_p50_ms",
                                     "itl_p95_ms", "setup_s"}
    # float32 at "highest" on the CPU: the served tokens are the
    # reference's first at every position
    gap = plain["checks"]["served_gap_mean"]
    assert 0 <= gap["value"] < 1e-6 < gap["limit"]
    assert plain["checks"]["compiles_in_window"]["value"] == 0


def test_rehearsal_traced_reads_the_new_counters():
    out = _run(True)
    assert out["correct"] is True
    got = out["metrics"]
    assert {"slots.count", "engine.batch_rows_mean", "step.decode_ms_p50",
            "step.prefill_ms_p50", "moe.held_assignments_per_token",
            "moe.held_load_max_over_mean"} <= set(got)
    # 8 of 16 experts held, 4 picks a token: 2 a token when even
    assert 1.0 < got["moe.held_assignments_per_token"]["value"] < 3.0
    assert got["moe.held_load_max_over_mean"]["value"] >= 1.0
    # no chip: nothing read from a device trace
    assert not set(got) & {"mla.decode_attn_roofline",
                           "step.decode_roofline", "serve.peak_hbm_gb"}


@pytest.mark.parametrize("control", ["program", "reference"])
def test_both_controls_read_above_the_program(control, plain):
    """One operand precision down, through the program's own path and
    through the reference: each reads far above what the program reads
    (0 here), so a limit between them exists."""
    out = _run(False, control=control)
    gap = out["checks"]["served_gap_mean"]["value"]
    assert gap > 1e-4 > plain["checks"]["served_gap_mean"]["value"]
