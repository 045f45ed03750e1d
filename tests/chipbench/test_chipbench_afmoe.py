"""Family ``afmoe`` through the benchmark: its configuration file
states its cut as the harness wants it and keeps every published width,
its parameter count and its counts equal hand sums, its new readers
read what the program adds (and nothing from a program without it), and
a toy cut of it (``tests/chipbench/data/afmoe-tiny.json``) runs the
serving driver end to end on the CPU, plain, traced and under its two
controls."""
import json
import os
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve-trinity-large-share8-longdocs"
CONFIG = "trinity-large-share8"
SEED = 2 ** 31 + 35
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
MIX = {"driver": "serve", "loop": "closed", "clients_per_slot": 1,
       "prompt_lens": [6, 20, 28], "output_lens": [6, 12],
       "sharing": "none", "trace_seconds": 0.5}
NEW = {"swa.window_pool_gb", "gqa.decode_attn_roofline",
       "swa.prefill_flash_roofline"}


@pytest.fixture(scope="module")
def loaded():
    from chipbench import harness

    bench = harness.load_benchmark()
    return (bench,) + harness.load_config(bench, CONFIG)


def _catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "Trinity-Large-Preview")


def test_the_configuration_states_its_cut(loaded):
    from chipbench import harness

    bench, raw, sizes, family = loaded
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == raw["source"]
    assert harness.cut_problems(entry["reduced"], raw, family.CUTS) == []
    assert raw["published"] == {"num_hidden_layers": 60, "num_experts": 256,
                                "vocab_size": 200192}
    dep = raw["deployment"]
    assert dep["chips_per_layer"] == 8 and dep["leading_dense_layers"] == 1
    assert dep["experts_held_first"] == 0
    assert dep["layers_held"] == [5, 6, 7, 8, 9]
    # the router keeps its published width and picks; the share is held
    assert sizes["n_routed"] == 256 and sizes["top_k"] == 4
    assert sizes["held"] == (0, 32) and sizes["vocab_size"] == 25024
    assert sizes["max_seq_len"] == raw["assumed"]["serving_max_len"] == 16384
    # published layers 5-9: the last dense layer, then one whole period
    assert sizes["layer_types"] == (
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention")
    assert sizes["dense_layers"] == 1 and raw["num_dense_layers"] == 6
    assert family.window_layers(sizes) == 4 and family.full_layers(sizes) == 1
    # a width of the source that differed would be a different model
    for key, value in {"hidden_size": 3072, "intermediate_size": 12288,
                       "moe_intermediate_size": 3072, "head_dim": 128,
                       "num_attention_heads": 48, "num_key_value_heads": 8,
                       "num_experts_per_tok": 4, "num_shared_experts": 1,
                       "n_group": 1, "topk_group": 1, "route_scale": 2.448,
                       "route_norm": True, "sliding_window": 4096,
                       "rms_norm_eps": 1e-5, "rope_theta": 10000,
                       "mup_enabled": True,
                       "max_position_embeddings": 262144}.items():
        assert raw[key] == value, key
    assert len(raw["layer_types"]) == 60            # the published list
    assert raw["layer_types"].count("full_attention") == 15
    bad = dict(raw, num_experts=6)
    assert any("8 routed experts" in p for p in harness.cut_problems(
        entry["reduced"], bad, family.CUTS))
    moved = dict(raw, deployment=dict(dep, layers_held=[4, 5, 6, 7, 8]))
    with pytest.raises(ValueError, match="dense"):
        family.sizes(moved)
    for word in ("gate", "positions", "qk_norm", "norm_order", "depth_scaled",
                 "embedding", "selection_bias", "cache", "weights",
                 "serving_max_len", "serving_compute"):
        assert word in raw["assumed"], word


def test_the_file_holds_the_catalog_entry_but_for_the_cut(loaded):
    """Every key of the catalog's `config` stands in the file with the
    catalog's value, `layer_types` whole, but the three reduced keys."""
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
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "longdocs-closed-1x-slots"
    assert len(cell["why"]) <= 199      # at most as long as the others'
    assert set(harness.cell_metrics(bench, CELL, "end_to_end")) == E2E
    longturns = set(harness.cell_metrics(
        bench, "serve-axk1-share16-longturns", "per_layer"))
    mine = set(harness.cell_metrics(bench, CELL, "per_layer"))
    # the serving metrics that still print and the expert layer's, and
    # the three this family brings; not the three `idle.*`, which print
    # nothing since the tick runs ahead, nor the latent kernel's
    moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert mine == {m for m in longturns
                    if not m.startswith(("idle.", "mla."))
                    and (moved[m] != "ttft_p50_ms"
                         or "ttft_p50_ms" in E2E)} | NEW
    for m in bench["per_layer"]:
        if m["name"] in NEW:            # a new metric lists this cell only
            assert m["workloads"] == [CELL] and m["unit"] in ("GB", "%")
        if m["name"].startswith(("idle.", "mla.", "kda.")):
            assert CELL not in m["workloads"]
    # the reader's share moves what the cell's numbers say it can: the
    # decode reader the tokens a second (a fraction of one step among
    # stalled gaps of a whole prefill), flash the stalled gap itself
    assert moved["gqa.decode_attn_roofline"] == "serve_tokens_per_s"
    assert moved["swa.prefill_flash_roofline"] == "itl_p95_ms"
    for name in mine:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", name + ".py")), name
    mix = traffic.load(cell["traffic"])
    assert mix["prompt_lens"] == [1536, 9216, 11264, 13312, 15360]
    assert mix["output_lens"] == [128, 160, 192, 224, 256]
    assert mix["sharing"] == "none" and mix["clients_per_slot"] == 1
    assert mix["loop"] == "closed" and mix["trace_seconds"] == 3.0
    assert mix["driver"] == "serve"
    assert traffic.longest_request(mix) == 15616 < 16384
    # four lengths in five lie more than two windows deep
    assert sum(p > 2 * 4096 for p in mix["prompt_lens"]) == 4


# The end-to-end metrics the cell reports (ISSUE 35: `ttft_p50_ms` and
# the three readers that move it only if six runs spread under half its
# bound; PERF.md section 2 has the readings).
E2E = {"serve_tokens_per_s", "itl_p95_ms", "setup_s", "ttft_p50_ms"}

# Hand sums at the published widths (ISSUE 35's arithmetic, redone).
ATTN = 3072 * 6144 * 3 + 3072 * 1024 * 2 + 2 * 128  # W_q W_g W_o, W_k W_v
DENSE = 3 * 3072 * 12288
EXPERT = 3 * 3072 * 3072
MOE = 32 * EXPERT + EXPERT + 3072 * 256 + 256       # held, shared, router, b
LAYERS = 5 * (ATTN + 4 * 3072) + DENSE + 4 * MOE
VOCAB = 2 * 25024 * 3072


def test_the_parameter_count_redone_from_the_file(loaded):
    _, raw, sizes, family = loaded
    assert (ATTN - 256, DENSE, EXPERT) == (62_914_560, 113_246_208,
                                           28_311_552)
    assert 32 * EXPERT == 905_969_664 and VOCAB == 153_747_456
    shapes = family.param_shapes(sizes)
    n = sum(int(v.size) for v in shapes.values())
    assert n == LAYERS + VOCAB + 3072               # + the final norm
    assert round(n / 1e6, 1) == 4321.9              # ISSUE 35: 4,321.8 M
    assert round(2 * n / 1e9, 2) == 8.64            # GB in bfloat16
    # whole, an expert layer is 14.7 GB: one chip cannot hold one
    whole = ATTN + EXPERT + 256 * EXPERT + 3072 * 256
    assert round(2 * whole / 1e9, 1) == 14.7
    assert family.attention_params(sizes) == ATTN
    f32 = {k for k, v in shapes.items() if str(v.dtype) == "float32"}
    assert {k.rsplit(".", 1)[1] for k in f32} == {"e_score_correction_bias"}
    assert {str(v.dtype) for k, v in shapes.items() if k not in f32} == {
        "bfloat16"}
    assert shapes["layers.1.mlp.experts.router"].shape == (3072, 256)
    assert shapes["layers.1.mlp.experts.gate_proj"].shape == (32, 3072, 3072)
    assert shapes["layers.0.mlp.gate_proj"].shape == (3072, 12288)
    assert shapes["layers.2.self_attn.k_proj"].shape == (3072, 1024)
    assert shapes["layers.2.self_attn.gate_proj"].shape == (3072, 6144)
    assert shapes["layers.2.self_attn.q_norm"].shape == (128,)
    assert family.fill("layers.0.self_attn.q_norm") == "ones"
    assert family.fill("layers.3.pre_mlp_layernorm") == "ones"
    assert family.fill("norm") == "ones"
    assert family.fill("layers.3.mlp.experts.down_proj") == 0.02
    assert family.fill("layers.1.mlp.experts.e_score_correction_bias") == 0.005
    # a slot: the full layer's pages for 16,384 positions + four rings
    from paddle_tpu.inference import model_kinds
    kind = model_kinds.for_config(family._config(sizes))
    assert kind.slot_bytes() == 67_108_864 + 4 * 16_777_216 == 134_217_728
    assert kind.state_bytes(26) == 27 * 4 * 16_777_216
    assert kind.default_page_tokens() == 128


def test_counts_against_hand_sums(loaded):
    _, _, sizes, family = loaded
    resident = LAYERS + 3072 + 3072 * 25024     # + final norm, head
    # every held expert hit (rows unknown): all but the embedding, once
    assert family.decode_weight_bytes(sizes) == 2 * resident
    # one cached position of one layer: a K and a V row of 1,024 values
    assert family.kv_row_bytes(sizes) == 4096
    hit = family.experts_hit(sizes, 26)
    assert hit == pytest.approx(32 * (1 - (63 / 64) ** 26))
    assert 0.33 < hit / 32 < 0.35               # a third of the held
    must = 2 * (resident - 4 * (32 - hit) * EXPERT)
    # 26 rows of 10,000 positions: the full layer reads them all, the
    # four window layers 4,096 of each
    assert family.decode_step_bytes(sizes, 260_000, rows=26) == \
        pytest.approx(must + (260_000 + 4 * 26 * 4096) * 4096)
    # inside the window every layer reads what there is
    assert family.decode_step_bytes(sizes, 26_000, rows=26) == \
        pytest.approx(must + 5 * 26_000 * 4096)
    flops, nbytes = family.gqa_attention_cost(sizes, 260_000, 100_000, 26)
    attended = 260_000 + 4 * 100_000
    assert flops == 4 * 48 * 128 * attended
    assert nbytes == attended * 4096 + 5 * 26 * 2 * 48 * 128 * 2
    # pairs a mask keeps: the triangle, and the band of 4,096
    assert family.band_pairs(16384) == 16384 * 16385 // 2
    assert family.band_pairs(16384, 4096) == 4096 * 4097 // 2 \
        + 12288 * 4096
    assert family.band_pairs(2048, 4096) == 2048 * 2049 // 2
    f = family.prefill_flash_flops(sizes, 16384)
    assert f == 4 * 48 * 128 * (16384 * 16385 // 2
                                + 4 * (4096 * 4097 // 2 + 12288 * 4096))
    assert 9.0e12 < f < 9.2e12      # 3.3 TFLOP full + 4 x 1.44 in the band
    assert family.PROGRAMS["paged_step"][0] == "exec:decode.pstep"


def test_the_readers_find_the_kernels_and_the_counter(loaded):
    from chipbench import harness
    from paddle_tpu.ops.pallas import gqa_attention

    _, _, sizes, family = loaded
    from paddle_tpu.ops.pallas import flash_attention  # noqa: F401
    assert family.GQA_ATTENTION_OP == "^%?" + gqa_attention.KERNEL_NAME
    assert family.FLASH_FORWARD_OP == "^%?flash_attention_fwd"
    records = [{"plen": 10000, "times": [0.1, 0.2, 0.3, 0.4]},
               {"plen": 1000, "times": [0.5, 0.6]}]
    flash = "%flash_attention_fwd.{} = (bf16[48,{},128]{{2,1,0}}, f32[48," \
        "{},128]{{2,1,0}}) custom-call"
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%paged_gqa_decode_attention.3 = custom-call", 0.0, 2.0e6],
            # operations that consume a kernel's result name it too
            ["%fusion.1 = bf16[2,48,128] fusion(%paged_gqa_decode_attention"
             ".3)", 3.0e6, 1.0e6],
            ["%fusion.2 = bf16[16384,6144] fusion(%flash_attention_fwd.1)",
             4.0e6, 1.0e6],
            ["%paged_gqa_decode_attention.4 = custom-call", 5.0e6, 4.0e6],
            [flash.format(1, 16384, 16384), 1.0e7, 3.0e7],
            [flash.format(2, 2048, 2048), 5.0e7, 1.0e6]]}]}]}
    ctx = {"family": family, "sizes": sizes, "trace": trace,
           "records": records, "t_open": 0.0, "t_close": 1.0,
           "peak": {"flops": 197e12, "bytes_per_s": 819e9},
           "engine_stats": ({"steps": 0}, {"steps": 2,
                                           "state_pool_bytes": 3_000_000})}
    got = harness.read_metrics(sorted(NEW), ctx)
    # 4 rows (token events after a request's first) in 2 steps; contexts
    # 10001, 10002, 10003 and 1001: the window layers see 4096 of the
    # long ones and all of the short one; five calls a step of 3 ms mean
    full = 10001 + 10002 + 10003 + 1001
    win = 3 * 4096 + 1001
    flops, nbytes = family.gqa_attention_cost(sizes, full / 2, win / 2, 2.0)
    assert nbytes / 819e9 > flops / 197e12          # bound by bytes
    assert got["gqa.decode_attn_roofline"] == pytest.approx(
        100 * (nbytes / 819e9) / (5 * 3.0e-3))
    # one call at each rung: a fifth of a prefill's attention each
    need = (family.prefill_flash_flops(sizes, 16384)
            + family.prefill_flash_flops(sizes, 2048)) / 5
    assert got["swa.prefill_flash_roofline"] == pytest.approx(
        100 * need / 197e12 / 3.1e-2)
    assert got["swa.window_pool_gb"] == 0.003
    # a program with no such kernel and no counter (the parent commit
    # under this PR's benchmark files, or another family): nothing
    gpt = dict(ctx, family=harness.load_family("gpt"),
               engine_stats=({"steps": 0}, {"steps": 2}))
    assert harness.read_metrics(sorted(NEW), gpt) == {}
    assert harness.read_metrics(sorted(NEW), dict(
        ctx, trace=None, engine_stats=({"steps": 0}, {
            "steps": 2, "state_pool_bytes": 0}))) == {}


def test_the_family_judges_a_part_of_a_long_window(loaded):
    """Of the requests a window finished the reference judges the
    traffic's longest kind and one in `CHECK_ONE_IN` of the others, by
    the request's own ids: the same for every run of a seed."""
    family = loaded[3]
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, 25024, 9300).tolist() for _ in range(200)]
    kept = sum(family.judged(r, 15616) for r in reqs)
    assert 30 <= kept <= 70 and family.CHECK_ONE_IN == 4
    assert all(family.judged(r + [0] * 6316, 15616) for r in reqs[:5])
    assert [family.judged(r, 15616) for r in reqs[:20]] == \
        [family.judged(list(r), 15616) for r in reqs[:20]]


def _run(trace, control=None, which="operand", seconds=2.0):
    from chipbench import harness
    from chipbench.reference import afmoe as reference

    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": "afmoe-tiny",
         "file": "tests/chipbench/data/afmoe-tiny.json",
         "reduced": REDUCED}]
    cell = {"name": CELL, "config": "afmoe-tiny", "traffic": "x",
            "chips": 1}
    was = reference.CONTROL, reference.CONTROL_WINDOW
    reference.CONTROL, reference.CONTROL_WINDOW = which, 4
    try:
        out = harness.load_driver("serve").run(
            bench=bench, cell=cell, mix=MIX, seed=SEED, seconds=seconds,
            trace=trace, t_process_start=time.perf_counter(),
            require_tpu=False, control=control,
            engine_kw={"max_slots": 4, "page_tokens": 4})
    finally:
        reference.CONTROL, reference.CONTROL_WINDOW = was
    return json.loads(json.dumps(out))


@pytest.fixture(scope="module")
def plain():
    return _run(False)


def test_rehearsal_end_to_end(plain):
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] > 5
    assert set(plain["metrics"]) == E2E
    # float32 at "highest" on the CPU: the served tokens are the
    # reference's first at every position, through pages AND rings
    # (a window of 8: every ring wraps within a request)
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
            "moe.held_load_max_over_mean", "swa.window_pool_gb"} <= set(got)
    # 5 slots' rings (4 and the null slot) of 4 window layers, K and V,
    # 8 rows of 2 x 16 float32 values
    assert got["swa.window_pool_gb"]["value"] == pytest.approx(
        5 * 4 * 2 * 8 * 32 * 4 / 1e9)
    # 8 of 16 experts held, 4 picks a token: 2 a token when even
    assert 1.0 < got["moe.held_assignments_per_token"]["value"] < 3.0
    # no chip: nothing read from a device trace
    assert not set(got) & {"gqa.decode_attn_roofline",
                           "swa.prefill_flash_roofline",
                           "step.decode_roofline", "serve.peak_hbm_gb"}


@pytest.mark.parametrize("control", ["program", "reference"])
@pytest.mark.parametrize("which", ["operand", "window"])
def test_a_control_reads_above_the_program(which, control, plain):
    """Float8 operands into every projection, and the window layers'
    ring kept at half its rows (4 of 8 here), each through the
    program's own path and through the reference: each reads above
    the limit, where the program reads 0 here, so the run's `correct`
    is false by that check alone and `correct` sees the mechanism. (A window of 4 s: a loaded
    machine finishes few requests in 1 s.)"""
    out = _run(False, control=control, which=which, seconds=4.0)
    gap = out["checks"]["served_gap_mean"]
    assert gap["value"] > gap["limit"] \
        > plain["checks"]["served_gap_mean"]["value"]
    # the harness's own verdict, and by this check alone
    assert out["correct"] is False
    assert [n for n, c in out["checks"].items()
            if c["value"] > c["limit"]] == ["served_gap_mean"]
