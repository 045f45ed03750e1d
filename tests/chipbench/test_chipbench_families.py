"""The seam a second architecture comes in by: a stand-in family and a
cut configuration that exist only under ``tests/chipbench/data/`` run
both drivers on the CPU, with nothing under ``chipbench/`` edited or
patched but the family search path; and the comparison of what the
window served with the reference says ``correct: false`` of an engine
that is wrong, and of the reference's own control."""
import json
import os
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join("tests", "chipbench", "data")
SEED = 2 ** 31 + 13
REDUCED = ["num_hidden_layers", "vocab_size"]
SERVE_MIX = {"driver": "serve", "loop": "closed", "clients_per_slot": 1,
             "prompt_lens": [8, 16, 24], "output_lens": [4, 8],
             "sharing": "none", "trace_seconds": 0.5}
CONTROL_MIX = dict(SERVE_MIX, output_lens=[8, 16])
TRAIN_MIX = {"driver": "train", "batch_size": 2, "seq_len": 64,
             "optimizer": "adam", "learning_rate": 3e-4, "amp": "O2",
             "preroll_steps": 3, "trace_seconds": 0.5}


@pytest.fixture(scope="module")
def harness():
    """The harness with the tests' families on its search path."""
    from chipbench import harness as h

    path = os.path.join(ROOT, DATA, "families")
    h.FAMILY_PATH.append(path)
    try:
        yield h
    finally:
        h.FAMILY_PATH.remove(path)


def family_limit():
    from chipbench.reference import gpt

    return gpt.GAP_TOL


def _run(harness, driver, cell_name, config, mix, trace, **kw):
    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": config, "file": f"{DATA}/{config}.json",
         "reduced": REDUCED}]
    cell = {"name": cell_name, "config": config, "traffic": "x", "chips": 1}
    out = harness.load_driver(driver).run(
        bench=bench, cell=cell, mix=mix, seed=SEED, seconds=1.0,
        trace=trace, t_process_start=time.perf_counter(),
        require_tpu=False, **kw)
    return json.loads(json.dumps(out))


def test_the_stand_in_exists_only_under_tests(harness):
    assert not os.path.exists(os.path.join(
        ROOT, "chipbench", "families", "standin.py"))
    family = harness.load_family("standin")
    with open(os.path.join(ROOT, DATA, "standin-cut.json")) as f:
        raw = json.load(f)
    assert harness.cut_problems(REDUCED, raw, family.CUTS) == []
    # its sizes share one key with family gpt's: the ids traffic draws
    gpt = harness.load_family("gpt")
    with open(os.path.join(ROOT, DATA, "gpt-tiny.json")) as f:
        tiny = json.load(f)
    assert set(family.sizes(raw)) & set(gpt.sizes(tiny)) == {"vocab_size"}


def test_a_cut_configuration_of_a_second_family_serves(harness):
    out = _run(harness, "serve", "serve-gpt2-124m-chat", "standin-cut",
               SERVE_MIX, True, engine_kw={"max_slots": 4})
    assert out["correct"] is True
    assert out["attempted"] > 10 and out["failed"] == 0
    assert {"slots.count", "engine.batch_rows_mean", "admit.host_ms_p50",
            "step.prefill_ms_p50", "step.decode_ms_p50",
            "queue.wait_ms_p50"} <= set(out["metrics"])
    # every number that decided `correct`, beside its limit, comes last
    assert list(out)[-1] == "checks"
    gap = out["checks"]["served_gap_mean"]
    assert 0 <= gap["value"] < 1e-6 and gap["limit"] == family_limit()
    assert out["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert out["checks"]["broken_streams"] == {"value": 0, "limit": 0}


def test_a_cut_configuration_of_a_second_family_trains(harness):
    out = _run(harness, "train", "train-gpt2-124m-fit", "standin-cut",
               TRAIN_MIX, False)
    assert out["correct"] is True
    assert out["attempted"] > 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["checks"]["loss_abs_err"]["value"] \
        <= out["checks"]["loss_abs_err"]["limit"] == 0.08


def test_an_undeclared_cut_is_refused_before_anything_runs(harness):
    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": "standin-cut", "file": f"{DATA}/standin-cut.json",
         "reduced": REDUCED[:1]}]
    with pytest.raises(ValueError, match="'vocab_size' has a published"):
        harness.load_config(bench, "standin-cut")


@pytest.mark.parametrize("fault", ["step", "weights"])
def test_a_wrong_engine_is_not_correct(harness, fault):
    """The whole of a run but the look for a chip, with the timed path
    broken underneath: a token altered where it is produced ("step":
    the step executable's logits rolled by one), and weights that are
    not the ones the seed made. The streams run, nothing compiles in
    the window, every request gets its tokens: only the comparison of
    the served tokens with the reference can tell."""
    out = _run(harness, "serve", "serve-gpt2-124m-chat", "standin-cut",
               SERVE_MIX, False, engine_kw={"max_slots": 4, "fault": fault})
    assert out["correct"] is False
    assert out["failed"] == 0 and out["attempted"] > 10
    checks = out["checks"]
    assert checks["served_gap_mean"]["value"] > 100 * family_limit()
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["broken_streams"]["value"] == 0


@pytest.mark.parametrize("control", ["program", "reference"])
def test_the_control_is_not_correct(harness, control):
    """A control put in the program's place comes out as not correct,
    at a size a test run can hold (the stand-in at 8 layers of 256, a
    vocabulary of 8,192, where both read three to five times the
    limit): "program", the program's own path one precision down, the
    engine built on its int8 weights; "reference", at each served
    position the token the reference puts first in bfloat16. On the
    chip at the cell's own size: ``chipbench/control.py`` (PERF.md
    section 2 has the readings)."""
    out = _run(harness, "serve", "serve-gpt2-124m-chat", "standin-wide",
               CONTROL_MIX, False, engine_kw={"max_slots": 4},
               control=control)
    assert out["correct"] is False
    assert out["checks"]["served_gap_mean"]["value"] > family_limit()
    assert out["checks"]["broken_streams"]["value"] == 0
    assert out["failed"] == 0


def test_the_harness_and_the_drivers_name_no_architecture():
    """Tentpole 1's grep: outside `families/gpt.py`, the reference and
    the GPT configuration files, nothing under `chipbench/` imports a
    model or reads a GPT key."""
    import re

    pat = re.compile(r"models\.gpt|GPTConfig|n_embd|n_head|"
                     r"reference import gpt")
    hits = []
    for d, dirs, files in os.walk(os.path.join(ROOT, "chipbench")):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", "reference",
                                                "configs")]
        for f in files:
            path = os.path.join(d, f)
            if not f.endswith(".py") or path.endswith(
                    os.path.join("families", "gpt.py")):
                continue
            with open(path) as fh:
                hits += [(os.path.relpath(path, ROOT), i + 1)
                         for i, line in enumerate(fh) if pat.search(line)]
    assert hits == []
