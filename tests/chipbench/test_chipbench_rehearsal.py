"""CPU rehearsal of both drivers end to end at gpt_tiny widths. What
it proves is control flow and counting; a CPU run is never printed
under the name of a device metric, and the measured path without a
chip fails."""
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 7


def _bench():
    from chipbench import harness

    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": "gpt-tiny", "file": "tests/chipbench/data/gpt-tiny.json"}]
    return bench


SERVE_MIX = {"driver": "serve", "loop": "closed", "clients_per_slot": 1,
             "prompt_lens": [8, 16, 24], "output_lens": [4, 8],
             "sharing": "none", "trace_seconds": 0.5}
TRAIN_MIX = {"driver": "train", "batch_size": 2, "seq_len": 64,
             "optimizer": "adam", "learning_rate": 3e-4, "amp": "O2",
             "preroll_steps": 3, "trace_seconds": 0.5}


def _run(driver, cell_name, mix, trace, **kw):
    from chipbench import harness

    cell = {"name": cell_name, "config": "gpt-tiny", "traffic": "x",
            "chips": 1}
    out = harness.load_driver(driver).run(
        bench=_bench(), cell=cell, mix=mix, seed=SEED, seconds=1.5,
        trace=trace, t_process_start=time.perf_counter(),
        require_tpu=False, **kw)
    return json.loads(json.dumps(out))       # the line as it is printed


@pytest.fixture(scope="module")
def serve_plain():
    return _run("serve", "serve-gpt2-124m-chat", SERVE_MIX, False,
                engine_kw={"max_slots": 4})


@pytest.fixture(scope="module")
def serve_traced():
    return _run("serve", "serve-gpt2-124m-chat", SERVE_MIX, True,
                engine_kw={"max_slots": 4})


@pytest.fixture(scope="module")
def train_plain():
    return _run("train", "train-gpt2-124m-fit", TRAIN_MIX, False)


@pytest.fixture(scope="module")
def train_traced():
    return _run("train", "train-gpt2-124m-fit", TRAIN_MIX, True)


def _well_formed(out):
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    for m in out["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


def test_serve_driver_end_to_end(serve_plain):
    out = serve_plain
    _well_formed(out)
    # correct = reference agreement + zero compiles in window + streams
    assert out["correct"] is True
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "ttft_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    # what the window served, held against the reference after it
    gap = out["checks"]["served_gap_mean"]
    assert 0 <= gap["value"] < 1e-6 < gap["limit"]


def test_serve_driver_traced_reports_per_layer_metrics_only(serve_traced):
    out = serve_traced
    _well_formed(out)
    assert out["correct"] is True
    got = set(out["metrics"])
    assert {"slots.count", "engine.batch_rows_mean",
            "engine.host_ms_per_tick", "engine.stalled_gap_share",
            "admit.host_ms_p50", "step.prefill_ms_p50",
            "step.decode_ms_p50"} <= got
    assert out["metrics"]["slots.count"]["value"] == 4
    assert 1 <= out["metrics"]["engine.batch_rows_mean"]["value"] <= 4
    # no chip: nothing read from a device trace or a device's memory
    assert not got & {"step.decode_roofline", "serve.device_idle_share",
                      "serve.peak_hbm_gb", "serve_tokens_per_s"}
    assert out["device"]["busy_s"] == 0 and out["breakdown"] == \
        {"device_ops": [], "idle_gaps": []}


def test_train_driver_end_to_end(train_plain):
    out = train_plain
    _well_formed(out)
    assert out["correct"] is True         # first loss vs reference loss
    assert out["attempted"] > 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_train_driver_traced(train_traced):
    out = train_traced
    _well_formed(out)
    assert out["correct"] is True
    got = set(out["metrics"])
    assert "fit.host_blocked_share" in got
    assert not got & {"train.mfu", "train.step_ms_p50",
                      "train.flash_roofline", "train.device_idle_share",
                      "train.peak_hbm_gb", "train_tokens_per_s"}


def test_reachable_rungs_come_from_the_traffic_and_the_public_ladders():
    from chipbench import harness, traffic

    serve = harness.load_driver("serve")

    class Engine:
        page_tokens = 16
        kv_ladder = [16, 32, 64, 128, 256, 512, 1024, 2048]
        page_ladder = [1, 2, 4, 8, 16, 32, 64, 128]
        batch_ladder = [1, 2, 4, 6]

    kv, pages, batch = serve.reachable(
        Engine, traffic.load("prompts-closed-1x-slots"))
    assert kv == [512, 1024, 2048]
    assert pages == [32, 64, 128] and batch == [1, 2, 4, 6]
    kv, pages, batch = serve.reachable(
        Engine, traffic.load("decode-closed-1x-slots"))
    assert kv == [64, 128, 256] and pages == [4, 8, 16, 32]


def test_the_sample_is_drawn_from_the_seed_and_holds_the_longest():
    from chipbench import harness

    serve = harness.load_driver("serve")

    def record(i, **kw):
        return dict({"prompt": [0] * 8, "plen": 8, "max_new": 4,
                     "t_submit": 10.0 + i, "times": [10.5 + i] * 4,
                     "tokens": [1, 2, 3, 4], "done": True, "error": None},
                    **kw)

    records = [record(i) for i in range(40)]
    records[17] = record(17, prompt=[0] * 24, plen=24)         # the longest
    records[3] = record(3, done=False, tokens=None)            # in flight
    records[4] = record(4, tokens=[1, 2, 3], times=[14.5] * 3)  # broken
    records[5] = record(5, t_submit=5.0)               # before the window
    a = serve.finished_sample(records, 9.0, 60.0, SEED, 8)
    assert len(a) == 8 and records[17] in a
    assert not any(r in a for r in (records[3], records[4], records[5]))
    assert a == serve.finished_sample(records, 9.0, 60.0, SEED, 8)
    assert a != serve.finished_sample(records, 9.0, 60.0, SEED + 1, 8)
    assert len(serve.finished_sample(records, 9.0, 60.0, SEED, 64)) == 37
    assert serve.finished_sample(records, 100.0, 160.0, SEED, 8) == []


def test_the_measured_path_without_a_chip_fails():
    from chipbench import harness

    with pytest.raises(SystemExit) as exc:
        harness.require_devices(1)
    assert exc.value.code == harness.NO_CHIP_RC != 0


def test_the_command_without_a_chip_prints_no_result(monkeypatch, capsys):
    import runpy

    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "serve-gpt2-124m-chat", "--seed", "1",
        "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(os.path.join(ROOT, "chipbench", "run.py"),
                       run_name="__main__")
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
