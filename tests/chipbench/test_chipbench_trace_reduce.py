"""The trace reduction, on synthetic events and on a small trace
recorded on the chip (``chipbench/fixtures/``: the first events of
every line of one v5e's plane, as `trace_reduce.head` cut them)."""
import json
import os

import pytest

from chipbench import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(tr.__file__), "fixtures")


def _trace(ops, modules, plane="/device:TPU:0"):
    return {"planes": [{"name": plane, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}


SYNTH = _trace(
    ops=[["%while.1", 0, 1000], ["%fusion.1 = f32[8]", 0, 400],
         ["%fusion.2 = f32[8]", 500, 400], ["%copy.3", 2000, 500],
         ["%fusion.1 = f32[8]", 3000, 100]],
    modules=[["jit_prefill(123)", 0, 1000], ["jit_paged_step(9)", 2000, 500],
             ["jit_paged_step(9)", 3000, 100]])


def test_busy_is_the_union_and_window_the_extent():
    busy, window = tr.device_busy(SYNTH)
    assert busy == pytest.approx(1600e-9)
    assert window == pytest.approx(3100e-9)


def test_union_counts_nested_and_overlapping_events_once():
    assert tr.union_seconds([["a", 0, 10], ["b", 2, 3], ["c", 8, 6],
                             ["d", 20, 5]]) == pytest.approx(19e-9)


def test_self_time_takes_children_off_their_parent():
    top = dict(tr.top_ops(SYNTH))
    assert top["while.1"] == pytest.approx(200e-9)
    assert top["fusion.1_f32_8"] == pytest.approx(500e-9)
    assert list(top)[0] in ("copy.3", "fusion.1_f32_8")


def test_idle_gaps_are_named_by_the_programs_around_them():
    gaps = dict(tr.idle_gaps(SYNTH, floor_ns=10))
    assert gaps == {"prefill-paged_step": pytest.approx(1000e-9),
                    "paged_step-paged_step": pytest.approx(500e-9)}


def test_module_and_op_durations_by_pattern():
    assert tr.module_durations(SYNTH, r"paged_step") == \
        pytest.approx([500e-9, 100e-9])
    assert tr.module_durations(SYNTH, r"^prefill$") == \
        pytest.approx([1000e-9])
    assert sum(tr.op_durations(SYNTH, r"fusion\.1")) == \
        pytest.approx(500e-9)


def test_a_trace_without_device_operations_reads_as_nothing():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["x", 0, 5]]}]}]}
    for t in (None, {}, host_only, _trace([], [])):
        assert tr.device_busy(t) == (0.0, 0.0)
        assert tr.breakdown(t) == {"device_ops": [], "idle_gaps": []}
        assert tr.module_durations(t, "x") == []


def test_names_are_cut_to_the_allowed_characters():
    name = tr.short_name("%fusion.7 = f32[6,50304]{1,0:T(8,128)} fusion(...)")
    assert name.startswith("fusion.7_f32_6_50304") and len(name) <= 64
    assert tr.module_name("jit__write_kv_pages(8899)") == "write_kv_pages"


def test_cut_keeps_one_chip_a_time_slice_and_short_names():
    two = {"planes": SYNTH["planes"] + _trace(
        [["x", 0, 1]], [["jit_x(1)", 0, 1]], "/device:TPU:1")["planes"]}
    cut = tr.cut(two, start_s=400e-9, length_s=2000e-9)
    assert [p["name"] for p in cut["planes"]] == ["/device:TPU:0"]
    ops, mods = (ln["events"] for ln in cut["planes"][0]["lines"])
    assert [e[0] for e in ops] == ["%fusion.2 = f32[8]", "%copy.3"]
    assert [e[0] for e in mods] == ["jit_paged_step(9)"]
    long = "%closed_call.2 = " + "x" * 300 + \
        ' custom_call_target="tpu_custom_call", more'
    short = tr.cut(_trace([[long, 0, 1]], [["jit_a(1)", 0, 1]]),
                   name_limit=40)["planes"][0]["lines"][0]["events"][0][0]
    assert len(short) < 100
    assert short.endswith('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(FIXTURES) if f.endswith(".json")))
def test_recorded_trace(name):
    with open(os.path.join(FIXTURES, name)) as f:
        fx = json.load(f)
    trace, want = fx["trace"], fx["expect"]
    busy, window = tr.device_busy(trace)
    assert 0 < busy <= window
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert window == pytest.approx(want["window_s"], rel=1e-9)
    steps = tr.module_durations(trace, want["step_program"])
    assert len(steps) == want["steps"] and min(steps) > 0
    ops = tr.top_ops(trace)
    assert 0 < len(ops) <= 10 and ops[0][1] >= ops[-1][1] > 0
    assert ops[0][0] == want["top_op"]
    # self times add up to the busy time (nothing counted twice)
    total = sum(s for _, s in tr.self_times(
        tr.device_planes(trace)[0]["lines"][want["ops_line"]]["events"]))
    assert total == pytest.approx(busy, rel=0.02)
    for key, sec in tr.idle_gaps(trace):
        assert sec > 0 and "-" in key
