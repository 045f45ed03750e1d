"""The readers of the scheduler's tiling spans on a ring and a trace
made by hand (known answers, a gap that no span covers, a ring that
cannot be placed), and on the CPU rehearsal, where there is no device
plane and the ``idle.*`` readers return nothing."""
import json
import time
import types

import pytest

RING_METRICS = ["queue.wait_ms_p50", "tick.build_ms_p50",
                "tick.pull_ms_p50", "tick.sample_ms_p50",
                "loop.overhead_ms_p50", "loop.unspanned_share"]
IDLE_METRICS = ["idle.admit_share", "idle.tick_share", "idle.unnamed_share"]
# The ring and the trace below are of an engine with THREE programs (an
# admission of two dispatches, as the program had until PR 26): the
# join takes the programs' names from the family, so a stand-in names
# them; `test_the_gpt_family_names_two_programs` holds the real one.
THREE = types.SimpleNamespace(
    PROGRAMS={"paged_step": ("exec:decode.pstep", "s"),
              "prefill": ("exec:decode.prefill", "p"),
              "write_kv_pages": ("exec:decode.pwrite", "w")})
LETTER = dict(THREE.PROGRAMS.values())
NAMED = {prog: event for prog, (event, _) in THREE.PROGRAMS.items()}
ENGINE, CLIENT = 7, 8           # thread ids
T0 = 100.0                      # the window opens, host clock
DEVICE_EPOCH_NS = 5e12          # the profiler's clock is another


def X(name, start_ms, end_ms, args=None, tid=ENGINE):
    return ("X", name, T0 + start_ms / 1e3, (end_ms - start_ms) / 1e3,
            tid, args)


def tick(start, prov, build, launch, step, pull, sample):
    """A `decode.step` and its five phases, in ms from `start`."""
    t, out = start, []
    for name, dur in (("decode.step.provision", prov),
                      ("decode.step.build", build), (None, launch),
                      ("exec:decode.pstep", step),
                      ("decode.step.pull", pull), ("decode.sample", sample)):
        if name:
            out.append(X(name, t, t + dur))
        t += dur
    return [X("decode.step", start, t, {"batch": 2})] + out


def ring():
    """Three iterations: an admission and a tick; a tick; a wait and a
    long tick. Two milliseconds before the third lie in no span."""
    loop1 = [
        X("decode.loop", 0, 100, {"admits": 1, "active": 1}),
        X("decode.schedule", 0, 2, {"pending": 1, "paused": 0}),
        X("decode.admit", 2, 42, {"req": 1, "queued_ms": 4.0, "ok": True}),
        X("decode.admit.lookup", 2, 3),
        X("exec:decode.prefill", 4, 6),
        X("decode.admit.logits_pull", 6, 7),
        X("decode.admit.alloc", 7, 8, {"pages": 2}),
        X("decode.admit.kv_pull", 8, 12, {"bytes": 1}),
        X("decode.admit.repack", 12, 18, {"bytes": 1}),
        X("decode.admit.upload", 18, 20, {"bytes": 1}),
        X("exec:decode.pwrite", 20, 34),
        X("decode.admit.emit", 34, 36),
        X("decode.gauges", 42, 43),
    ] + tick(44, 0.5, 1.0, 0.5, 50, 1, 3)
    loop2 = [
        X("decode.loop", 100, 160, {"admits": 0, "active": 1}),
        X("decode.schedule", 100, 101, {"pending": 0, "paused": 0}),
    ] + tick(102, 0.5, 2.0, 0.5, 50, 2, 3)
    loop3 = [
        X("decode.loop", 162, 300, {"admits": 0, "active": 1}),
        X("decode.schedule", 162, 200, {"pending": 0, "paused": 0}),
        X("decode.idle", 163, 199),
    ] + tick(200, 1, 1, 0, 90, 3, 5)
    other = [X("decode.gauges", 50, 250, tid=CLIENT),      # another thread
             ("i", "decode.emit", T0 + 0.099, 0.0, ENGINE, {"req": 1})]
    return loop1 + loop2 + loop3 + other


def trace(programs, extra=()):
    """A device plane whose programs are (name, start_ms, dur_ms) on the
    ring's time axis, written on the profiler's clock."""
    mods = [[name, DEVICE_EPOCH_NS + start * 1e6, dur * 1e6]
            for name, start, dur in list(programs) + list(extra)]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": [["fusion.1", s, d]
                                       for _, s, d in mods]}]}]}


PROGRAMS = [("jit_prefill(1)", 4, 1), ("jit__write_kv_pages(2)", 20, 13),
            ("jit_paged_step(3)", 46, 49), ("jit_paged_step(3)", 105, 49),
            ("jit_paged_step(3)", 202, 88)]
CONVERT = [("jit_convert_element_type(4)", 3.5, 0.001)]


def ctx_for(ring_events, traced=None, seconds=0.3, trace_seconds=0.2):
    return {"ring": ring_events, "trace": traced, "t_open": T0,
            "t_close": T0 + seconds, "family": THREE,
            "mix": {"trace_seconds": trace_seconds}}


def read(names, ctx):
    from chipbench import harness

    return harness.read_metrics(names, ctx)


def test_ring_metrics_known_answers():
    got = read(RING_METRICS, ctx_for(ring()))
    assert got == pytest.approx({
        "queue.wait_ms_p50": 4.0,
        "tick.build_ms_p50": 2.0,           # of 1.5, 2.5, 2.0
        "tick.pull_ms_p50": 2.0,
        "tick.sample_ms_p50": 3.0,
        "loop.overhead_ms_p50": 4.0,        # of 100-40-56, 60-58, 138-100
        # leaf spans hold 91.5 + 58.5 + 136 of the window's 300 ms
        "loop.unspanned_share": 100.0 * 14.0 / 300.0}, abs=1e-6)


def test_a_program_without_the_spans_reads_what_it_has():
    """The parent commit: `decode.admit`, `decode.step`, `decode.sample`
    and `exec:*` only. Nothing raises; what has no span is left out."""
    old = [e for e in ring() if e[1] in (
        "decode.admit", "decode.step", "decode.sample", "decode.emit",
        "exec:decode.pstep", "exec:decode.prefill", "exec:decode.pwrite")]
    old = [e[:5] + ({"req": 1},) if e[1] == "decode.admit" else e
           for e in old]
    got = read(RING_METRICS + IDLE_METRICS,
               ctx_for(old, trace(PROGRAMS, CONVERT)))
    assert set(got) == {"tick.sample_ms_p50"} | set(IDLE_METRICS)
    assert sum(got[n] for n in IDLE_METRICS) == pytest.approx(100.0)
    for empty in (None, []):
        assert read(RING_METRICS + IDLE_METRICS, ctx_for(empty)) == {}


def test_idle_gaps_are_shared_out_over_the_spans(capsys):
    """Five gaps, 86.499 ms: before the prefill (0.499, the admission's
    own time), prefill -> page write (15, all inside the admission),
    page write -> step (13: 9 admission, 2 tick, 2 between them), step
    -> step (10: 8 tick, 2 loop), step -> step over a wait (48: 8 tick,
    38 schedule and wait, 2 in no span at all)."""
    got = read(IDLE_METRICS, ctx_for(ring(), trace(PROGRAMS, CONVERT)))
    idle = 0.499 + 15 + 13 + 10 + 48
    assert got == pytest.approx({
        "idle.admit_share": 100 * (0.499 + 15 + 9) / idle,
        "idle.tick_share": 100 * (2 + 8 + 8) / idle,
        "idle.unnamed_share": 100 * (2 + 2 + 40) / idle}, abs=1e-6)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("IDLE ")]
    assert len(lines) == 1                  # made once for three readers
    line = json.loads(lines[0][5:])
    assert line["programs"] == 5
    assert line["idle_s"] == pytest.approx(idle / 1e3)
    leaf = line["leaf_s"]
    assert leaf["no-span"] == pytest.approx(0.002)
    assert leaf["decode.idle"] == pytest.approx(0.036)
    assert leaf["decode.admit.repack"] == pytest.approx(0.006)
    assert leaf["decode.admit/self"] == pytest.approx(0.006499)
    assert leaf["exec:decode.pstep"] == pytest.approx(0.002)   # the tails
    assert sum(leaf.values()) == pytest.approx(line["idle_s"])


def test_placement_goes_by_kind_and_duration():
    from chipbench import gapjoin

    calls = gapjoin.ring_programs(ring(), LETTER)
    assert "".join(LETTER[c[2]] for c in calls) == "pwsss"
    _, progs = gapjoin.device_programs(trace(PROGRAMS[3:]),   # 49, 88 ms
                                       NAMED)
    # by kind alone two steps fit at 2 and at 3; the 88 ms one only
    # under the 90 ms call
    assert gapjoin.place(progs, calls, 0, 1e9) == [3]
    assert gapjoin.place(progs, calls, 0, T0 + 0.05) == []    # too early
    _, progs = gapjoin.device_programs(trace(PROGRAMS[2:4]),  # 49, 49 ms
                                       NAMED)
    assert gapjoin.place(progs, calls, 0, 1e9) == [2, 3]


def test_the_gpt_family_names_two_programs():
    """Since PR 26 an admission is one dispatch: the page write has no
    program and no ring event of its own, and the join looks for none."""
    from chipbench import gapjoin, harness

    gpt = harness.load_family("gpt")
    assert gpt.PROGRAMS == {"paged_step": ("exec:decode.pstep", "s"),
                            "prefill": ("exec:decode.prefill", "p")}
    got = read(IDLE_METRICS, dict(ctx_for(ring(), trace(PROGRAMS, CONVERT)),
                                  family=gpt))
    # one placement of "psss"; the page write's 13 ms now read as idle
    assert sum(got.values()) == pytest.approx(100.0)
    assert got["idle.admit_share"] > 100 * (0.499 + 15 + 9) / 86.499


def test_a_ring_that_cannot_be_placed_yields_nothing(capsys):
    twice = read(IDLE_METRICS, ctx_for(ring(), trace(PROGRAMS[2:4])))
    never = read(IDLE_METRICS, ctx_for(ring(), trace([
        ("jit__write_kv_pages(2)", 4, 1), ("jit_prefill(1)", 20, 1)])))
    assert twice == never == {}
    lines = [json.loads(ln[5:]) for ln in
             capsys.readouterr().out.splitlines() if ln.startswith("IDLE ")]
    assert [ln["placements"] for ln in lines] == [2, 0]
    # the two sequences side by side, for the reader of the run
    assert lines[0]["device"] == "ss" and lines[0]["ring"] == "pwsss"
    assert lines[1]["device"] == "wp"


def test_segments_cut_a_thread_at_every_boundary():
    from chipbench import spanread

    events = [X("a", 0, 10), X("b", 2, 4), X("c", 3, 4), X("d", 12, 13),
              X("e", 0, 20, tid=CLIENT)]
    pieces = [(round((a - T0) * 1e3, 6), round((b - T0) * 1e3, 6), names,
               leaf) for a, b, names, leaf in
              spanread.segments(events, ENGINE)]
    assert pieces == [(0, 2, ("a",), False), (2, 3, ("a", "b"), False),
                      (3, 4, ("a", "b", "c"), True), (4, 10, ("a",), False),
                      (12, 13, ("d",), True)]
    assert spanread.engine_thread(events) is None
    assert spanread.engine_thread(ring()) == ENGINE


@pytest.fixture(scope="module")
def rehearsal():
    """The serve driver end to end on the CPU at gpt_tiny widths, traced."""
    from chipbench import harness

    bench = harness.load_benchmark()
    bench["configs"] = bench["configs"] + [
        {"name": "gpt-tiny", "file": "tests/chipbench/data/gpt-tiny.json"}]
    cell = {"name": "serve-gpt2-124m-chat", "config": "gpt-tiny",
            "traffic": "x", "chips": 1}
    mix = {"driver": "serve", "loop": "closed", "clients_per_slot": 1,
           "prompt_lens": [8, 16, 24], "output_lens": [4, 8],
           "sharing": "none", "trace_seconds": 0.5}
    return harness.load_driver("serve").run(
        bench=bench, cell=cell, mix=mix, seed=2 ** 31 + 11, seconds=1.5,
        trace=True, t_process_start=time.perf_counter(), require_tpu=False,
        engine_kw={"max_slots": 4})


def test_rehearsal_prints_the_ring_metrics_and_no_idle_share(rehearsal):
    got = rehearsal["metrics"]
    assert rehearsal["correct"] is True
    assert set(RING_METRICS) <= set(got)
    assert not set(IDLE_METRICS) & set(got)      # no device plane here
    assert all(got[n]["value"] >= 0 for n in RING_METRICS)
    assert got["loop.unspanned_share"]["value"] < 100
    tick_host = sum(got[n]["value"] for n in (
        "tick.build_ms_p50", "tick.pull_ms_p50", "tick.sample_ms_p50"))
    assert tick_host <= 1.5 * got["engine.host_ms_per_tick"]["value"]
