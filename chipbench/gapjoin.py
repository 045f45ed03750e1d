"""The device's idle gaps laid over the scheduler's spans.

A reader gets the program's ring (host clock) and the normalised device
planes of the trace (profiler clock), and nothing that ties the two
clocks. What ties them is the order of dispatch: the device runs
programs in the order the host dispatched them, and the programs the
engine compiles ahead of time each leave an ``exec:`` event in the
ring; which programs those are is the family's to say (``PROGRAMS``:
device program -> (ring event, its letter in the ``IDLE`` line)). The
traced sequence of those programs is placed in the ring's by kind and
duration, searched only near the host time at which the
harness started the profiler. Device time ``t`` before program *k* is
then host time ``t - start_dev[k] + start_ring[k]``: a gap that ends
where program *k* starts is the host interval of the same length that
ends where the ring's *k*-th ``exec:`` event begins. Each gap is shared
out over the spans that cover that interval on the engine's thread.
Without exactly one placement nothing is returned.
"""
import json

from . import ringread, spanread, trace_reduce

EARLY_S = 0.5           # the search starts this long before the start
LATE_S = 2.0            # and ends this long after the traced seconds
DEVICE_OVER_HOST_S = 5e-4   # a program cannot outlast the call that
#                             waited for it by more than clock jitter
GAP_FLOOR_NS = 1000.0   # as trace_reduce.idle_gaps


def device_programs(trace, named):
    """(modules, programs) of the first chip: every executed program as
    (start_s, end_s) in time order, and those that `named` (device
    program -> ring name) holds as (ring name, start_s, duration_s)."""
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return [], []
    mods = sorted(trace_reduce._line(planes[0], trace_reduce.MODULES_LINE),
                  key=lambda e: e[1])
    programs = [(named[trace_reduce.module_name(name)],
                 start / 1e9, dur / 1e9)
                for name, start, dur in mods
                if trace_reduce.module_name(name) in named]
    return [(s / 1e9, (s + d) / 1e9) for _, s, d in mods], programs


def ring_programs(ring, names):
    """The ring's `names` events as (start_s, duration_s, name), in
    time order."""
    return sorted((start, dur, name) for name in names
                  for start, dur in ringread.spans(ring, name))


def place(programs, calls, t_from, t_to):
    """Offsets `o` at which the traced `programs` fit the ring's
    `calls`: ``calls[o + i]`` has the kind of ``programs[i]`` and lasted
    at least as long, for every i, and ``calls[o]`` began inside
    [t_from, t_to]."""
    fits = []
    n = len(programs)
    for o in range(len(calls) - n + 1):
        if not t_from <= calls[o][0] <= t_to:
            continue
        if all(calls[o + i][2] == programs[i][0]
               and programs[i][2] <= calls[o + i][1] + DEVICE_OVER_HOST_S
               for i in range(n)):
            fits.append(o)
    return fits


def idle_intervals(modules, programs, calls, offset):
    """The device's idle gaps (between one program's end and the next
    one's start) as host intervals [(start_s, end_s)], each moved by the
    clock difference of the first placed program that starts at or
    after the gap's end (of the last one, for a gap after it)."""
    anchors = [(dev_start, calls[offset + i][0] - dev_start)
               for i, (_, dev_start, _) in enumerate(programs)]
    out, end, k = [], None, 0
    for start, stop in modules:
        if end is not None and (start - end) * 1e9 > GAP_FLOOR_NS:
            while k < len(anchors) - 1 and anchors[k][0] < start - 1e-9:
                k += 1
            out.append((end + anchors[k][1], start + anchors[k][1]))
        if end is None or stop > end:
            end = stop
    return out


def join(ring, trace, t_near, trace_seconds, family):
    """Seconds of device idleness per covering span, or (None, why)."""
    letter = dict(family.PROGRAMS.values())
    modules, programs = device_programs(
        trace, {prog: event for prog, (event, _) in family.PROGRAMS.items()})
    calls = ring_programs(ring, letter)
    if not programs or not calls:
        return None, None
    t_from, t_to = t_near - EARLY_S, t_near + trace_seconds + LATE_S
    fits = place(programs, calls, t_from, t_to)
    if len(fits) != 1:
        near = [c for c in calls
                if t_from <= c[0] <= t_to + trace_seconds]
        return None, {"placements": len(fits),
                      "device": "".join(letter[p[0]] for p in programs),
                      "ring": "".join(letter[c[2]] for c in near)}
    pieces = spanread.segments(ring, spanread.engine_thread(ring))
    out = {"idle_s": 0.0, "admit_s": 0.0, "tick_s": 0.0, "unnamed_s": 0.0,
           "programs": len(programs), "leaf_s": {}}
    for lo, hi in idle_intervals(modules, programs, calls, fits[0]):
        out["idle_s"] += hi - lo
        named, bare = 0.0, hi - lo
        for sec, names, leaf in spanread.overlap(pieces, lo, hi):
            label = names[-1] if leaf else names[-1] + "/self"
            out["leaf_s"][label] = out["leaf_s"].get(label, 0.0) + sec
            bare -= sec
            if "decode.admit" in names:
                out["admit_s"] += sec
                named += sec
            elif "decode.step" in names:
                out["tick_s"] += sec
                named += sec
        out["unnamed_s"] += (hi - lo) - named
        if bare > 0:
            out["leaf_s"]["no-span"] = out["leaf_s"].get("no-span", 0.0) \
                + bare
    return out, None


def joined(ctx):
    """The join for this run, made once and kept in `ctx`; prints the
    ``IDLE`` line (seconds of idle device per leaf span, or the two
    sequences that could not be placed) as it is made."""
    if "gapjoin" not in ctx:
        length = float((ctx.get("mix") or {}).get("trace_seconds", 3.0))
        t_near = ctx["t_open"] + max(
            (ctx["t_close"] - ctx["t_open"] - length) / 2.0, 0.0)
        got, why = join(ctx.get("ring"), ctx.get("trace"), t_near, length,
                        ctx["family"])
        ctx["gapjoin"] = got
        if got is not None or why is not None:
            line = why if got is None else dict(
                got, leaf_s={k: round(v, 6) for k, v in sorted(
                    got["leaf_s"].items(), key=lambda kv: -kv[1])})
            print("IDLE " + json.dumps(line), flush=True)
    return ctx["gapjoin"]


def share(ctx, key):
    """`key` ("admit_s" | "tick_s" | "unnamed_s") as a share (%) of the
    idle seconds the join placed; None without a placement."""
    got = joined(ctx)
    if not got or got["idle_s"] <= 0:
        return None
    return 100.0 * got[key] / got["idle_s"]
