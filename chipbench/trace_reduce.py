"""From a profiler trace to numbers: the only reading of the device's
own clock the benchmark has.

A trace is first *normalised* to plain data,

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

(`load_xplane` does that from the ``.xplane.pb`` the JAX profiler
writes), and every function below works on that form, so a small
recorded trace kept as JSON (``fixtures/``) tests them without a chip.

On a TPU each chip is one plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per executed HLO instruction (nested where
an instruction contains others: a ``while`` holds its body) and its
line ``XLA Modules`` one event per executed program, named after the
jitted function (``jit_paged_step(...)``).
"""
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(trace_dir):
    """Normalise the newest ``*.xplane.pb`` under `trace_dir`, keeping
    the device planes only. None when the profiler wrote nothing."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    """Planes of accelerator chips that ran something."""
    out = []
    for plane in (trace or {}).get("planes", []):
        if re.match(r"/device:(TPU|GPU):\d+$", plane["name"]) \
                and _line(plane, OPS_LINE):
            out.append(plane)
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name and line["events"]:
            return line["events"]
    return []


def union_seconds(events):
    """Seconds covered by at least one of the [name, start_ns, dur_ns]
    events (nested or overlapping events count once)."""
    total, end = 0.0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total / 1e9


def extent_seconds(events):
    if not events:
        return 0.0
    return (max(e[1] + e[2] for e in events)
            - min(e[1] for e in events)) / 1e9


def device_busy(trace):
    """(busy_s, window_s): seconds in which an operation ran, averaged
    over the chips used, and the length of the traced window (first
    operation's start to last operation's end, the widest over the
    chips). (0, 0) for a trace with no device operation."""
    planes = device_planes(trace)
    if not planes:
        return 0.0, 0.0
    busy = [union_seconds(_line(p, OPS_LINE)) for p in planes]
    window = max(extent_seconds(_line(p, OPS_LINE)) for p in planes)
    return sum(busy) / len(busy), window


def idle_share(trace):
    """Share (%) of the traced window in which no operation ran on the
    chip; None without a device trace."""
    busy_s, window_s = device_busy(trace)
    return 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None


def self_times(events):
    """[(name, self_seconds)] per event: its duration less the events
    nested directly inside it."""
    out, stack = [], []      # stack of [name, end_ns, self_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2] / 1e9))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2] / 1e9))
    return out


def short_name(name, limit=64):
    """An event name cut to a label: letters, digits, `_.-` only."""
    name = re.sub(r"^%", "", name.strip())
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name)[:limit].strip("_")


def module_name(name):
    """`jit_paged_step(1234567)` -> `paged_step`."""
    name = re.sub(r"\(.*$", "", name.strip())
    return re.sub(r"^jit_+", "", name) or name


def top_ops(trace, n=10):
    """The device operations that took most time (self time, summed per
    name over the first chip): [[name, seconds], ...]."""
    planes = device_planes(trace)
    if not planes:
        return []
    totals = {}
    for name, sec in self_times(_line(planes[0], OPS_LINE)):
        key = short_name(name)
        totals[key] = totals.get(key, 0.0) + sec
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def idle_gaps(trace, n=10, floor_ns=1000.0):
    """The longest idle gaps of the first chip, summed by what ran on
    either side: [["<program before>-<program after>", seconds], ...].
    The programs are the device's own (`XLA Modules`), so a gap's name
    says which two dispatches the host sat between."""
    planes = device_planes(trace)
    if not planes:
        return []
    mods = sorted(_line(planes[0], MODULES_LINE), key=lambda e: e[1])
    totals, end, prev = {}, None, "start"
    for name, start, dur in mods:
        if end is not None and start - end > floor_ns:
            key = f"{prev}-{module_name(name)}"
            totals[key] = totals.get(key, 0.0) + (start - end) / 1e9
        if end is None or start + dur > end:
            end = start + dur
        prev = module_name(name)
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(k), v] for k, v in rows]


def module_durations(trace, pattern):
    """Seconds of every executed program whose name matches `pattern`
    (first chip), in time order."""
    planes = device_planes(trace)
    if not planes:
        return []
    rx = re.compile(pattern)
    return [dur / 1e9 for name, _, dur in
            sorted(_line(planes[0], MODULES_LINE), key=lambda e: e[1])
            if rx.search(module_name(name))]


def op_durations(trace, pattern):
    """Seconds of every device operation whose name matches `pattern`
    (first chip)."""
    planes = device_planes(trace)
    if not planes:
        return []
    rx = re.compile(pattern)
    return [dur / 1e9 for name, _, dur in _line(planes[0], OPS_LINE)
            if rx.search(name)]


def breakdown(trace):
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}


KEEP_IN_NAME = re.compile(r'custom_call_target="[^"]*"')


def cut(trace, start_s=0.0, length_s=None, name_limit=None):
    """The first chip's events that start inside [start_s, start_s +
    length_s) of the trace. With `name_limit`, names are cut to that
    many characters (a custom call keeps its target): small enough to
    keep as a fixture."""
    planes = device_planes(trace)[:1]
    if not planes:
        return {"planes": []}
    t0 = min(e[1] for ln in planes[0]["lines"] for e in ln["events"]) \
        + start_s * 1e9
    t1 = float("inf") if length_s is None else t0 + length_s * 1e9

    def name_of(name):
        if name_limit is None or len(name) <= name_limit:
            return name
        kept = KEEP_IN_NAME.search(name[name_limit:])
        return name[:name_limit] + (" ... " + kept.group(0) if kept else "")

    return {"planes": [
        {"name": p["name"],
         "lines": [{"name": ln["name"],
                    "events": [[name_of(e[0]), e[1], e[2]]
                               for e in sorted(ln["events"],
                                               key=lambda e: e[1])
                               if t0 <= e[1] < t1]}
                   for ln in p["lines"]]} for p in planes]}
