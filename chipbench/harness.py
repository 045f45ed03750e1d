"""What every driver shares: the cell's files, the device check, the
set-up clock, the mid-window profiler, the per-layer metric readers and
the result line."""
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time

from . import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP_RC = 3
_FAMILY_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                     f"(known: {[c['name'] for c in bench['workloads']]})")


def cell_metrics(bench, cell_name, group):
    """Names of the `group` ("end_to_end" | "per_layer") metrics this
    cell reports: those with no `workloads` key, or that list it."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


# Where `load_family` looks for `<family>.py`, first match wins. A test
# that brings a stand-in family appends its directory.
FAMILY_PATH = [os.path.join(HERE, "families")]


def load_family(name):
    """The module that owns everything architecture-specific of the
    family `name` (``chipbench/families/gpt.py`` shows the members)."""
    if not isinstance(name, str) or not _FAMILY_NAME.match(name):
        raise ValueError(f"chipbench: not a family name: {name!r}")
    for base in FAMILY_PATH:
        path = os.path.join(base, name + ".py")
        if os.path.exists(path):
            return _load_file(path, "chipbench_family_" + name)
    raise ValueError(f"chipbench: no family {name!r} under {FAMILY_PATH}")


# The kinds of cut that the guide model-configs, section 4, allows (the
# chip's share of a stated deployment, and depth), each with the floor
# a cut configuration keeps to: held here, published -> whether it
# holds. A family's `CUTS` says of which kind a key of its file is; a
# key it does not list is a width or a shape, and is never cut.
CUT_FLOORS = {
    "depth": ("at least four layers after the leading dense ones",
              lambda held, published, dense: held >= dense + 4),
    "experts": ("at least 8 routed experts",
                lambda held, published, dense: held >= 8),
    "vocabulary": ("at least an eighth of the vocabulary",
                   lambda held, published, dense: 8 * held >= published),
    "heads": ("at least one head", lambda held, published, dense: held >= 1),
}


def cut_problems(reduced, raw, cuts):
    """What is wrong with a configuration file `raw` as a statement of
    how it was cut to size (guide model-configs, section 4), as a list
    of sentences; empty when nothing is. `reduced`: the list of its
    `BENCHMARK.json` entry; `cuts`: the family's `CUTS`, {key of the
    file: kind of cut, a key of `CUT_FLOORS`}."""
    bad = [f"the file lacks {key!r}"
           for key in ("source", "family", "assumed", "deployment")
           if key not in raw]
    published = raw.get("published", {})
    deployment = raw.get("deployment")
    deployment = deployment if isinstance(deployment, dict) else {}
    for key in reduced:
        if cuts.get(key) not in CUT_FLOORS:
            bad.append(f"{key!r} is not a key its family lets be cut "
                       f"({sorted(cuts)}): no width is ever cut")
        elif key not in raw:
            bad.append(f"reduced key {key!r} is not a key of the file")
        elif key not in published:
            bad.append(f"\"published\" lacks the source's {key!r}")
        elif not raw[key] < published[key]:
            bad.append(f"{key!r} is listed as reduced and is not below "
                       f"its published value")
        else:
            floor, holds = CUT_FLOORS[cuts[key]]
            if not holds(raw[key], published[key], int(
                    deployment.get("leading_dense_layers", 0))):
                bad.append(f"{key!r} = {raw[key]} of {published[key]}: a "
                           f"cut keeps {floor}")
    for key in published:
        if key not in reduced:
            bad.append(f"{key!r} has a published value and is not in "
                       f"\"reduced\"")
    if reduced:
        chips = deployment.get("chips_per_layer")
        if not isinstance(chips, int) or chips < 1:
            bad.append("a cut file's \"deployment\" is an object whose "
                       "\"chips_per_layer\" says over how many chips a "
                       "layer is divided")
    return bad


def load_config(bench, name):
    """(raw configuration file, its sizes as its family reads them, the
    family's module). A file whose cut is not declared as
    `cut_problems` wants it is refused."""
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        raw = json.load(f)
    if "family" not in raw:
        raise ValueError(f"{entry['file']}: a configuration names its "
                         f"\"family\"")
    family = load_family(raw["family"])
    bad = cut_problems(entry.get("reduced", []), raw, family.CUTS)
    if bad:
        raise ValueError(f"{entry['file']}: " + "; ".join(bad))
    return raw, family.sizes(raw), family


def load_driver(name):
    return _load_file(os.path.join(HERE, "drivers", name + ".py"),
                      f"chipbench_driver_{name}")


def _load_file(path, modname):
    spec = importlib.util.spec_from_file_location(
        modname.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_devices(chips, require_tpu=True):
    """The devices the cell runs on. Without an accelerator, or with
    fewer chips than the cell asks for, the process ends with a code
    other than 0 and prints no result: there is no CPU fallback on the
    measured path."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"chipbench: the cell needs {chips} TPU chip(s); "
              f"jax.devices() reports {len(devs)} x {devs[0].platform!r}",
              file=sys.stderr, flush=True)
        raise SystemExit(NO_CHIP_RC)
    return devs[:chips]


def program_temp_bytes(executables):
    """The largest `temp_size_in_bytes` the compiler reports for any of
    the compiled programs given (0 for those that report none)."""
    most = 0
    for exe in executables:
        try:
            most = max(most, int(exe.memory_analysis().temp_size_in_bytes))
        except (AttributeError, TypeError):
            continue
    return most


def device_json(devs, temp_bytes=0):
    """The device as JAX reports it. `memory_peak_bytes` is the peak on
    the fullest chip: the allocator's `peak_bytes_in_use` plus
    `temp_bytes`, the largest temporary of a program that ran. On this
    runtime the allocator's statistics count buffers and not what a
    running program takes for its temporaries (the train step of
    GPT-2 124M at B=8 needs 11.91 GB by the compiler's account, PR 21,
    and the allocator's peak after it reads 2.15 GB, PR 23), so without
    the second term the reading is the resident state alone. Without
    allocator statistics (the CPU) it is 0."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if peak:
        peak += int(temp_bytes)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class SetupClock:
    """Names the parts of set-up as they end; `total()` is process
    start -> now."""

    def __init__(self, t_process_start):
        self.t0 = t_process_start
        self._last = t_process_start
        self.parts = []

    def mark(self, name):
        now = time.perf_counter()
        self.parts.append([name, round(now - self._last, 3)])
        self._last = now

    def total(self):
        return time.perf_counter() - self.t0


class MidWindowTrace:
    """Puts the JAX profiler on for `length` seconds in the middle of
    the window, from a thread of its own, so that the window runs on
    while the trace is written out. `result()` waits for it and returns
    the normalised trace (None if the profiler wrote nothing)."""

    def __init__(self, t_open, seconds, length):
        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        length = min(float(length), max(seconds - 1.0, 0.5))
        self._start = t_open + max((seconds - length) / 2.0, 0.0)
        self._length = length
        self._error = None
        self._thread = threading.Thread(target=self._run,
                                        name="chipbench-trace")
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(max(self._start - time.perf_counter(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # device lines are what is read
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            try:
                time.sleep(self._length)
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:        # reported, the window goes on
            self._error = exc

    def result(self):
        self._thread.join()
        try:
            if self._error is not None:
                raise self._error
            return trace_reduce.load_xplane(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


class GcWatch:
    """The interpreter's garbage collections while it is installed:
    `pauses` holds (generation, seconds) of each. A full collection
    walks every container object of the process and stops every thread
    meanwhile, the engine's too, so a run that reads low shows here."""

    def __init__(self):
        self.pauses = []
        self._t0 = None
        gc.callbacks.append(self._note)

    def _note(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def stop(self):
        gc.callbacks.remove(self._note)
        full = [s for gen, s in self.pauses if gen == 2]
        return {"collections": len(self.pauses), "full": len(full),
                "full_ms": round(1e3 * sum(full), 1),
                "longest_ms": round(1e3 * max(
                    (s for _, s in self.pauses), default=0.0), 1)}


def ring_events(t_open, t_close):
    """The program's always-on event ring between the marks, or None
    when the ring dropped events (a metric read from a ring with holes
    would be wrong in silence)."""
    from paddle_tpu.observability import tracez

    events, total = tracez.RING.snapshot()
    if tracez.RING.capacity == 0 or total > tracez.RING.capacity:
        return None
    return [e for e in events if t_open < e[2] + e[3] <= t_close]


def read_metrics(names, ctx):
    """Evaluate `chipbench/metrics/<name>.py` for each name. A reader
    that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for name in names:
        path = os.path.join(HERE, "metrics", name + ".py")
        if not os.path.exists(path):
            continue
        value = _load_file(path, "chipbench_metric_" + name).read(ctx)
        if value is not None:
            out[name] = float(value)
    return out


def result_line(bench, cell, mix, sizes, family, window, trace, end_to_end,
                correct, attempted, failed, devs, traced=None, temp_bytes=0,
                checks=None, **seen):
    """The last line of standard output: with `trace` off the cell's
    end-to-end metrics, with it on its per-layer metrics, each read by
    its own file from what the driver has `seen` (ring events, records,
    counters) and from the trace, plus the breakdown of the traced
    seconds. `checks`, {name: (number, limit)}, is every number that
    decided `correct` beside its limit; it comes last in the line."""
    device = device_json(devs, temp_bytes)
    group, values = "end_to_end", end_to_end
    if trace:
        group = "per_layer"
        ctx = dict(seen, cell=cell, mix=mix, sizes=sizes, family=family,
                   t_open=window[0], t_close=window[1], trace=traced,
                   end_to_end=end_to_end, device=device, chips=len(devs),
                   peak=peaks.peak(devs[0].device_kind)
                   if devs[0].platform == "tpu" else None)
        values = read_metrics(cell_metrics(bench, cell["name"], group), ctx)
    units = {m["name"]: m["unit"] for m in bench[group]}
    metrics = {n: {"value": values[n], "unit": units[n]}
               for n in cell_metrics(bench, cell["name"], group)
               if values.get(n) is not None}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"], device["window_s"] = \
            trace_reduce.device_busy(traced)
        out["breakdown"] = trace_reduce.breakdown(traced)
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, (value, limit) in (checks or {}).items()}
    return out


def add_check(out, name, value, limit):
    """One more number that decides `correct`, read after the result
    line was built: `value` (None: nothing could be compared) has to be
    at most `limit`."""
    out["checks"][name] = {"value": value, "limit": limit}
    out["correct"] = bool(out["correct"] and value is not None
                          and value <= limit)


def print_checks(out):
    """Each number compared beside its limit, one to a line: the last
    lines of a run's standard error. Of a run that is not correct the
    driver's record keeps the end of standard error and the end of the
    result line and nothing else, so both carry them."""
    for name, c in out["checks"].items():
        print(f"CHECK {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
