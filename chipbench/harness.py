"""What every driver shares: the cell's files, the device check, the
set-up clock, the mid-window profiler, the per-layer metric readers and
the result line."""
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

from . import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NO_CHIP_RC = 3


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


def load_config(bench, name):
    """(raw config file, sizes as the formulas and drivers use them)."""
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        raw = json.load(f)
    sizes = {
        "vocab_size": int(raw["assumed"]["padded_vocab_size"]),
        "max_seq_len": int(raw["n_positions"]),
        "hidden": int(raw["n_embd"]),
        "layers": int(raw["n_layer"]),
        "heads": int(raw["n_head"]),
        "eps": float(raw["layer_norm_epsilon"]),
    }
    return raw, sizes


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


def result_line(bench, cell, mix, sizes, window, trace, end_to_end,
                correct, attempted, failed, devs, traced=None, temp_bytes=0,
                **seen):
    """The last line of standard output: with `trace` off the cell's
    end-to-end metrics, with it on its per-layer metrics, each read by
    its own file from what the driver has `seen` (ring events, records,
    counters) and from the trace, plus the breakdown of the traced
    seconds."""
    device = device_json(devs, temp_bytes)
    group, values = "end_to_end", end_to_end
    if trace:
        group = "per_layer"
        ctx = dict(seen, cell=cell, mix=mix, sizes=sizes,
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
    return out
