"""Roofline share of the KDA one-token state update: the least time the
chip could take for one call's operations and bytes (the family's
``kda_step_cost`` at the step's mean rows: each row's recurrent state
read once and written once, its q, k, decay, v and beta in, its outputs
out; the larger of bytes over peak bytes/s and FLOPs over peak FLOP/s)
over the kernel's device time. The kernel is found by its Pallas name
on the trace's ``XLA Ops`` line (the family's ``KDA_STEP_OP``); each
step calls it once a KDA layer.

Rows a step are the client's own count, as for
``step.decode_roofline``: every token event inside the window after a
request's first is one row of one step."""
from chipbench import stats, trace_reduce


def read(ctx):
    family = ctx["family"]
    name = getattr(family, "KDA_STEP_OP", None)
    if name is None or ctx.get("peak") is None:
        return None
    calls = trace_reduce.op_durations(ctx["trace"], name)
    rows = sum(1 for r in ctx["records"] for i, t in enumerate(r["times"])
               if i > 0 and stats.in_window(t, ctx["t_open"],
                                            ctx["t_close"]))
    steps = ctx["engine_stats"][1]["steps"] - ctx["engine_stats"][0]["steps"]
    if not calls or not rows or steps <= 0:
        return None
    flops, nbytes = family.kda_step_cost(ctx["sizes"], rows / steps)
    least = max(flops / ctx["peak"]["flops"],
                nbytes / ctx["peak"]["bytes_per_s"])
    return 100.0 * least / stats.mean(calls)
