"""Roofline share of the grouped-query decode-attention kernel: the
least time the chip could take for one step's calls (the family's
``gqa_attention_cost``: each live K and V row read once for all heads,
a full layer the whole context, a window layer ``min(context,
window)`` rows; the larger of bytes over peak bytes/s and FLOPs over
peak FLOP/s) over the kernel's device time a step. The kernel is found
by its Pallas name on the trace's ``XLA Ops`` line (the family's
``GQA_ATTENTION_OP``); each step calls it once a layer.

Live rows and their context lengths are the client's own count, as for
``step.decode_roofline``, here exact and not a mean: a request inside
the window counts its own rows."""
from chipbench import stats, trace_reduce


def read(ctx):
    family, s = ctx["family"], ctx["sizes"]
    name = getattr(family, "GQA_ATTENTION_OP", None)
    if name is None or ctx.get("peak") is None:
        return None
    calls = trace_reduce.op_durations(ctx["trace"], name)
    contexts = [r["plen"] + i for r in ctx["records"]
                for i, t in enumerate(r["times"])
                if i > 0 and stats.in_window(t, ctx["t_open"],
                                             ctx["t_close"])]
    steps = ctx["engine_stats"][1]["steps"] - ctx["engine_stats"][0]["steps"]
    if not calls or not contexts or steps <= 0:
        return None
    flops, nbytes = family.gqa_attention_cost(
        s, sum(contexts) / steps,
        sum(min(c, s["window"]) for c in contexts) / steps,
        len(contexts) / steps)
    least = max(flops / ctx["peak"]["flops"],
                nbytes / ctx["peak"]["bytes_per_s"])
    return 100.0 * least / (stats.mean(calls) * s["layers"])
