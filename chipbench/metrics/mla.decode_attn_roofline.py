"""Roofline share of the latent decode-attention kernel: the least time
the chip could take for one call's operations and bytes (the family's
``latent_attention_cost`` at the step's mean live rows: each live
latent row read once for all heads; the larger of bytes over peak
bytes/s and FLOPs over peak FLOP/s) over the kernel's device time. The
kernel is found by its Pallas name on the trace's ``XLA Ops`` line (the
family's ``LATENT_ATTENTION_OP``); each step calls it once a layer.

Live rows and their context lengths are the client's own count, as for
``step.decode_roofline``."""
from chipbench import stats, trace_reduce


def read(ctx):
    family = ctx["family"]
    name = getattr(family, "LATENT_ATTENTION_OP", None)
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
    flops, nbytes = family.latent_attention_cost(
        ctx["sizes"], sum(contexts) / steps, len(contexts) / steps)
    least = max(flops / ctx["peak"]["flops"],
                nbytes / ctx["peak"]["bytes_per_s"])
    return 100.0 * least / stats.mean(calls)
