"""Roofline share of the decode step: the bytes one step must move
(for a dense model the weights once, plus the live cached rows of its
sequences: the family's ``decode_step_bytes``) over the chip's peak
bytes/s, in the step's mean device time from the trace (the family's
``STEP_PROGRAM``). The step is bound by bytes: at a handful of rows
its FLOPs are nothing.

Live rows and their context lengths are the client's own count: every
token event inside the window is one row of one step, whose cache then
held the prompt plus the tokens before it."""
from chipbench import formulas, stats, trace_reduce


def read(ctx):
    family = ctx["family"]
    durs = trace_reduce.module_durations(ctx["trace"], family.STEP_PROGRAM)
    if not durs or ctx.get("peak") is None:
        return None
    contexts = [r["plen"] + i for r in ctx["records"]
                for i, t in enumerate(r["times"])
                if i > 0 and stats.in_window(t, ctx["t_open"],
                                             ctx["t_close"])]
    steps = ctx["engine_stats"][1]["steps"] - ctx["engine_stats"][0]["steps"]
    if not contexts or steps <= 0:
        return None
    live_tokens = sum(contexts) / steps       # per step, over its rows
    need = family.decode_step_bytes(ctx["sizes"], live_tokens,
                                    rows=len(contexts) / steps)
    share, _ = formulas.roofline_share(0, need, stats.mean(durs),
                                       ctx["peak"])
    return share
