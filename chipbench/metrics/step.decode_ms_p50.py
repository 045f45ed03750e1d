"""Median time of one decode-step executable call, dispatch to
``block_until_ready`` (the ring's ``exec:decode.pstep`` events)."""
from chipbench import ringread, stats


def read(ctx):
    durs = [1e3 * d for _, d in
            ringread.spans(ctx["ring"], "exec:decode.pstep")]
    return stats.median(durs)
