"""Median time of one prefill executable call, dispatch to
``block_until_ready`` (the ring's ``exec:decode.prefill`` events, which
``_ProfiledExecutable`` writes; host clock around a blocked call)."""
from chipbench import ringread, stats


def read(ctx):
    durs = [1e3 * d for _, d in
            ringread.spans(ctx["ring"], "exec:decode.prefill")]
    return stats.median(durs)
