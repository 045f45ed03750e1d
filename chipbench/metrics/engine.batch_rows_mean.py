"""Live rows per decode step, from the ``batch`` argument of the ring's
``decode.step`` spans over the window."""
from chipbench import ringread, stats


def read(ctx):
    rows = [a["batch"] for a in ringread.span_args(ctx["ring"], "decode.step")
            if "batch" in a]
    return stats.mean(rows)
