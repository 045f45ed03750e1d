"""The logits' way to the host after a tick's step: the ring's
``decode.step.pull`` span. Median over the window's ticks."""
from chipbench import spanread, stats


def read(ctx):
    return stats.median(spanread.durations_ms(ctx["ring"],
                                              "decode.step.pull"))
