"""Sampling, accounting and pushing one token per row: the ring's
``decode.sample`` span. Median over the window's ticks."""
from chipbench import spanread, stats


def read(ctx):
    return stats.median(spanread.durations_ms(ctx["ring"], "decode.sample"))
