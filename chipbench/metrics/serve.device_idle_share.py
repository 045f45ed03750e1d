"""Share of the traced seconds in which no operation ran on the chip."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.idle_share(ctx["trace"])
