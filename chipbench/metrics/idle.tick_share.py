"""Share of the device's idle seconds that fall inside a
``decode.step`` span, by the join of ``chipbench/gapjoin.py``."""
from chipbench import gapjoin


def read(ctx):
    return gapjoin.share(ctx, "tick_s")
