"""Share of the device's idle seconds that fall inside neither a
``decode.admit`` nor a ``decode.step`` span (loop, scheduler, launch),
by the join of ``chipbench/gapjoin.py``."""
from chipbench import gapjoin


def read(ctx):
    return gapjoin.share(ctx, "unnamed_s")
