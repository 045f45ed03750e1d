"""Share of the device's idle seconds (gaps between programs in the
traced seconds) that fall inside a ``decode.admit`` span, by the join
of ``chipbench/gapjoin.py``."""
from chipbench import gapjoin


def read(ctx):
    return gapjoin.share(ctx, "admit_s")
