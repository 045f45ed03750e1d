"""The blind spot: share of the window that the engine's thread spent
inside no leaf span (``decode.idle`` is a leaf; the self time of a span
that holds others is not)."""
from chipbench import spanread


def read(ctx):
    return spanread.unspanned_share(ctx["ring"], ctx["t_open"],
                                    ctx["t_close"])
