"""How long a request waited before its admission began: the
``queued_ms`` the engine writes on every ``decode.admit`` span
(``t_admit - t_submit``, both on its own monotonic clock). Median over
the requests admitted in the window."""
from chipbench import spanread, stats


def read(ctx):
    return stats.median(spanread.arg_values(ctx["ring"], "decode.admit",
                                            "queued_ms"))
