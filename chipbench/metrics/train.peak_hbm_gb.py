"""Peak HBM in use on the fullest chip (``memory_stats``), in GB."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes", 0)
    return peak / 1e9 if peak else None
