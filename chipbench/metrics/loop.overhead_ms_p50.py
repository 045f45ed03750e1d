"""What one scheduler iteration costs beside its work: a
``decode.loop`` span less the ``decode.admit`` and ``decode.step`` spans
inside it (lock, scheduling, gauges, any wait). Median over the
window's iterations."""
from chipbench import ringread, stats


def read(ctx):
    return stats.median(ringread.self_ms(
        ctx["ring"], "decode.loop", ["decode.admit", "decode.step"]))
