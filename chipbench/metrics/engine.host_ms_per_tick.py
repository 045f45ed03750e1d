"""Host time of one scheduler tick: the ring's ``decode.step`` span
less the ``exec:decode.pstep`` event inside it (table build, logits
pull, sampling, emit). Mean over the window's ticks."""
from chipbench import ringread, stats


def read(ctx):
    return stats.mean(ringread.self_ms(
        ctx["ring"], "decode.step", ["exec:decode.pstep"]))
