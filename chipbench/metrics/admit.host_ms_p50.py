"""Host time of one admission: the ring's ``decode.admit`` span less
the ``exec:decode.prefill`` event inside it (the one dispatch: prefill
and page write) -- the prefix lookup, the page allocation, the input
build, the logits pull and the first token's sampling. Median over the
window."""
from chipbench import ringread, stats


def read(ctx):
    return stats.median(ringread.self_ms(
        ctx["ring"], "decode.admit", ["exec:decode.prefill"]))
