"""Host time of one admission: the ring's ``decode.admit`` span less
the ``exec:decode.prefill`` and ``exec:decode.pwrite`` events inside it
-- the K/V panel's trip to numpy and back (ROADMAP S3), the page
allocation and the first token's sampling. Median over the window."""
from chipbench import ringread, stats


def read(ctx):
    return stats.median(ringread.self_ms(
        ctx["ring"], "decode.admit",
        ["exec:decode.prefill", "exec:decode.pwrite"]))
