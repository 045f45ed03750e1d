"""The K/V panel's trip through the host in one admission (ROADMAP
S3): the ``decode.admit.kv_pull``, ``decode.admit.repack`` and
``decode.admit.upload`` spans inside one ``decode.admit``, summed.
Median over the window's admissions that prefilled."""
from chipbench import spanread, stats

PHASES = ["decode.admit.kv_pull", "decode.admit.repack",
          "decode.admit.upload"]


def read(ctx):
    return stats.median(spanread.inside_ms(ctx["ring"], "decode.admit",
                                           PHASES))
