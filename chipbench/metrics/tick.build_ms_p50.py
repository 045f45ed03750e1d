"""Host time before a tick's dispatch: ``decode.step.provision``
(pages, copy-on-write) plus ``decode.step.build`` (tables, executable
lookup, the three uploads) inside one ``decode.step``. Median over the
window's ticks."""
from chipbench import spanread, stats

PHASES = ["decode.step.provision", "decode.step.build"]


def read(ctx):
    return stats.median(spanread.inside_ms(ctx["ring"], "decode.step",
                                           PHASES))
