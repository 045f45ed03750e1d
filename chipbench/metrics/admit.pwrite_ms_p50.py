"""The page write of one admission: the ring's ``exec:decode.pwrite``
event (dispatch -> ready of ``write_kv_pages``, a pass over the whole
pool that every running stream waits out). Median over the window."""
from chipbench import spanread, stats


def read(ctx):
    return stats.median(spanread.durations_ms(ctx["ring"],
                                              "exec:decode.pwrite"))
