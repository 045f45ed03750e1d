"""Bytes of the window layers' rings, which live by slot beside the
full layers' pages (the engine's ``stats()`` ``state_pool_bytes`` of a
kind that keeps a ring), in GB. An engine without the counter, or
whose kind keeps pages only, reads nothing.

The same counter as ``kda.state_pool_gb`` reads for a recurrent kind:
one reader under one neutral name is a ``benchmark`` PR's to make
(PERF.md section 7), since the accepted tests pin that metric's cells."""


def read(ctx):
    nbytes = ctx["engine_stats"][1].get("state_pool_bytes")
    return nbytes / 1e9 if nbytes else None
