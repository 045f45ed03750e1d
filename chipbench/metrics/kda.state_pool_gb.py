"""Bytes of state that lives by slot beside the pages (a recurrent
kind's state pool: the engine's ``stats()`` ``state_pool_bytes``), in
GB. An engine without the counter, or whose kind keeps pages only,
reads nothing."""


def read(ctx):
    nbytes = ctx["engine_stats"][1].get("state_pool_bytes")
    return nbytes / 1e9 if nbytes else None
