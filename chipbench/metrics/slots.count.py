"""Slots the engine sized itself to (``decode.default_slot_count``)."""


def read(ctx):
    return ctx.get("slots")
