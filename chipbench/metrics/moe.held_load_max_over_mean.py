"""The fullest held expert's assignments over the held experts' mean,
over the window and over every expert layer's held experts together
(the engine's device-side counters, ``stats()`` ``routed``, close less
open): 1.0 when routing is even; what the grouped product's longest
group is to its mean."""


def read(ctx):
    before, after = ctx["engine_stats"]
    if "routed" not in after or "routed" not in before:
        return None
    cells = [a - b for row_a, row_b in zip(after["routed"], before["routed"])
             for a, b in zip(row_a, row_b)]
    if not cells or sum(cells) <= 0:
        return None
    return max(cells) / (sum(cells) / len(cells))
