"""Routed assignments the held experts received, per routed token per
expert layer, over the window: the engine's device-side counters
(``stats()``: ``routed`` [expert layers][held], ``routed_tokens``) at
the close less at the open. Under even routing it reads
num_experts_per_tok x held / n_routed (0.5 at 8 x 12 / 192): how near
the held experts' load is to the deployment's."""


def read(ctx):
    before, after = ctx["engine_stats"]
    if "routed" not in after or "routed" not in before:
        return None
    tokens = after["routed_tokens"] - before["routed_tokens"]
    hits = sum(sum(row) for row in after["routed"]) \
        - sum(sum(row) for row in before["routed"])
    layers = len(after["routed"])
    if tokens <= 0 or not layers:
        return None
    return hits / (tokens * layers)
