"""Share of the window the fit loop spent blocked on the device
(``fetch_s`` of ``profiler.step_timeline``, which the async step
pipeline records when it retires a step). Near 100% the chip sets the
pace and the host hides behind it; a falling share says the host has
become the limit."""


def read(ctx):
    steps = ctx.get("step_timeline") or []
    window = ctx["t_close"] - ctx["t_open"]
    if not steps or window <= 0:
        return None
    return 100.0 * sum(e.get("fetch_s", 0.0) for e in steps) / window
