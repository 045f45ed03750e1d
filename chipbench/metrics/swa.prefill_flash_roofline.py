"""Roofline share of the flash-attention forward inside the prefills:
the FLOPs the attention of the traced prefills needs (the family's
``prefill_flash_flops`` at each call's rung: a full layer the causal
triangle, a window layer the band of ``sliding_window`` positions) over
the chip's peak FLOP/s, in the kernel's device time. The kernel is
found by its Pallas name on the trace's ``XLA Ops`` line (the family's
``FLASH_FORWARD_OP``); its event names its result ``[heads, T, D]``,
from which the rung T is read; a prefill calls it once a layer, so the
calls at a rung are ``layers`` to a prefill. The decode step has no
such call."""
import re

from chipbench import trace_reduce

RESULT = re.compile(r"\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    family, s = ctx["family"], ctx["sizes"]
    name = getattr(family, "FLASH_FORWARD_OP", None)
    planes = trace_reduce.device_planes(ctx["trace"])
    if name is None or ctx.get("peak") is None or not planes:
        return None
    rx = re.compile(name)
    seconds, flops = 0.0, 0.0
    for op, _, dur in trace_reduce._line(planes[0], trace_reduce.OPS_LINE):
        shape = RESULT.search(op) if rx.search(op) else None
        if shape is None:
            continue
        seconds += dur / 1e9
        flops += family.prefill_flash_flops(s, int(shape.group(2))) \
            / s["layers"]
    if seconds <= 0:
        return None
    return 100.0 * flops / ctx["peak"]["flops"] / seconds
