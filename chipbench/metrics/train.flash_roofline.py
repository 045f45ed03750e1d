"""Roofline share of the flash-attention kernels (forward and
backward) in the train step: the least time the chip could take for
their operations and bytes (``formulas.flash_attention_cost``, causal,
bfloat16 operands under AMP O2) over the kernels' device time from the
trace.

The kernels have no name of their own in the trace: they are the
Mosaic custom calls of the step (``custom_call_target=
"tpu_custom_call"``, which XLA names ``closed_call.<n>``), and the
train step has no other. Each layer runs one forward and one backward
call per step, so half of the calls seen are of each kind."""
from chipbench import formulas, trace_reduce

FLASH_OPS = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    calls = trace_reduce.op_durations(ctx["trace"], FLASH_OPS)
    if not calls or ctx.get("peak") is None:
        return None
    s, mix = ctx["sizes"], ctx["mix"]
    B, T = int(mix["batch_size"]), int(mix["seq_len"])
    D = s["hidden"] // s["heads"]
    least = 0.0
    for backward in (False, True):
        flops, nbytes = formulas.flash_attention_cost(
            B, s["heads"], T, D, causal=True, backward=backward)
        least += max(flops / ctx["peak"]["flops"],
                     nbytes / ctx["peak"]["bytes_per_s"])
    return 100.0 * least * (len(calls) / 2.0) / sum(calls)
