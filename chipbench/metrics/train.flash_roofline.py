"""Roofline share of the flash-attention kernels (forward and
backward) in the train step: the least time the chip could take for
their operations and bytes (the family's ``flash_attention_costs``:
``formulas.flash_attention_cost`` at its heads and head size, causal,
bfloat16 operands under AMP O2) over the kernels' device time from the
trace.

The kernels have no name of their own in the trace: they are the
Mosaic custom calls of the step (``custom_call_target=
"tpu_custom_call"``, which XLA names ``closed_call.<n>``), and the
train step has no other. Each layer runs one call of each kind the
family lists (forward, backward) per step, so the calls seen divide
evenly over the kinds."""
from chipbench import trace_reduce

FLASH_OPS = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    calls = trace_reduce.op_durations(ctx["trace"], FLASH_OPS)
    if not calls or ctx.get("peak") is None:
        return None
    mix = ctx["mix"]
    kinds = ctx["family"].flash_attention_costs(
        ctx["sizes"], int(mix["batch_size"]), int(mix["seq_len"]))
    if not kinds:
        return None
    least = 0.0
    for flops, nbytes in kinds:
        least += max(flops / ctx["peak"]["flops"],
                     nbytes / ctx["peak"]["bytes_per_s"])
    return 100.0 * least * (len(calls) / len(kinds)) / sum(calls)
