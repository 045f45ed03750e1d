"""Dequant-inside-matmul for int8 PTQ weights (quant/ptq.py layout).

A quantized decode weight is an int8 ``[in, out]`` tensor plus a
per-output-channel fp32 scale ``[out]`` (``w ~= q * scale``). Because
the scale is constant along the contraction axis it factors out of the
dot product::

    x @ (q * scale) == (x @ q) * scale

so dequantization costs one [*, out] multiply after the product instead
of materializing an fp32 copy of the weight. XLA fuses the two ops; a
Pallas kernel of the same composition (row x column tiles, contraction
whole) was timed against it on a v5e at the chat step's shapes and did
not win — 11.5 against 9.0 us at [106, 768] x [768, 2304], 14.1 against
9.5 at x [768, 3072], 10.9 against 11.0 at [106, 3072] x [3072, 768]
(PERF.md section 6, PR 31) — so there is one path and no switch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def int8_weight_matmul(x, w_q, scale):
    """``(x @ q) * scale`` with an f32 accumulate."""
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), w_q.astype(jnp.float32),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale).astype(x.dtype)
