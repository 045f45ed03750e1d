"""Dequant-inside-matmul for int8 PTQ weights (quant/ptq.py layout).

A quantized decode weight is an int8 ``[in, out]`` tensor plus a
per-output-channel fp32 scale ``[out]`` (``w ~= q * scale``). Because
the scale is constant along the contraction axis it factors out of the
dot product::

    x @ (q * scale) == (x @ q) * scale

so dequantization costs one [*, out] multiply after the GEMV instead of
materializing an fp32 copy of the weight. The Pallas kernel tiles rows
and output columns and keeps the contraction whole, so a cell needs no
accumulator: whole operands in VMEM stopped compiling at GPT-3 1.3B
widths (2048x8192: "Scoped allocation 16.34M, limit 16.00M"). The XLA
path is the same two-op composition; dispatch follows the existing
`PADDLE_TPU_DECODE_KERNEL=pallas|xla` knob.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ...core import flags as _flags
from . import _common
from ._common import I0 as _I0, VMEM

_ENV = "PADDLE_TPU_DECODE_KERNEL"


def int8_weight_matmul_reference(x, w_q, scale):
    """XLA fallback: ``(x @ q) * scale`` with an f32 accumulate."""
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), w_q.astype(jnp.float32),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale).astype(x.dtype)


def _mm_kernel(x_ref, w_ref, s_ref, o_ref):
    acc = jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def _tile(n, cap, unit):
    """Largest multiple-of-`unit` power-of-two tile <= cap dividing n;
    the whole dim when none does (a full-dim block is always legal)."""
    t = cap
    while t >= unit:
        if n % t == 0:
            return t
        t //= 2
    return n


def _int8_weight_matmul_pallas(x, w_q, scale):
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[-1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    # per cell: x tile bm*K*4 B and w tile K*bn B (double-buffered) plus
    # the in-kernel f32 copy of the w tile, K*bn*4 B — 64 x 128 tiles
    # keep that near 10 MB at K = 8192 (16 MiB scoped VMEM)
    bm, bn = _tile(M, 64, 8), _tile(N, 128, 128)
    out = pl.pallas_call(
        _mm_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, _I0), memory_space=VMEM),
            pl.BlockSpec((K, bn), lambda i, j: (_I0, j), memory_space=VMEM),
            pl.BlockSpec((1, bn), lambda i, j: (_I0, j), memory_space=VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("parallel", "parallel"),
    )(x2, w_q, scale.reshape(1, N))
    return out.reshape(*lead, N)


def int8_weight_matmul(x, w_q, scale, kernel=None):
    """Dispatch on `kernel` (or $PADDLE_TPU_DECODE_KERNEL, default xla)."""
    choice = (kernel or _flags.env_value(_ENV)).strip().lower()
    if choice == "pallas":
        return _int8_weight_matmul_pallas(x, w_q, scale)
    if choice in ("", "xla"):
        return int8_weight_matmul_reference(x, w_q, scale)
    raise ValueError(
        f"{_ENV}={choice!r}: expected 'pallas' or 'xla'")
