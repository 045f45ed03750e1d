"""Single-token (q_len == 1) decode attention for the KV-cache path.

During autoregressive decode every step attends one fresh query row per
sequence against that sequence's cached K/V — a GEMV per head, not the
GEMM the flash kernel is tiled for. This module provides:

  * `decode_attention_reference` — the jnp/XLA composition (masked
    softmax over the cache capacity). Always available, used by the
    correctness gate and as the default serving path.
  * `_decode_attention_pallas` — a Pallas kernel, one grid cell per
    (batch, head) pair: the query row and its cache panel live in VMEM,
    the score GEMV, masked softmax and output GEMV never round-trip
    through HBM between ops. Runs in interpret mode off-TPU so the CPU
    test suite exercises the same kernel body.
  * `decode_attention` — the dispatch point, selected by
    `PADDLE_TPU_DECODE_KERNEL=pallas|xla` (default `xla`; the Pallas
    path is opt-in until it has TPU soak time).

The paged trio (`paged_decode_attention[_reference]` and its Pallas
kernel) attends the same math over a PAGED cache: a shared page pool
plus per-sequence int32 block tables (inference/decode.py's paged
engine). The Pallas variant walks the block table via scalar-prefetch
index maps — one grid cell per (batch, page), every head of the page,
online softmax in scratch — so only mapped pages are ever streamed into
VMEM; the XLA path gathers pages with `jnp.take`.

Shapes (cap = KV-cache capacity rung, see inference/decode.py):

    q        [B, H, D]        fresh query row per sequence
    k, v     [B, cap, H, D]   cache panels (rows >= length are garbage)
    lengths  [B] int32        valid prefix per sequence (masks the rest)
    out      [B, H, D]
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from ...core import flags as _flags
from . import _common
from ._common import NEG_INF, VMEM, I0 as _I0, pltpu

_ENV = "PADDLE_TPU_DECODE_KERNEL"


def decode_attention_reference(q, k, v, lengths):
    """jnp reference: masked softmax(q.k/sqrt(D)).v over cache rows."""
    B, cap, H, D = k.shape
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhd,bkhd->bhk", q, k) * scale
    s = s.astype(jnp.float32)
    live = jnp.arange(cap, dtype=jnp.int32)[None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhk,bkhd->bhd", p, v)
    return o.astype(q.dtype)


def _kernel(q_ref, k_ref, v_ref, m_ref, o_ref, *, scale):
    q = q_ref[0]                                   # [1, D]
    kp = k_ref[0]                                  # [cap, D]
    vp = v_ref[0]
    s = jax.lax.dot_general(
        q, kp, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [1, cap]
    s = s + m_ref[0]                               # additive 0 / -inf mask
    p = jax.nn.softmax(s, axis=-1)
    o = jax.lax.dot(p.astype(vp.dtype), vp,
                    preferred_element_type=jnp.float32)   # [1, D]
    o_ref[0] = o.astype(o_ref.dtype)


def _decode_attention_pallas(q, k, v, lengths):
    B, cap, H, D = k.shape
    BH = B * H
    scale = 1.0 / math.sqrt(D)
    q3 = q.reshape(BH, 1, D)
    k3 = jnp.transpose(k, (0, 2, 1, 3)).reshape(BH, cap, D)
    v3 = jnp.transpose(v, (0, 2, 1, 3)).reshape(BH, cap, D)
    # additive mask rides VMEM instead of per-cell SMEM scalars: one
    # [1, cap] row per grid cell, 0 on live rows, -inf on dead ones
    live = jnp.arange(cap, dtype=jnp.int32)[None, :] \
        < lengths.astype(jnp.int32)[:, None]                  # [B, cap]
    mask = jnp.where(live, 0.0, NEG_INF).astype(jnp.float32)
    mask3 = jnp.repeat(mask[:, None, :], H, axis=0).reshape(BH, 1, cap)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda i: (i, _I0, _I0),
                         memory_space=VMEM),
            pl.BlockSpec((1, cap, D), lambda i: (i, _I0, _I0),
                         memory_space=VMEM),
            pl.BlockSpec((1, cap, D), lambda i: (i, _I0, _I0),
                         memory_space=VMEM),
            pl.BlockSpec((1, 1, cap), lambda i: (i, _I0, _I0),
                         memory_space=VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda i: (i, _I0, _I0),
                               memory_space=VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("arbitrary"),
    )(q3, k3, v3, mask3)
    return out.reshape(B, H, D)


def decode_attention(q, k, v, lengths, kernel=None):
    """Dispatch on `kernel` (or $PADDLE_TPU_DECODE_KERNEL, default xla)."""
    choice = (kernel or _flags.env_value(_ENV)).strip().lower()
    if choice == "pallas":
        return _decode_attention_pallas(q, k, v, lengths)
    if choice in ("", "xla"):
        return decode_attention_reference(q, k, v, lengths)
    raise ValueError(
        f"{_ENV}={choice!r}: expected 'pallas' or 'xla'")


# ---------------------------------------------------------------------------
# Paged variant: the cache is a shared page pool + per-sequence block table
# ---------------------------------------------------------------------------
#
#     q        [B, H, D]          fresh query row per sequence
#     k_pool   [P, pt, H, D]      one layer's page pool (pt = page tokens)
#     v_pool   [P, pt, H, D]
#     tables   [B, W] int32       block table: tables[b, w] = page holding
#                                 rows [w*pt, (w+1)*pt) of sequence b;
#                                 unused entries point at the null page
#     lengths  [B] int32          valid prefix per sequence
#     out      [B, H, D]

def paged_decode_attention_reference(q, k_pool, v_pool, tables, lengths):
    """XLA fallback: gather the table's pages (`jnp.take`), flatten to a
    contiguous [B, W*pt, H, D] view, reuse the masked-softmax math."""
    B, W = tables.shape
    P, pt, H, D = k_pool.shape
    with jax.named_scope("page_gather"):
        k = jnp.take(k_pool, tables, axis=0).reshape(B, W * pt, H, D)
        v = jnp.take(v_pool, tables, axis=0).reshape(B, W * pt, H, D)
    return decode_attention_reference(q, k, v, lengths)


def _online_softmax_page(s, vp, w, pt, length, m_s, l_s, acc_s, o_ref):
    """One page of the online (flash-style) softmax, all heads at once.
    s [pt, H, 1] scores, vp [pt, H, D] values; the running max m_s and
    denominator l_s are [H, 1], the accumulator acc_s [H, D]. Heads stay
    on sublanes and D on lanes throughout, so no step relayouts."""
    @pl.when(w == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    rows = w * pt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(rows < length, s, NEG_INF)
    m_prev = m_s[...]                                      # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[None])                           # [pt, H, 1]
    m_s[...] = m_new
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=0)
    acc_s[...] = acc_s[...] * corr + jnp.sum(p * vp, axis=0)

    @pl.when(w == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_s, l_s, acc_s, *, scale, pt):
    """One grid cell per (batch, page-slot): walk the block table along
    the last grid dim with the softmax state carried in VMEM scratch, so
    only the pages a sequence actually maps stream through VMEM — no
    gather materialization. A cell holds one whole page, every head of
    it: Mosaic wants a block's two minor dims to be the array's own
    (heads, head_dim) or multiples of (8, 128), and one head of one
    page — (1, head_dim) — is neither."""
    b = pl.program_id(0)
    w = pl.program_id(1)
    kp = k_ref[0].astype(jnp.float32)                      # [pt, H, D]
    vp = v_ref[0].astype(jnp.float32)
    qv = q_ref[0].astype(jnp.float32)                      # [H, D]
    s = jnp.sum(qv[None] * kp, axis=-1, keepdims=True) * scale
    _online_softmax_page(s, vp, w, pt, len_ref[b], m_s, l_s, acc_s, o_ref)


def _paged_grid_spec(B, H, D, W, pt, page_specs):
    """Grid (batch, page-slot) with (tables, lengths) scalar-prefetched:
    their VALUES drive the K/V index_map, so each grid cell DMAs exactly
    the page the block table names — the table walk happens in the
    pipeline, not the body."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[pl.BlockSpec((1, H, D),
                               lambda b, w, tbl, ln: (b, _I0, _I0))]
        + page_specs,
        out_specs=pl.BlockSpec((1, H, D),
                               lambda b, w, tbl, ln: (b, _I0, _I0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),     # running max
            pltpu.VMEM((H, 1), jnp.float32),     # running denominator
            pltpu.VMEM((H, D), jnp.float32),     # output accumulator
        ],
    )


def _paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths):
    B, H, D = q.shape
    P, pt, _, _ = k_pool.shape
    W = tables.shape[1]
    page = pl.BlockSpec((1, pt, H, D),
                        lambda b, w, tbl, ln: (tbl[b, w], _I0, _I0, _I0))
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / math.sqrt(D), pt=pt),
        grid_spec=_paged_grid_spec(B, H, D, W, pt, [page, page]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("arbitrary", "arbitrary"),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths, kernel=None):
    """Dispatch on `kernel` (or $PADDLE_TPU_DECODE_KERNEL, default xla)."""
    choice = (kernel or _flags.env_value(_ENV)).strip().lower()
    if choice == "pallas":
        return _paged_decode_attention_pallas(q, k_pool, v_pool,
                                              tables, lengths)
    if choice in ("", "xla"):
        return paged_decode_attention_reference(q, k_pool, v_pool,
                                                tables, lengths)
    raise ValueError(
        f"{_ENV}={choice!r}: expected 'pallas' or 'xla'")


# ---------------------------------------------------------------------------
# Int8 paged variant: fused dequant-inside-GEMV over quantized page pools
# ---------------------------------------------------------------------------
#
# The int8 pool (quant/kv.py) splits each fp32 K/V pool into an int8
# payload plus a per-(token row, head) fp32 scale:
#
#     k_pool, v_pool    [P, pt, H, D] int8
#     k_scale, v_scale  [P, pt, H]    f32   (row = q * scale)
#
# The Pallas kernel prefetches the scale page alongside its int8 page
# and dequantizes in-register right before the online-softmax
# accumulate — the fp32 panel never exists in HBM.

def paged_decode_attention_quant_reference(q, k_pool, k_scale,
                                           v_pool, v_scale,
                                           tables, lengths):
    """XLA fallback: gather int8 pages + scales, dequantize the gathered
    panel, reuse the fp32 masked-softmax math."""
    B, W = tables.shape
    P, pt, H, D = k_pool.shape
    k = (jnp.take(k_pool, tables, axis=0).astype(jnp.float32)
         * jnp.take(k_scale, tables, axis=0)[..., None])
    v = (jnp.take(v_pool, tables, axis=0).astype(jnp.float32)
         * jnp.take(v_scale, tables, axis=0)[..., None])
    k = k.reshape(B, W * pt, H, D)
    v = v.reshape(B, W * pt, H, D)
    return decode_attention_reference(q, k, v, lengths)


def _paged_quant_kernel(tbl_ref, len_ref, q_ref, k_ref, ks_ref,
                        v_ref, vs_ref, o_ref, m_s, l_s, acc_s,
                        *, scale, pt):
    """`_paged_kernel` with int8 pages: each page's scale block
    [pt, H, 1] rides its own prefetched block and the page dequantizes
    in-register before the score / accumulate."""
    b = pl.program_id(0)
    w = pl.program_id(1)
    kp = k_ref[0].astype(jnp.float32) * ks_ref[0]          # [pt, H, D]
    vp = v_ref[0].astype(jnp.float32) * vs_ref[0]
    qv = q_ref[0].astype(jnp.float32)                      # [H, D]
    s = jnp.sum(qv[None] * kp, axis=-1, keepdims=True) * scale
    _online_softmax_page(s, vp, w, pt, len_ref[b], m_s, l_s, acc_s, o_ref)


def _paged_decode_attention_quant_pallas(q, k_pool, k_scale,
                                         v_pool, v_scale,
                                         tables, lengths):
    B, H, D = q.shape
    P, pt, _, _ = k_pool.shape
    W = tables.shape[1]
    page = pl.BlockSpec((1, pt, H, D),
                        lambda b, w, tbl, ln: (tbl[b, w], _I0, _I0, _I0))
    # scales ride as [P, pt, H, 1]: heads on sublanes like the page rows
    # they multiply, so the in-kernel broadcast over D is a lane splat
    srow = pl.BlockSpec((1, pt, H, 1),
                        lambda b, w, tbl, ln: (tbl[b, w], _I0, _I0, _I0))
    return pl.pallas_call(
        functools.partial(_paged_quant_kernel, scale=1.0 / math.sqrt(D),
                          pt=pt),
        grid_spec=_paged_grid_spec(B, H, D, W, pt,
                                   [page, srow, page, srow]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("arbitrary", "arbitrary"),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, k_scale[..., None], v_pool, v_scale[..., None])


def paged_decode_attention_quant(q, k_pool, k_scale, v_pool, v_scale,
                                 tables, lengths, kernel=None):
    """Dispatch on `kernel` (or $PADDLE_TPU_DECODE_KERNEL, default xla)."""
    choice = (kernel or _flags.env_value(_ENV)).strip().lower()
    if choice == "pallas":
        return _paged_decode_attention_quant_pallas(
            q, k_pool, k_scale, v_pool, v_scale, tables, lengths)
    if choice in ("", "xla"):
        return paged_decode_attention_quant_reference(
            q, k_pool, k_scale, v_pool, v_scale, tables, lengths)
    raise ValueError(
        f"{_ENV}={choice!r}: expected 'pallas' or 'xla'")
