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
kernel) attends the same math over a PAGED cache: one layer's page pool
`[P, page_tokens, H * D]` (a token's row is every head side by side, so
it fills whole 128-lane tiles at any head size) plus per-sequence int32
block tables (inference/decode.py's paged engine). The XLA path gathers
the table's pages with `jnp.take` and keeps the gathered panel in that
row layout through the scores and the weighted sum (`head_scores`,
`head_mix`); the Pallas variant walks the block table via
scalar-prefetch index maps — one grid cell per (batch, page), every
head of the page, online softmax in scratch — so only mapped pages are
ever streamed into VMEM.

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
#     k_pool   [P, pt, H * D]     one layer's page pool (pt = page tokens);
#     v_pool   [P, pt, H * D]     a token's row is its H heads side by side
#     tables   [B, W] int32       block table: tables[b, w] = page holding
#                                 rows [w*pt, (w+1)*pt) of sequence b;
#                                 unused entries point at the null page
#     lengths  [B] int32          valid prefix per sequence
#     out      [B, H, D]

def _head_blocks(heads, width):
    """[H, C] bool: lane c of a row belongs to head c // (C // H)."""
    lane = jnp.arange(width, dtype=jnp.int32)[None, :] // (width // heads)
    return lane == jnp.arange(heads, dtype=jnp.int32)[:, None]


def head_scores(q, keys, heads):
    """Per-head scaled scores over rows that hold every head side by
    side: q [B, Q, C], keys [B, K, C] -> float32 [B, Q, H, K] with C =
    H * D. Each query row is spread over H rows that are zero outside
    their head's D lanes, so one batched product against the panel AS
    IT LIES gives every head's score: the panel is never reshaped to
    [.., H, D] (D < 128 lanes would make the compiler copy it into
    another layout). The zeros add nothing, so the operands and the sum
    are those of `einsum("bqhd,bkhd->bqhk")`."""
    B, Q, C = q.shape
    qb = jnp.where(_head_blocks(heads, C), q[:, :, None, :], 0)
    s = jnp.einsum("bmc,bkc->bmk", qb.reshape(B, Q * heads, C), keys)
    s = s * (1.0 / math.sqrt(C // heads))
    return s.astype(jnp.float32).reshape(B, Q, heads, keys.shape[1])


def head_mix(p, vals):
    """The weighted sum that goes with `head_scores`: p [B, Q, H, K],
    vals [B, K, C] -> [B, Q, C], head h's D lanes mixed by p[:, :, h].
    One batched product gives every head's weights over the whole row;
    each head keeps its own lanes of it."""
    B, Q, H, K = p.shape
    C = vals.shape[-1]
    r = jnp.einsum("bmk,bkc->bmc", p.reshape(B, Q * H, K), vals)
    return jnp.sum(jnp.where(_head_blocks(H, C), r.reshape(B, Q, H, C), 0),
                   axis=2)


def dequantize_rows(data, scale):
    """An int8 pool's rows as float32: data [..., H * D] times its
    per-head scale [..., H] spread over the head's D lanes."""
    D = data.shape[-1] // scale.shape[-1]
    return data.astype(jnp.float32) * jnp.repeat(scale, D, axis=-1)


def take_pages(pool, tables):
    """The pages a block table names, [B, W, pt, ..]. A table holds
    page ids of this pool and nothing else, so the gather clips
    instead of filling: `jnp.take`'s default marks out-of-range reads
    with NaN, which costs one more pass over the gathered panel."""
    return jnp.take(pool, tables, axis=0, mode="clip")


def _panel_attention(q, k, v, lengths):
    """q [B, H, D] over gathered panels k, v [B, K, H * D]: the masked
    softmax of `decode_attention_reference`, rows kept whole."""
    B, H, D = q.shape
    s = head_scores(q.reshape(B, 1, H * D), k, H)             # [B,1,H,K]
    live = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return head_mix(p, v).reshape(B, H, D).astype(q.dtype)


def gathered_panel(pool, tables):
    """The pages a block table names out of one layer's pool, as a
    float32 panel [B, W * pt, H * D] with every row whole; `pool` is
    the float32 array or the int8 pair `(data, scale)`, whose gathered
    panel is dequantized in the same expression."""
    if isinstance(pool, tuple):
        panel = dequantize_rows(take_pages(pool[0], tables),
                                take_pages(pool[1], tables))
    else:
        panel = take_pages(pool, tables)
    B, W, pt, C = panel.shape
    return panel.reshape(B, W * pt, C)


def paged_decode_attention_reference(q, k_pool, v_pool, tables, lengths):
    """XLA path: gather the table's pages (`jnp.take`) into a contiguous
    [B, W*pt, H*D] panel, masked softmax per head."""
    with jax.named_scope("page_gather"):
        k = gathered_panel(k_pool, tables)
        v = gathered_panel(v_pool, tables)
    return _panel_attention(q, k, v, lengths)


def _head_sum_matrices(heads, width):
    """(G [C, HP], G.T) float32, HP = heads rounded up to a lane tile:
    `x @ G` sums a row's lanes per head, `y @ G.T` spreads a per-head
    value back over its lanes. Exact at `Precision.HIGHEST` (0/1
    entries); the padding columns are zero."""
    hp = -(-heads // _common.LANE) * _common.LANE
    g = _head_blocks(heads, width).astype(jnp.float32).T      # [C, H]
    g = jnp.pad(g, ((0, 0), (0, hp - heads)))
    return g, g.T


def _hdot(a, b):
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _online_softmax_page(s, vp, gt, w, pt, length, m_s, l_s, acc_s, o_ref):
    """One page of the online (flash-style) softmax, all heads at once.
    s [pt, HP] scores (a head a lane), vp [pt, C] values, gt [HP, C]
    spreads a head's value over its lanes; the running max m_s and
    denominator l_s are [1, HP], the accumulator acc_s [1, C]."""
    @pl.when(w == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    rows = w * pt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(rows < length, s, NEG_INF)
    m_prev = m_s[...]                                      # [1, HP]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                 # [pt, HP]
    m_s[...] = m_new
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=0, keepdims=True)
    acc_s[...] = acc_s[...] * _hdot(corr, gt) \
        + jnp.sum(_hdot(p, gt) * vp, axis=0, keepdims=True)

    @pl.when(w == pl.num_programs(1) - 1)
    def _emit():
        # a padding lane of l_s is never zero (its scores are 0, not
        # masked), and gt's zero rows keep it out of the quotient
        o_ref[0] = (acc_s[...] / _hdot(l_s[...], gt)).astype(o_ref.dtype)


def _paged_kernel(tbl_ref, len_ref, q_ref, g_ref, gt_ref, k_ref, v_ref,
                  o_ref, m_s, l_s, acc_s, *, scale, pt):
    """One grid cell per (batch, page-slot): walk the block table along
    the last grid dim with the softmax state carried in VMEM scratch, so
    only the pages a sequence actually maps stream through VMEM — no
    gather materialization. A cell holds one whole page as it lies in
    the pool, [pt, H*D]: the per-head sum over D lanes is a product with
    the 0/1 matrix `g`."""
    b = pl.program_id(0)
    w = pl.program_id(1)
    kp = k_ref[0].astype(jnp.float32)                      # [pt, C]
    vp = v_ref[0].astype(jnp.float32)
    qv = q_ref[0].astype(jnp.float32)                      # [1, C]
    s = _hdot(qv * kp, g_ref[...]) * scale                 # [pt, HP]
    _online_softmax_page(s, vp, gt_ref[...], w, pt, len_ref[b],
                         m_s, l_s, acc_s, o_ref)


def _paged_grid_spec(B, C, HP, W, page_specs):
    """Grid (batch, page-slot) with (tables, lengths) scalar-prefetched:
    their VALUES drive the K/V index_map, so each grid cell DMAs exactly
    the page the block table names — the table walk happens in the
    pipeline, not the body. The head-sum matrices ride whole (one block,
    fetched once)."""
    row = pl.BlockSpec((1, 1, C), lambda b, w, tbl, ln: (b, _I0, _I0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[row,
                  pl.BlockSpec((C, HP), lambda b, w, tbl, ln: (_I0, _I0)),
                  pl.BlockSpec((HP, C), lambda b, w, tbl, ln: (_I0, _I0))]
        + page_specs,
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((1, HP), jnp.float32),    # running max
            pltpu.VMEM((1, HP), jnp.float32),    # running denominator
            pltpu.VMEM((1, C), jnp.float32),     # output accumulator
        ],
    )


def _paged_call(kernel, q, pools, page_specs, tables, lengths, pt):
    B, H, D = q.shape
    C = H * D
    g, gt = _head_sum_matrices(H, C)
    out = pl.pallas_call(
        functools.partial(kernel, scale=1.0 / math.sqrt(D), pt=pt),
        grid_spec=_paged_grid_spec(B, C, g.shape[1], tables.shape[1],
                                   page_specs),
        out_shape=jax.ShapeDtypeStruct((B, 1, C), q.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("arbitrary", "arbitrary"),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(B, 1, C), g, gt, *pools)
    return out.reshape(B, H, D)


def _page_spec(pt, width):
    return pl.BlockSpec((1, pt, width),
                        lambda b, w, tbl, ln: (tbl[b, w], _I0, _I0))


def _paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths):
    P, pt, C = k_pool.shape
    page = _page_spec(pt, C)
    return _paged_call(_paged_kernel, q, (k_pool, v_pool), [page, page],
                       tables, lengths, pt)


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
#     k_pool, v_pool    [P, pt, H * D] int8
#     k_scale, v_scale  [P, pt, H]     f32   (row = q * scale)
#
# The Pallas kernel prefetches the scale page alongside its int8 page
# and dequantizes in-register right before the online-softmax
# accumulate — the fp32 panel never exists in HBM.

def paged_decode_attention_quant_reference(q, k_pool, k_scale,
                                           v_pool, v_scale,
                                           tables, lengths):
    """XLA fallback: gather int8 pages + scales, dequantize the gathered
    panel, reuse the fp32 masked-softmax math."""
    return paged_decode_attention_reference(
        q, (k_pool, k_scale), (v_pool, v_scale), tables, lengths)


def _paged_quant_kernel(tbl_ref, len_ref, q_ref, g_ref, gt_ref, k_ref,
                        ks_ref, v_ref, vs_ref, o_ref, m_s, l_s, acc_s,
                        *, scale, pt):
    """`_paged_kernel` with int8 pages: each page's scale block
    [pt, HP] (a head a lane) rides its own prefetched block, is spread
    over its head's lanes by `gt`, and the page dequantizes in-register
    before the score / accumulate."""
    b = pl.program_id(0)
    w = pl.program_id(1)
    gt = gt_ref[...]
    kp = k_ref[0].astype(jnp.float32) * _hdot(ks_ref[0], gt)   # [pt, C]
    vp = v_ref[0].astype(jnp.float32) * _hdot(vs_ref[0], gt)
    qv = q_ref[0].astype(jnp.float32)                          # [1, C]
    s = _hdot(qv * kp, g_ref[...]) * scale
    _online_softmax_page(s, vp, gt, w, pt, len_ref[b], m_s, l_s, acc_s,
                         o_ref)


def _paged_decode_attention_quant_pallas(q, k_pool, k_scale,
                                         v_pool, v_scale,
                                         tables, lengths):
    B, H, D = q.shape
    P, pt, C = k_pool.shape
    hp = -(-H // _common.LANE) * _common.LANE
    page = _page_spec(pt, C)
    srow = _page_spec(pt, hp)
    # scales ride padded to a lane tile, [P, pt, HP]: a head a lane,
    # like the scores they sit beside
    pad = ((0, 0), (0, 0), (0, hp - H))
    return _paged_call(
        _paged_quant_kernel, q,
        (k_pool, jnp.pad(k_scale, pad), v_pool, jnp.pad(v_scale, pad)),
        [page, srow, page, srow], tables, lengths, pt)


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
