"""Single-token decode attention over a paged K/V cache with
grouped-query heads: `Hq` query heads over `Hkv` key/value heads of
width D, query head j reading K/V head `j // (Hq // Hkv)`.

A cached position is one K row and one V row of `Hkv * D` lanes, the
heads side by side. Per sequence,

    s[j, t] = q[j] . k[t, j // g] * scale
    p       = softmax over the live t (float32)
    o[j]    = sum_t p[j, t] v[t, j // g]

The kernel runs every query head against a page in ONE product a side:
the queries arrive spread block-diagonally, head j's D values in lane
block `j // g` of an `Hkv * D`-wide row and zeros elsewhere, so that
`q_spread [Hq, Hkv D] x page^T [Hkv D, pt]` is each head's own dot
product and nothing else; `p [Hq, pt] x v_page [pt, Hkv D]` then gives
every head every value head's sum, and the wrapper keeps each head's
own block. The systolic array takes as many passes over a page as one
product per K/V head would (its rows are far from full either way) and
each cached row is read once for all heads.

Shapes (pt = page tokens):

    q        [B, Hq, D]
    k_pool   [P, pt, Hkv * D]   one layer's K pages (page 0 = null page)
    v_pool   [P, pt, Hkv * D]   one layer's V pages
    tables   [B, W] int32       block table; unused entries -> any page
    lengths  [B] int32          live rows per sequence (>= 1)
    out      [B, Hq, D]         in q's dtype

Rows `0 .. lengths - 1` of a sequence's pages are attended, in any
order: a softmax does not care, so a ring of rows (a sliding window
kept by slot, row of position p at `p mod window`) is read as it lies,
with `lengths = min(positions, window)`.

`paged_gqa_decode_attention_reference` is the `jax.numpy` composition
(gathers the table's pages); the Pallas kernel walks the block table
through scalar-prefetch index maps, `PAGES_PER_STEP` pages a grid cell
with the online-softmax state in VMEM scratch. Cells past a sequence's
length skip their arithmetic and aim at its last live page, which the
pipeline does not fetch again. Off the chip the kernel body runs in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import _common
from ._common import NEG_INF, LANE, I0 as _I0, pltpu

PAGES_PER_STEP = 8      # pages one grid cell attends (W permitting)
KERNEL_NAME = "paged_gqa_decode_attention"


def paged_gqa_decode_attention_reference(q, k_pool, v_pool, tables, lengths,
                                         scale):
    """jnp reference: gather the table's pages, masked float32 softmax."""
    B, Hq, D = q.shape
    W = tables.shape[1]
    pt = k_pool.shape[1]
    Hkv = k_pool.shape[2] // D
    k = jnp.take(k_pool, tables, axis=0).reshape(B, W * pt, Hkv, D)
    v = jnp.take(v_pool, tables, axis=0).reshape(B, W * pt, Hkv, D)
    f32 = jnp.float32
    qg = q.reshape(B, Hkv, Hq // Hkv, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k, preferred_element_type=f32) \
        * f32(scale)
    live = jnp.arange(W * pt, dtype=jnp.int32)[None, None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None, None]
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=f32)
    return o.reshape(B, Hq, D).astype(q.dtype)


def _kernel(tbl_ref, len_ref, q_ref, *rest, scale, pt, G):
    k_pages, v_pages, o_ref = rest[:G], rest[G:2 * G], rest[2 * G]
    m_s, l_s, acc_s = rest[2 * G + 1:]
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]
    for j in range(G):
        base = (w * G + j) * pt

        @pl.when(base < length)
        def _page(k_ref=k_pages[j], v_ref=v_pages[j], base=base):
            k, v = k_ref[0], v_ref[0]                       # [pt, Hkv D]
            s = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < length, s, NEG_INF)        # [Hq, pt]
            m_prev = m_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_s[:, :1] + p.sum(axis=1, keepdims=True)
            acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [Hq, Hkv D]
            m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
            l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(w == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (acc_s[...] / l_s[:, :1]).astype(o_ref.dtype)


def _spread(q, Hkv):
    """q [B, Hq, D] -> [B, Hq, Hkv * D]: head j's values in lane block
    `j // g`, zeros in the others."""
    B, Hq, D = q.shape
    own = (jnp.arange(Hq, dtype=jnp.int32)[:, None] // (Hq // Hkv)
           == jnp.arange(Hkv, dtype=jnp.int32)[None, :])    # [Hq, Hkv]
    return jnp.where(own[None, :, :, None], q[:, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(B, Hq, Hkv * D)


def _own_block(o, Hkv):
    """[B, Hq, Hkv * D] -> [B, Hq, D]: each head's own value head."""
    B, Hq, width = o.shape
    D = width // Hkv
    o = o.reshape(B, Hkv, Hq // Hkv, Hkv, D)
    return jnp.moveaxis(jnp.diagonal(o, axis1=1, axis2=3), -1, 1) \
        .reshape(B, Hq, D)


def _pallas(q, k_pool, v_pool, tables, lengths, scale):
    B, Hq, D = q.shape
    P, pt, width = k_pool.shape
    Hkv = width // D
    W = tables.shape[1]
    G = PAGES_PER_STEP if W % PAGES_PER_STEP == 0 else 1

    def page_spec(j):
        # past the live rows: the last live page again (no new fetch)
        return pl.BlockSpec(
            (1, pt, width),
            lambda b, w, tbl, ln: (
                tbl[b, jnp.minimum(w * G + j, jax.lax.div(
                    ln[b] - 1, jnp.int32(pt)))], _I0, _I0))

    row = pl.BlockSpec((1, Hq, width), lambda b, w, tbl, ln: (b, _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W // G),
        in_specs=[row] + [page_spec(j) for j in range(G)] * 2,
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((Hq, LANE), jnp.float32),    # running max
            pltpu.VMEM((Hq, LANE), jnp.float32),    # running denominator
            pltpu.VMEM((Hq, width), jnp.float32),   # output accumulator
        ],
    )
    o = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), pt=pt, G=G),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, width), q.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("parallel", "arbitrary"),
    )(tables.astype(jnp.int32), jnp.maximum(lengths.astype(jnp.int32), 1),
      _spread(q, Hkv), *([k_pool] * G), *([v_pool] * G))
    return _own_block(o, Hkv)


def paged_gqa_decode_attention(q, k_pool, v_pool, tables, lengths, scale,
                               kernel=None):
    """The Pallas kernel on a TPU and the reference off it (the
    interpreter is for tests), unless `kernel` ("pallas" | "xla") says.
    The reference gathers every mapped page into a panel: on the chip
    it is the slow path by construction."""
    choice = kernel or ("pallas" if _common.on_tpu() else "xla")
    if choice == "pallas":
        return _pallas(q, k_pool, v_pool, tables, lengths, scale)
    if choice == "xla":
        return paged_gqa_decode_attention_reference(
            q, k_pool, v_pool, tables, lengths, scale)
    raise ValueError(f"kernel={choice!r}: expected 'pallas' or 'xla'")
