"""Single-token decode attention over a paged LATENT cache (MLA, the
DeepSeek-V2/V3 family's multi-head latent attention in its absorbed
form).

A cached position is one row `[c_kv | k_r]` shared by every head: the
normalised key/value latent (C wide) and the rotated positional key (R
wide). With the key up-projection absorbed into the query and the value
up-projection applied after the sum, a decode step is, per sequence,

    s[h, t]   = (q_abs[h] . c_kv[t] + q_rope[h] . k_r[t]) * scale
    p         = softmax over the live t (float32)
    o_lat[h]  = sum_t p[h, t] c_kv[t]

H query heads against ONE key row and ONE value row a token: the cache
is read once for all heads, and both products are [H, .] x [., pt]
matrix products, not the per-head GEMVs of `decode_attention.py`.

Shapes (pt = page tokens):

    q_abs    [B, H, C]        q_nope W_uk^T, per head
    q_rope   [B, H, R]        rotated positional query
    pool     [P, pt, >= C + R]  ONE layer's page pool (page 0 = null
                              page); a row is [c_kv | k_r | padding]
    tables   [B, W] int32     block table; unused entries -> page 0
    lengths  [B] int32        live prefix per sequence (>= 1)
    out      [B, H, C]        o_lat, in q_abs's dtype

`paged_latent_decode_attention_reference` is the `jax.numpy`
composition (gathers the table's pages); the Pallas kernel walks the
block table through scalar-prefetch index maps, `PAGES_PER_STEP` pages
a grid cell with the online-softmax state in VMEM scratch, so only
mapped pages stream through VMEM and no gathered panel exists in HBM.
Table padding repeats page 0, whose block the pipeline does not fetch
again, and cells past a sequence's length skip their arithmetic. Off
the chip the kernel body runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import _common
from ._common import NEG_INF, LANE, I0 as _I0, pltpu

PAGES_PER_STEP = 4      # pages one grid cell attends (W permitting)
KERNEL_NAME = "paged_latent_decode_attention"


def paged_latent_decode_attention_reference(q_abs, q_rope, pool, tables,
                                            lengths, scale):
    """jnp reference: gather the table's pages, masked float32 softmax."""
    B, W = tables.shape
    P, pt, width = pool.shape
    C = q_abs.shape[-1]
    rows = jnp.take(pool, tables, axis=0).reshape(B, W * pt, width)
    R = q_rope.shape[-1]
    c_kv, k_r = rows[..., :C], rows[..., C:C + R]
    f32 = jnp.float32
    s = jnp.einsum("bhc,btc->bht", q_abs, c_kv, preferred_element_type=f32) \
        + jnp.einsum("bhr,btr->bht", q_rope, k_r, preferred_element_type=f32)
    s = s * jnp.float32(scale)
    live = jnp.arange(W * pt, dtype=jnp.int32)[None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, NEG_INF), axis=-1)
    o = jnp.einsum("bht,btc->bhc", p.astype(c_kv.dtype), c_kv,
                   preferred_element_type=f32)
    return o.astype(q_abs.dtype)


def _kernel(tbl_ref, len_ref, qa_ref, qr_ref, *rest, scale, pt, C, R, G):
    pages, o_ref = rest[:G], rest[G]
    m_s, l_s, acc_s = rest[G + 1:]
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]
    for j, page_ref in enumerate(pages):
        base = (w * G + j) * pt

        @pl.when(base < length)
        def _page(page_ref=page_ref, base=base):
            page = page_ref[0]                              # [pt, row]
            c_kv, k_r = page[:, :C], page[:, C:C + R]
            dims = (((1,), (1,)), ((), ()))
            s = jax.lax.dot_general(
                qa_ref[0], c_kv, dims, preferred_element_type=jnp.float32) \
                + jax.lax.dot_general(
                    qr_ref[0], k_r, dims,
                    preferred_element_type=jnp.float32)     # [H, pt]
            s = s * jnp.float32(scale)
            cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < length, s, NEG_INF)
            m_prev = m_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_s[:, :1] + p.sum(axis=1, keepdims=True)
            acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
                p.astype(c_kv.dtype), c_kv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [H, C]
            m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
            l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(w == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0] = (acc_s[...] / l_s[:, :1]).astype(o_ref.dtype)


def _pallas(q_abs, q_rope, pool, tables, lengths, scale):
    B, H, C = q_abs.shape
    R = q_rope.shape[-1]
    P, pt, width = pool.shape
    W = tables.shape[1]
    G = PAGES_PER_STEP if W % PAGES_PER_STEP == 0 else 1

    def page_spec(j):
        return pl.BlockSpec(
            (1, pt, width),
            lambda b, w, tbl, ln: (tbl[b, w * G + j], _I0, _I0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W // G),
        in_specs=[
            pl.BlockSpec((1, H, C), lambda b, w, tbl, ln: (b, _I0, _I0)),
            pl.BlockSpec((1, H, R), lambda b, w, tbl, ln: (b, _I0, _I0)),
        ] + [page_spec(j) for j in range(G)],
        out_specs=pl.BlockSpec((1, H, C),
                               lambda b, w, tbl, ln: (b, _I0, _I0)),
        scratch_shapes=[
            pltpu.VMEM((H, LANE), jnp.float32),     # running max
            pltpu.VMEM((H, LANE), jnp.float32),     # running denominator
            pltpu.VMEM((H, C), jnp.float32),        # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), pt=pt, C=C, R=R,
                          G=G),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C), q_abs.dtype),
        interpret=_common.interpret(),
        **_common.compiler_params("parallel", "arbitrary"),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_abs, q_rope, *([pool] * G))


def paged_latent_decode_attention(q_abs, q_rope, pool, tables, lengths,
                                  scale, kernel=None):
    """The Pallas kernel on a TPU and the reference off it (the
    interpreter is for tests), unless `kernel` ("pallas" | "xla") says.
    The reference gathers every mapped page into a panel: on the chip
    it is the slow path by construction."""
    choice = kernel or ("pallas" if _common.on_tpu() else "xla")
    if choice == "pallas":
        return _pallas(q_abs, q_rope, pool, tables, lengths, scale)
    if choice == "xla":
        return paged_latent_decode_attention_reference(
            q_abs, q_rope, pool, tables, lengths, scale)
    raise ValueError(f"kernel={choice!r}: expected 'pallas' or 'xla'")
