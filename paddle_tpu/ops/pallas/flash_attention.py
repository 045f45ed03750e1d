"""Flash attention for TPU in Pallas — forward + flash backward custom VJP.

Replaces the reference's fused CUDA attention kernels
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu,
operators/fused/fused_embedding_eltwise_layernorm) with the memory-optimal
online-softmax algorithm: O(T) memory instead of materialising the [T, T]
score matrix, K/V streamed block-by-block through VMEM into the MXU.

Layout: [B, T, H, D] (paddle sdpa convention) reshaped to [B*H, T, D].
Kernel structure is the TPU-canonical *grid-loop* form: the k-block loop is
the innermost ("arbitrary") grid dimension and the online-softmax state
(m, l, acc) lives in VMEM scratch that persists across those grid steps —
Mosaic pipelines the K/V block DMAs against MXU work. Causal pruning skips
above-diagonal blocks with pl.when. f32 accumulation via
preferred_element_type; bf16-friendly inputs.

Backward: a fused single-pass kernel (one score recompute emits dq, dk
and dv together) when the k sweep is single-block (T <= the k-block cap);
the standard two-pass scheme (dq pass over k blocks, dkv pass over q
blocks) above that. delta = rowsum(dO * O) is computed in-kernel in the
dkv/fused bodies. Saved residuals: q, k, v, o, logsumexp.

logsumexp is stored lane-replicated as [BH, T, 128] f32 — nominally 128x
the bytes of the per-row scalar, but keeping the lane dim lets every
kernel read/write it as a native (sublane, lane) tile with zero
relayouts; the extra HBM traffic is ~bq*128*4 per grid step (<0.5% of
the qkv streams; measured in the noise on the flagship bench), while a
[BH, T] layout would force a lane->sublane transpose inside each of the
three consumers.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import _common
from ._common import (pltpu, VMEM as _VMEM, compiler_params as _compiler_params,
                      mxu_dtype as _mxu_dtype, NEG_INF, LANE, I0 as _I0)


def _pick_block(T, cap):
    """Largest block <= cap that divides T, stepping down by powers of two
    from cap to 128; tiny sequences (T < 128) use one block."""
    if T <= 128:
        return T
    b = cap
    while b > 128 and T % b:
        b //= 2
    return b


def _env_blocks(key, T):
    bq, bk = (min(int(v), T) for v in os.environ[key].split(","))
    if bq <= 0 or bk <= 0 or T % bq or T % bk:
        raise ValueError(f"{key}={os.environ[key]}: blocks must be positive "
                         f"and divide seq len {T}")
    return bq, bk


def _block_sizes(T, D, env_key="PT_FLASH_FWD_BLOCKS"):
    """Large blocks amortise per-grid-step overhead: at (128,128) a T=1024
    head is 6k grid steps of ~4 MFLOP each and the kernel is dispatch-bound
    (measured 8.5 ms/layer fwd+bwd vs 3.9 ms at (512,1024) on v5e). The env
    keys PT_FLASH_{FWD,BWD}_BLOCKS are perf-tuning escape hatches.

    Blocks are capped at (1024, 1024): 2048-wide blocks exceed VMEM at
    D=64 (the f32 score tile alone is 16 MB)."""
    if env_key in os.environ:
        return _env_blocks(env_key, T)
    return _pick_block(T, 1024), _pick_block(T, 1024)


def _bwd_block_sizes(T, D):
    """Backward caps get their own VMEM budget — the bwd working set is
    larger than the forward's. Per (bq, bk) grid step of the dkv kernel
    the f32 score-sized intermediates are s/p (reusable), dp and ds at
    bq*bk*4 B each (~3 live tiles), plus double-buffered I/O tiles
    (q/k/v/do/o bf16 + lse f32: ~(4*max(bq,bk)*D*2 + bq*128*4)*2 B) and
    the dk/dv f32 scratch (2*bk*D*4 B). At (1024, 1024):
      D=64 : 12 MB + 1.9 MB + 0.5 MB ~= 14.4 MB -> fits 16 MB VMEM
      D=128: 12 MB + 3.5 MB + 1.0 MB ~= 16.5 MB -> over budget, so wide
             heads cap bq at 512, halving the score tiles to 2 MB each
             (~9.75 MB total) with the same nk==1 fused-path eligibility
             (bk stays 1024). Measured cost of the halved caps: none —
             fwd+bwd at T=4096 on v5e runs 73.6 TF/s at D=128/(512,1024)
             vs 50.5 TF/s at D=64/(1024,1024) (the wider contraction
             feeds the MXU better)."""
    if "PT_FLASH_BWD_BLOCKS" in os.environ:
        return _env_blocks("PT_FLASH_BWD_BLOCKS", T)
    cap_q = 1024 if D <= 64 else 512
    return _pick_block(T, cap_q), _pick_block(T, 1024)


# ---------------------------------------------------------------------------
# forward kernel: grid (BH, nq, nk), scratch carries (m, l, acc) over nk
# ---------------------------------------------------------------------------

def _band_first(qi, block_q, block_k, window):
    """The first k block a q block's rows can see under a window:
    the one holding position `qi * block_q - window + 1`."""
    return jax.lax.div(jnp.maximum(qi * block_q - (window - 1), 0),
                       jnp.int32(block_k))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, causal, block_q, block_k, nk, mxu, window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc[:], NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc[:])
        acc_sc[:] = jnp.zeros_like(acc_sc[:])

    # under a window the grid's k axis counts from the band's first
    # block (`_fwd`'s index map), not from block 0
    kb = kj if window is None \
        else kj + _band_first(qi, block_q, block_k, window)
    # causal: process only blocks intersecting the lower triangle
    should = (kb * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(should)
    def _step():
        # bf16 operands feed the MXU at full rate; accumulation stays f32
        q = (q_ref[0].astype(jnp.float32) * np.float32(scale)).astype(mxu)                                 # [bq, D]
        k = k_ref[0].astype(mxu)                 # [bk, D]
        v = v_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = rows >= cols
            if window is not None:
                seen = seen & (rows - cols < window)
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_sc[:, :1] + p.sum(axis=1, keepdims=True)
        acc_sc[:] = alpha * acc_sc[:] + jax.lax.dot_general(
            p.astype(mxu), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:, :1], np.float32(1e-30))
        o_ref[0] = (acc_sc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_sc[:] + jnp.log(jnp.maximum(l_sc[:], np.float32(1e-30)))


def band_blocks(T, block_q, block_k, window):
    """The most k blocks any q block of `block_q` rows touches under
    `window`: the grid's k extent of a banded call."""
    return max((i * block_q + block_q - 1) // block_k
               - max(i * block_q - (window - 1), 0) // block_k + 1
               for i in range(T // block_q))


def _fwd(q3, k3, v3, scale, causal, window=None):
    """q3 [BH, T, D]; k3, v3 [BHkv, T, .], `BH // BHkv` query heads a
    K/V head, query head b reading K/V head `b // group` (no repeat in
    memory). `window` (with `causal`): row t sees keys `t - window + 1
    .. t`; the grid's k axis then spans the band alone, so a cell
    wholly behind the window does not exist, and past the diagonal the
    index map stays on the diagonal's block (no fetch, no work)."""
    BH, T, D = q3.shape
    Dv = v3.shape[2]        # the value width; training's calls have Dv == D
    group = BH // k3.shape[0]
    bq, bk = _block_sizes(T, D)
    nq, nk = T // bq, T // bk
    if window is not None and window >= T:
        window = None       # the band is the whole triangle
    if window is not None:
        nk = band_blocks(T, bq, bk, window)

    def kv_map(b, i, j):
        if window is not None:
            j = jnp.minimum(j + _band_first(i, bq, bk, window),
                            jax.lax.div(i * bq + bq - 1, jnp.int32(bk)))
        if group > 1:
            b = jax.lax.div(b, jnp.int32(group))
        return (b, j, _I0)

    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk, nk=nk, mxu=_mxu_dtype(),
                             window=window)
    o, lse = pl.pallas_call(
        kern,
        name="flash_attention_fwd",
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), kv_map, memory_space=_VMEM),
            pl.BlockSpec((1, bk, Dv), kv_map, memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, LANE), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, Dv), q3.dtype),
            jax.ShapeDtypeStruct((BH, T, LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, LANE), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=_common.interpret(),
        **_compiler_params("parallel", "parallel", "arbitrary"),
    )(q3, k3, v3)
    return o, lse


# ---------------------------------------------------------------------------
# backward: dq pass (grid over q blocks x k blocks, dq scratch)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_sc, *, scale, causal, block_q, block_k, nk, mxu):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc[:])

    should = (kj * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(should)
    def _step():
        q = (q_ref[0].astype(jnp.float32) * np.float32(scale)).astype(mxu)
        k = k_ref[0].astype(mxu)
        v = v_ref[0].astype(mxu)
        do = do_ref[0].astype(mxu)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds.astype(mxu), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = (dq_sc[:] * np.float32(scale)).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk/dv pass (grid over k blocks x q blocks, dk/dv scratch)
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                    *rest, scale, causal, block_q, block_k, nq, mxu,
                    emit_dq=False):
    """Shared dk/dv (+ optionally dq) backward body, grid (BH, nk, nq)
    with the q sweep innermost. dk/dv accumulate in scratch over the q
    sweep; with emit_dq each (ki, qj) writes that q block's dq directly —
    valid only when nk == 1 (each dq block visited once), which is how
    _bwd_dispatch routes it."""
    if emit_dq:
        dq_ref, dk_ref, dv_ref, dk_sc, dv_sc = rest
    else:
        dk_ref, dv_ref, dk_sc, dv_sc = rest
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc[:])
        dv_sc[:] = jnp.zeros_like(dv_sc[:])

    # causal: q blocks entirely above this k block contribute nothing
    should = (qj * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(should)
    def _step():
        q = (q_ref[0].astype(jnp.float32) * np.float32(scale)).astype(mxu)                                 # [bq, D]
        k = k_ref[0].astype(mxu)                 # [bk, D]
        v = v_ref[0].astype(mxu)
        do = do_ref[0].astype(mxu)
        lse = lse_ref[0][:, :1]
        # delta = rowsum(dO * O) computed in-kernel: avoids materialising
        # a [BH, T, LANE] f32 delta in HBM (ADVICE r1: 128x overhead for
        # per-row scalars)
        delta = jnp.sum(do_ref[0].astype(jnp.float32)
                        * o_ref[0].astype(jnp.float32), axis=1,
                        keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                              # [bq, bk]
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p.astype(mxu), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds.astype(mxu), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if emit_dq:
            dq_ref[0] = (jax.lax.dot_general(
                ds.astype(mxu), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                * np.float32(scale)).astype(dq_ref.dtype)

    if emit_dq:
        @pl.when(jnp.logical_not(should))
        def _masked_dq():
            dq_ref[0] = jnp.zeros_like(dq_ref[0])

    @pl.when(qj == nq - 1)
    def _finish():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)  # q already carries scale
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, res, g):
    q3, k3, v3, o3, lse = res
    BH, T, D = q3.shape
    bq, bk = _bwd_block_sizes(T, D)
    nq, nk = T // bq, T // bk
    do3 = g
    # dq pass still consumes a precomputed delta (its blocks iterate k
    # inner, so per-block recompute there would repeat the same rowsum)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (BH, T, LANE))

    kwargs = _compiler_params("parallel", "parallel", "arbitrary")

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk, mxu=_mxu_dtype()),
        name="flash_attention_bwd_dq",
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, LANE), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, LANE), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, _I0),
                               memory_space=_VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=_common.interpret(),
        **kwargs,
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, mxu=_mxu_dtype()),
        name="flash_attention_bwd_dkv",
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, LANE), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=_common.interpret(),
        **kwargs,
    )(q3, k3, v3, do3, lse, o3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash3(q3, k3, v3, scale, causal):
    o, _ = _fwd(q3, k3, v3, scale, causal)
    return o


def _flash3_fwd(q3, k3, v3, scale, causal):
    o, lse = _fwd(q3, k3, v3, scale, causal)
    return o, (q3, k3, v3, o, lse)


def _bwd_dispatch(scale, causal, res, g):
    """Fused single-pass backward when every q block sees a SINGLE k sweep
    (nk == 1, i.e. T <= the k block cap): its dq accumulation rides an
    aliased HBM buffer, which is only well-defined when no dq block is
    revisited across k iterations. Larger T uses the two-pass scheme."""
    T = res[0].shape[1]
    _, bk = _bwd_block_sizes(T, res[0].shape[2])
    if (T // bk) == 1 and os.environ.get("PT_FLASH_FUSED_BWD", "1") != "0":
        return _bwd_fused(scale, causal, res, g)
    return _bwd(scale, causal, res, g)


_flash3.defvjp(_flash3_fwd, _bwd_dispatch)


def flash_attention(q, k, v, causal=False, scale=None):
    """q/k/v: [B, T, H, D] (paddle layout) -> [B, T, H, D]."""
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # validate BOTH directions' blocks up front so a bad env override fails
    # here (where sdpa's fallback can catch it) rather than mid-backward
    bq, bk = _block_sizes(T, D)
    _bwd_block_sizes(T, D)
    if T % bq or T % bk:
        raise ValueError(f"flash_attention: seq len {T} must be a multiple "
                         f"of the block size {bq}")

    def to3(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)

    o3 = _flash3(to3(q), to3(k), to3(v), float(scale), bool(causal))
    return jnp.transpose(o3.reshape(B, H, T, D), (0, 2, 1, 3))


def flash_attention_forward(q, k, v, causal=False, scale=None, window=None):
    """Forward only, for inference: q [B, T, H, D], k [B, T, Hkv, D] and
    v [B, T, Hkv, Dv] -> [B, T, H, Dv]. The value width is its own
    (latent attention's expanded prefill has 192-wide q and k and
    128-wide v). `Hkv` may divide `H` (grouped-query heads): query head
    j reads K/V head `j // (H // Hkv)` where it lies. `window` (causal
    only): position t attends `t - window + 1 .. t`, and blocks wholly
    outside that band are not visited. No gradient is defined;
    `flash_attention` keeps one width and one head count, which is all
    its backward kernels know."""
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if H % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"flash_attention_forward: {H} query heads over "
                         f"{k.shape[2]} key and {v.shape[2]} value heads")
    if window is not None and (not causal or window < 1):
        raise ValueError("flash_attention_forward: a window is causal "
                         "and at least 1")
    bq, bk = _block_sizes(T, D)
    if T % bq or T % bk:
        raise ValueError(f"flash_attention_forward: seq len {T} must be a "
                         f"multiple of the block size {bq}")

    def to3(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(
            B * x.shape[2], T, x.shape[-1])

    o3, _ = _fwd(to3(q), to3(k), to3(v), float(scale), bool(causal),
                 None if window is None else int(window))
    return jnp.transpose(o3.reshape(B, H, T, Dv), (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# fused single-pass backward (nk == 1 route): the shared kernel body with
# emit_dq — each (ki=0, qj) step computes dq for its q block directly, so
# the second score/probability recompute of the two-pass scheme (~30% of
# backward FLOPs) disappears.
# ---------------------------------------------------------------------------

def _bwd_fused(scale, causal, res, g):
    q3, k3, v3, o3, lse = res
    BH, T, D = q3.shape
    bq, bk = _bwd_block_sizes(T, D)
    nq, nk = T // bq, T // bk
    assert nk == 1, "fused backward requires a single k sweep"
    do3 = g      # delta is computed in-kernel from (do, o) blocks

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, mxu=_mxu_dtype(),
                          emit_dq=True),
        name="flash_attention_bwd_fused",
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, LANE), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, j, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, _I0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=_common.interpret(),
        **_compiler_params("parallel", "arbitrary", "arbitrary"),
    )(q3, k3, v3, do3, lse, o3)
    return dq, dk, dv
