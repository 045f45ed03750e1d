"""Kimi Delta Attention (KDA): a gated delta rule with a decay of its own
for every key channel. Per head, the state S [K keys, V values] float32
follows, a token at a time,

    S <- Diag(alpha_t) S                       alpha_t = exp(g_t) in (0, 1]
    S <- S + beta_t k_t (v_t - S^T k_t)^T      the delta rule
    o_t = S^T q_t

with q, k already normalised (and q scaled) by the caller. Three forms
of the same mathematics, equal to float32 rounding:

* `kda_recurrence`: the definition, a `lax.scan` over positions. The
  oracle of the tests (the benchmark's reference has its own copy).
* `kda_chunk_prefill`: chunks of `CHUNK` positions; inside a chunk the
  WY / UT-transform form (Yang et al., gated delta networks), between
  chunks the state S carried through a scan. With G_i the log decay
  summed from the chunk's start to position i,

      A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     j < i
      B_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])     j <= i
      T    = (I + Diag(beta) A)^-1 Diag(beta)             forward substitution
      U    = T (V - (K . exp(G)) S)
      O    = (Q . exp(G)) S + B U
      S   <- Diag(exp(G_C)) S + (K . exp(G_C - G))^T U

  all matrix products but T's triangular solve. **The decays are per
  channel and can be e^-16 a token**, so the pairwise factor
  exp(G_i - G_j) is never split into exp(G_i) times exp(-G_j) over a
  chunk (the second overflows float32 after six such tokens). A chunk
  is cut into sub-blocks of `SUB` rows: for j in an EARLIER sub-block
  the factor is split at the row block's first position r,
  exp(G_i - G_r) exp(G_r - G_j), both at most 1; inside a sub-block the
  factor is computed pair by pair. No clamp, no dropped term.
* `kda_decode_step`: one token for B rows whose states live in a pool
  addressed by slot: gather, decay, delta update, output, write back.
  A Pallas kernel (`name=KERNEL_NAME`, so that it reaches the trace's
  `XLA Ops`) that reads each row's state once and writes it once, in
  place; `kda_decode_step_reference` is the `jax.numpy` composition.

Shapes: q, k, g [.., H, K]; v [.., H, V]; beta [.., H]; states
[.., H, K, V] float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import _common
from ._common import I0 as _I0, pltpu

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
CHUNK = 64          # positions a chunk
SUB = 16            # rows a sub-block (the decays' local reference point)
KERNEL_NAME = "kda_decode_step"
HEADS_PER_CELL = 32  # heads of one row a grid cell of the decode kernel


# ------------------------------------------------------------ recurrence


def kda_recurrence(q, k, v, g, beta, s0=None):
    """The definition. q, k, g [T, H, K]; v [T, H, V]; beta [T, H]; s0
    [H, K, V] or None (zeros) -> (o [T, H, V] float32, S [H, K, V])."""
    H, K, V = q.shape[1], q.shape[2], v.shape[2]
    s0 = jnp.zeros((H, K, V), F32) if s0 is None else s0.astype(F32)

    def one(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, :, None]
        pred = jnp.einsum("hkv,hk->hv", S, kt, precision=HI)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)

    S, o = jax.lax.scan(one, s0, tuple(
        a.astype(F32) for a in (q, k, v, g, beta)))
    return o, S


# ------------------------------------------------------------ chunk form


def _pairwise(rows, cols, G, sub):
    """sum_c rows_i[c] cols_j[c] exp(G_i[c] - G_j[c]) for every j <= i of
    a chunk, 0 for j > i: [N, H, C, C] from [N, H, C, K] operands. The
    decay factor never exceeds 1: split at the row sub-block's first
    position for columns of earlier sub-blocks, pair by pair inside a
    sub-block."""
    N, H, C, K = rows.shape
    nb = C // sub
    Gs = G.reshape(N, H, nb, sub, K)
    ref = Gs[:, :, :, :1, :]                            # [N,H,nb,1,K]
    r = rows.reshape(N, H, nb, sub, K) * jnp.exp(Gs - ref)
    # columns of earlier sub-blocks, as each row block sees them
    before = (jnp.arange(C)[None, :]
              < (jnp.arange(nb) * sub)[:, None])        # [nb, C]
    expo = jnp.where(before[None, None, :, :, None],
                     ref - G[:, :, None, :, :], -jnp.inf)
    c = cols[:, :, None, :, :] * jnp.exp(expo)          # [N,H,nb,C,K]
    off = jnp.einsum("nhbik,nhbjk->nhbij", r, c,
                     precision=HI).reshape(N, H, C, C)
    # inside a sub-block: pair by pair, j <= i
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    expo = jnp.where(lower[None, None, None, :, :, None],
                     Gs[:, :, :, :, None, :] - Gs[:, :, :, None, :, :],
                     -jnp.inf)
    cs = cols.reshape(N, H, nb, sub, K)
    diag = jnp.sum(rows.reshape(N, H, nb, sub, 1, K) * cs[:, :, :, None]
                   * jnp.exp(expo), axis=-1)            # [N,H,nb,sub,sub]
    eye = jnp.eye(nb, dtype=F32)
    diag = jnp.einsum("nhbij,bc->nhbicj", diag, eye).reshape(N, H, C, C)
    return off + diag


def kda_chunk_prefill(q, k, v, g, beta, s0=None, chunk=CHUNK, sub=SUB):
    """The chunk form: same arguments and results as `kda_recurrence`.
    T is padded up to whole chunks with positions that change nothing
    (g = 0, beta = 0)."""
    T, H, K = q.shape
    V = v.shape[2]
    s0 = jnp.zeros((H, K, V), F32) if s0 is None else s0.astype(F32)
    N = -(-T // chunk)
    pad = N * chunk - T

    def chunks(a):          # [T, H, ..] -> [N, H, C, ..]
        a = jnp.pad(a.astype(F32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        a = a.reshape((N, chunk) + a.shape[1:])
        return jnp.moveaxis(a, 1, 2)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta)                                  # [N, H, C]
    G = jnp.cumsum(g, axis=2)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    A = jnp.where(strict, _pairwise(k, k, G, sub), 0.0)
    Bm = _pairwise(q, k, G, sub)
    M = jnp.eye(chunk, dtype=F32) + beta[..., None] * A
    Tm = jax.scipy.linalg.solve_triangular(
        M, beta[..., None] * jnp.eye(chunk, dtype=F32), lower=True,
        unit_diagonal=True)                              # [N,H,C,C]
    decay = jnp.exp(G)                                   # from chunk start
    kt, qt = k * decay, q * decay
    g_end = G[:, :, -1:, :]                              # [N,H,1,K]
    kd = k * jnp.exp(g_end - G)                          # to chunk end

    def one(S, x):
        kt_n, qt_n, v_n, T_n, B_n, kd_n, ge_n = x
        rhs = v_n - jnp.einsum("hck,hkv->hcv", kt_n, S, precision=HI)
        U = jnp.einsum("hij,hjv->hiv", T_n, rhs, precision=HI)
        o = jnp.einsum("hck,hkv->hcv", qt_n, S, precision=HI) \
            + jnp.einsum("hij,hjv->hiv", B_n, U, precision=HI)
        S = S * jnp.exp(ge_n)[:, 0, :, None] \
            + jnp.einsum("hck,hcv->hkv", kd_n, U, precision=HI)
        return S, o

    S, o = jax.lax.scan(one, s0, (kt, qt, v, Tm, Bm, kd, g_end))
    o = jnp.moveaxis(o, 1, 2).reshape(N * chunk, H, V)
    return o[:T], S


# ----------------------------------------------------------- decode step


def kda_decode_step_reference(q, k, v, g, beta, state, slots):
    """jnp composition: q, k, g [B, H, K]; v [B, H, V]; beta [B, H];
    state [S, H, K, V] float32 (or bfloat16: the control); slots [B]
    -> (o [B, H, V] float32, state). Rows that share a slot (padding,
    on the null slot) leave whichever wrote last."""
    S = state[slots].astype(F32) * jnp.exp(g.astype(F32))[..., None]
    k, q, v = k.astype(F32), q.astype(F32), v.astype(F32)
    pred = jnp.sum(S * k[..., None], axis=2)
    S = S + k[..., None] * (beta.astype(F32)[..., None]
                            * (v - pred))[:, :, None, :]
    S = S.astype(state.dtype)
    o = jnp.sum(S.astype(F32) * q[..., None], axis=2)
    return o, state.at[slots].set(S)


def _step_kernel(slot_ref, qT_ref, kT_ref, aT_ref, v_ref, b_ref, s_ref,
                 o_ref, so_ref, *, hb):
    del slot_ref
    for h in range(hb):
        a = aT_ref[0, 0, :, h:h + 1]                    # [K, 1]
        kc = kT_ref[0, 0, :, h:h + 1]
        qc = qT_ref[0, 0, :, h:h + 1]
        S = s_ref[0, h].astype(jnp.float32) * a         # [K, V]
        pred = jnp.sum(S * kc, axis=0, keepdims=True)   # [1, V]
        u = b_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - pred)
        S = (S + kc * u).astype(so_ref.dtype)
        so_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S.astype(jnp.float32) * qc, axis=0,
                                       keepdims=True)


def _pallas_step(q, k, v, g, beta, state, slots):
    B, H, K = q.shape
    V = v.shape[2]
    hb = HEADS_PER_CELL if H % HEADS_PER_CELL == 0 else H
    nb = H // hb

    def cols(a):            # [B, H, K] -> [B, nb, K, hb]: a head a lane
        return jnp.swapaxes(a.astype(F32).reshape(B, nb, hb, K), 2, 3)

    col = pl.BlockSpec((1, 1, K, hb), lambda b, j, sl: (b, j, _I0, _I0))
    row = pl.BlockSpec((1, hb, V), lambda b, j, sl: (b, j, _I0))
    st = pl.BlockSpec((1, hb, K, V), lambda b, j, sl: (sl[b], j, _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, nb),
        in_specs=[col, col, col, row, row, st],
        out_specs=[row, st])
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        name=KERNEL_NAME,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, V), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state pool is updated where it lies (operand 6, counting
        # the prefetched slots, is result 1)
        input_output_aliases={6: 1},
        interpret=_common.interpret(),
        **_common.compiler_params("arbitrary", "arbitrary"),
    )(slots.astype(jnp.int32), cols(q), cols(k), cols(jnp.exp(g)),
      v.astype(F32),
      jnp.broadcast_to(beta.astype(F32)[..., None], (B, H, V)), state)
    return o, state


def kda_decode_step(q, k, v, g, beta, state, slots, kernel=None):
    """One token for B rows against the state pool: the Pallas kernel
    on a TPU and the reference off it (the interpreter is for tests),
    unless `kernel` ("pallas" | "xla") says."""
    choice = kernel or ("pallas" if _common.on_tpu() else "xla")
    if choice == "pallas":
        return _pallas_step(q, k, v, g, beta, state, slots)
    if choice == "xla":
        return kda_decode_step_reference(q, k, v, g, beta, state, slots)
    raise ValueError(f"kernel={choice!r}: expected 'pallas' or 'xla'")
