"""Mixture-of-Experts layer with expert parallelism over the 'ep' axis.

The reference snapshot has NO MoE/expert parallelism (SURVEY.md §2
parallelism census: EP absent) — this is a new TPU-native component.

Design: GSPMD-style einsum dispatch (the Mesh-TensorFlow/Switch
formulation). Tokens pick experts by gate logits; a capacity-bounded
dispatch one-hot [tokens, E, C] routes token vectors into per-expert
batches with two einsums. Expert weights are stacked [E, ...] and
sharded P('ep', ...): under jit, XLA partitions the expert dimension and
inserts the all-to-alls — no hand-written collectives, the same
compiler-owned pattern as the rest of the framework. Tokens over
capacity are dropped (standard Switch behavior); an auxiliary
load-balancing loss (Switch-style) is accumulated on the layer.

Routing math is exact w.r.t. the dense equivalent when capacity is
ample, which is what the tests pin.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.tensor import apply
from ..initializer import Normal
from .layers import Layer

__all__ = ["MoELayer", "RoutedExperts", "collect_aux_losses",
           "routed_experts", "route_sigmoid_grouped"]

# trace-local collector: GPT.loss (or any training loss) opens this scope
# so every MoE layer's load-balance loss from the CURRENT forward is
# gathered and added to the objective — storing tracers on the layer
# across steps would leak them
_aux_collector = [None]


class collect_aux_losses:
    """with collect_aux_losses() as aux: ...forward...; then sum(aux)."""

    def __enter__(self):
        self._prev = _aux_collector[0]
        _aux_collector[0] = []
        return _aux_collector[0]

    def __exit__(self, *exc):
        _aux_collector[0] = self._prev
        return False


class MoELayer(Layer):
    """Top-k routed FFN experts: y = sum_k gate_k * expert_k(x).

    Input [B, T, M] -> output [B, T, M]. Experts are position-wise FFNs
    (M -> hidden -> M, gelu), weights stacked on a leading E dim.
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=2.0, name=None):
        super().__init__()
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.d_model = d_model
        self.d_hidden = d_hidden
        init = Normal(0.0, 0.02)
        E = self.num_experts
        self.gate_w = self.create_parameter(
            [d_model, E], default_initializer=init)
        self.w_in = self.create_parameter(
            [E, d_model, d_hidden], default_initializer=init)
        self.b_in = self.create_parameter(
            [E, d_hidden], is_bias=True)
        self.w_out = self.create_parameter(
            [E, d_hidden, d_model], default_initializer=init)
        self.b_out = self.create_parameter(
            [E, d_model], is_bias=True)
        self.aux_loss = None   # set on every forward (load-balance loss)

    # -- strategy-compiler protocol: expert dim rides 'ep' -----------------
    def param_shardings(self, params, mesh_axis_tp="tp", mesh_axis_ep="ep"):
        from jax.sharding import PartitionSpec as P
        specs = {}
        for name, v in params.items():
            nd = len(v.shape)
            if any(name.endswith(s) for s in
                   ("w_in", "b_in", "w_out", "b_out")):
                specs[name] = P(mesh_axis_ep, *([None] * (nd - 1)))
            else:
                specs[name] = P(*([None] * nd))
        return specs

    def forward(self, x):
        E, K = self.num_experts, self.top_k
        M, H = self.d_model, self.d_hidden
        cap_f = self.capacity_factor

        def f(xa, gw, wi, bi, wo, bo):
            B, T, _ = xa.shape
            N = B * T
            C = max(int(math.ceil(cap_f * N * K / E)), 1)
            xt = xa.reshape(N, M)
            logits = (xt @ gw).astype(jnp.float32)          # [N, E]
            probs = jax.nn.softmax(logits, axis=-1)

            # top-k routing with capacity: process the k-th choices in
            # sequence so positions accumulate per expert
            gates_list, onehot_list = [], []
            masked = probs
            for _ in range(K):
                idx = masked.argmax(axis=-1)                # [N]
                oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)
                gates_list.append((probs * oh).sum(-1))     # [N]
                onehot_list.append(oh)
                masked = masked * (1.0 - oh)

            # positions within each expert's capacity, counted across the
            # flattened (k, token) order
            flat_oh = jnp.concatenate(onehot_list, 0)       # [K*N, E]
            pos = jnp.cumsum(flat_oh, axis=0) - flat_oh     # [K*N, E]
            keep = (pos < C) * flat_oh                      # drop overflow
            pos_id = (pos * flat_oh).sum(-1).astype(jnp.int32)   # [K*N]
            cap_oh = jax.nn.one_hot(pos_id, C, dtype=jnp.float32)

            gates = jnp.concatenate(gates_list, 0)          # [K*N]
            # dispatch/combine tensors [K*N, E, C]
            dispatch = keep[:, :, None] * cap_oh[:, None, :]
            combine = dispatch * gates[:, None, None]

            xrep = jnp.tile(xt, (K, 1))                     # [K*N, M]
            expert_in = jnp.einsum("nec,nm->ecm", dispatch,
                                   xrep.astype(jnp.float32))
            h = jnp.einsum("ecm,emh->ech", expert_in,
                           wi.astype(jnp.float32)) + bi[:, None, :]
            h = jax.nn.gelu(h)
            eout = jnp.einsum("ech,ehm->ecm", h,
                              wo.astype(jnp.float32)) + bo[:, None, :]
            y = jnp.einsum("nec,ecm->nm", combine, eout)    # [K*N, M]
            y = y.reshape(K, N, M).sum(0)

            # Switch aux loss: E * sum_e frac_tokens_e * mean_prob_e
            frac = onehot_list[0].mean(0)
            mean_p = probs.mean(0)
            aux = (frac * mean_p).sum() * E
            return y.reshape(B, T, M).astype(xa.dtype), aux

        out, aux = apply(f, x, self.gate_w, self.w_in, self.b_in,
                         self.w_out, self.b_out, op_name="moe")
        if _aux_collector[0] is not None:
            _aux_collector[0].append(aux)
        import jax.core as _core
        if not isinstance(aux._data, _core.Tracer):
            self.aux_loss = aux   # eager convenience; never store tracers
        return out


# ---------------------------------------------------------------------------
# Dropless routed experts (the DeepSeek-V2/V3 family's expert layer)
# ---------------------------------------------------------------------------
#
# Sigmoid scores over all `n_routed` experts, group-limited top-k, and a
# layer that is TOLD which experts it holds: `held = (first, count)`. It
# routes over all of them and computes the part of the result its own
# experts give; assignments to experts held elsewhere are left out (on a
# one-chip share nothing stands in for the absent chips or their
# exchange). No capacity, no dropped token. Which of two exact paths
# runs follows from the static token count:
#
# * few tokens (a decode step): every held expert over every row under
#   a zero/non-zero combine weight (`_held_dense`).
# * many tokens (a prefill): the assignments are sorted by expert once
#   a call, those that are held here and live first (`_held_grouped`).
#   That held prefix, and nothing after it, is walked in blocks of
#   `_block_rows` sorted rows under a trip count of ceil(held rows /
#   block): gather the block's rows, one grouped product for gate and
#   up with SwiGLU, one for down that adds each row, times its combine
#   weight in float32, onto its token (`ops/pallas/grouped_matmul.py`:
#   on a TPU Pallas kernels whose grid ends at the last tile a group
#   touches and that read an expert's weights once a block; off it
#   XLA's `ragged_dot` and a scatter). The work follows the held rows
#   (all of them in the worst case: no row is ever dropped), the
#   temporaries the block.

DENSE_MAX_TOKENS = 256      # at or under this many tokens: the dense path
BLOCK_ROWS = 16384          # the most sorted rows a block of the grouped path


def route_sigmoid_grouped(x, router_w, *, top_k, n_group=1, topk_group=1,
                          norm_topk_prob=True, scale=1.0, select_bias=None):
    """(idx [N, K] int32 over all routed experts, w [N, K] float32).

    s = sigmoid(x W_r) in float32 (six-pass matmul: the router is small
    and its picks decide everything after it). With `n_group` > 1 the
    experts form `n_group` equal groups, a group scores the sum of its
    two best s, only the `topk_group` best groups stay eligible; then
    the `top_k` best s among the eligible. w = s of the picked, divided
    by their sum under `norm_topk_prob`, times `scale`. With
    `select_bias` [E] (DeepSeek-V3's `e_score_correction_bias`) groups
    and picks are chosen on s + bias; the weights stay the picked s."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    N, E = s.shape
    pick_from = s if select_bias is None \
        else s + select_bias.astype(jnp.float32)
    if n_group > 1:
        g = pick_from.reshape(N, n_group, E // n_group)
        group_score = jax.lax.top_k(g, 2)[0].sum(-1)            # [N, G]
        _, best = jax.lax.top_k(group_score, topk_group)        # [N, g]
        keep = jnp.any(best[:, :, None]
                       == jnp.arange(n_group, dtype=best.dtype), axis=1)
        # an ineligible score is 0, the floor of a sigmoid; a biased one
        # may lie below 0
        pick_from = jnp.where(keep[:, :, None], g,
                              0.0 if select_bias is None
                              else -jnp.inf).reshape(N, E)
    _, idx = jax.lax.top_k(pick_from, top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * jnp.float32(scale)


def _swiglu_f32(g, u):
    return jax.nn.silu(g) * u


def _held_dense(x, local, held, w, wg, wu, wd):
    """Every held expert over every row, combined under a weight that is
    zero where the row was not routed to it."""
    count = wg.shape[0]
    hit = (local[..., None] == jnp.arange(count, dtype=local.dtype)) \
        & held[..., None]                                       # [N, K, c]
    cw = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)     # [N, c]
    f32 = jnp.float32
    g = jnp.einsum("nh,ehf->enf", x, wg, preferred_element_type=f32)
    u = jnp.einsum("nh,ehf->enf", x, wu, preferred_element_type=f32)
    a = (_swiglu_f32(g, u) * cw.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("enf,efh->nh", a, wd, preferred_element_type=f32)


def _block_rows(A, count, n_routed):
    """Sorted rows a block of the grouped path: twice what even routing
    would hold here of A assignments, in whole row tiles, at most
    `BLOCK_ROWS`. (At half of the experts held that is every assignment:
    one block, and no loop around it.)"""
    rows = min(A, 2 * A * count // n_routed)
    return min(BLOCK_ROWS, -(-rows // 512) * 512)


def _held_grouped(x, local, held, w, wg, wu, wd, n_routed):
    """The held assignments sorted by expert, walked block by block:
    grouped products over the block's rows, each row's result times its
    combine weight (in float32) added onto its token."""
    from ...ops.pallas import grouped_matmul as gm
    N, K = local.shape
    count = wg.shape[0]
    A = N * K
    rows = _block_rows(A, count, n_routed)
    tm = gm.row_tile(rows, count)
    key = jnp.where(held, local, count).reshape(-1)             # [A]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, -(-A // rows) * rows - A))
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=key.dtype),
                    axis=0, dtype=jnp.int32)                    # [count]
    ends = jnp.cumsum(sizes)
    total = ends[-1]
    wflat = w.reshape(-1)

    def block(b, y):
        start = b * rows
        pick = jax.lax.dynamic_slice(order, (start,), (rows,))
        token = pick // K
        here = jnp.clip(ends - start, 0, rows) \
            - jnp.clip(ends - sizes - start, 0, rows)           # [count]
        a = gm.grouped_swiglu(x[token], wg, wu, here, tm=tm)
        # rows past the last group belong to no expert: whatever the
        # first product left there is never multiplied, never added
        return gm.grouped_matmul_add(a, wd, here, token, wflat[pick], y,
                                     tm=tm)

    # (whole sublane tiles of tokens: the kernel adds onto rows in VMEM)
    y = jnp.zeros((-(-N // 8) * 8, x.shape[1]), jnp.float32)
    if A <= rows:       # one block at most: with no held row it adds nothing
        y = block(0, y)
    else:
        y = jax.lax.fori_loop(0, (total + rows - 1) // rows, block, y)
    return y if y.shape[0] == N else y[:N]


def routed_experts(x, router_w, wg, wu, wd, *, top_k, n_group=1,
                   topk_group=1, norm_topk_prob=True, scale=1.0,
                   held=None, live=None, select_bias=None):
    """The routed sum of one expert layer over the experts held here.

    x [N, H]; router_w [H, n_routed]; wg, wu [count, H, F]; wd
    [count, F, H]: the held experts' SwiGLU weights, expert `first + i`
    at index i. `held = (first, count)`, default all. `live` [N] bool:
    rows that are padding are neither computed nor counted.
    `select_bias` [n_routed]: the picks are the best of score + bias
    (`route_sigmoid_grouped`).

    Returns (y [N, H] float32, hits [count] int32: live assignments per
    held expert)."""
    n_routed = router_w.shape[1]
    first, count = held if held is not None else (0, n_routed)
    if wg.shape[0] != count:
        raise ValueError(f"routed_experts: {wg.shape[0]} expert weights "
                         f"for held={held}")
    idx, w = route_sigmoid_grouped(
        x, router_w, top_k=top_k, n_group=n_group, topk_group=topk_group,
        norm_topk_prob=norm_topk_prob, scale=scale,
        select_bias=select_bias)
    local = idx - jnp.int32(first)
    mine = (local >= 0) & (local < count)
    if live is not None:
        mine = mine & live[:, None]
    hits = jnp.sum((local[..., None] == jnp.arange(count, dtype=jnp.int32))
                   & mine[..., None], axis=(0, 1), dtype=jnp.int32)
    N = x.shape[0]
    if N <= DENSE_MAX_TOKENS:
        return _held_dense(x, local, mine, w, wg, wu, wd), hits
    return _held_grouped(x, local, mine, w, wg, wu, wd, n_routed), hits


class RoutedExperts(Layer):
    """Dropless routed SwiGLU experts as a layer of the framework:
    y = scale * sum over the top-k picks held here of w_e Expert_e(x).

    `held = (first, count)` of `n_routed` says which experts this layer
    holds (default all): the router keeps `n_routed` outputs, the expert
    weights are stacked [count, ...]. Input [..., d_model] -> output of
    the same shape. The shared expert of a DeepSeek-style block, which
    every chip computes alike, is not part of this layer. With
    `select_bias` the layer has a parameter `e_score_correction_bias`
    [n_routed] that the selection adds to the scores."""

    def __init__(self, d_model, d_hidden, n_routed, top_k, n_group=1,
                 topk_group=1, norm_topk_prob=True,
                 routed_scaling_factor=1.0, held=None, dtype=None,
                 select_bias=False):
        super().__init__()
        first, count = held if held is not None else (0, int(n_routed))
        if first < 0 or count < 1 or first + count > n_routed:
            raise ValueError(f"held={held} of {n_routed} routed experts")
        if n_routed % n_group:
            raise ValueError(f"{n_routed} experts in {n_group} groups")
        self.held = (int(first), int(count))
        self.routing = dict(top_k=int(top_k), n_group=int(n_group),
                            topk_group=int(topk_group),
                            norm_topk_prob=bool(norm_topk_prob),
                            scale=float(routed_scaling_factor))
        init = Normal(0.0, 0.02)
        self.router = self.create_parameter(
            [d_model, n_routed], dtype=dtype, default_initializer=init)
        self.gate_proj = self.create_parameter(
            [count, d_model, d_hidden], dtype=dtype,
            default_initializer=init)
        self.up_proj = self.create_parameter(
            [count, d_model, d_hidden], dtype=dtype,
            default_initializer=init)
        self.down_proj = self.create_parameter(
            [count, d_hidden, d_model], dtype=dtype,
            default_initializer=init)
        if select_bias:
            self.e_score_correction_bias = self.create_parameter(
                [n_routed], dtype="float32", default_initializer=init)

    def forward(self, x):
        def f(xa, rw, wg, wu, wd, *bias):
            y, _ = routed_experts(xa.reshape(-1, xa.shape[-1]), rw, wg, wu,
                                  wd, held=self.held, **self.routing,
                                  select_bias=bias[0] if bias else None)
            return y.reshape(xa.shape).astype(xa.dtype)

        bias = getattr(self, "e_score_correction_bias", None)
        bias = () if bias is None else (bias,)
        return apply(f, x, self.router, self.gate_proj, self.up_proj,
                     self.down_proj, *bias, op_name="routed_experts")
