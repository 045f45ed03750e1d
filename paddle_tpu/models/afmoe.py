"""AFMoE (`model_type: afmoe`, Arcee Trinity): a decoder whose layers
attend either to the last `sliding_window` positions, with rotary
positions, or to everything, with none (`layer_types`, three of the
first to one of the second); grouped-query heads with a per-head
RMSNorm of queries and keys and a sigmoid gate on the attention
output; four norms a layer (sandwich); `num_dense_layers` leading
dense SwiGLU layers, then sigmoid-routed experts with a bias-corrected
selection beside a shared expert.

Config keys are the source's (`config.json` of
arcee-ai/Trinity-Large-Preview). Equations, h [T, hidden]:
``h = E[ids] * sqrt(hidden)`` (`mup_enabled`); per layer

* ``a = RMSNorm_in(h)``; ``q = a W_q`` [T, Hq, D], ``k = a W_k``,
  ``v = a W_v`` [T, Hkv, D], ``g = a W_g`` [T, Hq * D]; ``q =
  RMSNorm_q(q)``, ``k = RMSNorm_k(k)`` over each head's D (gains [D]);
  on a `sliding_attention` layer rotary on q and k (the whole D,
  rotate-half pairing, `rope_theta`), on a `full_attention` layer none.
  Query head j reads K/V head ``j // (Hq // Hkv)``; scores ``q k^T /
  sqrt(D)``, causal, and on a sliding layer key s is visible from query
  t only if ``t - s < sliding_window``; float32 softmax; ``o = (softmax
  v) * sigmoid(g)``; ``h = h + RMSNorm_post_attn(o W_o)``.
* ``m = RMSNorm_pre_mlp(h)``; ``f`` = SwiGLU of `intermediate_size`
  under `num_dense_layers`, else ``Shared(m) + routed(m)``
  (`nn.layer.moe.routed_experts`: sigmoid scores over `num_experts`,
  `num_experts_per_tok` picks on score + bias, the picked scores
  renormalised under `route_norm`, times `route_scale`); ``h = h +
  RMSNorm_post_mlp(f)``.
* ``logits = RMSNorm(h) W_head`` (untied); no bias anywhere.

**The cache is of two classes.** A full layer keeps every position: K
and V pages, addressed through the engine's block table. A window
layer keeps a RING of `sliding_window` rows a stream, K and V, the row
of position p at ``p mod sliding_window``: the rotation is applied
before a key is stored and a softmax does not care for order, so the
ring is read as it lies and never unrolled. The ring lives BY SLOT
(`inference.model_kinds`' contract for slot state): an admission
overwrites it, a step advances it in place, a finish abandons it.

**The share.** `held_experts = (first, count)` and `vocab_size` as
`models.axk1` has them. A cut in depth holds some of the published
layers: `layer_types` then lists the held layers' types and
`num_dense_layers` counts the dense ones among them.

Parameters, activations and cached rows are `dtype` (bfloat16 as
served): products take operands of that type and accumulate in
float32; norms, rotary, router, softmax and the gate are float32;
logits are float32.

Serving goes through `inference.decode.DecodeEngine` (the model kind
`afmoe` of `inference.model_kinds`); `afmoe_paged_fns` below are the
pure step and prefill it dispatches.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer.moe import RoutedExperts
from .axk1 import (F32, _count, _lo, _mm, _Weights, ffn, layer_params,
                   rms_norm)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    # one of SLIDING / FULL a layer held; None: the period above
    layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # positions the server sizes a slot for (an operator's max-model-len;
    # the source's is 262144)
    max_position_embeddings: int = 262144
    # the chip's share: experts [first, first + count) of every layer
    held_experts: Tuple[int, int] = (0, 256)
    dtype: str = "bfloat16"
    # None as served; a type name rounds the normed activations that
    # enter each layer's projections through it (the benchmark's control)
    operand_dtype: Optional[str] = None

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            n = self.global_attn_every_n_layers
            types = [FULL if (i + 1) % n == 0 else SLIDING
                     for i in range(self.num_hidden_layers)]
        object.__setattr__(self, "layer_types", tuple(str(t) for t in types))
        object.__setattr__(self, "held_experts",
                           tuple(int(v) for v in self.held_experts))
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"AfmoeConfig: layer_types names {SLIDING!r} "
                             f"or {FULL!r} for each of "
                             f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("AfmoeConfig: query heads in whole groups "
                             "over the key/value heads")

    # ---- what the shared FFN function of models.axk1 reads
    first_k_dense_replace = property(lambda self: self.num_dense_layers)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    norm_topk_prob = property(lambda self: self.route_norm)
    routed_scaling_factor = property(lambda self: self.route_scale)
    max_seq_len = property(lambda self: self.max_position_embeddings)

    @property
    def moe_layers(self):
        return max(self.num_hidden_layers - self.num_dense_layers, 0)

    @property
    def kv_width(self):
        """Lanes of one cached K (or V) row: the K/V heads side by side."""
        return self.num_key_value_heads * self.head_dim

    def is_window(self, i):
        return self.layer_types[i] == SLIDING

    @property
    def window_index(self):
        """Layer -> its place among the window layers held."""
        held = [i for i in range(self.num_hidden_layers) if self.is_window(i)]
        return {i: n for n, i in enumerate(held)}

    @property
    def full_index(self):
        held = [i for i in range(self.num_hidden_layers)
                if not self.is_window(i)]
        return {i: n for n, i in enumerate(held)}

    @property
    def ring_slot_bytes(self):
        """Bytes one slot's rings take, K and V, all window layers held."""
        return len(self.window_index) * 2 * self.sliding_window \
            * self.kv_width * jnp.dtype(self.dtype).itemsize


def afmoe_tiny(**kw):
    """A CPU-test preset: every mechanism, toy widths; one dense layer
    and one period of the pattern (window, window, full, window,
    window), a window of 8."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_dense_layers=1, num_attention_heads=6,
                num_key_value_heads=2, head_dim=16, sliding_window=8,
                layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
                num_experts=16, num_experts_per_tok=4,
                max_position_embeddings=128, held_experts=(0, 16),
                dtype="float32")
    base.update(kw)
    return AfmoeConfig(**base)


# -------------------------------------------------- pure building blocks


def rope_half(x, positions, theta):
    """Rotary over the whole last axis of x [..., H, D] at int positions
    [...], rotate-half pairing (i with i + D/2), float32."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[..., None, None] * inv       # [.., 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(F32)
    a, b = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def qkv_gate(cfg, lp, a, positions, window):
    """(q [.., Hq, D], k, v [.., Hkv, D], gate [.., Hq * D] float32) of
    the normed tokens a [.., hidden]: per-head norms of q and k, and on
    a `window` layer rotary at `positions`."""
    Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    eps = cfg.rms_norm_eps
    lead = a.shape[:-1]
    q = rms_norm(_mm(a, lp["self_attn.q_proj"]).reshape(lead + (Hq, D)),
                 lp["self_attn.q_norm"], eps)
    k = rms_norm(_mm(a, lp["self_attn.k_proj"]).reshape(lead + (Hkv, D)),
                 lp["self_attn.k_norm"], eps)
    v = _mm(a, lp["self_attn.v_proj"]).reshape(lead + (Hkv, D))
    if window:
        q = rope_half(q, positions, cfg.rope_theta)
        k = rope_half(k, positions, cfg.rope_theta)
    gate = jax.nn.sigmoid(jnp.dot(a, lp["self_attn.gate_proj"],
                                  preferred_element_type=F32))
    return q, k, v, gate


def attend_sequence(cfg, q, k, v, window, flash=False):
    """Causal attention of one sequence, q [T, Hq, D], k, v [T, Hkv, D]
    -> [T, Hq * D]; `window`: the layer sees the last `sliding_window`
    positions only."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    band = cfg.sliding_window if window else None
    if flash:
        from ..ops.pallas.flash_attention import flash_attention_forward
        o = flash_attention_forward(q[None], k[None], v[None], causal=True,
                                    scale=scale, window=band)[0]
        return o.reshape(T, Hq * D)
    qg = q.reshape(T, Hkv, Hq // Hkv, D)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k,
                   preferred_element_type=F32) * F32(scale)
    t = jnp.arange(T, dtype=jnp.int32)
    seen = t[:, None] >= t[None, :]
    if band is not None:
        seen = seen & (t[:, None] - t[None, :] < band)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                   preferred_element_type=F32).astype(v.dtype)
    return o.reshape(T, Hq * D)


def attn_output(lp, o, gate):
    """The gated heads through W_o: o [.., Hq * D], gate float32."""
    return _mm((o.astype(F32) * gate).astype(o.dtype),
               lp["self_attn.o_proj"])


def embed(cfg, params, ids):
    x = params["embed_tokens"][ids]
    if cfg.mup_enabled:
        x = (x.astype(F32) * F32(math.sqrt(cfg.hidden_size))).astype(x.dtype)
    return x


def mlp_residual(cfg, lp, i, x, live=None):
    """(x + RMSNorm_post_mlp(FFN_i(RMSNorm_pre_mlp(x))), hits)."""
    eps = cfg.rms_norm_eps
    m = _lo(cfg, rms_norm(x, lp["pre_mlp_layernorm"], eps))
    f, hit = ffn(cfg, lp, i, m, live)
    return x + rms_norm(f, lp["post_mlp_layernorm"], eps), hit


def head_logits(cfg, params, x):
    xf = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    return jnp.dot(xf, params["lm_head"], preferred_element_type=F32)


def afmoe_forward(cfg: AfmoeConfig, params, ids):
    """Logits [T, vocab] (float32) of one sequence of ids [T]: the plain
    full-sequence forward, no cache."""
    T = ids.shape[0]
    positions = jnp.arange(T, dtype=jnp.int32)
    eps = cfg.rms_norm_eps
    x = embed(cfg, params, ids)
    for i in range(cfg.num_hidden_layers):
        lp = layer_params(params, i)
        window = cfg.is_window(i)
        a = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
        q, k, v, gate = qkv_gate(cfg, lp, a, positions, window)
        o = attn_output(lp, attend_sequence(cfg, q, k, v, window), gate)
        x = x + rms_norm(o, lp["post_attention_layernorm"], eps)
        x = mlp_residual(cfg, lp, i, x)[0]
    return head_logits(cfg, params, x)


# ------------------------------------------------------------ the layer


class _Block(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, i: int):
        super().__init__()
        H, D = cfg.hidden_size, cfg.head_dim
        Hq, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        dt = cfg.dtype
        for name in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm"):
            setattr(self, name, self.create_parameter(
                [H], dtype=dt, default_initializer=Constant(1.0)))
        self.self_attn = _Weights(
            dt, q_proj=(H, Hq * D), k_proj=(H, Hkv * D), v_proj=(H, Hkv * D),
            gate_proj=(H, Hq * D), q_norm=(D,), k_norm=(D,),
            o_proj=(Hq * D, H))
        if i < cfg.num_dense_layers:
            F = cfg.intermediate_size
            self.mlp = _Weights(dt, gate_proj=(H, F), up_proj=(H, F),
                                down_proj=(F, H))
        else:
            F = cfg.moe_intermediate_size
            Fs = F * cfg.num_shared_experts
            self.mlp = nn.Layer()
            self.mlp.experts = RoutedExperts(
                H, F, cfg.num_experts, cfg.num_experts_per_tok,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                norm_topk_prob=cfg.route_norm,
                routed_scaling_factor=cfg.route_scale,
                held=cfg.held_experts, dtype=dt, select_bias=True)
            self.mlp.shared_experts = _Weights(
                dt, gate_proj=(H, Fs), up_proj=(H, Fs), down_proj=(Fs, H))


class Afmoe(nn.Layer):
    """The model as a layer of the framework (as `models.axk1.AXK1`):
    the constructor seeds every parameter; `forward(ids [T])` is the
    plain full-sequence forward."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        dt = cfg.dtype
        init = Normal(0.0, 0.02)
        self.embed_tokens = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=dt,
            default_initializer=init)
        self.layers = nn.LayerList(
            [_Block(cfg, i) for i in range(cfg.num_hidden_layers)])
        self.norm = self.create_parameter(
            [cfg.hidden_size], dtype=dt, default_initializer=Constant(1.0))
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=dt,
            default_initializer=init)

    def forward(self, ids):
        names, tensors = zip(*self.named_parameters())

        def f(ids_a, *arrays):
            return afmoe_forward(self.cfg, dict(zip(names, arrays)), ids_a)

        return apply(f, ids, *tensors, op_name="afmoe_forward")


# ---------------------------------------------------- the serving functions
#
# The pools of this model kind are one pytree the engine threads and
# donates whole. Two classes of cache lie in it side by side, every
# array `[pages, page_tokens, kv_width]` with the page axis at 0, so
# that ONE reader serves both:
#
#   "k", "v"            tuple over the FULL layers: pages addressed
#                       through the engine's block table (page 0 the
#                       null page), a row a position
#   "ring_k", "ring_v"  tuple over the WINDOW layers of
#                       [(slots + 1) * ring_pages, ..]: slot s owns
#                       pages `s * ring_pages ..`, its ring of
#                       `sliding_window` rows, the row of position p at
#                       `p mod sliding_window`; the last `ring_pages`
#                       are the null slot's, where padding rows land
#   "routed", "routed_tokens"  as `models.axk1`'s


def ring_pages(cfg: AfmoeConfig, page_tokens: int) -> int:
    if cfg.sliding_window % int(page_tokens):
        raise ValueError(f"afmoe: a ring of {cfg.sliding_window} rows is "
                         f"not whole pages of {page_tokens} tokens")
    return cfg.sliding_window // int(page_tokens)


def afmoe_pools_sds(cfg: AfmoeConfig, num_pages: int, page_tokens: int,
                    slots: int):
    dt = jnp.dtype(cfg.dtype)
    page = jax.ShapeDtypeStruct(
        (int(num_pages), int(page_tokens), cfg.kv_width), dt)
    ring = jax.ShapeDtypeStruct(
        ((int(slots) + 1) * ring_pages(cfg, page_tokens), int(page_tokens),
         cfg.kv_width), dt)
    n_full, n_win = len(cfg.full_index), len(cfg.window_index)
    return {"k": (page,) * n_full, "v": (page,) * n_full,
            "ring_k": (ring,) * n_win, "ring_v": (ring,) * n_win,
            "routed": jax.ShapeDtypeStruct(
                (cfg.moe_layers, cfg.held_experts[1]), jnp.int32),
            "routed_tokens": jax.ShapeDtypeStruct((), jnp.int32)}


def afmoe_paged_fns(cfg: AfmoeConfig, page_tokens: int,
                    prefill_name: str = "prefill"):
    """(paged_prefill, paged_step) over the pools above, each taking
    the SLOT (`inference.model_kinds`' contract for slot state):

    paged_prefill(params, pools, toks [1, R], tables [1, W], n [1], slot [])
        -> (logits [1, V] float32 at position n - 1, pools)
      full layers: rows at or past n are written as zeros, padding of
      the table aims at the null page. Window layers: the slot's ring is
      OVERWRITTEN whole with the last `sliding_window` positions before
      n (all of a shorter sequence; the rest zeros).
    paged_step(params, pools, tables [B, W], last_tok [B], cache_len [B],
               slots [B]) -> (logits [B, V] float32, pools)
      writes each row's new K and V rows (full: at tables[b, cache_len
      // pt]; window: at the slot's ring row cache_len mod window) and
      attends 0..cache_len, a window layer the `min(cache_len + 1,
      window)` rows of its ring. A padding row (cache_len 0, an
      all-null table, the null slot) touches the null page and the null
      slot's ring only."""
    from ..ops.pallas.gqa_attention import paged_gqa_decode_attention
    pt = int(page_tokens)
    L = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps
    win_at, full_at = cfg.window_index, cfg.full_index
    window, width = cfg.sliding_window, cfg.kv_width
    rp = ring_pages(cfg, pt)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    ring_page = jnp.arange(rp, dtype=jnp.int32)

    def paged_prefill(params, pools, toks, tables, n, slot):
        from ..ops.pallas import _common
        R, W = toks.shape[1], tables.shape[1]
        positions = jnp.arange(R, dtype=jnp.int32)
        live = positions < n[0]
        flash = _common.on_tpu() and R >= 256
        # ring row r holds the last position before n that is r mod window
        r = jnp.arange(window, dtype=jnp.int32)
        held = r + window * ((n[0] - 1 - r) // window)
        in_ring = (r < n[0])[:, None]
        held = jnp.clip(held, 0, R - 1)
        mine = slot.astype(jnp.int32) * rp + ring_page
        x = embed(cfg, params, toks[0])
        cache = {c: list(pools[c]) for c in ("k", "v", "ring_k", "ring_v")}
        hits = []
        for i in range(L):
            lp = layer_params(params, i)
            is_win = i in win_at
            a = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
            q, k, v, gate = qkv_gate(cfg, lp, a, positions, is_win)
            o = attn_output(lp, attend_sequence(cfg, q, k, v, is_win, flash),
                            gate)
            x = x + rms_norm(o, lp["post_attention_layernorm"], eps)
            for c, rows in (("k", k.reshape(R, width)),
                            ("v", v.reshape(R, width))):
                if is_win:
                    pool, j = cache["ring_" + c], win_at[i]
                    ring = jnp.where(in_ring, rows[held], 0)
                    pool[j] = pool[j].at[mine].set(
                        ring.reshape(rp, pt, width))
                else:
                    pool, j = cache[c], full_at[i]
                    pages = jnp.pad(jnp.where(live[:, None], rows, 0),
                                    ((0, W * pt - R), (0, 0)))
                    pool[j] = pool[j].at[tables[0]].set(
                        pages.reshape(W, pt, width))
            x, hit = mlp_residual(cfg, lp, i, x, live)
            if hit is not None:
                hits.append(hit)
        last = jnp.clip(n[0] - 1, 0, R - 1)
        logits = head_logits(
            cfg, params, jax.lax.dynamic_slice_in_dim(x, last, 1, axis=0))
        pools = dict(pools, **{c: tuple(a) for c, a in cache.items()})
        return logits, _count(pools, hits, jnp.sum(live))

    def paged_step(params, pools, tables, last_tok, cache_len, slots):
        W = tables.shape[1]
        pos = jnp.clip(cache_len.astype(jnp.int32), 0, cfg.max_seq_len - 1)
        live = cache_len > 0
        offset = pos % pt
        page_idx = jnp.take_along_axis(
            tables, jnp.minimum(pos // pt, W - 1)[:, None], axis=1)[:, 0]
        lengths = pos + 1                 # the row just written is live
        ring_tables = slots.astype(jnp.int32)[:, None] * rp + ring_page
        ring_idx = ring_tables[:, 0] + (pos % window) // pt
        ring_lengths = jnp.minimum(lengths, window)
        x = embed(cfg, params, last_tok)
        cache = {c: list(pools[c]) for c in ("k", "v", "ring_k", "ring_v")}
        hits = []
        for i in range(L):
            lp = layer_params(params, i)
            is_win = i in win_at
            a = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
            q, k, v, gate = qkv_gate(cfg, lp, a, pos, is_win)
            if is_win:
                j, kp, vp = win_at[i], cache["ring_k"], cache["ring_v"]
                at, tbl, lens = ring_idx, ring_tables, ring_lengths
            else:
                j, kp, vp = full_at[i], cache["k"], cache["v"]
                at, tbl, lens = page_idx, tables, lengths
            B = q.shape[0]
            kp[j] = kp[j].at[at, offset].set(k.reshape(B, width))
            vp[j] = vp[j].at[at, offset].set(v.reshape(B, width))
            o = paged_gqa_decode_attention(q, kp[j], vp[j], tbl, lens, scale)
            o = attn_output(lp, o.reshape(B, -1), gate)
            x = x + rms_norm(o, lp["post_attention_layernorm"], eps)
            x, hit = mlp_residual(cfg, lp, i, x, live)
            if hit is not None:
                hits.append(hit)
        logits = head_logits(cfg, params, x)
        pools = dict(pools, **{c: tuple(a) for c, a in cache.items()})
        return logits, _count(pools, hits, jnp.sum(live))

    paged_prefill.__name__ = paged_prefill.__qualname__ = prefill_name
    return paged_prefill, paged_step
