"""A.X-K1 (`model_type: axk1`): a DeepSeek-V2/V3-family decoder — latent
attention (MLA) with YaRN rotary positions, one leading dense SwiGLU
layer, then layers of sigmoid-routed experts beside a shared expert.

Config keys are the source's (`config.json` of skt/A.X-K1). Equations,
per layer l: ``x = x + MLA(RMSNorm(x))``; ``x = x + FFN_l(RMSNorm(x))``;
a final RMSNorm; an untied head; no biases.

* **MLA.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` in heads of
  ``[q_nope | q_rope]``. ``[c_kv | k_r] = x W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``k_r = RoPE(k_r)`` (one for all heads); ``q_rope =
  RoPE(q_rope)``. ``[k_nope | v] = c_kv W_kvb`` per head. Scores
  ``(q_nope . k_nope + q_rope . k_r) * s``, causal float32 softmax,
  ``out = concat_h(sum p v) W_o``. **The cache row is ``[c_kv | k_r]``**
  (after the norm, after the rotation): `kv_lora_rank +
  qk_rope_head_dim` values a token a layer, in ONE pool. Prefill runs
  the expanded form (flash attention with a value width of its own on a
  TPU) and writes those rows; the decode step runs the absorbed form
  over the pool (`ops.pallas.latent_attention`): ``q_abs = q_nope
  W_uk^T``, scores ``q_abs . c_kv + q_rope . k_r``, ``o = (sum p c_kv)
  W_uv``.
* **RoPE** is YaRN as DeepSeek-V3 computes it (`yarn_inv_freq`,
  `softmax_scale`). Pairs are (2i, 2i+1) of the projection's output, as
  the family stores them, written out de-interleaved (first of each
  pair | second), as its reference code does: q_rope and k_r alike, so
  every dot product is that of the interleaved form.
* **FFN.** Layer < `first_k_dense_replace`: SwiGLU of width
  `intermediate_size`. Else ``Shared(x) + routed(x)``, the routed sum by
  `nn.layer.moe.routed_experts` (sigmoid scores over `n_routed_experts`,
  `n_group` groups of which `topk_group` stay, `num_experts_per_tok`
  picks, normalised, times `routed_scaling_factor`). `topk_method:
  "none"` is read as "no bias-corrected selection".
* **The share.** `held_experts = (first, count)`: the experts this chip
  holds of each layer; the router keeps all its outputs and the layer
  leaves out what experts held elsewhere would add. `vocab_size` is the
  slice of the vocabulary held (embedding rows and head columns).

Parameters and cache are `dtype` (bfloat16 as served): matrix products
take operands of that type and accumulate in float32; norms, rotary,
router and softmax are float32; logits are float32.

Serving goes through `inference.decode.DecodeEngine` (the model kind
`axk1` of `inference.model_kinds`); `axk1_paged_fns` below are the pure
step and prefill-into-pages it dispatches. Training of the routed layers
is not implemented (ROADMAP R4).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer.moe import RoutedExperts, routed_experts

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class AXK1Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # rope_scaling (type "yarn"), flattened so the config stays hashable
    rope_factor: float = 32.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # positions the server sizes a slot for (an operator's max-model-len;
    # the source's 131072 is what the rotary scaling reaches)
    max_position_embeddings: int = 131072
    # the chip's share: experts [first, first + count) of every layer
    held_experts: Tuple[int, int] = (0, 192)
    dtype: str = "bfloat16"
    # None as served. A type name ("float8_e4m3fn") rounds the normed
    # activations that enter each layer's projections through that type:
    # the path one operand precision down, which the benchmark's control
    # runs to show that its tolerance would catch it.
    operand_dtype: Optional[str] = None

    def __post_init__(self):
        object.__setattr__(self, "held_experts",
                           tuple(int(v) for v in self.held_experts))

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def latent_width(self):
        """Values of one cached position of one layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_width(self):
        """`latent_width` rounded up to whole 128-lane tiles: what a
        pool row is allocated as. The chip's tiled layout pads the
        minor dimension to that anyway, and with a minor dimension that
        is NOT a multiple of 128 the compiler lays the pool out with the
        page's token axis minor-most, which the row scatter and the
        attention kernel then undo by copying the whole pool, twice a
        layer a step (seen in the compiled step, PR 28)."""
        return -(-self.latent_width // 128) * 128

    @property
    def moe_layers(self):
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)


def axk1_tiny(**kw):
    """A CPU-test preset: every mechanism, toy widths."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=16, num_experts_per_tok=4, n_group=4,
                topk_group=2, rope_factor=4.0,
                rope_original_max_position_embeddings=32,
                max_position_embeddings=128, held_experts=(0, 16),
                dtype="float32")
    base.update(kw)
    return AXK1Config(**base)


# ------------------------------------------------------------- rotary


def yarn_inv_freq(cfg: AXK1Config) -> np.ndarray:
    """[qk_rope_head_dim / 2] inverse frequencies, YaRN as DeepSeek-V3
    computes them: the plain ones (base ** (-2i/d)) where a dimension
    turns more than `beta_fast` times over the original context, those
    divided by `factor` where it turns fewer than `beta_slow` times, and
    a linear ramp between the two correction dimensions."""
    d = cfg.qk_rope_head_dim
    base = float(cfg.rope_theta)
    exps = np.arange(0, d, 2, dtype=np.float64) / d
    extra = 1.0 / base ** exps                      # extrapolation
    inter = extra / float(cfg.rope_factor)          # interpolation

    def correction_dim(rotations):
        return d * math.log(cfg.rope_original_max_position_embeddings
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                               # 1: extrapolate
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: AXK1Config) -> float:
    """(qk_nope + qk_rope) ** -0.5, times yarn_mscale(factor,
    mscale_all_dim) ** 2 (DeepSeek-V3's attention scale under YaRN)."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim:
        s *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return float(s)


def rope_cos_sin(cfg: AXK1Config, positions):
    """cos, sin [..., qk_rope_head_dim / 2] float32 at int positions,
    scaled by mscale / mscale_all_dim (1 for the published config)."""
    ang = positions.astype(F32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * F32(m), jnp.sin(ang) * F32(m)


def apply_rope(x, cos, sin):
    """Rotate the pairs (2i, 2i+1) of x [..., d]; the result holds the
    first of each pair in its first half and the second in its second."""
    x32 = x.astype(F32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = x32[..., 0], x32[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# -------------------------------------------------- pure building blocks


def rms_norm(x, w, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + F32(eps))
    return (y * w.astype(F32)).astype(x.dtype)


def _lo(cfg, x):
    if cfg.operand_dtype is None:
        return x
    return x.astype(jnp.dtype(cfg.operand_dtype)).astype(x.dtype)


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)


def swiglu(x, wg, wu, wd):
    g = jnp.dot(x, wg, preferred_element_type=F32)
    u = jnp.dot(x, wu, preferred_element_type=F32)
    return _mm((jax.nn.silu(g) * u).astype(x.dtype), wd)


def layer_params(params, i):
    pref = f"layers.{i}."
    return {k[len(pref):]: v for k, v in params.items()
            if k.startswith(pref)}


def mla_project(cfg, lp, x, positions):
    """(q_nope [..., H, dn], q_rope [..., H, dr], cache rows [..., C+dr])
    of the tokens x [..., hidden] at `positions` [...]. A config without
    a query low-rank (`q_lora_rank` None) projects q straight from x
    (`self_attn.q_proj`); `positions` None leaves the `dr`-wide parts
    unrotated (NoPE)."""
    nh = cfg.num_attention_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps = cfg.rms_norm_eps
    if cfg.q_lora_rank:
        c_q = rms_norm(_mm(x, lp["self_attn.q_a_proj"]),
                       lp["self_attn.q_a_layernorm"], eps)
        q = _mm(c_q, lp["self_attn.q_b_proj"])
    else:
        q = _mm(x, lp["self_attn.q_proj"])
    q = q.reshape(x.shape[:-1] + (nh, dn + dr))
    kv = _mm(x, lp["self_attn.kv_a_proj_with_mqa"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank],
                    lp["self_attn.kv_a_layernorm"], eps)
    if positions is None:
        k_r, q_rope = kv[..., cfg.kv_lora_rank:], q[..., dn:]
    else:
        cos, sin = rope_cos_sin(cfg, positions)
        k_r = apply_rope(kv[..., cfg.kv_lora_rank:], cos, sin)
        q_rope = apply_rope(q[..., dn:], cos[..., None, :],
                            sin[..., None, :])
    return q[..., :dn], q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


def _kv_b(cfg, lp):
    """W_kvb as [C, H, dn + dv]: per head the key up-projection and the
    value up-projection."""
    return lp["self_attn.kv_b_proj"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_expanded(cfg, lp, q_nope, q_rope, rows, flash=False):
    """Causal attention of one sequence in the expanded form: q_* [T, H,
    .], rows [T, C+dr] -> [T, H * dv]."""
    T = rows.shape[0]
    nh, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim
    c_kv, k_r = rows[:, :cfg.kv_lora_rank], rows[:, cfg.kv_lora_rank:]
    kvb = jnp.einsum("tc,chd->thd", c_kv, _kv_b(cfg, lp),
                     preferred_element_type=F32).astype(rows.dtype)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_r[:, None, :],
                                         (T, nh, k_r.shape[-1]))], axis=-1)
    v = kvb[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = softmax_scale(cfg)
    if flash:
        from ..ops.pallas.flash_attention import flash_attention_forward
        o = flash_attention_forward(q[None], k[None], v[None], causal=True,
                                    scale=scale)[0]
    else:
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       preferred_element_type=F32) * F32(scale)
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                       preferred_element_type=F32).astype(v.dtype)
    return o.reshape(T, nh * cfg.v_head_dim)


def mla_absorbed(cfg, lp, q_nope, q_rope, pool, tables, lengths):
    """The decode step's attention over one layer's latent pool: q_*
    [B, H, .] -> [B, H * dv]."""
    from ..ops.pallas.latent_attention import paged_latent_decode_attention
    dn = cfg.qk_nope_head_dim
    w = _kv_b(cfg, lp)
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w[..., :dn],
                       preferred_element_type=F32).astype(q_nope.dtype)
    o_lat = paged_latent_decode_attention(
        q_abs, q_rope, pool, tables, lengths, softmax_scale(cfg))
    o = jnp.einsum("bhc,chd->bhd", o_lat, w[..., dn:],
                   preferred_element_type=F32).astype(q_nope.dtype)
    return o.reshape(o.shape[0], -1)


def _routing(cfg):
    return dict(top_k=cfg.num_experts_per_tok, n_group=cfg.n_group,
                topk_group=cfg.topk_group,
                norm_topk_prob=cfg.norm_topk_prob,
                scale=cfg.routed_scaling_factor, held=cfg.held_experts)


def ffn(cfg, lp, i, x, live=None):
    """(FFN_i(x) for x [N, hidden], hits [count] int32 or None)."""
    if i < cfg.first_k_dense_replace:
        return swiglu(x, lp["mlp.gate_proj"], lp["mlp.up_proj"],
                      lp["mlp.down_proj"]), None
    y, hits = routed_experts(
        x, lp["mlp.experts.router"], lp["mlp.experts.gate_proj"],
        lp["mlp.experts.up_proj"], lp["mlp.experts.down_proj"],
        live=live, **_routing(cfg),
        select_bias=lp.get("mlp.experts.e_score_correction_bias"))
    shared = swiglu(x, lp["mlp.shared_experts.gate_proj"],
                    lp["mlp.shared_experts.up_proj"],
                    lp["mlp.shared_experts.down_proj"])
    return shared + y.astype(x.dtype), hits


def axk1_forward(cfg: AXK1Config, params, ids):
    """Logits [T, vocab] (float32) of one sequence of ids [T]: the plain
    full-sequence forward, expanded attention, no cache."""
    T = ids.shape[0]
    positions = jnp.arange(T, dtype=jnp.int32)
    x = params["embed_tokens"][ids]
    for i in range(cfg.num_hidden_layers):
        lp = layer_params(params, i)
        h = _lo(cfg, rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps))
        q_nope, q_rope, rows = mla_project(cfg, lp, h, positions)
        x = x + _mm(mla_expanded(cfg, lp, q_nope, q_rope, rows),
                    lp["self_attn.o_proj"])
        h = _lo(cfg, rms_norm(x, lp["post_attention_layernorm"],
                              cfg.rms_norm_eps))
        x = x + ffn(cfg, lp, i, h)[0]
    xf = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    return jnp.dot(xf, params["lm_head"], preferred_element_type=F32)


# ------------------------------------------------------------ the layer


class _Weights(nn.Layer):
    """A bag of parameters under the names the pure functions read."""

    def __init__(self, dtype, **shapes):
        super().__init__()
        for name, shape in shapes.items():
            norm = name.endswith("norm")
            setattr(self, name, self.create_parameter(
                list(shape), dtype=dtype,
                default_initializer=Constant(1.0) if norm
                else Normal(0.0, 0.02)))


class _Block(nn.Layer):
    def __init__(self, cfg: AXK1Config, i: int):
        super().__init__()
        H, nh = cfg.hidden_size, cfg.num_attention_heads
        dt = cfg.dtype
        self.input_layernorm = self.create_parameter(
            [H], dtype=dt, default_initializer=Constant(1.0))
        self.post_attention_layernorm = self.create_parameter(
            [H], dtype=dt, default_initializer=Constant(1.0))
        self.self_attn = _Weights(
            dt, q_a_proj=(H, cfg.q_lora_rank),
            q_a_layernorm=(cfg.q_lora_rank,),
            q_b_proj=(cfg.q_lora_rank,
                      nh * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
            kv_a_proj_with_mqa=(H, cfg.latent_width),
            kv_a_layernorm=(cfg.kv_lora_rank,),
            kv_b_proj=(cfg.kv_lora_rank,
                       nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            o_proj=(nh * cfg.v_head_dim, H))
        if i < cfg.first_k_dense_replace:
            F = cfg.intermediate_size
            self.mlp = _Weights(dt, gate_proj=(H, F), up_proj=(H, F),
                                down_proj=(F, H))
        else:
            F = cfg.moe_intermediate_size
            Fs = F * cfg.n_shared_experts
            self.mlp = nn.Layer()
            self.mlp.experts = RoutedExperts(
                H, F, cfg.n_routed_experts, cfg.num_experts_per_tok,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                held=cfg.held_experts, dtype=dt)
            self.mlp.shared_experts = _Weights(
                dt, gate_proj=(H, Fs), up_proj=(H, Fs), down_proj=(Fs, H))


class AXK1(nn.Layer):
    """The model as a layer of the framework: the constructor seeds every
    parameter (so `framework.param_arrays` and `jax.eval_shape` work as
    for GPT); `forward(ids [T])` is the plain full-sequence forward."""

    def __init__(self, cfg: AXK1Config):
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
            return axk1_forward(self.cfg, dict(zip(names, arrays)), ids_a)

        return apply(f, ids, *tensors, op_name="axk1_forward")


# ---------------------------------------------------- the serving functions
#
# The pools of this model kind are one pytree the engine threads and
# donates whole:
#
#   "latent"         tuple over layers of [P, page_tokens, row]: ONE
#                    array a layer, so that a layer's pool reaches the
#                    attention kernel and the row scatter as a whole
#                    buffer (no slice of a stacked pool, which the
#                    compiler would copy), page 0 the null page; a row
#                    is [c_kv | k_r | zeros] (`cfg.pool_row_width`)
#   "routed"         int32 [expert layers, held]: live routed
#                    assignments per held expert since the pools were
#                    made, accumulated on the device by step and prefill
#   "routed_tokens"  int32 []: live tokens that went through the routers


def latent_pools_sds(cfg: AXK1Config, num_pages: int, page_tokens: int):
    page = jax.ShapeDtypeStruct(
        (int(num_pages), int(page_tokens), cfg.pool_row_width),
        jnp.dtype(cfg.dtype))
    return {"latent": tuple(page for _ in range(cfg.num_hidden_layers)),
            "routed": jax.ShapeDtypeStruct(
                (cfg.moe_layers, cfg.held_experts[1]), jnp.int32),
            "routed_tokens": jax.ShapeDtypeStruct((), jnp.int32)}


def _pool_rows(cfg, rows):
    """Cache rows [..., C + dr] padded with zeros to the pool's width."""
    pad = cfg.pool_row_width - rows.shape[-1]
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, pad),))


def _count(pools, hits, n_live):
    """The pools with this dispatch's routed assignments added."""
    if not hits:
        return pools
    return dict(pools, routed=pools["routed"] + jnp.stack(hits),
                routed_tokens=pools["routed_tokens"]
                + n_live.astype(jnp.int32))


def axk1_paged_fns(cfg: AXK1Config, page_tokens: int,
                   prefill_name: str = "prefill"):
    """(paged_prefill, paged_step) over the latent pools.

    paged_prefill(params, pools, toks [1, R], tables [1, W], n [1])
        -> (logits [1, V] float32 at position n - 1, pools)
      expanded attention over the R positions; rows at or past n are
      written as zeros, padding of the table aims at the null page.
    paged_step(params, pools, tables [B, W], last_tok [B], cache_len [B])
        -> (logits [B, V] float32, pools)
      writes each row's new cache row at tables[b, cache_len // pt],
      row cache_len % pt, and attends 0..cache_len in the absorbed
      form. Batch padding (cache_len 0, an all-null table) lands on the
      null page and is not counted."""
    pt = int(page_tokens)
    L = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps

    def paged_prefill(params, pools, toks, tables, n):
        from ..ops.pallas import _common
        R, W = toks.shape[1], tables.shape[1]
        positions = jnp.arange(R, dtype=jnp.int32)
        live = positions < n[0]
        flash = _common.on_tpu() and R >= 256
        x = params["embed_tokens"][toks[0]]
        latent, hits = list(pools["latent"]), []
        for i in range(L):
            lp = layer_params(params, i)
            h = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
            q_nope, q_rope, rows = mla_project(cfg, lp, h, positions)
            x = x + _mm(mla_expanded(cfg, lp, q_nope, q_rope, rows, flash),
                        lp["self_attn.o_proj"])
            pages = jnp.pad(
                _pool_rows(cfg, jnp.where(live[:, None], rows, 0)),
                ((0, W * pt - R), (0, 0)))
            latent[i] = latent[i].at[tables[0]].set(
                pages.reshape(W, pt, cfg.pool_row_width))
            h = _lo(cfg, rms_norm(x, lp["post_attention_layernorm"], eps))
            y, hit = ffn(cfg, lp, i, h, live)
            x = x + y
            if hit is not None:
                hits.append(hit)
        last = jnp.clip(n[0] - 1, 0, R - 1)
        xf = rms_norm(jax.lax.dynamic_slice_in_dim(x, last, 1, axis=0),
                      params["norm"], eps)
        logits = jnp.dot(xf, params["lm_head"], preferred_element_type=F32)
        return logits, _count(dict(pools, latent=tuple(latent)), hits,
                              jnp.sum(live))

    def paged_step(params, pools, tables, last_tok, cache_len):
        W = tables.shape[1]
        pos = jnp.clip(cache_len.astype(jnp.int32), 0, cfg.max_seq_len - 1)
        live = cache_len > 0
        page_idx = jnp.take_along_axis(
            tables, jnp.minimum(pos // pt, W - 1)[:, None], axis=1)[:, 0]
        offset = pos % pt
        lengths = pos + 1                 # the row just written is live
        x = params["embed_tokens"][last_tok]
        latent, hits = list(pools["latent"]), []
        for i in range(L):
            lp = layer_params(params, i)
            h = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
            q_nope, q_rope, rows = mla_project(cfg, lp, h, pos)
            latent[i] = latent[i].at[page_idx, offset].set(
                _pool_rows(cfg, rows))
            x = x + _mm(mla_absorbed(cfg, lp, q_nope, q_rope, latent[i],
                                     tables, lengths),
                        lp["self_attn.o_proj"])
            h = _lo(cfg, rms_norm(x, lp["post_attention_layernorm"], eps))
            y, hit = ffn(cfg, lp, i, h, live)
            x = x + y
            if hit is not None:
                hits.append(hit)
        xf = rms_norm(x, params["norm"], eps)
        logits = jnp.dot(xf, params["lm_head"], preferred_element_type=F32)
        return logits, _count(dict(pools, latent=tuple(latent)), hits,
                              jnp.sum(live))

    paged_prefill.__name__ = paged_prefill.__qualname__ = prefill_name
    return paged_prefill, paged_step
