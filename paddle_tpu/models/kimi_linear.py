"""Kimi Linear (`model_type: kimi_linear`): a hybrid decoder whose layers
mix tokens either by Kimi Delta Attention (KDA: a gated delta-rule
linear attention with a per-channel decay, a fixed-size recurrent
state a stream) or by latent attention without positions (MLA, NoPE),
three of the first to one of the second; one leading dense SwiGLU
layer, then sigmoid-routed experts with a bias-corrected selection
beside a shared expert.

Config keys are the source's (`config.json` of
moonshotai/Kimi-Linear-48B-A3B-Instruct), `linear_attn_config`
flattened. Layers are numbered from 1 there (`kda_layers`,
`full_attn_layers`) and from 0 here. Every block: ``x += Mix(RMSNorm(x))``;
``x += FFN(RMSNorm(x))``; a final RMSNorm; an untied head.

* **KDA** (h the normed input, per head of `kda_head_dim` keys and
  values): ``q~, k~, v = SiLU(conv4(h W_q | W_k | W_v))``, `conv4` a
  causal depthwise convolution over time (`short_conv_kernel_size`
  taps, no bias: ``y_t = sum_j w[:, j] x_{t-3+j}``); ``q = q~/|q~| *
  d^-0.5``, ``k = k~/|k~|``; log decay ``g = -exp(A_log) * softplus((h
  W_fa) W_fb + dt_bias)``, one a key channel; ``beta = sigmoid(h W_b)``,
  one a head; the state follows `ops.pallas.kda`'s recurrence; ``y =
  RMSNorm(o; o_norm) * sigmoid((h W_ga) W_gb)``; ``out = concat(y)
  W_o``. What a stream keeps a layer: the state [H, K, V] float32 and
  the last three inputs of the convolution.
* **MLA, NoPE**: `models.axk1`'s latent attention without the query
  low-rank and without rotation (`mla_project(.., positions=None)`);
  the cached row is ``[c_kv | k_r]`` as there.
* **FFN**: `models.axk1.ffn` (dense SwiGLU under
  `first_k_dense_replace`, else shared + `routed_experts` told which
  experts it holds), the picks chosen on score + `e_score_correction_bias`.

Parameters and cached rows are `dtype`; the recurrent state is
`state_dtype` (float32 as served); products accumulate in float32;
norms, decays, the delta rule, router and softmax are float32.

Serving goes through `inference.decode.DecodeEngine` (the model kind
`kimi_linear` of `inference.model_kinds`): latent pages for the MLA
layers, and for the KDA layers state that lives BY SLOT, beside the
pages (`kimi_linear_paged_fns`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer.moe import RoutedExperts
from .axk1 import (F32, _count, _lo, _mm, _pool_rows, _Weights, ffn,
                   layer_params, mla_absorbed, mla_expanded, mla_project,
                   rms_norm)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_token: int = 8
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    # linear_attn_config, flattened; the layer lists count from 1
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    # positions the server sizes a slot for (an operator's max-model-len;
    # the source's model_max_length is 1048576)
    max_position_embeddings: int = 1048576
    # the chip's share: experts [first, first + count) of every layer
    held_experts: Tuple[int, int] = (0, 256)
    dtype: str = "bfloat16"
    # The benchmark's controls, None / "float32" as served: a type the
    # normed activations entering each layer's projections are rounded
    # through, and the type the recurrent state is kept in.
    operand_dtype: Optional[str] = None
    state_dtype: str = "float32"

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers", "held_experts"):
            object.__setattr__(self, name,
                               tuple(int(v) for v in getattr(self, name)))
        if not self.mla_use_nope:
            raise ValueError("KimiLinearConfig: only NoPE latent attention "
                             "(mla_use_nope) is written down")
        for i in range(1, self.num_hidden_layers + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(f"layer {i} is in one of kda_layers and "
                                 f"full_attn_layers")

    # ---- what the shared MLA / FFN functions of models.axk1 read
    n_routed_experts = property(lambda self: self.num_experts)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    n_group = property(lambda self: self.num_expert_group)
    norm_topk_prob = property(lambda self: self.moe_renormalize)
    rope_mscale_all_dim = 0.0       # rope_scaling null: the plain scale
    max_seq_len = property(lambda self: self.max_position_embeddings)

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_width(self):
        """As `AXK1Config.pool_row_width`: whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128

    @property
    def moe_layers(self):
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)

    # ---- which layer mixes how (0-based)
    def is_kda(self, i):
        return (i + 1) in self.kda_layers

    @property
    def kda_index(self):
        """Layer -> its place among the KDA layers held."""
        held = [i for i in range(self.num_hidden_layers) if self.is_kda(i)]
        return {i: n for n, i in enumerate(held)}

    @property
    def mla_index(self):
        held = [i for i in range(self.num_hidden_layers)
                if not self.is_kda(i)]
        return {i: n for n, i in enumerate(held)}

    @property
    def kda_width(self):
        return self.kda_num_heads * self.kda_head_dim

    @property
    def conv_row_width(self):
        """One stream's convolution inputs of one layer: the last
        `short_conv_kernel_size - 1` rows of [q | k | v] channels."""
        return (self.short_conv_kernel_size - 1) * 3 * self.kda_width

    @property
    def state_slot_bytes(self):
        """Bytes one slot's KDA state takes, all KDA layers held."""
        per = self.kda_num_heads * self.kda_head_dim ** 2 \
            * jnp.dtype(self.state_dtype).itemsize \
            + self.conv_row_width * jnp.dtype(self.dtype).itemsize
        return len(self.kda_index) * per


def kimi_linear_tiny(**kw):
    """A CPU-test preset: every mechanism, toy widths; one period of the
    pattern after the dense layer (KDA, KDA, KDA, MLA, KDA)."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, num_experts=16,
                num_experts_per_token=4, kda_layers=(1, 2, 3, 5),
                full_attn_layers=(4,), kda_num_heads=4, kda_head_dim=16,
                max_position_embeddings=128, held_experts=(0, 16),
                dtype="float32")
    base.update(kw)
    return KimiLinearConfig(**base)


# -------------------------------------------------- pure building blocks

def _conv_taps(w, window):
    """sum_j w[:, j] window[j]: `window` the taps' inputs, oldest first,
    each [.., C]; w [C, taps]."""
    w = w.astype(F32)
    return sum(x.astype(F32) * w[:, j] for j, x in enumerate(window))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_inputs(cfg, lp, h, window):
    """(q, k [.., H, K] normalised, v [.., H, V], g [.., H, K], beta
    [.., H], z [.., H, V]) float32 of the normed tokens h [.., hidden];
    `window` holds each token's convolution inputs, oldest first (its
    own projection last): `taps` arrays [.., 3 * width]."""
    H, D = cfg.kda_num_heads, cfg.kda_head_dim
    W = cfg.kda_width
    conv_w = jnp.concatenate([lp["self_attn.q_conv1d"],
                              lp["self_attn.k_conv1d"],
                              lp["self_attn.v_conv1d"]], axis=0)
    qkv = jax.nn.silu(_conv_taps(conv_w, window))
    heads = h.shape[:-1] + (H, D)
    q = _l2(qkv[..., :W].reshape(heads)) * F32(D ** -0.5)
    k = _l2(qkv[..., W:2 * W].reshape(heads))
    v = qkv[..., 2 * W:].reshape(heads)
    f = jnp.dot(_mm(h, lp["self_attn.f_a_proj"]), lp["self_attn.f_b_proj"],
                preferred_element_type=F32)
    g = -jnp.exp(lp["self_attn.A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(f + lp["self_attn.dt_bias"].astype(F32)
                          ).reshape(heads)
    beta = jax.nn.sigmoid(jnp.dot(h, lp["self_attn.b_proj"],
                                  preferred_element_type=F32))
    z = jnp.dot(_mm(h, lp["self_attn.g_a_proj"]), lp["self_attn.g_b_proj"],
                preferred_element_type=F32).reshape(heads)
    return q, k, v, g, beta, z


def kda_project(cfg, lp, h):
    """[q | k | v] projections of h [.., hidden] -> [.., 3 * width], the
    convolution's inputs (what a stream's conv state keeps)."""
    return jnp.concatenate([_mm(h, lp["self_attn.q_proj"]),
                            _mm(h, lp["self_attn.k_proj"]),
                            _mm(h, lp["self_attn.v_proj"])], axis=-1)


def kda_output(cfg, lp, o, z, dtype):
    """The gated, normed heads through W_o: o, z [.., H, V] float32."""
    y = rms_norm(o, lp["self_attn.o_norm"], cfg.rms_norm_eps) \
        * jax.nn.sigmoid(z)
    return _mm(y.reshape(y.shape[:-2] + (-1,)).astype(dtype),
               lp["self_attn.o_proj"])


def _windows(cfg, x):
    """x [T, C] -> `taps` arrays [T, C]: x shifted down by taps - 1, ..,
    0 positions (zeros before the sequence)."""
    taps = cfg.short_conv_kernel_size
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return [xp[j:j + x.shape[0]] for j in range(taps)]


def kda_sequence(cfg, lp, h, n=None, chunked=True):
    """KDA over one sequence h [T, hidden] from an empty state: (out
    [T, hidden], state [H, K, V] float32, conv rows [taps - 1, 3 *
    width]) after position n - 1 (n None: T). Positions at or past n
    change nothing."""
    # imported where it runs, as `axk1` imports its kernel: a process
    # that serves another kind loads no Pallas module for this one
    from ..ops.pallas import kda
    T = h.shape[0]
    x = kda_project(cfg, lp, h)
    q, k, v, g, beta, z = kda_inputs(cfg, lp, h, _windows(cfg, x))
    if n is not None:
        live = jnp.arange(T) < n
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    run = kda.kda_chunk_prefill if chunked else kda.kda_recurrence
    o, S = run(q, k, v, g, beta)
    taps = cfg.short_conv_kernel_size
    last = (T if n is None else n) - (taps - 1) + jnp.arange(taps - 1)
    rows = jnp.where((last >= 0)[:, None], x[jnp.clip(last, 0, T - 1)], 0)
    return kda_output(cfg, lp, o, z, h.dtype), S, rows


def kimi_linear_forward(cfg: KimiLinearConfig, params, ids):
    """Logits [T, vocab] (float32) of one sequence of ids [T]: the plain
    full-sequence forward (KDA by the recurrence, expanded attention,
    no cache)."""
    x = params["embed_tokens"][ids]
    eps = cfg.rms_norm_eps
    for i in range(cfg.num_hidden_layers):
        lp = layer_params(params, i)
        h = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
        if cfg.is_kda(i):
            x = x + kda_sequence(cfg, lp, h, chunked=False)[0]
        else:
            q_nope, q_rope, rows = mla_project(cfg, lp, h, None)
            x = x + _mm(mla_expanded(cfg, lp, q_nope, q_rope, rows),
                        lp["self_attn.o_proj"])
        h = _lo(cfg, rms_norm(x, lp["post_attention_layernorm"], eps))
        x = x + ffn(cfg, lp, i, h)[0]
    xf = rms_norm(x, params["norm"], eps)
    return jnp.dot(xf, params["lm_head"], preferred_element_type=F32)


# ------------------------------------------------------------ the layer


class _KDAWeights(_Weights):
    """A KDA mixer's parameters: `_Weights` for the matrices and the
    norm gain, plus the two float32 vectors of the decay."""

    def __init__(self, cfg: KimiLinearConfig):
        H, W, D = cfg.hidden_size, cfg.kda_width, cfg.kda_head_dim
        taps = cfg.short_conv_kernel_size
        super().__init__(
            cfg.dtype, q_proj=(H, W), k_proj=(H, W), v_proj=(H, W),
            q_conv1d=(W, taps), k_conv1d=(W, taps), v_conv1d=(W, taps),
            f_a_proj=(H, D), f_b_proj=(D, W), b_proj=(H, cfg.kda_num_heads),
            g_a_proj=(H, D), g_b_proj=(D, W), o_norm=(D,), o_proj=(W, H))
        # A = exp(A_log) = 1 and softplus(dt_bias) = ln 2 at construction;
        # a checkpoint (or the benchmark's seed) brings its own
        self.A_log = self.create_parameter(
            [cfg.kda_num_heads], dtype="float32",
            default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [W], dtype="float32", default_initializer=Constant(0.0))


class _Block(nn.Layer):
    def __init__(self, cfg: KimiLinearConfig, i: int):
        super().__init__()
        H, nh = cfg.hidden_size, cfg.num_attention_heads
        dt = cfg.dtype
        self.input_layernorm = self.create_parameter(
            [H], dtype=dt, default_initializer=Constant(1.0))
        self.post_attention_layernorm = self.create_parameter(
            [H], dtype=dt, default_initializer=Constant(1.0))
        if cfg.is_kda(i):
            self.self_attn = _KDAWeights(cfg)
        else:
            self.self_attn = _Weights(
                dt, q_proj=(H, nh * (cfg.qk_nope_head_dim
                                     + cfg.qk_rope_head_dim)),
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
            Fs = F * cfg.num_shared_experts
            self.mlp = nn.Layer()
            self.mlp.experts = RoutedExperts(
                H, F, cfg.num_experts, cfg.num_experts_per_token,
                n_group=cfg.num_expert_group, topk_group=cfg.topk_group,
                norm_topk_prob=cfg.moe_renormalize,
                routed_scaling_factor=cfg.routed_scaling_factor,
                held=cfg.held_experts, dtype=dt, select_bias=True)
            self.mlp.shared_experts = _Weights(
                dt, gate_proj=(H, Fs), up_proj=(H, Fs), down_proj=(Fs, H))


class KimiLinear(nn.Layer):
    """The model as a layer of the framework (as `models.axk1.AXK1`):
    the constructor seeds every parameter; `forward(ids [T])` is the
    plain full-sequence forward."""

    def __init__(self, cfg: KimiLinearConfig):
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
            return kimi_linear_forward(self.cfg, dict(zip(names, arrays)),
                                       ids_a)

        return apply(f, ids, *tensors, op_name="kimi_linear_forward")


# ---------------------------------------------------- the serving functions
#
# The pools of this model kind are one pytree the engine threads and
# donates whole. Two kinds of state lie in it side by side:
#
#   "latent"  tuple over the MLA layers of [P, page_tokens, row]: pages,
#             addressed through a block table, as `models.axk1`'s
#   "state"   tuple over the KDA layers of [slots + 1, H, K, V]
#             `state_dtype`: one recurrent state a SLOT, addressed by
#             the slot's index; the last entry is the null slot, where
#             a step's padding rows land
#   "conv"    tuple over the KDA layers of [slots + 1, conv_row_width]:
#             the last three convolution inputs of each slot, oldest
#             first, [q | k | v] channels a row
#   "routed", "routed_tokens"  as `models.axk1`'s


def kimi_linear_pools_sds(cfg: KimiLinearConfig, num_pages: int,
                          page_tokens: int, slots: int):
    n_kda, n_mla = len(cfg.kda_index), len(cfg.mla_index)
    page = jax.ShapeDtypeStruct(
        (int(num_pages), int(page_tokens), cfg.pool_row_width),
        jnp.dtype(cfg.dtype))
    state = jax.ShapeDtypeStruct(
        (int(slots) + 1, cfg.kda_num_heads, cfg.kda_head_dim,
         cfg.kda_head_dim), jnp.dtype(cfg.state_dtype))
    conv = jax.ShapeDtypeStruct((int(slots) + 1, cfg.conv_row_width),
                                jnp.dtype(cfg.dtype))
    return {"latent": tuple(page for _ in range(n_mla)),
            "state": tuple(state for _ in range(n_kda)),
            "conv": tuple(conv for _ in range(n_kda)),
            "routed": jax.ShapeDtypeStruct(
                (cfg.moe_layers, cfg.held_experts[1]), jnp.int32),
            "routed_tokens": jax.ShapeDtypeStruct((), jnp.int32)}


def kimi_linear_paged_fns(cfg: KimiLinearConfig, page_tokens: int,
                          prefill_name: str = "prefill"):
    """(paged_prefill, paged_step) over the pools above. Beside
    `models.axk1.axk1_paged_fns`' arguments each takes the SLOT:

    paged_prefill(params, pools, toks [1, R], tables [1, W], n [1], slot [])
        -> (logits [1, V] float32 at position n - 1, pools)
      runs the sequence from an EMPTY state (KDA in chunks) and
      OVERWRITES the slot's state and convolution rows with those after
      position n - 1: whatever stream held the slot before is gone.
    paged_step(params, pools, tables [B, W], last_tok [B], cache_len [B],
               slots [B]) -> (logits [B, V] float32, pools)
      advances each row's slot by one token, in place. A padding row
      carries the null slot (the engine's slot count): it reads and
      writes that entry and no stream's."""
    pt = int(page_tokens)
    L = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps
    kda_at, mla_at = cfg.kda_index, cfg.mla_index
    taps = cfg.short_conv_kernel_size

    def paged_prefill(params, pools, toks, tables, n, slot):
        from ..ops.pallas import _common
        R, W = toks.shape[1], tables.shape[1]
        live = jnp.arange(R, dtype=jnp.int32) < n[0]
        flash = _common.on_tpu() and R >= 256
        x = params["embed_tokens"][toks[0]]
        latent, state, conv = (list(pools[k])
                               for k in ("latent", "state", "conv"))
        hits = []
        for i in range(L):
            lp = layer_params(params, i)
            h = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
            if i in kda_at:
                j = kda_at[i]
                y, S, rows = kda_sequence(cfg, lp, h, n[0])
                x = x + y
                state[j] = state[j].at[slot].set(S.astype(state[j].dtype))
                conv[j] = conv[j].at[slot].set(rows.reshape(-1))
            else:
                j = mla_at[i]
                q_nope, q_rope, rows = mla_project(cfg, lp, h, None)
                x = x + _mm(mla_expanded(cfg, lp, q_nope, q_rope, rows,
                                         flash), lp["self_attn.o_proj"])
                pages = jnp.pad(
                    _pool_rows(cfg, jnp.where(live[:, None], rows, 0)),
                    ((0, W * pt - R), (0, 0)))
                latent[j] = latent[j].at[tables[0]].set(
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
        pools = dict(pools, latent=tuple(latent), state=tuple(state),
                     conv=tuple(conv))
        return logits, _count(pools, hits, jnp.sum(live))

    def paged_step(params, pools, tables, last_tok, cache_len, slots):
        from ..ops.pallas import kda
        W = tables.shape[1]
        B = last_tok.shape[0]
        pos = jnp.clip(cache_len.astype(jnp.int32), 0, cfg.max_seq_len - 1)
        live = cache_len > 0
        page_idx = jnp.take_along_axis(
            tables, jnp.minimum(pos // pt, W - 1)[:, None], axis=1)[:, 0]
        offset = pos % pt
        lengths = pos + 1                 # the row just written is live
        x = params["embed_tokens"][last_tok]
        latent, state, conv = (list(pools[k])
                               for k in ("latent", "state", "conv"))
        hits = []
        for i in range(L):
            lp = layer_params(params, i)
            h = _lo(cfg, rms_norm(x, lp["input_layernorm"], eps))
            if i in kda_at:
                j = kda_at[i]
                new = kda_project(cfg, lp, h)                    # [B, 3W]
                old = conv[j][slots]                  # [B, (taps-1) 3W]
                keep = jnp.concatenate([old[:, new.shape[1]:], new], axis=1)
                conv[j] = conv[j].at[slots].set(keep)
                window = jnp.split(old, taps - 1, axis=1) + [new]
                q, k, v, g, beta, z = kda_inputs(cfg, lp, h, window)
                o, state[j] = kda.kda_decode_step(q, k, v, g, beta,
                                                  state[j], slots)
                x = x + kda_output(cfg, lp, o, z, h.dtype)
            else:
                j = mla_at[i]
                q_nope, q_rope, rows = mla_project(cfg, lp, h, None)
                latent[j] = latent[j].at[page_idx, offset].set(
                    _pool_rows(cfg, rows))
                x = x + _mm(mla_absorbed(cfg, lp, q_nope, q_rope, latent[j],
                                         tables, lengths),
                            lp["self_attn.o_proj"])
            h = _lo(cfg, rms_norm(x, lp["post_attention_layernorm"], eps))
            y, hit = ffn(cfg, lp, i, h, live)
            x = x + y
            if hit is not None:
                hits.append(hit)
        xf = rms_norm(x, params["norm"], eps)
        logits = jnp.dot(xf, params["lm_head"], preferred_element_type=F32)
        pools = dict(pools, latent=tuple(latent), state=tuple(state),
                     conv=tuple(conv))
        return logits, _count(pools, hits, jnp.sum(live))

    paged_prefill.__name__ = paged_prefill.__qualname__ = prefill_name
    return paged_prefill, paged_step
