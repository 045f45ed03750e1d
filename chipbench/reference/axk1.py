"""The plain reference of family ``axk1`` (A.X-K1, a DeepSeek-V2/V3-family
decoder): the equations in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``; no cache, no kernel, no
batching, no sorted dispatch. It imports nothing from ``paddle_tpu``.

Equations (DeepSeek-V2, arXiv 2405.04434, section 2.1 for the latent
attention; DeepSeek-V3, arXiv 2412.19437, section 2.1.2 for sigmoid
scores, group-limited selection and the shared expert; the family's
released modelling code for YaRN and the pairing of rotary dimensions).
Decoder layer l: ``x = x + MLA(RMSNorm(x))``; ``x = x + FFN_l(RMSNorm(x))``;
a final RMSNorm; an untied head; no biases.

* MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``; ``k_r =
  RoPE(k_r)`` (shared by the heads); ``q_rope = RoPE(q_rope)``; ``[k_nope |
  v] = c_kv W_kvb`` per head; scores ``(q_nope . k_nope + q_rope . k_r) *
  s``, causal softmax, ``out = concat(sum p v) W_o``. Always the expanded
  form here: the absorbed form is the program's business.
* RoPE: YaRN inverse frequencies (`yarn_inv_freq`), cos/sin unscaled
  (mscale = mscale_all_dim), ``s = (d_nope + d_rope)^-0.5 * (0.1 ln
  factor + 1)^2``. Pairs are (2i, 2i+1); the rotated vector is written
  first-of-pairs | second-of-pairs (q_rope and k_r alike, so each dot
  product is that of the interleaved form).
* Dense FFN (the leading layers): ``W_down(silu(x W_gate) * (x W_up))``.
* Expert FFN: ``Shared(x) + scale * sum_{e in picks} w_e Expert_e(x)``;
  ``s = sigmoid(x W_r)`` over ALL routed experts; `n_group` groups, a
  group scores the sum of its two best s, the `topk_group` best groups
  stay; the `top_k` best s inside them; ``w_e = s_e / sum of the
  picked``.

Departures, each because the configuration states it:

* **The share.** The chip holds experts ``[first, first + count)`` of
  every expert layer and a slice of the vocabulary. The router keeps
  all its outputs and its picks; the routed sum runs over the picks
  whose expert is held and leaves the rest out; that partial result
  goes on to the next layer. Here each held expert is applied, in a
  plain loop, to exactly the tokens routed to it (the host reads the
  picks: shapes follow the data, so the loop is not one jitted
  program).
* `topk_method: "none"` is read as "no bias-corrected selection".
* Weights arrive as the benchmark made them (bfloat16) and are cast to
  float32 a layer at a time, so the reference fits beside them.

`operand` (None for the reference proper) names a type the activations
that enter the weight matrices are rounded through, the same places the
program's `operand_dtype` rounds: the control one precision down.

TOLERANCE. `GAP_TOL` = 8.0e-3 bounds the mean, over the served tokens
compared, of how far a served token's logit lies below this reference's
best at its position, in standard deviations of the logits
(`gaps_below_best`). Read on the chip at the published widths over the
requests a 45 s long-turn window both admits and finishes (14-39 of
them, 4,200-13,400 served tokens; PERF.md section 2 has every reading):
the program, bfloat16 weights, cache and activations against this
float32 reference on the same bfloat16 weights, 1.9e-3 to 2.6e-3 over
two dozen seeds (4.4-4.8% of its tokens are not the reference's first:
with logits of standard deviation 1.7 a near-tie is common and an 8-bit
activation flips it); one operand precision down (float8 e4m3
activations into every layer's projections), through the program's own
path 3.5e-2 to 3.6e-2 and through this reference judged in the served
tokens' place 3.3e-2 to 3.5e-2. The limit is 3.1 times the largest
sound reading and 4.1 times under the smallest control. The mean and
not the widest gap, for the reason `reference/gpt.py` gives: the widest
hangs on one near-tie (program 0.43-0.53, controls 0.72-0.89). A wrong
position, page, expert or weight reads tens of times the limit; on the
CPU in float32 at "highest" the program reads 0.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

GAP_TOL = 8.0e-3
CONTROL_DTYPE = jnp.float8_e4m3fn   # the nearest precision below bfloat16
F32 = jnp.float32


# ------------------------------------------------------------- pieces


def yarn_inv_freq(c):
    d, base = c["rope_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / float(rs["factor"])

    def correction_dim(rotations):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(c):
    rs = c["rope_scaling"]
    s = (c["nope_dim"] + c["rope_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, cos, sin):
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _low(x, operand):
    return x if operand is None else x.astype(operand).astype(F32)


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg.astype(F32)) * (x @ wu.astype(F32))) \
        @ wd.astype(F32)


def _cfg_key(c):
    """The sizes as a hashable static argument."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else tuple(v) if isinstance(v, list) else v)
                        for k, v in c.items()))


def _cfg(key):
    return {k: dict(v) if k == "rope_scaling" else v for k, v in key}


HEAD_BLOCK = 8      # heads attended at a time: [8, T, T] scores, not [64, ..]


@functools.partial(jax.jit, static_argnums=(2, 3))
def attention_block(w, x, key, operand):
    """x + MLA(RMSNorm(x)) for one sequence x [T, hidden]; w: the
    layer's attention weights and its input norm."""
    c = _cfg(key)
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        nh, dn, dr, dv = c["heads"], c["nope_dim"], c["rope_dim"], c["v_dim"]
        C, eps = c["kv_lora_rank"], c["eps"]
        h = _low(rms_norm(x, w["input_layernorm"], eps), operand)
        c_q = rms_norm(h @ w["q_a_proj"].astype(F32), w["q_a_layernorm"], eps)
        q = (c_q @ w["q_b_proj"].astype(F32)).reshape(T, nh, dn + dr)
        kv = h @ w["kv_a_proj_with_mqa"].astype(F32)
        c_kv = rms_norm(kv[:, :C], w["kv_a_layernorm"], eps)
        ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(yarn_inv_freq(c))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        k_r = rope(kv[:, C:], cos, sin)                        # [T, dr]
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], cos[:, None],
                                           sin[:, None])
        kvb = (c_kv @ w["kv_b_proj"].astype(F32)).reshape(T, nh, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        causal = jnp.tril(jnp.ones((T, T), bool))
        scale = softmax_scale(c)

        def heads(args):
            qn, qr, kn, vv = args          # [hb, T, .]
            s = (jnp.einsum("hqd,hkd->hqk", qn, kn)
                 + jnp.einsum("hqd,kd->hqk", qr, k_r)) * scale
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,hkd->hqd", p, vv)

        hb = HEAD_BLOCK if nh % HEAD_BLOCK == 0 else 1

        def blocks(a):                     # [T, nh, d] -> [nh/hb, hb, T, d]
            return a.transpose(1, 0, 2).reshape(nh // hb, hb, T, a.shape[-1])

        o = jax.lax.map(heads, (blocks(q_nope), blocks(q_rope),
                                blocks(k_nope), blocks(v)))
        o = o.reshape(nh, T, dv).transpose(1, 0, 2).reshape(T, nh * dv)
        return x + o @ w["o_proj"].astype(F32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def dense_ffn_block(w, x, eps, operand):
    """x + FFN(RMSNorm(x)) for a dense layer."""
    with jax.default_matmul_precision("highest"):
        h = _low(rms_norm(x, w["post_attention_layernorm"], eps), operand)
        return x + swiglu(h, w["gate_proj"], w["up_proj"], w["down_proj"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def shared_and_route(w, x, key, operand):
    """(x + Shared(h), h, picks [T, K], weights [T, K]) of an expert
    layer, h = RMSNorm(x): the router over ALL routed experts."""
    c = _cfg(key)
    with jax.default_matmul_precision("highest"):
        h = _low(rms_norm(x, w["post_attention_layernorm"], c["eps"]),
                 operand)
        s = jax.nn.sigmoid(h @ w["router"].astype(F32))
        T, E = s.shape
        G = c["n_group"]
        eligible = s
        if G > 1:
            g = s.reshape(T, G, E // G)
            score = jnp.sort(g, axis=-1)[..., -2:].sum(-1)          # [T, G]
            best = jnp.argsort(-score, axis=-1)[:, :c["topk_group"]]
            keep = jnp.zeros((T, G), bool).at[
                jnp.arange(T)[:, None], best].set(True)
            eligible = jnp.where(keep[:, :, None], g, 0.0).reshape(T, E)
        picks = jnp.argsort(-eligible, axis=-1)[:, :c["top_k"]]
        wts = jnp.take_along_axis(s, picks, axis=1)
        if c["norm_topk_prob"]:
            wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
        wts = wts * c["routed_scaling_factor"]
        y = x + swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                       w["shared_down_proj"])
        return y, h, picks, wts


@jax.jit
def expert_add(y, h, rows, wts, wg, wu, wd):
    """y with w * Expert(h[rows]) added at `rows` (padding: weight 0)."""
    with jax.default_matmul_precision("highest"):
        return y.at[rows].add(swiglu(h[rows], wg, wu, wd) * wts[:, None])


@functools.partial(jax.jit, static_argnums=(3,))
def head(w_norm, w_head, x, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, w_norm, eps) @ w_head.astype(F32)


def _bucket(n, floor=64):
    b = floor
    while b < n:
        b *= 2
    return b


def forward(p, tokens, c, operand=None):
    """Logits [T, V] (float32) of one sequence of ids [T] under the
    share `c` states. `p`: the program's parameter names -> arrays of
    any float type (cast to float32 a layer at a time)."""
    key = _cfg_key(c)
    first, count = c["held"]
    x = p["embed_tokens"][jnp.asarray(tokens)].astype(F32)
    for i in range(c["layers"]):
        pre = f"layers.{i}."
        att = {k: p[pre + "self_attn." + k] for k in (
            "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
            "kv_a_layernorm", "kv_b_proj", "o_proj")}
        att["input_layernorm"] = p[pre + "input_layernorm"]
        x = attention_block(att, x, key, operand)
        norm = p[pre + "post_attention_layernorm"]
        if i < c["dense_layers"]:
            x = dense_ffn_block(
                {"post_attention_layernorm": norm,
                 **{k: p[pre + "mlp." + k]
                    for k in ("gate_proj", "up_proj", "down_proj")}},
                x, c["eps"], operand)
            continue
        ex = pre + "mlp.experts."
        w = {"post_attention_layernorm": norm, "router": p[ex + "router"],
             **{"shared_" + k: p[pre + "mlp.shared_experts." + k]
                for k in ("gate_proj", "up_proj", "down_proj")}}
        x, h, picks, wts = shared_and_route(w, x, key, operand)
        picks, wts_np = np.asarray(picks), np.asarray(wts)
        for e in range(count):              # each held expert, its tokens
            rows, slot = np.nonzero(picks == first + e)
            if not len(rows):
                continue
            n = _bucket(len(rows))
            rows_p = np.zeros(n, np.int32)
            wts_p = np.zeros(n, np.float32)
            rows_p[:len(rows)] = rows
            wts_p[:len(rows)] = wts_np[rows, slot]
            x = expert_add(x, h, jnp.asarray(rows_p), jnp.asarray(wts_p),
                           p[ex + "gate_proj"][e], p[ex + "up_proj"][e],
                           p[ex + "down_proj"][e])
    return head(p["norm"], p["lm_head"], x, c["eps"])


@jax.jit
def gaps_below_best(logits, chosen, n):
    """How far the logit of `chosen[i]` lies below the best logit of
    row i, in standard deviations of the first `n` rows of `logits`
    [T, V] (the rest is padding): [T] floats, 0 where the chosen token
    is the best one."""
    logits = logits.astype(F32)
    at = jnp.take_along_axis(logits, chosen[:, None], axis=1)[:, 0]
    real = (jnp.arange(logits.shape[0]) < n)[:, None]
    count = n * logits.shape[1]
    mean = jnp.sum(jnp.where(real, logits, 0.0)) / count
    var = jnp.sum(jnp.where(real, (logits - mean) ** 2, 0.0)) / count
    return (jnp.max(logits, axis=-1) - at) / jnp.sqrt(var)
