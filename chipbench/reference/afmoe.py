"""The plain reference of family ``afmoe`` (Arcee Trinity, `model_type`
afmoe: window and full attention mixed over grouped-query heads, a
gated attention output, sandwich norms, bias-selected sigmoid experts):
the equations in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``; no cache, no ring, no
kernel, no batching, no sorted dispatch. It imports nothing from
``paddle_tpu`` and was written from the equations below, not from
``paddle_tpu/models/afmoe.py``; the pieces that know no architecture
(RMSNorm, SwiGLU, the head, `gaps_below_best`, the held experts' loop)
are its siblings', ``reference/axk1.py`` and ``reference/kimi_linear.py``.

Equations (ISSUE 35, from the source's `config.json`; what the config
does not state is the configuration file's `assumed`). x [T, H]:
``h = E[ids] sqrt(H)`` (`mup_enabled`). Layer l, of type `t_l`:

* ``a = RMSNorm_in(h)``; ``q = a W_q`` [T, Hq, D], ``k = a W_k``, ``v = a
  W_v`` [T, Hkv, D], ``g = a W_g`` [T, Hq D]; ``q = RMSNorm_q(q)``, ``k =
  RMSNorm_k(k)`` over each head's D (gains [D]); if `t_l` is
  `sliding_attention`, rotary on q and k (theta `rope_theta`, the whole
  D, rotate-half pairing: i with i + D/2), else none. Query head j reads
  K/V head ``j // (Hq / Hkv)``; scores ``q k^T / sqrt(D)``, causal, and
  on a sliding layer key s is visible from query t only if ``t - s <
  sliding_window``; softmax; ``o = (softmax v) sigmoid(g)``; ``h = h +
  RMSNorm_post_attn(o W_o)``.
* ``m = RMSNorm_pre_mlp(h)``; a dense layer: ``f = W_down(silu(m W_gate)
  (m W_up))``; an expert layer: ``s = sigmoid(m W_r)`` over ALL routed
  experts, the picks the `top_k` best of ``s + b``, ``w_e = s_e / (sum of
  the picked s + 1e-20)`` times `route_scale`, ``f = Shared(m) + sum_picks
  w_e Expert_e(m)``; ``h = h + RMSNorm_post_mlp(f)``.
* ``logits = RMSNorm(h) W_head``; eps `rms_norm_eps`; no bias anywhere.

Attention is computed a block of `QUERY_BLOCK` queries and one K/V head
(its six query heads) at a time, so that 16k tokens fit (48 x 16k x 16k
float32 scores do not): a full layer's block against every key, a
sliding layer's against the `sliding_window - 1 + QUERY_BLOCK` keys it
can see, each under the mask written out.

Departures, each because the configuration states it:

* **The share.** The chip holds experts ``[first, first + count)`` of
  every expert layer and a slice of the vocabulary; the routed sum runs
  over the picks whose expert is held (`kimi_linear.held_experts_add`).
* Weights arrive as the benchmark made them (bfloat16; the selection
  bias float32) and are cast to float32 a layer at a time.

CONTROLS, each through `forward`'s arguments. `operand`: a type the
normed activations entering the weight matrices are rounded through
(float8 e4m3 against the program's bfloat16), one precision down.
`window`: the sliding layers see `CONTROL_WINDOW` = 2,048 positions
where the configuration says 4,096: what a ring kept at half its rows
serves, the control that shows `correct` sees the mechanism. `CONTROL`
says which of the two a control run reads; the family's control engine
follows it.

TOLERANCE. `GAP_TOL` = 1.0e-2 bounds the mean, over the served tokens
compared, of how far a served token's logit lies below this
reference's best at its position, in standard deviations of the logits
(`gaps_below_best`). Read on the chip at the published widths (my chip
runs, PR 35; PERF.md section 2 has every reading): the program
(bfloat16 weights, activations, K/V rows and rings) 1.35e-3 to 4.04e-3
over seventeen seeds: eight windows of the long-document cell (25-30
requests judged of about 100 finished, 4-6 thousand served tokens:
2.5e-3 to 3.4e-3) and nine shorter ones (1.4-2.8 thousand tokens, which
scatter wider: 1.35e-3 to 4.04e-3); 2.2-3.5% of its tokens are not the
reference's first. The chip holds an eighth of the experts, so a
bfloat16 rounding that flips the fourth against the fifth of 256
scores changes what a layer adds half as often as with a half held
(`kimi_linear`: 1e-2) and as often as with a sixteenth (`axk1`: 2e-3);
the sandwich norms carry the rest. The controls, both through the
program's own path and through this reference judged in the served
tokens' place: float8 (e4m3) operands into every projection 3.07e-2 /
2.71e-2; the window layers' ring kept at 2,048 rows **1.27 / 1.29**
(88-90% of the tokens not the first: a stream three windows deep that
sees half a window is another model). The limit is 2.5 times the
largest sound reading and 2.7 times under the smallest control. The
mean and not the widest gap, for the reason `reference/gpt.py` gives
(program 0.30-1.26, float8 1.02-1.22: the widest separates nothing).
On the CPU in float32 at "highest" the program reads 0.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.axk1 import (_bucket, _cfg, _cfg_key,  # noqa: F401
                                      _low, gaps_below_best, head, rms_norm,
                                      swiglu)
from chipbench.reference.kimi_linear import held_experts_add

GAP_TOL = 1.0e-2
CONTROL_DTYPE = jnp.float8_e4m3fn   # operands: the precision below bfloat16
CONTROL_WINDOW = 2048               # a ring kept at half its rows
CONTROL = "operand"                 # which control a control run reads
QUERY_BLOCK = 1024
F32 = jnp.float32


# ------------------------------------------------------------- pieces


def rope_half(x, theta):
    """Rotary over the whole last axis of x [T, heads, D] at positions
    0..T-1, pairing i with i + D/2."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attend(q, k, v, window):
    """q [T, Hq, D]; k, v [T, Hkv, D] -> [T, Hq, D]: causal softmax
    attention, query head j over K/V head j // (Hq / Hkv); `window`
    (None: a full layer) hides keys at or beyond that distance."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    banded = window is not None and window - 1 + qb < T
    span = window - 1 + qb if banded else T
    pad = window - 1 if banded else 0
    qs = q.reshape(T // qb, qb, Hkv, G, D).transpose(2, 0, 3, 1, 4)
    ks = jnp.pad(k, ((pad, 0), (0, 0), (0, 0))).transpose(1, 0, 2)
    vs = jnp.pad(v, ((pad, 0), (0, 0), (0, 0))).transpose(1, 0, 2)
    scale = 1.0 / math.sqrt(D)

    def one_head(args):
        q_h, k_h, v_h = args            # [nb, G, qb, D], [pad + T, D] x 2

        def one_block(blk):
            q_b, first = blk            # [G, qb, D], the block's first row
            start = first if banded else 0
            keys = jax.lax.dynamic_slice_in_dim(k_h, start, span)
            vals = jax.lax.dynamic_slice_in_dim(v_h, start, span)
            t = first + jnp.arange(qb)[:, None]
            s_pos = start - pad + jnp.arange(span)[None, :]
            seen = (s_pos >= 0) & (s_pos <= t)
            if window is not None:
                seen = seen & (t - s_pos < window)
            s = jnp.einsum("gqd,kd->gqk", q_b, keys) * scale
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->gqd", p, vals)

        return jax.lax.map(one_block,
                           (q_h, jnp.arange(T // qb) * qb))

    o = jax.lax.map(one_head, (qs, ks, vs))     # [Hkv, nb, G, qb, D]
    return o.transpose(1, 3, 0, 2, 4).reshape(T, Hq, D)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def attention_block(w, x, key, operand, window):
    """x + RMSNorm_post_attn(Attention(RMSNorm_in(x))) for one sequence
    x [T, hidden]; `window` None: a full layer, no positions."""
    c = _cfg(key)
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        Hq, Hkv, D, eps = c["heads"], c["kv_heads"], c["head_dim"], c["eps"]
        a = _low(rms_norm(x, w["input_layernorm"], eps), operand)
        q = rms_norm((a @ w["q_proj"].astype(F32)).reshape(T, Hq, D),
                     w["q_norm"], eps)
        k = rms_norm((a @ w["k_proj"].astype(F32)).reshape(T, Hkv, D),
                     w["k_norm"], eps)
        v = (a @ w["v_proj"].astype(F32)).reshape(T, Hkv, D)
        gate = jax.nn.sigmoid(a @ w["gate_proj"].astype(F32))
        if window is not None:
            q, k = rope_half(q, c["rope_theta"]), rope_half(k, c["rope_theta"])
        o = attend(q, k, v, window).reshape(T, Hq * D) * gate
        return x + rms_norm(o @ w["o_proj"].astype(F32),
                            w["post_attention_layernorm"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3))
def dense_block(w, x, eps, operand):
    """x + RMSNorm_post_mlp(SwiGLU(RMSNorm_pre_mlp(x)))."""
    with jax.default_matmul_precision("highest"):
        m = _low(rms_norm(x, w["pre_mlp_layernorm"], eps), operand)
        f = swiglu(m, w["gate_proj"], w["up_proj"], w["down_proj"])
        return x + rms_norm(f, w["post_mlp_layernorm"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3))
def shared_and_route(w, x, key, operand):
    """(Shared(m), m, picks [T, K], weights [T, K]) of an expert layer,
    m = RMSNorm_pre_mlp(x): the router over ALL routed experts, the
    picks chosen on score + bias, the weights the picked scores."""
    c = _cfg(key)
    with jax.default_matmul_precision("highest"):
        m = _low(rms_norm(x, w["pre_mlp_layernorm"], c["eps"]), operand)
        s = jax.nn.sigmoid(m @ w["router"].astype(F32))
        picks = jnp.argsort(-(s + w["bias"].astype(F32)),
                            axis=-1)[:, :c["top_k"]]
        wts = jnp.take_along_axis(s, picks, axis=1)
        if c["route_norm"]:
            wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
        wts = wts * c["route_scale"]
        f = swiglu(m, w["shared_gate_proj"], w["shared_up_proj"],
                   w["shared_down_proj"])
        return f, m, picks, wts


@functools.partial(jax.jit, static_argnums=(3,))
def add_normed(x, f, gain, eps):
    return x + rms_norm(f, gain, eps)


ATTENTION_WEIGHTS = ("q_proj", "k_proj", "v_proj", "gate_proj", "q_norm",
                     "k_norm", "o_proj")


def forward(p, tokens, c, operand=None, window=None):
    """Logits [T, V] (float32) of one sequence of ids [T] under the
    share `c` states. `p`: the program's parameter names -> arrays of
    any float type (cast to float32 a layer at a time). `c["sliding"]`
    lists, layer by layer from 0, whether the layer is a sliding one;
    `window` (a control) takes `c["window"]`'s place."""
    key = _cfg_key(c)
    first, count = c["held"]
    window = c["window"] if window is None else int(window)
    x = p["embed_tokens"][jnp.asarray(tokens)].astype(F32)
    if c["mup"]:
        x = x * math.sqrt(x.shape[1])
    for i in range(c["layers"]):
        pre = f"layers.{i}."
        att = {k: p[pre + "self_attn." + k] for k in ATTENTION_WEIGHTS}
        for k in ("input_layernorm", "post_attention_layernorm"):
            att[k] = p[pre + k]
        x = attention_block(att, x, key, operand,
                            window if c["sliding"][i] else None)
        norms = {k: p[pre + k]
                 for k in ("pre_mlp_layernorm", "post_mlp_layernorm")}
        if i < c["dense_layers"]:
            x = dense_block(
                {**norms, **{k: p[pre + "mlp." + k]
                             for k in ("gate_proj", "up_proj", "down_proj")}},
                x, c["eps"], operand)
            continue
        ex = pre + "mlp.experts."
        w = {**norms, "router": p[ex + "router"],
             "bias": p[ex + "e_score_correction_bias"],
             **{"shared_" + k: p[pre + "mlp.shared_experts." + k]
                for k in ("gate_proj", "up_proj", "down_proj")}}
        f, m, picks, wts = shared_and_route(w, x, key, operand)
        load = np.bincount(np.asarray(picks).ravel(),
                           minlength=first + count)[first:first + count]
        f = held_experts_add(f, m, picks, wts, p[ex + "gate_proj"],
                             p[ex + "up_proj"], p[ex + "down_proj"],
                             first, _bucket(int(load.max()), floor=512))
        x = add_normed(x, f, norms["post_mlp_layernorm"], c["eps"])
    return head(p["norm"], p["lm_head"], x, c["eps"])
