"""The plain reference: a pre-LN GPT decoder in straightforward
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``; no
cache, no kernels, no batching tricks. It imports nothing from
``paddle_tpu``.

Written from the published equations (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", section 2.3: layer
normalisation moved to the input of each sub-block, one more after the
last block; Vaswani et al. 2017 for the attention and feed-forward
sub-blocks; Brown et al. 2020 use the same architecture). Departures,
each because the program under test makes the same choice:

* GELU is the exact form x * Phi(x) (Hendrycks & Gimpel 2016), not the
  tanh approximation of OpenAI's released GPT-2 code;
* the output head is the transposed token embedding (tied), with no
  bias, as in GPT-2;
* weights are stored [in, out].

Parameters (`p`): ``wte`` [V, C], ``wpe`` [P, C], ``lnf_g``/``lnf_b``
[C], and per-layer stacks with a leading [L] axis: ``ln1_g ln1_b`` [L, C],
``w_qkv`` [L, C, 3C], ``b_qkv`` [L, 3C], ``w_proj`` [L, C, C], ``b_proj``
[L, C], ``ln2_g ln2_b`` [L, C], ``w_fc`` [L, C, 4C], ``b_fc`` [L, 4C],
``w_out`` [L, 4C, C], ``b_out`` [L, C].

TOLERANCE. The program serves float32 weights through XLA's *default*
matmul precision, which on a TPU rounds the operands of every float32
matmul to bfloat16 (one MXU pass); this reference runs six passes
("highest"). With random N(0, 0.02) weights the logits have a standard
deviation near 0.5, and 12 to 24 layers of one-pass matmuls move one by
a few hundredths. The comparison is max |program - reference| over max
|reference| across all compared positions:

* ``LOGIT_TOL`` = 0.02. Measured on the chip (PR 23, prefill + eight
  paged decode steps, several seeds): 0.0059 at GPT-2 124M widths and
  0.0065 to 0.0072 at GPT-3 1.3B widths, so the bound is about three
  times what one-pass float32 matmuls need. A wrong position, page,
  layer or weight moves logits by their own size (relative error near
  1); on the CPU at "highest" the same comparison reads 3e-7.
* ``LOSS_TOL`` = 0.08 absolute, for the first training step under AMP
  O2: the trainer reports the loss as bfloat16, whose spacing between 8
  and 16 is 0.0625, plus a little for bfloat16 activations. Measured
  on the chip (PR 23): 11.0 against 10.9877 and 10.9767 (two seeds).
"""
import math

import jax
import jax.numpy as jnp

LOGIT_TOL = 0.02
LOSS_TOL = 0.08


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def attention(x, w_qkv, b_qkv, w_proj, b_proj, n_head):
    T, C = x.shape
    D = C // n_head
    qkv = x @ w_qkv + b_qkv
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(T, n_head, D).transpose(1, 0, 2)
    k = k.reshape(T, n_head, D).transpose(1, 0, 2)
    v = v.reshape(T, n_head, D).transpose(1, 0, 2)
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(1, 0, 2).reshape(T, C) @ w_proj + b_proj


def forward(p, tokens, n_head, eps=1e-5):
    """Logits [T, V] of one sequence of token ids [T]."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        x = p["wte"][tokens] + p["wpe"][jnp.arange(T)]
        x = x.astype(jnp.float32)
        for i in range(p["w_qkv"].shape[0]):
            h = layer_norm(x, p["ln1_g"][i], p["ln1_b"][i], eps)
            x = x + attention(h, p["w_qkv"][i], p["b_qkv"][i],
                              p["w_proj"][i], p["b_proj"][i], n_head)
            h = layer_norm(x, p["ln2_g"][i], p["ln2_b"][i], eps)
            x = x + gelu(h @ p["w_fc"][i] + p["b_fc"][i]) @ p["w_out"][i] \
                + p["b_out"][i]
        x = layer_norm(x, p["lnf_g"], p["lnf_b"], eps)
        return x @ p["wte"].T


def loss(p, tokens, labels, n_head, eps=1e-5):
    """Mean next-token cross-entropy of one sequence (natural log)."""
    logits = forward(p, tokens, n_head, eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def relative_error(got, want):
    """max |got - want| / max |want|, as a Python float."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
