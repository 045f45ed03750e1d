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
deviation near 0.5 and the program's lie a few hundredths off, so a
greedy token is not always the reference's first: it is the one the
reference puts a little lower.

* ``GAP_TOL`` = 1.4e-4, on the mean, over the served tokens compared,
  of how far a served token's logit lies below the reference's best at
  its position (`gaps_below_best`, in standard deviations of the
  logits). Read on the chip at GPT-2 124M widths over 128 requests of
  a 45 s chat window, about 8,000 tokens (PERF.md section 2 has every
  reading): the program 5.4e-5 to 1.0e-4 over 25 seeds, 1.1-1.6% of
  its tokens not the reference's first; this reference in bfloat16 in
  the program's place (`CONTROL_DTYPE`) 1.8e-4 to 2.4e-4, only twice
  the program, whose matmuls are bfloat16 passes already; the program
  on its own int8 weights 9.3e-4 to 1.2e-3. The limit is 1.4 times the
  largest sound reading and fails both. The mean and not the widest
  gap: the mean grows with the square of the logits' error (more
  tokens flip, and each by more) and is steady over 8,000 tokens,
  while the widest gap of the program (0.012-0.038) and of the bfloat16
  control (0.019-0.048) overlap. A wrong position, page, layer or
  weight reads hundreds of times the limit; on the CPU at "highest"
  the program reads 0.
* ``LOSS_TOL`` = 0.08 absolute, for the first training step under AMP
  O2: the trainer reports the loss as bfloat16, whose spacing between 8
  and 16 is 0.0625, plus a little for bfloat16 activations. Measured
  on the chip (PR 23): 11.0 against 10.9877 and 10.9767 (two seeds).
"""
import math

import jax
import jax.numpy as jnp

GAP_TOL = 1.4e-4
LOSS_TOL = 0.08
CONTROL_DTYPE = jnp.bfloat16    # the nearest precision below float32


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


def forward(p, tokens, n_head, eps=1e-5, dtype=jnp.float32):
    """Logits [T, V] of one sequence of token ids [T]. `dtype` other
    than float32 is for the control alone: the same equations with
    parameters and activations in that type (`p` cast by the caller)."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        x = p["wte"][tokens] + p["wpe"][jnp.arange(T)]
        x = x.astype(dtype)
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


def gaps_below_best(logits, chosen):
    """How far the logit of `chosen[i]` lies below the best logit of
    row i, in standard deviations of `logits` [T, V]: [T] floats, 0
    where the chosen token is the best one."""
    logits = logits.astype(jnp.float32)
    at = jnp.take_along_axis(logits, chosen[:, None], axis=1)[:, 0]
    return (jnp.max(logits, axis=-1) - at) / jnp.std(logits)
