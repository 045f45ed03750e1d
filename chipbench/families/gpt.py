"""Family ``gpt``: the pre-LN GPT decoder with a tied head
(``paddle_tpu.models.gpt``: GPT-2, GPT-3).

Everything in the benchmark that knows this architecture is here, and
nothing else under ``chipbench/`` may: the keys of its configuration
file, how the program's side is built and its weights filled, the plain
reference with its parameter map and tolerances, the operation and byte
counts, and the names of its device programs. A configuration names its
family by the key ``"family"`` of its file; ``harness.load_family``
finds ``families/<family>.py``. A new architecture is a new file beside
this one with the same members (PERF.md section 4 lists them).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import formulas
from chipbench.reference import gpt as reference

# Keys of the configuration file that a cut may change, each with its
# kind (`harness.CUT_FLOORS`); every other key is a width or a shape
# and is never cut. The heads are not among them: this file states the
# width of one as n_embd / n_head, so fewer heads would be wider ones.
CUTS = {"n_layer": "depth"}

# ------------------------------------------------------------ the sizes


def sizes(raw):
    """The configuration file as the counts and the program's side use
    it. Every family's sizes have ``vocab_size``: the ids the traffic
    draws from."""
    return {
        "vocab_size": int(raw["assumed"]["padded_vocab_size"]),
        "max_seq_len": int(raw["n_positions"]),
        "hidden": int(raw["n_embd"]),
        "layers": int(raw["n_layer"]),
        "heads": int(raw["n_head"]),
        "eps": float(raw["layer_norm_epsilon"]),
    }


# ------------------------------------------------- the program's side


def _config(s):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=s["vocab_size"], max_seq_len=s["max_seq_len"],
                     hidden=s["hidden"], layers=s["layers"], heads=s["heads"])


def param_shapes(s):
    """{name: ShapeDtypeStruct} of the program's own constructor."""
    from paddle_tpu import framework
    from paddle_tpu.models.gpt import GPT

    cfg = _config(s)
    return jax.eval_shape(lambda: framework.param_arrays(GPT(cfg)))


def fill(name):
    """How a parameter is filled from the seed, by its name: "zeros"
    (biases), "ones" (layer-norm gains) or the standard deviation of a
    centred normal (every matrix and embedding: 0.02)."""
    if name.endswith(".bias"):
        return "zeros"
    if name.split(".")[-2].startswith("ln"):
        return "ones"
    return 0.02


def serving_engine(s, params, control=False, **engine_kw):
    """The engine as ``serve.py --decode`` builds it (default slot
    sizing, page size and prefix cache unless `engine_kw` says).
    `control`: the program's own path one precision down switched on,
    its int8 weights (``quant.ptq``), for `chipbench/control.py`."""
    from paddle_tpu.inference.decode import DecodeEngine

    if control:
        from paddle_tpu.quant.ptq import quantize_params

        params = quantize_params(params)
    return DecodeEngine(cfg=_config(s), params=params, eps=s["eps"],
                        **engine_kw)


def training_net(s):
    """(net, params): the layer ``Model.fit`` trains, forward(ids,
    labels) -> loss, built by the program's own seeded constructor, and
    its parameters under the program's names (for `to_reference`)."""
    import paddle_tpu.nn as nn
    from paddle_tpu import framework
    from paddle_tpu.models.gpt import GPT

    class LMLoss(nn.Layer):
        """forward(ids, labels) -> the GPT's LM loss."""

        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, ids, labels):
            return self.m.loss(ids, labels)

        def param_shardings(self, params, mesh_axis_tp="tp"):
            inner = self.m.param_shardings(
                {k[len("m."):]: v for k, v in params.items()},
                mesh_axis_tp=mesh_axis_tp)
            return {"m." + k: spec for k, spec in inner.items()}

    net = LMLoss(GPT(_config(s)))
    net.train()
    return net, {k[len("m."):]: v
                 for k, v in framework.param_arrays(net).items()}


# ------------------------------------------------------- the reference

GAP_TOL = reference.GAP_TOL
LOSS_TOL = reference.LOSS_TOL

# program parameter name -> reference parameter name
_REF_NAMES = {
    "wte.weight": "wte", "wpe.weight": "wpe",
    "ln_f.weight": "lnf_g", "ln_f.bias": "lnf_b",
    "blocks.ln1.weight": "ln1_g", "blocks.ln1.bias": "ln1_b",
    "blocks.attn.qkv.weight": "w_qkv", "blocks.attn.qkv.bias": "b_qkv",
    "blocks.attn.proj.weight": "w_proj", "blocks.attn.proj.bias": "b_proj",
    "blocks.ln2.weight": "ln2_g", "blocks.ln2.bias": "ln2_b",
    "blocks.fc1.weight": "w_fc", "blocks.fc1.bias": "b_fc",
    "blocks.fc2.weight": "w_out", "blocks.fc2.bias": "b_out",
}

_loss = jax.jit(reference.loss, static_argnums=(3, 4))


def to_reference(params):
    """The program's scan-stacked parameter dict under the reference's
    names (same arrays, float32)."""
    return {ref: jnp.asarray(params[name], jnp.float32)
            for name, ref in _REF_NAMES.items()}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _gaps(p, tokens, heads, eps, control):
    logits = reference.forward(p, tokens, heads, eps)
    chosen = jnp.roll(tokens, -1)
    if control:
        low = jax.tree_util.tree_map(
            lambda a: a.astype(reference.CONTROL_DTYPE), p)
        chosen = jnp.argmax(reference.forward(
            low, tokens, heads, eps, reference.CONTROL_DTYPE), axis=-1)
    return reference.gaps_below_best(logits, chosen)


def served_gaps(ref_params, tokens, s, pad_to, control=False):
    """For one sequence of ids (a prompt and the tokens served after
    it), how far the reference's logit of token i + 1 lies below the
    reference's best logit at position i, in standard deviations of
    its logits: [len(tokens) - 1] floats, one full forward pass. The
    sequence is padded on the right to `pad_to` (causal attention: a
    position sees nothing to its right), so that one program serves
    every length of a mix. With `control`, the token judged at each
    position is not the next one of `tokens` but the one the same
    reference puts first when it computes in the nearest precision
    below the configuration's (bfloat16 for float32): what a run would
    read if the program were that."""
    n = len(tokens)
    padded = np.zeros(max(int(pad_to), n), np.int32)
    padded[:n] = tokens
    return np.asarray(_gaps(ref_params, jnp.asarray(padded), s["heads"],
                            s["eps"], bool(control)))[:n - 1]


def reference_loss(ref_params, ids, labels, s):
    """Mean next-token cross-entropy of one sequence, as a float."""
    return float(_loss(ref_params, jnp.asarray(ids), jnp.asarray(labels),
                       s["heads"], s["eps"]))


# ---------------------------------------------------------- the counts
# What the mathematics needs, not what an implementation happens to do:
# recomputed operations and padded or re-laid-out bytes do not count.
# ``train_flops_per_token`` is a copy of ``GPT.flops_per_token``
# (6N + 12*L*H*T), kept here so that the yardstick does not move with
# the model file.


def param_count(s):
    """Parameters of the pre-LN GPT with a tied head (ffn is 4x
    hidden)."""
    V, P, C, L = s["vocab_size"], s["max_seq_len"], s["hidden"], s["layers"]
    F = 4 * C
    per_block = (C * 3 * C + 3 * C) + (C * C + C) + (C * F + F) \
        + (F * C + C) + 4 * C
    return V * C + P * C + L * per_block + 2 * C


def train_flops_per_token(s, seq_len):
    """Forward + backward FLOPs per trained token: 6 per parameter for
    the weight matmuls, plus the attention score and value matmuls at
    12 * layers * hidden * seq_len (2*T*hidden each, forward; x3 with
    the backward pass)."""
    return 6 * param_count(s) + 12 * s["layers"] * s["hidden"] * seq_len


def flash_attention_costs(s, batch, seq_len):
    """[(FLOPs, HBM bytes)] of the two flash-attention calls one layer
    makes in a train step, forward then backward (causal, bfloat16
    operands under AMP O2)."""
    return [formulas.flash_attention_cost(
        batch, s["heads"], seq_len, s["hidden"] // s["heads"], causal=True,
        backward=backward) for backward in (False, True)]


def decode_weight_bytes(s, dtype_bytes=4):
    """Bytes of weights one decode step must read: every block and the
    tied head (the whole embedding matrix); of the position table only
    one row per sequence, which is not counted."""
    return (param_count(s) - s["max_seq_len"] * s["hidden"]) * dtype_bytes


def kv_bytes_per_token(s, dtype_bytes=4):
    """K and V of one cached position, all layers."""
    return s["layers"] * 2 * s["hidden"] * dtype_bytes


def decode_step_bytes(s, live_tokens, rows=None, dtype_bytes=4):
    """Least HBM traffic of one decode step over `rows` sequences whose
    caches hold `live_tokens` positions together: the weights once,
    plus the live K/V rows once. A dense model reads every weight
    whatever its rows are, so `rows` moves nothing here; it is what a
    family with experts counts the experts hit from."""
    return decode_weight_bytes(s, dtype_bytes) \
        + live_tokens * kv_bytes_per_token(s, dtype_bytes)


# ----------------------------------------------------------- the names
# Device programs of the serving engine (their names on the trace's
# ``XLA Modules`` line): the ring event each one's call leaves, and the
# letter it has in the ``IDLE`` line's two sequences.
PROGRAMS = {"paged_step": ("exec:decode.pstep", "s"),
            "prefill": ("exec:decode.prefill", "p")}
STEP_PROGRAM = r"paged_step"        # the decode step, as a regex
