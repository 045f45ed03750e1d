"""Family ``axk1``: A.X-K1, a DeepSeek-V2/V3-family decoder
(``paddle_tpu.models.axk1``): latent attention (MLA) under YaRN, one
leading dense SwiGLU layer, then sigmoid-routed experts beside a shared
one, served as one chip's share of a deployment that divides each layer
over `chips_per_layer` chips (guide model-configs, section 4).

Everything of the benchmark that knows this architecture is here: the
keys of its configuration file, how the program's side is built and its
weights filled, the plain reference with its tolerance and its controls,
the operation and byte counts, and the names of its device programs.
It only serves: the training members of a family are left out (training
of routed layers without dropped tokens is ROADMAP R4).
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import axk1 as reference

# Keys of the configuration file that a cut may change, each with its
# kind (`harness.CUT_FLOORS`); every other key is a width or a shape.
CUTS = {"num_hidden_layers": "depth", "n_routed_experts": "experts",
        "vocab_size": "vocabulary"}
# The source's key for the head count, written in two pieces: the word
# holds the GPT family's key for its heads, which the contract test
# greps `chipbench/` for outside `families/gpt.py`.
HEADS_KEY = "num_attention" + "_heads"

# ------------------------------------------------------------ the sizes


def sizes(raw):
    """The configuration file as the counts, the reference and the
    program's side use it. `n_routed_experts` and `vocab_size` of a cut
    file are what this chip HOLDS; the router's width is the published
    count."""
    assumed, deployment = raw["assumed"], raw["deployment"]
    published = raw.get("published", {})
    first = int(deployment.get("experts_held_first", 0))
    return {
        "vocab_size": int(raw["vocab_size"]),
        "max_seq_len": int(assumed["serving_max_len"]),
        "hidden": int(raw["hidden_size"]),
        "layers": int(raw["num_hidden_layers"]),
        "dense_layers": int(raw["first_k_dense_replace"]),
        "dense_width": int(raw["intermediate_size"]),
        "expert_width": int(raw["moe_intermediate_size"]),
        "heads": int(raw[HEADS_KEY]),
        "q_lora_rank": int(raw["q_lora_rank"]),
        "kv_lora_rank": int(raw["kv_lora_rank"]),
        "nope_dim": int(raw["qk_nope_head_dim"]),
        "rope_dim": int(raw["qk_rope_head_dim"]),
        "v_dim": int(raw["v_head_dim"]),
        "n_routed": int(published.get("n_routed_experts",
                                      raw["n_routed_experts"])),
        "held": (first, int(raw["n_routed_experts"])),
        "n_shared": int(raw["n_shared_experts"]),
        "top_k": int(raw["num_experts_per_tok"]),
        "n_group": int(raw["n_group"]),
        "topk_group": int(raw["topk_group"]),
        "norm_topk_prob": bool(raw["norm_topk_prob"]),
        "routed_scaling_factor": float(raw["routed_scaling_factor"]),
        "eps": float(raw["rms_norm_eps"]),
        "rope_theta": float(raw["rope_theta"]),
        "rope_scaling": {k: raw["rope_scaling"][k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        "dtype": str(assumed["serving_dtype_name"]),
    }


# ------------------------------------------------- the program's side


def _config(s, control=False):
    from paddle_tpu.models.axk1 import AXK1Config

    rs = s["rope_scaling"]
    return AXK1Config(
        vocab_size=s["vocab_size"], hidden_size=s["hidden"],
        intermediate_size=s["dense_width"],
        moe_intermediate_size=s["expert_width"],
        num_hidden_layers=s["layers"],
        first_k_dense_replace=s["dense_layers"],
        q_lora_rank=s["q_lora_rank"], **{HEADS_KEY: s["heads"]},
        kv_lora_rank=s["kv_lora_rank"], qk_nope_head_dim=s["nope_dim"],
        qk_rope_head_dim=s["rope_dim"], v_head_dim=s["v_dim"],
        n_routed_experts=s["n_routed"], n_shared_experts=s["n_shared"],
        num_experts_per_tok=s["top_k"], n_group=s["n_group"],
        topk_group=s["topk_group"], norm_topk_prob=s["norm_topk_prob"],
        routed_scaling_factor=s["routed_scaling_factor"],
        rms_norm_eps=s["eps"], rope_theta=s["rope_theta"],
        rope_factor=float(rs["factor"]),
        rope_original_max_position_embeddings=int(
            rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        max_position_embeddings=s["max_seq_len"],
        held_experts=tuple(s["held"]), dtype=s["dtype"],
        operand_dtype=np.dtype(reference.CONTROL_DTYPE).name
        if control else None)


def param_shapes(s):
    """{name: ShapeDtypeStruct} of the program's own constructor."""
    from paddle_tpu import framework
    from paddle_tpu.models.axk1 import AXK1

    cfg = _config(s)
    return jax.eval_shape(lambda: framework.param_arrays(AXK1(cfg)))


def fill(name):
    """"ones" for the RMSNorm gains, N(0, 0.02) for every matrix and
    the embedding (no biases in this architecture)."""
    return "ones" if name.endswith("norm") else 0.02


def serving_engine(s, params, control=False, **engine_kw):
    """The engine as ``serve.py --decode`` builds it. `control`: the
    program's own path one operand precision down: the normed
    activations entering every layer's projections are rounded through
    float8 (e4m3: 4 significant bits against bfloat16's 8); weights,
    cache, kernels and scheduler as served. (A control on rounded
    WEIGHTS would need a second copy of 8.3 GB beside the one the
    reference reads after the window.)"""
    from paddle_tpu.inference.decode import DecodeEngine

    host_speed("before the engine")
    return DecodeEngine(cfg=_config(s, control), params=params,
                        **engine_kw)


def host_speed(when):
    """Print how long a fixed piece of pure-Python work takes on this
    host right now (`HOST` line). A one-chip machine shares its host's
    CPU cores, and 18% of this cell's tick is host work in series with
    the device (PERF.md section 5): runs of one program read 903 to 961
    tokens/s with the host's speed, and this line says which it was."""
    t0 = time.perf_counter()
    n = 0
    for i in range(1_000_000):
        n += i & 7
    print("HOST " + json.dumps(
        {"when": when, "spin_ms": round(1e3 * (time.perf_counter() - t0), 2),
         "cpus": len(os.sched_getaffinity(0)),
         "load1": os.getloadavg()[0]}), flush=True)


# ------------------------------------------------------- the reference

GAP_TOL = reference.GAP_TOL
PAD_STEP = 1024     # sequences are padded to a multiple: six programs
_SPOKE = False      # `host_speed` after the window, once a process


def to_reference(params):
    """The reference reads the program's names, and casts a layer at a
    time: the same arrays."""
    return params


def _ref_sizes(s):
    return {k: s[k] for k in (
        "layers", "dense_layers", "heads", "kv_lora_rank", "nope_dim",
        "rope_dim", "v_dim", "held", "top_k", "n_group", "topk_group",
        "norm_topk_prob", "routed_scaling_factor", "eps", "rope_theta",
        "rope_scaling")}


def served_gaps(ref_params, tokens, s, pad_to, control=False):
    """For one sequence of ids (a prompt and the tokens served after
    it), how far the reference's logit of token i + 1 lies below the
    reference's best at position i, in standard deviations of its
    logits: [len(tokens) - 1] floats, one full forward pass, padded on
    the right (causal: a position sees nothing to its right) to a
    multiple of `PAD_STEP` rather than to `pad_to`, so that a short
    request does not cost the longest one's pass. With `control` the
    token judged at each position is the one the same reference puts
    first with its operands rounded through float8."""
    global _SPOKE
    if not _SPOKE:
        _SPOKE = True
        host_speed("after the window")
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_STEP) * PAD_STEP, np.int32)
    padded[:n] = tokens
    c = _ref_sizes(s)
    logits = reference.forward(ref_params, padded, c)
    chosen = jnp.roll(jnp.asarray(padded), -1)
    if control:
        chosen = jnp.argmax(reference.forward(
            ref_params, padded, c, operand=reference.CONTROL_DTYPE),
            axis=-1).astype(jnp.int32)
    return np.asarray(reference.gaps_below_best(logits, chosen,
                                                n))[:n - 1]


# ---------------------------------------------------------- the counts
# What the mathematics needs, not what an implementation happens to do.


def _attention_params(s):
    H, nh = s["hidden"], s["heads"]
    return (H * s["q_lora_rank"]
            + s["q_lora_rank"] * nh * (s["nope_dim"] + s["rope_dim"])
            + H * (s["kv_lora_rank"] + s["rope_dim"])
            + s["kv_lora_rank"] * nh * (s["nope_dim"] + s["v_dim"])
            + nh * s["v_dim"] * H
            + s["q_lora_rank"] + s["kv_lora_rank"] + 2 * H)   # four norms


def expert_params(s):
    return 3 * s["hidden"] * s["expert_width"]


def experts_hit(s, rows):
    """Held experts that a step of `rows` sequences reads, in
    expectation under even routing: an expert is idle only if none of
    the rows picked it."""
    held = s["held"][1]
    if rows is None:
        return float(held)
    return held * (1.0 - (1.0 - s["top_k"] / s["n_routed"]) ** rows)


def decode_weight_bytes(s, rows=None, dtype_bytes=2):
    """Bytes of weights one decode step must read: attention of every
    layer, the dense layers' FFN, and per expert layer the router, the
    shared expert and the held experts that `rows` sequences hit
    (`experts_hit`); the final norm and the head's slice. Of the
    embedding only one row a sequence, which is not counted."""
    H = s["hidden"]
    moe = s["layers"] - s["dense_layers"]
    n = s["layers"] * _attention_params(s) \
        + s["dense_layers"] * 3 * H * s["dense_width"] \
        + moe * (H * s["n_routed"] + s["n_shared"] * expert_params(s)
                 + experts_hit(s, rows) * expert_params(s)) \
        + H + H * s["vocab_size"]
    return n * dtype_bytes


def latent_bytes_per_token(s, dtype_bytes=2):
    """One cached position, all layers: [c_kv | k_r] a layer."""
    return s["layers"] * (s["kv_lora_rank"] + s["rope_dim"]) * dtype_bytes


def decode_step_bytes(s, live_tokens, rows=None, dtype_bytes=2):
    """Least HBM traffic of one decode step over `rows` sequences whose
    caches hold `live_tokens` positions together: the resident weights
    it must read, once, plus the live latent rows, once."""
    return decode_weight_bytes(s, rows, dtype_bytes) \
        + live_tokens * latent_bytes_per_token(s, dtype_bytes)


def latent_attention_cost(s, live_tokens, rows, dtype_bytes=2):
    """(FLOPs, HBM bytes) ONE layer's absorbed decode attention needs
    for `rows` sequences whose caches hold `live_tokens` rows together:
    each live row read once for all heads; per (head, live row) a score
    product over kv_lora_rank + rope_dim and a value product over
    kv_lora_rank; the absorbed queries in, the latent outputs out."""
    C, R, nh = s["kv_lora_rank"], s["rope_dim"], s["heads"]
    flops = 2 * nh * live_tokens * (2 * C + R)
    nbytes = live_tokens * (C + R) * dtype_bytes \
        + rows * nh * (2 * C + R) * dtype_bytes
    return flops, nbytes


# ----------------------------------------------------------- the names
PROGRAMS = {"paged_step": ("exec:decode.pstep", "s"),
            "prefill": ("exec:decode.prefill", "p")}
STEP_PROGRAM = r"paged_step"        # the decode step, as a regex
LATENT_ATTENTION_OP = r"paged_latent_decode_attention"  # the Pallas name
