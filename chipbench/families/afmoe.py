"""Family ``afmoe``: Arcee Trinity (``paddle_tpu.models.afmoe``), a
decoder of window and full attention mixed, three layers of the first
to one of the second, over grouped-query heads with a gated output and
sandwich norms, then sigmoid-routed experts with a bias-corrected
selection; served as one chip's share of a deployment that divides
each layer over `chips_per_layer` chips and its depth over pipeline
stages (guide model-configs, section 4).

Everything of the benchmark that knows this architecture is here: the
keys of its configuration file, how the program's side is built and its
weights filled, the plain reference with its tolerance and its two
controls, the operation and byte counts, and the names of its device
programs and kernels. It only serves.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import afmoe as reference

# Keys of the configuration file that a cut may change, each with its
# kind (`harness.CUT_FLOORS`); every other key is a width or a shape.
CUTS = {"num_hidden_layers": "depth", "num_experts": "experts",
        "vocab_size": "vocabulary"}
# The source's key for the head count, in two pieces (the contract
# test greps `chipbench/` for the GPT family's key, a part of it).
HEADS_KEY = "num_attention" + "_heads"
SLIDING = "sliding_attention"

# ------------------------------------------------------------ the sizes


def sizes(raw):
    """The configuration file as the counts, the reference and the
    program's side use it. `num_experts` and `vocab_size` of a cut file
    are what this chip HOLDS; the router's width is the published
    count. `layer_types` and `num_dense_layers` are the published ones:
    the family reads them at the layers `deployment.layers_held` names
    (all of them where the key is absent)."""
    assumed, deployment = raw["assumed"], raw["deployment"]
    published = raw.get("published", {})
    layers = int(raw["num_hidden_layers"])
    held = [int(i) for i in deployment.get("layers_held", range(layers))]
    dense = sum(i < int(raw["num_dense_layers"]) for i in held)
    if len(held) != layers or held != list(range(held[0], held[0] + layers)):
        raise ValueError(f"layers_held {held} is not {layers} layers in a row")
    if dense != int(deployment.get("leading_dense_layers", dense)):
        raise ValueError(f"{dense} of the layers held are dense, not "
                         f"leading_dense_layers")
    first = int(deployment.get("experts_held_first", 0))
    return {
        "vocab_size": int(raw["vocab_size"]),
        "max_seq_len": int(assumed["serving_max_len"]),
        "hidden": int(raw["hidden_size"]),
        "layers": layers,
        "layer_types": tuple(raw["layer_types"][i] for i in held),
        "dense_layers": dense,
        "dense_width": int(raw["intermediate_size"]),
        "expert_width": int(raw["moe_intermediate_size"]),
        "heads": int(raw[HEADS_KEY]),
        "kv_heads": int(raw["num_key_value_heads"]),
        "head_dim": int(raw["head_dim"]),
        "window": int(raw["sliding_window"]),
        "rope_theta": float(raw["rope_theta"]),
        "n_routed": int(published.get("num_experts", raw["num_experts"])),
        "held": (first, int(raw["num_experts"])),
        "n_shared": int(raw["num_shared_experts"]),
        "top_k": int(raw["num_experts_per_tok"]),
        "n_group": int(raw["n_group"]),
        "topk_group": int(raw["topk_group"]),
        "route_norm": bool(raw["route_norm"]),
        "route_scale": float(raw["route_scale"]),
        "mup": bool(raw["mup_enabled"]),
        "eps": float(raw["rms_norm_eps"]),
        "dtype": str(assumed["serving_dtype_name"]),
    }


def window_layers(s):
    return sum(t == SLIDING for t in s["layer_types"])


def full_layers(s):
    return s["layers"] - window_layers(s)


# ------------------------------------------------- the program's side


def _config(s, control=False):
    """The program's config; `control` puts it in the place
    `reference.CONTROL` names: float8 operands into every projection,
    or the window layers' ring kept at `CONTROL_WINDOW` rows."""
    from paddle_tpu.models.afmoe import AfmoeConfig

    low = {"sliding_window": s["window"]}
    if control and reference.CONTROL == "operand":
        low["operand_dtype"] = np.dtype(reference.CONTROL_DTYPE).name
    elif control and reference.CONTROL == "window":
        low["sliding_window"] = reference.CONTROL_WINDOW
    elif control:
        raise ValueError(f"reference.CONTROL = {reference.CONTROL!r}")
    return AfmoeConfig(
        vocab_size=s["vocab_size"], hidden_size=s["hidden"],
        intermediate_size=s["dense_width"],
        moe_intermediate_size=s["expert_width"],
        num_hidden_layers=s["layers"], num_dense_layers=s["dense_layers"],
        **{HEADS_KEY: s["heads"]}, num_key_value_heads=s["kv_heads"],
        head_dim=s["head_dim"], layer_types=s["layer_types"],
        num_experts=s["n_routed"], num_shared_experts=s["n_shared"],
        num_experts_per_tok=s["top_k"], n_group=s["n_group"],
        topk_group=s["topk_group"], route_norm=s["route_norm"],
        route_scale=s["route_scale"], mup_enabled=s["mup"],
        rms_norm_eps=s["eps"], rope_theta=s["rope_theta"],
        max_position_embeddings=s["max_seq_len"],
        held_experts=tuple(s["held"]), dtype=s["dtype"], **low)


def param_shapes(s):
    """{name: ShapeDtypeStruct} of the program's own constructor."""
    from paddle_tpu import framework
    from paddle_tpu.models.afmoe import Afmoe

    cfg = _config(s)
    return jax.eval_shape(lambda: framework.param_arrays(Afmoe(cfg)))


def fill(name):
    """"ones" for every RMSNorm gain (the four of a layer, q_norm,
    k_norm, the last: "depth-scaled" in the published description is
    read as an initialisation of the gains); N(0, 0.005) for the
    selection bias (as family ``kimi_linear``: about the distance
    between neighbouring scores near the last pick, so that the picks
    differ from the plain best for many tokens and the bias decides
    none alone); N(0, 0.02) for every matrix and the embedding."""
    if name.endswith(("norm", "layernorm")):
        return "ones"
    if name.endswith("e_score_correction_bias"):
        return 0.005
    return 0.02


def serving_engine(s, params, control=False, **engine_kw):
    """The engine as ``serve.py --decode`` builds it. `control`: the
    program's own path under the control `_config` names: weights,
    cache, kernels and scheduler as served."""
    from paddle_tpu.inference.decode import DecodeEngine

    return DecodeEngine(cfg=_config(s, control), params=params, **engine_kw)


# ------------------------------------------------------- the reference

GAP_TOL = reference.GAP_TOL
# Sequences are padded to a multiple of this (of the power of two that
# holds the traffic's longest request, where that is less): two
# programs a block of the reference. At 2,048 the mix's five lengths
# made five, and with nothing compiled the reference took 729 s of
# which the last run of the call, everything cached, took 32 (my chip
# run, PR 35).
PAD_STEP = 8192
# Of the requests a window finished the reference judges one in
# `CHECK_ONE_IN`, chosen by the request's own ids, and every request of
# the traffic's longest kind: 15-25 of the 80-90 a window finishes,
# 3-5 thousand served tokens, so that the reference stays near a
# minute at 9-16 thousand tokens a pass. 1: every request (the tests).
CHECK_ONE_IN = 4


def to_reference(params):
    """The reference reads the program's names, and casts a layer at a
    time: the same arrays."""
    return params


def _ref_sizes(s):
    c = {k: s[k] for k in (
        "layers", "dense_layers", "heads", "kv_heads", "head_dim", "window",
        "rope_theta", "held", "top_k", "route_norm", "route_scale", "mup",
        "eps")}
    c["sliding"] = tuple(t == SLIDING for t in s["layer_types"])
    return c


def judged(tokens, pad_to):
    """Whether the reference judges this request (`CHECK_ONE_IN`)."""
    if len(tokens) >= pad_to or CHECK_ONE_IN <= 1:
        return True
    head = np.asarray(tokens[:16], np.int64).tobytes()
    return zlib.crc32(head) % CHECK_ONE_IN == 0


def served_gaps(ref_params, tokens, s, pad_to, control=False):
    """For one sequence of ids (a prompt and the tokens served after
    it), how far the reference's logit of token i + 1 lies below the
    reference's best at position i, in standard deviations of its
    logits: [len(tokens) - 1] floats, one full forward pass, padded on
    the right (causal: a position sees nothing to its right) to a
    multiple of `PAD_STEP`; none for a request that `judged` leaves
    out. With `control` the token judged at each position is the one
    the same reference puts first under the control `reference.CONTROL`
    names."""
    n = len(tokens)
    if not judged(tokens, pad_to):
        return np.zeros(0, np.float32)
    step = min(PAD_STEP, reference._bucket(pad_to))
    padded = np.zeros(-(-n // step) * step, np.int32)
    padded[:n] = tokens
    c = _ref_sizes(s)
    logits = reference.forward(ref_params, padded, c)
    chosen = jnp.roll(jnp.asarray(padded), -1)
    if control:
        low = {"operand": reference.CONTROL_DTYPE} \
            if reference.CONTROL == "operand" \
            else {"window": reference.CONTROL_WINDOW}
        chosen = jnp.argmax(reference.forward(ref_params, padded, c, **low),
                            axis=-1).astype(jnp.int32)
    return np.asarray(reference.gaps_below_best(logits, chosen,
                                                n))[:n - 1]


# ---------------------------------------------------------- the counts
# What the mathematics needs, not what an implementation happens to do.


def attention_params(s):
    """One layer's attention: W_q, W_g and W_o (hidden x heads x
    head_dim each), W_k and W_v (hidden x kv heads x head_dim), and the
    two per-head gains."""
    H, D = s["hidden"], s["head_dim"]
    return 3 * H * s["heads"] * D + 2 * H * s["kv_heads"] * D + 2 * D


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
    """Bytes of weights one decode step must read: every layer's
    attention and its four norms, the dense layers' FFN, and per expert
    layer the router with its selection bias, the shared expert and
    the held experts that `rows` sequences hit; the final norm and the
    head's slice. Of the embedding only one row a sequence, not
    counted."""
    H = s["hidden"]
    moe = s["layers"] - s["dense_layers"]
    n = s["layers"] * (attention_params(s) + 4 * H) \
        + s["dense_layers"] * 3 * H * s["dense_width"] \
        + moe * (H * s["n_routed"] + s["n_routed"]
                 + s["n_shared"] * expert_params(s)
                 + experts_hit(s, rows) * expert_params(s)) \
        + H + H * s["vocab_size"]
    return n * dtype_bytes


def kv_row_bytes(s, dtype_bytes=2):
    """One cached position of ONE layer: a K row and a V row."""
    return 2 * s["kv_heads"] * s["head_dim"] * dtype_bytes


def decode_step_bytes(s, live_tokens, rows=None, dtype_bytes=2):
    """Least HBM traffic of one decode step over `rows` sequences whose
    caches hold `live_tokens` positions together: the weights it must
    read, once; the full layers' live rows, once; and of each window
    layer the rows inside the window. The reader hands MEANS, so the
    window layers are counted at ``min(live_tokens / rows, window)``
    rows a sequence: a request shorter than the window among longer
    ones (the long-document mix's fifth, 1.5k) is then counted at the
    full window, which over-counts by under 2% of the step's bytes."""
    per = live_tokens / rows if rows else 0.0
    windowed = min(per, s["window"]) * (rows or 0)
    return decode_weight_bytes(s, rows, dtype_bytes) \
        + (full_layers(s) * live_tokens + window_layers(s) * windowed) \
        * kv_row_bytes(s, dtype_bytes)


def gqa_attention_cost(s, full_rows, window_rows, rows, dtype_bytes=2):
    """(FLOPs, HBM bytes) the decode attention of ONE step needs, all
    layers: each live K and V row read once for all heads (a full layer
    `full_rows` in all, the contexts' sum; a window layer `window_rows`,
    the sum of ``min(context, window)``), the queries in and the
    outputs out; two products of head_dim a query head a row."""
    attended = full_layers(s) * full_rows + window_layers(s) * window_rows
    flops = 4 * s["heads"] * s["head_dim"] * attended
    nbytes = attended * kv_row_bytes(s, dtype_bytes) \
        + s["layers"] * rows * 2 * s["heads"] * s["head_dim"] * dtype_bytes
    return flops, nbytes


def band_pairs(tokens, window=None):
    """(query, key) pairs a causal mask keeps over `tokens` positions,
    under `window` those no further back than it."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def prefill_flash_flops(s, tokens):
    """FLOPs the attention of ONE prefill of `tokens` positions needs,
    all layers: two products of head_dim a query head a kept pair; a
    full layer keeps the causal triangle, a window layer the band."""
    pairs = full_layers(s) * band_pairs(tokens) \
        + window_layers(s) * band_pairs(tokens, s["window"])
    return 4 * s["heads"] * s["head_dim"] * pairs


# ----------------------------------------------------------- the names
PROGRAMS = {"paged_step": ("exec:decode.pstep", "s"),
            "prefill": ("exec:decode.prefill", "p")}
STEP_PROGRAM = r"paged_step"        # the decode step, as a regex
# The Pallas names, as regexes on a device operation's text: anchored,
# because the text of an operation that CONSUMES a kernel's result
# names the kernel too
GQA_ATTENTION_OP = r"^%?paged_gqa_decode_attention"
FLASH_FORWARD_OP = r"^%?flash_attention_fwd"
