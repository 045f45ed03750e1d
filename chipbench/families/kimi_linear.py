"""Family ``kimi_linear``: Kimi-Linear-48B-A3B
(``paddle_tpu.models.kimi_linear``), a hybrid decoder of Kimi Delta
Attention (KDA: a recurrent state a stream) and latent attention (MLA,
NoPE: a latent page pool), three layers of the first to one of the
second, over sigmoid-routed experts with a bias-corrected selection,
served as one chip's share of a deployment that divides each layer over
`chips_per_layer` chips (guide model-configs, section 4).

Everything of the benchmark that knows this architecture is here: the
keys of its configuration file, how the program's side is built and its
weights filled, the plain reference with its tolerance and its two
controls, the operation and byte counts, and the names of its device
programs and kernels. It only serves.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import kimi_linear as reference

# Keys of the configuration file that a cut may change, each with its
# kind (`harness.CUT_FLOORS`); every other key is a width or a shape.
CUTS = {"num_hidden_layers": "depth", "num_experts": "experts",
        "vocab_size": "vocabulary"}
# The source's key for the head count, in two pieces (the contract
# test greps `chipbench/` for the GPT family's key, a part of it).
HEADS_KEY = "num_attention" + "_heads"

# ------------------------------------------------------------ the sizes


def sizes(raw):
    """The configuration file as the counts, the reference and the
    program's side use it. `num_experts` and `vocab_size` of a cut file
    are what this chip HOLDS; the router's width is the published
    count. The layer lists are the published ones, counted from 1: the
    family reads them up to the depth held."""
    assumed, deployment = raw["assumed"], raw["deployment"]
    published = raw.get("published", {})
    linear = raw["linear_attn_config"]
    layers = int(raw["num_hidden_layers"])
    first = int(deployment.get("experts_held_first", 0))
    kda = [i for i in linear["kda_layers"] if i <= layers]
    full = [i for i in linear["full_attn_layers"] if i <= layers]
    if sorted(kda + full) != list(range(1, layers + 1)):
        raise ValueError("kda_layers and full_attn_layers do not divide "
                         f"layers 1..{layers} between them")
    return {
        "vocab_size": int(raw["vocab_size"]),
        "max_seq_len": int(assumed["serving_max_len"]),
        "hidden": int(raw["hidden_size"]),
        "layers": layers,
        "kda_layers": tuple(kda), "full_attn_layers": tuple(full),
        "dense_layers": int(raw["first_k_dense_replace"]),
        "dense_width": int(raw["intermediate_size"]),
        "expert_width": int(raw["moe_intermediate_size"]),
        "heads": int(raw[HEADS_KEY]),
        "kv_lora_rank": int(raw["kv_lora_rank"]),
        "nope_dim": int(raw["qk_nope_head_dim"]),
        "rope_dim": int(raw["qk_rope_head_dim"]),
        "v_dim": int(raw["v_head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_head_dim": int(linear["head_dim"]),
        "conv_taps": int(linear["short_conv_kernel_size"]),
        "n_routed": int(published.get("num_experts", raw["num_experts"])),
        "held": (first, int(raw["num_experts"])),
        "n_shared": int(raw["num_shared_experts"]),
        "top_k": int(raw["num_experts_per_token"]),
        "n_group": int(raw["num_expert_group"]),
        "topk_group": int(raw["topk_group"]),
        "norm_topk_prob": bool(raw["moe_renormalize"]),
        "routed_scaling_factor": float(raw["routed_scaling_factor"]),
        "eps": float(raw["rms_norm_eps"]),
        "dtype": str(assumed["serving_dtype_name"]),
        "state_dtype": str(assumed["state_dtype_name"]),
    }


# ------------------------------------------------- the program's side


def _config(s, control=False):
    """The program's config; `control` puts it one precision down in
    the place `reference.CONTROL` names: float8 operands into every
    projection, or the recurrent state kept in bfloat16."""
    from paddle_tpu.models.kimi_linear import KimiLinearConfig

    low = {}
    if control and reference.CONTROL == "operand":
        low = {"operand_dtype": np.dtype(reference.CONTROL_DTYPE).name}
    elif control and reference.CONTROL == "state":
        low = {"state_dtype": np.dtype(reference.CONTROL_STATE_DTYPE).name}
    elif control:
        raise ValueError(f"reference.CONTROL = {reference.CONTROL!r}")
    return KimiLinearConfig(
        vocab_size=s["vocab_size"], hidden_size=s["hidden"],
        intermediate_size=s["dense_width"],
        moe_intermediate_size=s["expert_width"],
        num_hidden_layers=s["layers"],
        first_k_dense_replace=s["dense_layers"],
        **{HEADS_KEY: s["heads"]}, kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["nope_dim"], qk_rope_head_dim=s["rope_dim"],
        v_head_dim=s["v_dim"], num_experts=s["n_routed"],
        num_shared_experts=s["n_shared"], num_experts_per_token=s["top_k"],
        num_expert_group=s["n_group"], topk_group=s["topk_group"],
        moe_renormalize=s["norm_topk_prob"],
        routed_scaling_factor=s["routed_scaling_factor"],
        rms_norm_eps=s["eps"], kda_layers=s["kda_layers"],
        full_attn_layers=s["full_attn_layers"],
        kda_num_heads=s["kda_heads"], kda_head_dim=s["kda_head_dim"],
        short_conv_kernel_size=s["conv_taps"],
        max_position_embeddings=s["max_seq_len"],
        held_experts=tuple(s["held"]), dtype=s["dtype"],
        **{"state_dtype": s["state_dtype"], **low})


def param_shapes(s):
    """{name: ShapeDtypeStruct} of the program's own constructor."""
    from paddle_tpu import framework
    from paddle_tpu.models.kimi_linear import KimiLinear

    cfg = _config(s)
    return jax.eval_shape(lambda: framework.param_arrays(KimiLinear(cfg)))


DT_RANGE = (0.001, 0.1)     # softplus(dt_bias), log-uniform
A_RANGE = (1.0, 16.0)       # exp(A_log), uniform


def fill(name):
    """"ones" for the RMSNorm gains; N(0, 0.3) for the convolutions'
    taps; a standard normal for `A_log` and `dt_bias`, which
    `decay_params` maps to their ranges; N(0, 0.005) for the selection
    bias (about the distance between neighbouring scores near the
    eighth best of 256, so that the picks differ from plain top-8 for
    many tokens and the bias decides none alone; the load's skew on the
    chip, the fullest held expert at 6.8 to 9.2 times the mean, is the
    random model's and not the bias's: it read 6.8 at N(0, 0.02) too);
    N(0, 0.02) for every matrix and the embedding."""
    if name.endswith("norm"):
        return "ones"
    if name.endswith("conv1d"):
        return 0.3
    if name.endswith(("A_log", "dt_bias")):
        return 1.0
    if name.endswith("e_score_correction_bias"):
        return 0.005
    return 0.02


@jax.jit
def _to_ranges(a_log, dt_bias):
    u = jax.scipy.special.ndtr(a_log.astype(jnp.float32))
    a = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * u
    lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
    dt = jnp.exp(lo + (hi - lo)
                 * jax.scipy.special.ndtr(dt_bias.astype(jnp.float32)))
    return jnp.log(a), jnp.log(jnp.expm1(dt))


def decay_params(params):
    """The weights as the benchmark made them, with the two decay
    vectors of each KDA layer drawn as the family's published
    initialisation draws them (`weights.make_params` fills normals
    only): a standard normal z becomes, through its distribution
    function, ``A_log = ln U(1, 16)`` and ``dt_bias = softplus^-1(dt)``,
    dt log-uniform in [0.001, 0.1]. Program and reference both read
    the result: the same arrays."""
    out = dict(params)
    for name in params:
        if name.endswith("self_attn.A_log"):
            dt = name[:-len("A_log")] + "dt_bias"
            out[name], out[dt] = _to_ranges(params[name], params[dt])
    return out


def serving_engine(s, params, control=False, **engine_kw):
    """The engine as ``serve.py --decode`` builds it. `control`: the
    program's own path one precision down (`_config`): weights, cache,
    kernels and scheduler as served."""
    from paddle_tpu.inference.decode import DecodeEngine

    return DecodeEngine(cfg=_config(s, control), params=decay_params(params),
                        **engine_kw)


# ------------------------------------------------------- the reference

GAP_TOL = reference.GAP_TOL
PAD_STEP = 1024     # sequences are padded to a multiple: three programs


def to_reference(params):
    """The reference reads the program's names, and casts a layer at a
    time: the same arrays, the decay vectors in their ranges."""
    return decay_params(params)


def _ref_sizes(s):
    c = {k: s[k] for k in (
        "layers", "dense_layers", "heads", "kv_lora_rank", "nope_dim",
        "rope_dim", "v_dim", "kda_heads", "kda_head_dim", "held", "top_k",
        "norm_topk_prob", "routed_scaling_factor", "eps")}
    c["kda"] = tuple(i + 1 in s["kda_layers"] for i in range(s["layers"]))
    return c


def served_gaps(ref_params, tokens, s, pad_to, control=False):
    """For one sequence of ids (a prompt and the tokens served after
    it), how far the reference's logit of token i + 1 lies below the
    reference's best at position i, in standard deviations of its
    logits: [len(tokens) - 1] floats, one full forward pass, padded on
    the right (causal: a position sees nothing to its right) to a
    multiple of `PAD_STEP`. With `control` the token judged at each
    position is the one the same reference puts first one precision
    down, in the place `reference.CONTROL` names."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_STEP) * PAD_STEP, np.int32)
    padded[:n] = tokens
    c = _ref_sizes(s)
    logits = reference.forward(ref_params, padded, c)
    chosen = jnp.roll(jnp.asarray(padded), -1)
    if control:
        low = {"operand": reference.CONTROL_DTYPE} \
            if reference.CONTROL == "operand" \
            else {"state": reference.CONTROL_STATE_DTYPE}
        chosen = jnp.argmax(reference.forward(ref_params, padded, c, **low),
                            axis=-1).astype(jnp.int32)
    return np.asarray(reference.gaps_below_best(logits, chosen,
                                                n))[:n - 1]


# ---------------------------------------------------------- the counts
# What the mathematics needs, not what an implementation happens to do.


def kda_params(s):
    """One KDA mixer: three projections and their convolutions, the
    decay's and the gate's low-rank pairs, beta, the output norm and
    projection, `A_log`, `dt_bias`."""
    H, W, d = s["hidden"], s["kda_heads"] * s["kda_head_dim"], \
        s["kda_head_dim"]
    return 3 * H * W + 3 * W * s["conv_taps"] + 2 * (H * d + d * W) \
        + W + s["kda_heads"] + H * s["kda_heads"] + d + W * H


def mla_params(s):
    H, nh = s["hidden"], s["heads"]
    return (H * nh * (s["nope_dim"] + s["rope_dim"])
            + H * (s["kv_lora_rank"] + s["rope_dim"])
            + s["kv_lora_rank"] * nh * (s["nope_dim"] + s["v_dim"])
            + nh * s["v_dim"] * H + s["kv_lora_rank"])


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
    """Bytes of weights one decode step must read: every layer's mixer
    and its two norms, the dense layers' FFN, and per expert layer the
    router with its selection bias, the shared expert and the held
    experts that `rows` sequences hit; the final norm and the head's
    slice. Of the embedding only one row a sequence, not counted."""
    H = s["hidden"]
    n_kda, n_mla = len(s["kda_layers"]), len(s["full_attn_layers"])
    moe = s["layers"] - s["dense_layers"]
    n = n_kda * kda_params(s) + n_mla * mla_params(s) + s["layers"] * 2 * H \
        + s["dense_layers"] * 3 * H * s["dense_width"] \
        + moe * (H * s["n_routed"] + s["n_routed"]
                 + s["n_shared"] * expert_params(s)
                 + experts_hit(s, rows) * expert_params(s)) \
        + H + H * s["vocab_size"]
    return n * dtype_bytes


def latent_bytes_per_token(s, dtype_bytes=2):
    """One cached position: [c_kv | k_r] a latent-attention layer."""
    return len(s["full_attn_layers"]) \
        * (s["kv_lora_rank"] + s["rope_dim"]) * dtype_bytes


def kda_state_bytes(s):
    """One stream's recurrent state of ONE KDA layer (float32)."""
    return s["kda_heads"] * s["kda_head_dim"] ** 2 \
        * np.dtype(s["state_dtype"]).itemsize


def decode_step_bytes(s, live_tokens, rows=None, dtype_bytes=2):
    """Least HBM traffic of one decode step over `rows` sequences whose
    caches hold `live_tokens` positions together: the weights it must
    read, once; the live latent rows of the MLA layers, once; each
    row's recurrent state of every KDA layer, read once and written
    once."""
    return decode_weight_bytes(s, rows, dtype_bytes) \
        + live_tokens * latent_bytes_per_token(s, dtype_bytes) \
        + (rows or 0) * len(s["kda_layers"]) * 2 * kda_state_bytes(s)


def latent_attention_cost(s, live_tokens, rows, dtype_bytes=2):
    """(FLOPs, HBM bytes) ONE layer's absorbed decode attention needs
    (as family ``axk1``'s: each live row read once for all heads)."""
    C, R, nh = s["kv_lora_rank"], s["rope_dim"], s["heads"]
    flops = 2 * nh * live_tokens * (2 * C + R)
    nbytes = live_tokens * (C + R) * dtype_bytes \
        + rows * nh * (2 * C + R) * dtype_bytes
    return flops, nbytes


def kda_step_cost(s, rows):
    """(FLOPs, HBM bytes) ONE layer's one-token state update needs for
    `rows` sequences: each row's state read once and written once; q,
    k, the decay (a key channel each), v and beta in, the outputs out
    (float32); per (head, key, value) a decay, a prediction, an update
    and an output: seven operations. The convolution's rows are the
    step's, not this kernel's, and are not counted."""
    H, d = s["kda_heads"], s["kda_head_dim"]
    flops = rows * H * 7 * d * d
    nbytes = rows * (2 * kda_state_bytes(s) + H * (5 * d + 1) * 4)
    return flops, nbytes


# ----------------------------------------------------------- the names
PROGRAMS = {"paged_step": ("exec:decode.pstep", "s"),
            "prefill": ("exec:decode.prefill", "p")}
STEP_PROGRAM = r"paged_step"        # the decode step, as a regex
LATENT_ATTENTION_OP = r"paged_latent_decode_attention"  # the Pallas names
KDA_STEP_OP = r"kda_decode_step"
