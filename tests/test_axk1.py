"""A.X-K1 (`models.axk1`) at toy widths on the CPU: the served path
(prefill into latent pages, then the absorbed paged step, through
`DecodeEngine`) against the benchmark's plain reference; the pieces
(YaRN, absorbed against expanded MLA, the latent kernel, the routed
layer and its share); what the model kind refuses; its artifact."""
import functools
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import framework
from paddle_tpu.inference import model_kinds
from paddle_tpu.inference.decode import (DecodeEngine, SpecDecodeEngine,
                                         _load_decode_artifact,
                                         load_for_decode, save_for_decode)
from paddle_tpu.inference.errors import (ERR_FAILED_PRECONDITION,
                                         TypedServeError)
from paddle_tpu.models import axk1
from paddle_tpu.models.axk1 import AXK1, AXK1Config, axk1_tiny
from paddle_tpu.models.gpt import GPT, gpt_tiny
from paddle_tpu.nn.layer import moe
from paddle_tpu.ops.pallas import latent_attention as la


def reference():
    from chipbench.reference import axk1 as ref
    return ref


def ref_sizes(cfg):
    """The reference's sizes for a program config (what the benchmark's
    family hands it)."""
    return {"layers": cfg.num_hidden_layers,
            "dense_layers": cfg.first_k_dense_replace,
            "heads": cfg.num_attention_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "nope_dim": cfg.qk_nope_head_dim, "rope_dim": cfg.qk_rope_head_dim,
            "v_dim": cfg.v_head_dim, "held": cfg.held_experts,
            "top_k": cfg.num_experts_per_tok, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {
                "factor": cfg.rope_factor,
                "original_max_position_embeddings":
                    cfg.rope_original_max_position_embeddings,
                "beta_fast": cfg.rope_beta_fast,
                "beta_slow": cfg.rope_beta_slow,
                "mscale": cfg.rope_mscale,
                "mscale_all_dim": cfg.rope_mscale_all_dim}}


def build(seed=0, **kw):
    paddle.seed(seed)
    model = AXK1(axk1_tiny(**kw))
    return model, framework.param_arrays(model)


# ------------------------------------------------------------- rotary


def test_yarn_frequencies_and_scale_against_hand_computed_values():
    cfg = AXK1Config()          # the published sizes
    inv = axk1.yarn_inv_freq(cfg)
    # 64 rotary dims, base 10000, factor 32 over 4096: the correction
    # dimensions are floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 10000)) = 10
    # and ceil(64 ln(4096 / (2 pi)) / (2 ln 10000)) = 23
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)   # kept
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=1e-6)
    # halfway up the ramp (dimension 16 of 10..23: 6/13 interpolated)
    np.testing.assert_allclose(
        inv[16], plain[16] * (1 - 6 / 13) + plain[16] / 32 * (6 / 13),
        rtol=1e-6)
    assert inv.shape == (32,) and np.all(np.diff(inv) < 0)
    # s = 192^-0.5 * (0.1 ln 32 + 1)^2
    assert axk1.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2)
    assert axk1.softmax_scale(cfg) == pytest.approx(0.130861, rel=1e-5)
    # mscale == mscale_all_dim: cos and sin are not scaled
    cos, sin = axk1.rope_cos_sin(cfg, jnp.asarray([0, 3]))
    np.testing.assert_allclose(np.asarray(cos[0]), 1.0)
    np.testing.assert_allclose(np.asarray(sin[1]), np.sin(3 * inv),
                               rtol=1e-5)
    np.testing.assert_allclose(reference().yarn_inv_freq(
        ref_sizes(cfg)), inv, rtol=1e-7)


def test_rope_rotates_interleaved_pairs():
    x = jnp.asarray([[1.0, 0.0, 0.0, 2.0]])        # pairs (1, 0), (0, 2)
    cos = jnp.asarray([[0.0, 0.0]])
    sin = jnp.asarray([[1.0, 1.0]])                # a quarter turn each
    out = np.asarray(axk1.apply_rope(x, cos, sin))
    # (a, b) -> (a cos - b sin, b cos + a sin); firsts | seconds
    np.testing.assert_allclose(out, [[0.0, -2.0, 1.0, 0.0]], atol=1e-7)


# ------------------------------------------------------ latent attention


def _latent_case(rng, B, H, C, R, pt, W, P):
    qa = jnp.asarray(rng.normal(size=(B, H, C)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(B, H, R)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(P, pt, -(-(C + R) // 128) * 128)),
                       jnp.float32)
    lengths = jnp.asarray(rng.integers(1, W * pt + 1, size=(B,)), jnp.int32)
    tables = jnp.asarray(rng.integers(1, P, size=(B, W)), jnp.int32)
    tables = jnp.where(jnp.arange(W)[None] * pt < lengths[:, None],
                       tables, 0)                   # padding -> null page
    return qa, qr, pool, tables, lengths


@pytest.mark.parametrize("shape", [(3, 4, 16, 8, 8, 4, 20),    # 4 pages a cell
                                   (2, 8, 32, 8, 4, 3, 9),     # 1 page a cell
                                   (2, 4, 128, 64, 16, 8, 40)])
def test_latent_kernel_matches_its_reference_in_interpret_mode(shape):
    args = _latent_case(np.random.default_rng(sum(shape)), *shape)
    want = la.paged_latent_decode_attention_reference(*args, 0.3)
    got = la.paged_latent_decode_attention(*args, 0.3, kernel="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # off the chip the dispatch takes the reference
    np.testing.assert_array_equal(
        np.asarray(la.paged_latent_decode_attention(*args, 0.3)),
        np.asarray(want))
    with pytest.raises(ValueError):
        la.paged_latent_decode_attention(*args, 0.3, kernel="cuda")


def test_absorbed_attention_equals_expanded(monkeypatch):
    """The decode step's absorbed form over the latent pool gives, for
    the last position, what the expanded form gives over the whole
    sequence, through either reader of the pool."""
    model, params = build()
    cfg = model.cfg
    lp = axk1.layer_params(params, 1)
    T, pt = 21, 8
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(T, cfg.hidden_size)), jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)
    q_nope, q_rope, rows = axk1.mla_project(cfg, lp, h, pos)
    want = axk1.mla_expanded(cfg, lp, q_nope, q_rope, rows)[-1]
    W = -(-T // pt)
    pool = jnp.zeros((W + 1, pt, cfg.pool_row_width), jnp.float32)
    pages = jnp.pad(axk1._pool_rows(cfg, rows), ((0, W * pt - T), (0, 0)))
    pool = pool.at[jnp.arange(1, W + 1)].set(
        pages.reshape(W, pt, cfg.pool_row_width))
    tables = jnp.arange(1, W + 1, dtype=jnp.int32)[None]
    reader = la.paged_latent_decode_attention
    for kernel in ("xla", "pallas"):
        monkeypatch.setattr(la, "paged_latent_decode_attention",
                            functools.partial(reader, kernel=kernel))
        got = axk1.mla_absorbed(cfg, lp, q_nope[-1:], q_rope[-1:], pool,
                                tables, jnp.asarray([T], jnp.int32))[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_flash_forward_takes_a_value_width_of_its_own():
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_forward)
    rng = np.random.default_rng(2)
    B, T, H, D, Dv = 1, 256, 2, 24, 16
    q, k = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, T, H, Dv)), jnp.float32)
    got = flash_attention_forward(q, k, v, causal=True, scale=0.2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.2
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    assert got.shape == (B, T, H, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # one width: the forward-only entry is training's forward
    np.testing.assert_array_equal(
        np.asarray(flash_attention_forward(q, k, k, causal=True)),
        np.asarray(flash_attention(q, k, k, causal=True)))


# --------------------------------------------------------- routed layer


def _routed_case(rng, N=300, H=32, F=16, E=16, held=(0, 16)):
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(H, E)) * 0.3, jnp.float32)
    ws = [jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
          for shape in ((E, H, F), (E, H, F), (E, F, H))]
    first, count = held
    return x, rw, [w[first:first + count] for w in ws], ws


ROUTING = dict(top_k=4, n_group=4, topk_group=2, norm_topk_prob=True,
               scale=2.5)


def _plain_routed(x, rw, ws, held):
    """The routed sum over the held experts, one expert at a time over
    the tokens the reference's router gave it."""
    ref = reference()
    c = {"n_group": 4, "topk_group": 2, "top_k": 4, "norm_topk_prob": True,
         "routed_scaling_factor": 2.5, "eps": 1e-6}
    w = {"post_attention_layernorm": jnp.ones(x.shape[1]), "router": rw,
         "shared_gate_proj": jnp.zeros((x.shape[1], 4)),
         "shared_up_proj": jnp.zeros((x.shape[1], 4)),
         "shared_down_proj": jnp.zeros((4, x.shape[1]))}
    # the reference norms its input and hands back the normed rows
    _, h, picks, wts = ref.shared_and_route(w, x, ref._cfg_key(c), None)
    y = jnp.zeros_like(x)
    picks, wts = np.asarray(picks), np.asarray(wts)
    first, count = held
    for e in range(first, first + count):
        rows, slot = np.nonzero(picks == e)
        if len(rows):
            y = ref.expert_add(y, h, jnp.asarray(rows),
                               jnp.asarray(wts[rows, slot]),
                               ws[0][e], ws[1][e], ws[2][e])
    return np.asarray(y), np.asarray(h), picks


def _unit_rms(x):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: what the four shares' routed parts give
    adds up, to float32 rounding, to the uncut reference layer (the
    shared expert, which every chip computes alike, is outside the
    routed layer and would be counted once)."""
    x, rw, _, ws = _routed_case(np.random.default_rng(3))
    whole, h, _ = _plain_routed(x, rw, ws, (0, 16))
    parts, hits = 0.0, 0
    for first in (0, 4, 8, 12):
        y, n = moe.routed_experts(
            jnp.asarray(h), rw, *(w[first:first + 4] for w in ws),
            held=(first, 4), **ROUTING)
        parts = parts + np.asarray(y)
        hits += int(n.sum())
    assert hits == x.shape[0] * ROUTING["top_k"]     # every pick, once
    np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("path", ["dense", "sorted", "chunked"])
def test_a_share_matches_the_plain_loop_on_every_path(path, monkeypatch):
    x, rw, held_ws, ws = _routed_case(np.random.default_rng(4),
                                      held=(4, 6))
    want, h, picks = _plain_routed(x, rw, ws, (4, 6))
    if path == "dense":
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 10 ** 9)
    else:
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)
        # "chunked": the 450 held rows of 1,200 in four blocks
        monkeypatch.setattr(moe, "BLOCK_ROWS",
                            128 if path == "chunked" else 10 ** 9)
    live = jnp.arange(x.shape[0]) < 280
    y, hits = moe.routed_experts(jnp.asarray(h), rw, *held_ws, held=(4, 6),
                                 live=live, **ROUTING)
    np.testing.assert_allclose(np.asarray(y)[:280], want[:280], rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(y)[280:].any()         # padding: not computed
    want_hits = [(picks[:280] == e).sum() for e in range(4, 10)]
    assert np.asarray(hits).tolist() == want_hits


def test_no_token_is_dropped_when_routing_piles_onto_one_expert(monkeypatch):
    """A router that sends every token to experts 5 and 6 first (two,
    so that their group always stays eligible): the grouped path computes
    all of them (a capacity-C dispatch would drop all but C)."""
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)
    rng = np.random.default_rng(5)
    x, rw, _, ws = _routed_case(rng, N=400)
    x = _unit_rms(jnp.abs(x))                  # positive rows
    rw = rw.at[:, 5:7].set(3.0)                # both score ~1 for all
    y, hits = moe.routed_experts(x, rw, *(w[4:8] for w in ws), held=(4, 4),
                                 **ROUTING)
    assert hits[1] == hits[2] == 400           # every token, none dropped
    want, _, _ = _plain_routed(x, rw, ws, (4, 4))
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)


# ---- the many-token path: grouped products over the held rows only -------


def _grouped_case(count, n_routed, N, K=4, seed=7, H=32, F=16):
    """Random rows, picks (K distinct experts a token) and weights; the
    first `count` of `n_routed` experts are held."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    local = jnp.asarray(np.stack([rng.permutation(n_routed)[:K]
                                  for _ in range(N)]), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(N, K)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
          for shape in ((count, H, F), (count, H, F), (count, F, H))]
    return x, local, w, ws


def _per_token_loop(x, local, held, w, ws):
    """y[n] = sum over n's held picks of w * Expert(x[n]), a token and a
    pick at a time in numpy float64."""
    x, local, held, w = (np.asarray(a) for a in (x, local, held, w))
    wg, wu, wd = (np.asarray(a, np.float64) for a in ws)
    y = np.zeros((x.shape[0], wd.shape[2]))
    for n, k in zip(*np.nonzero(held)):
        e = local[n, k]
        g, u = x[n] @ wg[e], x[n] @ wu[e]
        y[n] += w[n, k] * ((g / (1 + np.exp(-g)) * u) @ wd[e])
    return y


GROUPED_CASES = {
    # name: (held, routed, tokens, rows a block)
    "12-held-of-48": (12, 48, 300, 10 ** 9),
    "128-held-of-256": (128, 256, 300, 10 ** 9),
    "12-held-three-blocks": (12, 48, 300, 128),
    "128-held-five-blocks": (128, 256, 300, 128),
    "tokens-a-multiple-of-no-block": (12, 48, 331, 256),
}


@pytest.mark.parametrize("name", GROUPED_CASES)
def test_the_grouped_path_equals_the_dense_path_and_a_loop(name, monkeypatch):
    count, n_routed, N, block = GROUPED_CASES[name]
    monkeypatch.setattr(moe, "BLOCK_ROWS", block)
    x, local, w, ws = _grouped_case(count, n_routed, N)
    held = local < count
    got = np.asarray(moe._held_grouped(x, local, held, w, *ws, n_routed))
    dense = np.asarray(moe._held_dense(x, local, held, w, *ws))
    np.testing.assert_allclose(got, dense, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, _per_token_loop(x, local, held, w, ws),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("which", ["every-pick-held", "none-held"])
@pytest.mark.parametrize("block", [256, 10 ** 9])
@pytest.mark.parametrize("n_routed", [8, 64])
def test_the_grouped_path_at_its_two_ends(which, block, n_routed,
                                          monkeypatch):
    """Every pick of every token held here (the worst case a dropless
    layer must compute: all N x K rows, in one block with no loop around
    it where the layer holds every expert, in three of 512 rows where it
    was told it holds 8 of 64, in five of 256), and none (no block runs:
    the result is zeros, exactly)."""
    monkeypatch.setattr(moe, "BLOCK_ROWS", block)
    x, local, w, ws = _grouped_case(8, 8, 300)
    held = jnp.full(local.shape, which == "every-pick-held")
    got = np.asarray(moe._held_grouped(x, local, held, w, *ws, n_routed))
    if which == "none-held":
        assert not got.any()
    else:
        assert np.abs(got).min(axis=1).max() > 0      # every token
        np.testing.assert_allclose(
            got, _per_token_loop(x, local, held, w, ws), rtol=2e-5,
            atol=2e-5)


def test_dead_rows_are_neither_computed_nor_counted(monkeypatch):
    """Rows that are padding (`live` false) sort behind the held rows:
    with NaN for input they would poison whatever read them, they add
    no hit, and their result is zero; a block's trip count follows the
    live held rows alone."""
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)
    monkeypatch.setattr(moe, "BLOCK_ROWS", 128)
    x, rw, held_ws, ws = _routed_case(np.random.default_rng(8), held=(4, 6))
    live = jnp.arange(x.shape[0]) < 200
    y, hits = moe.routed_experts(x, rw, *held_ws, held=(4, 6), live=live,
                                 **ROUTING)
    poisoned = jnp.where(live[:, None], x, jnp.nan)
    # the router sees NaN rows too: route on x, compute on the poisoned
    idx, w = moe.route_sigmoid_grouped(x, rw, **ROUTING)
    local = idx - 4
    mine = (local >= 0) & (local < 6) & live[:, None]
    y2 = np.asarray(moe._held_grouped(poisoned, local, mine, w, *held_ws,
                                      16))
    assert np.isfinite(y2).all()
    np.testing.assert_array_equal(y2, np.asarray(y))
    assert not y2[200:].any()
    assert int(hits.sum()) == int(mine.sum())


@pytest.mark.parametrize("case", ["swiglu", "add", "layer"])
def test_the_kernel_body_equals_the_xla_form(case, monkeypatch):
    """The Pallas kernel's body, run by the interpreter: groups that
    straddle tiles, an empty group, rows past the last group (left
    unwritten, so compared up to the last group only)."""
    from paddle_tpu.ops.pallas import _common, grouped_matmul as gm
    rng = np.random.default_rng(9)
    if case == "layer":
        monkeypatch.setattr(_common, "on_tpu", lambda: True)
        monkeypatch.setattr(moe, "BLOCK_ROWS", 256)    # 300 rows: two
        monkeypatch.setattr(gm, "row_tile", lambda rows, groups: 16)
        x, local, w, ws = _grouped_case(12, 48, 300)
        held = local < 12
        args = (x, local, held, w, *ws)
        # (a fresh function a trace: the tracing cache does not see
        # what a monkeypatch changed)
        assert gm.KERNEL_NAME in str(
            jax.make_jaxpr(lambda *a: moe._held_grouped(*a, 48))(*args))
        got = np.asarray(moe._held_grouped(*args, 48))
        monkeypatch.setattr(_common, "on_tpu", lambda: False)
        assert "pallas_call" not in str(
            jax.make_jaxpr(lambda *a: moe._held_grouped(*a, 48))(*args))
        want = np.asarray(moe._held_grouped(*args, 48))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        return
    sizes = jnp.asarray([10, 0, 37, 1, 23], jnp.int32)       # 71 of 96 rows
    x = jnp.asarray(rng.normal(size=(96, 32)), jnp.float32)
    wa, wb = (jnp.asarray(rng.normal(size=(5, 32, 256)) * 0.2, jnp.float32)
              for _ in range(2))
    if case == "add":       # rows onto 40 tokens, some of them twice
        token = jnp.asarray(rng.integers(0, 40, 96), jnp.int32)
        scale = jnp.asarray(rng.uniform(0.1, 1, 96), jnp.float32)
        y = jnp.asarray(rng.normal(size=(40, 256)), jnp.float32)
        got = gm.grouped_matmul_add(x, wa, sizes, token, scale, y, tm=16,
                                    kernel="pallas")
        want = gm.grouped_matmul_add(x, wa, sizes, token, scale, y,
                                     kernel="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        none = gm.grouped_matmul_add(x, wa, sizes * 0, token, scale, y,
                                     tm=16, kernel="pallas")
        np.testing.assert_array_equal(np.asarray(none), np.asarray(y))
        return
    got = gm.grouped_swiglu(x, wa, wb, sizes, tm=16, kernel="pallas")
    want = gm.grouped_swiglu(x, wa, wb, sizes, kernel="xla")
    np.testing.assert_allclose(np.asarray(got)[:71], np.asarray(want)[:71],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tokens,count,n_routed,rows,tile", [
    # kimi's share, its three rungs: every assignment in one block
    (512, 128, 256, 4096, 128), (1024, 128, 256, 8192, 128),
    (2048, 128, 256, 16384, 128),
    (8192, 128, 256, 16384, 128),           # a long forward: the cap
    # axk1's share: twice the sixteenth even routing holds here
    (2048, 12, 192, 2048, 128), (4096, 12, 192, 4096, 256),
    (8192, 12, 192, 8192, 256),
    (300, 6, 16, 2048, 256)])
def test_block_and_tile_follow_the_static_shapes(tokens, count, n_routed,
                                                 rows, tile):
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    assert moe._block_rows(tokens * 8, count, n_routed) == rows
    assert gm.row_tile(rows, count) == tile


def test_routed_layer_is_a_layer_of_the_framework():
    paddle.seed(6)
    layer = moe.RoutedExperts(32, 16, 16, 4, n_group=4, topk_group=2,
                              routed_scaling_factor=2.5, held=(8, 4))
    shapes = {k: tuple(v.shape)
              for k, v in framework.param_arrays(layer).items()}
    assert shapes == {"router": (32, 16), "gate_proj": (4, 32, 16),
                      "up_proj": (4, 32, 16), "down_proj": (4, 16, 32)}
    x = paddle.to_tensor(np.random.default_rng(6).normal(
        size=(2, 5, 32)).astype(np.float32))
    assert tuple(layer(x).shape) == (2, 5, 32)
    with pytest.raises(ValueError):
        moe.RoutedExperts(32, 16, 16, 4, held=(14, 4))


# ------------------------------------------- the engine against the reference


def _engine_logits(model, prompts, max_new, **engine_kw):
    """Each request's tokens and the logits rows the engine sampled
    them from (prefill's, then every step's). The requests sample
    with `top_k=1`, which leaves the best id alone: a tick of greedy
    rows would pull the device's picks and no logits."""
    eng = DecodeEngine(model, max_slots=3, page_tokens=8,
                       max_new_tokens=max_new, **engine_kw)
    rows = {}
    sample = eng._sample

    def tap(row, req, pos=None):
        rows.setdefault(req.id, []).append(np.array(row, np.float32))
        return sample(row, req, pos)

    eng._sample = tap
    try:
        streams = [eng.submit(p, max_new_tokens=max_new, temperature=1.0,
                              top_k=1) for p in prompts]
        tokens = [s.result(timeout=300) for s in streams]
        stats = eng.stats()
    finally:
        eng.stop()
    return tokens, [rows[s.request_id] for s in streams], stats


@pytest.mark.parametrize("dtype,tol,why", [
    ("float32", 2e-4,
     "float32 on both sides: what is left is the order of summation "
     "(absorbed against expanded attention, sorted against looped "
     "experts), a few ulps through 3 layers"),
    ("bfloat16", 0.06,
     "bfloat16 weights are common to both sides; the program rounds "
     "every activation to 8 bits between matrix products and the "
     "reference keeps float32: relative 2^-8 a rounding, some 30 "
     "roundings deep, against logits of standard deviation ~0.16: a "
     "few percent of it"),
])
def test_engine_logits_follow_the_reference_through_the_cache(dtype, tol,
                                                             why):
    """Prefill into latent pages, then the absorbed paged step, as
    `DecodeEngine` dispatches them at three live slots: every sampled
    row against the plain reference's full forward over prompt + served
    tokens."""
    model, params = build(seed=7, held_experts=(4, 6), dtype=dtype)
    cfg = model.cfg
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 19, 40)]
    tokens, rows, stats = _engine_logits(model, prompts, 6)
    ref, c = reference(), ref_sizes(cfg)
    for prompt, toks, got in zip(prompts, tokens, rows):
        assert len(toks) == 6 and len(got) == 6
        logits = np.asarray(ref.forward(params, np.asarray(prompt + toks),
                                        c))
        want = logits[len(prompt) - 1:len(prompt) + 5]
        scale = float(np.std(want))
        assert np.abs(np.stack(got) - want).max() <= tol * scale, why
        if dtype == "float32":          # the tokens are the reference's
            assert toks == want.argmax(-1).tolist()
    # the device-side counters: 6 requests-worth of tokens went through
    # the routers of the 2 expert layers, each at most top_k times
    n_tok = sum(len(p) + 5 for p in prompts)
    assert stats["routed_tokens"] == n_tok and stats["model_kind"] == "axk1"
    assert np.shape(stats["routed"]) == (2, 6)
    assert 0 < np.sum(stats["routed"]) <= 2 * 4 * n_tok


def test_layer_forward_is_the_reference():
    model, params = build(seed=8)
    ids = np.random.default_rng(8).integers(0, 128, 33)
    got = model(paddle.to_tensor(ids))
    want = reference().forward(params, ids, ref_sizes(model.cfg))
    np.testing.assert_allclose(np.asarray(got._data), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_prefix_hit_and_copy_on_write_on_the_latent_pool():
    from paddle_tpu.observability import REGISTRY

    def count(name):
        return REGISTRY.flat().get(name, 0.0)

    model, _ = build(seed=9)
    rng = np.random.default_rng(9)
    head = rng.integers(0, 128, 16).tolist()         # two whole pages
    eng = DecodeEngine(model, max_slots=2, page_tokens=8, max_new_tokens=5,
                       prefix_cache=True)
    hit, cow = ("paddle_tpu_decode_prefix_hits_total",
                "paddle_tpu_decode_page_cow_copies_total")
    try:
        first = eng.submit(head, max_new_tokens=5).result(timeout=300)
        hits0, cow0 = count(hit), count(cow)
        # the same page-aligned prompt again: both pages map from the
        # trie, the last prompt token is re-fed INTO the shared second
        # page, which is copied first
        again = eng.submit(head, max_new_tokens=5).result(timeout=300)
        other = eng.submit(head[:8] + [1, 2, 3], max_new_tokens=5
                           ).result(timeout=300)
        assert again == first
        assert count(hit) - hits0 == 2
        assert count(cow) - cow0 >= 1
        assert eng.stats()["prefix_cache"]["cached_pages"] >= 2
    finally:
        eng.stop()
    fresh = DecodeEngine(model, max_slots=2, page_tokens=8,
                         max_new_tokens=5, prefix_cache=False)
    try:        # a hit's tail-fed tokens are those of a cold prefill
        assert fresh.submit(head[:8] + [1, 2, 3], max_new_tokens=5
                            ).result(timeout=300) == other
    finally:
        fresh.stop()


def test_preemption_resumes_on_the_latent_pool():
    model, _ = build(seed=10)
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, 128, 12).tolist()
    solo = DecodeEngine(model, max_slots=1, page_tokens=8, max_new_tokens=8)
    try:
        want = solo.submit(prompt, max_new_tokens=8).result(timeout=300)
    finally:
        solo.stop()
    eng = DecodeEngine(model, max_slots=1, page_tokens=8, max_new_tokens=8,
                       preempt=True)
    try:
        low = eng.submit(prompt, max_new_tokens=8, priority=0)
        assert low.next_event(timeout=300)[0] == "token"
        high = eng.submit(prompt[:5], max_new_tokens=3, priority=5)
        assert len(high.result(timeout=300)) == 3
        assert low.result(timeout=300) == want      # gapless, identical
        assert eng.stats()["paused"] == 0
    finally:
        eng.stop()


# ------------------------------------------------------ what it refuses


@pytest.mark.parametrize("kw,what", [
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(host_pages=4), "host tiering"),
    (dict(handoff=True), "KV handoff"),
])
def test_typed_refusals_at_construction(kw, what):
    model, _ = build()
    with pytest.raises(TypedServeError) as err:
        DecodeEngine(model, max_slots=1, **kw)
    assert err.value.code == ERR_FAILED_PRECONDITION
    assert what in str(err.value) and "ROADMAP" in str(err.value)


def test_speculation_is_refused_for_target_and_for_draft():
    model, _ = build()
    paddle.seed(0)
    gpt = GPT(gpt_tiny())
    for kw in (dict(model=model, draft_model=gpt),
               dict(model=gpt, draft_model=model)):
        with pytest.raises(TypedServeError) as err:
            SpecDecodeEngine(speculate_k=2, max_slots=1, **kw)
        assert err.value.code == ERR_FAILED_PRECONDITION
        assert "speculative" in str(err.value)


def test_speculation_over_axk1_never_reaches_a_gpt_builder(monkeypatch):
    """`SpecDecodeEngine` asks its kinds for verify and rollout; an
    `AXK1Kind` has neither and says so (typed) before anything is built:
    no gpt program is asked for on the way."""
    model, params = build()
    asked = []
    monkeypatch.setattr(model_kinds, "gpt_paged_fns",
                        lambda *a, **k: asked.append(a) or 1 / 0)
    with pytest.raises(TypedServeError) as err:
        SpecDecodeEngine(model, draft_cfg=model.cfg, draft_params=params,
                         speculate_k=2, max_slots=1)
    assert err.value.code == ERR_FAILED_PRECONDITION
    assert "speculative" in str(err.value) and "ROADMAP" in str(err.value)
    assert not asked
    assert not hasattr(model_kinds.AXK1Kind, "verify_fn")
    assert not hasattr(model_kinds.AXK1Kind, "rollout_fn")


# ------------------------------------------------------------ artifacts


def test_artifact_carries_the_model_kind(tmp_path):
    model, params = build(seed=11, dtype="bfloat16", held_experts=(2, 5))
    prefix = str(tmp_path / "ax")
    save_for_decode(model, prefix)
    meta = json.load(open(prefix + ".decode.json"))
    assert meta["model_kind"] == "axk1"
    assert meta["config"]["held_experts"] == [2, 5]
    assert "embed_tokens" in meta["bfloat16"]
    kind, loaded = _load_decode_artifact(prefix)
    assert kind.name == "axk1" and kind.cfg == model.cfg
    for k, v in params.items():
        assert loaded[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(loaded[k]).view(np.uint16),
            np.asarray(v).view(np.uint16))
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    a = DecodeEngine(model, max_slots=1, page_tokens=8)
    b = load_for_decode(prefix, max_slots=1, page_tokens=8)
    try:
        assert isinstance(b, DecodeEngine) and b.fingerprint == a.fingerprint
        assert b.submit(prompt, max_new_tokens=4).result(timeout=300) == \
            a.submit(prompt, max_new_tokens=4).result(timeout=300)
    finally:
        a.stop()
        b.stop()
    with pytest.raises(ValueError):
        save_for_decode(model, prefix, quant="int8")


def test_an_artifact_without_the_key_is_a_gpt(tmp_path):
    paddle.seed(12)
    gpt = GPT(gpt_tiny())
    prefix = str(tmp_path / "g")
    save_for_decode(gpt, prefix)
    meta = json.load(open(prefix + ".decode.json"))
    # as every artifact written before the key existed
    assert sorted(meta) == ["config", "eps", "format"]
    eng = load_for_decode(prefix, max_slots=1)
    try:
        assert eng.stats()["model_kind"] == "gpt"
        assert len(eng.submit([1, 2, 3], max_new_tokens=3
                              ).result(timeout=300)) == 3
    finally:
        eng.stop()
    meta["model_kind"] = "mamba"
    json.dump(meta, open(prefix + ".decode.json", "w"))
    with pytest.raises(ValueError, match="unknown model kind"):
        load_for_decode(prefix, max_slots=1)


# ------------------------------------------------------------- the seam


def test_the_seam_leaves_the_gpt_step_program_as_it_was():
    """The chat cell's step through `GPTKind` is the builder's own
    (`gpt_paged_fns`, no closure between): the module is
    `jit_paged_step`, and its arguments are the parameters, 2 x `layers`
    pool leaves, tables, tokens, lengths, in that order."""
    import re

    cfg = gpt_tiny()
    paddle.seed(13)
    params = framework.param_arrays(GPT(cfg))
    kind = model_kinds.for_config(cfg)
    pools = kind.pools_sds(9, 4, "float32")
    i32 = jnp.int32
    rest = (jax.ShapeDtypeStruct((2, 4), i32),
            jax.ShapeDtypeStruct((2,), i32), jax.ShapeDtypeStruct((2,), i32))
    step = kind.step_fn(4)
    assert step.__name__ == "paged_step" \
        and step.__module__ == "paddle_tpu.models.gpt"
    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, *rest).compiler_ir(
            dialect="stablehlo").operation.get_asm(enable_debug_info=False)
    assert "module @jit_paged_step" in text
    main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S)
    got = re.findall(r"%arg\d+: tensor<([^>]*)>", main.group(1))
    leaves = jax.tree.leaves((params, pools, *rest))
    assert len(leaves) == len(params) + 2 * cfg.layers + 3
    dt = {"float32": "f32", "int32": "i32"}
    assert got == ["x".join([*map(str, a.shape), dt[str(a.dtype)]])
                   for a in leaves]


def test_the_base_engine_names_no_gpt():
    """The base `DecodeEngine` reaches a model only through its kind:
    no `GPTConfig`, head count, head size or pair of pools in its source
    (`SpecDecodeEngine`, behind its typed refusal, keeps GPT's), and
    nothing in `decode.py` reaches round a kind for a gpt builder."""
    import inspect

    from paddle_tpu.inference import decode

    src = inspect.getsource(decode.DecodeEngine) \
        + inspect.getsource(decode.default_slot_count)
    for word in ("GPTConfig", "gpt_paged", ".heads", "head_dim", "k_pool",
                 "v_pool", "_kpool", "_vpool", "cfg.layers"):
        assert word not in src, word
    # (its module docstring says where a GPT's programs come from)
    assert "gpt_paged" not in inspect.getsource(decode).replace(
        decode.__doc__, "")
