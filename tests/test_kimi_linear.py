"""Kimi Linear (`models.kimi_linear`) at toy widths on the CPU: the KDA
forms against the recurrence (`ops.pallas.kda`), the served path
(prefill into latent pages AND slot state, then the paged step, through
`DecodeEngine`) against the benchmark's plain reference, what state by
slot asks of the engine (a reused slot, preemption, no prefix reuse),
the expert layer's share under a selection bias, what the model kind
refuses, its artifact, and the seam: the `gpt` and `axk1` programs are
the parent's, text for text."""
import hashlib
import json
import os
import subprocess
import sys

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
from paddle_tpu.models.axk1 import AXK1, axk1_tiny
from paddle_tpu.models.gpt import GPT, gpt_tiny
from paddle_tpu.models.kimi_linear import (KimiLinear, KimiLinearConfig,
                                           kimi_linear_tiny)
from paddle_tpu.nn.layer import moe
from paddle_tpu.ops.pallas import kda


def reference():
    from chipbench.reference import kimi_linear as ref
    return ref


def ref_sizes(cfg):
    """The reference's sizes for a program config (what the benchmark's
    family hands it)."""
    return {"layers": cfg.num_hidden_layers,
            "dense_layers": cfg.first_k_dense_replace,
            "heads": cfg.num_attention_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "nope_dim": cfg.qk_nope_head_dim, "rope_dim": cfg.qk_rope_head_dim,
            "v_dim": cfg.v_head_dim, "kda_heads": cfg.kda_num_heads,
            "kda_head_dim": cfg.kda_head_dim, "held": cfg.held_experts,
            "top_k": cfg.num_experts_per_token,
            "norm_topk_prob": cfg.moe_renormalize,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "eps": cfg.rms_norm_eps,
            "kda": tuple(cfg.is_kda(i)
                         for i in range(cfg.num_hidden_layers))}


def build(seed=0, **kw):
    """A seeded toy model whose decays are drawn as the benchmark draws
    them, but up to the strongest the source allows: exp(A_log) in
    [1, 16], softplus(dt_bias) about 0.05 to 3."""
    paddle.seed(seed)
    model = KimiLinear(kimi_linear_tiny(**kw))
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            p._data = jnp.asarray(np.log(rng.uniform(1, 16, p.shape)),
                                  jnp.float32)
        elif name.endswith("dt_bias"):
            p._data = jnp.asarray(rng.normal(-0.5, 1.5, p.shape),
                                  jnp.float32)
        elif name.endswith("conv1d"):
            p._data = jnp.asarray(rng.normal(0, 0.3, p.shape), p._data.dtype)
    return model, framework.param_arrays(model)


# ------------------------------------------------ the three forms of KDA


def _kda_case(rng, T, H=2, K=16, V=16, a_max=16.0):
    q = rng.normal(size=(T, H, K))
    k = rng.normal(size=(T, H, K))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * K ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, H, V))
    A = np.concatenate([[a_max], rng.uniform(1, a_max, H - 1)])
    g = -A[None, :, None] * np.log1p(np.exp(rng.normal(size=(T, H, K))))
    beta = 1 / (1 + np.exp(-rng.normal(size=(T, H))))
    s0 = rng.normal(size=(H, K, V))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, s0)]


@pytest.mark.parametrize("T", [1, 37, 64, 150])
def test_chunk_form_equals_the_recurrence(T):
    """Float32, decays as strong as A = 16 allows (the largest log decay
    a token here is under -30: exp(-G) over a chunk would overflow), T
    not a multiple of the chunk, a non-zero initial state."""
    q, k, v, g, beta, s0 = _kda_case(np.random.default_rng(T), T)
    assert float(g.min()) < -16.0 or T == 1
    o, S = kda.kda_recurrence(q, k, v, g, beta, s0)
    o2, S2 = jax.jit(kda.kda_chunk_prefill)(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=2e-6,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S), atol=2e-6,
                               rtol=1e-5)
    assert np.isfinite(np.asarray(o2)).all()


def test_weak_decays_keep_the_state_and_the_forms_still_agree():
    """Decays near 1 (the published initialisation: dt down to 0.001):
    the state remembers the whole sequence."""
    rng = np.random.default_rng(5)
    q, k, v, g, beta, s0 = _kda_case(rng, 200)
    g = g * 1e-3
    o, S = kda.kda_recurrence(q, k, v, g, beta, s0)
    o2, S2 = kda.kda_chunk_prefill(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=5e-6,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S), atol=5e-6,
                               rtol=1e-5)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_chunk_prefill_then_steps_equal_the_recurrence(kernel):
    """Chunk-prefill T positions into a slot of a state pool, then k
    one-token steps (the Pallas kernel in interpret mode, or its jnp
    composition) against the recurrence over T + k; a second row on
    another slot and padding rows on the null slot ride along."""
    rng = np.random.default_rng(6)
    T, steps, H, K = 70, 5, 4, 16
    q, k, v, g, beta, _ = _kda_case(rng, T + steps, H=H, K=K, V=K)
    want_o, want_S = kda.kda_recurrence(q, k, v, g, beta)
    _, S = kda.kda_chunk_prefill(q[:T], k[:T], v[:T], g[:T], beta[:T])
    slots_n = 3                                   # + the null slot
    pool = jnp.asarray(rng.normal(size=(slots_n + 1, H, K, K)), jnp.float32)
    other = pool[0]
    pool = pool.at[2].set(S)
    slots = jnp.asarray([2, 0, slots_n, slots_n], jnp.int32)
    untouched = pool[1]
    got = []
    for t in range(T, T + steps):
        row = [jnp.stack([a[t], a[t - 1], a[0], a[1]])
               for a in (q, k, v, g, beta)]
        o, pool = kda.kda_decode_step(*row, pool, slots, kernel=kernel)
        got.append(o[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got)),
                               np.asarray(want_o[T:]), atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pool[2]), np.asarray(want_S),
                               atol=2e-6, rtol=1e-5)
    assert not np.array_equal(np.asarray(pool[0]), np.asarray(other))
    np.testing.assert_array_equal(np.asarray(pool[1]),
                                  np.asarray(untouched))


def test_the_kernel_and_its_composition_agree_row_for_row():
    rng = np.random.default_rng(7)
    B, H, K = 5, 32, 16                    # two cells of 16 heads a row
    q, k, v, g, beta, _ = _kda_case(rng, B, H=H, K=K, V=K)
    pool = jnp.asarray(rng.normal(size=(7, H, K, K)), jnp.float32)
    slots = jnp.asarray([3, 0, 5, 1, 4], jnp.int32)
    o1, p1 = kda.kda_decode_step(q, k, v, g, beta, pool, slots, kernel="xla")
    o2, p2 = kda.kda_decode_step(q, k, v, g, beta, pool, slots,
                                 kernel="pallas")
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p1), atol=1e-6)
    # slots no row named are as they were
    for s in (2, 6):
        np.testing.assert_array_equal(np.asarray(p2[s]), np.asarray(pool[s]))
    with pytest.raises(ValueError):
        kda.kda_decode_step(q, k, v, g, beta, pool, slots, kernel="cuda")


def test_layer_forward_is_the_reference():
    model, params = build(seed=8, held_experts=(4, 8))
    ids = np.random.default_rng(8).integers(0, 128, 33)
    got = model(paddle.to_tensor(ids))
    want = reference().forward(params, ids, ref_sizes(model.cfg))
    np.testing.assert_allclose(np.asarray(got._data), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_the_config_says_which_layer_mixes_how():
    cfg = KimiLinearConfig()
    assert [i + 1 for i in range(27) if not cfg.is_kda(i)] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert len(cfg.kda_index) == 20 and len(cfg.mla_index) == 7
    # a slot's state of one layer: 2 MiB float32 + 3 rows of 3 x 4096
    assert cfg.state_slot_bytes == 20 * (32 * 128 * 128 * 4 + 73_728)
    assert cfg.pool_row_width == 640 and cfg.conv_row_width == 36_864
    with pytest.raises(ValueError, match="layer 4"):
        KimiLinearConfig(num_hidden_layers=5, kda_layers=(1, 2, 3, 5),
                         full_attn_layers=())
    with pytest.raises(ValueError, match="NoPE"):
        KimiLinearConfig(mla_use_nope=False)


# ------------------------------------------- the engine against the reference


def _engine_logits(model, prompts, max_new, slots=2, **engine_kw):
    """Each request's tokens and the logits rows the engine sampled
    them from (prefill's, then every step's): `top_k=1` sampling keeps
    the best id and makes the tick pull the logits."""
    eng = DecodeEngine(model, max_slots=slots, page_tokens=8,
                       max_new_tokens=max(max_new), **engine_kw)
    rows = {}
    sample = eng._sample

    def tap(row, req, pos=None):
        rows.setdefault(req.id, []).append(np.array(row, np.float32))
        return sample(row, req, pos)

    eng._sample = tap
    try:
        streams = [eng.submit(p, max_new_tokens=n, temperature=1.0, top_k=1)
                   for p, n in zip(prompts, max_new)]
        tokens = [s.result(timeout=300) for s in streams]
        stats = eng.stats()
    finally:
        eng.stop()
    return tokens, [rows[s.request_id] for s in streams], stats


@pytest.mark.parametrize("dtype,tol,why", [
    ("float32", 3e-4,
     "float32 on both sides: what is left is the order of summation "
     "(chunks against the recurrence, absorbed against expanded "
     "attention, dense-over-held against looped experts), a few ulps "
     "through 5 layers"),
    ("bfloat16", 0.12,
     "bfloat16 weights are common to both sides; the program rounds "
     "every activation to 8 bits between matrix products and the "
     "reference keeps float32: relative 2^-8 a rounding, some 50 "
     "roundings deep (read: 9% of the logits' standard deviation, 6% for "
     "`axk1`'s three layers)"),
])
def test_engine_logits_follow_the_reference_through_pages_and_state(
        dtype, tol, why):
    """Five requests over two slots: prefill into latent pages and
    slot state, the paged step, rows joining and leaving (different
    lengths and counts), and each slot reused by a later request whose
    prefill must overwrite the last stream's state. Every sampled row
    against the plain reference's full forward over prompt + served."""
    model, params = build(seed=7, held_experts=(4, 8), dtype=dtype)
    cfg = model.cfg
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 19, 40, 9, 70)]
    max_new = [6, 3, 5, 8, 4]
    tokens, rows, stats = _engine_logits(model, prompts, max_new)
    ref, c = reference(), ref_sizes(cfg)
    for prompt, n, toks, got in zip(prompts, max_new, tokens, rows):
        assert len(toks) == n and len(got) == n
        logits = np.asarray(ref.forward(params, np.asarray(prompt + toks),
                                        c))
        want = logits[len(prompt) - 1:len(prompt) + n - 1]
        scale = float(np.std(want))
        assert np.abs(np.stack(got) - want).max() <= tol * scale, why
        if dtype == "float32":          # the tokens are the reference's
            assert toks == want.argmax(-1).tolist()
    assert stats["model_kind"] == "kimi_linear"
    # state by slot: 2 slots and the null slot, 4 KDA layers; no trie
    isz = 4 if dtype == "float32" else 2
    assert stats["state_slots"] == 2
    assert stats["state_pool_bytes"] == 3 * 4 * (4 * 16 * 16 * 4
                                                 + 9 * 64 * isz)
    assert "prefix_cache" not in stats
    n_tok = sum(len(p) + n - 1 for p, n in zip(prompts, max_new))
    assert stats["routed_tokens"] == n_tok
    assert np.shape(stats["routed"]) == (4, 8)


def test_a_prompt_fed_by_its_tail_through_the_step():
    """What the engine's tail feeding and its resume do, on the kind's
    own programs: prefill the first m tokens into a slot, then feed the
    rest of the prompt one token a step; every step's logits are the
    reference's at that position, beside a second row that advances
    another slot and a padding row on the null slot."""
    model, params = build(seed=12, held_experts=(0, 16))
    cfg = model.cfg
    kind = model_kinds.for_model(model)
    pt, slots_n = 8, 2
    pools = kind.pools_zeros(9, pt, None, slots=slots_n)
    # stale state in every slot: a prefill has to overwrite it
    pools = dict(pools, state=tuple(s + 1.0 for s in pools["state"]),
                 conv=tuple(c + 1.0 for c in pools["conv"]))
    prefill = jax.jit(kind.prefill_fn(pt))
    step = jax.jit(kind.step_fn(pt))
    rng = np.random.default_rng(12)
    ids = rng.integers(0, 128, 30)
    other = rng.integers(0, 128, 30)
    m = 11
    i32 = jnp.int32

    def pre(pools, toks, pages, slot):
        inp = np.zeros((1, 16), np.int32)
        inp[0, :len(toks)] = toks
        return prefill(params, pools, jnp.asarray(inp),
                       jnp.asarray([pages], i32),
                       jnp.asarray([len(toks)], i32), jnp.asarray(slot, i32))

    _, pools = pre(pools, ids[:m], [1, 2], 1)
    _, pools = pre(pools, other[:m], [3, 4], 0)
    tables = jnp.asarray([[1, 2, 5, 6], [3, 4, 7, 8], [0, 0, 0, 0]], i32)
    want = np.asarray(reference().forward(params, ids, ref_sizes(cfg)))
    want_other = np.asarray(reference().forward(params, other,
                                                ref_sizes(cfg)))
    for t in range(m, 30):
        logits, pools = step(
            params, pools, tables, jnp.asarray([ids[t], other[t], 0], i32),
            jnp.asarray([t, t, 0], i32),
            jnp.asarray([1, 0, slots_n], i32))
        np.testing.assert_allclose(np.asarray(logits[0]), want[t],
                                   rtol=3e-4, atol=3e-5)
        np.testing.assert_allclose(np.asarray(logits[1]), want_other[t],
                                   rtol=3e-4, atol=3e-5)


def test_preemption_resumes_token_identically():
    """A victim's pages and slot are let go (nothing is stashed: no
    trie); its resume prefills prompt + generated into whatever slot it
    gets and goes on as if nothing had happened."""
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
        st = eng.stats()
        assert st["paused"] == 0 and "prefix_cache" not in st
        assert st["pages"]["pages_used"] == 0       # nothing kept
    finally:
        eng.stop()


def test_a_common_head_is_not_reused():
    """Two requests with a page-aligned common head serve what each
    serves alone, even when the prefix cache is asked for: a page hit
    without the state at that boundary would serve wrong tokens, so
    this kind builds no trie and every admission is a prefill."""
    from paddle_tpu.observability import REGISTRY

    def count(name):
        return REGISTRY.flat().get(name, 0.0)

    model, _ = build(seed=9)
    rng = np.random.default_rng(9)
    head = rng.integers(0, 128, 16).tolist()         # two whole pages
    a, b = head + [1, 2, 3], head + [7, 7]
    alone = []
    for p in (a, b, head):
        eng = DecodeEngine(model, max_slots=1, page_tokens=8,
                           max_new_tokens=5)
        try:
            alone.append(eng.submit(p, max_new_tokens=5).result(timeout=300))
        finally:
            eng.stop()
    eng = DecodeEngine(model, max_slots=2, page_tokens=8, max_new_tokens=5,
                       prefix_cache=True)
    hit, pre = ("paddle_tpu_decode_prefix_hits_total",
                "paddle_tpu_decode_prefills_total")
    try:
        assert eng._prefix is None
        hits0, pre0 = count(hit), count(pre)
        got = [eng.submit(p, max_new_tokens=5).result(timeout=300)
               for p in (a, b, head, head)]
        assert got == alone + [alone[2]]
        assert count(hit) == hits0 and count(pre) - pre0 == 4
    finally:
        eng.stop()


# ----------------------------------------------------- the expert layer


def _routed_case(rng, N=64, H=32, F=16, E=16):
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(H, E)) * 0.3, jnp.float32)
    ws = [jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
          for s in ((E, H, F), (E, H, F), (E, F, H))]
    bias = jnp.asarray(rng.normal(size=E) * 0.2, jnp.float32)
    return x, rw, ws, bias


ROUTING = dict(top_k=4, norm_topk_prob=True, scale=2.446)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """16 experts over 2 chips, picks chosen on score + bias: the two
    shares' routed parts add up to the uncut reference layer's routed
    part, and with the shared expert (which both chips compute alike)
    counted ONCE, to the whole layer."""
    ref = reference()
    x, rw, ws, bias = _routed_case(np.random.default_rng(3))
    shared = [jnp.asarray(np.random.default_rng(4).normal(size=s) * 0.1,
                          jnp.float32) for s in ((32, 16), (32, 16), (16, 32))]
    c = {"top_k": 4, "norm_topk_prob": True, "routed_scaling_factor": 2.446,
         "eps": 1e-5}
    w = {"post_attention_layernorm": jnp.ones(32), "router": rw,
         "bias": bias, "shared_gate_proj": shared[0],
         "shared_up_proj": shared[1], "shared_down_proj": shared[2]}
    y, h, picks, wts = ref.shared_and_route(w, x, ref._cfg_key(c), None)
    whole = np.asarray(ref.held_experts_add(y, h, picks, wts, *ws, 0, 64))
    # the bias changes the selection (else this test shows nothing)
    plain = np.asarray(moe.route_sigmoid_grouped(h, rw, **ROUTING)[0])
    assert (np.sort(plain, 1) != np.sort(np.asarray(picks), 1)).any()
    parts, hits = 0.0, 0
    for first in (0, 8):
        part, n = moe.routed_experts(
            h, rw, *(a[first:first + 8] for a in ws), held=(first, 8),
            select_bias=bias, **ROUTING)
        parts = parts + np.asarray(part)
        hits += int(n.sum())
    assert hits == x.shape[0] * 4                    # every pick, once
    once = np.asarray(ref.swiglu(h, *shared))
    np.testing.assert_allclose(np.asarray(x) + once + parts, whole,
                               rtol=2e-5, atol=2e-5)


def test_the_weights_are_the_scores_not_the_biased_scores():
    x, rw, ws, bias = _routed_case(np.random.default_rng(5))
    idx, w = moe.route_sigmoid_grouped(x, rw, select_bias=bias, **ROUTING)
    s = np.asarray(jax.nn.sigmoid(x @ rw))
    best = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    assert (np.sort(np.asarray(idx), 1) == np.sort(best, 1)).all()
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(w), 2.446 * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)
    # in groups the bias takes part in the choice of groups too, and an
    # ineligible expert is never picked, however low the biased scores
    low = bias - 5.0
    idx, _ = moe.route_sigmoid_grouped(x, rw, top_k=4, n_group=4,
                                       topk_group=1, select_bias=low)
    groups = np.asarray(idx) // 4
    assert (groups == groups[:, :1]).all()


def test_without_a_bias_the_routed_layer_is_what_it_was():
    """No `select_bias`: the same program as before the argument
    existed (its text hashes to the parent's), and a zero bias picks
    and weighs bit for bit as none."""
    x, rw, ws, _ = _routed_case(np.random.default_rng(6))
    kw = dict(top_k=4, n_group=4, topk_group=2, norm_topk_prob=True,
              scale=2.5, held=(4, 8))
    held = [a[4:12] for a in ws]
    text = jax.jit(lambda *a: moe.routed_experts(*a, **kw)).lower(
        x, rw, *held).compiler_ir(dialect="stablehlo").operation.get_asm(
            enable_debug_info=False)
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == PARENT["routed"], got
    y0, n0 = moe.routed_experts(x, rw, *held, **kw)
    y1, n1 = moe.routed_experts(x, rw, *held, **kw,
                                select_bias=jnp.zeros(16))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(n0), np.asarray(n1))


def test_many_tokens_take_the_new_path_and_few_tokens_the_old(monkeypatch):
    """Above `DENSE_MAX_TOKENS` the layer lowers to another program than
    its parent did (PR 34: grouped products over the held rows in place
    of `ragged_dot` over every assignment), with the dense path's
    values and hits."""
    x, rw, ws, _ = _routed_case(np.random.default_rng(7), N=320)
    kw = dict(top_k=4, n_group=4, topk_group=2, norm_topk_prob=True,
              scale=2.5, held=(4, 8))
    held = [a[4:12] for a in ws]
    text = jax.jit(lambda *a: moe.routed_experts(*a, **kw)).lower(
        x, rw, *held).compiler_ir(dialect="stablehlo").operation.get_asm(
            enable_debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest() != PARENT["routed.many"]
    y, n = moe.routed_experts(x, rw, *held, **kw)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 10 ** 9)
    y0, n0 = jax.jit(lambda *a: moe.routed_experts(*a, **kw))(x, rw, *held)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n0))


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
    assert what in str(err.value) and "ROADMAP.md R5" in str(err.value)
    assert "kimi_linear" in str(err.value)


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
    assert not hasattr(model_kinds.KimiLinearKind, "verify_fn")


# ------------------------------------------------------------ artifacts


def test_artifact_carries_the_model_kind(tmp_path):
    model, params = build(seed=11, dtype="bfloat16", held_experts=(2, 5))
    prefix = str(tmp_path / "kimi")
    save_for_decode(model, prefix)
    meta = json.load(open(prefix + ".decode.json"))
    assert meta["model_kind"] == "kimi_linear"
    assert meta["config"]["held_experts"] == [2, 5]
    assert meta["config"]["kda_layers"] == [1, 2, 3, 5]
    assert meta["config"]["q_lora_rank"] is None
    kind, loaded = _load_decode_artifact(prefix)
    assert kind.name == "kimi_linear" and kind.cfg == model.cfg
    assert kind.slot_state is True
    for k, v in params.items():
        assert loaded[k].dtype == v.dtype
        np.testing.assert_array_equal(np.asarray(loaded[k], np.float32),
                                      np.asarray(v, np.float32))
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


# ------------------------------------------------------------- the seam

# sha256 of the StableHLO text (no debug info) of the programs below as
# the PARENT of the PR that brought slot state lowers them, under this
# test suite's settings. `python -m pytest tests/test_kimi_linear.py -k
# seam` prints what it finds beside what it wants; a PR that changes
# one of these programs on purpose replaces its line.
PARENT = {   # noqa: E501
    "gpt.step": "6aad1bb6ee6cf800372f026414e53b4f27e3a3d4eaa4628ee91debd92eedb475",
    "gpt.prefill": "864141f56f1746f1ef86a6bad98a13f6c434520e1c3cd20e817368840780ca63",
    "axk1.step": "09d4c15dddc75df5fab26cb927ae5aa5195409c3b7c27cf91d5ea628b224d805",
    "axk1.prefill": "79f4a063381775a85bfeb4c732d935c139622ef36c6c13c841e5da69c0518209",
    "routed": "852c886fde95e9fc44e03cb1a0bca956673b886d27945d7a792c2e18b4620995",
    # the parent of PR 34 (the expert layer's many-token path changed)
    "kimi_linear.step": "ab2b0c93490c7dd8be31a2c317fa7c406f96f5e9f7cf79facd2cba67c4c8c5d6",
    "routed.many": "f48cd35e3476a3a389d7a35611bbae5dc01d5c118f056bd0ecc088606f12589a",
    # the parent of PR 35 (the fourth kind; flash attention gained a band)
    "kimi_linear.prefill": "64bef6ad572df183ae769ee5c91fed128d2f2e921066d0014f922da0efe9a5e9",
    # PR 35 itself: the kind it brought, pinned for the PRs after it
    "afmoe.step": "1273830a58efa041efb24ebf6b64deaea62b3a4f12f1c4e78dd58d2accaa4a52",
    "afmoe.prefill": "91e63b577008409371af831ea7a22b0d60f035957bfb581f95116dbb8d2a87b7",
}


def _seam_texts():
    i32 = jnp.int32
    out = {}
    for name, make in (("gpt", lambda: GPT(gpt_tiny())),
                       ("axk1", lambda: AXK1(axk1_tiny(held_experts=(4, 6))))):
        paddle.seed(13)
        model = make()
        params = framework.param_arrays(model)
        kind = model_kinds.for_model(model)
        assert not getattr(kind, "slot_state", False)
        pools = kind.pools_sds(9, 4, kind.pool_dtype(None))
        step = (jax.ShapeDtypeStruct((2, 4), i32),
                jax.ShapeDtypeStruct((2,), i32),
                jax.ShapeDtypeStruct((2,), i32))
        pre = (jax.ShapeDtypeStruct((1, 16), i32),
               jax.ShapeDtypeStruct((1, 4), i32),
               jax.ShapeDtypeStruct((1,), i32))
        for what, fn, rest in (
                ("step", kind.step_fn(4), step),
                ("prefill", kind.prefill_fn(4, name="prefill"), pre)):
            out[f"{name}.{what}"] = jax.jit(fn, donate_argnums=(1,)).lower(
                params, pools, *rest).compiler_ir(
                    dialect="stablehlo").operation.get_asm(
                        enable_debug_info=False)
    # this kind's own step (PR 34: the expert layer's many-token path
    # changed; a step is few tokens and must not)
    model, _ = build(seed=13)
    kind = model_kinds.for_model(model)
    rows = jax.ShapeDtypeStruct((2,), i32)
    # and, since PR 35, its prefill and the two of the fourth kind (the
    # second with state by slot), as the PR that brought that kind
    # lowers them
    from paddle_tpu.models.afmoe import Afmoe, afmoe_tiny
    paddle.seed(13)
    for name, model in (("kimi_linear", model), ("afmoe", Afmoe(afmoe_tiny(
            held_experts=(4, 6))))):
        kind = model_kinds.for_model(model)
        args = (framework.param_arrays(model),
                kind.pools_sds(9, 4, kind.pool_dtype(None), 2))
        for what, fn, rest in (
                ("step", kind.step_fn(4),
                 (jax.ShapeDtypeStruct((2, 4), i32), rows, rows, rows)),
                ("prefill", kind.prefill_fn(4, name="prefill"),
                 (jax.ShapeDtypeStruct((1, 16), i32),
                  jax.ShapeDtypeStruct((1, 4), i32),
                  jax.ShapeDtypeStruct((1,), i32),
                  jax.ShapeDtypeStruct((), i32)))):
            out[f"{name}.{what}"] = jax.jit(fn, donate_argnums=(1,)).lower(
                *args, *rest).compiler_ir(
                    dialect="stablehlo").operation.get_asm(
                        enable_debug_info=False)
    return out


@pytest.mark.parametrize("program", ["gpt.step", "gpt.prefill", "axk1.step",
                                     "axk1.prefill", "kimi_linear.step",
                                     "kimi_linear.prefill", "afmoe.step",
                                     "afmoe.prefill"])
def test_the_seam_leaves_the_other_kinds_programs_text_equal(program):
    """No slot argument is threaded through kinds that have no such
    state, and the shared MLA / FFN / routing functions trace for
    `axk1` exactly what they traced: the step and the prefill of `gpt`
    and of `axk1`, through their kinds, lower to the parent's text (and
    since PR 34 this kind's own step to its parent's)."""
    got = hashlib.sha256(_seam_texts()[program].encode()).hexdigest()
    assert got == PARENT[program], (program, got)


def test_the_engine_hands_the_slot_only_to_a_kind_with_slot_state():
    """The engine's own dispatch: three step arguments after the tables
    for `kimi_linear` (token, length, slot), two for the others, and
    the token program to match."""
    model, _ = build(seed=14)
    paddle.seed(14)
    engines = {"kimi": DecodeEngine(model, max_slots=2, page_tokens=8),
               "axk1": DecodeEngine(AXK1(axk1_tiny()), max_slots=2,
                                    page_tokens=8)}
    try:
        for name, eng in engines.items():
            eng.submit([1, 2, 3], max_new_tokens=3).result(timeout=300)
            by_slot = name == "kimi"
            assert eng._by_slot is by_slot
            exe = eng._token_exes(1)[0]
            outs = exe(jnp.zeros((2,), jnp.int32),
                       jnp.zeros((3, 1), jnp.int32))
            assert len(outs) == (3 if by_slot else 2)
            assert eng.stats()["state_slots"] == (2 if by_slot else 0)
    finally:
        for eng in engines.values():
            eng.stop()


def test_another_kinds_process_loads_no_module_for_this_kind():
    """A process that serves `gpt` or `axk1` imports the engine and, by
    `model_kinds`, this model's module, but none of what only this kind
    runs: the KDA kernels (and with them `jax.experimental.pallas`,
    129 modules and a second of set-up in the chat cell) are imported
    where they run, as `axk1` imports its latent kernel."""
    code = ("import sys, paddle_tpu.inference.decode as d; "
            "assert 'paddle_tpu.models.kimi_linear' in sys.modules; "
            "bad = [m for m in sys.modules if 'pallas' in m]; "
            "assert not bad, bad[:5]")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))),
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
