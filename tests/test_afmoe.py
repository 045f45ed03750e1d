"""AFMoE (`models.afmoe`, Arcee Trinity) at toy widths on the CPU: the
plain forward against the benchmark's reference, the served path
(prefill into full-layer pages AND the window layers' rings, then the
paged step, through `DecodeEngine`) against the reference's full
forward while the rings wrap, what a ring by slot asks of the engine (a
reused slot, preemption, padding rows, no prefix reuse), the eight
shares of one expert layer, both kernels against masks written out,
what the model kind refuses, its artifact, and the lookup of a kind by
config and by model."""
import json

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
from paddle_tpu.models.afmoe import (FULL, SLIDING, Afmoe, AfmoeConfig,
                                     afmoe_forward, afmoe_tiny)
from paddle_tpu.models.axk1 import AXK1, AXK1Config, axk1_tiny
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_tiny
from paddle_tpu.models.kimi_linear import (KimiLinear, KimiLinearConfig,
                                           kimi_linear_tiny)
from paddle_tpu.nn.layer import moe
from paddle_tpu.ops.pallas import flash_attention, gqa_attention


def reference():
    from chipbench.reference import afmoe as ref
    return ref


def ref_sizes(cfg):
    """The reference's sizes for a program config (what the benchmark's
    family hands it)."""
    return {"layers": cfg.num_hidden_layers,
            "dense_layers": cfg.num_dense_layers,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "window": cfg.sliding_window, "rope_theta": cfg.rope_theta,
            "held": cfg.held_experts, "top_k": cfg.num_experts_per_tok,
            "route_norm": cfg.route_norm, "route_scale": cfg.route_scale,
            "mup": cfg.mup_enabled, "eps": cfg.rms_norm_eps,
            "sliding": tuple(cfg.is_window(i)
                             for i in range(cfg.num_hidden_layers))}


def build(seed=0, **kw):
    paddle.seed(seed)
    model = Afmoe(afmoe_tiny(**kw))
    return model, framework.param_arrays(model)


# ------------------------------------------------------------ the forward


def test_the_config_says_which_layer_attends_how():
    cfg = AfmoeConfig()                     # the published model
    assert cfg.num_hidden_layers == 60 and len(cfg.layer_types) == 60
    assert [i for i in range(60) if not cfg.is_window(i)] == list(
        range(3, 60, 4))                    # every fourth layer is full
    assert cfg.kv_width == 1024 and cfg.moe_layers == 54
    tiny = afmoe_tiny()
    assert tiny.window_index == {0: 0, 1: 1, 3: 2, 4: 3}
    assert tiny.full_index == {2: 0}
    # K and V, four window layers, a ring of 8 rows of 2 x 16 values
    assert tiny.ring_slot_bytes == 4 * 2 * 8 * 32 * 4
    with pytest.raises(ValueError):
        afmoe_tiny(layer_types=(SLIDING, FULL))
    with pytest.raises(ValueError):
        afmoe_tiny(num_key_value_heads=4)


@pytest.mark.parametrize("kw", [{}, {"held_experts": (4, 6)},
                                {"sliding_window": 1000}])
def test_layer_forward_is_the_reference(kw):
    """Logits of the uncached forward against the plain reference.
    float32 on both sides at "highest": what is left is the order of
    summation (grouped heads in one einsum against a head at a time,
    dense-over-held against looped experts), a few ulps through 5
    layers of sandwich norms."""
    model, params = build(seed=1, **kw)
    ids = np.random.default_rng(1).integers(0, 128, 45)
    want = np.asarray(reference().forward(params, ids, ref_sizes(model.cfg)))
    got = np.asarray(afmoe_forward(model.cfg, params, jnp.asarray(ids)))
    assert np.abs(got - want).max() <= 3e-4 * want.std()
    np.testing.assert_array_equal(
        np.asarray(model(paddle.to_tensor(ids))._data), got)


# ----------------------------------------- pages and rings, the engine


def _engine_logits(model, prompts, max_new, slots=2, **engine_kw):
    """Each request's tokens and the logits rows the engine sampled
    them from (prefill's, then every step's): `top_k=1` sampling keeps
    the best id and makes the tick pull the logits."""
    eng = DecodeEngine(model, max_slots=slots, page_tokens=4,
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
        tokens = [s.result(timeout=600) for s in streams]
        stats = eng.stats()
    finally:
        eng.stop()
    return tokens, [rows[s.request_id] for s in streams], stats


@pytest.mark.parametrize("dtype,tol,why", [
    ("float32", 3e-4,
     "float32 on both sides: what is left is the order of summation "
     "(the ring's rows in ring order against positions in order, online "
     "softmax a page at a time, dense-over-held against looped experts)"),
    ("bfloat16", 0.08,
     "bfloat16 weights are common to both sides; the program rounds "
     "every activation and cached row to 8 bits between products and "
     "the reference keeps float32 (read: 2-6% of the logits' standard "
     "deviation a row). A rounding that flips the fourth against the "
     "fifth of 16 expert scores, half of them held, changes what a "
     "layer adds, and that row, one in six, then reads 0.2-0.7 of a "
     "deviation: so the median row keeps to the tolerance and every "
     "row to one deviation"),
])
def test_engine_logits_follow_the_reference_through_pages_and_ring(
        dtype, tol, why):
    """A window of 8 positions in pages of 4: prompts shorter than,
    equal to and longer than the window (one several windows long),
    decoded until 40 and more positions lie behind, so every ring wraps
    several times; two slots for five requests, so rows join and leave
    at different depths and each slot is reused by a request whose
    prefill must overwrite the last stream's ring. Every sampled row
    against the plain reference's full forward over prompt + served."""
    model, params = build(seed=7, held_experts=(4, 8), dtype=dtype)
    cfg = model.cfg
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 8, 21, 3, 33)]
    max_new = [40, 14, 9, 6, 12]
    tokens, rows, stats = _engine_logits(model, prompts, max_new)
    ref, c, off = reference(), ref_sizes(cfg), []
    for prompt, n, toks, got in zip(prompts, max_new, tokens, rows):
        assert len(toks) == n and len(got) == n
        logits = np.asarray(ref.forward(params, np.asarray(prompt + toks),
                                        c))
        want = logits[len(prompt) - 1:len(prompt) + n - 1]
        off.extend(np.abs(np.stack(got) - want).max(-1) / np.std(want))
        if dtype == "float32":          # the tokens are the reference's
            assert toks == want.argmax(-1).tolist()
    if dtype == "float32":
        assert max(off) <= tol, why
    else:
        assert np.median(off) <= tol and max(off) <= 1.0, why
    assert stats["model_kind"] == "afmoe"
    # rings by slot: 2 slots and the null slot, 4 window layers, K and
    # V, 8 rows of 2 x 16 values; no trie
    isz = 4 if dtype == "float32" else 2
    assert stats["state_slots"] == 2
    assert stats["state_pool_bytes"] == 3 * 4 * 2 * 8 * 32 * isz
    assert "prefix_cache" not in stats
    n_tok = sum(len(p) + n - 1 for p, n in zip(prompts, max_new))
    assert stats["routed_tokens"] == n_tok
    assert np.shape(stats["routed"]) == (4, 8)


def test_a_tail_fed_through_the_step_beside_another_depth_and_padding():
    """On the kind's own programs: prefill the first m tokens into a
    slot whose rings hold another stream's rows, then feed the rest one
    token a step (what the engine's tail feeding and its resume do);
    beside it a second row at another depth in another slot, and a
    padding row on the null slot. Every step's logits are the
    reference's at that position; the padding row touches the null page
    and the null slot's ring and nothing else."""
    model, params = build(seed=12)
    cfg = model.cfg
    kind = model_kinds.for_model(model)
    pt, slots_n, rp = 4, 2, 2
    pools = kind.pools_zeros(17, pt, None, slots=slots_n)
    stale = {c: tuple(a + 1.0 for a in pools[c])
             for c in ("k", "v", "ring_k", "ring_v")}
    pools = dict(pools, **stale)
    prefill = jax.jit(kind.prefill_fn(pt))
    step = jax.jit(kind.step_fn(pt))
    rng = np.random.default_rng(12)
    ids, other = rng.integers(0, 128, 30), rng.integers(0, 128, 30)
    m, m_other = 11, 3
    i32 = jnp.int32

    def pre(pools, toks, pages, slot):
        inp = np.zeros((1, 16), np.int32)
        inp[0, :len(toks)] = toks
        tbl = np.zeros((1, 4), np.int32)
        tbl[0, :len(pages)] = pages
        return prefill(params, pools, jnp.asarray(inp), jnp.asarray(tbl),
                       jnp.asarray([len(toks)], i32), jnp.asarray(slot, i32))

    start = jax.tree.map(np.asarray, pools)
    _, pools = pre(pools, ids[:m], [1, 2, 3], 1)
    _, pools = pre(pools, other[:m_other], [9], 0)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8],
                          [9, 10, 11, 12, 13, 14, 15, 16],
                          [0] * 8], i32)
    want = np.asarray(reference().forward(params, ids, ref_sizes(cfg)))
    want_other = np.asarray(reference().forward(params, other,
                                                ref_sizes(cfg)))
    for t in range(m, 30):
        t2 = t - m + m_other
        logits, pools = step(
            params, pools, tables,
            jnp.asarray([ids[t], other[t2], 0], i32),
            jnp.asarray([t, t2, 0], i32), jnp.asarray([1, 0, slots_n], i32))
        np.testing.assert_allclose(np.asarray(logits[0]), want[t],
                                   rtol=3e-4, atol=3e-5)
        np.testing.assert_allclose(np.asarray(logits[1]), want_other[t2],
                                   rtol=3e-4, atol=3e-5)
    # a step of padding rows alone: the null page, the null slot
    before = jax.tree.map(np.asarray, pools)
    _, pools = step(params, pools, jnp.zeros((2, 8), i32),
                    jnp.zeros((2,), i32), jnp.zeros((2,), i32),
                    jnp.full((2,), slots_n, i32))
    after = jax.tree.map(np.asarray, pools)
    for c in ("k", "v"):
        for a, b in zip(before[c], after[c]):
            np.testing.assert_array_equal(a[1:], b[1:])
    for c in ("ring_k", "ring_v"):
        for a, b in zip(before[c], after[c]):
            np.testing.assert_array_equal(a[:slots_n * rp], b[:slots_n * rp])
    # and the padding rows of the steps before did land there: the null
    # slot's first ring row is no longer the stale one
    for c in ("ring_k", "ring_v"):
        for a, b in zip(start[c], after[c]):
            assert (a[slots_n * rp, 0] != b[slots_n * rp, 0]).all()
            np.testing.assert_array_equal(a[slots_n * rp, 1:],
                                          b[slots_n * rp, 1:])
    assert int(after["routed_tokens"]) == int(before["routed_tokens"])


def test_preemption_resumes_token_identically():
    """A victim's pages and slot are let go (nothing is stashed: no
    trie); its resume prefills prompt + generated, longer than the
    window by then, into whatever slot it gets and goes on as if
    nothing had happened."""
    model, _ = build(seed=10)
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, 128, 12).tolist()
    solo = DecodeEngine(model, max_slots=1, page_tokens=4, max_new_tokens=12)
    try:
        want = solo.submit(prompt, max_new_tokens=12).result(timeout=300)
    finally:
        solo.stop()
    eng = DecodeEngine(model, max_slots=1, page_tokens=4, max_new_tokens=12,
                       preempt=True)
    try:
        low = eng.submit(prompt, max_new_tokens=12, priority=0)
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
    without the ring at that boundary would serve wrong tokens, so this
    kind builds no trie."""
    model, _ = build(seed=9)
    rng = np.random.default_rng(9)
    head = rng.integers(0, 128, 16).tolist()         # four whole pages
    a, b = head + [1, 2, 3], head + [7, 7]
    alone = []
    for p in (a, b):
        eng = DecodeEngine(model, max_slots=1, page_tokens=4,
                           max_new_tokens=5)
        try:
            alone.append(eng.submit(p, max_new_tokens=5).result(timeout=300))
        finally:
            eng.stop()
    eng = DecodeEngine(model, max_slots=2, page_tokens=4, max_new_tokens=5,
                       prefix_cache=True)
    try:
        assert eng._prefix is None
        got = [eng.submit(p, max_new_tokens=5).result(timeout=300)
               for p in (a, b)]
        assert got == alone
    finally:
        eng.stop()


# ----------------------------------------------------- the expert layer


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """32 experts over 8 chips, 4 picks a token chosen on score + bias:
    the eight shares' routed parts add up to the uncut reference
    layer's routed part, and with the shared expert (which every chip
    computes alike) counted ONCE, to the whole layer's FFN."""
    ref = reference()
    rng = np.random.default_rng(3)
    N, H, F, E = 64, 32, 16, 32
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(H, E)) * 0.3, jnp.float32)
    ws = [jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
          for s in ((E, H, F), (E, H, F), (E, F, H))]
    bias = jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)
    shared = [jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
              for s in ((H, F), (H, F), (F, H))]
    c = {"top_k": 4, "route_norm": True, "route_scale": 2.448, "eps": 1e-5}
    w = {"pre_mlp_layernorm": jnp.ones(H), "router": rw, "bias": bias,
         "shared_gate_proj": shared[0], "shared_up_proj": shared[1],
         "shared_down_proj": shared[2]}
    f, m, picks, wts = ref.shared_and_route(w, x, ref._cfg_key(c), None)
    whole = np.asarray(ref.held_experts_add(f, m, picks, wts, *ws, 0, 64))
    routing = dict(top_k=4, norm_topk_prob=True, scale=2.448)
    # the bias changes the selection (else this test shows nothing)
    plain = np.asarray(moe.route_sigmoid_grouped(m, rw, **routing)[0])
    assert (np.sort(plain, 1) != np.sort(np.asarray(picks), 1)).any()
    parts, hits = 0.0, 0
    for first in range(0, E, E // 8):
        part, n = moe.routed_experts(
            m, rw, *(a[first:first + E // 8] for a in ws),
            held=(first, E // 8), select_bias=bias, **routing)
        parts = parts + np.asarray(part)
        hits += int(n.sum())
    assert hits == N * 4                             # every pick, once
    once = np.asarray(ref.swiglu(m, *shared))
    np.testing.assert_allclose(once + parts, whole, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ the kernels


def _masked_attention(q, k, v, scale, window):
    """The mask written out: q [T, Hq, D], k, v [T, Hkv, D]."""
    T, Hq, _ = q.shape
    g = Hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    t = np.arange(T)
    seen = t[:, None] >= t[None, :]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("T,Hq,Hkv,window,cells", [
    (512, 6, 1, 200, 3),        # group 6; a window that is no block multiple
    (512, 6, 1, None, 4),       # group 6, the whole triangle
    (512, 4, 4, 129, 2),        # group 1 under a window
    (256, 12, 2, 1000, 2),      # T below the window: the triangle again
    (512, 6, 2, 1, 1),          # a window of the position itself
])
def test_flash_forward_in_a_band_over_grouped_heads(monkeypatch, T, Hq, Hkv,
                                                    window, cells):
    monkeypatch.setenv("PT_FLASH_FWD_BLOCKS", "128,128")
    rng = np.random.default_rng(T + Hq)
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, h, 32)), jnp.float32)
               for h in (Hq, Hkv, Hkv))
    got = flash_attention.flash_attention_forward(
        q, k, v, causal=True, scale=0.2, window=window)[0]
    want = _masked_attention(q[0], k[0], v[0], 0.2, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the grid's k extent is the band's, not the sequence's
    if window is not None and window < T:
        assert flash_attention.band_blocks(T, 128, 128, window) == cells
    with pytest.raises(ValueError):
        flash_attention.flash_attention_forward(q, k, v, causal=False,
                                                window=4)
    with pytest.raises(ValueError):
        flash_attention.flash_attention_forward(   # 4 does not divide
            q[:, :, :4], jnp.zeros((1, T, 3, 32)), jnp.zeros((1, T, 3, 32)))


@pytest.mark.parametrize("B,Hq,Hkv,D,pt,W,P", [
    (3, 6, 1, 16, 4, 8, 40),        # group 6, four pages a grid cell
    (2, 4, 4, 16, 8, 3, 9),         # group 1, one page a cell
    (3, 12, 2, 8, 4, 4, 20),
])
def test_the_paged_reader_against_its_composition(B, Hq, Hkv, D, pt, W, P):
    rng = np.random.default_rng(B + W)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(P, pt, Hkv * D)), jnp.float32)
              for _ in range(2))
    tables = jnp.asarray(rng.integers(1, P, (B, W)), jnp.int32)
    lengths = jnp.asarray([1, W * pt, W * pt // 2 - 1][:B], jnp.int32)
    got = gqa_attention.paged_gqa_decode_attention(
        q, kp, vp, tables, lengths, 0.3, kernel="pallas")
    want = gqa_attention.paged_gqa_decode_attention(
        q, kp, vp, tables, lengths, 0.3, kernel="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # against the mask written out, row 2: its first `lengths` rows
    n = int(lengths[-1])
    b = B - 1
    k = np.asarray(kp)[np.asarray(tables[b])].reshape(-1, Hkv, D)[:n]
    v = np.asarray(vp)[np.asarray(tables[b])].reshape(-1, Hkv, D)[:n]
    g = Hq // Hkv
    s = np.einsum("hd,thd->ht", np.asarray(q[b]),
                  np.repeat(k, g, axis=1)) * 0.3
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(got[b]), np.einsum("ht,thd->hd", p,
                                      np.repeat(v, g, axis=1)),
        rtol=2e-5, atol=2e-5)


def test_a_ring_is_read_as_it_lies():
    """Rows of a ring in ring order (position p at p mod window) give
    what the same rows in position order give: the reader needs no
    unrolling, only the count of live rows."""
    rng = np.random.default_rng(5)
    Hq, Hkv, D, pt, window = 6, 2, 16, 4, 16
    q = jnp.asarray(rng.normal(size=(1, Hq, D)), jnp.float32)
    k = rng.normal(size=(window, Hkv * D)).astype(np.float32)
    v = rng.normal(size=(window, Hkv * D)).astype(np.float32)
    tables = jnp.arange(window // pt, dtype=jnp.int32)[None]
    outs = []
    for shift in (0, 5):        # the ring after 5 more positions wrapped
        kp = jnp.asarray(np.roll(k, shift, 0).reshape(-1, pt, Hkv * D))
        vp = jnp.asarray(np.roll(v, shift, 0).reshape(-1, pt, Hkv * D))
        outs.append(np.asarray(gqa_attention.paged_gqa_decode_attention(
            q, kp, vp, tables, jnp.asarray([window], jnp.int32), 0.25,
            kernel="pallas")))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ the model kind


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
    assert what in str(err.value) and "ROADMAP.md R2" in str(err.value)
    assert "afmoe" in str(err.value) and "K/V pages" in str(err.value)


def test_speculation_and_ragged_rings_are_refused():
    model, _ = build()
    paddle.seed(0)
    gpt = GPT(gpt_tiny())
    for kw in (dict(model=model, draft_model=gpt),
               dict(model=gpt, draft_model=model)):
        with pytest.raises(TypedServeError) as err:
            SpecDecodeEngine(speculate_k=2, max_slots=1, **kw)
        assert err.value.code == ERR_FAILED_PRECONDITION
        assert "speculative" in str(err.value)
    assert not hasattr(model_kinds.AfmoeKind, "verify_fn")
    with pytest.raises(ValueError, match="whole pages"):
        DecodeEngine(model, max_slots=1, page_tokens=3)


def test_the_kind_counts_pages_and_rings():
    kind = model_kinds.for_config(afmoe_tiny(dtype="bfloat16"))
    assert kind.name == "afmoe" and kind.slot_state
    # one full layer: a K and a V row of 32 values a position
    assert kind.page_bytes(4, None) == 2 * 4 * 32 * 2
    ring = 4 * 2 * 8 * 32 * 2
    assert kind.state_bytes(3) == 4 * ring
    assert kind.slot_bytes() == 2 * 128 * 32 * 2 + ring
    pools = kind.pools_sds(9, 4, None, slots=3)
    assert [a.shape for a in pools["k"]] == [(9, 4, 32)]
    assert [a.shape for a in pools["ring_v"]] == [(4 * 2, 4, 32)] * 4
    assert pools["routed"].shape == (4, 16)
    zeros = kind.pools_zeros(9, 4, None, slots=3)
    marked = dict(zeros, k=(zeros["k"][0].at[2].set(1.0),))
    copied = kind.copy_page(marked, jnp.int32(2), jnp.int32(5))
    assert float(copied["k"][0][5].min()) == 1.0
    assert float(copied["ring_k"][0].max()) == 0.0


def test_a_kind_is_looked_up_by_its_config_and_by_its_model():
    """`for_config` and `for_model` are one lookup over `KINDS`: each
    kind names its config class and its model class."""
    paddle.seed(0)
    cases = [("gpt", GPTConfig, GPT, gpt_tiny()),
             ("axk1", AXK1Config, AXK1, axk1_tiny()),
             ("kimi_linear", KimiLinearConfig, KimiLinear,
              kimi_linear_tiny()),
             ("afmoe", AfmoeConfig, Afmoe, afmoe_tiny())]
    assert sorted(model_kinds.KINDS) == sorted(c[0] for c in cases)
    for name, config_cls, model_cls, cfg in cases:
        kind = model_kinds.KINDS[name]
        assert kind.config_cls is config_cls and kind.model_cls is model_cls
        assert type(model_kinds.for_config(cfg)) is kind
        assert type(model_kinds.for_model(model_cls(cfg))) is kind

    class Mine(GPT):
        pass

    assert type(model_kinds.for_model(Mine(gpt_tiny()))) is \
        model_kinds.GPTKind
    with pytest.raises(TypeError):
        model_kinds.for_config(object())
    with pytest.raises(ValueError):
        model_kinds.from_manifest({"model_kind": "nope"})


def test_artifact_carries_the_model_kind(tmp_path):
    model, params = build(seed=11, dtype="bfloat16", held_experts=(2, 5))
    prefix = str(tmp_path / "afmoe")
    save_for_decode(model, prefix)
    meta = json.load(open(prefix + ".decode.json"))
    assert meta["model_kind"] == "afmoe"
    assert meta["config"]["held_experts"] == [2, 5]
    assert meta["config"]["layer_types"] == [SLIDING, SLIDING, FULL,
                                             SLIDING, SLIDING]
    kind, loaded = _load_decode_artifact(prefix)
    assert kind.name == "afmoe" and kind.cfg == model.cfg
    assert kind.slot_state is True
    for k, v in params.items():
        assert loaded[k].dtype == v.dtype
        np.testing.assert_array_equal(np.asarray(loaded[k], np.float32),
                                      np.asarray(v, np.float32))
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    a = DecodeEngine(model, max_slots=1, page_tokens=4)
    b = load_for_decode(prefix, max_slots=1, page_tokens=4)
    try:
        assert isinstance(b, DecodeEngine) and b.fingerprint == a.fingerprint
        assert b.submit(prompt, max_new_tokens=4).result(timeout=300) == \
            a.submit(prompt, max_new_tokens=4).result(timeout=300)
    finally:
        a.stop()
        b.stop()
    with pytest.raises(ValueError):
        save_for_decode(model, prefix, quant="int8")
