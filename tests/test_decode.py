"""BeamSearchDecoder + dynamic_decode (reference fluid/layers/rnn.py:866,
1581; test strategy: test_rnn_decode_api.py greedy-equivalence +
hand-checked beam)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn

RNG = np.random.RandomState(17)


class _FixedLogitCell(nn.RNNCellBase):
    """Cell that ignores input and emits logits from a fixed table
    indexed by time (via a counter in state)."""

    def __init__(self, table):
        super().__init__()
        self.table = np.asarray(table, np.float32)   # [T, V]

    def forward(self, inputs, states):
        import jax.numpy as jnp
        from paddle_tpu.core.tensor import Tensor
        step = states._data if isinstance(states, Tensor) else states
        t = jnp.clip(step[:, 0].astype(jnp.int32), 0, len(self.table) - 1)
        logits = jnp.asarray(self.table)[t]
        return Tensor(logits), Tensor(step + 1.0)


def test_gather_tree_hand_case():
    # kernel example: T=3, B=1, K=2
    ids = np.array([[[2, 2]], [[6, 1]], [[3, 9]]], np.int64)
    parents = np.array([[[0, 0]], [[1, 0]], [[0, 1]]], np.int64)
    out = nn.gather_tree(paddle.to_tensor(ids),
                         paddle.to_tensor(parents)).numpy()
    # beam 0 at t=2 came from parent 0 at t=1 (token 6), whose parent at
    # t=0 is 1 -> token 2; beam 1 traces 9 <- parent 1 (token 1) <- 0 (2)
    np.testing.assert_array_equal(out[:, 0, 0], [2, 6, 3])
    np.testing.assert_array_equal(out[:, 0, 1], [2, 1, 9])


def test_beam1_equals_greedy():
    V = 6
    table = RNG.randn(5, V).astype(np.float32)
    table[:, 0] -= 100.0          # avoid instant EOS (end_token=0)
    cell = _FixedLogitCell(table)
    dec = nn.BeamSearchDecoder(cell, start_token=1, end_token=0,
                               beam_size=1,
                               embedding_fn=lambda ids: paddle.to_tensor(
                                   np.zeros((int(np.prod(ids.shape)), 1),
                                            np.float32)))
    init = paddle.to_tensor(np.zeros((2, 1), np.float32))
    out, _, lens = nn.dynamic_decode(dec, inits=init, max_step_num=5,
                                     return_length=True)
    pred = out.numpy()                  # [B, T, 1]
    greedy = table.argmax(axis=1)
    for b in range(2):
        np.testing.assert_array_equal(pred[b, :, 0], greedy)


def test_beam4_hand_checked():
    # V=3, end=2. Step logits chosen so the best 2-step path switches beams
    t0 = np.log(np.array([0.6, 0.3, 0.1], np.float32))
    t1 = np.log(np.array([0.1, 0.2, 0.7], np.float32))
    table = np.stack([t0, t1])
    cell = _FixedLogitCell(table)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=2,
                               beam_size=3,
                               embedding_fn=lambda ids: paddle.to_tensor(
                                   np.zeros((int(np.prod(ids.shape)), 1),
                                            np.float32)))
    init = paddle.to_tensor(np.zeros((1, 1), np.float32))
    out, states, lens = nn.dynamic_decode(dec, inits=init, max_step_num=2,
                                          return_length=True)
    pred = out.numpy()[0]               # [T, K]
    # step0 best tokens: 0 (0.6), 1 (0.3), 2 (0.1). step1 all beams see
    # the same logits; best joint: 0->2 (0.6*0.7); then 1->2 (0.3*0.7);
    # then the step-0 EOS beam (0.1, frozen emitting eos, total 0.1 >
    # 0.6*0.2=0.12? no: 0.12 > 0.1) -> 0->1 (0.12)
    np.testing.assert_array_equal(pred[:, 0], [0, 2])
    np.testing.assert_array_equal(pred[:, 1], [1, 2])
    np.testing.assert_array_equal(pred[:, 2], [0, 1])
    sc = states.log_probs.numpy()[0]
    np.testing.assert_allclose(np.exp(sc), [0.42, 0.21, 0.12], atol=1e-4)
    np.testing.assert_array_equal(lens.numpy()[0], [2, 2, 2])


def test_beam_search_with_real_gru_trains_nothing_but_runs():
    # full wiring: embedding + GRUCell + output projection, batch 2
    V, D, H, K = 10, 8, 8, 4
    emb = nn.Embedding(V, D)
    cell = nn.GRUCell(D, H)
    proj = nn.Linear(H, V)
    dec = nn.BeamSearchDecoder(cell, start_token=1, end_token=2,
                               beam_size=K, embedding_fn=emb,
                               output_fn=proj)
    enc_final = paddle.to_tensor(RNG.randn(2, H).astype(np.float32))
    out, states, lens = nn.dynamic_decode(dec, inits=enc_final,
                                          max_step_num=7,
                                          return_length=True)
    o = out.numpy()
    assert o.shape[0] == 2 and o.shape[2] == K and o.shape[1] <= 7
    assert (o >= 0).all() and (o < V).all()
    assert lens.numpy().shape == (2, K)
    # time-major variant
    out_tm, _ = nn.dynamic_decode(dec, inits=enc_final, max_step_num=4,
                                  output_time_major=True)
    assert out_tm.numpy().shape[1] == 2


def test_dynamic_decode_stops_on_eos():
    # logits force EOS at step 1 for every beam -> decode stops early
    table = np.array([[0.0, 5.0, -5.0], [-5.0, -5.0, 5.0]], np.float32)
    cell = _FixedLogitCell(table)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=2,
                               beam_size=2,
                               embedding_fn=lambda ids: paddle.to_tensor(
                                   np.zeros((int(np.prod(ids.shape)), 1),
                                            np.float32)))
    init = paddle.to_tensor(np.zeros((1, 1), np.float32))
    out, states, lens = nn.dynamic_decode(dec, inits=init, max_step_num=10,
                                          return_length=True)
    assert out.numpy().shape[1] == 2          # stopped at t=2, not 10
    assert states.finished.numpy().all()


def test_dynamic_decode_exports_under_jit():
    import jax
    import jax.numpy as jnp
    V, D, H, K = 8, 4, 4, 2
    emb = nn.Embedding(V, D)
    cell = nn.GRUCell(D, H)
    proj = nn.Linear(H, V)
    dec = nn.BeamSearchDecoder(cell, start_token=1, end_token=0,
                               beam_size=K, embedding_fn=emb,
                               output_fn=proj)

    def decode(enc):
        out, _ = nn.dynamic_decode(dec, inits=paddle.to_tensor(enc),
                                   max_step_num=5)
        return out._data

    enc = RNG.randn(2, H).astype(np.float32)
    jitted = jax.jit(decode)
    got = jitted(enc)
    assert got.shape == (2, 5, K)
    eager, _ = nn.dynamic_decode(dec, inits=paddle.to_tensor(enc),
                                 max_step_num=5)
    e = eager.numpy()
    np.testing.assert_array_equal(np.asarray(got)[:, :e.shape[1]], e)


def test_early_stop_preserves_distinct_beams():
    # regression: padded gather_tree rows must not collapse beams to
    # beam 0 when decoding stops well before max_step_num
    t0 = np.log(np.array([0.55, 0.35, 0.1], np.float32))
    t1 = np.log(np.array([0.05, 0.05, 0.9], np.float32))   # all -> EOS
    cell = _FixedLogitCell(np.stack([t0, t1]))
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=2,
                               beam_size=3,
                               embedding_fn=lambda ids: paddle.to_tensor(
                                   np.zeros((int(np.prod(ids.shape)), 1),
                                            np.float32)))
    init = paddle.to_tensor(np.zeros((1, 1), np.float32))
    out, _, lens = nn.dynamic_decode(dec, inits=init, max_step_num=20,
                                     return_length=True)
    pred = out.numpy()[0]
    assert pred.shape[0] == 2          # stopped at t=2, not 20
    # the three beams end distinct: 0->2, 1->2, 2(eos at t=0)
    np.testing.assert_array_equal(pred[:, 0], [0, 2])
    np.testing.assert_array_equal(pred[:, 1], [1, 2])
    assert pred[0, 2] == 2


def test_custom_decoder_generic_path():
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor

    class CountDecoder(nn.Decoder):
        """Emits time indices; finishes after 3 steps."""

        def initialize(self, inits):
            b = int(inits.shape[0])
            state = {"t": jnp.zeros((b,), jnp.int32)}
            return jnp.zeros((b, 1), jnp.float32), state, \
                jnp.zeros((b,), bool)

        def step(self, time, inputs, states):
            t = states["t"]
            out = {"tok": t * 10}
            nxt = {"t": t + 1}
            fin = (t + 1) >= 3
            return out, nxt, inputs, fin

    dec = CountDecoder()
    out, final = nn.dynamic_decode(
        dec, inits=paddle.to_tensor(np.zeros((2, 1), np.float32)),
        max_step_num=8)
    tok = out["tok"].numpy()          # [B, T]
    assert tok.shape == (2, 3)
    np.testing.assert_array_equal(tok[0], [0, 10, 20])
    np.testing.assert_array_equal(final["t"].numpy(), [3, 3])


# -- continuous-batching KV-cache decode engine (inference/decode.py) ----
#
# Correctness gate: the incremental prefill / paged-step path must emit
# logits identical (to fp32 rounding) to the full forward pass, on BOTH
# parameter layouts a GPT can produce (scan-stacked and per-block
# unrolled). Everything downstream (engine, serving, bench) rides on it.

import time

import jax
import jax.numpy as jnp

import paddle_tpu.framework as framework
from paddle_tpu import profiler
from paddle_tpu.inference.decode import DecodeEngine, save_for_decode
from paddle_tpu.inference.errors import (ERR_INVALID_ARGUMENT,
                                         ERR_UNAVAILABLE, TypedServeError)
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_tiny
from paddle_tpu.testing import chaos

_DECODE_CFGS = [
    ("tiny-scan", gpt_tiny()),                       # scan-stacked params
    ("small-unrolled", GPTConfig(vocab_size=256, max_seq_len=64, hidden=32,
                                 layers=3, heads=2, scan_layers=False)),
]


@pytest.fixture(scope="module")
def gpt_models():
    paddle.seed(7)
    return {name: GPT(cfg) for name, cfg in _DECODE_CFGS}


def _full_logits(model, toks):
    """Reference: full forward over the whole sequence, last position."""
    idx = paddle.to_tensor(np.asarray([toks], np.int64))
    return model(idx).numpy()[0, -1].astype(np.float32)


def _ref_greedy(model, prompt, n, eos_id=None):
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        t = int(_full_logits(model, toks).argmax())
        out.append(t)
        toks.append(t)
        if eos_id is not None and t == eos_id:
            break
    return out


@pytest.mark.parametrize("name", [n for n, _ in _DECODE_CFGS])
def test_incremental_decode_matches_full_forward(gpt_models, name):
    """prefill-into-pages + N paged steps == full forward, token for
    token AND logit for logit, on both param layouts."""
    from paddle_tpu.inference import model_kinds
    model = gpt_models[name]
    cfg = model.cfg
    kind = model_kinds.for_model(model)
    pt = 8
    prefill = jax.jit(kind.prefill_fn(pt))
    step = jax.jit(kind.step_fn(pt))
    params = {k: jnp.asarray(v)
              for k, v in framework.param_arrays(model).items()}

    rng = np.random.RandomState(3)
    plen, steps, cap = 9, 6, 32
    toks = [int(t) for t in rng.randint(0, cfg.vocab_size, size=plen)]
    padded = np.zeros((1, cap), np.int32)
    padded[0, :plen] = toks
    W = cap // pt
    pools = kind.pools_zeros(W + 1, pt, "float32")
    tables = jnp.arange(1, W + 1, dtype=jnp.int32)[None]
    logits, pools = prefill(params, pools, jnp.asarray(padded), tables,
                            jnp.asarray([plen], np.int32))
    np.testing.assert_allclose(np.asarray(logits)[0],
                               _full_logits(model, toks), atol=1e-4)
    cache_len = plen
    last = int(np.asarray(logits)[0].argmax())
    for _ in range(steps):
        toks.append(last)
        logits, pools = step(params, pools, tables,
                             jnp.asarray([last], np.int32),
                             jnp.asarray([cache_len], np.int32))
        np.testing.assert_allclose(np.asarray(logits)[0],
                                   _full_logits(model, toks), atol=1e-4)
        cache_len += 1
        last = int(np.asarray(logits)[0].argmax())


def test_engine_zero_compiles_after_warmup(gpt_models):
    """The AOT ladder covers every (batch-rung x kv-rung) signature the
    engine can dispatch: after warmup() a full multi-request run — with
    ragged joins forcing pool rebuilds — compiles NOTHING."""
    model = gpt_models["tiny-scan"]
    eng = DecodeEngine(model, max_slots=4, max_new_tokens=16)
    try:
        n = eng.warmup()
        assert n >= 0
        c0 = len(profiler.compile_events())
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, model.cfg.vocab_size, size=p)
                   for p in (5, 11, 8)]
        streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
        results = [s.result(timeout=120) for s in streams]
        assert len(profiler.compile_events()) == c0, \
            "decode engine compiled during a warmed-up run"
        for p, got in zip(prompts, results):
            assert got == _ref_greedy(model, p, 12), \
                "engine tokens diverged from full-forward reference"
        st = eng.stats()
        assert st["active"] == 0 and st["pending"] == 0
    finally:
        eng.stop()


def test_ragged_join_and_early_leave(gpt_models):
    """Continuous batching semantics: a request arriving mid-run joins
    the running batch; one hitting EOS early frees its KV slot for the
    next admission — and nobody's tokens change."""
    model = gpt_models["tiny-scan"]
    rng = np.random.RandomState(23)
    p_long = rng.randint(0, 512, size=10)
    p_eos = rng.randint(0, 512, size=6)
    p_late = rng.randint(0, 512, size=7)
    ref_long = _ref_greedy(model, p_long, 20)
    ref_eos_full = _ref_greedy(model, p_eos, 20)
    eos = ref_eos_full[2]            # stop at its first occurrence
    ref_eos = ref_eos_full[:ref_eos_full.index(eos) + 1]
    ref_late = _ref_greedy(model, p_late, 8)

    eng = DecodeEngine(model, max_slots=2, max_new_tokens=32)
    try:
        s_long = eng.submit(p_long, max_new_tokens=20)
        s_eos = eng.submit(p_eos, max_new_tokens=20, eos_id=eos)
        # the EOS stream dies early -> its slot frees -> the late
        # arrival joins while s_long is still mid-generation
        assert s_eos.result(timeout=120) == ref_eos
        s_late = eng.submit(p_late, max_new_tokens=8)
        assert s_late.result(timeout=120) == ref_late
        assert s_long.result(timeout=120) == ref_long
        st = eng.stats()
        assert st["active"] == 0 and st["tokens"] >= \
            len(ref_long) + len(ref_eos) + len(ref_late)
    finally:
        eng.stop()


def test_decode_chaos_kill_mid_stream(gpt_models):
    """Chaos drill: first token delivery raises -> THAT stream gets a
    typed UNAVAILABLE; the concurrently running stream is unharmed."""
    from paddle_tpu.observability import REGISTRY
    model = gpt_models["tiny-scan"]
    rng = np.random.RandomState(31)
    p1 = rng.randint(0, 512, size=8)
    p2 = rng.randint(0, 512, size=8)
    ref2 = _ref_greedy(model, p2, 6)
    eng = DecodeEngine(model, max_slots=2, max_new_tokens=8)
    try:
        with chaos.inject("decode.stream:1:RuntimeError") as inj:
            s1 = eng.submit(p1, max_new_tokens=6)
            time.sleep(0.2)          # ensure s1 admits first (site call 1)
            s2 = eng.submit(p2, max_new_tokens=6)
            with pytest.raises(TypedServeError) as ei:
                s1.result(timeout=120)
            assert ei.value.code == ERR_UNAVAILABLE
            assert s2.result(timeout=120) == ref2
            assert inj.fired
        flat = REGISTRY.flat()
        assert flat.get(
            'paddle_tpu_decode_cache_evictions_total{reason="error"}', 0) \
            >= 1
    finally:
        eng.stop()


def test_engine_submit_validation(gpt_models):
    model = gpt_models["tiny-scan"]
    eng = DecodeEngine(model, max_slots=1, max_new_tokens=4)
    try:
        with pytest.raises(TypedServeError) as ei:
            eng.submit([])
        assert ei.value.code == ERR_INVALID_ARGUMENT
        with pytest.raises(TypedServeError) as ei:
            eng.submit([512])        # vocab is 512 -> out of range
        assert ei.value.code == ERR_INVALID_ARGUMENT
        with pytest.raises(TypedServeError) as ei:
            eng.submit(np.arange(200) % 512)   # longer than max_seq_len
        assert ei.value.code == ERR_INVALID_ARGUMENT
    finally:
        eng.stop()
    with pytest.raises(TypedServeError) as ei:
        eng.submit([1, 2, 3])
    assert ei.value.code == ERR_UNAVAILABLE


def test_decode_artifact_roundtrip(gpt_models, tmp_path):
    """save_for_decode -> load_for_decode serves the same tokens."""
    from paddle_tpu.inference.decode import load_for_decode
    model = gpt_models["small-unrolled"]
    prefix = str(tmp_path / "gpt")
    save_for_decode(model, prefix)
    prompt = np.random.RandomState(5).randint(0, 256, size=7)
    ref = _ref_greedy(model, prompt, 5)
    eng = load_for_decode(prefix, max_slots=1, max_new_tokens=8)
    try:
        assert eng.submit(prompt, max_new_tokens=5).result(timeout=120) \
            == ref
    finally:
        eng.stop()


def test_serve_decode_wire_roundtrip(gpt_models, tmp_path):
    """End-to-end over a socket: PDI2 clients stream per-token frames
    (seq-numbered, final done frame carries the accumulated reply);
    PDI1 clients get ONE accumulated frame — same bytes as ever."""
    import socket as socketlib

    from paddle_tpu.inference.serve import (InferenceServer, decode_request,
                                            read_reply_ctx, write_tensors)
    model = gpt_models["tiny-scan"]
    prefix = str(tmp_path / "gpt")
    save_for_decode(model, prefix)
    srv = InferenceServer(prefix, port=0, decode=True, decode_slots=2,
                          decode_max_new=6, metrics_port=0)
    try:
        prompt = np.random.RandomState(9).randint(0, 512, size=8)
        ref = _ref_greedy(model, prompt, 6)
        seen = []
        s = socketlib.create_connection(("127.0.0.1", srv.port), timeout=60)
        toks = decode_request(s, prompt, opts={"max_new_tokens": 6},
                              on_token=lambda t, c: seen.append(
                                  (t, c.get("seq"))))
        assert toks == ref
        assert [t for t, _ in seen] == ref
        assert [q for _, q in seen] == list(range(6))
        # bad prompt -> typed error frame; the connection survives
        write_tensors(s, [np.ones((4,), np.float32)],
                      ctx={"trace_id": "bad"})
        _, err, _ = read_reply_ctx(s)
        assert err and err.startswith(ERR_INVALID_ARGUMENT)
        assert decode_request(s, prompt,
                              opts={"max_new_tokens": 3}) == ref[:3]
        s.close()
        # PDI1: no context field -> server default max_new (6), one frame
        s = socketlib.create_connection(("127.0.0.1", srv.port), timeout=60)
        assert decode_request(s, prompt, trace=False) == ref
        s.close()
        assert srv._status()["engine"] == "decode"
    finally:
        srv.stop()


def test_decode_request_error_after_partial(gpt_models, tmp_path):
    """An error frame after seq>0 token frames surfaces the typed error
    AND the tokens already received — callers must never silently drop
    the partial prefix."""
    import socket as socketlib

    from paddle_tpu.inference.serve import InferenceServer, decode_request
    model = gpt_models["tiny-scan"]
    prefix = str(tmp_path / "gpt")
    save_for_decode(model, prefix)
    srv = InferenceServer(prefix, port=0, decode=True, decode_slots=2,
                          decode_max_new=8, metrics_port=0)
    try:
        prompt = np.random.RandomState(17).randint(0, 512, size=6)
        ref = _ref_greedy(model, prompt, 6)
        # token deliveries 1-3 stream, the 4th raises mid-generation
        with chaos.inject("decode.stream:4:RuntimeError"):
            with socketlib.create_connection(("127.0.0.1", srv.port),
                                             timeout=60) as s:
                with pytest.raises(TypedServeError) as ei:
                    decode_request(s, prompt, opts={"max_new_tokens": 6})
        assert ei.value.code == ERR_UNAVAILABLE
        assert ei.value.partial_tokens == ref[:3]
        assert ei.value.last_seq == 2
    finally:
        srv.stop()


def test_decode_request_done_frame_reordering():
    """Wire-order hardening: duplicated token frames are dropped by seq,
    out-of-order frames do not corrupt the prefix, and the done frame's
    accumulated payload is authoritative."""
    import socket as socketlib
    import threading

    from paddle_tpu.inference.serve import (decode_request, read_request,
                                            write_tensors)
    toks = [11, 22, 33, 44]
    a, b = socketlib.socketpair()

    def server():
        read_request(b)
        def frame(i):
            write_tensors(b, [np.asarray([toks[i]], np.int32)],
                          ctx={"stream": {"seq": i, "eos": False,
                                          "done": False}})
        frame(0)
        frame(1)
        frame(1)                       # failover-style duplicate
        frame(3)                       # reordered ahead of seq 2
        frame(2)
        write_tensors(b, [np.asarray(toks, np.int32)],
                      ctx={"stream": {"done": True, "n_tokens": 4}})

    t = threading.Thread(target=server, daemon=True)
    t.start()
    seen = []
    try:
        got = decode_request(a, [1, 2, 3], opts={"max_new_tokens": 4},
                             on_token=lambda tok, st: seen.append(
                                 (tok, st.get("seq"))))
    finally:
        t.join(timeout=5)
        a.close()
        b.close()
    assert got == toks                 # done payload wins regardless
    seqs = [q for _, q in seen]
    assert len(seqs) == len(set(seqs)), "duplicate seq surfaced twice"
    assert {tok for tok, _ in seen} <= set(toks)


@pytest.mark.slow
def test_decode_churn_sweep(gpt_models):
    """Long ragged-churn drill across KV-rung growth (prompt+generation
    crossing the 16-row rung): staggered submits, mixed lengths, every
    stream token-exact vs the full-forward reference."""
    model = gpt_models["tiny-scan"]
    rng = np.random.RandomState(53)
    eng = DecodeEngine(model, max_slots=3, max_new_tokens=32)
    try:
        eng.warmup()
        c0 = len(profiler.compile_events())
        jobs = []
        for i in range(8):
            plen = int(rng.randint(3, 24))
            n = int(rng.randint(4, 24))
            p = rng.randint(0, 512, size=plen)
            jobs.append((p, n, eng.submit(p, max_new_tokens=n)))
            time.sleep(0.02 * (i % 3))
        for p, n, s in jobs:
            assert s.result(timeout=300) == _ref_greedy(model, p, n)
        assert len(profiler.compile_events()) == c0
    finally:
        eng.stop()
