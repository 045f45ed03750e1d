"""Quantization (paddle_tpu.quant): fake-quant STE, QAT training,
int8 conversion, PTQ calibration. Reference: contrib/slim/quantization
(ImperativeQuantAware, fake_quantize_*_op — SURVEY refs in quant/).

Second half: serving-side PTQ — per-channel int8 decode weights
(quant/ptq.py), the int8 KV page pool (quant/kv.py), the fused dequant
Pallas kernels, and the DecodeEngine identity/tolerance contracts
behind PADDLE_TPU_DECODE_KV_DTYPE (docs/serving.md#quantized-serving)."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
from paddle_tpu import framework, profiler
from paddle_tpu.inference.decode import (DecodeEngine, SpecDecodeEngine,
                                         kv_page_bytes, load_for_decode,
                                         save_for_decode)
from paddle_tpu.memory.page_allocator import copy_page, write_pages
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_tiny
from paddle_tpu.quant import (Int8Linear, PTQ, QAT, QATLinear, SCALE_SUFFIX,
                              dequantize_kv, dequantize_params,
                              fake_quant_abs_max, is_quantized, kv_pool_sds,
                              kv_pool_zeros, quanted_layers, quantize_kv,
                              quantize_params, validate_kv_dtype)

rng = np.random.default_rng(3)


def _net():
    paddle.seed(0)
    return nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 4))


def _data(n=64):
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = rng.integers(0, 4, (n,)).astype(np.int64)
    return x, y


def test_fake_quant_roundtrip_error_bounded():
    x = paddle.to_tensor(rng.normal(size=(64,)).astype(np.float32))
    q = fake_quant_abs_max(x)
    err = np.abs(q.numpy() - x.numpy()).max()
    scale = np.abs(x.numpy()).max()
    assert err <= scale / 127.0 + 1e-7       # one int8 step
    # values land on the int8 grid
    grid = q.numpy() / (scale / 127.0)
    np.testing.assert_allclose(grid, np.round(grid), atol=1e-4)


def test_fake_quant_ste_gradient():
    x = paddle.to_tensor(rng.normal(size=(16,)).astype(np.float32),
                         stop_gradient=False)
    fake_quant_abs_max(x).sum().backward()
    # straight-through: gradient of sum is ~1 inside the clip range
    np.testing.assert_allclose(x.grad.numpy(), np.ones(16), atol=1e-6)


def test_qat_quantize_replaces_and_trains():
    net = _net()
    QAT().quantize(net)
    qls = quanted_layers(net)
    assert len(qls) == 2 and all(isinstance(l, QATLinear) for l in qls)
    x, y = _data()
    sgd = opt.SGD(learning_rate=0.1, parameters=list(net.parameters()))
    losses = []
    for _ in range(30):
        loss = F.cross_entropy(net(paddle.to_tensor(x)),
                               paddle.to_tensor(y))
        loss.backward()
        sgd.step()
        sgd.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2
    # observers moved off zero
    assert all(float(l.act_scale._data) > 0 for l in qls)


def test_qat_convert_int8_close_to_float():
    net = _net()
    x, _ = _data(32)
    ref = net(paddle.to_tensor(x)).numpy()
    QAT().quantize(net)
    net.eval()
    # freeze observers with one calibration pass in train mode
    for l in quanted_layers(net):
        l.train()
    net(paddle.to_tensor(x))
    QAT().convert(net)
    assert all(isinstance(l, Int8Linear) for l in quanted_layers(net))
    got = net(paddle.to_tensor(x)).numpy()
    # int8 simulation error stays small relative to the output range
    denom = np.abs(ref).max()
    assert np.abs(got - ref).max() / denom < 0.1
    # top-1 agreement on most samples (the metric that matters)
    agree = (got.argmax(1) == ref.argmax(1)).mean()
    assert agree >= 0.9


def test_int8_matmul_is_integer():
    lin = Int8Linear(rng.normal(size=(8, 4)).astype(np.float32), None)
    assert lin.w_q._data.dtype == jnp.int8
    x = paddle.to_tensor(rng.normal(size=(3, 8)).astype(np.float32))
    out = lin(x)
    assert out.shape == [3, 4]


def test_ptq_flow():
    net = _net()
    x, _ = _data(32)
    ref = net(paddle.to_tensor(x)).numpy()
    ptq = PTQ()
    ptq.quantize(net)
    net.eval()      # dropout/BN off; observers still run (_calibrating)
    for i in range(4):                      # calibration batches
        net(paddle.to_tensor(x[i * 8:(i + 1) * 8]))
    ptq.convert(net)
    # calibration must flow into the converted layers as STATIC scales
    assert all(l._static_act and float(l.act_scale._data) > 0
               for l in quanted_layers(net))
    got = net(paddle.to_tensor(x)).numpy()
    assert (got.argmax(1) == ref.argmax(1)).mean() >= 0.9


def test_eval_without_calibration_falls_back_to_dynamic():
    net = _net()
    x, _ = _data(16)
    ref = net(paddle.to_tensor(x)).numpy()
    QAT().quantize(net)
    net.eval()                               # observers never updated (0)
    got = net(paddle.to_tensor(x)).numpy()   # must not collapse to ~bias
    assert np.abs(got).max() > 0.1 * np.abs(ref).max()
    assert (got.argmax(1) == ref.argmax(1)).mean() >= 0.8


def test_quantize_twice_is_idempotent():
    """ADVICE r2: quantize() twice (or PTQ after QAT) must not descend
    into QATLinear and double-wrap its inner Linear."""
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    q = QAT()
    q.quantize(net)
    first = [id(m) for m in net if isinstance(m, QATLinear)]
    q.quantize(net)
    second = [id(m) for m in net if isinstance(m, QATLinear)]
    assert first == second
    for m in net:
        if isinstance(m, QATLinear):
            assert not isinstance(m.inner, QATLinear)

# ===========================================================================
# Serving PTQ: int8 decode weights, int8 KV pages, fused dequant kernels
# ===========================================================================


def test_serving_ptq_roundtrip_and_skiplist():
    """quantize_params: per-out-channel symmetric int8 for >=2-D .weight
    tensors, everything else (embeddings, biases, norms) kept fp32; the
    roundtrip error is bounded by half a quantization step per channel."""
    rng2 = np.random.default_rng(11)
    params = {
        "wte.weight": rng2.normal(size=(32, 16)).astype(np.float32),
        "wpe.weight": rng2.normal(size=(8, 16)).astype(np.float32),
        "blocks.0.attn.qkv.weight":
            rng2.normal(size=(16, 48)).astype(np.float32),
        # scan-stacked layout: leading layer axis, scale per (layer, out)
        "blocks.attn.proj.weight":
            rng2.normal(size=(2, 16, 16)).astype(np.float32),
        "blocks.0.ln1.weight": np.ones(16, np.float32),
        # scan-stacked norm gain: 2-D but per-layer 1-D — MUST stay fp32
        # (the ln path applies it raw, with no ::scale dequant)
        "blocks.ln2.weight": np.ones((2, 16), np.float32),
        "blocks.0.attn.qkv.bias": rng2.normal(size=(48,)).astype(np.float32),
    }
    q = quantize_params(params)
    assert is_quantized(q) and not is_quantized(params)
    for k in ("wte.weight", "wpe.weight", "blocks.0.ln1.weight",
              "blocks.ln2.weight", "blocks.0.attn.qkv.bias"):
        assert q[k].dtype == np.float32 and k + SCALE_SUFFIX not in q
        np.testing.assert_array_equal(q[k], params[k])
    deq = dequantize_params(q)
    for k in ("blocks.0.attn.qkv.weight", "blocks.attn.proj.weight"):
        assert q[k].dtype == np.int8
        scale = np.expand_dims(q[k + SCALE_SUFFIX], -2)
        assert scale.shape[:-2] == q[k].shape[:-2]
        err = np.abs(deq[k] - params[k])
        assert (err <= scale * 0.5 + 1e-7).all()
    with pytest.raises(ValueError):
        quantize_params(q)                     # double-quantize is loud
    assert SCALE_SUFFIX not in "".join(dequantize_params(q))


def test_kv_row_quant_roundtrip_bound():
    """quantize_kv: one fp32 scale per (row, head); |err| <= scale/2 and
    all-zero rows stay exactly zero (scale floor, no NaN/inf)."""
    rng2 = np.random.default_rng(5)
    rows = jnp.asarray(
        rng2.normal(size=(3, 4, 2, 16)).astype(np.float32) * 3.0)
    data, scale = quantize_kv(rows)
    assert data.dtype == jnp.int8 and scale.shape == (3, 4, 2)
    err = np.abs(np.asarray(dequantize_kv(data, scale)) - np.asarray(rows))
    assert (err <= np.asarray(scale)[..., None] * 0.5 + 1e-7).all()
    zd, zs = quantize_kv(jnp.zeros((2, 2, 4)))
    assert float(jnp.abs(dequantize_kv(zd, zs)).max()) == 0.0


def test_kv_dtype_validation_and_page_bytes_math():
    """The PADDLE_TPU_DECODE_KV_DTYPE surface: alias normalization, junk
    rejection, and the kv_page_bytes slot math — fp32 default unchanged,
    int8 pays 1 byte/element + one fp32 scale per (row, head) for the
    >=1.9x page-size reduction the bench scores."""
    assert validate_kv_dtype("") == "float32"
    assert validate_kv_dtype("f32") == "float32"
    assert validate_kv_dtype("int8") == "int8"
    with pytest.raises(ValueError):
        validate_kv_dtype("int4")
    cfg = gpt_tiny()
    rows = cfg.layers * 2 * 16 * cfg.heads
    assert kv_page_bytes(cfg, 16) == rows * cfg.head_dim * 4
    assert kv_page_bytes(cfg, 16, "float32") == kv_page_bytes(cfg, 16)
    i8 = kv_page_bytes(cfg, 16, "int8")
    assert i8 == rows * cfg.head_dim + rows * 4
    assert kv_page_bytes(cfg, 16) / i8 >= 1.9
    with pytest.raises(ValueError):
        kv_page_bytes(cfg, 16, "int4")


def test_int8_pool_write_and_copy_pytree():
    """The int8 pool is a (data, scale) pytree: `write_pages` lands
    quantized rows (the fused prefill quantizes them in-executable;
    test_decode_paged compares the two bit for bit) and the engine's
    COW entry point moves both leaves together, leaving untouched pages
    zero in both."""
    shape = (2, 4, 3, 2, 8)                    # L, P, pt, H, D
    kp = kv_pool_zeros(shape, "int8")
    vp = kv_pool_zeros(shape, "int8")
    # one (data, scale) pair a layer; a row is the heads side by side
    assert isinstance(kp, tuple) and len(kp) == 2
    assert kp[0][0].shape == (4, 3, 16) and kp[0][0].dtype == jnp.int8
    assert kp[0][1].shape == (4, 3, 2) and kp[0][1].dtype == jnp.float32
    rng2 = np.random.default_rng(2)
    k_rows = jnp.asarray(
        rng2.normal(size=(2, 2, 3, 2, 8)).astype(np.float32))
    v_rows = jnp.asarray(
        rng2.normal(size=(2, 2, 3, 2, 8)).astype(np.float32))
    ids = jnp.asarray([2, 1], jnp.int32)

    def pages(rows):                 # [L, W, pt, H, D] -> a pair a layer
        q, s = quantize_kv(rows)
        return tuple((q[i].reshape(2, 3, 16), s[i]) for i in range(2))

    kp = write_pages(kp, pages(k_rows), ids)
    vp = write_pages(vp, pages(v_rows), ids)
    for li in range(2):
        data, scale = kp[li]
        got = dequantize_kv(data[2].reshape(3, 2, 8), scale[2])
        err = np.abs(np.asarray(got) - np.asarray(k_rows[li, 0]))
        assert (err <= np.asarray(scale[2])[..., None] * 0.5 + 1e-7).all()
        assert int(jnp.abs(data[3].astype(jnp.int32)).sum()) == 0
    kp, vp = copy_page((kp, vp), jnp.int32(2), jnp.int32(3))
    for leaf in jax.tree.leaves((kp, vp)):
        np.testing.assert_array_equal(np.asarray(leaf[3]),
                                      np.asarray(leaf[2]))
    # the SDS mirror (AOT warmup signatures) matches shape AND dtype
    assert jax.tree.map(lambda x: (x.shape, x.dtype),
                        kv_pool_sds(shape, "int8")) \
        == jax.tree.map(lambda x: (x.shape, x.dtype), kp)
    fsds = kv_pool_sds(shape, "float32")
    assert len(fsds) == 2 and fsds[1].shape == (4, 3, 16) \
        and fsds[1].dtype == jnp.float32


def test_quant_kernels_match_reference():
    """Gate for the int8 paths: (a) paged attention over an int8 pool
    (the gathered panel dequantized in the reader) vs fp32 ground truth
    within the documented serving tolerance; (b) dequant-inside-matmul
    for int8 weights vs the product with the dequantized weight."""
    from paddle_tpu.ops.pallas.decode_attention import paged_decode_attention
    from paddle_tpu.ops.pallas.quant_matmul import int8_weight_matmul
    rng2 = np.random.RandomState(7)
    P, pt, H, D, B, W = 16, 4, 4, 16, 3, 4
    k = jnp.asarray(rng2.randn(P, pt, H, D).astype(np.float32))
    v = jnp.asarray(rng2.randn(P, pt, H, D).astype(np.float32))
    q = jnp.asarray(rng2.randn(B, H, D).astype(np.float32))
    tables = jnp.asarray(rng2.randint(0, P, size=(B, W)), jnp.int32)
    lengths = jnp.asarray([5, 16, 11], jnp.int32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    # as they lie in a layer's pool: a row is the heads side by side
    k, v, kq, vq = (x.reshape(P, pt, H * D) for x in (k, v, kq, vq))
    truth = paged_decode_attention(q, k, v, tables, lengths)
    ref = paged_decode_attention(q, (kq, ks), (vq, vs), tables, lengths)
    # int8 KV numeric tolerance (documented in docs/serving.md)
    assert float(jnp.max(jnp.abs(ref - truth))) < 0.05

    w = rng2.randn(16, 8).astype(np.float32)
    qd = quantize_params({"l.weight": w})
    wq, s = jnp.asarray(qd["l.weight"]), jnp.asarray(
        qd["l.weight" + SCALE_SUFFIX])
    for x in (jnp.asarray(rng2.randn(3, 16).astype(np.float32)),
              jnp.asarray(rng2.randn(2, 3, 16).astype(np.float32))):
        ref = int8_weight_matmul(x, wq, s)
        assert ref.shape == x.shape[:-1] + (8,)
        exact = x @ (wq.astype(jnp.float32) * s)
        assert float(jnp.max(jnp.abs(ref - exact))) < 1e-5


def _mild_gpt():
    """gpt_tiny with its transformer-block weights scaled down 10x: the
    logit gaps stay dominated by the fp32 embeddings, so int8 KV error
    sits far below every argmax margin — the deterministic rig behind
    the stream-identity claims (the bench documents the raw-logit
    tolerance; identity on arbitrary weights is not claimed)."""
    paddle.seed(21)
    model = GPT(gpt_tiny())
    params = {k: np.asarray(v) * (0.1 if k.startswith("blocks.") else 1.0)
              for k, v in framework.param_arrays(model).items()}
    return model.cfg, params


def test_int8_kv_engine_matches_fp32_under_churn():
    """PADDLE_TPU_DECODE_KV_DTYPE=int8 end to end: same streams as the
    fp32 engine through two waves of ragged admission/eviction churn,
    page-size accounting from the stats surface, and ZERO steady-state
    compiles after warmup (the pool pytree must not retrace)."""
    cfg, params = _mild_gpt()
    rng2 = np.random.default_rng(9)
    fp32 = DecodeEngine(cfg=cfg, params=params, max_slots=2,
                        max_new_tokens=16, page_tokens=4)
    int8 = DecodeEngine(cfg=cfg, params=params, kv_dtype="int8",
                        max_slots=2, max_new_tokens=16, page_tokens=4)
    try:
        assert fp32.stats()["kv_dtype"] == "float32"
        assert int8.stats()["kv_dtype"] == "int8"
        assert int8.stats()["kv_page_bytes"] == kv_page_bytes(cfg, 4, "int8")
        assert fp32.stats()["kv_page_bytes"] == kv_page_bytes(cfg, 4)
        fp32.warmup()
        int8.warmup()
        c0 = len(profiler.compile_events())
        prompts = [rng2.integers(0, cfg.vocab_size, size=int(p))
                   for p in rng2.integers(3, 10, size=5)]
        gens = [int(g) for g in rng2.integers(4, 12, size=5)]
        for _wave in range(2):                  # slots recycle across waves
            ref = [fp32.submit(p, max_new_tokens=g)
                   for p, g in zip(prompts, gens)]
            got = [int8.submit(p, max_new_tokens=g)
                   for p, g in zip(prompts, gens)]
            for r, g in zip(ref, got):
                assert g.result(timeout=180) == r.result(timeout=180)
        assert len(profiler.compile_events()) == c0, \
            "int8-KV engine compiled during a warmed-up churn run"
    finally:
        fp32.stop()
        int8.stop()


def test_int8_draft_preserves_target_stream():
    """Quantizing the DRAFT weights must never move the target stream:
    verification is sample-then-compare, so draft numerics only shift
    the acceptance rate. Spec engine with an int8 draft == plain fp32
    engine, token for token, with zero steady-state compiles."""
    paddle.seed(23)
    model = GPT(gpt_tiny())
    draft = GPT(GPTConfig(vocab_size=512, max_seq_len=128, hidden=32,
                          layers=1, heads=2, scan_layers=False))
    dq = quantize_params({k: np.asarray(v)
                          for k, v in framework.param_arrays(draft).items()})
    assert is_quantized(dq)
    plain = DecodeEngine(model, max_slots=2, max_new_tokens=12,
                         page_tokens=8)
    spec = SpecDecodeEngine(model, draft_cfg=draft.cfg, draft_params=dq,
                            speculate_k=2, max_slots=2, max_new_tokens=12,
                            page_tokens=8)
    try:
        plain.warmup()
        spec.warmup()
        c0 = len(profiler.compile_events())
        rng2 = np.random.default_rng(3)
        prompts = [rng2.integers(0, 512, size=6) for _ in range(3)]
        refs = [plain.submit(p, max_new_tokens=8) for p in prompts]
        gots = [spec.submit(p, max_new_tokens=8) for p in prompts]
        for r, g in zip(refs, gots):
            assert g.result(timeout=180) == r.result(timeout=180)
        assert len(profiler.compile_events()) == c0, \
            "int8-draft spec engine compiled after warmup"
    finally:
        plain.stop()
        spec.stop()


def test_decode_artifact_quant_roundtrip_and_backcompat(tmp_path):
    """save_for_decode(quant="int8"): int8 weights + ::scale siblings in
    the npz, `"quant": "int8"` in the manifest; the fp32 artifact stays
    byte-compatible (same three manifest fields, no scale keys); the
    quantized artifact loads into a serving engine whose greedy stream
    matches the fp32 artifact's token-for-token on the mild rig.

    Deliberately scan-stacked: every block param carries a leading [L]
    axis there, so a stacked layernorm gain is 2-D — it must NOT pick
    up a ::scale sibling (the ln path applies gains raw)."""
    paddle.seed(29)
    model = GPT(GPTConfig(vocab_size=256, max_seq_len=64, hidden=32,
                          layers=2, heads=2, scan_layers=True))
    for n, p in model.named_parameters():
        if n.startswith("blocks."):
            p._data = p._data * 0.1
    fp, qp = str(tmp_path / "fp32"), str(tmp_path / "int8")
    save_for_decode(model, fp)
    save_for_decode(model, qp, quant="int8")
    with pytest.raises(ValueError):
        save_for_decode(model, str(tmp_path / "bad"), quant="int4")
    meta = json.loads((tmp_path / "fp32.decode.json").read_text())
    assert set(meta) == {"config", "eps", "format"}
    qmeta = json.loads((tmp_path / "int8.decode.json").read_text())
    assert qmeta["quant"] == "int8"
    with np.load(fp + ".decode.npz") as z:
        orig = {k: z[k] for k in z.files}
    assert not any(k.endswith(SCALE_SUFFIX) for k in orig)
    with np.load(qp + ".decode.npz") as z:
        qparams = {k: z[k] for k in z.files}
    assert is_quantized(qparams)
    # scan-stacked norm gains/biases are 2-D yet stay fp32 scale-free
    for k in qparams:
        if ".ln" in k or k.endswith(".bias"):
            assert not k.endswith(SCALE_SUFFIX), k
            assert qparams[k].dtype != np.int8, k
    deq = dequantize_params(qparams)
    for k, w in orig.items():
        if qparams[k].dtype == np.int8:
            scale = np.expand_dims(qparams[k + SCALE_SUFFIX], -2)
            assert (np.abs(deq[k] - w) <= scale * 0.5 + 1e-7).all()
        else:
            np.testing.assert_array_equal(deq[k], w)
    ref_eng = load_for_decode(fp, max_slots=1, page_tokens=8)
    try:
        refs = [ref_eng.submit(p, max_new_tokens=4).result(timeout=180)
                for p in ([1, 2, 3], [7, 5, 9, 11, 2])]
    finally:
        ref_eng.stop()
    eng = load_for_decode(qp, max_slots=1, page_tokens=8)
    try:
        for p, ref in zip(([1, 2, 3], [7, 5, 9, 11, 2]), refs):
            out = eng.submit(p, max_new_tokens=4).result(timeout=180)
            assert out == ref, (out, ref)
    finally:
        eng.stop()
