"""Pallas kernel semantics vs XLA reference (interpret mode on CPU; the
same code paths compile on TPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention


def _ref_attention(q, k, v, causal, scale):
    B, T, H, D = q.shape
    qt = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)
    kt = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32)
    vt = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.transpose(o, (0, 2, 1, 3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [128, 256])
def test_flash_forward_matches_reference(causal, T):
    rng = np.random.default_rng(0)
    B, H, D = 2, 2, 32
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    out = flash_attention(q, k, v, causal=causal)
    ref = _ref_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    rng = np.random.default_rng(1)
    B, T, H, D = 1, 128, 2, 32
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_attention(q, k, v, causal, scale) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_under_jit_and_seqlen_guard():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 128, 1, 32)), jnp.float32)
    f = jax.jit(lambda a: flash_attention(a, a, a, causal=True))
    out = f(q)
    assert out.shape == (1, 128, 1, 32)
    with pytest.raises(ValueError):
        bad = jnp.zeros((1, 200, 1, 32), jnp.float32)
        flash_attention(bad, bad, bad)


def test_sdpa_routes_to_flash():
    """F.scaled_dot_product_attention uses the pallas kernel when the flag
    is on, the call qualifies (no mask, no dropout), and the sequence is
    long enough (below the threshold XLA's composition is faster)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    old = paddle.get_flags("pallas_attention_min_seq")
    paddle.set_flags({"pallas_attention_min_seq": 128})
    try:
        rng = np.random.default_rng(3)
        x = paddle.to_tensor(
            rng.standard_normal((1, 128, 2, 32)).astype(np.float32))
        out = F.scaled_dot_product_attention(x, x, x, is_causal=True)
        ref = _ref_attention(x._data, x._data, x._data, True, 1 / np.sqrt(32))
        np.testing.assert_allclose(np.asarray(out._data), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
    finally:
        paddle.set_flags({"pallas_attention_min_seq": old})


# ---------------------------------------------------------------------------
# fused linear + cross-entropy (ops/pallas/fused_ce.py)
# ---------------------------------------------------------------------------

from paddle_tpu.ops.pallas.fused_ce import linear_cross_entropy


def _ref_lce(x, w, labels):
    lg = (x.astype(jnp.float32) @ w.astype(jnp.float32).T)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


@pytest.mark.parametrize("N,H,V", [(128, 128, 384), (256, 256, 1000)])
def test_linear_cross_entropy_forward(N, H, V):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, H)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    out = linear_cross_entropy(x, w, labels)
    ref = _ref_lce(x, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_linear_cross_entropy_grads():
    rng = np.random.default_rng(1)
    N, H, V = 128, 128, 500
    x = jnp.asarray(rng.normal(size=(N, H)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)

    gx, gw = jax.grad(lambda x, w: linear_cross_entropy(x, w, labels).mean(),
                      argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: _ref_lce(x, w, labels).mean(),
                      argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=2e-3, atol=1e-5)


def test_linear_cross_entropy_under_jit():
    rng = np.random.default_rng(2)
    N, H, V = 128, 128, 384
    x = jnp.asarray(rng.normal(size=(N, H)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    f = jax.jit(lambda x, w: linear_cross_entropy(x, w, labels).mean())
    np.testing.assert_allclose(float(f(x, w)),
                               float(_ref_lce(x, w, labels).mean()),
                               rtol=1e-4)


def test_flash_multiblock_carry():
    """Pin small blocks so T=256 exercises the cross-block online-softmax
    carry (m/l/acc scratch across the inner grid dim) in fwd and bwd."""
    import os
    os.environ["PT_FLASH_FWD_BLOCKS"] = "128,128"
    os.environ["PT_FLASH_BWD_BLOCKS"] = "128,128"
    try:
        rng = np.random.default_rng(7)
        B, T, H, D = 1, 256, 2, 32
        q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                               jnp.float32) * 0.3 for _ in range(3))
        out = flash_attention(q, k, v, causal=True)
        ref = _ref_attention(q, k, v, True, 1 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        gf = jax.grad(lambda q, k, v: (
            flash_attention(q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: (
            _ref_attention(q, k, v, True, 1 / np.sqrt(D)) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{n} mismatch")
    finally:
        del os.environ["PT_FLASH_FWD_BLOCKS"]
        del os.environ["PT_FLASH_BWD_BLOCKS"]


def test_flash_env_blocks_must_divide():
    import os
    os.environ["PT_FLASH_FWD_BLOCKS"] = "96,96"
    try:
        q = jnp.zeros((1, 256, 1, 32), jnp.float32)
        with pytest.raises(ValueError):
            flash_attention(q, q, q)
    finally:
        del os.environ["PT_FLASH_FWD_BLOCKS"]


def test_linear_cross_entropy_pallas_kernels_interpret():
    """fused=True runs the Pallas path (interpret mode on CPU): covers the
    actual kernels incl. vocab padding, not just the XLA composition —
    and shapes the kernel cannot tile are an error, not a quiet XLA run."""
    from paddle_tpu.ops.pallas import fused_ce
    with pytest.raises(ValueError, match="multiples of 128"):
        fused_ce.linear_cross_entropy(jnp.zeros((100, 128)),
                                      jnp.zeros((384, 128)),
                                      jnp.zeros((100,), jnp.int32),
                                      fused=True)
    rng = np.random.default_rng(3)
    N, H, V = 128, 128, 700    # pads to 1024 internally
    x = jnp.asarray(rng.normal(size=(N, H)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, H)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (N,)), jnp.int32)
    out = fused_ce.linear_cross_entropy(x, w, labels, fused=True)
    ref = _ref_lce(x, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    gx, gw = jax.grad(
        lambda x, w: fused_ce.linear_cross_entropy(
            x, w, labels, fused=True).mean(), argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda x, w: _ref_lce(x, w, labels).mean(),
                      argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=2e-3, atol=1e-5)


def test_functional_linear_cross_entropy_tensor_api():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    rng = np.random.default_rng(4)
    N, H, V = 64, 32, 100
    x = paddle.to_tensor(rng.normal(size=(N, H)).astype(np.float32) * 0.1,
                         stop_gradient=False)
    w = paddle.to_tensor(rng.normal(size=(V, H)).astype(np.float32) * 0.1,
                         stop_gradient=False)
    lbl = paddle.to_tensor(rng.integers(0, V, (N,)).astype(np.int64))
    loss = F.linear_cross_entropy(x, w, lbl)
    loss.backward()
    ref = _ref_lce(x._data, w._data, lbl._data.astype(jnp.int32)).mean()
    np.testing.assert_allclose(float(loss.numpy()), float(ref), rtol=1e-4)
    assert x.grad is not None and w.grad is not None


def test_flash_fused_bwd_single_sweep_matches():
    """The fused single-pass backward (nk==1 route) matches the two-pass
    scheme exactly."""
    import os
    import math
    import paddle_tpu.ops.pallas.flash_attention as fa
    rng = np.random.default_rng(11)
    B, T, H, D = 1, 256, 2, 32
    scale = 1.0 / math.sqrt(D)

    def to3(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)

    q, k, v, ct = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                               jnp.float32) * 0.3 for _ in range(4))
    for causal in (False, True):
        o, lse = fa._fwd(to3(q), to3(k), to3(v), scale, causal)
        res = (to3(q), to3(k), to3(v), o, lse)
        d_two = fa._bwd(scale, causal, res, to3(ct))
        d_fused = fa._bwd_fused(scale, causal, res, to3(ct))
        for a, b, n in zip(d_fused, d_two, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"{n} causal={causal}")


def test_flash_bwd_dispatch_routes_by_k_sweeps():
    import paddle_tpu.ops.pallas.flash_attention as fa
    # T=256 default bk=256 -> nk=1 -> fused; forced bk=128 -> two-pass
    assert fa._bwd_block_sizes(256, 32)[1] == 256
    import os
    os.environ["PT_FLASH_BWD_BLOCKS"] = "128,128"
    try:
        assert fa._bwd_block_sizes(256, 32)[1] == 128
    finally:
        del os.environ["PT_FLASH_BWD_BLOCKS"]
