"""The plain reference against the program at gpt_tiny widths on the
CPU (on the chip the drivers make the same comparison at the published
widths, outside the window)."""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import framework
    from paddle_tpu.models import GPT, gpt_tiny

    from chipbench import weights

    cfg = gpt_tiny()
    shapes = jax.eval_shape(lambda: framework.param_arrays(GPT(cfg)))
    params = weights.make_params(shapes, 2 ** 31 + 3)
    paddle.seed(0)
    model = GPT(cfg)
    model.eval()
    return cfg, params, model


def test_reference_imports_nothing_from_the_program():
    import chipbench.reference.gpt as ref

    with open(ref.__file__) as f:
        src = f.read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_weights_are_seeded_and_made_in_the_served_type(tiny):
    import jax

    from paddle_tpu import framework
    from paddle_tpu.models import GPT

    from chipbench import weights

    cfg, params, _ = tiny
    shapes = jax.eval_shape(lambda: framework.param_arrays(GPT(cfg)))
    again = weights.make_params(shapes, 2 ** 31 + 3)
    other = weights.make_params(shapes, 2 ** 31 + 4)
    assert set(params) == set(shapes)
    for k, v in params.items():
        assert v.shape == shapes[k].shape and v.dtype == shapes[k].dtype
        assert (np.asarray(v) == np.asarray(again[k])).all()
    w = np.asarray(params["blocks.fc1.weight"])
    assert abs(w.std() - 0.02) < 0.002 and abs(w.mean()) < 0.002
    assert (np.asarray(other["blocks.fc1.weight"]) != w).any()
    assert (np.asarray(params["blocks.ln1.weight"]) == 1).all()
    assert (np.asarray(params["blocks.fc1.bias"]) == 0).all()


def test_reference_forward_agrees_with_the_programs_forward(tiny):
    import jax.numpy as jnp

    from paddle_tpu import framework

    from chipbench import weights
    from chipbench.reference import gpt as ref

    cfg, params, model = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 48))
    logits = framework.functional_call(
        model, dict(params), {}, jnp.asarray(ids, jnp.int32))
    logits = logits[0] if isinstance(logits, tuple) else logits
    got = np.asarray(getattr(logits, "_data", logits))[0]
    want = ref.forward(weights.to_reference(params),
                       jnp.asarray(ids[0], jnp.int32), cfg.heads)
    assert ref.relative_error(got, want) < 1e-4


def test_reference_loss_is_the_cross_entropy_of_its_logits(tiny):
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.reference import gpt as ref

    cfg, params, _ = tiny
    p = weights.to_reference(params)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, 32), jnp.int32)
    labels = jnp.roll(ids, -1)
    logits = np.asarray(ref.forward(p, ids, cfg.heads), np.float64)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -logp[np.arange(32), np.asarray(labels)].mean()
    assert float(ref.loss(p, ids, labels, cfg.heads)) == \
        pytest.approx(want, rel=1e-5)
    # untrained: near the uniform distribution's log(V)
    assert abs(want - np.log(cfg.vocab_size)) < 0.5


def test_a_wrong_weight_is_far_outside_the_tolerance(tiny):
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.reference import gpt as ref

    cfg, params, _ = tiny
    p = weights.to_reference(params)
    ids = jnp.asarray(np.arange(24) % cfg.vocab_size, jnp.int32)
    want = ref.forward(p, ids, cfg.heads)
    broken = dict(p, w_proj=p["w_proj"].at[1].set(0.0))
    err = ref.relative_error(ref.forward(broken, ids, cfg.heads), want)
    assert err > 2 * ref.LOGIT_TOL
