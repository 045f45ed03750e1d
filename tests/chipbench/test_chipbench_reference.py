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

    from chipbench import harness, weights

    cfg = gpt_tiny()
    shapes = jax.eval_shape(lambda: framework.param_arrays(GPT(cfg)))
    params = weights.make_params(shapes, 2 ** 31 + 3,
                                 harness.load_family("gpt").fill)
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

    from chipbench import harness, weights

    cfg, params, _ = tiny
    gpt = harness.load_family("gpt")
    shapes = jax.eval_shape(lambda: framework.param_arrays(GPT(cfg)))
    assert gpt.param_shapes(gpt.sizes({
        "n_positions": cfg.max_seq_len, "n_embd": cfg.hidden,
        "n_layer": cfg.layers, "n_head": cfg.heads,
        "layer_norm_epsilon": 1e-5,
        "assumed": {"padded_vocab_size": cfg.vocab_size}})) == shapes
    again = weights.make_params(shapes, 2 ** 31 + 3, gpt.fill)
    other = weights.make_params(shapes, 2 ** 31 + 4, gpt.fill)
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

    from chipbench import harness
    from chipbench.reference import gpt as ref

    gpt = harness.load_family("gpt")

    cfg, params, model = tiny
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 48))
    logits = framework.functional_call(
        model, dict(params), {}, jnp.asarray(ids, jnp.int32))
    logits = logits[0] if isinstance(logits, tuple) else logits
    got = np.asarray(getattr(logits, "_data", logits))[0]
    want = np.asarray(ref.forward(gpt.to_reference(params),
                                  jnp.asarray(ids[0], jnp.int32), cfg.heads))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-4


def test_reference_loss_is_the_cross_entropy_of_its_logits(tiny):
    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.reference import gpt as ref

    gpt = harness.load_family("gpt")

    cfg, params, _ = tiny
    p = gpt.to_reference(params)
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
    """The tokens a model with one projection zeroed puts first lie far
    below the reference's best by the reference's own logits; its own
    first tokens lie 0 below, and a gap is counted in standard
    deviations of the logits."""
    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.reference import gpt as ref

    gpt = harness.load_family("gpt")

    cfg, params, _ = tiny
    p = gpt.to_reference(params)
    ids = jnp.asarray(np.arange(24) % cfg.vocab_size, jnp.int32)
    want = ref.forward(p, ids, cfg.heads)
    own = ref.gaps_below_best(want, jnp.argmax(want, axis=-1))
    assert float(jnp.max(own)) == 0.0
    broken = dict(p, w_proj=p["w_proj"].at[1].set(0.0))
    chosen = jnp.argmax(ref.forward(broken, ids, cfg.heads), axis=-1)
    gaps = np.asarray(ref.gaps_below_best(want, chosen))
    assert gaps.max() > 3 * ref.GAP_TOL
    logits = np.asarray(want)
    at = logits[np.arange(24), np.asarray(chosen)]
    assert gaps == pytest.approx((logits.max(-1) - at) / logits.std(),
                                 rel=1e-4, abs=1e-6)


def test_the_family_pads_on_the_right_and_reads_the_next_token(tiny):
    """`served_gaps` of a sequence: position i is judged by token
    i + 1, and padding to the mix's longest request moves nothing."""
    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.reference import gpt as ref

    gpt = harness.load_family("gpt")
    cfg, params, _ = tiny
    s = {"heads": cfg.heads, "eps": 1e-5}
    p = gpt.to_reference(params)
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, 20)
    plain = gpt.served_gaps(p, ids, s, 20)
    padded = gpt.served_gaps(p, ids, s, 48)
    assert plain.shape == padded.shape == (19,)
    # unpadded, the spread is taken over fewer rows: the same to 10%
    assert padded == pytest.approx(plain, rel=0.1, abs=1e-6)
    logits = ref.forward(p, jnp.asarray(ids, jnp.int32), cfg.heads)
    want = ref.gaps_below_best(logits[:-1], jnp.asarray(ids[1:]))
    assert plain == pytest.approx(np.asarray(want), rel=0.1)
    greedy = list(ids[:4])              # a prompt and its greedy tokens
    for _ in range(6):
        greedy.append(int(jnp.argmax(ref.forward(
            p, jnp.asarray(greedy, jnp.int32), cfg.heads)[-1])))
    assert gpt.served_gaps(p, greedy, s, 20)[3:].max() < 1e-6
