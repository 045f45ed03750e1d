"""Family ``standin``: exists only under ``tests/``, to show that a
second family and a cut configuration run both drivers with no file
under ``chipbench/`` edited. It names no model. Its configuration file
has other keys than family ``gpt``'s and its sizes other names, so a
driver, a reader or the harness that still read a GPT key would fail
here. Underneath it builds the one decoder the program has and borrows
family ``gpt``'s reference; its counts are its own.

``serving_engine`` takes a ``fault`` (through the driver's
``engine_kw``): the engine is then built wrong on purpose, and the
comparison with the reference has to say so
(``test_chipbench_families.py``):

* ``"step"``: the step executable's logits come out rolled by one, a
  token altered where it is produced; the prefill is sound.
* ``"weights"``: one layer's output projection is zeroed in what the
  engine holds, after the benchmark made the weights.
"""
import jax.numpy as jnp

from chipbench import harness

_gpt = harness.load_family("gpt")

CUTS = {"num_hidden_layers": "depth", "vocab_size": "vocabulary"}


def sizes(raw):
    return {"vocab_size": int(raw["vocab_size"]),
            "ctx": int(raw["max_position_embeddings"]),
            "d": int(raw["hidden_size"]),
            "depth": int(raw["num_hidden_layers"]),
            "nh": int(raw["hidden_size"]) // int(raw["head_dim"]),
            "norm_eps": float(raw["norm_eps"])}


def _as_gpt(s):
    return {"vocab_size": s["vocab_size"], "max_seq_len": s["ctx"],
            "hidden": s["d"], "layers": s["depth"], "heads": s["nh"],
            "eps": s["norm_eps"]}


def param_shapes(s):
    return _gpt.param_shapes(_as_gpt(s))


fill = _gpt.fill


class _RolledLogits:
    """An executable cache whose executables return their logits
    rolled by one along the vocabulary."""

    def __init__(self, aot):
        self._aot = aot

    def __getattr__(self, name):
        return getattr(self._aot, name)

    def _wrong(self, exe):
        if exe is None:
            return None

        def call(*args):
            logits, *pools = exe(*args)
            return (jnp.roll(logits, 1, axis=-1), *pools)
        return call

    def get(self, key):
        return self._wrong(self._aot.get(key))

    def get_or_compile(self, *args, **kw):
        return self._wrong(self._aot.get_or_compile(*args, **kw))


def serving_engine(s, params, control=False, fault=None, **engine_kw):
    engine = _gpt.serving_engine(_as_gpt(s), params, control, **engine_kw)
    if fault == "step":
        engine._step_aot = _RolledLogits(engine._step_aot)
    elif fault == "weights":
        w = engine.params["blocks.attn.proj.weight"]
        engine.params = dict(engine.params, **{
            "blocks.attn.proj.weight": w.at[-1].set(0.0)})
    elif fault is not None:
        raise ValueError(f"standin: no fault {fault!r}")
    return engine


def training_net(s):
    return _gpt.training_net(_as_gpt(s))


GAP_TOL = _gpt.GAP_TOL
LOSS_TOL = _gpt.LOSS_TOL
to_reference = _gpt.to_reference


def served_gaps(ref_params, tokens, s, pad_to, control=False):
    return _gpt.served_gaps(ref_params, tokens, _as_gpt(s), pad_to, control)


def reference_loss(ref_params, ids, labels, s):
    return _gpt.reference_loss(ref_params, ids, labels, _as_gpt(s))


def train_flops_per_token(s, seq_len):
    return 6 * s["depth"] * 12 * s["d"] ** 2


def flash_attention_costs(s, batch, seq_len):
    return []


def decode_step_bytes(s, live_tokens, rows=None):
    """Counts what its rows hit, as a family with experts would: here
    simply 1,000 bytes a row, which no dense count could give."""
    return 1000.0 * rows + live_tokens


PROGRAMS = _gpt.PROGRAMS
STEP_PROGRAM = _gpt.STEP_PROGRAM
