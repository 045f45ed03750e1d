"""Weights made on the device from ``--seed`` in one jitted call, in
the type they are served in: not initialised on the host and uploaded,
and not leaf by leaf."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed_words

INIT_STD = 0.02


def kind_of(name):
    """How a GPT parameter is filled, from its name: biases zero,
    layer-norm gains one, every matrix and embedding N(0, 0.02)."""
    if name.endswith(".bias"):
        return "zeros"
    if name.split(".")[-2].startswith("ln"):
        return "ones"
    return "normal"


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec, key_data):
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    out = {}
    for i, (name, shape, dtype) in enumerate(spec):
        kind = kind_of(name)
        if kind == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        elif kind == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(dtype)
    return out


def make_params(shapes, seed):
    """`shapes`: {name: ShapeDtypeStruct} (from ``jax.eval_shape`` of
    the program's own constructor). Returns {name: device array}."""
    spec = tuple((k, tuple(v.shape), np.dtype(v.dtype).name)
                 for k, v in sorted(shapes.items()))
    return _make(spec, jnp.asarray(seed_words(seed), jnp.uint32))


# program parameter name -> reference parameter name
_REF_NAMES = {
    "wte.weight": "wte", "wpe.weight": "wpe",
    "ln_f.weight": "lnf_g", "ln_f.bias": "lnf_b",
    "blocks.ln1.weight": "ln1_g", "blocks.ln1.bias": "ln1_b",
    "blocks.attn.qkv.weight": "w_qkv", "blocks.attn.qkv.bias": "b_qkv",
    "blocks.attn.proj.weight": "w_proj", "blocks.attn.proj.bias": "b_proj",
    "blocks.ln2.weight": "ln2_g", "blocks.ln2.bias": "ln2_b",
    "blocks.fc1.weight": "w_fc", "blocks.fc1.bias": "b_fc",
    "blocks.fc2.weight": "w_out", "blocks.fc2.bias": "b_out",
}


def to_reference(params):
    """The program's scan-stacked parameter dict under the reference's
    names (same arrays, float32)."""
    return {ref: jnp.asarray(params[name], jnp.float32)
            for name, ref in _REF_NAMES.items()}
