"""Weights made on the device from ``--seed`` in one jitted call, in
the type they are served in: not initialised on the host and uploaded,
and not leaf by leaf. How each parameter is filled is its family's to
say (``families/<family>.py``: ``fill``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed_words


@functools.partial(jax.jit, static_argnums=(0,))
def _make(spec, key_data):
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    out = {}
    for i, (name, shape, dtype, how) in enumerate(spec):
        if how == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        elif how == "ones":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (how * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(dtype)
    return out


def make_params(shapes, seed, fill):
    """`shapes`: {name: ShapeDtypeStruct} (from ``jax.eval_shape`` of
    the program's own constructor). `fill(name)`: "zeros", "ones" or
    the standard deviation of a centred normal. Returns {name: device
    array}."""
    spec = tuple((k, tuple(v.shape), np.dtype(v.dtype).name, fill(k))
                 for k, v in sorted(shapes.items()))
    return _make(spec, jnp.asarray(seed_words(seed), jnp.uint32))
