"""paddle.nn parity surface (reference: python/paddle/nn/__init__.py)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from . import utils  # noqa: F401  (weight_norm_hook import path)
from .layer.activation import *   # noqa: F401,F403
from .layer.common import *      # noqa: F401,F403
from .layer.container import *   # noqa: F401,F403
from .layer.moe import MoELayer, RoutedExperts  # noqa: F401
from .layer.conv import *        # noqa: F401,F403
from .layer.layers import Layer  # noqa: F401
from .layer.loss import *        # noqa: F401,F403
from .layer.norm import *        # noqa: F401,F403
from .layer.pooling import *     # noqa: F401,F403
from .layer.rnn import *         # noqa: F401,F403
from .layer.transformer import *  # noqa: F401,F403
from .layer.extras import *      # noqa: F401,F403
from .layer.decode import (Decoder, BeamSearchDecoder, dynamic_decode,  # noqa: F401
                           gather_tree)
from .layer.rnn_builders import DynamicRNN, StaticRNN  # noqa: F401
from .layer import weight_norm_hook  # noqa: F401
from .layer.weight_norm_hook import remove_weight_norm, weight_norm  # noqa: F401
from .functional.extension import crf_decoding  # noqa: F401
from ..static.nn import cond, while_loop  # noqa: F401

# reference nn exposes its layer/functional submodules as attributes
from .layer import (common, conv, loss, norm, rnn)  # noqa: F401
from .functional import extension, vision  # noqa: F401


def Input(shape=None, dtype="float32", name=None):
    """Static input declaration (reference paddle.nn.Input -> fluid
    data): a placeholder spec consumed by jit.save / to_static."""
    from ..static import InputSpec
    return InputSpec(shape or [None], dtype=dtype, name=name)

from ..framework import Parameter, ParamAttr  # noqa: F401


def initializer_setup():  # pragma: no cover
    pass


class ClipGradByGlobalNorm:
    """reference: python/paddle/fluid/clip.py GradientClipByGlobalNorm."""

    def __init__(self, clip_norm=1.0, group_name="default_group"):
        self.clip_norm = clip_norm

    def __call__(self, params_grads):
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        grads = [g for _, g in params_grads if g is not None]
        if not grads:
            return params_grads
        global_norm = jnp.sqrt(sum(jnp.sum(jnp.square(
            g._data.astype(jnp.float32))) for g in grads))
        scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(global_norm,
                                                              1e-12))
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
            else:
                out.append((p, Tensor((g._data.astype(jnp.float32) * scale)
                                      .astype(g._data.dtype))))
        return out


class ClipGradByNorm:
    def __init__(self, clip_norm=1.0):
        self.clip_norm = clip_norm

    def __call__(self, params_grads):
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
                continue
            norm = jnp.sqrt(jnp.sum(jnp.square(g._data.astype(jnp.float32))))
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(norm, 1e-12))
            out.append((p, Tensor((g._data * scale).astype(g._data.dtype))))
        return out


class ClipGradByValue:
    def __init__(self, max, min=None):
        self.max = max
        self.min = -max if min is None else min

    def __call__(self, params_grads):
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
                continue
            out.append((p, Tensor(jnp.clip(g._data, self.min, self.max))))
        return out
