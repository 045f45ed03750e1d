"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle ~2.0 (reference: /root/reference), rebuilt on
JAX/XLA/Pallas/pjit. See SURVEY.md for the blueprint.

Public API mirrors `import paddle`: tensors + ops at top level, `nn`,
`optimizer`, `amp`, `metric`, `io`, `vision`, `jit`, `static`, `distributed`,
and the high-level `Model`.
"""
from .core import dtype as _dtype_mod
from .core.dtype import (bfloat16, bool_, complex64, complex128, float16,
                         float32, float64, get_default_dtype, int8, int16,
                         int32, int64, set_default_dtype, uint8)
from .core.errors import enforce
from .core.flags import get_flags, set_flags
from .core.place import (CPUPlace, CUDAPlace, TPUPlace, TPUPinnedPlace,
                         device_count, get_device, is_compiled_with_cuda,
                         is_compiled_with_tpu, set_device)
from .core.random import get_rng_state, seed, set_rng_state
from .core.tensor import Tensor, enable_grad, no_grad, set_grad_enabled, to_tensor
from .core.autograd import grad

from .ops import *  # noqa: F401,F403  — tensor function library
from .ops import einsum  # noqa: F401

from .framework import Parameter, ParamAttr, save, load  # noqa: F401
from .hapi import Model, summary, flops  # noqa: F401

# submodules reachable as attributes (paddle.nn.Linear, paddle.amp.auto_cast
# ... — matches the reference package layout python/paddle/__init__.py)
from . import amp  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import inference  # noqa: F401
from . import text  # noqa: F401
from . import utils  # noqa: F401
from . import quant  # noqa: F401
from . import onnx  # noqa: F401
from . import dataset  # noqa: F401
from . import distribution  # noqa: F401
from . import incubate  # noqa: F401
from . import regularizer  # noqa: F401
from . import profiler  # noqa: F401
from . import observability  # noqa: F401
from .core import monitor  # noqa: F401
from . import device  # noqa: F401

# fluid-era compatibility tail. The reference exposes these through
# paddle.fluid.layers.* (its 2.0 __init__ lists most of them commented
# out); they live at the top level HERE as migration shims so fluid-era
# user code ports with one import change — a deliberate superset of the
# reference's top-level contract.
from .legacy_alias import *  # noqa: F401,F403
from .distributed.parallel import DataParallel  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .static import data  # noqa: F401

# LoD-era type aliases: a LoDTensor is a Tensor plus the host-side length
# descriptor (core/lod.py); VarBase is the eager Tensor
LoDTensor = Tensor
VarBase = Tensor
LoDTensorArray = list
from .core.place import (CUDAPinnedPlace, XPUPlace)  # noqa: F401,E402

# mode switches (reference python/paddle/__init__.py:269-271 maps them
# onto the dygraph toggles: enable_static == disable_dygraph). The
# framework is always-eager with jit/to_static as the graph path, so the
# flag is observable state for ported code, not an execution-engine swap.
from .legacy_alias import (enable_dygraph as disable_static,  # noqa: E402,F401
                           disable_dygraph as enable_static,
                           in_dygraph_mode as in_dynamic_mode)
from . import tensor  # noqa: F401,E402  (paddle.tensor submodule alias)


def batch(reader, batch_size, drop_last=False):
    """Wrap a sample reader into a batched reader (reference
    python/paddle/batch.py:1): `reader` is a zero-arg generator
    function; the result yields lists of `batch_size` samples."""
    def batch_reader():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batch_reader

__version__ = "0.3.0"
full_version = __version__
commit = "tpu-native"
