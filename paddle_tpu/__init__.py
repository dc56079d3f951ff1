"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up rebuild of the capabilities of the reference PaddlePaddle
(v1.7 Fluid era, /root/reference) designed for TPU hardware: jax/XLA for
the compute path (MXU-friendly ops, one compiled computation per train
step), `jax.sharding.Mesh` + shard_map for distribution (ICI collectives
instead of NCCL), Pallas for fused kernels, and a C++ host runtime for the
input pipeline.

Top-level API mirrors the reference's `paddle` / `paddle.fluid` surface:
Tensor, nn.Layer, optimizers, static Program/Executor, fleet, io.
"""
from time import perf_counter as _perf_counter
_t_import = _perf_counter()

__version__ = "0.1.0"

from .tensor import (Tensor, Parameter, to_tensor, set_default_dtype,
                     get_default_dtype)
from .random import seed, get_seed
from . import autograd
from .autograd import no_grad, enable_grad, grad
from . import ops
from .ops import *  # noqa: F401,F403  (functional surface: paddle.add etc.)
from . import nn
from . import optimizer
from .optimizer import lr  # noqa: F401
from . import initializer
from . import regularizer
from . import clip
from .clip import ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm
# the clip *module* import above shadowed the clip op — rebind the function
# (the module stays importable as `paddle_tpu.clip` via sys.modules)
from .ops.math import clip  # noqa: F811
from .param_attr import ParamAttr, WeightNormParamAttr
from . import device
from .device import (CPUPlace, TPUPlace, CUDAPlace, set_device, get_device,
                     is_compiled_with_cuda, device_count,
                     enable_compilation_cache)

# framework-level namespaces filled in by submodules as they land
from . import jit
from . import static
from . import io
from . import metric
from . import amp
from . import parallel
from . import distributed
from . import models
from . import utils
from . import inference
from . import fluid
from . import reader
from .reader import batch
from . import compat
from . import sysconfig
from . import distribution
from . import quantization
from . import slim
from . import fleet
from . import dataset
from . import monitor
from . import resilience
from . import serving

# PADDLE_TPU_MONITOR=1 turns the metrics runtime on for the whole
# process (sink location via PADDLE_TPU_MONITOR_DIR); default stays
# off — a single flag check on the dispatch fast path.
import os as _os
if _os.environ.get("PADDLE_TPU_MONITOR", "") not in ("", "0", "false",
                                                     "False"):
    monitor.enable()

# dygraph/static mode management (reference: fluid.enable_dygraph /
# paddle.enable_static). Dygraph is the default here (modern surface).
from .dispatch import in_static_mode as in_static_mode  # noqa


def enable_static():
    from . import static as _static
    _static.enable_static()


def disable_static():
    from . import static as _static
    _static.disable_static()


def in_dynamic_mode():
    return not in_static_mode()


# reference python/paddle/__init__.py top-level name parity tail
def _reduce_alias(fn):
    # reference reduce_* signature uses dim/keep_dim keywords
    def f(input, dim=None, keep_dim=False, name=None):
        return fn(input, axis=dim, keepdim=keep_dim)
    f.__name__ = "reduce_" + fn.__name__
    return f


reduce_sum = _reduce_alias(ops.sum)
reduce_mean = _reduce_alias(ops.mean)
reduce_max = _reduce_alias(ops.max)
reduce_min = _reduce_alias(ops.min)
reduce_prod = _reduce_alias(ops.prod)
reduce_all = _reduce_alias(ops.all)
reduce_any = _reduce_alias(ops.any)
manual_seed = seed
shuffle = reader.shuffle


def in_dygraph_mode():
    """reference fluid framework.py:in_dygraph_mode."""
    return not in_static_mode()


def enable_dygraph(place=None):
    if in_static_mode():
        disable_static()


def disable_dygraph():
    if not in_static_mode():
        enable_static()


def save(obj, path, protocol=4):
    """paddle.save → io.save."""
    from . import io as _io
    return _io.save(obj, path, protocol=protocol)


def load(path, **kw):
    """paddle.load → io.load. Unsupported options raise rather than
    silently changing semantics."""
    if kw:
        raise ValueError(f"paddle_tpu.load: unsupported options {set(kw)}")
    from . import io as _io
    return _io.load(path)


from . import hapi  # noqa: E402  (high-level Model API)
from . import incubate  # noqa: E402


from . import framework  # noqa: E402
from . import imperative  # noqa: E402

# what `import paddle_tpu` cost this process (the package's own imports
# and jax's, where this import was the first to ask for it)
monitor.gauge("runtime.import_s").set(_perf_counter() - _t_import)
