"""paddle_tpu.tensor — the core Tensor/Parameter types.

TPU-native rebuild of the reference's Variable/LoDTensor/Parameter stack
(reference: python/paddle/fluid/framework.py Variable/Parameter;
paddle/fluid/framework/lod_tensor.h). Instead of a C++ LoDTensor with
device-specific allocations, a Tensor here wraps a `jax.Array` (device
placement and memory are owned by XLA's arena) plus the dygraph autograd
metadata (stop_gradient, accumulated grad, tape linkage).

Tensors are pytree-registered so whole models/optimizer states can flow
through `jax.jit` / `pjit` as pytrees.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .monitor import trace as _trace

# ---------------------------------------------------------------------------
# dtype utilities

_DTYPE_ALIASES = {
    "float32": jnp.float32, "float64": jnp.float64, "float16": jnp.float16,
    "bfloat16": jnp.bfloat16, "int32": jnp.int32, "int64": jnp.int64,
    "int16": jnp.int16, "int8": jnp.int8, "uint8": jnp.uint8,
    "bool": jnp.bool_, "complex64": jnp.complex64,
}

_default_dtype = jnp.float32

# optimizer.arena coherence hook: set (module-wide) while any flat param
# arena is alive; called as _arena_hook(tensor, "read"|"write") so stale
# per-leaf views materialize lazily and external writes trigger a repack.
_arena_hook = None


def set_default_dtype(dtype):
    """Set the default floating dtype used for tensor creation (cf. reference
    fluid default FP32)."""
    global _default_dtype
    _default_dtype = convert_dtype(dtype)


def get_default_dtype():
    return _default_dtype


def convert_dtype(dtype):
    if dtype is None:
        return None
    if isinstance(dtype, str):
        dtype = _DTYPE_ALIASES[dtype]
    elif not isinstance(dtype, type):
        dtype = jnp.dtype(dtype).type
    # canonicalize 64-bit requests when x64 is off (TPU default) — avoids
    # per-op truncation warnings; paddle's int64 labels become int32 lanes
    if not jax.config.jax_enable_x64:
        dtype = {jnp.int64: jnp.int32, jnp.float64: jnp.float32,
                 np.int64: jnp.int32, np.float64: jnp.float32}.get(dtype,
                                                                   dtype)
    return dtype


def is_floating(dtype):
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


# ---------------------------------------------------------------------------
# Tensor

class Tensor:
    """Eager tensor wrapping a jax.Array.

    Mirrors the dygraph VarBase of the reference (paddle/fluid/imperative/
    layer.h + python/paddle/fluid/framework.py Variable): holds data, a
    ``stop_gradient`` flag, and an accumulated ``grad``. The tape node is
    attached by the op dispatcher (see paddle_tpu/dispatch.py).
    """

    __slots__ = ("data", "stop_gradient", "_grad", "_tape_node", "name",
                 "persistable", "_graph_freed", "error_clip", "grad_clip",
                 "__weakref__")

    def __init__(self, data, stop_gradient=True, name=None, dtype=None):
        if isinstance(data, Tensor):
            data = data.data
        if not isinstance(data, (jax.Array, jax.core.Tracer)):
            arr = np.asarray(data)
            if dtype is None and arr.dtype == np.float64:
                dtype = _default_dtype
            if dtype is None and arr.dtype == np.int64 and arr.ndim == 0:
                dtype = jnp.int64
            data = jnp.asarray(arr, dtype=convert_dtype(dtype))
        elif dtype is not None:
            data = data.astype(convert_dtype(dtype))
        self.data = data
        self.stop_gradient = bool(stop_gradient)
        self._grad = None
        self._tape_node = None
        self._graph_freed = False
        self.name = name
        self.persistable = False

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self.data.shape)

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def size(self):
        return int(np.prod(self.data.shape)) if self.data.shape else 1

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        self._grad = value

    def numpy(self):
        if _arena_hook is not None:
            _arena_hook(self, "read")
        # the wait for the device and the copy to the host, as a span of
        # the program on the profiler's clock (one flag check when off)
        with _trace.span("tensor.to_host"):
            return np.asarray(jax.device_get(self.data))

    def item(self):
        return self.numpy().item()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.data.shape[0]

    def __repr__(self):
        return (f"Tensor(shape={self.shape}, dtype={self.data.dtype}, "
                f"stop_gradient={self.stop_gradient},\n{self.data})")

    def __bool__(self):
        return bool(self.data)

    def __int__(self):
        return int(self.data)

    def __float__(self):
        return float(self.data)

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from . import autograd
        autograd.backward(self, grad_tensor, retain_graph=retain_graph)

    def clear_gradient(self):
        self._grad = None

    def clear_grad(self):
        self._grad = None

    def detach(self):
        t = Tensor(self.data, stop_gradient=True, name=self.name)
        return t

    def stop_grad_(self):
        self.stop_gradient = True
        return self

    # -- in-place-ish helpers (dygraph parity) ------------------------------
    def set_value(self, value):
        """Overwrite the payload in place (reference: Variable.set_value).
        Copies device arrays so the holder never aliases a buffer that a
        donated compiled step may later invalidate."""
        if _arena_hook is not None:
            _arena_hook(self, "write")
        if isinstance(value, Tensor):
            value = value.data
        was_jax = isinstance(value, jax.Array)
        value = jnp.asarray(value, dtype=self.data.dtype)
        if tuple(value.shape) != tuple(self.data.shape):
            raise ValueError(
                f"set_value shape mismatch: {value.shape} vs {self.data.shape}")
        if was_jax and not isinstance(value, jax.core.Tracer):
            value = jnp.array(value, copy=True)
        # keep the holder's mesh placement: restoring a checkpoint into a
        # dp×tp-sharded parameter must not silently re-replicate it
        old = self.data
        if (isinstance(old, jax.Array)
                and not isinstance(old, jax.core.Tracer)
                and not isinstance(value, jax.core.Tracer)):
            try:
                if value.sharding != old.sharding:
                    value = jax.device_put(value, old.sharding)
            except (AttributeError, ValueError):
                pass
        self.data = value
        return self

    def copy_(self, other):
        return self.set_value(other)

    def astype(self, dtype):
        from . import ops
        return ops.cast(self, dtype)

    def cast(self, dtype):
        return self.astype(dtype)

    # pickling (checkpoints / save_inference_model)
    def __getstate__(self):
        return {"data": self.numpy(), "stop_gradient": self.stop_gradient,
                "name": self.name, "persistable": self.persistable}

    def __setstate__(self, state):
        self.data = jnp.asarray(state["data"])
        self.stop_gradient = state["stop_gradient"]
        self.name = state["name"]
        self.persistable = state["persistable"]
        self._grad = None
        self._tape_node = None
        self._graph_freed = False

    # numeric magic methods are attached by paddle_tpu.ops at import time to
    # avoid a circular import (ops needs Tensor for dispatch).


class Parameter(Tensor):
    """Trainable tensor (reference: framework.py Parameter). Defaults to
    requiring grad and being persistable."""

    __slots__ = ("trainable", "regularizer", "optimize_attr")

    def __init__(self, data, name=None, trainable=True, dtype=None):
        super().__init__(data, stop_gradient=not trainable, name=name,
                         dtype=dtype)
        self.trainable = trainable
        self.persistable = True
        self.regularizer = None
        self.optimize_attr = {"learning_rate": 1.0}

    def __repr__(self):
        return (f"Parameter(name={self.name}, shape={self.shape}, "
                f"dtype={self.data.dtype}, trainable={self.trainable})")

    def __getstate__(self):
        state = super().__getstate__()
        state["trainable"] = self.trainable
        return state

    def __setstate__(self, state):
        super().__setstate__(state)
        self.trainable = state.get("trainable", True)
        self.stop_gradient = not self.trainable
        self.regularizer = None
        self.optimize_attr = {"learning_rate": 1.0}


# ---------------------------------------------------------------------------
# pytree registration: Tensor flattens to its payload so models / states can
# cross jit/pjit boundaries as pytrees.

def _tensor_flatten(t):
    return (t.data,), (type(t), t.stop_gradient, t.name)


def _tensor_unflatten(aux, children):
    cls, stop_gradient, name = aux
    t = Tensor.__new__(cls)
    Tensor.__init__(t, children[0], stop_gradient=stop_gradient, name=name)
    if cls is Parameter:
        t.trainable = not stop_gradient
        t.persistable = True
        t.regularizer = None
        t.optimize_attr = {"learning_rate": 1.0}
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)
jax.tree_util.register_pytree_node(Parameter, _tensor_flatten,
                                   _tensor_unflatten)


# ---------------------------------------------------------------------------
# creation API

def to_tensor(data, dtype=None, stop_gradient=True, name=None):
    """paddle.to_tensor equivalent."""
    return Tensor(data, stop_gradient=stop_gradient, name=name, dtype=dtype)


def as_tensor(x):
    """Coerce python scalars / numpy arrays to Tensor for op dispatch."""
    if isinstance(x, Tensor):
        return x
    return Tensor(x)


def unbind(input, axis=0):
    """reference tensor/manipulation.py:unbind — module-level twin of
    Tensor.unbind (lazy import: ops depends on this module)."""
    from .ops.manip import unbind as _unbind
    return _unbind(input, axis)
