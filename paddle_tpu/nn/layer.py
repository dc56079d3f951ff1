"""paddle_tpu.nn.layer — the Layer base class.

TPU-native rebuild of the reference's dygraph Layer
(reference: python/paddle/fluid/dygraph/layers.py Layer +
paddle/fluid/imperative/layer.h). A Layer owns Parameters and sub-Layers,
has train/eval mode, state_dict/set_state_dict, named traversal, and hooks.

TPU twist: Layers also support *functional extraction* — ``functional_call``
temporarily swaps every Parameter's payload with values from a pytree so the
same user-defined Layer runs under jit/pjit tracing (this is what
jit.to_static and the static Executor build on; the reference instead
re-declares the model as a static Program).
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict

import numpy as np
import jax

from ..tensor import Tensor, Parameter, convert_dtype, get_default_dtype
from .. import initializer as I
from ..monitor import profile as _profile

# Remat hook (memory_plan): None until the first remat feature is used
# (mirrors tensor._arena_hook's cost discipline), then consulted once
# per __call__. The thread-local suspends it inside jit.recompute's
# checkpointed body — the subtree is already under a checkpoint, and
# the suspension must also hold during the backward replay.
_remat_hook = None
_remat_tls = threading.local()


@contextlib.contextmanager
def _remat_suspended():
    prev = getattr(_remat_tls, "skip", False)
    _remat_tls.skip = True
    try:
        yield
    finally:
        _remat_tls.skip = prev


# Global structure version: bumped whenever any Layer's parameter /
# sublayer / buffer maps change. jit.to_static caches its name→holder
# state map against this (plus optimizer-slot counts), turning the
# per-call named_parameters() walk — ~17ms/call on ResNet-50 — into a
# dict reuse. Coarse by design: layer construction happens at setup
# time, so the version stops moving once the train loop starts.
_STRUCT_VERSION = 0


def _bump_struct_version():
    global _STRUCT_VERSION
    _STRUCT_VERSION += 1


def struct_version():
    return _STRUCT_VERSION


class Layer:
    """Base network building block (reference: dygraph/layers.py:Layer)."""

    def __init__(self, name_scope=None, dtype=None, remat=None):
        self._parameters = OrderedDict()
        self._sub_layers = OrderedDict()
        self._buffers = OrderedDict()
        self._dtype = convert_dtype(dtype) or get_default_dtype()
        self.training = True
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__
        # memory_plan: this layer's own remat policy ("dots"/"full"/
        # rules; "none" pins the layer out of an ambient policy).
        # Assignable after construction too — it's a plain attribute.
        self._remat = remat
        if remat is not None:
            from ..memory_plan import install_layer_hook
            install_layer_hook()

    # -- attribute plumbing -------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        if isinstance(value, Parameter) and params is not None:
            params[name] = value
            self.__dict__.pop(name, None)
            _bump_struct_version()
        elif isinstance(value, Layer) and layers is not None:
            layers[name] = value
            self.__dict__.pop(name, None)
            _bump_struct_version()
        else:
            if params is not None and name in params:
                del params[name]
                _bump_struct_version()
            if layers is not None and name in layers:
                del layers[name]
                _bump_struct_version()
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        params = self.__dict__.get("_parameters")
        if params is not None and name in params:
            return params[name]
        layers = self.__dict__.get("_sub_layers")
        if layers is not None and name in layers:
            return layers[name]
        buffers = self.__dict__.get("_buffers")
        if buffers is not None and name in buffers:
            return buffers[name]
        raise AttributeError(
            f"{type(self).__name__} has no attribute {name!r}")

    def __delattr__(self, name):
        if name in self._parameters:
            del self._parameters[name]
            _bump_struct_version()
        elif name in self._sub_layers:
            del self._sub_layers[name]
            _bump_struct_version()
        elif name in self._buffers:
            del self._buffers[name]
            _bump_struct_version()
        else:
            object.__delattr__(self, name)

    # -- parameter management ----------------------------------------------
    def create_parameter(self, shape, dtype=None, attr=None,
                         default_initializer=None, is_bias=False,
                         name=None):
        """reference: Layer.create_parameter + LayerHelper semantics."""
        from ..param_attr import ParamAttr
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = convert_dtype(dtype) or self._dtype
        attr_init = attr.initializer if isinstance(attr, ParamAttr) else None
        if not isinstance(attr_init, I.Initializer) and not isinstance(
                attr_init, (int, float)):
            attr_init = None
        init = I._resolve(
            attr_init,
            I._resolve(default_initializer,
                       I.Constant(0.0) if is_bias else I.XavierUniform()))
        data = init(shape, dtype)
        p = Parameter(data, name=(attr.name if isinstance(attr, ParamAttr)
                                  and attr.name else name))
        if isinstance(attr, ParamAttr):
            if not attr.trainable:
                p.trainable = False
                p.stop_gradient = True
            p.regularizer = attr.regularizer
            p.optimize_attr["learning_rate"] = attr.learning_rate
        return p

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        _bump_struct_version()
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        """Non-trainable state (running stats etc.)."""
        if isinstance(tensor, Tensor):
            tensor.persistable = persistable
        self._buffers[name] = tensor
        _bump_struct_version()
        return tensor

    def add_sublayer(self, name, sublayer):
        self._sub_layers[name] = sublayer
        _bump_struct_version()
        return sublayer

    # -- traversal ----------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else f"{prefix}.{name}"), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for item in layer.named_parameters(sub_prefix, True):
                    if id(item[1]) not in seen:
                        seen.add(id(item[1]))
                        yield item

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix, True)

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for layer in self._sub_layers.values():
            out.extend(layer.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(sub_prefix, include_self=True)

    def children(self):
        return list(self._sub_layers.values())

    def named_children(self):
        return list(self._sub_layers.items())

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # -- train / eval -------------------------------------------------------
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    # -- state dict ---------------------------------------------------------
    def state_dict(self, include_sublayers=True, keep_vars=True):
        """reference: Layer.state_dict — params + persistable buffers."""
        from .. import tensor as _ptensor
        if _ptensor._arena_hook is not None:
            # flat-arena training leaves param views stale between
            # steps; a state_dict read is a sync boundary
            from ..optimizer.arena import sync_all
            sync_all()
        out = OrderedDict()
        for name, p in self.named_parameters(
                include_sublayers=include_sublayers):
            out[name] = p if keep_vars else p.numpy()
        for name, b in self.named_buffers(
                include_sublayers=include_sublayers):
            if isinstance(b, Tensor) and b.persistable:
                out[name] = b if keep_vars else b.numpy()
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        """reference: Layer.set_state_dict/set_dict."""
        own = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in own:
                unexpected.append(name)
                continue
            target = own[name]
            if isinstance(value, Tensor):
                value = value.data
            target.set_value(value)
        for name in own:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype --------------------------------------------------------------
    def to(self, dtype=None):
        if dtype is not None:
            dt = convert_dtype(dtype)
            import jax.numpy as jnp
            for p in self.parameters():
                if jnp.issubdtype(p.data.dtype, jnp.floating):
                    p.data = p.data.astype(dt)
            for b in self.buffers():
                if isinstance(b, Tensor) and jnp.issubdtype(
                        b.data.dtype, jnp.floating):
                    b.data = b.data.astype(dt)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks, hook)
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks, hook)
        return handle

    # -- call ---------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        # cost discipline: each disarmed hook (the default) costs one
        # module-flag check — no scope name, no context manager
        if _remat_hook is not None and \
                not getattr(_remat_tls, "skip", False):
            out = _remat_hook(self, args, kwargs)
            if out is not NotImplemented:
                return out
        if _profile.live and _profile.armed():
            with _profile.scope(_profile.layer_scope(self)):
                return self._run_forward(args, kwargs)
        return self._run_forward(args, kwargs)

    def _run_forward(self, args, kwargs):
        from .. import tensor as _ptensor
        if _ptensor._arena_hook is not None:
            # an EAGER forward is a read boundary for flat-arena params:
            # compiled steps leave leaf views stale on purpose (the flat
            # buffer is the carried state), so settle them before eager
            # math reads the payloads. Inside a trace the views are
            # bound by jit.py and must not be touched.
            from ..optimizer.arena import flush_eager
            flush_eager(self, args, kwargs)
        for hook in self._forward_pre_hooks.values():
            res = hook(self, args)
            if res is not None:
                args = res if isinstance(res, tuple) else (res,)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_post_hooks.values():
            res = hook(self, args, out)
            if res is not None:
                out = res
        return out

    # -- grad management ----------------------------------------------------
    def clear_gradients(self):
        for p in self.parameters():
            p.clear_gradient()

    clear_grad = clear_gradients

    def __repr__(self):
        lines = [self.__class__.__name__ + "("]
        for name, layer in self._sub_layers.items():
            sub = repr(layer).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub}")
        lines.append(")")
        return "\n".join(lines) if len(lines) > 2 else (
            self.__class__.__name__ + "()")


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks, hook):
        _HookHandle._next_id[0] += 1
        self.hook_id = _HookHandle._next_id[0]
        self._hooks = hooks
        hooks[self.hook_id] = hook

    def remove(self):
        self._hooks.pop(self.hook_id, None)


# ---------------------------------------------------------------------------
# functional extraction: run a Layer with parameter payloads swapped from a
# pytree. This is the bridge from the stateful Layer world to jax's
# functional transforms (jit / grad / pjit / shard_map).

def state_pytree(layer: Layer):
    """Collect {name: jax.Array} for all params + persistable buffers."""
    tree = {}
    for name, p in layer.named_parameters():
        tree[name] = p.data
    for name, b in layer.named_buffers():
        if isinstance(b, Tensor):
            tree["buffer:" + name] = b.data
    return tree


@contextlib.contextmanager
def bind_state(layer: Layer, tree):
    """Temporarily swap layer state payloads with ``tree`` values."""
    saved = {}
    params = dict(layer.named_parameters())
    buffers = {"buffer:" + n: b for n, b in layer.named_buffers()
               if isinstance(b, Tensor)}
    holders = {**params, **buffers}
    try:
        for name, holder in holders.items():
            if name in tree:
                saved[name] = holder.data
                holder.data = tree[name]
        yield holders
    finally:
        for name, value in saved.items():
            holders[name].data = value


def functional_call(layer: Layer, tree, *args, **kwargs):
    """Run layer.forward with parameters taken from ``tree`` (pytree of
    arrays keyed like state_pytree). Returns (output, new_tree) where
    new_tree reflects buffer mutations (e.g. batch-norm running stats)."""
    with bind_state(layer, tree) as holders:
        out = layer(*args, **kwargs)
        new_tree = {name: holder.data for name, holder in holders.items()}
    return out, new_tree
