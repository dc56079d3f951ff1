"""paddle_tpu.autograd — tape-based reverse-mode autograd for dygraph.

TPU-native rebuild of the reference's imperative autograd engine
(reference: paddle/fluid/imperative/tracer.cc + engine.cc, and
python/paddle/fluid/dygraph/base.py for no_grad/guard semantics).

Design: instead of recording grad *ops* into a graph and replaying them on a
C++ engine, each forward op records a `jax.vjp` closure (a TapeNode). At
``loss.backward()`` we walk the recorded nodes in reverse creation order and
accumulate cotangents into every reachable Tensor with
``stop_gradient=False``. All of this is jit-traceable: under
``jit.to_static`` the same tape runs on tracers and the whole
forward+backward collapses into one XLA computation.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np
import jax
import jax.numpy as jnp

from .tensor import Tensor
from .monitor import profile as _profile

float0 = jax.dtypes.float0


class TapeNode:
    """One recorded op: inputs, a vjp closure, and weak links to outputs."""
    __slots__ = ("inputs", "vjp", "outputs", "seq", "name", "scope")

    _counter = [0]

    def __init__(self, inputs, vjp, outputs, name=""):
        self.inputs = inputs          # list[Tensor]
        self.vjp = vjp                # cotangents(tuple) -> tuple of in-grads
        self.outputs = outputs        # list[Tensor] (strong refs are fine:
                                      # the graph dies with the step)
        TapeNode._counter[0] += 1
        self.seq = TapeNode._counter[0]
        self.name = name
        # monitor.profile scope path of the forward op (() when
        # profiling is off): backward() re-enters it around the vjp
        self.scope = ()


class _State(threading.local):
    def __init__(self):
        self.grad_enabled = True


_state = _State()


def grad_enabled():
    return _state.grad_enabled


@contextlib.contextmanager
def no_grad():
    """Disable tape recording (reference: fluid.dygraph.no_grad)."""
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


def no_grad_(fn):
    """Decorator form of no_grad."""
    def wrapper(*args, **kwargs):
        with no_grad():
            return fn(*args, **kwargs)
    return wrapper


@contextlib.contextmanager
def enable_grad():
    prev = _state.grad_enabled
    _state.grad_enabled = True
    try:
        yield
    finally:
        _state.grad_enabled = prev


def _zero_cotangent(arr):
    dt = jnp.result_type(arr)
    if jnp.issubdtype(dt, jnp.floating) or jnp.issubdtype(dt, jnp.complexfloating):
        return jnp.zeros(arr.shape, dt)
    return np.zeros(arr.shape, float0)


def backward(root: Tensor, grad_tensor=None, retain_graph=False, _only=None):
    """Reverse sweep from ``root``; accumulates into ``t._grad`` for every
    reachable tensor with stop_gradient=False (reference semantics of
    VarBase.backward + gradient accumulation until clear_gradients)."""
    if root._tape_node is None:
        if root._graph_freed:
            raise RuntimeError(
                "Trying to backward through a graph that has already been "
                "freed. Pass retain_graph=True to the first backward() if "
                "you need to backward twice.")
        return
    if grad_tensor is None:
        seed = jnp.ones(root.data.shape, jnp.result_type(root.data))
    else:
        seed = grad_tensor.data if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)

    # Collect reachable nodes.
    nodes = []
    seen = set()
    stack = [root._tape_node]
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        nodes.append(node)
        for t in node.inputs:
            if t._tape_node is not None and id(t._tape_node) not in seen:
                stack.append(t._tape_node)
    nodes.sort(key=lambda n: n.seq, reverse=True)

    # Cotangent accumulator keyed by tensor identity. Reverse-topological
    # order guarantees a tensor's cotangent is complete when its producing
    # node is processed (all consumers ran first).
    cotangents = {id(root): seed}
    holders = {id(root): root}

    def _clip_err(t, ct):
        # reference ErrorClipByValue (fluid/clip.py): a per-var clip on
        # the INCOMING error signal — affects both the stored .grad and
        # everything propagated further upstream
        eclip = getattr(t, "error_clip", None)
        return ct if eclip is None else eclip(ct)

    def _accumulate_grad(t, ct):
        if t.stop_gradient or (_only is not None and id(t) not in _only):
            return
        t._grad = ct if t._grad is None else t._grad + ct

    # the sweep is the step's backward phase: under tracing (or an armed
    # profile) its ops carry the scope "bwd" in front of their layer's
    with (_profile.backward_scope() if _profile.live and _profile.armed()
          else contextlib.nullcontext()):
        for node in nodes:
            outs_ct = []
            any_ct = False
            for o in node.outputs:
                ct = cotangents.pop(id(o), None)
                holders.pop(id(o), None)
                if ct is None:
                    ct = _zero_cotangent(o.data)
                else:
                    ct = _clip_err(o, ct)
                    any_ct = True
                    _accumulate_grad(o, ct)
                outs_ct.append(ct)
            if not any_ct:
                continue
            if node.vjp is None:
                raise RuntimeError(
                    "Trying to backward through a graph that has been freed "
                    f"(op '{node.name}'). Call backward(retain_graph=True) on "
                    "the first backward if you need to backward twice.")
            cts = tuple(outs_ct) if len(outs_ct) > 1 else outs_ct[0]
            if node.scope:
                with _profile.reenter(node.scope):
                    in_grads = node.vjp(cts)
            else:
                in_grads = node.vjp(cts)
            for t, g in zip(node.inputs, in_grads):
                if g is None or (hasattr(g, "dtype") and g.dtype == float0):
                    continue
                if t.stop_gradient and t._tape_node is None:
                    continue  # dead end: nothing downstream wants this grad
                if t._tape_node is None and t._graph_freed:
                    raise RuntimeError(
                        "Trying to backward through a sub-graph that has "
                        "already been freed (shared intermediate "
                        f"feeding op '{node.name}'). Use retain_graph=True.")
                prev = cotangents.get(id(t))
                cotangents[id(t)] = g if prev is None else prev + g
                holders[id(t)] = t

        # Whatever is left in the accumulator belongs to leaf tensors.
        for key, ct in cotangents.items():
            _accumulate_grad(holders[key], _clip_err(holders[key], ct))

    if not retain_graph:
        for node in nodes:
            node.vjp = None
        for node in nodes:
            for o in node.outputs:
                o._tape_node = None
                o._graph_freed = True


def grad(outputs, inputs, grad_outputs=None, retain_graph=False):
    """Functional gradient a la paddle.grad: returns grads of outputs wrt
    inputs without touching .grad accumulators."""
    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    saved = [(t, t._grad) for t in inputs]
    for t in inputs:
        t._grad = None
    saved_flags = [t.stop_gradient for t in inputs]
    for t in inputs:
        t.stop_gradient = False
    try:
        only = {id(t) for t in inputs}
        for i, out in enumerate(outputs):
            g = None if grad_outputs is None else grad_outputs[i]
            backward(out, g, retain_graph=True, _only=only)
        results = [t._grad if t._grad is not None else
                   jnp.zeros(t.data.shape, t.data.dtype) for t in inputs]
        results = [Tensor(r, stop_gradient=True) for r in results]
    finally:
        for (t, g), flag in zip(saved, saved_flags):
            t._grad = g
            t.stop_gradient = flag
        if not retain_graph:
            for out in outputs:
                clear_graph(out)
    return results if len(results) > 1 else results[0]


def clear_graph(root):
    if root._tape_node is None:
        return
    stack = [root._tape_node]
    seen = set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        for t in node.inputs:
            if t._tape_node is not None:
                stack.append(t._tape_node)
        node.vjp = None
        for o in node.outputs:
            o._tape_node = None
            o._graph_freed = True
