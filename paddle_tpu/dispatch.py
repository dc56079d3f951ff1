"""paddle_tpu.dispatch — the single op-dispatch point.

TPU-native rebuild of the reference's operator dispatch
(reference: paddle/fluid/imperative/tracer.cc TraceOp for dygraph;
python/paddle/fluid/framework.py append_op for static graph). Every
functional op in paddle_tpu.ops funnels through :func:`apply`:

* **dygraph** (default): run the pure-jax impl eagerly; when grad is
  required, run it under ``jax.vjp`` and record a TapeNode.
* **static graph**: append an OpNode carrying the same pure-jax impl to the
  current Program; the Executor later interprets the whole graph under one
  ``jax.jit`` (the XLA analogue of the reference's C++ executor loop).

Because impls are pure jax functions, the same code path works on eager
arrays and on tracers — ``jit.to_static`` simply traces the dygraph path.
"""
from __future__ import annotations

import time as _time

import jax

from .tensor import Tensor, as_tensor
from . import autograd
from .autograd import TapeNode
from .monitor import profile as _profile

# Static-graph hook, installed by paddle_tpu.static to avoid a circular
# import. When non-None and static mode is on, apply() records graph nodes.
_static_recorder = None
_in_static_mode = False

# Monitor hook, installed by paddle_tpu.monitor.enable(). None (the
# default) keeps the fast path at a single `is None` check — the
# disabled-mode cost contract asserted by tests/test_monitor.py.
# With time_ops, the hook's t0 stamp also feeds per-op `dispatch.<op>`
# complete events into monitor.trace (the span timeline reuses the one
# perf_counter() pair time_dispatch already pays — no extra cost here).
_monitor_hook = None
_monitor_time = False


def install_monitor_hook(fn, time_ops=False):
    """fn(name, grad, t0, static=False) or None to uninstall. With
    time_ops, apply() stamps t0 before running the impl so the hook can
    histogram host-side dispatch latency."""
    global _monitor_hook, _monitor_time
    _monitor_hook = fn
    _monitor_time = bool(time_ops) and fn is not None


def set_static_mode(flag):
    global _in_static_mode
    _in_static_mode = flag


def in_static_mode():
    return _in_static_mode


def install_static_recorder(fn):
    global _static_recorder
    _static_recorder = fn


def apply(impl, tensors, attrs=None, nondiff=False, n_out=1, name=""):
    """Dispatch one op.

    impl: pure function (*jax_arrays, **attrs) -> array | tuple of arrays
    tensors: the differentiable positional inputs (Tensor or array-likes)
    attrs: static keyword attrs baked into the op
    nondiff: output carries no gradient (argmax, comparisons, ...)
    """
    attrs = attrs or {}
    hook = _monitor_hook  # the single flag check on the disabled path
    if _in_static_mode and _static_recorder is not None:
        if hook is not None:
            hook(name, False, None, static=True)
        return _static_recorder(impl, tensors, attrs, nondiff, n_out, name)
    if hook is not None:
        t0 = _time.perf_counter() if _monitor_time else None

    ts = [as_tensor(t) for t in tensors]
    arrays = [t.data for t in ts]

    need_grad = (not nondiff and autograd.grad_enabled()
                 and any(not t.stop_gradient for t in ts))

    if need_grad:
        outs, vjp = jax.vjp(lambda *xs: impl(*xs, **attrs), *arrays)
    else:
        outs = impl(*arrays, **attrs)

    single = not isinstance(outs, (tuple, list))
    outs_seq = (outs,) if single else tuple(outs)
    out_tensors = tuple(Tensor(o, stop_gradient=not need_grad)
                        for o in outs_seq)

    if need_grad:
        node = TapeNode(ts, vjp, list(out_tensors), name=name)
        if _profile.live and _profile.armed():
            node.scope = _profile.current_path()
        for ot in out_tensors:
            ot._tape_node = node

    if hook is not None:
        hook(name, need_grad, t0)

    return out_tensors[0] if single else out_tensors
