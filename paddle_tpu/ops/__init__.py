"""paddle_tpu.ops — the functional op library (≈250 ops).

TPU-native rebuild of the reference's operator zoo
(reference: paddle/fluid/operators/* with python surface in
python/paddle/fluid/layers/). Every op is one pure-jax impl dispatched
through paddle_tpu.dispatch.apply, so a single definition serves dygraph
(tape autograd), jit-traced to_static, and static Program recording.

This module also attaches the numeric magic methods to Tensor (done here
rather than in tensor.py to break the import cycle — same role as the
reference's monkey-patching in python/paddle/fluid/dygraph/math_op_patch.py).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..tensor import Tensor
from .math import *  # noqa: F401,F403
from .manip import *  # noqa: F401,F403
from .creation import *  # noqa: F401,F403
from .nn_ops import *  # noqa: F401,F403
from .ssm import *  # noqa: F401,F403
from .moe import *  # noqa: F401,F403
from .control_flow import cond, while_loop, case, switch_case  # noqa: F401
from .imperative_flow import (IfElse, Switch, DynamicRNN,  # noqa: F401
                              TensorArray, create_array, array_write,
                              array_read, array_length)
from . import loss  # noqa: F401
from . import detection  # noqa: F401
from . import math as math_ops
from . import manip as manip_ops
from . import nn_ops
from . import creation as creation_ops
from ..dispatch import apply


# ---------------------------------------------------------------------------
# Tensor magic-method patching (reference: math_op_patch.py monkeypatch_math)

def _getitem(self, idx):
    def _fix(i):
        if isinstance(i, Tensor):
            return i.data
        return i
    if isinstance(idx, tuple):
        jidx = tuple(_fix(i) for i in idx)
    else:
        jidx = _fix(idx)
    return apply(lambda x, jidx: x[jidx], (self,), dict(jidx=jidx),
                 name="getitem")


def _setitem(self, idx, value):
    if isinstance(value, Tensor):
        value = value.data
    if isinstance(idx, Tensor):
        idx = idx.data
    self.data = self.data.at[idx].set(value)
    return self


def _patch():
    T = Tensor
    T.__add__ = lambda s, o: math_ops.add(s, o)
    T.__radd__ = lambda s, o: math_ops.add(o, s)
    T.__sub__ = lambda s, o: math_ops.subtract(s, o)
    T.__rsub__ = lambda s, o: math_ops.subtract(o, s)
    T.__mul__ = lambda s, o: math_ops.multiply(s, o)
    T.__rmul__ = lambda s, o: math_ops.multiply(o, s)
    T.__truediv__ = lambda s, o: math_ops.divide(s, o)
    T.__rtruediv__ = lambda s, o: math_ops.divide(o, s)
    T.__floordiv__ = lambda s, o: math_ops.floor_divide(s, o)
    T.__mod__ = lambda s, o: math_ops.mod(s, o)
    T.__pow__ = lambda s, o: math_ops.pow(s, o)
    T.__rpow__ = lambda s, o: math_ops.pow(o, s)
    T.__neg__ = lambda s: math_ops.negative(s)
    T.__abs__ = lambda s: math_ops.abs(s)
    T.__matmul__ = lambda s, o: math_ops.matmul(s, o)
    T.__rmatmul__ = lambda s, o: math_ops.matmul(o, s)
    T.__eq__ = lambda s, o: math_ops.equal(s, o)
    T.__ne__ = lambda s, o: math_ops.not_equal(s, o)
    T.__lt__ = lambda s, o: math_ops.less_than(s, o)
    T.__le__ = lambda s, o: math_ops.less_equal(s, o)
    T.__gt__ = lambda s, o: math_ops.greater_than(s, o)
    T.__ge__ = lambda s, o: math_ops.greater_equal(s, o)
    T.__getitem__ = _getitem
    T.__setitem__ = _setitem
    # tensor methods (paddle Tensor method surface)
    T.matmul = lambda s, o, transpose_x=False, transpose_y=False: \
        math_ops.matmul(s, o, transpose_x, transpose_y)
    T.mm = T.matmul
    T.reshape = lambda s, shape: manip_ops.reshape(s, shape)
    T.transpose = lambda s, perm: manip_ops.transpose(s, perm)
    T.flatten = lambda s, start_axis=0, stop_axis=-1: manip_ops.flatten(
        s, start_axis, stop_axis)
    T.squeeze = lambda s, axis=None: manip_ops.squeeze(s, axis)
    T.unsqueeze = lambda s, axis: manip_ops.unsqueeze(s, axis)
    T.sum = lambda s, axis=None, keepdim=False: math_ops.sum(s, axis,
                                                             keepdim)
    T.mean = lambda s, axis=None, keepdim=False: math_ops.mean(s, axis,
                                                               keepdim)
    T.max = lambda s, axis=None, keepdim=False: math_ops.max(s, axis,
                                                             keepdim)
    T.min = lambda s, axis=None, keepdim=False: math_ops.min(s, axis,
                                                             keepdim)
    T.prod = lambda s, axis=None, keepdim=False: math_ops.prod(s, axis,
                                                               keepdim)
    T.argmax = lambda s, axis=None, keepdim=False: math_ops.argmax(
        s, axis, keepdim)
    T.argmin = lambda s, axis=None, keepdim=False: math_ops.argmin(
        s, axis, keepdim)
    T.exp = lambda s: math_ops.exp(s)
    T.log = lambda s: math_ops.log(s)
    T.sqrt = lambda s: math_ops.sqrt(s)
    T.square = lambda s: math_ops.square(s)
    T.abs = lambda s: math_ops.abs(s)
    T.tanh = lambda s: math_ops.tanh(s)
    T.sigmoid = lambda s: nn_ops.sigmoid(s)
    T.clip = lambda s, min=None, max=None: math_ops.clip(s, min, max)
    T.pow = lambda s, o: math_ops.pow(s, o)
    T.norm = lambda s, p=2, axis=None, keepdim=False: math_ops.norm(
        s, p, axis, keepdim)
    T.gather = lambda s, index, axis=0: manip_ops.gather(s, index, axis)
    T.concat = staticmethod(manip_ops.concat)
    T.split = lambda s, n, axis=0: manip_ops.split(s, n, axis)
    T.tile = lambda s, reps: manip_ops.tile(s, reps)
    T.expand = lambda s, shape: manip_ops.expand(s, shape)
    T.flip = lambda s, axis: manip_ops.flip(s, axis)
    T.cumsum = lambda s, axis=None: math_ops.cumsum(s, axis)
    T.topk = lambda s, k, axis=-1: math_ops.topk(s, k, axis)
    T.sort = lambda s, axis=-1, descending=False: math_ops.sort(
        s, axis, descending)
    T.argsort = lambda s, axis=-1, descending=False: math_ops.argsort(
        s, axis, descending)
    T.add = lambda s, o: math_ops.add(s, o)
    T.subtract = lambda s, o: math_ops.subtract(s, o)
    T.multiply = lambda s, o: math_ops.multiply(s, o)
    T.divide = lambda s, o: math_ops.divide(s, o)
    T.scale = lambda s, scale=1.0, bias=0.0: math_ops.scale(s, scale, bias)
    T.unbind = lambda s, axis=0: manip_ops.unstack(s, axis)


_patch()
del _patch
from . import sequence  # noqa: F401
from .sequence import (sequence_pool, sequence_softmax,  # noqa: F401
                       sequence_reverse, sequence_expand, sequence_pad,
                       sequence_unpad, sequence_concat, sequence_conv,
                       sequence_slice, sequence_expand_as,
                       sequence_reshape, sequence_scatter,
                       sequence_enumerate, sequence_first_step,
                       sequence_last_step)
from . import crf  # noqa: F401
from .crf import linear_chain_crf, crf_decoding  # noqa: F401
from . import ctc  # noqa: F401
from .ctc import ctc_loss, warpctc, ctc_greedy_decoder  # noqa: F401
