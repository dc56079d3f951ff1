"""paddle_tpu.ops.nn_ops — neural-net functional ops.

TPU-native rebuild of the reference's NN operators
(reference: paddle/fluid/operators/{conv_op, pool_op, batch_norm_op,
layer_norm_op, group_norm_op, instance_norm_op, softmax_op, dropout_op,
lookup_table_op, interpolate_op, prelu_op}.cc/.cu; python surface in
python/paddle/fluid/layers/nn.py).

TPU-first choices:
* convs lower to one `lax.conv_general_dilated` (MXU); NCHW accepted for
  API parity but internally dims are passed via dimension_numbers so XLA
  picks the TPU-friendly layout — no manual im2col as in the CUDA kernels.
* normalizations are fused arithmetic XLA folds into neighbouring matmuls;
  a Pallas fused layer_norm lives in paddle_tpu/ops/pallas for the hot path.
* dropout threads the global PRNG key (see paddle_tpu.random) — no curand.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from contextlib import nullcontext

from ..tensor import Tensor, as_tensor, convert_dtype
from ..dispatch import apply
from ..monitor import profile as _profile
from .. import random as prandom


def _pscope(name):
    """named_scope(F.<name>) when profiling is armed, else a no-op —
    one flag check, so the disabled path stays free."""
    if _profile.live and _profile.armed():
        return _profile.scope(_profile.fscope(name))
    return nullcontext()


# ---------------------------------------------------------------------------
# activations (reference: activation_op.cc, gelu_op, prelu_op)

def relu(x, name=None):
    return apply(lambda x: jnp.maximum(x, 0), (x,), name="relu")


def relu6(x, name=None):
    return apply(lambda x: jnp.clip(x, 0, 6), (x,), name="relu6")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(lambda x, a: jnp.where(x >= 0, x, a * x), (x,),
                 dict(a=negative_slope), name="leaky_relu")


def prelu(x, weight, data_format="NCHW", name=None):
    def impl(x, w):
        if w.size == 1:
            wb = w.reshape(())
        elif data_format == "NCHW" and x.ndim > 2:
            wb = w.reshape((1, -1) + (1,) * (x.ndim - 2))
        else:
            wb = w
        return jnp.where(x >= 0, x, wb * x)
    return apply(impl, (x, weight), name="prelu")


def elu(x, alpha=1.0, name=None):
    return apply(lambda x, a: jnp.where(x > 0, x, a * jnp.expm1(x)), (x,),
                 dict(a=alpha), name="elu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply(lambda x, s, a: s * jnp.where(x > 0, x, a * jnp.expm1(x)),
                 (x,), dict(s=scale, a=alpha), name="selu")


def gelu(x, approximate=False, name=None):
    return apply(lambda x, approximate: jax.nn.gelu(x, approximate=approximate),
                 (x,), dict(approximate=approximate), name="gelu")


def sigmoid(x, name=None):
    return apply(jax.nn.sigmoid, (x,), name="sigmoid")


def log_sigmoid(x, name=None):
    return apply(jax.nn.log_sigmoid, (x,), name="log_sigmoid")


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return apply(lambda x, s, o: jnp.clip(s * x + o, 0.0, 1.0), (x,),
                 dict(s=slope, o=offset), name="hard_sigmoid")


def hard_swish(x, name=None):
    return apply(lambda x: x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0, (x,),
                 name="hard_swish")


def swish(x, name=None):
    return apply(lambda x: x * jax.nn.sigmoid(x), (x,), name="swish")


silu = swish


def mish(x, name=None):
    return apply(lambda x: x * jnp.tanh(jax.nn.softplus(x)), (x,),
                 name="mish")


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(lambda x, b, t: jnp.where(
        b * x > t, x, jax.nn.softplus(b * x) / b), (x,),
        dict(b=beta, t=threshold), name="softplus")


def softsign(x, name=None):
    return apply(lambda x: x / (1 + jnp.abs(x)), (x,), name="softsign")


def softshrink(x, threshold=0.5, name=None):
    return apply(lambda x, t: jnp.where(x > t, x - t,
                                        jnp.where(x < -t, x + t, 0.0)),
                 (x,), dict(t=threshold), name="softshrink")


def hard_shrink(x, threshold=0.5, name=None):
    """reference: layers/ops.py:113 hard_shrink."""
    t = 0.5 if threshold is None else threshold
    return apply(lambda x, t: jnp.where(jnp.abs(x) > t, x, 0.0), (x,),
                 dict(t=t), name="hard_shrink")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(lambda x, lo, hi: jnp.clip(x, lo, hi), (x,),
                 dict(lo=min, hi=max), name="hardtanh")


def tanhshrink(x, name=None):
    return apply(lambda x: x - jnp.tanh(x), (x,), name="tanhshrink")


def thresholded_relu(x, threshold=1.0, name=None):
    return apply(lambda x, t: jnp.where(x > t, x, 0.0), (x,),
                 dict(t=threshold), name="thresholded_relu")


def maxout(x, groups, axis=1, name=None):
    def impl(x, groups, axis):
        c = x.shape[axis]
        new_shape = x.shape[:axis] + (c // groups, groups) + x.shape[axis + 1:]
        return jnp.max(x.reshape(new_shape), axis=axis + 1)
    return apply(impl, (x,), dict(groups=groups, axis=axis), name="maxout")


def softmax(x, axis=-1, name=None):
    """reference: softmax_op.cc — one fused XLA softmax."""
    with _pscope("F.softmax"):
        return apply(lambda x, axis: jax.nn.softmax(x, axis=axis), (x,),
                     dict(axis=axis), name="softmax")


def log_softmax(x, axis=-1, name=None):
    with _pscope("F.log_softmax"):
        return apply(lambda x, axis: jax.nn.log_softmax(x, axis=axis), (x,),
                     dict(axis=axis), name="log_softmax")


# ---------------------------------------------------------------------------
# linear / embedding

def linear(x, weight, bias=None, name=None):
    """fc core (reference: mul_op + elementwise_add bias in fc layer):
    x @ W + b in one dot for the MXU. AMP white-listed."""
    from .. import amp
    from .math import cast as _cast
    if amp.is_enabled():
        dt = amp.compute_dtype()
        x, weight = _cast(x, dt), _cast(weight, dt)
        bias = None if bias is None else _cast(bias, dt)
    if bias is None:
        return apply(lambda x, w: jnp.matmul(x, w), (x, weight),
                     name="linear")
    return apply(lambda x, w, b: jnp.matmul(x, w) + b, (x, weight, bias),
                 name="linear")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """reference: lookup_table_op.cc. TPU: a gather; rows at padding_idx
    produce zeros and receive no gradient (mask trick keeps it one fused
    gather + where instead of the CUDA scatter-special-case)."""
    def impl(ids, w, padding_idx):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return apply(impl, (x, weight), dict(padding_idx=padding_idx),
                 name="embedding")


# ---------------------------------------------------------------------------
# convolution (reference: conv_op.cc/conv_cudnn_op.cu)

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


def _conv_dimension_numbers(ndim, data_format):
    # weights are ALWAYS OIHW/OIDHW (reference parity — state dicts stay
    # layout-independent); only the activation layout varies, which lax
    # supports via mixed dimension numbers
    if ndim == 4:
        return ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else (
            "NHWC", "OIHW", "NHWC")
    return ("NCDHW", "OIDHW", "NCDHW") if data_format == "NCDHW" else (
        "NDHWC", "OIDHW", "NDHWC")


def _norm_padding(padding, nsp):
    """paddle padding: int, pair list, 'SAME'/'VALID'."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nsp
    padding = list(padding)
    if len(padding) == nsp and not isinstance(padding[0], (list, tuple)):
        return [(p, p) for p in padding]
    if len(padding) == 2 * nsp:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(nsp)]
    return [tuple(p) for p in padding]


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    """One lax.conv_general_dilated → single MXU conv (no im2col).
    AMP white-listed."""
    from .. import amp
    from .math import cast as _cast
    if amp.is_enabled():
        dt = amp.compute_dtype()
        x, weight = _cast(x, dt), _cast(weight, dt)
        bias = None if bias is None else _cast(bias, dt)
    nsp = 2
    dn = _conv_dimension_numbers(4, data_format)
    attrs = dict(stride=_pair(stride, nsp), padding=_norm_padding(padding, nsp),
                 dilation=_pair(dilation, nsp), groups=groups, dn=dn)

    def impl(x, w, *maybe_bias, stride, padding, dilation, groups, dn):
        out = lax.conv_general_dilated(
            x, w, window_strides=stride, padding=padding,
            rhs_dilation=dilation, feature_group_count=groups,
            dimension_numbers=dn)
        if maybe_bias:
            b = maybe_bias[0]
            if dn[2] == "NCHW":
                out = out + b.reshape(1, -1, 1, 1)
            else:
                out = out + b.reshape(1, 1, 1, -1)
        return out

    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(impl, args, attrs, name="conv2d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    nsp = 3
    dn = _conv_dimension_numbers(5, data_format)
    attrs = dict(stride=_pair(stride, nsp), padding=_norm_padding(padding, nsp),
                 dilation=_pair(dilation, nsp), groups=groups, dn=dn)

    def impl(x, w, *maybe_bias, stride, padding, dilation, groups, dn):
        out = lax.conv_general_dilated(
            x, w, window_strides=stride, padding=padding,
            rhs_dilation=dilation, feature_group_count=groups,
            dimension_numbers=dn)
        if maybe_bias:
            b = maybe_bias[0]
            shape = ((1, -1) + (1,) * 3) if dn[2] == "NCDHW" else (
                (1,) * 4 + (-1,))
            out = out + b.reshape(shape)
        return out

    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(impl, args, attrs, name="conv3d")


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCHW", name=None):
    """reference: conv_transpose_op.cc. Expressed as an lhs-dilated conv of
    the gradient — XLA lowers this straight onto the MXU.

    The weight is ALWAYS the reference's IOHW layout
    (in, out/groups, kh, kw), regardless of data_format (which only
    describes the activations)."""
    nsp = 2
    lhs_spec = data_format  # "NCHW" or "NHWC"
    dn = (lhs_spec, "OIHW", lhs_spec)
    stride_t = _pair(stride, nsp)
    pad = _norm_padding(padding, nsp)
    dil = _pair(dilation, nsp)
    outpad = _pair(output_padding, nsp)

    def impl(x, w, *maybe_bias):
        kdims = w.shape[2:]
        if isinstance(pad, str):
            padding_cfg = pad
        else:
            # transpose padding math: effective pad = d*(k-1) - p
            padding_cfg = [
                (dil[i] * (kdims[i] - 1) - pad[i][0],
                 dil[i] * (kdims[i] - 1) - pad[i][1] + outpad[i])
                for i in range(nsp)]
        if groups > 1:
            # per-group: (in/g, out/g, kh, kw) -> (out/g, in/g, kh, kw)
            ci = w.shape[0]
            w_g = w.reshape(groups, ci // groups, *w.shape[1:])
            w_t = jnp.concatenate(
                [jnp.flip(w_g[g], axis=(2, 3)).swapaxes(0, 1)
                 for g in range(groups)], axis=0)
        else:
            # (in, out, kh, kw) -> flip spatial, swap io -> (out, in, kh, kw)
            w_t = jnp.flip(w, axis=(2, 3)).swapaxes(0, 1)
        out = lax.conv_general_dilated(
            x, w_t, window_strides=(1, 1), padding=padding_cfg,
            lhs_dilation=stride_t, rhs_dilation=dil,
            feature_group_count=groups, dimension_numbers=dn)
        if maybe_bias:
            b = maybe_bias[0]
            if data_format == "NCHW":
                out = out + b.reshape(1, -1, 1, 1)
            else:
                out = out + b.reshape(1, 1, 1, -1)
        return out

    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(impl, args, name="conv2d_transpose")


# ---------------------------------------------------------------------------
# pooling (reference: pool_op.cc) — lax.reduce_window

def _pool(x, kind, kernel, stride, padding, data_format, ceil_mode=False,
          exclusive=True, global_pool=False):
    def impl(x, kernel, stride, padding, data_format, global_pool):
        nd = x.ndim
        nsp = nd - 2
        if global_pool:
            kernel = x.shape[2:] if data_format.startswith("NC") else x.shape[1:-1]
            stride = kernel
            padding = [(0, 0)] * nsp
        kernel = _pair(kernel, nsp)
        stride = _pair(stride if stride is not None else kernel, nsp)
        pad = _norm_padding(padding, nsp)
        if data_format in ("NCHW", "NCDHW"):
            window = (1, 1) + kernel
            strides = (1, 1) + stride
            pads = ([(0, 0), (0, 0)] + pad) if not isinstance(pad, str) else pad
        else:
            window = (1,) + kernel + (1,)
            strides = (1,) + stride + (1,)
            pads = ([(0, 0)] + pad + [(0, 0)]) if not isinstance(pad, str) else pad
        if kind == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else (
                jnp.iinfo(x.dtype).min)
            return lax.reduce_window(x, init, lax.max, window, strides, pads)
        # avg
        ones = jnp.ones_like(x)
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        if exclusive and not isinstance(pads, str):
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
            return s / cnt
        return s / float(np.prod(kernel))

    return apply(impl, (x,), dict(kernel=kernel, stride=stride,
                                  padding=padding, data_format=data_format,
                                  global_pool=global_pool),
                 name=f"{kind}_pool")


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW", name=None):
    return _pool(x, "max", kernel_size, stride, padding, data_format,
                 ceil_mode)


def avg_pool2d(x, kernel_size, stride=None, padding=0, exclusive=True,
               data_format="NCHW", name=None):
    return _pool(x, "avg", kernel_size, stride, padding, data_format,
                 exclusive=exclusive)


def _adaptive_pool2d(x, output_size, data_format, reduce_name):
    """Adaptive pooling with paddle's start/end-index formula — handles
    non-divisible spatial sizes (the divisible case stays a single reshape)."""
    def impl(x, output_size, data_format):
        os_ = _pair(output_size, 2)
        chan_last = data_format == "NHWC"
        if chan_last:
            x = jnp.moveaxis(x, -1, 1)
        n, c, h, w = x.shape
        red = jnp.mean if reduce_name == "avg" else jnp.max
        if h % os_[0] == 0 and w % os_[1] == 0:
            x6 = x.reshape(n, c, os_[0], h // os_[0], os_[1], w // os_[1])
            out = red(x6, axis=(3, 5))
        else:
            rows = []
            for i in range(os_[0]):
                h0, h1 = (i * h) // os_[0], -(-((i + 1) * h) // os_[0])
                cols = []
                for j in range(os_[1]):
                    w0, w1 = (j * w) // os_[1], -(-((j + 1) * w) // os_[1])
                    cols.append(red(x[:, :, h0:h1, w0:w1], axis=(2, 3)))
                rows.append(jnp.stack(cols, axis=-1))
            out = jnp.stack(rows, axis=-2)
        if chan_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    return apply(impl, (x,), dict(output_size=output_size,
                                  data_format=data_format),
                 name=f"adaptive_{reduce_name}_pool2d")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool2d(x, output_size, data_format, "avg")


def adaptive_max_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool2d(x, output_size, data_format, "max")


def pool2d(x, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, data_format="NCHW", name=None):
    """fluid.layers.pool2d parity wrapper."""
    return _pool(x, "max" if pool_type == "max" else "avg", pool_size,
                 pool_stride, pool_padding, data_format,
                 global_pool=global_pooling)


# ---------------------------------------------------------------------------
# normalization (reference: batch_norm_op.cc, layer_norm_op.cu fused kernel,
# group_norm_op, instance_norm_op)

def _one_pass_moments(x, axes, keepdims=False):
    """(mean, var) over `axes` reading x ONCE: sum and sum-of-squares
    land in the same XLA multi-output fusion, vs jnp.mean + jnp.var's
    two sequential passes (the HBM-bound cost that dominates norm-heavy
    conv nets). Accumulates in f32, shifted by a stop_gradient sample
    (variance is shift-invariant) so large-mean inputs don't cancel."""
    xf = x.astype(jnp.float32)
    n = np.prod([x.shape[a] for a in axes])
    c = lax.stop_gradient(xf[tuple(
        slice(0, 1) if a in axes else slice(None)
        for a in range(x.ndim))])
    xs = xf - c
    m_s = jnp.sum(xs, axis=axes, keepdims=keepdims) / n
    mean = m_s + (c if keepdims else jnp.squeeze(c, axis=axes))
    var = jnp.maximum(
        jnp.sum(jnp.square(xs), axis=axes, keepdims=keepdims) / n -
        jnp.square(m_s), 0.0)
    return mean, var


def _fold_scale_shift(x, mean, var, w, b, epsilon, shape):
    """Fold (mean, var, w, b) into ONE per-channel scale+shift applied
    in x's compute dtype: under amp the whole elementwise chain (and
    the residual adds downstream) stays bf16 instead of promoting to
    f32, halving HBM traffic on the BN→relu→add path. w/b may be None
    (no-affine). Shared by batch_norm and SyncBatchNorm so the
    amp-sensitive folding can't drift between the SPMD and local
    paths."""
    inv = lax.rsqrt(var.astype(jnp.float32) + epsilon)
    scale, shift = inv, -mean.astype(jnp.float32) * inv
    if w is not None:
        scale = inv * w.astype(jnp.float32)
        shift = b.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return x * scale.astype(x.dtype).reshape(shape) + \
        shift.astype(x.dtype).reshape(shape)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", name=None):
    """Returns (out, new_running_mean, new_running_var). The Layer writes the
    running stats back (stateless-functional twist on the reference's
    in-place MomentumTensor update)."""
    def impl(x, rm, rv, *wb, training, momentum, epsilon, data_format):
        if data_format in ("NCHW", "NCL", "NCDHW") and x.ndim > 2:
            axes = (0,) + tuple(range(2, x.ndim))
            shape = (1, -1) + (1,) * (x.ndim - 2)
        else:
            axes = tuple(range(x.ndim - 1))
            shape = (1,) * (x.ndim - 1) + (-1,)
        if training:
            # batch stats in f32 via the shared one-pass moments (see
            # _one_pass_moments: single read, cancellation-guarded);
            # running stats stay in the buffer dtype
            mean, var = _one_pass_moments(x, axes)
            new_rm = momentum * rm + (1 - momentum) * mean.astype(rm.dtype)
            new_rv = momentum * rv + (1 - momentum) * var.astype(rv.dtype)
        else:
            mean, var = rm, rv
            new_rm, new_rv = rm, rv
        w, b = wb if wb else (None, None)
        out = _fold_scale_shift(x, mean, var, w, b, epsilon, shape)
        return out, new_rm, new_rv

    from . import pallas as P
    if weight is not None and bias is None:
        # bias_attr=False layers: affine with weight only — substitute
        # zeros so both branches below keep their two-or-none contract
        w_arr = as_tensor(weight).data
        bias = jnp.zeros(w_arr.shape, w_arr.dtype)
    elif weight is None and bias is not None:
        # weight_attr=False: the symmetric case — ones for the scale,
        # else the real bias parameter would be silently dropped
        b_arr = as_tensor(bias).data
        weight = jnp.ones(b_arr.shape, b_arr.dtype)
    chan_last = not (data_format in ("NCHW", "NCL", "NCDHW") and
                     getattr(x, "ndim", 2) > 2)
    if training and weight is not None and chan_last and \
            P.enabled("batch_norm"):
        # fused Pallas path (channels-last only — a transpose around the
        # kernel would cost the pass it saves); running stats fold on top
        # of the kernel's (out, mean, var)
        from .pallas.batch_norm import bn_channels_last

        def impl_pl(x, rm, rv, w, b):
            out2, mean, var = bn_channels_last(x, w, b, epsilon)
            new_rm = momentum * rm + (1 - momentum) * mean.astype(rm.dtype)
            new_rv = momentum * rv + (1 - momentum) * var.astype(rv.dtype)
            return out2, new_rm, new_rv

        return apply(impl_pl,
                     (x, running_mean, running_var, weight, bias),
                     n_out=3, name="pallas_batch_norm")

    args = (x, running_mean, running_var)
    if weight is not None:
        args = args + (weight, bias)
    with _pscope("F.batch_norm"):
        out = apply(impl, args,
                    dict(training=training, momentum=momentum,
                         epsilon=epsilon, data_format=data_format),
                    n_out=3, name="batch_norm")
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    """reference: layer_norm_op fused CUDA kernel → here plain XLA (fused by
    the compiler); Pallas variant in ops/pallas/layer_norm.py for the
    flagship path."""
    ns = (normalized_shape,) if isinstance(normalized_shape, int) else tuple(
        normalized_shape)
    naxes = len(ns)

    def impl(x, *wb, naxes, epsilon):
        axes = tuple(range(x.ndim - naxes, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + epsilon)
        if wb:
            w, b = wb
            out = out * w + b
        return out

    args = (x,) if weight is None else (x, weight, bias)
    with _pscope("F.layer_norm"):
        return apply(impl, args, dict(naxes=naxes, epsilon=epsilon),
                     name="layer_norm")


def _rms_norm(x, *rest, epsilon, num_groups, gated, scaled):
    """The portable path of ``rms_norm`` (``rest``: the gate where
    ``gated``, then the weight where ``scaled``) and the oracle of the
    gated kernels."""
    rest = list(rest)
    h = x.astype(jnp.float32)
    if gated:
        h = h * jax.nn.silu(rest.pop(0).astype(jnp.float32))
    shape = h.shape
    h = h.reshape(shape[:-1] + (num_groups, shape[-1] // num_groups))
    h = h * lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True)
                      + epsilon)
    h = h.reshape(shape)
    if scaled:
        h = h * rest.pop(0).astype(jnp.float32)
    return h.astype(x.dtype)


def rms_norm(x, weight=None, epsilon=1e-5, num_groups=1, gate=None,
             name=None):
    """Root-mean-square norm over the last axis (Zhang & Sennrich,
    arXiv:1910.07467): ``x * rsqrt(mean(x^2) + epsilon) * weight``, no mean
    taken off and no shift. ``num_groups`` > 1 splits the last axis into
    that many groups, each with a mean square of its own; ``gate``
    multiplies ``x`` by ``silu(gate)`` BEFORE the norm (Mamba-2's gated
    norm). Statistics in float32 whatever ``x`` is; the result has ``x``'s
    dtype. The gated norm of a ``[B, S, D]`` array runs, on one TPU and
    where its tiles fit, as the kernel pair of
    ``ops/pallas/gated_rms_norm.py`` (a group is a window of lanes, no
    reshape; PERF.md section 6, PR 30); counters
    ``rms_norm.gated_kernel_traced`` / ``rms_norm.gated_xla_traced``."""
    args = (x,) + (() if gate is None else (gate,)) \
        + (() if weight is None else (weight,))
    attrs = dict(epsilon=float(epsilon), num_groups=int(num_groups))
    fn = functools.partial(_rms_norm, gated=gate is not None,
                           scaled=weight is not None)
    if gate is not None:
        from .. import monitor
        from . import pallas
        # read off the call: the kernel pair where its tiles fit and the
        # registry has it on, else _rms_norm
        kernel = (pallas.enabled("gated_rms_norm")
                  and pallas.gated_rms_norm_mod.supported(
                      tuple(x.shape), int(num_groups))
                  and tuple(gate.shape) == tuple(x.shape)
                  and (weight is None
                       or tuple(weight.shape) == tuple(x.shape[-1:])))
        monitor.counter("rms_norm.gated_kernel_traced" if kernel
                        else "rms_norm.gated_xla_traced").inc()
        if kernel:
            fn = pallas.gated_rms_norm_mod.gated_rms_norm
    with _pscope("F.rms_norm"):
        return apply(fn, args, attrs, name="rms_norm")


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None):
    def impl(x, *wb, num_groups, epsilon, data_format):
        if data_format == "NHWC":
            x = jnp.moveaxis(x, -1, 1)
        n, c = x.shape[:2]
        sp = x.shape[2:]
        xg = x.reshape(n, num_groups, c // num_groups, *sp)
        axes = tuple(range(2, xg.ndim))
        mean, var = _one_pass_moments(xg, axes, keepdims=True)
        out = ((xg - mean) * lax.rsqrt(var + epsilon)).astype(
            x.dtype).reshape(x.shape)
        if wb:
            w, b = wb
            shape = (1, c) + (1,) * len(sp)
            out = out * w.reshape(shape) + b.reshape(shape)
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out
    args = (x,) if weight is None else (x, weight, bias)
    return apply(impl, args, dict(num_groups=num_groups, epsilon=epsilon,
                                  data_format=data_format), name="group_norm")


def instance_norm(x, weight=None, bias=None, epsilon=1e-5, name=None):
    def impl(x, *wb, epsilon):
        axes = tuple(range(2, x.ndim))
        mean, var = _one_pass_moments(x, axes, keepdims=True)
        out = ((x - mean) * lax.rsqrt(var + epsilon)).astype(x.dtype)
        if wb:
            w, b = wb
            shape = (1, -1) + (1,) * (x.ndim - 2)
            out = out * w.reshape(shape) + b.reshape(shape)
        return out
    args = (x,) if weight is None else (x, weight, bias)
    return apply(impl, args, dict(epsilon=epsilon), name="instance_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def impl(x, p, axis, epsilon):
        nrm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis,
                                keepdims=True), 1.0 / p)
        return x / jnp.maximum(nrm, epsilon)
    return apply(impl, (x,), dict(p=p, axis=axis, epsilon=epsilon),
                 name="normalize")


def local_response_norm(x, size=5, alpha=1e-4, beta=0.75, k=1.0, name=None):
    """reference: lrn_op.cc (NCHW)."""
    def impl(x, size, alpha, beta, k):
        sq = jnp.square(x)
        half = size // 2
        pads = [(0, 0), (half, size - 1 - half), (0, 0), (0, 0)]
        sq = jnp.pad(sq, pads)
        acc = sum(sq[:, i:i + x.shape[1]] for i in range(size))
        return x / jnp.power(k + alpha * acc, beta)
    return apply(impl, (x,), dict(size=size, alpha=alpha, beta=beta, k=k),
                 name="lrn")


# ---------------------------------------------------------------------------
# dropout (reference: dropout_op.cu) — global threaded PRNG

def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None,
            name=None):
    x = as_tensor(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply(lambda x, p: x * (1 - p), (x,), dict(p=p),
                         name="dropout_infer")
        return x
    key = prandom.next_key_graph()  # symbolic per-run key in static mode

    def impl(x, key, p, mode, axis):
        shape = x.shape
        if axis is not None:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, x / (1.0 - p), 0.0)
        return jnp.where(keep, x, 0.0)

    return apply(impl, (x, key), dict(p=p, mode=mode, axis=axis),
                 name="dropout")


# ---------------------------------------------------------------------------
# attention / misc

def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None,
                                 name=None):
    """Plain XLA attention (B, H, S, D). Flash/pallas variant in
    ops/pallas/flash_attention.py; ring variant in parallel/ring_attention."""
    p_drop = float(dropout_p) if training else 0.0
    attrs = dict(is_causal=is_causal, scale=scale)

    def impl(q, k, v, *rest, is_causal, scale):
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / np.sqrt(d)
        logits = jnp.einsum("...qd,...kd->...qk", q, k) * s
        if attn_mask is not None:
            m = rest[0]
            if m.dtype == jnp.bool_:
                logits = jnp.where(m, logits, -1e9)
            else:
                logits = logits + m
        if is_causal:
            sq, sk = logits.shape[-2:]
            causal = jnp.tril(jnp.ones((sq, sk), jnp.bool_))
            logits = jnp.where(causal, logits, -1e9)
        probs = jax.nn.softmax(logits, axis=-1)
        if p_drop > 0.0:
            # dropout on the attention PROBABILITIES (reference semantics:
            # the attn_dropout in multihead attention / what the fused
            # Pallas kernel does in-kernel), not on the context output
            keep = jax.random.bernoulli(rest[-1], 1.0 - p_drop,
                                        probs.shape)
            probs = jnp.where(keep, probs / (1.0 - p_drop), 0.0)
        return jnp.einsum("...qk,...kd->...qd", probs, v)

    args = (q, k, v)
    if attn_mask is not None:
        args = args + (attn_mask,)
    if p_drop > 0.0:
        args = args + (prandom.next_key_graph(),)
    return apply(impl, args, attrs, name="sdpa")


def rotary_embedding(x, positions=None, theta=10000.0, interleaved=True,
                     sections=None, name=None):
    """Rotary position embedding (Su et al., arXiv:2104.09864) of ``x``
    ``[..., S, D]``, the sequence on the axis before the last and ``D``
    even: pair ``j < D / 2`` at position ``p`` turns by ``p * theta ** (-2 j
    / D)``. ``positions`` ``[S]`` (or anything that broadcasts against
    ``x``'s leading axes and ``S``); None counts from 0. With ``sections``
    (whole numbers that add up to ``D / 2``) the positions have one row
    an AXIS, ``[len(sections), S]``, and the pairs are cut into contiguous
    chunks of those sizes, chunk ``a`` turning by row ``a``'s position
    (Qwen2-VL's ``mrope_section``: temporal, height, width); rows equal to
    each other give the one-axis result bit for bit.

    ``interleaved=True`` pairs the neighbours ``(x[2j], x[2j+1])`` and
    returns the halves apart, as the DeepSeek-V3 family's code
    de-interleaves and then rotates halves: ``out[j] = x[2j] cos - x[2j+1]
    sin``, ``out[j + D/2] = x[2j+1] cos + x[2j] sin``. ``False`` pairs
    ``(x[j], x[j + D/2])`` and keeps that layout. A score ``q . k`` is the
    same for any layout q and k share, and depends on the two positions'
    difference only. Angles and the rotation in float32 whatever ``x`` is
    (the frequencies are made in float64 on the host); the result has
    ``x``'s dtype. Whole heads on their way from a projection to the
    attention op take ``qk_heads`` below, which rotates in the same pass
    that norms and transposes them."""
    freq = _rotary_frequencies(x.shape[-1], theta, "rotary_embedding")
    sections = _rotary_sections(sections, positions, x.shape[-1],
                                "rotary_embedding")

    def impl(x, *pos, interleaved):
        return _rotate(x, pos[0] if pos else None, freq, interleaved,
                       sections)

    args = (x,) if positions is None else (x, positions)
    with _pscope("F.rotary_embedding"):
        return apply(impl, args, dict(interleaved=bool(interleaved)),
                     name="rotary_embedding")


def _rotary_frequencies(d, theta, op):
    d = int(d)
    if d % 2:
        raise ValueError(f"{op}: the last axis has to be even, got {d}")
    return np.asarray(float(theta) ** (-np.arange(0, d, 2, dtype=np.float64)
                                       / d), np.float32)


def _rotary_sections(sections, positions, d, op):
    """``sections`` as a tuple of whole numbers, checked against the
    positions' rows and the pairs of a head of ``d``; None stays None."""
    if sections is None:
        return None
    sections = tuple(int(n) for n in sections)
    shape = None if positions is None else tuple(positions.shape)
    if shape is None or len(shape) != 2 or shape[0] != len(sections) \
            or sum(sections) != d // 2 or min(sections) < 1:
        raise ValueError(
            f"{op}: sections {sections} take positions [{len(sections)}, S] "
            f"and add up to the {d // 2} pairs of a head; positions "
            f"{shape}")
    return sections


def _cos_sin(positions, s, freq, sections=None):
    """Cosine and sine ``[..., S, D / 2]`` of the rotation's angles,
    float32; ``positions`` None counts ``s`` rows from 0. Under
    ``sections`` the positions are ``[axes, S]`` and pair ``j`` reads the
    row of the chunk it lies in."""
    p = jnp.arange(s, dtype=jnp.float32) if positions is None \
        else positions.astype(jnp.float32)
    if sections is None:
        p = p[..., None]
    else:
        p = p[np.repeat(np.arange(len(sections)), sections)].T
    angle = p * jnp.asarray(freq, jnp.float32)
    return jnp.cos(angle), jnp.sin(angle)


def _rotate(x, positions, freq, interleaved, sections=None):
    """``rotary_embedding`` on arrays: ``x`` [..., S, D], ``positions``
    None or what broadcasts against ``x``'s leading axes and ``S``."""
    d = x.shape[-1]
    cos, sin = _cos_sin(positions, x.shape[-2], freq, sections)
    xf = x.astype(jnp.float32)
    a, b = (xf[..., 0::2], xf[..., 1::2]) if interleaved \
        else (xf[..., :d // 2], xf[..., d // 2:])
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _qk_heads(x, *rest, heads, epsilon, freq, normed, positioned,
              sections=None):
    """The portable path of ``qk_heads`` (``rest``: the norm's weight where
    ``normed``, then the positions where ``positioned``) and the kernels'
    oracle: ``_rms_norm`` over each head, the transpose, ``_rotate``."""
    rest = list(rest)
    b, s, hd = x.shape
    t = x.reshape(b, s, heads, hd // heads)
    if normed:
        t = _rms_norm(t, rest.pop(0), epsilon=epsilon, num_groups=1,
                      gated=False, scaled=True)
    t = jnp.transpose(t, (0, 2, 1, 3))
    if freq is not None:
        t = _rotate(t, rest.pop(0) if positioned else None, freq, False,
                    sections)
    return t


def qk_heads(x, num_heads, weight=None, epsilon=1e-6, positions=None,
             theta=None, sections=None, name=None):
    """A projection's result ``x`` [B, S, num_heads * D] as the attention
    op takes its heads, [B, num_heads, S, D]: an RMS norm over each head
    where ``weight`` [D] is given (``rms_norm``'s numbers, ``epsilon``), a
    rotary embedding where ``theta`` is (``rotary_embedding(interleaved=
    False)``'s: pairs ``(x[j], x[j + D/2])``, ``positions`` [S] or None
    for 0, 1, ...; ``[axes, S]`` with ``sections``, as
    ``rotary_embedding`` reads them), the norm first. Float32 inside, rounded to ``x``'s
    dtype after the norm and after the rotation, as the two ops round.

    On one TPU, where a head is whole 128-lane tiles and the rows whole
    row tiles, the kernel pair of ``ops/pallas/qk_heads.py``: one read and
    one write of the array forward, the rotation a lane roll, the
    transpose the index maps' (PERF.md section 6, PR 45). Else the
    composition of the three ops, ``_qk_heads`` above. Counters
    ``qk_heads.kernel_traced`` / ``qk_heads.xla_traced``."""
    if x.ndim != 3 or x.shape[-1] % num_heads:
        raise ValueError(f"qk_heads: x {tuple(x.shape)} is not [B, S, "
                         f"{num_heads} heads * D]")
    d = x.shape[-1] // num_heads
    freq = None if theta is None else tuple(
        _rotary_frequencies(d, theta, "qk_heads").tolist())
    if freq is None and positions is not None:
        raise ValueError("qk_heads: positions without theta rotate nothing")
    sections = _rotary_sections(sections, positions, d, "qk_heads")
    from .. import monitor
    from . import pallas
    # read off the call, as rms_norm's gated form above
    kernel = (pallas.enabled("qk_heads")
              and pallas.qk_heads_mod.supported(
                  tuple(x.shape), int(num_heads),
                  None if positions is None else tuple(positions.shape),
                  sections)
              and (weight is None or tuple(weight.shape) == (d,)))
    monitor.counter("qk_heads.kernel_traced" if kernel
                    else "qk_heads.xla_traced").inc()
    args = (x,) + (() if weight is None else (weight,)) \
        + (() if positions is None else (positions,))
    attrs = dict(heads=int(num_heads), epsilon=float(epsilon), freq=freq,
                 normed=weight is not None, positioned=positions is not None)
    if sections is not None:    # a call without them traces what it traced
        attrs["sections"] = sections
    with _pscope("F.qk_heads"):
        return apply(pallas.qk_heads_mod.qk_heads if kernel else _qk_heads,
                     args, attrs, name="qk_heads")


def _mla_heads(q, kv, k_rope, *, heads, nope, v, freq):
    """The portable path of ``mla_heads`` and the kernels' oracle: what
    ``MultiHeadLatentAttention.qkv`` wrote down before the op was there."""
    b, s, rope = k_rope.shape
    q = jnp.transpose(q.reshape(b, s, heads, nope + rope), (0, 2, 1, 3))
    kv = jnp.transpose(kv.reshape(b, s, heads, nope + v), (0, 2, 1, 3))
    q_rope = _rotate(q[:, :, :, nope:], None, freq, True)
    k_rope = _rotate(k_rope, None, freq, True)
    k_rope = jnp.broadcast_to(k_rope[:, None], (b, heads, s, rope))
    q = jnp.concatenate([q[:, :, :, :nope], q_rope], -1)
    k = jnp.concatenate([kv[:, :, :, :nope], k_rope], -1)
    return q, k, kv[:, :, :, nope:]


def mla_heads(q, kv, k_rope, num_heads, qk_nope_head_dim, v_head_dim,
              theta=10000.0, name=None):
    """A latent attention's three projected arrays as the attention op
    takes its heads (DeepSeek-V2, arXiv:2405.04434 section 2.1): ``q`` [B,
    S, num_heads * (nope + rope)], a head's lanes without positions then
    its rotary lanes; ``kv`` [B, S, num_heads * (nope + v)], a head's
    ``k_nope`` then its value; ``k_rope`` [B, S, rope], the ONE rotary key
    head. Returns ``(q, k, v)``: [B, num_heads, S, nope + rope] twice and
    [B, num_heads, S, v]. The rotary lanes are
    ``rotary_embedding(interleaved=True)``'s at positions 0, 1, ...
    (neighbouring pairs, the halves returned apart; float32 inside, one
    rounding), every other lane is moved as it is, and the rotated key
    head stands behind every head's ``k_nope``.

    On one TPU, where the rotary part is 64 lanes, ``nope`` and ``v`` whole
    128-lane tiles, the heads even and the rows whole row tiles, the kernel
    pair of ``ops/pallas/mla_heads.py``: one read of each input and one
    write of each result each way, the key head's gradient summed over the
    heads in VMEM (PERF.md section 6, PR 48). Else the composition,
    ``_mla_heads`` above. Counters ``mla_heads.kernel_traced`` /
    ``mla_heads.xla_traced``."""
    heads, nope, v = int(num_heads), int(qk_nope_head_dim), int(v_head_dim)
    if q.ndim != 3 or k_rope.ndim != 3 or heads < 1:
        raise ValueError(f"mla_heads: q {tuple(q.shape)} and k_rope "
                         f"{tuple(k_rope.shape)} are not [B, S, width]")
    b, s, rope = k_rope.shape
    if tuple(q.shape) != (b, s, heads * (nope + rope)) \
            or tuple(kv.shape) != (b, s, heads * (nope + v)):
        raise ValueError(
            f"mla_heads: {heads} heads of {nope} + {rope} and {nope} + {v} "
            f"lanes over k_rope's [{b}, {s}] rows are q [{b}, {s}, "
            f"{heads * (nope + rope)}] and kv [{b}, {s}, "
            f"{heads * (nope + v)}]; got {tuple(q.shape)} and "
            f"{tuple(kv.shape)}")
    freq = tuple(_rotary_frequencies(rope, theta, "mla_heads").tolist())
    from .. import monitor
    from . import pallas
    kernel = (pallas.enabled("mla_heads")     # read off the call
              and pallas.mla_heads_mod.supported(
                  tuple(q.shape), tuple(kv.shape), tuple(k_rope.shape),
                  heads, nope, v, [t.dtype for t in (q, kv, k_rope)]))
    monitor.counter("mla_heads.kernel_traced" if kernel
                    else "mla_heads.xla_traced").inc()
    with _pscope("F.mla_heads"):
        return apply(pallas.mla_heads_mod.mla_heads if kernel
                     else _mla_heads, (q, kv, k_rope),
                     dict(heads=heads, nope=nope, v=v, freq=freq), n_out=3,
                     name="mla_heads")


def _differential_heads(ctx, lq1, lk1, lq2, lk2, weight, *, lambda_init,
                        epsilon):
    f32 = jnp.float32
    b, h, s, w = ctx.shape
    lam = (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
           - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32)))
           + lambda_init)
    pair = ctx.astype(f32).reshape(b, h // 2, 2, s, w)
    diff = pair[:, :, 0] - lam * pair[:, :, 1]
    diff = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), -1, keepdims=True) + epsilon)
    out = (1.0 - lambda_init) * diff * weight.astype(f32)
    return jnp.moveaxis(out, 1, 2).reshape(b, s, h // 2 * w).astype(ctx.dtype)


def differential_heads(ctx, lambda_q1, lambda_k1, lambda_q2, lambda_k2,
                       weight, lambda_init, epsilon=1e-5, name=None):
    """What differential attention (Ye et al., arXiv:2410.05258) does
    behind its two soft-max maps. ``ctx`` [B, H, S, W] holds ``A1 V`` in
    the even heads and ``A2 V`` in the odd ones (one attention call over
    paired heads); pair ``j`` gives ``(1 - lambda_init) * RMSNorm_W(ctx[2j]
    - lambda * ctx[2j + 1]) * weight`` with ``lambda = exp(lambda_q1 .
    lambda_k1) - exp(lambda_q2 . lambda_k2) + lambda_init`` and ONE scale
    ``weight`` [W] for all pairs. Returns [B, S, H / 2 * W], the pairs
    side by side, in ``ctx``'s dtype; ``lambda``, the subtraction (whose
    two terms nearly cancel) and the norm are float32 whatever it is."""
    if ctx.ndim != 4 or ctx.shape[1] % 2:
        raise ValueError(f"differential_heads: ctx {tuple(ctx.shape)} is "
                         f"not [B, an even number of heads, S, W]")
    with _pscope("F.differential_heads"):
        return apply(_differential_heads,
                     (ctx, lambda_q1, lambda_k1, lambda_q2, lambda_k2, weight),
                     dict(lambda_init=float(lambda_init),
                          epsilon=float(epsilon)),
                     name="differential_heads")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW", name=None):
    """reference: interpolate_op.cc (nearest/bilinear)."""
    def impl(x, size, scale_factor, mode, align_corners, data_format):
        chan_last = data_format == "NHWC"
        if not chan_last:
            x = jnp.moveaxis(x, 1, -1)
        n, h, w, c = x.shape
        if size is None:
            sf = _pair(scale_factor, 2)
            size = (int(h * sf[0]), int(w * sf[1]))
        method = {"nearest": "nearest", "bilinear": "bilinear",
                  "bicubic": "cubic"}[mode]
        out = jax.image.resize(x, (n, size[0], size[1], c), method=method)
        if not chan_last:
            out = jnp.moveaxis(out, -1, 1)
        return out
    sz = tuple(size) if isinstance(size, (list, tuple)) else size
    return apply(impl, (x,), dict(size=sz, scale_factor=scale_factor,
                                  mode=mode, align_corners=align_corners,
                                  data_format=data_format),
                 name="interpolate")


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    def impl(x, r, data_format):
        if data_format == "NCHW":
            n, c, h, w = x.shape
            x = x.reshape(n, c // (r * r), r, r, h, w)
            x = x.transpose(0, 1, 4, 2, 5, 3)
            return x.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = x.shape
        x = x.reshape(n, h, w, r, r, c // (r * r))
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(n, h * r, w * r, c // (r * r))
    return apply(impl, (x,), dict(r=upscale_factor, data_format=data_format),
                 name="pixel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """reference: unfold_op.cc (im2col)."""
    def impl(x, kernel_sizes, strides, paddings, dilations):
        k = _pair(kernel_sizes, 2)
        s = _pair(strides, 2)
        p = _norm_padding(paddings, 2)
        d = _pair(dilations, 2)
        patches = lax.conv_general_dilated_patches(
            x, filter_shape=k, window_strides=s, padding=p, rhs_dilation=d,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        n, ckk, oh, ow = patches.shape
        return patches.reshape(n, ckk, oh * ow)
    return apply(impl, (x,), dict(kernel_sizes=kernel_sizes, strides=strides,
                                  paddings=paddings, dilations=dilations),
                 name="unfold")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    """reference: label_smooth_op.cc"""
    def impl(label, epsilon):
        k = label.shape[-1]
        return (1 - epsilon) * label + epsilon / k
    return apply(impl, (label,), dict(epsilon=epsilon), name="label_smooth")
