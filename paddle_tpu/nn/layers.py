"""paddle_tpu.nn.layers — the dygraph Layer zoo.

TPU-native rebuild of the reference's dygraph layers
(reference: python/paddle/fluid/dygraph/nn.py — Linear, Conv2D, Conv3D,
Conv2DTranspose, Pool2D, BatchNorm, LayerNorm, GroupNorm, InstanceNorm,
SpectralNorm, Embedding, Dropout, PRelu, NCE, BilinearTensorProduct,
GRUUnit). Parameters are created eagerly at construction (no LayerHelper /
startup Program); forward calls the pure functional ops, so every Layer
works identically in eager, to_static, and static-Program modes.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..tensor import Tensor, Parameter, convert_dtype
from .. import initializer as I
from .. import ops
from ..ops import nn_ops as F
from .layer import Layer


class Linear(Layer):
    """reference: dygraph/nn.py:Linear (weight [in, out] + bias)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierUniform())
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (out_features,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.linear(x, self.weight, self.bias)

    def __repr__(self):
        return (f"Linear(in={self.in_features}, out={self.out_features}, "
                f"bias={self.bias is not None})")


class Conv2D(Layer):
    """reference: dygraph/nn.py:Conv2D. Weight layout OIHW (API parity);
    XLA re-lays out for the MXU internally."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, weight_attr=None,
                 bias_attr=None, data_format="NCHW"):
        super().__init__()
        ks = F._pair(kernel_size, 2)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation,
                           groups=groups, data_format=data_format)
        fan_in = in_channels * ks[0] * ks[1] // groups
        self.weight = self.create_parameter(
            (out_channels, in_channels // groups, ks[0], ks[1]),
            attr=weight_attr,
            default_initializer=I.Normal(0.0, np.sqrt(2.0 / fan_in)))
        self.bias = None if bias_attr is False else self.create_parameter(
            (out_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.conv2d(x, self.weight, self.bias, **self._attrs)


class Conv2DTranspose(Layer):
    """reference: dygraph/nn.py:Conv2DTranspose (weight IOHW)."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, output_padding=0, dilation=1, groups=1,
                 weight_attr=None, bias_attr=None, data_format="NCHW"):
        super().__init__()
        ks = F._pair(kernel_size, 2)
        self._attrs = dict(stride=stride, padding=padding,
                           output_padding=output_padding, dilation=dilation,
                           groups=groups, data_format=data_format)
        self.weight = self.create_parameter(
            (in_channels, out_channels // groups, ks[0], ks[1]),
            attr=weight_attr, default_initializer=I.XavierUniform())
        self.bias = None if bias_attr is False else self.create_parameter(
            (out_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.conv2d_transpose(x, self.weight, self.bias, **self._attrs)


class Conv3D(Layer):
    """reference: dygraph/nn.py:Conv3D."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, weight_attr=None,
                 bias_attr=None, data_format="NCDHW"):
        super().__init__()
        ks = F._pair(kernel_size, 3)
        self._attrs = dict(stride=stride, padding=padding, dilation=dilation,
                           groups=groups, data_format=data_format)
        fan_in = in_channels * int(np.prod(ks)) // groups
        self.weight = self.create_parameter(
            (out_channels, in_channels // groups) + ks, attr=weight_attr,
            default_initializer=I.Normal(0.0, np.sqrt(2.0 / fan_in)))
        self.bias = None if bias_attr is False else self.create_parameter(
            (out_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.conv3d(x, self.weight, self.bias, **self._attrs)


class MaxPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, ceil_mode=False,
                 data_format="NCHW"):
        super().__init__()
        self._a = dict(kernel_size=kernel_size, stride=stride,
                       padding=padding, ceil_mode=ceil_mode,
                       data_format=data_format)

    def forward(self, x):
        return F.max_pool2d(x, **self._a)


class AvgPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0, exclusive=True,
                 data_format="NCHW"):
        super().__init__()
        self._a = dict(kernel_size=kernel_size, stride=stride,
                       padding=padding, exclusive=exclusive,
                       data_format=data_format)

    def forward(self, x):
        return F.avg_pool2d(x, **self._a)


class AdaptiveAvgPool2D(Layer):
    def __init__(self, output_size, data_format="NCHW"):
        super().__init__()
        self._a = dict(output_size=output_size, data_format=data_format)

    def forward(self, x):
        return F.adaptive_avg_pool2d(x, **self._a)


class Pool2D(Layer):
    """fluid.dygraph.Pool2D parity shim."""

    def __init__(self, pool_size=-1, pool_type="max", pool_stride=1,
                 pool_padding=0, global_pooling=False, data_format="NCHW"):
        super().__init__()
        self._a = dict(pool_size=pool_size, pool_type=pool_type,
                       pool_stride=pool_stride, pool_padding=pool_padding,
                       global_pooling=global_pooling, data_format=data_format)

    def forward(self, x):
        return F.pool2d(x, **self._a)


class BatchNorm(Layer):
    """reference: dygraph/nn.py:BatchNorm. Running stats live in buffers;
    forward in train mode returns fresh stats and we write them back
    (functionally visible to to_static as carried state)."""

    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 dtype=None):
        super().__init__(dtype=dtype)
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = self.create_parameter(
            (num_features,), attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                          is_bias=True)
        self.register_buffer("_mean",
                             Tensor(jnp.zeros((num_features,), self._dtype)))
        self.register_buffer("_variance",
                             Tensor(jnp.ones((num_features,), self._dtype)))

    def forward(self, x):
        out, new_mean, new_var = F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format)
        if self.training:
            self._mean.data = new_mean.data
            self._variance.data = new_var.data
        return out


class BatchNorm1D(BatchNorm):
    def __init__(self, num_features, **kw):
        kw.setdefault("data_format", "NCL")
        super().__init__(num_features, **kw)


class BatchNorm2D(BatchNorm):
    pass


class BatchNorm3D(BatchNorm):
    def __init__(self, num_features, **kw):
        kw.setdefault("data_format", "NCDHW")
        super().__init__(num_features, **kw)


class SyncBatchNorm(BatchNorm):
    """Cross-replica BN (reference: sync_batch_norm_op.cu): inside a
    shard_map region with the data-parallel axis bound, batch statistics
    are psum-averaged over that axis, so all replicas normalize with the
    same global-batch stats; running stats are updated from the synced
    values. Outside SPMD it degrades to ordinary BatchNorm."""

    def __init__(self, num_features, axis_name="dp", **kw):
        super().__init__(num_features, **kw)
        self._axis_name = axis_name

    def forward(self, x):
        from ..parallel import collective
        from ..dispatch import apply as _apply
        if not (self.training and collective.in_spmd_context(
                self._axis_name)):
            return super().forward(x)

        axis_name = self._axis_name
        momentum, eps = self._momentum, self._epsilon
        chan_first = self._data_format.startswith("NC")

        def impl(x, rm, rv, w, b):
            import jax.numpy as jnp
            from jax import lax
            axes = ((0,) + tuple(range(2, x.ndim))) if chan_first else \
                tuple(range(x.ndim - 1))
            shape = ((1, -1) + (1,) * (x.ndim - 2)) if chan_first else \
                ((1,) * (x.ndim - 1) + (-1,))
            # shift accumulators by the running mean: it is REPLICATED
            # state (identical on every dp shard, unlike a local data
            # sample) so the psum'd moments stay consistent, and once rm
            # tracks the data mean both accumulators are O(sigma^2) —
            # the same cancellation guard as _one_pass_moments
            c = lax.stop_gradient(rm.astype(jnp.float32))
            xs = x.astype(jnp.float32) - c.reshape(shape)
            s = lax.psum(jnp.sum(xs, axis=axes), axis_name)
            sq = lax.psum(jnp.sum(jnp.square(xs), axis=axes), axis_name)
            cnt = lax.psum(jnp.asarray(
                np.prod([x.shape[a] for a in axes]), jnp.float32), axis_name)
            m_s = s / cnt
            mean = m_s + c
            var = jnp.maximum(sq / cnt - jnp.square(m_s), 0.0)
            new_rm = (momentum * rm + (1 - momentum) * mean).astype(
                rm.dtype)
            new_rv = (momentum * rv + (1 - momentum) * var).astype(
                rv.dtype)
            out = F._fold_scale_shift(x, mean, var, w, b, eps, shape)
            return out, new_rm, new_rv

        # weight_attr/bias_attr=False make the params None — substitute
        # identity affine (mirrors F.batch_norm's guard)
        w = self.weight if self.weight is not None else \
            Tensor(jnp.ones((self._num_features,), jnp.float32))
        b = self.bias if self.bias is not None else \
            Tensor(jnp.zeros((self._num_features,), jnp.float32))
        out, new_mean, new_var = _apply(
            impl, (x, self._mean, self._variance, w, b),
            n_out=3, name="sync_batch_norm")
        self._mean.data = new_mean.data
        self._variance.data = new_var.data
        return out


class LayerNorm(Layer):
    """reference: dygraph/nn.py:LayerNorm (fused kernel → XLA/Pallas)."""

    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, use_pallas=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self._normalized_shape = tuple(normalized_shape)
        self._epsilon = epsilon
        # None = auto, resolved via pallas.enabled() when forward traces
        # (configure() before the first jitted step; traced steps keep
        # the choice they were compiled with)
        self._use_pallas = use_pallas if len(self._normalized_shape) == 1 \
            else False
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(self._normalized_shape,
                                              attr=bias_attr, is_bias=True)

    def forward(self, x):
        use = self._use_pallas
        if use is None:
            from ..ops import pallas as P
            use = P.enabled("layer_norm")
        if use and self.weight is not None and self.bias is not None:
            from ..ops.pallas.layer_norm import layer_norm as pallas_ln
            return pallas_ln(x, self.weight, self.bias, self._epsilon)
        return F.layer_norm(x, self._normalized_shape, self.weight,
                            self.bias, self._epsilon)


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned scale
    (``F.rms_norm``): no mean, no shift. ``num_groups`` > 1 norms each
    group of the last axis by itself; ``forward(x, gate)`` multiplies by
    ``silu(gate)`` before the norm (Mamba-2's gated norm)."""

    def __init__(self, hidden_size, epsilon=1e-5, num_groups=1,
                 weight_attr=None):
        super().__init__()
        if hidden_size % num_groups:
            raise ValueError(f"RMSNorm: {hidden_size} does not split into "
                             f"{num_groups} groups")
        self._epsilon = epsilon
        self._num_groups = num_groups
        self.weight = self.create_parameter(
            (hidden_size,), attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, x, gate=None):
        return F.rms_norm(x, self.weight, self._epsilon, self._num_groups,
                          gate=gate)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW"):
        super().__init__()
        self._a = dict(num_groups=num_groups, epsilon=epsilon,
                       data_format=data_format)
        self.weight = None if weight_attr is False else self.create_parameter(
            (num_channels,), attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            (num_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, weight=self.weight, bias=self.bias, **self._a)


class InstanceNorm2D(Layer):
    def __init__(self, num_features, epsilon=1e-5, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = None if weight_attr is False else self.create_parameter(
            (num_features,), attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = None if bias_attr is False else self.create_parameter(
            (num_features,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, self.weight, self.bias, self._epsilon)


class SpectralNorm(Layer):
    """reference: dygraph/nn.py:SpectralNorm — power-iteration normalized
    weight. Returns the normalized weight of shape `weight_shape`."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.weight_u = self.create_parameter(
            (h,), default_initializer=I.Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(
            (w,), default_initializer=I.Normal(0.0, 1.0))
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ..dispatch import apply
        dim, iters, eps = self._dim, self._power_iters, self._eps

        def impl(w, u, v):
            mat = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            for _ in range(iters):
                v = mat.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = mat @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ mat @ v
            return w / sigma, u, v

        out, u, v = apply(impl, (weight, self.weight_u, self.weight_v),
                          n_out=3, name="spectral_norm")
        self.weight_u.data = u.data
        self.weight_v.data = v.data
        return out


class Embedding(Layer):
    """reference: dygraph/nn.py:Embedding (lookup_table)."""

    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self._padding_idx = padding_idx
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.Normal(0.0, 1.0 / np.sqrt(embedding_dim)))

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self._padding_idx)


class Dropout(Layer):
    def __init__(self, p=0.5, axis=None, mode="upscale_in_train"):
        super().__init__()
        self._a = dict(p=p, axis=axis, mode=mode)

    def forward(self, x):
        return F.dropout(x, training=self.training, **self._a)


class PRelu(Layer):
    """reference: dygraph/nn.py:PRelu (modes: all/channel/element)."""

    def __init__(self, mode="all", channel=None, input_shape=None,
                 weight_attr=None):
        super().__init__()
        if mode == "all":
            shape = (1,)
        elif mode == "channel":
            shape = (channel,)
        else:
            shape = tuple(input_shape)
        self.weight = self.create_parameter(
            shape, attr=weight_attr, default_initializer=I.Constant(0.25))

    def forward(self, x):
        return F.prelu(x, self.weight)


class BilinearTensorProduct(Layer):
    """reference: dygraph/nn.py:BilinearTensorProduct
    out_k = x W_k y^T + b_k."""

    def __init__(self, input1_dim, input2_dim, output_dim, name=None,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        self.weight = self.create_parameter(
            (output_dim, input1_dim, input2_dim), attr=weight_attr)
        self.bias = self.create_parameter((output_dim,), attr=bias_attr,
                                          is_bias=True)

    def forward(self, x, y):
        from ..dispatch import apply
        def impl(x, y, w, b):
            return jnp.einsum("bi,oij,bj->bo", x, w, y) + b
        return apply(impl, (x, y, self.weight, self.bias),
                     name="bilinear_tensor_product")


class GRUUnit(Layer):
    """reference: dygraph/nn.py:GRUUnit — one GRU step (gate_weight holds
    update/reset gates, candidate_weight the candidate state)."""

    def __init__(self, size, weight_attr=None, bias_attr=None,
                 activation="tanh", gate_activation="sigmoid"):
        super().__init__()
        d = size // 3
        self._hidden = d
        self.gate_weight = self.create_parameter((d, d * 2), attr=weight_attr)
        self.candidate_weight = self.create_parameter((d, d),
                                                      attr=weight_attr)
        self.gate_bias = self.create_parameter((d * 2,), attr=bias_attr,
                                               is_bias=True)
        self.candidate_bias = self.create_parameter((d,), attr=bias_attr,
                                                    is_bias=True)
        self._act = getattr(jnp, activation) if hasattr(jnp, activation) \
            else jnp.tanh
        import jax
        self._gate_act = jax.nn.sigmoid if gate_activation == "sigmoid" \
            else jnp.tanh

    def forward(self, input, hidden):
        from ..dispatch import apply
        d = self._hidden
        act, gate_act = self._act, self._gate_act

        def impl(x, h, gw, cw, gb, cb):
            xu, xr, xc = x[:, :d], x[:, d:2 * d], x[:, 2 * d:]
            gates = gate_act(jnp.concatenate([xu, xr], 1) + h @ gw + gb)
            u, r = gates[:, :d], gates[:, d:]
            c = act(xc + (r * h) @ cw + cb)
            new_h = u * h + (1 - u) * c
            return new_h, r, c

        out = apply(impl, (input, hidden, self.gate_weight,
                           self.candidate_weight, self.gate_bias,
                           self.candidate_bias), n_out=3, name="gru_unit")
        return out  # (hidden, reset_hidden_pre, gate)


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self._start, self._stop = start_axis, stop_axis

    def forward(self, x):
        return ops.flatten(x, self._start, self._stop)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False, data_format="NCHW"):
        super().__init__()
        self._a = dict(size=size, scale_factor=scale_factor, mode=mode,
                       align_corners=align_corners, data_format=data_format)

    def forward(self, x):
        return F.interpolate(x, **self._a)


class Pad2D(Layer):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCHW"):
        super().__init__()
        self._padding = padding
        self._mode = mode
        self._value = value

    def forward(self, x):
        return ops.pad(x, self._padding, self._mode, self._value)


# -- simple activation layers ------------------------------------------------

def _act_layer(name, fn):
    class _Act(Layer):
        def __init__(self, *a, **kw):
            super().__init__()
            self._a, self._kw = a, kw

        def forward(self, x):
            return fn(x, *self._a, **self._kw)
    _Act.__name__ = name
    _Act.__qualname__ = name
    return _Act


ReLU = _act_layer("ReLU", F.relu)
ReLU6 = _act_layer("ReLU6", F.relu6)
LeakyReLU = _act_layer("LeakyReLU", F.leaky_relu)
GELU = _act_layer("GELU", F.gelu)
Sigmoid = _act_layer("Sigmoid", F.sigmoid)
Tanh = _act_layer("Tanh", ops.tanh)
Softmax = _act_layer("Softmax", F.softmax)
LogSoftmax = _act_layer("LogSoftmax", F.log_softmax)
Softplus = _act_layer("Softplus", F.softplus)
Hardswish = _act_layer("Hardswish", F.hard_swish)
Hardsigmoid = _act_layer("Hardsigmoid", F.hard_sigmoid)
Swish = _act_layer("Swish", F.swish)
Silu = _act_layer("Silu", F.silu)
Mish = _act_layer("Mish", F.mish)
ELU = _act_layer("ELU", F.elu)
SELU = _act_layer("SELU", F.selu)
Hardtanh = _act_layer("Hardtanh", F.hardtanh)


class NCE(Layer):
    """reference: dygraph/nn.py:NCE — noise-contrastive estimation loss for
    large-vocab softmax. Samples `num_neg_samples` noise classes per batch
    (uniform or custom_dist) and returns the NCE logistic loss."""

    def __init__(self, num_total_classes, dim, sample_weight=None,
                 param_attr=None, bias_attr=None, num_neg_samples=10,
                 sampler="uniform", custom_dist=None, seed=0):
        super().__init__()
        self.num_total_classes = num_total_classes
        self.num_neg_samples = num_neg_samples
        self.weight = self.create_parameter((num_total_classes, dim),
                                            attr=param_attr)
        self.bias = self.create_parameter((num_total_classes,),
                                          attr=bias_attr, is_bias=True)
        self._custom_dist = (np.asarray(custom_dist, dtype="f4")
                             if custom_dist is not None else None)

    def forward(self, input, label):
        from ..dispatch import apply
        from .. import random as prandom
        import jax
        k = self.num_neg_samples
        n_cls = self.num_total_classes
        key = prandom.next_key_graph()  # per-run symbolic key in static
        custom = self._custom_dist

        def impl(x, label, w, b, key):
            if custom is not None:
                dist = jnp.asarray(custom)
                noise = jax.random.categorical(key, jnp.log(dist + 1e-12),
                                               shape=(k,))
                noise_p = dist[noise]
            else:
                dist = None
                noise = jax.random.randint(key, (k,), 0, n_cls)
                noise_p = jnp.full((k,), 1.0 / n_cls)
            lbl = label.reshape(-1)
            pos_logit = jnp.sum(x * w[lbl], axis=-1) + b[lbl]
            # NCE logistic loss: each logit is corrected by log(k·q(class))
            # under the SAME noise distribution q for positives and
            # negatives
            pos_q = dist[lbl] if dist is not None else 1.0 / n_cls
            pos_loss = jax.nn.softplus(-(pos_logit -
                                         jnp.log(k * pos_q + 1e-12)))
            neg_logit = x @ w[noise].T + b[noise]  # [B, k]
            neg_loss = jax.nn.softplus(neg_logit -
                                       jnp.log(k * noise_p + 1e-12))
            return (pos_loss + jnp.sum(neg_loss, axis=-1)).reshape(-1, 1)

        return apply(impl, (input, label, self.weight, self.bias, key),
                     name="nce")


InstanceNorm = InstanceNorm2D  # fluid dygraph name (reference dygraph/nn.py)


class Conv3DTranspose(Layer):
    """reference: dygraph/nn.py:Conv3DTranspose → the lhs-dilated conv
    formulation (fluid.layers_extra.conv3d_transpose math)."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, groups=1, weight_attr=None,
                 bias_attr=None, data_format="NCDHW"):
        super().__init__()
        ks = F._pair(kernel_size, 3)
        self._cfg = dict(stride=F._pair(stride, 3),
                         padding=F._pair(padding, 3),
                         dilation=F._pair(dilation, 3), groups=groups)
        self.weight = self.create_parameter(
            (in_channels, out_channels // groups) + ks, attr=weight_attr,
            default_initializer=I.XavierUniform())
        self.bias = None if bias_attr is False else self.create_parameter(
            (out_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        from ..dispatch import apply as _apply
        import jax.numpy as jnp
        from jax import lax
        st, pd, dl = (self._cfg["stride"], self._cfg["padding"],
                      self._cfg["dilation"])
        groups = self._cfg["groups"]

        def impl(x, w, *maybe_b):
            kdims = w.shape[2:]
            pads = [(dl[i] * (kdims[i] - 1) - pd[i],
                     dl[i] * (kdims[i] - 1) - pd[i]) for i in range(3)]
            wf = jnp.flip(w, axis=(2, 3, 4))
            cin = wf.shape[0]
            if groups > 1:
                wf = wf.reshape(groups, cin // groups, -1, *kdims)
                wf = jnp.moveaxis(wf, 2, 1)
                rhs = wf.reshape(-1, cin // groups, *kdims)
            else:
                rhs = jnp.moveaxis(wf, 1, 0)
            out = lax.conv_general_dilated(
                x, rhs, window_strides=(1, 1, 1), padding=pads,
                lhs_dilation=st, rhs_dilation=dl,
                feature_group_count=groups,
                dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
            if maybe_b:
                out = out + maybe_b[0].reshape(1, -1, 1, 1, 1)
            return out

        args = (x, self.weight)
        if self.bias is not None:
            args = args + (self.bias,)
        return _apply(impl, args, name="conv3d_transpose")


class TreeConv(Layer):
    """reference: dygraph/nn.py:TreeConv (tree-based convolution,
    TBCNN). nodes_vector (B, N, D) + edge_set (B, E, 2) parent→child
    edges; each node convolves over its (parent, self, children)
    neighborhood via three weight matrices — the adjacency-matmul
    formulation (dense, MXU-friendly) of the reference's gather kernel."""

    def __init__(self, feature_size, output_size, num_filters=1,
                 max_depth=8, act="tanh", param_attr=None, bias_attr=None,
                 name=None):
        super().__init__()
        self._act = act
        self.num_filters = num_filters
        self.output_size = output_size
        # three role matrices: self / parent-side / child-side
        self.weight = self.create_parameter(
            (3, feature_size, output_size * num_filters), attr=param_attr,
            default_initializer=I.XavierUniform())
        self.bias = None if bias_attr is False else self.create_parameter(
            (output_size * num_filters,), attr=bias_attr, is_bias=True)

    def forward(self, nodes_vector, edge_set):
        from ..dispatch import apply as _apply
        import jax.numpy as jnp
        act = self._act

        def impl(x, edges, w, *b):
            bsz, n, d = x.shape
            par = edges[..., 0].astype(jnp.int32)
            chi = edges[..., 1].astype(jnp.int32)
            adj = jnp.zeros((bsz, n, n), x.dtype)
            bidx = jnp.arange(bsz)[:, None]
            down = adj.at[bidx, par, chi].set(1.0)   # parent → child
            up = adj.at[bidx, chi, par].set(1.0)     # child → parent
            self_t = jnp.einsum("bnd,do->bno", x, w[0])
            child_t = jnp.einsum("bnm,bmd,do->bno", down, x, w[1])
            parent_t = jnp.einsum("bnm,bmd,do->bno", up, x, w[2])
            out = self_t + child_t + parent_t
            if b:
                out = out + b[0]
            return out.reshape(bsz, n, -1, self.num_filters) \
                if self.num_filters > 1 else out

        args = (nodes_vector, edge_set, self.weight)
        if self.bias is not None:
            args = args + (self.bias,)
        out = _apply(impl, args, name="tree_conv")
        if act:
            out = getattr(F, act)(out) if hasattr(F, act) else \
                getattr(ops, act)(out)
        return out


class HSigmoid(Layer):
    """Hierarchical sigmoid (reference: dygraph/nn.py HSigmoid over
    hierarchical_sigmoid_op.cc). Default complete-binary-tree code book:
    class c's path is the ancestor chain of leaf c in a complete binary
    tree over num_classes leaves — path nodes and left/right codes come
    straight from the bits of (c + num_classes), so no Huffman tables are
    materialized. loss[i] = -Σ_d log σ((1-2·code_d)·(x_i·w_{node_d}+b))."""

    def __init__(self, feature_size, num_classes, param_attr=None,
                 bias_attr=None, is_custom=False, is_sparse=False,
                 dtype="float32"):
        super().__init__(dtype=dtype)
        if num_classes < 2:
            raise ValueError("num_classes must be >= 2")
        if is_custom or is_sparse:
            raise NotImplementedError(
                "HSigmoid is_custom/is_sparse trees are not supported; "
                "the default complete-binary-tree code book covers the "
                "reference's non-custom path")
        self._C = int(num_classes)
        self._depth = max(1, int(np.ceil(np.log2(self._C))))
        # (num_classes - 1, feature): one row per INTERNAL tree node,
        # matching the reference's parameter shape
        self.weight = self.create_parameter(
            (self._C - 1, feature_size), attr=param_attr,
            default_initializer=I.Normal(0.0, 1.0 / np.sqrt(feature_size)))
        self.bias = self.create_parameter((self._C - 1,), attr=bias_attr,
                                          is_bias=True)

    def forward(self, input, label):
        from ..dispatch import apply
        import jax
        import jax.numpy as jnp
        C, D = self._C, self._depth

        has_bias = self.bias is not None

        def impl(x, w, *rest):
            b = rest[0] if has_bias else jnp.zeros((C - 1,), x.dtype)
            lab = rest[-1]
            lab = lab.reshape(-1).astype(jnp.int32)
            # heap index of leaf `c` in a complete binary tree is c + C;
            # its ancestors c>>1 ... are the internal nodes (1..C-1)
            node = lab + C
            loss = jnp.zeros((x.shape[0],), jnp.float32)
            for _ in range(D):
                code = node & 1          # 1 = right child
                parent = node >> 1
                # internal node k (1..C-1) lives in weight row k-1
                idx = jnp.clip(parent, 1, C - 1) - 1
                logit = jnp.einsum("bd,bd->b", x, w[idx]) + b[idx]
                sign = 1.0 - 2.0 * code.astype(jnp.float32)
                valid = parent >= 1
                term = jax.nn.softplus(-sign * logit)
                loss = loss + jnp.where(valid, term, 0.0)
                node = parent
            return loss[:, None]

        args = (input, self.weight) + \
            ((self.bias,) if has_bias else ()) + (label,)
        return apply(impl, args, name="hsigmoid")
