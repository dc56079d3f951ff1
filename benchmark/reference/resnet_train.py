"""Plain reference for ResNet training: float32 ``jax.numpy``/``lax``, no
kernels, nothing of paddle_tpu.

Model: He et al., arXiv:1512.03385, table 1 and section 3.4 — a 7x7/2
convolution, batch norm, ReLU, 3x3/2 max-pool; four stages of bottleneck
blocks (1x1, 3x3, 1x1 with batch norm after each, ReLU after the first
two, a projection shortcut where the shape changes, ReLU after the add);
global average pool; a 1000-way classifier. Batch norm (Ioffe & Szegedy,
arXiv:1502.03167, algorithm 1) normalises with the statistics of the
batch, biased variance. One departure from table 1, noted in the
configuration: a stage's stride sits on the block's 3x3 convolution, not
its first 1x1 (the fb.resnet.torch variant, which the served model is).
Initialisation: He et al., arXiv:1502.01852, normal with variance 2 /
fan-out for convolutions; unit scales, zero shifts; classifier normal with
standard deviation 0.01, zero bias.

Input is uint8, normalised on the device as the user's step does:
(x / 255 - 0.45) / 0.22. Loss: mean cross entropy. Optimizer: SGD with
momentum (Sutskever et al., 2013, in the form v <- mu v + g; p <- p - lr v).

Batch norm couples the rows of a batch, so the batch is not walked in
blocks; each bottleneck block is recomputed in the backward pass instead.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from .common import bilinear, follow, leaf_norms, matrix_leaves, seed_key


def _blocks(cfg):
    """(name prefix, in channels, mid channels, stride, has projection)."""
    out, in_ch = [], cfg["base_width"]
    for s, n in enumerate(cfg["depths"]):
        mid = cfg["base_width"] * 2 ** s
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            out.append((f"layers.{s}.{b}.", in_ch, mid, stride,
                        b == 0))
            in_ch = mid * cfg["expansion"]
    return out


def param_shapes(cfg):
    w = cfg["base_width"]
    shapes = {"stem.0.weight": (w, cfg["in_channels"], 7, 7),
              "stem.1.weight": (w,), "stem.1.bias": (w,)}
    for p, in_ch, mid, _, proj in _blocks(cfg):
        out_ch = mid * cfg["expansion"]
        shapes.update({
            p + "conv0.weight": (mid, in_ch, 1, 1),
            p + "bn0.weight": (mid,), p + "bn0.bias": (mid,),
            p + "conv1.weight": (mid, mid, 3, 3),
            p + "bn1.weight": (mid,), p + "bn1.bias": (mid,),
            p + "conv2.weight": (out_ch, mid, 1, 1),
            p + "bn2.weight": (out_ch,), p + "bn2.bias": (out_ch,),
        })
        if proj:
            shapes.update({
                p + "downsample.0.weight": (out_ch, in_ch, 1, 1),
                p + "downsample.1.weight": (out_ch,),
                p + "downsample.1.bias": (out_ch,)})
    feat = cfg["base_width"] * 8 * cfg["expansion"]
    shapes["fc.weight"] = (feat, cfg["num_classes"])
    shapes["fc.bias"] = (cfg["num_classes"],)
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call."""
    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if len(shape) == 4:
                std = math.sqrt(2.0 / (shape[0] * shape[2] * shape[3]))
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif name == "fc.weight":
                out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith(".weight"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def _batch_norm(x, g, b, eps):
    mean = jnp.mean(x, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), (0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g[None, :, None, None] \
        + b[None, :, None, None]


def forward(cfg, p, x_u8, precision="float32"):
    """Class logits [B, num_classes] of a uint8 batch [B, 3, H, W]."""
    eps = cfg["batch_norm_eps"]

    def conv(x, w, stride, pad):
        return bilinear(lambda a, b: lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW")), precision)(x, w)

    def bn(x, name):
        return _batch_norm(x, p[name + ".weight"], p[name + ".bias"], eps)

    @jax.checkpoint
    def stem(x):
        x = (x.astype(jnp.float32) / 255.0 - 0.45) / 0.22
        x = jax.nn.relu(bn(conv(x, p["stem.0.weight"], 2, 3), "stem.1"))
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                                 (1, 1, 2, 2),
                                 [(0, 0), (0, 0), (1, 1), (1, 1)])

    def block(n, stride, proj):
        @jax.checkpoint
        def run(x):
            y = jax.nn.relu(bn(conv(x, p[n + "conv0.weight"], 1, 0),
                               n + "bn0"))
            y = jax.nn.relu(bn(conv(y, p[n + "conv1.weight"], stride, 1),
                               n + "bn1"))
            y = bn(conv(y, p[n + "conv2.weight"], 1, 0), n + "bn2")
            if proj:
                x = bn(conv(x, p[n + "downsample.0.weight"], stride, 0),
                       n + "downsample.1")
            return jax.nn.relu(y + x)
        return run

    x = stem(x_u8)
    for n, _, _, stride, proj in _blocks(cfg):
        x = block(n, stride, proj)(x)
    x = jnp.mean(x, (2, 3))
    fc = bilinear(lambda a, b: a @ b, precision)
    return fc(x, p["fc.weight"]) + p["fc.bias"]


def loss_fn(cfg, p, batch, precision="float32"):
    x, y = batch
    logits = forward(cfg, p, x, precision)
    return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, y[:, None], -1)[:, 0])


def train(cfg, hyper, seed, batches, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from the seed's weights; see
    ``common.follow`` for what comes back."""
    lr, mu = hyper["learning_rate"], hyper["momentum"]

    def step(p, slots, t, batch):
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(cfg, q, batch, precision))(p)
        v = {k: mu * slots[0][k] + g[k] for k in p}
        return {k: p[k] - lr * v[k] for k in p}, (v,), loss, leaf_norms(g)

    with jax.default_matmul_precision("highest"):
        return follow(init_weights(cfg, seed), 1, step, batches)
