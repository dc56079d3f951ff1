"""The Mamba mixer's gated grouped RMS norm (``F.rms_norm(gate=z,
num_groups=G)``) as a Pallas kernel pair over tiles of rows x one group.

The numbers are ``ops/nn_ops.py: _rms_norm``'s: ``h = y * silu(z)``,
each group's ``rsqrt(mean(h^2) + epsilon)``, the weight, all in float32,
rounded to the call's dtype once, on the way out. What differs is the
layout. ``_rms_norm`` reshapes ``[.., D]`` to ``[.., G, D/G]``
to take a group's mean, which on a TPU puts the group count on the
sublanes: every (8 x 128) tile changes owner, through HBM, on the way in
and on the way back. Here a group is a window of lanes: grid (batch,
group, row tile), a program reads (rows x D/G) of ``y`` and ``z`` out of
the ``[B, S, D]`` arrays the mixer holds, in their own dtype, and the
group's statistic is a column (rows x 1) in VMEM.

The backward kernel reads ``y``, ``z`` and ``dout``, recomputes ``h``
and the statistic, writes ``dy`` and ``dz`` in the inputs' dtype and adds
up the weight's gradient in float32 in an output block that stays in
VMEM while the row axis, the grid's last and sequential, runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_ROW_TILES = (512, 256, 128)
_TILE_ELEMENTS = 512 * 512      # rows x lanes of a program's tile, at most


def _row_tile(s, lanes):
    return next((t for t in _ROW_TILES
                 if s % t == 0 and t * lanes <= _TILE_ELEMENTS), None)


def supported(x_shape, num_groups):
    """Whether the kernels' tiles fit ``x`` [B, S, D] in ``num_groups``
    groups: a group's lanes whole 128-lane tiles, whole row tiles (S a
    multiple of 128), and a tile of 128 rows of one group no larger than
    the kernels' budget (D/G up to 2,048)."""
    if len(x_shape) != 3 or x_shape[2] % num_groups:
        return False
    lanes = x_shape[2] // num_groups
    return lanes % 128 == 0 and _row_tile(x_shape[1], lanes) is not None


def _gate(y_ref, z_ref):
    """``h = y silu(z)`` in float32, with what its gradient needs."""
    y, z = y_ref[0].astype(_F32), z_ref[0].astype(_F32)
    sig = 1.0 / (1.0 + jnp.exp(-z))
    return y, z, sig, y * (z * sig)


def _fwd_kernel(y_ref, z_ref, w_ref, o_ref, *, epsilon):
    _, _, _, h = _gate(y_ref, z_ref)
    r = jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + epsilon)
    o_ref[0] = (h * r * w_ref[...]).astype(o_ref.dtype)


def _bwd_kernel(y_ref, z_ref, w_ref, g_ref, dy_ref, dz_ref, dw_ref, *,
                epsilon):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    y, z, sig, h = _gate(y_ref, z_ref)
    r = jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + epsilon)
    n = h * r
    g = g_ref[0].astype(_F32)
    dw_ref[0] += jnp.sum(g * n, 0, keepdims=True)
    dn = g * w_ref[...]
    dh = r * (dn - n * jnp.mean(dn * n, -1, keepdims=True))
    silu = z * sig
    dy_ref[0] = (dh * silu).astype(dy_ref.dtype)
    dz_ref[0] = (dh * y * (sig * (1.0 + z * (1.0 - sig)))).astype(
        dz_ref.dtype)


_vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(y, groups):
    bsz, s, d = y.shape
    lanes = d // groups
    ts = _row_tile(s, lanes)
    return ((bsz, groups, s // ts),
            _vmem((1, ts, lanes), lambda b, g, k: (b, k, g)),
            _vmem((1, lanes), lambda b, g, k: (0, g)))


def _forward(y, z, w, groups, epsilon):
    from . import interpret_mode
    grid, tile, w_spec = _specs(y, groups)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, epsilon=epsilon),
        grid=grid,
        in_specs=[tile, tile, w_spec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="gated_norm_fwd",
    )(y, z, w)


def _backward(y, z, w, g, groups, epsilon):
    from . import interpret_mode
    grid, tile, w_spec = _specs(y, groups)
    lanes = tile.block_shape[2]
    dy, dz, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, epsilon=epsilon),
        grid=grid,
        in_specs=[tile, tile, w_spec, tile],
        out_specs=[tile, tile,
                   _vmem((1, 1, lanes), lambda b, g, k: (b, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((y.shape[0], 1, y.shape[2]), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="gated_norm_bwd",
    )(y, z, w, g)
    return dy, dz, jnp.sum(dw, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm(y, z, w, groups, epsilon):
    return _forward(y, z, w, groups, epsilon)


def _norm_fwd(y, z, w, groups, epsilon):
    return _forward(y, z, w, groups, epsilon), (y, z, w)


def _norm_bwd(groups, epsilon, res, g):
    return _backward(*res, g, groups, epsilon)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_rms_norm(x, gate, *weight, epsilon, num_groups):
    """``ops/nn_ops.py: _rms_norm`` with a gate, through the kernels;
    same result. The shapes have to be ``supported``."""
    w = weight[0].astype(_F32) if weight else jnp.ones(x.shape[-1:], _F32)
    return _norm(x, gate, w[None], num_groups, float(epsilon))
