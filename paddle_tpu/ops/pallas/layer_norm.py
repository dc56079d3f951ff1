"""Fused layer-norm Pallas kernel (reference: the fused CUDA
layer_norm_op.cu — one pass computing mean/var/normalize/affine).

Forward: grid over row-blocks; each block loads (BR, D) into VMEM, computes
row statistics on the VPU and writes the normalized affine output — one HBM
round-trip instead of the 4+ an unfused chain costs. Backward is a second
kernel producing dx exactly (the classic layernorm gradient) plus per-block
partial dw/db that are summed outside.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_rows(d, target=1 << 19):
    br = max(8, min(1024, target // max(d, 1)))
    return int(8 * max(1, br // 8))


# The bwd kernel holds 3 (BR, D) blocks double-buffered PLUS ~4 f32
# stack temporaries (x, g, xhat, dxhat); at 512K-element blocks that
# sits right at the 16MB scoped-VMEM edge — bf16 inputs fit, f32 inputs
# blew it on hardware at (8192, 768). 256K-element blocks (1MB f32)
# keep the worst case near ~10MB.
_BWD_TARGET = 1 << 18


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, mu_ref, rstd_ref, *, eps, d):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    o_ref[:] = (xhat * w_ref[:].astype(jnp.float32) +
                b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mu_ref[:] = mu[:, 0][:, None]
    rstd_ref[:] = rstd[:, 0][:, None]


def _bwd_kernel(x_ref, w_ref, mu_ref, rstd_ref, g_ref, dx_ref, dw_ref,
                db_ref, *, d, n, br):
    # mask rows past n: the padding of a partial final block must not
    # poison the dw/db partial sums (OOB reads are NaN in interpret mode)
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0) + i * br
    valid = rows < n
    x = jnp.where(valid, x_ref[:].astype(jnp.float32), 0.0)
    g = jnp.where(valid, g_ref[:].astype(jnp.float32), 0.0)
    w = w_ref[:].astype(jnp.float32)
    mu = jnp.where(valid, mu_ref[:], 0.0)
    rstd = jnp.where(valid, rstd_ref[:], 0.0)
    xhat = (x - mu) * rstd
    dxhat = g * w
    m1 = jnp.mean(dxhat, axis=1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    dx_ref[:] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)
    # dw/db accumulate into ONE (1, d) output block revisited by every
    # grid step — TPU grids run sequentially, so += is a sound reduction
    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)
    dw_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(g, axis=0, keepdims=True)


def _run_fwd(x2, w, b, eps):
    from . import interpret_mode
    n, d = x2.shape
    br = _block_rows(d)
    grid = (pl.cdiv(n, br),)
    out, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="layer_norm_fwd",
    )(x2, w.reshape(1, d), b.reshape(1, d))
    return out, mu, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm2(x2, w, b, eps):
    out, _, _ = _run_fwd(x2, w, b, eps)
    return out


def _ln_fwd(x2, w, b, eps):
    out, mu, rstd = _run_fwd(x2, w, b, eps)
    return out, (x2, w, mu, rstd)


def _ln_bwd(eps, res, g):
    from . import interpret_mode
    x2, w, mu, rstd = res
    n, d = x2.shape
    br = _block_rows(d, _BWD_TARGET)
    nblocks = pl.cdiv(n, br)
    dx, dw_part, db_part = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, n=n, br=br),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((br, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="layer_norm_bwd",
    )(x2, w.reshape(1, d), mu, rstd, g)
    dw = dw_part[0].astype(w.dtype)
    db = db_part[0].astype(w.dtype)
    return dx, dw, db


_layer_norm2.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, weight, bias, epsilon=1e-5):
    """Framework op: fused layer norm over the LAST axis. Accepts Tensors
    or arrays; differentiable through the tape and under jit."""
    from ...dispatch import apply

    def impl(x, w, b):
        d = x.shape[-1]
        lead = x.shape[:-1]
        x2 = x.reshape(-1, d)
        out = _layer_norm2(x2, w, b, epsilon)
        return out.reshape(*lead, d)

    return apply(impl, (x, weight, bias), name="pallas_layer_norm")
