"""Fused softmax + cross-entropy Pallas kernel (reference: the fused CUDA
softmax_with_cross_entropy_op.cu).

Forward: one VMEM pass per row-block — row max, exp-sum, and the picked
logit produce the loss directly; the [N, V] softmax matrix is never
materialized in HBM. The per-row lse is saved as a residual, which makes
the backward purely elementwise (dx = (exp(x − lse) − target)·g): it
tiles over BOTH rows and vocab, so no kernel ever holds a full-width row
block in VMEM (the full-width variant blew the 16MB scoped-VMEM limit at
BERT shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_rows(v):
    target = 1 << 20
    br = max(8, min(512, target // max(v, 1)))
    return int(8 * max(1, br // 8))


def _fwd_kernel(logits_ref, labels_ref, loss_ref, lse_ref, *, v, eps):
    """eps>0 = uniform label smoothing folded into the same pass
    (reference: label_smooth + the soft path of
    softmax_with_cross_entropy_op, without materializing the (N, V)
    smoothed one-hot): loss = lse − (1−eps)·picked − (eps/V)·Σx.

    Also emits the per-row lse as a residual: with it, the backward pass
    is purely elementwise (p = exp(x − lse)), so it tiles over BOTH rows
    and vocab instead of holding whole 30k-wide rows in VMEM (which blew
    the 16MB scoped-VMEM limit at BERT shapes)."""
    x = logits_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    lse = jnp.log(jnp.sum(e, axis=1, keepdims=True)) + m
    labels = labels_ref[:]
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = cols == labels
    picked = jnp.sum(jnp.where(onehot, x, 0.0), axis=1, keepdims=True)
    if eps:
        loss_ref[:] = (lse - (1.0 - eps) * picked -
                       (eps / v) * jnp.sum(x, axis=1, keepdims=True))
    else:
        loss_ref[:] = (lse - picked)
    lse_ref[:] = lse


def _bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dx_ref, *, v, eps,
                bv):
    """Elementwise given the forward's lse: dx = (exp(x−lse) − target)·g.
    Grid is (row-blocks, vocab-blocks); each block sees only a (br, bv)
    logits tile, so VMEM stays bounded for any vocab size."""
    j = pl.program_id(1)
    x = logits_ref[:].astype(jnp.float32)
    p = jnp.exp(x - lse_ref[:])
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == labels_ref[:]).astype(jnp.float32)
    if eps:
        target = (1.0 - eps) * onehot + (eps / v)
    else:
        target = onehot
    valid = (cols < v).astype(jnp.float32)  # vocab-tail padding → 0
    dx_ref[:] = ((p - target) * g_ref[:] * valid).astype(dx_ref.dtype)


def _run_fwd(logits2, labels2, eps):
    from . import interpret_mode
    n, v = logits2.shape
    br = _block_rows(v)
    grid = (pl.cdiv(n, br),)
    narrow = pl.BlockSpec((br, 1), lambda i: (i, 0),
                          memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, v=v, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, v), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            narrow,
        ],
        out_specs=(narrow, narrow),
        out_shape=(jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)),
        interpret=interpret_mode(),
        name="softmax_xent_fwd",
    )(logits2, labels2)


def _run_bwd(logits2, labels2, lse, g, eps):
    from . import interpret_mode
    n, v = logits2.shape
    bv = min(v, 2048)
    # 128×2048 f32 = 1MB tiles: in+out double-buffered plus ~4 stack
    # temps ≈ 8MB — half the scoped-VMEM limit (the 2MB-tile variant
    # also passed on hardware, but with zero headroom)
    br = max(8, min(128, _block_rows(bv)))
    grid = (pl.cdiv(n, br), pl.cdiv(v, bv))
    narrow = pl.BlockSpec((br, 1), lambda i, j: (i, 0),
                          memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, v=v, eps=eps, bv=bv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            narrow, narrow, narrow,
        ],
        out_specs=pl.BlockSpec((br, bv), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, v), logits2.dtype),
        interpret=interpret_mode(),
        name="softmax_xent_bwd",
    )(logits2, labels2, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_xent2(logits2, labels2, eps=0.0):
    return _run_fwd(logits2, labels2, eps)[0]


def _fwd(logits2, labels2, eps):
    loss, lse = _run_fwd(logits2, labels2, eps)
    return loss, (logits2, labels2, lse)


def _bwd(eps, res, g):
    logits2, labels2, lse = res
    dx = _run_bwd(logits2, labels2, lse, g.astype(jnp.float32), eps)
    return dx, None


_softmax_xent2.defvjp(_fwd, _bwd)


def softmax_cross_entropy(logits, label, smooth_eps=0.0):
    """Framework op: fused per-position softmax cross-entropy with hard
    labels; returns loss with shape label.shape + (1,). smooth_eps>0 folds
    uniform label smoothing into the kernel (reference: label_smooth +
    softmax_with_cross_entropy(soft_label=True), without the (N, V)
    smoothed one-hot ever touching HBM)."""
    from ...dispatch import apply

    def impl(logits, label):
        v = logits.shape[-1]
        lead = logits.shape[:-1]
        l2 = logits.reshape(-1, v)
        lab2 = label.reshape(-1, 1).astype(jnp.int32)
        loss = _softmax_xent2(l2, lab2, float(smooth_eps))
        return loss.reshape(*lead, 1)

    return apply(impl, (logits, label), name="pallas_softmax_xent")
