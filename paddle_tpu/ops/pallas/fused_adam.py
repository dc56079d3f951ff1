"""Fused Adam Pallas kernel (reference: adam_op.cu — the fused/multi-tensor
update path FusedAdamKernel).

One kernel updates param, m, v in place (input_output_aliases) per tensor:
param/m/v stream HBM→VMEM once each and back once, with the whole update
arithmetic fused — matching what the reference needed a dedicated CUDA
kernel for. Scalars (lr, beta-pows) ride in SMEM.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref,
                 vo_ref, *, beta1, beta2, eps):
    lr = scal_ref[0]
    b1p = scal_ref[1]
    b2p = scal_ref[2]
    g = g_ref[:].astype(jnp.float32)
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    mhat = m / (1.0 - b1p)
    vhat = v / (1.0 - b2p)
    po_ref[:] = (p_ref[:].astype(jnp.float32) -
                 lr * mhat / (jnp.sqrt(vhat) + eps)).astype(po_ref.dtype)
    mo_ref[:] = m
    vo_ref[:] = v


def fused_adam_update(p, g, m, v, lr, beta1_pow, beta2_pow, beta1=0.9,
                      beta2=0.999, eps=1e-8):
    """Single-tensor fused update: returns (new_p, new_m, new_v).
    Called by optimizer.Adam when use_fused=True (arrays already flat or
    any-shaped; kernel sees a flattened 2D view)."""
    from . import interpret_mode
    shape = p.shape
    n = int(np.prod(shape)) if shape else 1
    # pad to a (rows, 128) layout
    cols = 128
    rows = -(-n // cols)
    pad = rows * cols - n

    def flat(x, dtype=jnp.float32):
        x = x.reshape(-1).astype(dtype)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), dtype)])
        return x.reshape(rows, cols)

    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(beta1_pow, jnp.float32),
                      jnp.asarray(beta2_pow, jnp.float32)])

    # 7 VMEM refs (4 in + 3 out) × br×128×4B × 2 (double-buffer) must stay
    # under the ~16MB scoped-VMEM limit: br=1024 → 7MB. 4096 OOMs on v5e.
    br = min(rows, 1024)
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2, eps=eps),
        grid=(pl.cdiv(rows, br),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), p.dtype),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        ],
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret_mode(),
        name="fused_adam",
    )(scal, flat(p, p.dtype), flat(g), flat(m), flat(v))

    def unflat(x, dtype):
        x = x.reshape(-1)[:n].reshape(shape)
        return x.astype(dtype)

    return (unflat(new_p, p.dtype), unflat(new_m, jnp.float32),
            unflat(new_v, jnp.float32))


def _adam_multi_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, po_ref,
                       mo_ref, vo_ref, *, beta1, beta2, eps):
    lr = scal_ref[0]
    b1p = scal_ref[1]
    b2p = scal_ref[2]
    wd = scal_ref[3]
    g = g_ref[:]
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    mhat = m / (1.0 - b1p)
    vhat = v / (1.0 - b2p)
    p = p_ref[:]
    po_ref[:] = p - lr * mhat / (jnp.sqrt(vhat) + eps) - (lr * wd) * p
    mo_ref[:] = m
    vo_ref[:] = v


def fused_adam_update_multi(ps, gs, ms, vs, lr, beta1_pow, beta2_pow,
                            beta1=0.9, beta2=0.999, eps=1e-8,
                            weight_decay=0.0):
    """Multi-tensor fused update (reference: adam_op.cu's multi-tensor
    FusedAdamKernel intent): ONE Pallas dispatch over every parameter,
    via flattened+concatenated f32 buffers, instead of one dispatch per
    tensor. Decoupled weight decay (AdamW) folds into the same pass.

    Layout note: the concat offsets are python-side values derived from
    static shapes, so they are "built once per trace" — jit.to_static's
    structure-version cache already guarantees a retrace (and thus a
    new layout) only when the param set changes.

    Semantics note: beta-pow bias correction is SHARED across tensors
    (the reference's multi-tensor kernel also carries one beta1_pow/
    beta2_pow). Identical to per-tensor updates whenever all params
    step together — the SPMD/jit training reality; per-tensor pows that
    diverged via selective freezing are not representable here.

    Returns (new_ps, new_ms, new_vs) with original shapes/dtypes."""
    from . import interpret_mode
    cols = 128
    sizes = [int(np.prod(p.shape)) if p.shape else 1 for p in ps]
    rows_each = [-(-n // cols) for n in sizes]  # per-tensor row padding
    offsets = np.cumsum([0] + rows_each)
    rows = int(offsets[-1])

    def flat_cat(xs, dtype=jnp.float32):
        parts = []
        for x, n, r in zip(xs, sizes, rows_each):
            x = x.reshape(-1).astype(dtype)
            pad = r * cols - n
            if pad:
                x = jnp.concatenate([x, jnp.zeros((pad,), dtype)])
            parts.append(x.reshape(r, cols))
        return jnp.concatenate(parts, axis=0)

    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(beta1_pow, jnp.float32),
                      jnp.asarray(beta2_pow, jnp.float32),
                      jnp.asarray(weight_decay, jnp.float32)])

    br = min(rows, 1024)  # same scoped-VMEM budget as the single path
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_adam_multi_kernel, beta1=beta1, beta2=beta2,
                          eps=eps),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)] * 4,
        out_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, cols), jnp.float32)] * 3,
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret_mode(),
        name="fused_adam_multi",
    )(scal, flat_cat(ps), flat_cat(gs), flat_cat(ms), flat_cat(vs))

    def split(buf, refs, dtype_from=None):
        outs = []
        for i, (n, x) in enumerate(zip(sizes, refs)):
            seg = buf[offsets[i]:offsets[i + 1]].reshape(-1)[:n]
            outs.append(seg.reshape(x.shape).astype(
                x.dtype if dtype_from else jnp.float32))
        return outs

    return (split(new_p, ps, dtype_from=True), split(new_m, ms),
            split(new_v, vs))


def adam_step(p, g, m, v, lr, beta1_pow, beta2_pow, *, beta1=0.9,
              beta2=0.999, eps=1e-8, use_fused=None):
    """THE Adam update rule, shared by optimizer.Adam and the fleet/
    megatron SPMD step: the fused Pallas kernel when pallas.enabled
    ('fused_adam') (or use_fused forces it), else the identical plain-XLA
    math. Returns (new_p, new_m, new_v)."""
    if use_fused is None:
        from . import enabled
        use_fused = enabled("fused_adam")
    if use_fused:
        return fused_adam_update(p, g, m, v, lr, beta1_pow, beta2_pow,
                                 beta1=beta1, beta2=beta2, eps=eps)
    new_m = beta1 * m + (1 - beta1) * g
    new_v = beta2 * v + (1 - beta2) * g * g
    mhat = new_m / (1 - beta1_pow)
    vhat = new_v / (1 - beta2_pow)
    # cast back to the param dtype: the f32 strong-typed lr would
    # otherwise silently promote a bf16 param to f32 after one step
    # (dtype drift = a state-shape recompile); the fused kernel above
    # already preserves it via unflat
    new_p = (p - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
    return new_p, new_m, new_v


def fused_adam_update_flat(p, g, m, v, lr, beta1_pow, beta2_pow,
                           beta1=0.9, beta2=0.999, eps=1e-8,
                           weight_decay=0.0):
    """Fused kernel over an arena-flat 1-D buffer. The arena pads every
    group to an (8, 128)-tile multiple, so the (rows, 128) kernel view
    is a FREE reshape — no pad, no concat, unlike the multi-tensor path
    that rebuilds its concatenated layout every call."""
    from . import interpret_mode
    n = p.shape[0]
    cols = 128
    assert n % cols == 0, "arena buffers are 128-lane aligned"
    rows = n // cols

    def tile(x):
        return x.astype(jnp.float32).reshape(rows, cols)

    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(beta1_pow, jnp.float32),
                      jnp.asarray(beta2_pow, jnp.float32),
                      jnp.asarray(weight_decay, jnp.float32)])
    br = min(rows, 1024)  # same scoped-VMEM budget as the multi path
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_adam_multi_kernel, beta1=beta1, beta2=beta2,
                          eps=eps),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [
            pl.BlockSpec((br, cols), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)] * 4,
        out_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, cols), jnp.float32)] * 3,
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret_mode(),
        name="fused_adam_flat",
    )(scal, tile(p), tile(g), tile(m), tile(v))
    return (new_p.reshape(-1).astype(p.dtype),
            new_m.reshape(-1).astype(m.dtype),
            new_v.reshape(-1).astype(v.dtype))


def adam_step_flat(p, g, m, v, lr, beta1_pow, beta2_pow, *, beta1=0.9,
                   beta2=0.999, eps=1e-8, weight_decay=0.0, mask=None,
                   use_fused=None):
    """The Adam/AdamW update over arena-flat 1-D buffers — the same
    dispatch discipline as :func:`adam_step` (Pallas kernel when
    'fused_adam_multi' is enabled or ``use_fused`` forces it, identical
    plain-XLA math otherwise). The pure path's cast sequencing matches
    the per-leaf rule exactly — ``astype(p.dtype)`` after the adam term
    and again after the decoupled decay — so arena mode is bit-identical
    per element to the per-leaf update it replaces. ``mask`` (bool [n])
    freezes elements of members that produced no grad this step."""
    if use_fused is None:
        from . import enabled
        use_fused = enabled("fused_adam_multi")
    if use_fused and mask is None and p.dtype == jnp.float32:
        new_p, new_m, new_v = fused_adam_update_flat(
            p, g, m, v, lr, beta1_pow, beta2_pow, beta1=beta1,
            beta2=beta2, eps=eps, weight_decay=weight_decay)
        return new_p, new_m, new_v
    new_m = beta1 * m + (1 - beta1) * g
    new_v = beta2 * v + (1 - beta2) * g * g
    mhat = new_m / (1 - beta1_pow)
    vhat = new_v / (1 - beta2_pow)
    new_p = (p - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
    if weight_decay:
        new_p = (new_p - lr * weight_decay * p).astype(p.dtype)
    if mask is not None:
        new_p = jnp.where(mask, new_p, p)
        new_m = jnp.where(mask, new_m, m)
        new_v = jnp.where(mask, new_v, v)
    return new_p, new_m, new_v

