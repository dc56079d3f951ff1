"""Fused training-mode batch-norm Pallas kernels (reference: the fused
CUDA batch_norm_op.cu / sync_batch_norm_op.cu pair; here the single-chip
training path).

Channels-LAST only: x viewed as (M, C) rows with C on the lanes — the
natural layout for NHWC conv stacks, where the (N,H,W,C)→(M,C) view is
free. NCHW callers keep the XLA path (a transpose around the kernel would
cost the very HBM pass this kernel exists to save).

Pass structure (the HBM-traffic floor for batch norm):
  fwd: stats kernel reads x once, accumulating per-channel Σx and Σx² in
       f32 into (1, C) outputs revisited across the sequential TPU grid;
       normalize kernel reads x once more and writes y = x·scale + shift
       with the (1, C) scale/shift staged in VMEM.
  bwd: reduction kernel reads (x, g) once for dgamma = Σ g·x̂ and
       dbeta = Σ g; elementwise kernel reads (x, g) again and writes
       dx = (w·rstd)·(g − dbeta/M − x̂·dgamma/M).

Five array passes total — the same count a perfectly-fused XLA schedule
needs, but with the f32 converts, squares and x̂ recomputation kept in
registers instead of round-tripping f32 copies through HBM (the
`convert_reduce_fusion` cost the ResNet-50 trace showed at ~8 ms/step).

Default-OFF (`pallas.configure(batch_norm=True)` opts in): the lesson of
the Adam kernels (they lost to XLA's own fusion and were removed, PERF.md
section 6, PR 29) is that hand-written kernels must beat the compiler on
the chip before they ride the default path; scripts/bench_pallas_bn.py
measures exactly that when a chip window is available.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_rows(c, target=1 << 18):
    br = max(8, min(1024, target // max(c, 1)))
    return int(8 * max(1, br // 8))


def _stats_kernel(x_ref, c_ref, s_ref, s2_ref, *, m, br):
    """Accumulates Σ(x−c) and Σ(x−c)² with c = a per-channel sample
    (the same cancellation guard as the XLA path in nn_ops.batch_norm:
    raw Σx² at large mean loses the entire variance to f32 rounding;
    shifted, both accumulators stay O(σ²)-scaled)."""
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0) + i * br
    valid = rows < m  # padding rows of the final block must not pollute
    x = jnp.where(valid, x_ref[:].astype(jnp.float32) - c_ref[:], 0.0)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    s_ref[:] += jnp.sum(x, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(x * x, axis=0, keepdims=True)


def _norm_kernel(x_ref, scale_ref, shift_ref, o_ref):
    o_ref[:] = (x_ref[:].astype(jnp.float32) * scale_ref[:] +
                shift_ref[:]).astype(o_ref.dtype)


def _bwd_reduce_kernel(x_ref, g_ref, mean_ref, rstd_ref, dg_ref, db_ref,
                       *, m, br):
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0) + i * br
    valid = rows < m
    x = jnp.where(valid, x_ref[:].astype(jnp.float32), 0.0)
    g = jnp.where(valid, g_ref[:].astype(jnp.float32), 0.0)
    xhat = (x - mean_ref[:]) * rstd_ref[:]

    @pl.when(i == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dg_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(g, axis=0, keepdims=True)


def _bwd_dx_kernel(x_ref, g_ref, mean_ref, rstd_ref, wr_ref, dgm_ref,
                   dbm_ref, gmv_ref, dx_ref):
    """dx = (w·rstd)·(g − dbeta/M − x̂·dgamma/M) + gm/M + (2/M)(x−mean)gv.
    dgm/dbm arrive pre-divided by M; gmv carries the (rarely nonzero)
    cotangents of the direct mean/var outputs, pre-scaled (gm/M stacked
    over 2gv/M), so consuming batch stats in a loss stays exact."""
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    xc = x - mean_ref[:]
    xhat = xc * rstd_ref[:]
    extra = gmv_ref[0:1, :] + xc * gmv_ref[1:2, :]
    dx_ref[:] = (wr_ref[:] * (g - dbm_ref[:] - xhat * dgm_ref[:]) + extra
                 ).astype(dx_ref.dtype)


def _row_specs(br, c, n_narrow):
    wide = pl.BlockSpec((br, c), lambda i: (i, 0), memory_space=pltpu.VMEM)
    narrow = pl.BlockSpec((1, c), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    return wide, [narrow] * n_narrow


def _stats(x2):
    from . import interpret_mode
    m, c = x2.shape
    br = _block_rows(c)
    wide, narrows = _row_specs(br, c, 1)
    narrow_out = pl.BlockSpec((1, c), lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    shift = jax.lax.stop_gradient(x2[0:1].astype(jnp.float32))
    s, s2 = pl.pallas_call(
        functools.partial(_stats_kernel, m=m, br=br),
        grid=(pl.cdiv(m, br),),
        in_specs=[wide] + narrows,
        out_specs=(narrow_out, narrow_out),
        out_shape=(jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        interpret=interpret_mode(),
        name="batch_norm_stats",
    )(x2, shift)
    m_s = s / m
    mean = m_s + shift
    var = jnp.maximum(s2 / m - jnp.square(m_s), 0.0)
    return mean, var


def _normalize(x2, scale, shift):
    from . import interpret_mode
    m, c = x2.shape
    br = _block_rows(c)
    wide, narrows = _row_specs(br, c, 2)
    return pl.pallas_call(
        _norm_kernel,
        grid=(pl.cdiv(m, br),),
        in_specs=[wide] + narrows,
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((m, c), x2.dtype),
        interpret=interpret_mode(),
        name="batch_norm_apply",
    )(x2, scale, shift)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _batch_norm2(x2, w, b, eps):
    """Returns (out, mean, var) — batch stats ride out of the same
    forward (the Layer's running-stat update consumes them), so no
    extra stats pass is ever taken."""
    out, mean, var, _ = _bn_fwd_res(x2, w, b, eps)
    return out, mean, var


def _bn_fwd_res(x2, w, b, eps):
    mean, var = _stats(x2)
    rstd = jax.lax.rsqrt(var + eps)
    wf = w.astype(jnp.float32).reshape(1, -1)
    scale = rstd * wf
    shift = b.astype(jnp.float32).reshape(1, -1) - mean * scale
    out = _normalize(x2, scale, shift)
    return out, mean, var, rstd


def _bn_fwd(x2, w, b, eps):
    out, mean, var, rstd = _bn_fwd_res(x2, w, b, eps)
    return (out, mean, var), (x2, w, mean, rstd)


def _bn_bwd(eps, res, gs):
    g, g_mean, g_var = gs
    x2, w, mean, rstd = res
    m, c = x2.shape
    br = _block_rows(c)
    wide, narrows = _row_specs(br, c, 2)
    narrow_out = pl.BlockSpec((1, c), lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    from . import interpret_mode
    dg, db = pl.pallas_call(
        functools.partial(_bwd_reduce_kernel, m=m, br=br),
        grid=(pl.cdiv(m, br),),
        in_specs=[wide, wide] + narrows,
        out_specs=(narrow_out, narrow_out),
        out_shape=(jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        interpret=interpret_mode(),
        name="batch_norm_bwd_reduce",
    )(x2, g, mean, rstd)
    wr = (w.astype(jnp.float32).reshape(1, -1) * rstd)
    # cotangents of the direct mean/var outputs, pre-scaled and stacked
    # into one (2, C) operand: row 0 = gm/M, row 1 = 2·gv/M
    gmv = jnp.concatenate([
        jnp.asarray(g_mean, jnp.float32).reshape(1, c) / m,
        2.0 * jnp.asarray(g_var, jnp.float32).reshape(1, c) / m,
    ], axis=0)
    gmv_spec = pl.BlockSpec((2, c), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    dx = pl.pallas_call(
        _bwd_dx_kernel,
        grid=(pl.cdiv(m, br),),
        in_specs=[wide, wide] + [narrow_out] * 5 + [gmv_spec],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((m, c), x2.dtype),
        interpret=interpret_mode(),
        name="batch_norm_bwd_dx",
    )(x2, g, mean, rstd, wr, dg / m, db / m, gmv)
    return dx, dg[0].astype(w.dtype), db[0].astype(w.dtype)


_batch_norm2.defvjp(_bn_fwd, _bn_bwd)


def bn_channels_last(x, w, b, epsilon):
    """Raw-array helper: fused BN over the LAST axis of any-rank x.
    Returns (out, mean(C,), var(C,)). The single shared body under both
    fused_batch_norm_train and nn_ops.batch_norm's Pallas branch."""
    cdim = x.shape[-1]
    lead = x.shape[:-1]
    out, mean, var = _batch_norm2(x.reshape(-1, cdim), w, b, epsilon)
    return (out.reshape(*lead, cdim), mean.reshape(cdim),
            var.reshape(cdim))


def fused_batch_norm_train(x, weight, bias, epsilon=1e-5):
    """Framework op: training-mode fused BN over the LAST axis (NHWC /
    NLC / (N, C)). Returns (out, batch_mean, batch_var) — the Layer
    folds the running-stat update on top. Differentiable w.r.t.
    x/weight/bias through the custom VJP (including exact handling of
    losses that consume the batch stats directly)."""
    from ...dispatch import apply

    def impl(x, w, b):
        return bn_channels_last(x, w, b, epsilon)

    return apply(impl, (x, weight, bias), n_out=3,
                 name="pallas_batch_norm")
