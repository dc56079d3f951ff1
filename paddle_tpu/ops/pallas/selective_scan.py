"""Mamba-1's selective scan (``F.selective_scan``) as a Pallas kernel pair.

The recurrence of Gu & Dao (arXiv:2312.00752, section 3.2) over a state
``[D, N]`` a sequence, with a transition that differs for every (channel,
state) pair and a step size for every (token, channel):

    H_t = exp(dt_t A) * H_{t-1} + (dt_t * x_t) B_t^T      y_t = H_t C_t + D x_t

There is no chunked matrix form (``ops/pallas/ssd_scan.py`` needs ONE decay
a head; here the decay between two positions differs for every pair), so
the kernels walk the positions one by one ON THE VECTOR UNIT: the first
kernels of the repo whose bound is neither the MXU nor HBM, but the vector
and transcendental units (``N`` exponentials and about ``6 N`` products and
sums a (token, 1,024 channels) forward).

Layout. 1,024 channels are one float32 vreg, ``[8, 128]``: the operands are
passed as ``[B, S, D / 1024, 8, 128]`` (a relayout XLA makes in front of
the call, fused with the cast to float32), the grid is (batch, channel
block, chunk of positions) with the chunk axis sequential, and a program's
state is ``N`` vregs that a ``fori_loop`` over the chunk's positions
carries in registers. ``B_t[n]`` and ``C_t[n]`` are SCALARS, read from SMEM
(a chunk's ``L * N`` of each) and splat over a vreg by the product itself,
so nothing is broadcast across lanes or sublanes. The state crosses chunks
in a VMEM scratch; the forward writes the state that ENTERS every chunk
(``[B, K, D / 1024, N, 8, 128]`` float32, 42 MB at 8,192 x 5,120 x 16 and
chunks of 64) as the one residual of the backward.

Backward: a program takes a chunk (the grid walks them in reverse), makes
its ``L`` states again from the entering one into a VMEM scratch (``L x N``
vregs: 4 MiB at 64), then walks the positions backwards with the state's
gradient in registers: ``dx``, ``d dt`` by position, ``dA`` and ``dD``
accumulated in the (resident) output block of a (batch, channel block), and
``dB_t[n]`` / ``dC_t[n]`` as sums over the block's 1,024 channels, laid into
lanes ``n`` and ``N + n`` of row ``t`` of a ``[L, 128]`` block (the channel
blocks' parts are added outside). Every number is float32: the state, the
exponentials, the step sizes.

``softplus`` of the raw step sizes, ``A = -exp(A_log)``, the relayouts and
the gate ``y * silu(z)`` stay in XLA (``ops/ssm.py``), differentiated by
JAX; the pair returns the gradients of what it was given.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
CHANNELS = 1024         # one float32 vreg of channels
CHUNK = 64              # positions a program; the backward holds their states
MAX_STATE = 32          # N vregs of state (and N of its gradient) in registers


def supported(x_shape, n, chunk=CHUNK):
    """Whether the kernels' tiles fit ``x`` [B, S, D] with a state of
    ``n``: whole vregs of channels, whole chunks of positions, a state
    that stays in registers."""
    _, s, d = x_shape
    return d % CHANNELS == 0 and s % chunk == 0 and 1 <= n <= MAX_STATE


def _advance(h, n, t, dt, u, a_ref, b_ref, n_state):
    """State ``n`` after position ``t``: ``exp(dt A_n) h + (dt x) B_t[n]``."""
    return jnp.exp(dt * a_ref[0, n]) * h \
        + u * b_ref[0, 0, 0, t * n_state + n]


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, *rest,
                n_state, chunk, save):
    state = rest[-1]                    # [N, 8, 128], entering this chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if save:
        rest[0][0, 0, 0] = state[...]
    skip = d_ref[0]

    def step(t, hs):
        dt = dt_ref[0, t, 0]
        xt = x_ref[0, t, 0]
        u = dt * xt
        y = skip * xt
        out = []
        for n in range(n_state):
            h = _advance(hs[n], n, t, dt, u, a_ref, b_ref, n_state)
            y = y + h * c_ref[0, 0, 0, t * n_state + n]
            out.append(h)
        y_ref[0, t, 0] = y
        return tuple(out)

    hs = jax.lax.fori_loop(0, chunk, step,
                           tuple(state[n] for n in range(n_state)))
    for n in range(n_state):
        state[n] = hs[n]


def _bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, dy_ref, hres_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, dbc_ref, hs_ref, dstate, *,
                n_state, chunk):
    # dstate: the gradient of the state that LEAVES this chunk (the grid's
    # chunk axis runs backwards through the index maps)
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    def remake(t, hs):                  # the chunk's states, once more
        dt = dt_ref[0, t, 0]
        u = dt * x_ref[0, t, 0]
        out = []
        for n in range(n_state):
            h = _advance(hs[n], n, t, dt, u, a_ref, b_ref, n_state)
            hs_ref[t, n] = h
            out.append(h)
        return tuple(out)

    jax.lax.fori_loop(0, chunk, remake,
                      tuple(hres_ref[0, 0, 0, n] for n in range(n_state)))
    skip = d_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    def total(tile):                    # [8, 128] -> [1, 1]
        return jnp.sum(jnp.sum(tile, 0, keepdims=True), 1, keepdims=True)

    def step(i, carry):
        dhs, dd = carry
        t = chunk - 1 - i
        before = jnp.maximum(t - 1, 0)
        dt = dt_ref[0, t, 0]
        xt = x_ref[0, t, 0]
        g = dy_ref[0, t, 0]
        u = dt * xt
        du = jnp.zeros_like(u)
        ddt = jnp.zeros_like(u)
        row = jnp.zeros((1, 128), _F32)
        out = []
        for n in range(n_state):
            a = a_ref[0, n]
            at = t * n_state + n
            dh = dhs[n] + g * c_ref[0, 0, 0, at]
            row = jnp.where(lane == n, total(dh * u), row)
            row = jnp.where(lane == n_state + n, total(g * hs_ref[t, n]), row)
            du = du + dh * b_ref[0, 0, 0, at]
            back = dh * jnp.exp(dt * a)         # to the state before t
            prev = jnp.where(t > 0, hs_ref[before, n], hres_ref[0, 0, 0, n])
            through = back * prev               # to dt_t * A
            ddt = ddt + through * a
            da_ref[0, 0, n] += through * dt
            out.append(back)
        dx_ref[0, t, 0] = du * dt + skip * g
        ddt_ref[0, t, 0] = ddt + du * xt
        dbc_ref[0, 0, 0, pl.ds(t, 1), :] = row
        return tuple(out), dd + g * xt

    dhs, dd = jax.lax.fori_loop(
        0, chunk, step, (tuple(dstate[n] for n in range(n_state)),
                         jnp.zeros((8, 128), _F32)))
    for n in range(n_state):
        dstate[n] = dhs[n]
    dd_ref[0, 0] += dd


def _specs(chunk, n, step):
    """Block specs of what both kernels read: x and dt by position, A and
    D by channel block, a chunk's B and C as scalars. ``step`` maps the
    grid's chunk index to the chunk (the backward's runs in reverse)."""
    by_pos = pl.BlockSpec((1, chunk, 1, 8, 128),
                          lambda b, g, k: (b, step(k), g, 0, 0))
    scalars = pl.BlockSpec((1, 1, 1, chunk * n),
                           lambda b, g, k: (b, step(k), 0, 0),
                           memory_space=pltpu.SMEM)
    return [by_pos, by_pos,
            pl.BlockSpec((1, n, 8, 128), lambda b, g, k: (g, 0, 0, 0)),
            pl.BlockSpec((1, 8, 128), lambda b, g, k: (g, 0, 0)),
            scalars, scalars]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _dims(x, a, chunk):
    bsz, s, g = x.shape[:3]
    return bsz, s // chunk, g, a.shape[1]


def _forward(x, dt, a, d, b, c, chunk, save):
    from . import interpret_mode
    bsz, k, g, n = _dims(x, a, chunk)
    out_shape = [jax.ShapeDtypeStruct(x.shape, _F32)]
    out_specs = [_specs(chunk, n, lambda k: k)[0]]
    if save:        # the state that enters each chunk, for the backward
        out_shape.append(jax.ShapeDtypeStruct((bsz, k, g, n, 8, 128), _F32))
        out_specs.append(pl.BlockSpec((1, 1, 1, n, 8, 128),
                                      lambda b, g, k: (b, k, g, 0, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n_state=n, chunk=chunk, save=save),
        grid=(bsz, g, k),
        in_specs=_specs(chunk, n, lambda k: k),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, 8, 128), _F32)],
        compiler_params=_PARAMS, interpret=interpret_mode(),
        name="selective_scan_fwd",
    )(x, dt, a, d, b, c)
    return out if save else out[0]


def _backward(x, dt, a, d, b, c, hres, dy, chunk):
    from . import interpret_mode
    bsz, k, g, n = _dims(x, a, chunk)

    def rev(i):
        return k - 1 - i

    specs = _specs(chunk, n, rev)
    by_block = pl.BlockSpec((1, 1, n, 8, 128), lambda b, g, k: (b, g, 0, 0, 0))
    dx, ddt, da, dd, dbc = pl.pallas_call(
        functools.partial(_bwd_kernel, n_state=n, chunk=chunk),
        grid=(bsz, g, k),
        in_specs=specs + [
            specs[0],
            pl.BlockSpec((1, 1, 1, n, 8, 128),
                         lambda b, g, k: (b, rev(k), g, 0, 0, 0))],
        out_specs=[specs[0], specs[0], by_block,
                   pl.BlockSpec((1, 1, 8, 128), lambda b, g, k: (b, g, 0, 0)),
                   pl.BlockSpec((1, 1, 1, chunk, 128),
                                lambda b, g, k: (b, rev(k), g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, g, n, 8, 128), _F32),
                   jax.ShapeDtypeStruct((bsz, g, 8, 128), _F32),
                   jax.ShapeDtypeStruct((bsz, k, g, chunk, 128), _F32)],
        scratch_shapes=[pltpu.VMEM((chunk, n, 8, 128), _F32),
                        pltpu.VMEM((n, 8, 128), _F32)],
        compiler_params=_PARAMS, interpret=interpret_mode(),
        name="selective_scan_bwd",
    )(x, dt, a, d, b, c, dy, hres)
    # the channel blocks' parts of dB | dC: [B, K, L, 128] -> [B, K, 1, L N]
    dbc = jnp.sum(dbc, 2)
    db, dc = (dbc[..., i * n:(i + 1) * n].reshape(bsz, k, 1, chunk * n)
              for i in (0, 1))
    return dx, ddt, jnp.sum(da, 0), jnp.sum(dd, 0), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, d, b, c, chunk):
    return _forward(x, dt, a, d, b, c, chunk, False)


def _scan_fwd(x, dt, a, d, b, c, chunk):
    y, hres = _forward(x, dt, a, d, b, c, chunk, True)
    return y, (x, dt, a, d, b, c, hres)


def _scan_bwd(chunk, res, dy):
    return _backward(*res, dy, chunk)


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def selective_scan(x, dt, a, b, c, d_skip, *, chunk=CHUNK):
    """``ops/ssm.py: _selective_scan``'s recurrence through the kernels:
    ``x`` [B, S, D], ``dt`` [B, S, D] (the step sizes, softplus taken),
    ``a`` [D, N] (negative), ``b`` and ``c`` [B, S, N], ``d_skip`` [D];
    returns ``y`` [B, S, D] float32, un-gated. The shapes have to be
    ``supported``. A module-level ``jax.jit``: a step lowers the pair once
    a distinct shape, not once a call site."""
    bsz, s, d = x.shape
    n = a.shape[1]
    g, k = d // CHANNELS, s // chunk

    def lanes(t):                       # [..., D] -> [..., G, 8, 128]
        return t.astype(_F32).reshape(*t.shape[:-1], g, 8, 128)

    def scalars(t):                     # [B, S, N] -> [B, K, 1, L N]
        return t.astype(_F32).reshape(bsz, k, 1, chunk * n)

    y = _scan(lanes(x), lanes(dt),
              jnp.moveaxis(lanes(a.astype(_F32).T), 0, 1), lanes(d_skip),
              scalars(b), scalars(c), chunk)
    return y.reshape(bsz, s, d)
