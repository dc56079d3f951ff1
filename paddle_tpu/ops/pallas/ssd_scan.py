"""Mamba-2's chunked scan (``F.ssd_scan``) as a Pallas kernel pair.

The algorithm is ``ops/ssm.py: _ssd``'s (Dao & Gu, arXiv:2405.21060): per
head, inside a chunk of L positions the masked decay matrix ``M[t, s] =
exp(cum_t - cum_s)`` on ``(C_t . B_s) dt_s x_s``; between chunks the
carried state. What differs is where things live. Grid (batch, group,
chunk), the chunk axis sequential: a program reads the mixer's own layout
— (L x R*P) of ``x`` (a group's R heads are adjacent lanes), (L x N) of
``B`` and ``C`` — and everything of size L x L or R x P x N (the scores,
each head's decay matrix and its mask, the state) exists only in VMEM.
The state is carried in scratch, transposed and heads side by side, ``[N,
R*P]`` float32: ``state <- exp(total) state + B^T (x dt left)``. The
backward kernel walks the chunks in reverse with the state's gradient in
scratch and reads the state that entered each chunk from the one residual
the forward wrote (``[B, K, N, H*P]`` float32).

The same numbers as ``_ssd``: decays, their differences, the state and
every accumulation in float32; the MXU takes ``dot_dtype`` operands
exactly where ``_ssd`` casts (the scores' operands, ``scores * decay``,
``x dt``, ``x dt left``, the entering state). The only reordering: the
state is carried chunk to chunk instead of summed over K x K decays.

What stays outside, in XLA (all O(S*H), 2 MB at the nemotron cell's
size): ``softplus``, ``dt * A``, the cumulative sum inside a chunk — a
product with a triangle of ones at ``precision="highest"``, an exact
float32 sum (at default precision the MXU would round ``dt * A`` to
bfloat16) — and the two layouts a program needs of them, positions down
the sublanes (``[B, G, S, 2R]``: dt | cum) and along the lanes (``[B, G,
R, S]``). JAX's own autodiff differentiates that chain; the kernel pair
returns the gradients of ``x``, ``B``, ``C``, ``D`` (by lane) and of dt
and cum in both layouts, the latter as row and column sums of ``dM * M``
taken in VMEM.

A lane tile holds ``128 // P`` heads (two at P = 64): per-head products
run on the whole tile and keep their head's lanes, which costs the MXU
nothing (a 64-wide result takes the cycles of a 128-wide one) and keeps
every slice aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def supported(x_shape, b_shape, chunk):
    """Whether the kernels' tiles fit these shapes (``x`` [B, S, H, P],
    ``B`` [B, S, G, N]): whole chunks of a multiple of 128 positions, a
    head 64 wide or a multiple of 128, and a group's lanes of ``x`` (R*P)
    and of ``B`` / ``C`` (N) whole 128-lane tiles."""
    _, s, h, p = x_shape
    g, n = b_shape[2:]
    if chunk % 128 or s % chunk or h % g:
        return False
    r = h // g
    return ((p == 64 or p % 128 == 0) and (r * p) % 128 == 0
            and n % 128 == 0)


def _by_head(parts, p):
    """Head i's part over lanes [i*p, (i+1)*p): each part is a column
    (L, 1) or a whole tile (L, len(parts) * p)."""
    l = max(part.shape[0] for part in parts)
    w = len(parts) * p
    out = jnp.broadcast_to(parts[-1], (l, w))
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (l, w), 1)
        for i in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (i + 1) * p, parts[i], out)
    return out


def _tiles(heads, p):
    """(heads a lane tile, lane tiles) of a group."""
    hp = min(heads, max(1, 128 // p))
    return hp, heads // hp


def _chunk(x_ref, tc_ref, lanes, hd, heads, p, dot_dtype):
    """One lane tile's float32 ``x``, step sizes and cumulative log-decays
    by lane, ``x dt`` as the MXU takes it, and the heads' cum columns."""
    dt_t = _by_head([tc_ref[0, 0, :, r:r + 1] for r in hd], p)
    cum_cols = [tc_ref[0, 0, :, heads + r:heads + r + 1] for r in hd]
    cum_t = _by_head(cum_cols, p)
    xf = x_ref[0, :, lanes].astype(_F32)
    u = (xf * dt_t).astype(dot_dtype)
    return xf, dt_t, cum_t, cum_cols, u


def _decay(cum_col, crow_ref, r, lower):
    """A head's masked decay matrix: exp(cum_t - cum_s) where t >= s."""
    seg = cum_col - crow_ref[0, 0, r:r + 1, :]
    return jnp.where(lower, jnp.exp(seg), 0.0)


def _lower(l):
    return (jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1))


def _last_row(t, last):
    """The chunk's last position of a tile, (1, W), as a masked sum down
    the sublanes: a slice of a head's broadcast column would reach Mosaic
    as a (1, 1) value to broadcast both ways, which it does not lower."""
    return jnp.sum(jnp.where(last, t, 0.0), 0, keepdims=True)


def _fwd_kernel(x_ref, b_ref, c_ref, tc_ref, crow_ref, d_ref, y_ref, *rest,
                heads, p, dot_dtype, save):
    state = rest[-1]                    # [N, R*P] f32, entering this chunk
    l = x_ref.shape[1]
    hp, tiles = _tiles(heads, p)
    tw = hp * p

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if save:
        rest[0][0, 0] = state[...]
    bb = b_ref[0].astype(dot_dtype)
    cb = c_ref[0].astype(dot_dtype)
    scores = jax.lax.dot_general(cb, bb, _NT, preferred_element_type=_F32)
    lower = _lower(l)
    last = jax.lax.broadcasted_iota(jnp.int32, (l, tw), 0) == l - 1
    for j in range(tiles):
        lanes = slice(j * tw, (j + 1) * tw)
        hd = range(j * hp, (j + 1) * hp)
        xf, _, cum_t, cum_cols, u = _chunk(x_ref, tc_ref, lanes, hd, heads,
                                           p, dot_dtype)
        h_in = state[:, lanes]
        y = _by_head(
            [jnp.dot((scores * _decay(cum_cols[i], crow_ref, r, lower)
                      ).astype(dot_dtype), u, preferred_element_type=_F32)
             for i, r in enumerate(hd)], p)
        y = y + jnp.exp(cum_t) * jnp.dot(cb, h_in.astype(dot_dtype),
                                         preferred_element_type=_F32)
        y_ref[0, :, lanes] = (y + d_ref[:, lanes] * xf).astype(y_ref.dtype)
        total = _last_row(cum_t, last)
        v = (u.astype(_F32) * jnp.exp(total - cum_t)).astype(dot_dtype)
        state[:, lanes] = jnp.exp(total) * h_in + jax.lax.dot_general(
            bb, v, _TN, preferred_element_type=_F32)


def _bwd_kernel(x_ref, b_ref, c_ref, tc_ref, crow_ref, d_ref, dy_ref,
                hres_ref, dx_ref, db_ref, dc_ref, dtc_ref, drow_ref, dd_ref,
                dstate, *, heads, p, dot_dtype):
    # dstate: [N, R*P] f32, the gradient of the state that LEAVES this
    # chunk (the grid's chunk axis runs backwards through the index maps)
    l = x_ref.shape[1]
    hp, tiles = _tiles(heads, p)
    tw = hp * p

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bb = b_ref[0].astype(dot_dtype)
    cb = c_ref[0].astype(dot_dtype)
    scores = jax.lax.dot_general(cb, bb, _NT, preferred_element_type=_F32)
    lower = _lower(l)
    last = jax.lax.broadcasted_iota(jnp.int32, (l, tw), 0) == l - 1
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (l, tw), 1)
    mine = [(lane_t >= i * p) & (lane_t < (i + 1) * p) for i in range(hp)]
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (l, 2 * heads), 1)
    ds = jnp.zeros((l, l), _F32)
    db = jnp.zeros(db_ref.shape[1:], _F32)
    dc = jnp.zeros(dc_ref.shape[1:], _F32)
    dtc = jnp.zeros((l, 2 * heads), _F32)
    for j in range(tiles):
        lanes = slice(j * tw, (j + 1) * tw)
        hd = range(j * hp, (j + 1) * hp)
        xf, dt_t, cum_t, cum_cols, u = _chunk(x_ref, tc_ref, lanes, hd,
                                              heads, p, dot_dtype)
        total = _last_row(cum_t, last)
        left = jnp.exp(total - cum_t)
        vf = u.astype(_F32) * left
        gf = dy_ref[0, :, lanes].astype(_F32)
        gb = gf.astype(dot_dtype)
        h_in = hres_ref[0, 0, :, lanes]
        hb = h_in.astype(dot_dtype)
        # y's part from the entering state: exp(cum) * (C h^T)
        eg = jnp.exp(cum_t) * gf
        egb = eg.astype(dot_dtype)
        dc = dc + jax.lax.dot_general(egb, hb, _NT,
                                      preferred_element_type=_F32)
        dh_in = jax.lax.dot_general(cb, egb, _TN,
                                    preferred_element_type=_F32)
        # the state this chunk leaves: exp(total) h + B^T (x dt left)
        dho = dstate[:, lanes]
        dhob = dho.astype(dot_dtype)
        dv = jnp.dot(bb, dhob, preferred_element_type=_F32)
        db = db + jax.lax.dot_general(vf.astype(dot_dtype), dhob, _NT,
                                      preferred_element_type=_F32)
        # what reaches cum by position, summed over a head's lanes below:
        # exp(cum) from y, -left from the state, and in the last row the
        # chunk's total (left's and the carried state's decay)
        dvv = dv * vf
        acc = eg * jnp.dot(cb, hb, preferred_element_type=_F32) - dvv
        acc = acc + jnp.where(
            last,
            jnp.sum(dvv, 0, keepdims=True)
            + jnp.exp(total) * jnp.sum(dho * h_in, 0, keepdims=True), 0.0)
        du = []
        for i, r in enumerate(hd):
            m = _decay(cum_cols[i], crow_ref, r, lower)
            sm = scores * m
            dw = jax.lax.dot_general(
                jnp.where(mine[i], gb, jnp.zeros_like(gb)) if hp > 1 else gb,
                u, _NT, preferred_element_type=_F32)
            du.append(jax.lax.dot_general(sm.astype(dot_dtype), gb, _TN,
                                          preferred_element_type=_F32))
            ds = ds + dw * m
            q = dw * sm
            drow_ref[0, 0, r:r + 1, :] = -jnp.sum(q, 0, keepdims=True)
            dcum = (jnp.sum(q, 1, keepdims=True)
                    + jnp.sum(jnp.where(mine[i], acc, 0.0), 1,
                              keepdims=True))
            dtc = jnp.where(lane_c == heads + r, dcum, dtc)
        du = _by_head(du, p) + dv * left
        dux = du * xf
        for i, r in enumerate(hd):
            dtc = jnp.where(lane_c == r, jnp.sum(
                jnp.where(mine[i], dux, 0.0), 1, keepdims=True), dtc)
        dx_ref[0, :, lanes] = (du * dt_t
                               + d_ref[:, lanes] * gf).astype(dx_ref.dtype)
        dd_ref[0, :, lanes] += jnp.sum(gf * xf, 0, keepdims=True)
        dstate[:, lanes] = jnp.exp(total) * dho + dh_in
    dsb = ds.astype(dot_dtype)
    dc_ref[0] = (dc + jnp.dot(dsb, bb, preferred_element_type=_F32)
                 ).astype(dc_ref.dtype)
    db_ref[0] = (db + jax.lax.dot_general(dsb, cb, _TN,
                                          preferred_element_type=_F32)
                 ).astype(db_ref.dtype)
    dtc_ref[0, 0] = dtc


_vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)


def _dims(x, b, crow, chunk):
    """(batch, chunks, groups, heads a group, a group's lanes of x, state
    size) of the kernels' operands."""
    bsz, s, lanes = x.shape
    g, r = crow.shape[1:3]
    return bsz, s // chunk, g, r, lanes // g, b.shape[2] // g


def _specs(chunk, r, rp, n, step):
    """Block specs of the operands both kernels read: x, B, C, dt | cum
    by sublane, cum by lane, D by lane. ``step`` maps the grid's chunk
    index to the chunk (the backward kernel's runs in reverse)."""
    return [
        _vmem((1, chunk, rp), lambda b, g, k: (b, step(k), g)),
        _vmem((1, chunk, n), lambda b, g, k: (b, step(k), g)),
        _vmem((1, chunk, n), lambda b, g, k: (b, step(k), g)),
        _vmem((1, 1, chunk, 2 * r), lambda b, g, k: (b, g, step(k), 0)),
        _vmem((1, 1, r, chunk), lambda b, g, k: (b, g, 0, step(k))),
        _vmem((1, rp), lambda b, g, k: (0, g)),
    ]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(x, b, c, tc, crow, d_lanes, p, chunk, dot_dtype, save):
    from . import interpret_mode
    bsz, k, g, r, rp, n = _dims(x, b, crow, chunk)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [_vmem((1, chunk, rp), lambda b, g, k: (b, k, g))]
    if save:        # the state that enters each chunk, for the backward
        out_shape.append(jax.ShapeDtypeStruct((bsz, k, n, g * rp), _F32))
        out_specs.append(_vmem((1, 1, n, rp), lambda b, g, k: (b, k, 0, g)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=r, p=p, dot_dtype=dot_dtype,
                          save=save),
        grid=(bsz, g, k),
        in_specs=_specs(chunk, r, rp, n, lambda k: k),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, rp), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="ssd_fwd",
    )(x, b, c, tc, crow, d_lanes)
    return out if save else out[0]


def _backward(x, b, c, tc, crow, d_lanes, hres, dy, p, chunk, dot_dtype):
    from . import interpret_mode
    bsz, k, g, r, rp, n = _dims(x, b, crow, chunk)

    def rev(i):
        return k - 1 - i

    x_spec, b_spec, _, tc_spec, crow_spec, _ = specs = _specs(
        chunk, r, rp, n, rev)
    dx, db, dc, dtc, drow, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=r, p=p, dot_dtype=dot_dtype),
        grid=(bsz, g, k),
        in_specs=specs + [
            x_spec,
            _vmem((1, 1, n, rp), lambda b, g, k: (b, rev(k), 0, g))],
        out_specs=[x_spec, b_spec, b_spec, tc_spec, crow_spec,
                   _vmem((1, 1, rp), lambda b, g, k: (b, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(tc.shape, _F32),
                   jax.ShapeDtypeStruct(crow.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, 1, g * rp), _F32)],
        scratch_shapes=[pltpu.VMEM((n, rp), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="ssd_bwd",
    )(x, b, c, tc, crow, d_lanes, dy, hres)
    return dx, db, dc, dtc, drow, jnp.sum(dd, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, b, c, tc, crow, d_lanes, p, chunk, dot_dtype):
    return _forward(x, b, c, tc, crow, d_lanes, p, chunk, dot_dtype, False)


def _scan_fwd(x, b, c, tc, crow, d_lanes, p, chunk, dot_dtype):
    y, hres = _forward(x, b, c, tc, crow, d_lanes, p, chunk, dot_dtype,
                       True)
    return y, (x, b, c, tc, crow, d_lanes, hres)


def _scan_bwd(p, chunk, dot_dtype, res, dy):
    return _backward(*res, dy, p, chunk, dot_dtype)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a_log, b, c, d_skip, dt_bias, *, chunk, dot_dtype):
    """``ops/ssm.py: _ssd`` through the kernels; same arguments, same
    result. The shapes have to be ``supported``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r, k = h // g, s // chunk
    dt = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))   # [B,S,H]
    log_decay = dt * -jnp.exp(a_log.astype(_F32))
    # the cumulative sum inside a chunk, an exact float32 sum: the ones are
    # exact in bfloat16 and "highest" keeps all 24 bits of dt * A
    ones = jnp.tril(jnp.ones((chunk, chunk), _F32))
    cum = jnp.einsum("bkuh,tu->bkth", log_decay.reshape(bsz, k, chunk, h),
                     ones, precision="highest").reshape(bsz, s, h)

    def by_group(t):                    # [B, S, H] -> [B, G, S, R]
        return jnp.moveaxis(t.reshape(bsz, s, g, r), 2, 1)

    cum = by_group(cum)
    y = _scan(x.reshape(bsz, s, h * p), b.reshape(bsz, s, g * n),
              c.reshape(bsz, s, g * n),
              jnp.concatenate([by_group(dt), cum], -1),
              jnp.swapaxes(cum, 2, 3),
              jnp.repeat(d_skip.astype(_F32), p)[None], p, chunk,
              jnp.dtype(dot_dtype))
    return y.reshape(bsz, s, h, p)
