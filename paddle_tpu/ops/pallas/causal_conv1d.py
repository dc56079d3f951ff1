"""Two causal depthwise convolutions along the sequence, each a Pallas
kernel pair over tiles of rows x channels: the Mamba mixer's
(``F.causal_conv1d``: taps, bias, SiLU; kernels ``conv1d_fwd`` /
``conv1d_bwd``) and the double-gated short convolution of the ``lfm2``
family (``F.gated_short_conv``: ``y = c * conv(b * u)`` over the one array
``[b | c | u]``; kernels ``gated_conv_fwd`` / ``gated_conv_bwd``). The two
pairs share the tiles (``_tile``, ``_specs``), the 16-row halo read through
a second ``BlockSpec`` over the same array, the float32 scratch and its
filling (``_fill``), the shifted sums (``_pre``) and the taps' gradient
summed in a resident block over the sequential row axis; what the gated
pair adds is at the end of the file. First the Mamba mixer's.

The numbers are ``ops/ssm.py: _conv1d``'s: float32 taps, sums, bias and
SiLU, rounded to the call's dtype once, on the way out. What differs is
what crosses HBM. Grid (batch, channel tile, row tile),
channels on the lanes — the ``[B, S, C]`` array the mixer already holds.
A program reads its tile of ``x`` in ``x``'s dtype and, through a second
``BlockSpec`` over the same array, the 16 rows in front of it (one
bfloat16 sublane tile; the first tile's are zero: the sequence starts
there). It upcasts both into one float32 scratch and takes the ``K``
shifted terms as loads of that scratch ``K - 1 - j`` rows up. No padded
copy, no float32 array of rows x channels outside VMEM.

The backward kernel reads ``x`` and ``dy`` — the op's own input and the
cotangent, nothing the forward saved — with 16 rows on either side,
recomputes the pre-activation in float32, ``d(pre) = dy * silu'(pre)``,
and from it ``dx[t] = sum_j w[:, j] d(pre)[t + K - 1 - j]`` (the rows
past the tile's end come from the next tile, zero past the sequence's
end), ``dw[:, j] = sum_t d(pre)[t] x[t - (K - 1) + j]`` and ``db = sum_t
d(pre)[t]``, the last two added up in float32 in an output block that
stays in VMEM while the row axis, the grid's last and sequential, runs.

The gated pair (``[b | c | u] = bcx`` ``[B, S, 3 C]``, ``v = b * u``, ``z =
conv(v)``, ``y = c * z``; no bias, no activation) reads the three thirds of
``bcx`` through three ``BlockSpec``s over the one array (``C`` is a whole
number of lane tiles: no slice is copied out) and makes ``v`` in the
float32 scratch, so nothing of rows x channels exists between the gates
and the taps. Its backward kernel reads ``bcx`` and ``dy`` with the halo
rows (``b`` and ``u`` in front of the tile, ``c`` and ``dy`` behind it),
makes ``z`` again, and writes ``d(bcx)`` whole: ``dc = dy * z``, and with
``dv[t] = sum_j w[:, j] (c * dy)[t + K - 1 - j]``, ``db = u * dv`` and ``du
= b * dv``, through three windows of ONE output — the grid's last axis
walks the thirds of ``d(bcx)``; its first step computes all three and keeps
two in VMEM for the steps that write them (the inputs' blocks do not move
along that axis, so nothing is read twice). Grid axis 0 is the batch and a
halo never leaves its sequence: every sequence starts from zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
HALO = 16               # rows read of a neighbouring tile: a bf16 sublane tile
_ROW_TILES = (512, 256, 128)
_LANE_TILES = (512, 256, 128)


def _tile(n, tiles):
    return next((t for t in tiles if n % t == 0), None)


def supported(x_shape, taps):
    """Whether the kernels' tiles fit ``x`` [B, S, C] and a filter of
    ``taps``: whole row tiles (S a multiple of 128), whole lane tiles (C
    a multiple of 128), and at most 9 taps (the ``taps - 1`` rows a
    program needs of its neighbour lie inside the 16 it reads)."""
    if len(x_shape) != 3:
        return False
    _, s, c = x_shape
    return (_tile(s, _ROW_TILES) is not None and c % 128 == 0
            and 1 <= taps <= 9)


def _silu(pre):
    sig = 1.0 / (1.0 + jnp.exp(-pre))
    return pre * sig, sig


def _fill(xs, parts):
    """The float32 scratch ``xs`` from ``parts``, (ref, outside) pairs of
    consecutive rows: a part reads zero where ``outside`` holds (the rows
    in front of the sequence's start, or past its end). A pair of refs in
    a ref's place gives the product of their rows (the gated pair's ``b *
    u`` and ``c * dy``)."""
    at = 0
    for refs, outside in parts:
        refs = refs if isinstance(refs, tuple) else (refs,)
        rows = slice(at, at + refs[0].shape[1])
        value = refs[0][0].astype(_F32)
        for ref in refs[1:]:
            value = value * ref[0].astype(_F32)
        xs[rows] = value
        if outside is not None:
            @pl.when(outside)
            def _(rows=rows):
                xs[rows] = jnp.zeros((rows.stop - rows.start, xs.shape[1]),
                                     _F32)
        at = rows.stop


def _pre(xs, w_ref, b_ref, taps, rows):
    """The pre-activation of the ``rows`` rows that start at ``xs``'s row
    ``HALO``: tap ``j`` reads ``K - 1 - j`` rows up. ``b_ref`` None: no
    bias."""
    pre = 0.0 if b_ref is None else b_ref[...]
    for j in range(taps):
        d = taps - 1 - j
        pre = pre + w_ref[j:j + 1] * xs[HALO - d:HALO - d + rows]
    return pre


def _fwd_kernel(x_ref, prev_ref, w_ref, b_ref, y_ref, xs, *, taps, silu):
    _fill(xs, [(prev_ref, pl.program_id(2) == 0), (x_ref, None)])
    pre = _pre(xs, w_ref, b_ref, taps, x_ref.shape[1])
    y_ref[0] = (_silu(pre)[0] if silu else pre).astype(y_ref.dtype)


def _bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dnext_ref, w_ref, b_ref,
                dx_ref, dwb_ref, xs, gs, *, taps, silu):
    ts = x_ref.shape[1]
    k = pl.program_id(2)
    first, last = k == 0, k == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    _fill(xs, [(prev_ref, first), (x_ref, None), (next_ref, last)])
    # d(pre) of this tile's rows and of the HALO rows after them
    _fill(gs, [(dy_ref, None), (dnext_ref, last)])
    if silu:
        pre = _pre(xs, w_ref, b_ref, taps, ts + HALO)
        _, sig = _silu(pre)
        gs[...] = gs[...] * (sig * (1.0 + pre * (1.0 - sig)))
    g = gs[0:ts]
    dx = 0.0
    for j in range(taps):
        d = taps - 1 - j
        dx = dx + w_ref[j:j + 1] * gs[d:d + ts]
        dwb_ref[0, j:j + 1] += jnp.sum(g * xs[HALO - d:HALO - d + ts], 0,
                                       keepdims=True)
    dwb_ref[0, taps:taps + 1] += jnp.sum(g, 0, keepdims=True)
    dx_ref[0] = dx.astype(dx_ref.dtype)


_vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(shape, taps, at=0):
    """(the tile's rows and lanes, grid, the row tile's spec, the specs of
    the 16 rows before and after it, the specs of the taps and of the
    bias by lane) for rows x channels ``shape`` [B, S, C]. ``at``: the
    array the three row specs read is several ``C`` wide and they read its
    ``at``-th stretch of ``C`` lanes (the gated pair's thirds). An index
    map takes the grid's first three axes and lets a fourth pass."""
    bsz, s, c = shape
    ts, tc = _tile(s, _ROW_TILES), _tile(c, _LANE_TILES)
    per, blocks, off = ts // HALO, s // HALO, at * (c // tc)

    def lane(c):        # the Mamba pair's maps stay as they were traced
        return c + off if off else c

    return ((ts, tc), (bsz, c // tc, s // ts),
            _vmem((1, ts, tc), lambda b, c, k, *_: (b, k, lane(c))),
            _vmem((1, HALO, tc),
                  lambda b, c, k, *_: (b, jnp.maximum(k * per - 1, 0),
                                       lane(c))),
            _vmem((1, HALO, tc),
                  lambda b, c, k, *_: (b, jnp.minimum((k + 1) * per,
                                                      blocks - 1), lane(c))),
            _vmem((taps, tc), lambda b, c, k, *_: (0, c)),
            _vmem((1, tc), lambda b, c, k, *_: (0, c)))


def _forward(x, wt, b, silu):
    from . import interpret_mode
    taps = wt.shape[0]
    (ts, tc), grid, tile, prev, _, w_spec, b_spec = _specs(x.shape, taps)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, silu=silu),
        grid=grid,
        in_specs=[tile, prev, w_spec, b_spec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((ts + HALO, tc), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="conv1d_fwd",
    )(x, x, wt, b)


def _backward(x, wt, b, dy, silu):
    from . import interpret_mode
    taps = wt.shape[0]
    (ts, tc), grid, tile, prev, nxt, w_spec, b_spec = _specs(x.shape, taps)
    dx, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, silu=silu),
        grid=grid,
        in_specs=[tile, prev, nxt, tile, nxt, w_spec, b_spec],
        out_specs=[tile,
                   _vmem((1, taps + 1, tc), lambda b, c, k: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((x.shape[0], taps + 1, x.shape[2]),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((ts + 2 * HALO, tc), _F32),
                        pltpu.VMEM((ts + HALO, tc), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="conv1d_bwd",
    )(x, x, x, dy, dy, wt, b)
    dwb = jnp.sum(dwb, 0)          # the taps' rows, then the bias's
    return dx, dwb[:taps], dwb[taps:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(x, wt, b, silu):
    return _forward(x, wt, b, silu)


def _conv_fwd(x, wt, b, silu):
    return _forward(x, wt, b, silu), (x, wt, b)


def _conv_bwd(silu, res, dy):
    return _backward(*res, dy, silu)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv1d(x, w, *b, activation):
    """``ops/ssm.py: _conv1d`` through the kernels; same
    arguments, same result. The shapes have to be ``supported``."""
    bias = b[0].astype(_F32)[None] if b else jnp.zeros((1, x.shape[2]), _F32)
    return _conv(x, w.astype(_F32).T, bias, activation == "silu")


# -- the double-gated short convolution: y = c * conv(b * u) -----------------

def gated_supported(bcx_shape, taps):
    """Whether the gated pair's tiles fit ``bcx`` [B, S, 3 C]: ``C`` whole
    lane tiles (each third then starts on a tile), rows and taps as
    ``supported`` has them."""
    return (len(bcx_shape) == 3 and bcx_shape[2] % 3 == 0
            and supported(bcx_shape[:2] + (bcx_shape[2] // 3,), taps))


def _gated_fwd_kernel(b_ref, bprev_ref, c_ref, u_ref, uprev_ref, w_ref,
                      y_ref, xs, *, taps):
    _fill(xs, [((bprev_ref, uprev_ref), pl.program_id(2) == 0),
               ((b_ref, u_ref), None)])
    z = _pre(xs, w_ref, None, taps, y_ref.shape[1])
    y_ref[0] = (c_ref[0].astype(_F32) * z).astype(y_ref.dtype)


def _gated_bwd_kernel(b_ref, bprev_ref, c_ref, cnext_ref, u_ref, uprev_ref,
                      dy_ref, dnext_ref, w_ref, dbcx_ref, dw_ref, xs, gs,
                      held, *, taps):
    ts = b_ref.shape[1]
    k, third = pl.program_id(2), pl.program_id(3)

    @pl.when((k == 0) & (third == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(third == 0)                # d(b), and d(c), d(u) kept for later
    def _():
        _fill(xs, [((bprev_ref, uprev_ref), k == 0), ((b_ref, u_ref), None)])
        # g = c * dy of this tile's rows and of the HALO rows after them
        _fill(gs, [((c_ref, dy_ref), None),
                   ((cnext_ref, dnext_ref), k == pl.num_programs(2) - 1)])
        z = _pre(xs, w_ref, None, taps, ts)
        held[0] = (dy_ref[0].astype(_F32) * z).astype(held.dtype)
        g = gs[0:ts]
        dv = 0.0
        for j in range(taps):
            d = taps - 1 - j
            dv = dv + w_ref[j:j + 1] * gs[d:d + ts]
            dw_ref[0, j:j + 1] += jnp.sum(g * xs[HALO - d:HALO - d + ts], 0,
                                          keepdims=True)
        held[1] = (b_ref[0].astype(_F32) * dv).astype(held.dtype)
        dbcx_ref[0] = (u_ref[0].astype(_F32) * dv).astype(dbcx_ref.dtype)

    @pl.when(third > 0)
    def _():
        dbcx_ref[0] = held[third - 1]


_GATED_BWD_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit, static_argnums=(2,))
def _gated_forward(bcx, wt, interpret):
    bsz, s, c3 = bcx.shape
    taps, shape = wt.shape[0], (bsz, s, c3 // 3)
    (ts, tc), grid, b_tile, b_prev, _, w_spec, _ = _specs(shape, taps)
    c_tile = _specs(shape, taps, at=1)[2]
    u_tile, u_prev = _specs(shape, taps, at=2)[2:4]
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, taps=taps),
        grid=grid,
        in_specs=[b_tile, b_prev, c_tile, u_tile, u_prev, w_spec],
        out_specs=b_tile,
        out_shape=jax.ShapeDtypeStruct(shape, bcx.dtype),
        scratch_shapes=[pltpu.VMEM((ts + HALO, tc), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="gated_conv_fwd",
    )(bcx, bcx, bcx, bcx, bcx, wt)


@functools.partial(jax.jit, static_argnums=(3,))
def _gated_backward(bcx, wt, dy, interpret):
    bsz, s, c3 = bcx.shape
    taps, shape = wt.shape[0], (bsz, s, c3 // 3)
    (ts, tc), grid, b_tile, b_prev, y_next, w_spec, _ = _specs(shape, taps)
    c_tile, _, c_next = _specs(shape, taps, at=1)[2:5]
    u_tile, u_prev = _specs(shape, taps, at=2)[2:4]
    thirds = shape[2] // tc             # lane blocks a third
    dbcx, dw = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, taps=taps),
        grid=grid + (3,),
        in_specs=[b_tile, b_prev, c_tile, c_next, u_tile, u_prev, b_tile,
                  y_next, w_spec],
        out_specs=[_vmem((1, ts, tc),
                         lambda b, c, k, third: (b, k, third * thirds + c)),
                   _vmem((1, taps, tc), lambda b, c, k, third: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((bsz, taps, shape[2]), _F32)],
        scratch_shapes=[pltpu.VMEM((ts + HALO, tc), _F32),
                        pltpu.VMEM((ts + HALO, tc), _F32),
                        pltpu.VMEM((2, ts, tc), bcx.dtype)],
        compiler_params=_GATED_BWD_PARAMS,
        interpret=interpret,
        name="gated_conv_bwd",
    )(bcx, bcx, bcx, bcx, bcx, bcx, dy, dy, wt)
    return dbcx, jnp.sum(dw, 0)


@jax.custom_vjp
def _gated(bcx, wt):
    from . import interpret_mode
    return _gated_forward(bcx, wt, interpret_mode())


def _gated_fwd(bcx, wt):
    return _gated(bcx, wt), (bcx, wt)


def _gated_bwd(res, dy):
    from . import interpret_mode
    return _gated_backward(*res, dy, interpret_mode())


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_short_conv(bcx, w):
    """``ops/ssm.py: _gated_conv`` through the kernels; same arguments,
    same result. The shapes have to be ``gated_supported``."""
    return _gated(bcx, w.astype(_F32).T)
