"""The Mamba mixer's causal depthwise convolution (``F.causal_conv1d``) as
a Pallas kernel pair over tiles of rows x channels.

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
    in front of the sequence's start, or past its end)."""
    at = 0
    for ref, outside in parts:
        rows = slice(at, at + ref.shape[1])
        xs[rows] = ref[0].astype(_F32)
        if outside is not None:
            @pl.when(outside)
            def _(rows=rows):
                xs[rows] = jnp.zeros((rows.stop - rows.start, xs.shape[1]),
                                     _F32)
        at = rows.stop


def _pre(xs, w_ref, b_ref, taps, rows):
    """The pre-activation of the ``rows`` rows that start at ``xs``'s row
    ``HALO``: tap ``j`` reads ``K - 1 - j`` rows up."""
    pre = b_ref[...]
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


def _specs(x, taps):
    """(the tile's rows and lanes, grid, the row tile's spec, the specs of
    the 16 rows before and after it, the specs of the taps and of the
    bias by lane)."""
    bsz, s, c = x.shape
    ts, tc = _tile(s, _ROW_TILES), _tile(c, _LANE_TILES)
    per, blocks = ts // HALO, s // HALO
    return ((ts, tc), (bsz, c // tc, s // ts),
            _vmem((1, ts, tc), lambda b, c, k: (b, k, c)),
            _vmem((1, HALO, tc),
                  lambda b, c, k: (b, jnp.maximum(k * per - 1, 0), c)),
            _vmem((1, HALO, tc),
                  lambda b, c, k: (b, jnp.minimum((k + 1) * per,
                                                  blocks - 1), c)),
            _vmem((taps, tc), lambda b, c, k: (0, c)),
            _vmem((1, tc), lambda b, c, k: (0, c)))


def _forward(x, wt, b, silu):
    from . import interpret_mode
    taps = wt.shape[0]
    (ts, tc), grid, tile, prev, _, w_spec, b_spec = _specs(x, taps)
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
    (ts, tc), grid, tile, prev, nxt, w_spec, b_spec = _specs(x, taps)
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
