"""From a projection's result to the flash kernels' heads
(``F.qk_heads``): the RMS norm over each head, the half-split rotation and
the move ``[B, S, H * D]`` -> ``[B, H, S, D]`` as one Pallas kernel pair,
one read and one write of the array each way.

The numbers are the composition's (``ops/nn_ops.py: _qk_heads``, which is
``_rms_norm``, a transpose and ``_rotate``): float32 inside, a head's
``x * rsqrt(mean(x^2) + epsilon) * w`` rounded to the call's dtype, then
``y * [cos | cos] + roll(y, D / 2) * [-sin | sin]`` rounded once. The roll
is one lane rotation on the XLU where the composition slices the lanes in
two halves, concatenates them and, backward, pads each half's gradient
back: relayouts at half a vreg's width that XLA runs as passes of their
own (PERF.md section 6, PR 45). What differs from the composition is the
order of the float32 sums in a head's mean square.

Layout: the forward reads the 2-D view ``[B * S, H * D]`` in tiles of
``rows x (heads a program) * D`` lanes and writes ``[B, H, S, D]`` blocks
``(1, heads a program, rows, D)``: the transpose is the index maps'. Grid
(batch, row tile, head block), the heads innermost, so that the two
float32 angle tables ``[S, D]`` -- twice the bytes of a bfloat16 tile a
row -- change block once a row tile and not once a head. The backward
reads the gradient ``[B, H, S, D]`` and the projection's result again,
undoes the rotation (the same expression with the sine negated: a roll by
``D / 2`` is its own inverse), rounds where the composition's backward
rounds, applies the norm's backward ``r (g w - n mean(g w n))``, writes
``d[B * S, H * D]`` through the forward's index map and the weight's
gradient as one float32 row a row tile, added up over the head blocks in
VMEM and over the row tiles by XLA. Residuals: the input, the weight and
the positions; the tables are made again from the positions.

Every ``pl.pallas_call`` is behind a module-level ``jax.jit``: a step
lowers each once a distinct shape, however many layers, replays and
``lax`` branches call it (PERF.md section 6, PR 38).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_ROW_TILES = (1024, 512, 256, 128)
_TILE_ELEMENTS = 512 * 1024     # rows x lanes of a program's tile, at most


def _tiles(s, heads, d, tiles=None):
    """(rows, heads) of a program's tile: the largest row tile that
    divides ``s`` with the most heads the element budget then leaves."""
    if tiles is not None:
        return tiles
    for ts in _ROW_TILES:
        if s % ts == 0:
            fit = [h for h in range(1, heads + 1)
                   if heads % h == 0 and ts * h * d <= _TILE_ELEMENTS]
            if fit:
                return ts, fit[-1]
    return None


def supported(x_shape, heads, positions_shape=None, sections=None):
    """Whether the kernels' tiles fit ``x`` [B, S, heads * D]: a head a
    whole number of 128-lane tiles, whole row tiles (S a multiple of 128),
    positions (where given) one a row, or one a row and axis under
    ``sections``."""
    if len(x_shape) != 3 or heads < 1 or x_shape[2] % heads:
        return False
    s, d = x_shape[1], x_shape[2] // heads
    rows = (s,) if sections is None else (len(sections), s)
    if positions_shape is not None and tuple(positions_shape) != rows:
        return False
    return d % 128 == 0 and _tiles(s, heads, d) is not None


def tables(positions, s, freq, sections=None):
    """The rotation's two float32 tables ``[S, D]``: ``[cos | cos]`` and
    ``[-sin | sin]`` of ``ops/nn_ops.py: _rotate``'s angles; under
    ``sections`` each pair's column from its chunk's row of the
    positions."""
    from ..nn_ops import _cos_sin
    cos, sin = _cos_sin(positions, s, freq, sections)
    return (jnp.concatenate([cos, cos], -1),
            jnp.concatenate([-sin, sin], -1))


def _fwd_kernel(*refs, d, hb, epsilon, normed, rotated):
    refs = list(refs)
    x_ref = refs.pop(0)
    w = refs.pop(0)[...] if normed else None
    cos, sin = (refs.pop(0)[...], refs.pop(0)[...]) if rotated \
        else (None, None)
    o_ref, = refs
    for h in range(hb):
        y = x_ref[:, h * d:(h + 1) * d].astype(_F32)
        if normed:
            r = jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + epsilon)
            y = y * r * w
        if normed and rotated:      # F.rms_norm's rounding, then float32
            y = y.astype(o_ref.dtype).astype(_F32)
        if rotated:
            y = y * cos + pltpu.roll(y, d // 2, 1) * sin
        o_ref[0, h] = y.astype(o_ref.dtype)


def _bwd_kernel(*refs, d, hb, epsilon, normed, rotated):
    refs = list(refs)
    g_ref, x_ref = refs.pop(0), refs.pop(0)
    w = refs.pop(0)[...] if normed else None
    cos, sin = (refs.pop(0)[...], refs.pop(0)[...]) if rotated \
        else (None, None)
    dx_ref = refs.pop(0)
    if normed:
        dw_ref, = refs

        @pl.when(pl.program_id(2) == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

    for h in range(hb):
        g = g_ref[0, h].astype(_F32)
        if rotated:
            g = g * cos - pltpu.roll(g, d // 2, 1) * sin
        if normed:
            if rotated:             # the norm's result was dx's dtype
                g = g.astype(dx_ref.dtype).astype(_F32)
            x = x_ref[:, h * d:(h + 1) * d].astype(_F32)
            r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + epsilon)
            n = x * r
            dw_ref[0] += jnp.sum(g * n, 0, keepdims=True)
            g = g * w
            g = r * (g - n * jnp.mean(g * n, -1, keepdims=True))
        dx_ref[:, h * d:(h + 1) * d] = g.astype(dx_ref.dtype)


_vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(batch, s, heads, d, tiles):
    """The grid (batch, row tile, head block) and the blocks of a call."""
    ts, hb = _tiles(s, heads, d, tiles)
    nk = s // ts
    return (hb, (batch, nk, heads // hb),
            _vmem((ts, hb * d), lambda b, k, j: (b * nk + k, j)),   # [BS, HD]
            _vmem((1, hb, ts, d), lambda b, k, j: (b, j, k, 0)),   # [B,H,S,D]
            _vmem((ts, d), lambda b, k, j: (k, 0)),                # a table
            _vmem((1, d), lambda b, k, j: (0, 0)))                 # weight


@functools.partial(jax.jit, static_argnames=("heads", "epsilon", "interpret",
                                             "tiles"))
def _forward(x, w, cos, sin, *, heads, epsilon, interpret, tiles=None):
    """``x`` [B, S, H D]; ``w`` [1, D] float32 or None; the tables
    [S, D] float32 or both None."""
    batch, s, hd = x.shape
    d = hd // heads
    hb, grid, rows, out, table, w_spec = _specs(batch, s, heads, d, tiles)
    normed, rotated = w is not None, cos is not None
    args = [x.reshape(batch * s, hd)] + [w] * normed + [cos, sin] * rotated
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, hb=hb, epsilon=epsilon,
                          normed=normed, rotated=rotated),
        grid=grid,
        in_specs=[rows] + [w_spec] * normed + [table, table] * rotated,
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((batch, heads, s, d), x.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="qk_heads_fwd",
    )(*args)


@functools.partial(jax.jit, static_argnames=("epsilon", "interpret", "tiles"))
def _backward(g, x, w, cos, sin, *, epsilon, interpret, tiles=None):
    """(dx [B, S, H D], dw [D] float32 or None) of ``g`` [B, H, S, D]."""
    batch, heads, s, d = g.shape
    hb, grid, rows, out, table, w_spec = _specs(batch, s, heads, d, tiles)
    normed, rotated = w is not None, cos is not None
    nk = grid[1]
    args = [g, x.reshape(batch * s, heads * d)] + [w] * normed \
        + [cos, sin] * rotated
    dw_spec = _vmem((1, 1, d), lambda b, k, j: (b * nk + k, 0, 0))
    got = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, hb=hb, epsilon=epsilon,
                          normed=normed, rotated=rotated),
        grid=grid,
        in_specs=[out, rows] + [w_spec] * normed + [table, table] * rotated,
        out_specs=[rows] + [dw_spec] * normed,
        out_shape=[jax.ShapeDtypeStruct((batch * s, heads * d), x.dtype)]
        + [jax.ShapeDtypeStruct((batch * nk, 1, d), _F32)] * normed,
        compiler_params=_PARAMS,
        interpret=interpret,
        name="qk_heads_bwd",
    )(*args)
    return (got[0].reshape(x.shape),
            jnp.sum(got[1], (0, 1)) if normed else None)


def _operands(x, w, positions, freq, sections=None):
    cos, sin = (None, None) if freq is None \
        else tables(positions, x.shape[1], freq, sections)
    return (None if w is None else w.astype(_F32)[None]), cos, sin


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _heads(x, w, positions, heads, epsilon, freq, sections=None):
    from . import interpret_mode
    return _forward(x, *_operands(x, w, positions, freq, sections),
                    heads=heads, epsilon=epsilon, interpret=interpret_mode())


def _heads_fwd(x, w, positions, heads, epsilon, freq, sections):
    return (_heads(x, w, positions, heads, epsilon, freq, sections),
            (x, w, positions))


def _heads_bwd(heads, epsilon, freq, sections, res, g):
    from . import interpret_mode
    x, w, positions = res
    dx, dw = _backward(g, x, *_operands(x, w, positions, freq, sections),
                       epsilon=epsilon, interpret=interpret_mode())
    at = None if positions is None \
        else np.zeros(positions.shape, jax.dtypes.float0)
    return dx, (None if w is None else dw.astype(w.dtype)), at


_heads.defvjp(_heads_fwd, _heads_bwd)


def qk_heads(x, *rest, heads, epsilon, freq, normed, positioned,
             sections=None):
    """``ops/nn_ops.py: _qk_heads`` through the kernels; same arguments
    (``rest``: the norm's weight where ``normed``, then the positions
    where ``positioned``), same result. The shapes have to be
    ``supported``."""
    rest = list(rest)
    w = rest.pop(0) if normed else None
    positions = rest.pop(0) if positioned else None
    return _heads(x, w, positions, heads, float(epsilon), freq, sections)
