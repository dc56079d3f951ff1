"""The held experts' products of ``F.moe_experts`` as grouped products: one
kernel call a matrix and pass over rows sorted by expert, whose grid
follows the group sizes.

``ops/moe.py`` lays the routed rows out expert by expert, each expert's
rows padded to whole row tiles of ``tile`` rows, so a row tile belongs to
ONE expert. Every kernel here takes that layout's table by scalar
prefetch: ``group`` int32 [tiles], the expert whose weights a row tile
reads, and ``live`` int32 [1], the row tiles that hold rows. A program
past ``live`` computes nothing and asks for the blocks the last live
program held, so it moves nothing either: what a call costs follows the
rows routed, to the tile, and not a capacity.

* ``hidden``: ``h = act(xs W_gate[g]) * (xs W_up[g]) * gate`` (or
  ``relu(xs W_up[g])^2 * gate``), the products in float32, ``h`` in the
  operands' dtype: the epilogue is the gate, so the float32 hidden
  activations never cross HBM.
* ``gmm``: ``sum_i lhs_i @ rhs_i[g]`` in float32, the weights as they lie
  or transposed (``h W_down``; ``d_a W_gate^T + d_u W_up^T``).
* ``hidden_bwd``: the hidden activations made again beside ``d_h = dy
  W_down[g]^T`` on the same row tile, and from them what the backward
  pass needs, in the operands' dtype: ``d_a``, ``d_u`` (the products'
  cotangents), ``h * gate`` (``d_down``'s operand) and the gate's own
  gradient ``sum_f d_h h`` as 128 partial sums a row and column block.
* ``tgmm``: the transposed grouped product ``acc[g] += lhs_g^T rhs_g`` in
  float32, a group's tiles summed in the output block while it stays in
  VMEM; experts with no row in the call keep ``acc`` (it is the output,
  in place).

Grids are (column block, row tile): a weight block stays in VMEM while
its expert's row tiles go by, so each weight crosses HBM once a call
whatever the row tile, and the rows cross once a column block. The
matrices may stay float32 under bfloat16 products: a block is cast in
VMEM when a program meets a new expert (``_in_dtype``), so no pass over
the matrices in HBM casts them (at the lfm2 cell's shape the op alone
reads 24.1 ms a layer so, 24.8 with XLA's casts; 12.8 against 13.9 at
sdar's, 7.2 against 8.6 at joyai's: PERF.md section 6, PR 44).

**One lowering a module**, as ``moe_scatter_add``: every entry point is a
module-level ``jax.jit``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_BLOCK_BYTES = 4 << 20      # a weight block or an accumulator block
_VMEM_BYTES = 16 << 20      # what Mosaic grants a kernel unasked


def col_tile(n, rows, itemsize):
    """Columns of a block ``rows`` high: the widest whole-128-lane divisor
    of ``n`` that keeps the block under ``_BLOCK_BYTES`` (128 at least)."""
    fit = [t for t in range(128, n + 1, 128)
           if n % t == 0 and rows * t * itemsize <= _BLOCK_BYTES]
    return fit[-1] if fit else 128


def supported(d, f, tile):
    """Whether the kernels' blocks fit rows ``d`` wide and experts ``f``
    wide: whole 128-lane tiles of both, and row tiles of whole sublanes."""
    return d % 128 == 0 and f % 128 == 0 and tile % 8 == 0


def _params(*block_bytes):
    """VMEM for the blocks given, twice each (Pallas double-buffers), and
    room for the compiler's own."""
    need = 2 * sum(block_bytes) + (4 << 20)
    return dict(vmem_limit_bytes=max(int(need), _VMEM_BYTES))


def _row(i, live):
    """The row tile program ``i`` asks for: its own, or the last live."""
    return jnp.maximum(jnp.minimum(i, live[0] - 1), 0)


def _mm(a, b, contract=((1,), (0,))):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=_F32)


_NT = ((1,), (1,))


def _rows_spec(tile, width):
    return pl.BlockSpec((tile, width),
                        lambda j, i, group, live: (_row(i, live), 0))


def _weight_spec(rows, cols, transposed=False):
    """A [rows, cols] block of expert ``group[i]``'s matrix: column block
    ``j`` of ``[held, rows, n]``, or row block ``j`` of ``[held, n,
    cols]``."""
    if transposed:
        return pl.BlockSpec(
            (None, rows, cols),
            lambda j, i, group, live: (group[_row(i, live)], j, 0))
    return pl.BlockSpec(
        (None, rows, cols),
        lambda j, i, group, live: (group[_row(i, live)], 0, j))


def _out_spec(tile, cols):
    return pl.BlockSpec((tile, cols),
                        lambda j, i, group, live: (_row(i, live), j))


def _new_expert(group_ref, i):
    """Whether program ``i`` is the first of its column block or reads
    another expert than the program before it."""
    return jnp.logical_or(
        i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])


def _in_dtype(group_ref, i, w_refs, casts):
    """The weight blocks as the products take them: as they lie, or - the
    matrices float32 under products in another dtype - from ``casts``,
    VMEM scratch they are cast into when a program's expert, or its column
    block, is not the program's before: once an expert and column block,
    and no pass over the matrices in HBM to cast them. ``i`` is the
    program's row tile."""
    if not casts:
        return [w[...] for w in w_refs]

    @pl.when(_new_expert(group_ref, i))
    def _():
        for w, c in zip(w_refs, casts):
            c[...] = w[...].astype(c.dtype)
    return [c[...] for c in casts]


def _cast_scratch(ws, dtype, block):
    """Scratch for ``_in_dtype``: a ``block`` a matrix in ``dtype`` where
    the matrices ``ws`` are of another."""
    return [] if ws[0].dtype == dtype else [pltpu.VMEM(block, dtype)] * len(ws)


def _activate(pre, relu_gate):
    """``h`` of the products ``pre`` (float32): ``relu(a)^2``, ``silu(a) *
    u`` or ``relu(a) * u``, in ``ops/moe.py``'s order of operations."""
    if len(pre) == 1:
        return jnp.square(jax.nn.relu(pre[0]))
    a, u = pre
    return (jax.nn.relu(a) if relu_gate else jax.nn.silu(a)) * u


# -- forward: the hidden activations ---------------------------------------

def _hidden_kernel(group_ref, live_ref, x_ref, g_ref, *refs, n, relu_gate):
    w_refs, h_ref, casts = refs[:n], refs[n], refs[n + 1:]
    i = pl.program_id(1)

    @pl.when(i < live_ref[0])
    def _():
        x = x_ref[...]
        h = _activate([_mm(x, w) for w in _in_dtype(group_ref, i, w_refs,
                                                    casts)], relu_gate)
        h_ref[...] = (h * g_ref[...]).astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "relu_gate",
                                             "interpret"))
def hidden(xs, gate, ups, group, live, *, tile, relu_gate, interpret):
    """``xs`` [rows, d], ``gate`` float32 [rows, 1], ``ups`` one or two
    ``[held, d, f]`` (in ``xs``' dtype, or float32 and cast here) -> ``h``
    [rows, f] in ``xs``' dtype."""
    rows, d = xs.shape
    f = ups[0].shape[2]
    size, wsize = xs.dtype.itemsize, ups[0].dtype.itemsize
    tn = col_tile(f, d, size)
    casts = _cast_scratch(ups, xs.dtype, (d, tn))
    return pl.pallas_call(
        functools.partial(_hidden_kernel, n=len(ups), relu_gate=relu_gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // tn, rows // tile),
            in_specs=[_rows_spec(tile, d), _rows_spec(tile, 1)]
            + [_weight_spec(d, tn)] * len(ups),
            out_specs=_out_spec(tile, tn), scratch_shapes=casts),
        out_shape=jax.ShapeDtypeStruct((rows, f), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            **_params(*[d * tn * wsize] * len(ups), tile * d * size,
                      (2 + len(ups)) * tile * tn * 4,
                      len(casts) * d * tn * size // 2)),
        interpret=interpret,
        name="moe_hidden",
    )(group, live, xs, gate, *ups)


# -- a grouped product, one term or the sum of several ---------------------

def _gmm_kernel(group_ref, live_ref, *refs, n, transpose_rhs):
    lhs, rhs, o_ref, casts = refs[:n], refs[n:2 * n], refs[2 * n], \
        refs[2 * n + 1:]
    i = pl.program_id(1)

    @pl.when(i < live_ref[0])
    def _():
        contract = _NT if transpose_rhs else ((1,), (0,))
        acc = None
        for a, b in zip(lhs, _in_dtype(group_ref, i, rhs, casts)):
            term = _mm(a[...], b, contract)
            acc = term if acc is None else acc + term
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "transpose_rhs",
                                             "interpret"))
def gmm(lhs, rhs, group, live, *, tile, transpose_rhs, interpret):
    """float32 ``sum_i lhs[i] @ rhs[i][g]`` over the tuples ``lhs`` ([rows,
    k] each) and ``rhs`` (``[held, k, n]`` each, or ``[held, n, k]`` with
    ``transpose_rhs``): [rows, n]."""
    rows, k = lhs[0].shape
    n = rhs[0].shape[1 if transpose_rhs else 2]
    size, wsize = lhs[0].dtype.itemsize, rhs[0].dtype.itemsize
    tn = col_tile(n, k * len(lhs), size)
    w = _weight_spec(tn, k, True) if transpose_rhs else _weight_spec(k, tn)
    casts = _cast_scratch(rhs, lhs[0].dtype,
                          (tn, k) if transpose_rhs else (k, tn))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n=len(lhs),
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, rows // tile),
            in_specs=[_rows_spec(tile, k)] * len(lhs) + [w] * len(rhs),
            out_specs=_out_spec(tile, tn), scratch_shapes=casts),
        out_shape=jax.ShapeDtypeStruct((rows, n), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            **_params(*[k * tn * wsize, tile * k * size] * len(lhs),
                      2 * tile * tn * 4, len(casts) * k * tn * size // 2)),
        interpret=interpret,
        name="moe_gmm",
    )(group, live, *lhs, *rhs)


# -- backward: the hidden activations again, and what follows from them ----

def _fold(p):
    """[rows, n] -> [rows, 128]: the column blocks of 128 lanes added up
    (no sum across lanes: ``ops/moe.py`` adds the 128 up)."""
    out = p[:, :128]
    for c in range(128, p.shape[1], 128):
        out += p[:, c:c + 128]
    return out


def _hidden_bwd_kernel(group_ref, live_ref, x_ref, dy_ref, g_ref, *refs,
                       gated, relu_gate):
    n = 2 + gated                       # the matrices: ups, then down
    w_refs, outs, casts = refs[:n], refs[n:2 * n + 1], refs[2 * n + 1:]
    i = pl.program_id(1)

    @pl.when(i < live_ref[0])
    def _():
        x, ga = x_ref[...], g_ref[...]
        *ups, down = _in_dtype(group_ref, i, w_refs, casts)
        d_h = _mm(dy_ref[...], down, _NT)
        pre = [_mm(x, w) for w in ups]
        if not gated:
            act = jax.nn.relu(pre[0])
            h = jnp.square(act)
            cotangents = [d_h * (2.0 * ga) * act]
        else:
            a, u = pre
            if relu_gate:
                act, slope = jax.nn.relu(a), (a > 0).astype(_F32)
            else:
                sig = jax.nn.sigmoid(a)
                act = a * sig                                   # silu(a)
                slope = sig + act * (1.0 - sig)
            h = act * u
            d_hg = d_h * ga
            cotangents = [d_hg * u * slope, d_hg * act]
        *d_refs, hg_ref, dg_ref = outs
        for ref, c in zip(d_refs, cotangents):
            ref[...] = c.astype(ref.dtype)
        hg_ref[...] = (h * ga).astype(hg_ref.dtype)
        dg_ref[...] = _fold(d_h * h)


@functools.partial(jax.jit, static_argnames=("tile", "relu_gate",
                                             "interpret"))
def hidden_bwd(xs, dy, gate, ups, down, group, live, *, tile, relu_gate,
               interpret):
    """``(cotangents, hg, dg)``: the cotangents of ``ups``' products (one
    or two [rows, f]) and ``h * gate`` [rows, f] in ``xs``' dtype, and
    float32 ``dg`` [f / tn, rows, 128], whose sum over the first and the
    last axis is ``sum_f (dy W_down^T) h`` a row."""
    rows, d = xs.shape
    f = ups[0].shape[2]
    size, wsize = xs.dtype.itemsize, down.dtype.itemsize
    tn = col_tile(f, d, size)
    nf = len(ups) + 1
    wide = jax.ShapeDtypeStruct((rows, f), xs.dtype)
    casts = _cast_scratch(ups, xs.dtype, (d, tn)) \
        + _cast_scratch((down,), xs.dtype, (tn, d))
    return _split(pl.pallas_call(
        functools.partial(_hidden_bwd_kernel, gated=len(ups) == 2,
                          relu_gate=relu_gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(f // tn, rows // tile),
            in_specs=[_rows_spec(tile, d), _rows_spec(tile, d),
                      _rows_spec(tile, 1)]
            + [_weight_spec(d, tn)] * len(ups) + [_weight_spec(tn, d, True)],
            out_specs=[_out_spec(tile, tn)] * nf + [pl.BlockSpec(
                (None, tile, 128),
                lambda j, i, group, live: (j, _row(i, live), 0))],
            scratch_shapes=casts),
        out_shape=[wide] * nf + [jax.ShapeDtypeStruct(
            (f // tn, rows, 128), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            **_params(*[d * tn * wsize] * nf, 2 * tile * d * size,
                      (4 + 2 * nf) * tile * tn * 4,
                      len(casts) * d * tn * size // 2)),
        interpret=interpret,
        name="moe_hidden_bwd",
    )(group, live, xs, dy, gate, *ups, down))


def _split(outs):
    *cotangents, hg, dg = outs
    return tuple(cotangents), hg, dg


# -- the transposed grouped product ----------------------------------------

def _tgmm_kernel(group_ref, live_ref, lhs_ref, rhs_ref, acc_ref, o_ref):
    i = pl.program_id(2)

    @pl.when(i < live_ref[0])
    def _():
        new = _mm(lhs_ref[...], rhs_ref[...], ((0,), (0,)))
        first = _new_expert(group_ref, i)

        @pl.when(first)
        def _():
            o_ref[...] = acc_ref[...] + new

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[...] += new


def tgmm_tiles(k, n):
    """``(tk, tn)`` of ``tgmm``'s accumulator block: whole-128-lane
    divisors under ``_BLOCK_BYTES`` of float32 that re-read the fewest
    operand bytes a row, ``n / tn`` times ``lhs`` and ``k / tk`` times
    ``rhs``."""
    def divisors(m):
        return [t for t in range(128, m + 1, 128) if m % t == 0]
    return min(((tk, tn) for tk in divisors(k) for tn in divisors(n)
                if tk * tn * 4 <= _BLOCK_BYTES or (tk, tn) == (128, 128)),
               key=lambda t: (n // t[1] * k + k // t[0] * n, -t[1]))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def tgmm(lhs, rhs, acc, group, live, *, tile, interpret):
    """``acc`` float32 [held, k, n] with ``lhs_g^T rhs_g`` added to expert
    ``g``'s matrix for each expert with a row tile in ``group[:live]``
    (``lhs`` [rows, k], ``rhs`` [rows, n]), in place."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    tk, tn = tgmm_tiles(k, n)
    size = lhs.dtype.itemsize

    def block(kk, jn, i, group, live):
        return group[_row(i, live)], kk, jn

    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(k // tk, n // tn, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, tk), lambda kk, jn, i, group, live:
                             (_row(i, live), kk)),
                pl.BlockSpec((tile, tn), lambda kk, jn, i, group, live:
                             (_row(i, live), jn)),
                pl.BlockSpec((None, tk, tn), block)],
            out_specs=pl.BlockSpec((None, tk, tn), block)),
        out_shape=jax.ShapeDtypeStruct(acc.shape, _F32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            **_params(3 * tk * tn * 4, tile * (tk + tn) * size)),
        interpret=interpret,
        name="moe_tgmm",
    )(group, live, lhs, rhs, acc)
