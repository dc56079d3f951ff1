"""``acc[at[i]] += rows[i]``: the combine of ``F.moe_experts`` as an
in-place row scatter-add.

An expert of ``ops/moe.py`` ends its branch of the rung's ``lax.switch``
by adding its ``cap`` result rows to the scan's float32 accumulator at the
tokens it drew. As ``acc.at[at].add(rows)`` XLA copies the accumulator
whole through the switch, once an expert and pass (134 MB at 16,384 x
2,048: PERF.md section 7 row 23). Here the accumulator stays where it is
(``input_output_aliases``) and only the ``cap`` rows move: a program
takes a tile of ``rows`` through VMEM, fetches the accumulator's rows
``at[i]`` (scalar prefetch) one DMA a row, adds, and writes them back.

Since PR 44 the grouped path of ``ops/moe.py`` ends a round of sorted rows
with the same call: one scatter of a round's rows where the ladder makes
one an expert, at a program tile that divides the layout's row tile, so a
program's rows are one expert's (``scatter_add(..., tile=)``).

The accumulator is ``[tokens, 1, d]``: on a TPU that array lies row by
row in HBM (tiles of 1 x 128), so one row is one slice of it; as
``[tokens, d]`` eight rows share a tile and no single row can be
addressed. ``at`` holds no token twice (a prefix of a permutation), so
rows in flight never meet.

**One lowering a module.** A ``pl.pallas_call`` is lowered to Mosaic on
the host, in Python, in every process, before the compilation cache can
be asked (PERF.md section 6, PR 38). ``scatter_add`` is therefore a
module-level ``jax.jit``: JAX lowers an inner jit once a module for equal
shapes and calls it, so the rungs of a ladder cost one kernel each and
the layers, passes and call sites of a step share them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_ROW_TILES = (256, 128, 64, 32, 16, 8)
_TILE_BYTES = 2 << 20       # of float32 rows a program holds, at most


def row_tile(cap, d):
    """Rows of ``rows`` a program takes: whole sublanes of eight that
    divide ``cap``, or all of a small ``cap``. (Given a row tile of the
    grouped layout as ``cap``, a tile that divides it: no program then
    holds rows of two experts, so none holds a token twice.)"""
    fit = _TILE_BYTES // (4 * d)
    return next((t for t in _ROW_TILES if cap % t == 0 and t <= fit),
                cap if cap <= min(fit, _ROW_TILES[0]) else None)


def supported(d, caps):
    """Whether the kernel's tiles fit rows of ``d`` at every capacity of
    ``caps``: whole 128-lane tiles and a row tile for each."""
    return d % 128 == 0 and all(row_tile(c, d) is not None for c in caps)


def _kernel(at_ref, rows_ref, _, acc_ref, buf, sem, *, tile):
    base = pl.program_id(0) * tile

    def fetch(r):
        return pltpu.make_async_copy(
            acc_ref.at[pl.ds(at_ref[base + r], 1)], buf.at[pl.ds(r, 1)],
            sem.at[0])

    def store(r):
        return pltpu.make_async_copy(
            buf.at[pl.ds(r, 1)], acc_ref.at[pl.ds(at_ref[base + r], 1)],
            sem.at[1])

    def each(do):
        # eight rows an iteration: the scalar core's loop is what a row
        # costs here (PERF.md section 6, PR 38), and Mosaic unrolls a
        # fori_loop whole or not at all
        n = 8 if tile % 8 == 0 else 1

        def some(i, carry):
            for j in range(n):
                do(i * n + j)
            return carry
        lax.fori_loop(0, tile // n, some, 0)

    each(lambda r: fetch(r).start())
    each(lambda r: fetch(r).wait())
    buf[...] += rows_ref[...].astype(_F32).reshape(buf.shape)
    each(lambda r: store(r).start())
    each(lambda r: store(r).wait())


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def scatter_add(acc, at, rows, *, interpret, tile=None):
    """``acc`` float32 [tokens, 1, d] with ``rows[i]`` ([cap, d], any
    float dtype) added to row ``at[i]`` (int32 [cap], no token twice
    among the ``tile`` rows of a program: ``row_tile(cap, d)`` of them
    unless told), in place."""
    cap, d = rows.shape
    tile = tile or row_tile(cap, d)
    return pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(cap // tile,),
            in_specs=[pl.BlockSpec((tile, d), lambda i, at: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile, 1, d), _F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_scatter_add",
    )(at, rows, acc)
