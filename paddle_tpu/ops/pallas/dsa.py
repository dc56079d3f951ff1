"""The kernels of a learned sparse attention's indexer (``F.dsa_select``,
``F.dsa_indexer_loss``; ``ops/sparse_attention.py`` has the definitions).

``select``: ONE kernel a layer. A program owns a block of rows and holds
their index scores against every causal key in VMEM (128 rows x 16,384 keys
x 4 bytes = 8 MiB, as ordered integers: a float32's bits, the magnitude
flipped where the sign is set, order as the numbers do). It makes the
scores chunk of keys by chunk (sixteen 64-deep products a chunk, a ReLU and
a weight each), finds each row's ``top_k``-th largest EXACTLY by building
the threshold's 32 bits from the top, one counting pass over the held
scores a bit, and writes the selection as PACKED BITS (``ops/
sparse_attention.py: _pack``: int8 ``[B, 1, S, S / 8]``, bit ``b`` of
element ``[t, c]`` is key ``b * S / 8 + c``, so an element is made of eight
whole chunks of the held scores, a shift and an or each; 256 KiB a program
where the int8 selection was 2 MiB) beside the threshold, the selected
scores' log-sum-exp and their number. The ``[S, S]`` scores never reach
HBM. Only the chunks up to the block's diagonal are made, counted or
packed; the bits of the others are 0.

Both calls are behind module-level ``jax.jit``s (one lowering a distinct
shape however many layers and replays call them, PERF.md section 6, PR 38).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot, _dot_nt, _stat_row

_F32 = jnp.float32
_I32 = jnp.int32
SELECT_ROWS = 128       # rows a program of the select kernel
SELECT_CHUNK = 512      # keys a chunk of its loops
_INT_MIN = -2 ** 31
_MAGNITUDE = 0x7FFFFFFF
_LANES = 128


def _flip(bits):
    """The magnitude bits flipped where the sign is set: its own inverse."""
    return bits ^ ((bits >> 31) & _MAGNITUDE)


def _ordered(x):
    """float32 -> int32 that orders as the floats do."""
    return _flip(jax.lax.bitcast_convert_type(x, _I32))


def _unordered(key):
    return jax.lax.bitcast_convert_type(_flip(key), _F32)


def select_supported(qi_shape, rows=SELECT_ROWS, chunk=SELECT_CHUNK):
    """Whether the select kernel's tiles fit ``qi`` [B, Hi, S, Di]: whole
    row blocks, and whole key chunks in each of the ``PACK`` column chunks
    that share a packed element."""
    from ..sparse_attention import PACK
    s = qi_shape[2]
    return s % rows == 0 and s % (PACK * chunk) == 0 and chunk % rows == 0 \
        and chunk % _LANES == 0


def _select_kernel(qi_ref, ki_ref, w_ref, sel_ref, tau_ref, lse_ref, cnt_ref,
                   key_ref, bits_ref, *, rows, chunk, heads, top_k):
    # qi_ref (1, Hi, R, Di); ki_ref (1, S, Di); w_ref (1, R, Hi);
    # sel_ref (1, 1, R, S / PACK) int8, the packed selection; tau / lse /
    # cnt (1, 1, R); key_ref (R, S) int32 scratch: the block's scores as
    # ordered integers; bits_ref (R, S / PACK) int32 scratch: the packed
    # elements while they are or-ed together
    i = pl.program_id(1)
    wide = sel_ref.shape[3] // chunk                # chunks a packed row
    live = ((i + 1) * rows + chunk - 1) // chunk    # chunks up to the diagonal
    row_at = i * rows + jax.lax.broadcasted_iota(_I32, (rows, 1), 0)

    def causal(c):
        return c * chunk + jax.lax.broadcasted_iota(
            _I32, (rows, chunk), 1) <= row_at

    def cols(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def make(c, top):
        k = ki_ref[0, cols(c), :]
        acc = jnp.zeros((rows, chunk), _F32)
        for j in range(heads):
            acc = acc + w_ref[0, :, j:j + 1].astype(_F32) * jnp.maximum(
                _dot_nt(qi_ref[0, j], k), 0.0)
        acc = jnp.where(causal(c), acc + 0.0, -jnp.inf)
        key_ref[:, cols(c)] = _ordered(acc)
        return jnp.maximum(top, jnp.max(acc, axis=1, keepdims=True))

    top = jax.lax.fori_loop(0, live, make,
                            jnp.full((rows, 1), -jnp.inf, _F32))

    def count_from(cand):
        """How many held scores a row has at or above its ``cand``: lane
        tile by lane tile into one (R, 128) sum, so that a counting pass
        is compares and adds and ONE reduction across lanes."""
        wide = jnp.broadcast_to(cand, (rows, _LANES))

        def add(c, n):
            key = key_ref[:, cols(c)]
            for t in range(chunk // _LANES):
                n = n + jnp.where(
                    key[:, t * _LANES:(t + 1) * _LANES] >= wide, 1.0, 0.0)
            return n

        return jnp.sum(jax.lax.fori_loop(
            0, live, add, jnp.zeros((rows, _LANES), _F32)), axis=1,
            keepdims=True)

    # the top_k-th largest, bit by bit from the top: the sign first (the
    # non-negative integers are the upper half), then each magnitude bit
    # stays set where top_k scores still lie at or above the candidate
    zero = jnp.zeros((rows, 1), _I32)
    tau = jnp.where(count_from(zero) >= top_k, zero, _INT_MIN)

    def bit(b, tau):
        cand = tau | (jnp.int32(1) << (30 - b))
        return jnp.where(count_from(cand) >= top_k, cand, tau)

    tau = jax.lax.fori_loop(0, 31, bit, tau)
    # a row with no more than top_k causal keys keeps them all
    tau = jnp.where(row_at + 1 <= top_k, _INT_MIN, tau)

    # chunk c is bit c // wide of the packed columns of chunk c % wide; a
    # chunk past ``live`` is never made and leaves its bit 0
    bits_ref[...] = jnp.zeros(bits_ref.shape, _I32)

    def write(c, carry):
        total_exp, n = carry
        key = key_ref[:, cols(c)]
        keep = (key >= tau) & causal(c)
        at = cols(c % wide)
        bits_ref[:, at] = bits_ref[:, at] | jnp.where(
            keep, jnp.int32(1) << (c // wide), 0)
        e = jnp.where(keep, jnp.exp(_unordered(key) - top), 0.0)
        return (total_exp + jnp.sum(e, axis=1, keepdims=True),
                n + jnp.sum(jnp.where(keep, 1.0, 0.0), axis=1, keepdims=True))

    total_exp, n = jax.lax.fori_loop(
        0, live, write, (jnp.zeros((rows, 1), _F32),) * 2)
    sel_ref[0, 0] = bits_ref[...].astype(jnp.int8)  # bit 7 is int8's sign
    tau_ref[0] = _stat_row(jnp.where(tau == _INT_MIN, -jnp.inf,
                                     _unordered(tau)))
    lse_ref[0] = _stat_row(top + jnp.log(total_exp))
    cnt_ref[0] = _stat_row(n)


@functools.partial(jax.jit, static_argnames=("top_k", "rows", "chunk",
                                             "interpret"))
def _select_call(qi, ki, w, *, top_k, rows, chunk, interpret):
    from ..sparse_attention import PACK
    b, heads, s, d = qi.shape
    width = s // PACK
    row = pl.BlockSpec((1, 1, rows), lambda n, i: (n, 0, i),
                       memory_space=pltpu.VMEM)
    stat = jax.ShapeDtypeStruct((b, 1, s), _F32)
    return pl.pallas_call(
        functools.partial(_select_kernel, rows=rows, chunk=chunk,
                          heads=heads, top_k=top_k),
        grid=(b, s // rows),
        in_specs=[
            pl.BlockSpec((1, heads, rows, d), lambda n, i: (n, 0, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, d), lambda n, i: (n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, rows, heads), lambda n, i: (n, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, width), lambda n, i: (n, 0, i, 0),
                         memory_space=pltpu.VMEM),
            row, row, row,
        ],
        out_shape=[jax.ShapeDtypeStruct((b, 1, s, width), jnp.int8),
                   stat, stat, stat],
        scratch_shapes=[pltpu.VMEM((rows, s), _I32),
                        pltpu.VMEM((rows, width), _I32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the held scores, the packed elements as int32 and two
            # buffers of them as int8, kI's two buffers (64 of 128 lanes)
            vmem_limit_bytes=int(
                max(16 * 2 ** 20, rows * s * 4 + rows * width * (4 + 2 * 1)
                    + 4 * s * 128 * 2 + 16 * 2 ** 20))),
        interpret=interpret,
        name="dsa_select",
    )(qi, ki, w)


def select(qi, ki, w, *, top_k, rows=SELECT_ROWS, chunk=SELECT_CHUNK):
    """``ops/sparse_attention.py: _select`` through the kernel; same
    arguments, same results. The shapes have to be ``select_supported``."""
    from . import interpret_mode
    bits, tau, lse, n = _select_call(qi, ki, w, top_k=int(top_k), rows=rows,
                                     chunk=chunk, interpret=interpret_mode())
    count = jnp.sum(n[:, 0].astype(jnp.int32), axis=-1)
    return bits, lse[:, 0], tau[:, 0], count


KL_BLOCK = 512          # rows and keys a tile of the loss's pass


def kl_supported(q_shape, qi_shape, block=KL_BLOCK):
    """Whether the tiles of the loss's kernel fit ``q`` [B, H, S, D] and
    ``qi`` [B, Hi, S, Di]: whole tiles of rows and keys, whole lane tiles
    of a head."""
    s, d = q_shape[2], q_shape[3]
    return s % block == 0 and d % 128 == 0 and qi_shape[3] % 8 == 0


def _kl_kernel(q_ref, k_ref, m_ref, linv_ref, sel_ref, qi_ref, ki_ref, wt_ref,
               lse_ref, loss_ref, dqit_ref, dki_ref, dwt_ref, kit_ref, *,
               heads, index_heads, block, scale_rows):
    # One (q-block i, k-block j) tile, j <= i, TRANSPOSED as the flash
    # backward's: keys on sublanes, rows on lanes, so a row's statistic is a
    # (1, BQ) row that broadcasts down a tile as it is read.
    # q_ref (1, H, BQ, D) scaled; k_ref (1, H, BK, D); m_ref / linv_ref
    # (1, H, 1, BQ); sel_ref (1, 1, BK, BQ) int8, the selection transposed;
    # qi_ref (1, Hi, BQ, Di); ki_ref (1, BK, Di); wt_ref (1, Hi, 1, BQ);
    # lse_ref (1, 1, BQ). Results, all float32, added up over a q-block's
    # k-blocks in their output blocks: loss_ref (1, 1, BQ) a row's part of
    # the loss; dqit_ref (1, Hi, Di, BQ) d qI transposed; dwt_ref (1, Hi, 1,
    # BQ); and dki_ref (1, S, Di), which stays in VMEM for a sequence's
    # whole walk. kit_ref (Di, BK): kI transposed, made once a tile.
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _():
        dki_ref[...] = jnp.zeros(dki_ref.shape, _F32)

    @pl.when(j == 0)
    def _():
        loss_ref[...] = jnp.zeros(loss_ref.shape, _F32)
        dqit_ref[...] = jnp.zeros(dqit_ref.shape, _F32)
        dwt_ref[...] = jnp.zeros(dwt_ref.shape, _F32)

    @pl.when(j <= i)
    def _():
        keep = sel_ref[0, 0].astype(_F32) > 0.5             # (BK, BQ)
        ki = ki_ref[0]
        di = ki.shape[1]
        eye = (jax.lax.broadcasted_iota(_I32, (di, di), 0)
               == jax.lax.broadcasted_iota(_I32, (di, di), 1))
        kit_ref[...] = _dot_nt(eye.astype(ki.dtype), ki).astype(
            kit_ref.dtype)

        def head(h, acc):
            st = _dot_nt(k_ref[0, h], q_ref[0, h])
            return acc + jnp.exp(st - m_ref[0, h]) * linv_ref[0, h]

        target = jax.lax.fori_loop(0, heads, head,
                                   jnp.zeros((block, block), _F32))
        target = jnp.where(keep, target * (1.0 / heads), 0.0)

        def index_head(jh, acc):
            dots = _dot_nt(ki, qi_ref[0, jh])
            return acc + wt_ref[0, jh] * jnp.maximum(dots, 0.0)

        scores = jax.lax.fori_loop(0, index_heads, index_head,
                                   jnp.zeros((block, block), _F32)) + 0.0
        log_r = scores - lse_ref[0]
        seen = target > 0.0
        part = jnp.where(seen, target * (jnp.log(jnp.where(
            seen, target, 1.0)) - log_r), 0.0)
        loss_ref[0] += jnp.sum(part, axis=0, keepdims=True) * scale_rows
        d_scores = (jnp.where(keep, jnp.exp(log_r), 0.0) - target) \
            * scale_rows

        def grads(jh, d_ki):
            qi = qi_ref[0, jh]
            dots = _dot_nt(ki, qi)
            dwt_ref[0, jh] += jnp.sum(d_scores * jnp.maximum(dots, 0.0),
                                      axis=0, keepdims=True)
            g = jnp.where(dots > 0.0, d_scores * wt_ref[0, jh],
                          0.0).astype(qi.dtype)
            dqit_ref[0, jh] += _dot(kit_ref[...], g)
            return d_ki + _dot(g, qi)

        d_ki = jax.lax.fori_loop(0, index_heads, grads,
                                 jnp.zeros((block, di), _F32))
        cols = pl.ds(pl.multiple_of(j * block, block), block)
        dki_ref[0, cols, :] += d_ki


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _kl_call(qs, k, m, linv, sel_t, qi, ki, wt, lse, *, block, interpret):
    b, h, s, d = qs.shape
    hi, di = qi.shape[1], qi.shape[3]
    n = s // block

    def rows(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    # a skipped tile (j > i) asks for the diagonal's blocks again: no copy
    key_block = lambda n_, i, j: jnp.minimum(j, i)
    stat = rows((1, h, 1, block), lambda n_, i, j: (n_, 0, 0, i))
    return pl.pallas_call(
        functools.partial(_kl_kernel, heads=h, index_heads=hi, block=block,
                          scale_rows=1.0 / (b * s)),
        grid=(b, n, n),
        in_specs=[
            rows((1, h, block, d), lambda n_, i, j: (n_, 0, i, 0)),
            rows((1, h, block, d),
                 lambda n_, i, j: (n_, 0, key_block(n_, i, j), 0)),
            stat, stat,
            rows((1, 1, block, block),
                 lambda n_, i, j: (n_, 0, key_block(n_, i, j), i)),
            rows((1, hi, block, di), lambda n_, i, j: (n_, 0, i, 0)),
            rows((1, block, di),
                 lambda n_, i, j: (n_, key_block(n_, i, j), 0)),
            rows((1, hi, 1, block), lambda n_, i, j: (n_, 0, 0, i)),
            rows((1, 1, block), lambda n_, i, j: (n_, 0, i)),
        ],
        out_specs=[
            rows((1, 1, block), lambda n_, i, j: (n_, 0, i)),
            rows((1, hi, di, block), lambda n_, i, j: (n_, 0, 0, i)),
            rows((1, s, di), lambda n_, i, j: (n_, 0, 0)),
            rows((1, hi, 1, block), lambda n_, i, j: (n_, 0, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, 1, s), _F32),
                   jax.ShapeDtypeStruct((b, hi, di, s), _F32),
                   jax.ShapeDtypeStruct((b, s, di), _F32),
                   jax.ShapeDtypeStruct((b, hi, 1, s), _F32)],
        scratch_shapes=[pltpu.VMEM((di, block), ki.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(
                4 * h * block * max(d, 128) * qs.dtype.itemsize
                + 2 * s * 128 * 4 + 4 * hi * max(di, 8) * block * 4
                + 12 * block * block * 4 + 8 * 2 ** 20)),
        interpret=interpret,
        name="dsa_kl",
    )(qs, k, m, linv, sel_t, qi, ki, wt, lse)


def kl_and_grads(q, k, m, l, selected, qi, ki, w, lse, scale,
                 block=KL_BLOCK):
    """``ops/sparse_attention.py: _kl_and_grads`` through the kernel; same
    arguments, same results. The shapes have to be ``kl_supported``."""
    from . import interpret_mode
    from ..sparse_attention import _scaled
    b, h, s, _ = q.shape
    hi = qi.shape[1]
    block = min(block, s)
    loss, dqit, dki, dwt = _kl_call(
        _scaled(q, scale), k, m.reshape(b, h, 1, s),
        (1.0 / l).reshape(b, h, 1, s), jnp.swapaxes(selected, 2, 3), qi, ki,
        jnp.swapaxes(w, 1, 2).astype(_F32).reshape(b, hi, 1, s),
        lse.reshape(b, 1, s), block=block, interpret=interpret_mode())
    return (jnp.sum(loss), jnp.swapaxes(dqit, 2, 3).astype(qi.dtype),
            dki.astype(ki.dtype),
            jnp.swapaxes(dwt.reshape(b, hi, s), 1, 2).astype(w.dtype))
