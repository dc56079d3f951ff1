"""From a latent attention's projections to the flash kernels' heads
(``F.mla_heads``): the interleaved rotation of each head's rotary lanes,
the one rotary key head written behind every head's ``k_nope``, the
split of ``kv_b_proj``'s result into K and V and the move ``[B, S, H * d]``
-> ``[B, H, S, d]`` as one Pallas kernel pair, one read of each input and
one write of each result each way.

The numbers are the composition's (``ops/nn_ops.py: _mla_heads``, which
is ``MultiHeadLatentAttention.qkv``'s chain of transposes, slices,
``_rotate(interleaved=True)``, a broadcast and two concatenations):
every lane without positions is moved bit for bit; a rotary part ``x``
[rows, 64] becomes ``[a cos - b sin | b cos + a sin]`` with ``a = x[0::2]``,
``b = x[1::2]``, float32 inside and rounded once. The de-interleave is a
product with a 0/1 matrix on the MXU, which is exact for finite values (a
row's lanes are each added to zeros): ``x @ M = [a | b | b | a]``, 128
lanes, times the float32 table ``[cos | cos | -sin | sin]``, and the two
64-lane halves added by one lane roll. Backward the transpose of that:
``g @ M' = [E1 | E2]``, the gradient's halves interleaved both ways, times
``[C | S']`` (the tables interleaved), halves added; nothing of the
forward is a residual, the rotation is linear and the positions count
from 0. The rotary key head's gradient is summed over the heads in a
float32 VMEM block across the (sequential) head axis of the grid, rounded
where the composition's broadcast rounds its sum, and turned back once a
row tile.

Layout: a program takes ``rows`` rows of a PAIR of heads (a 192-lane
head is one and a half lane tiles; two are three whole ones), reads
``[B * S, H * (nope + 64)]`` and ``[B * S, H * (nope + v)]`` in blocks of
a pair's lanes and writes ``(1, 2, rows, d)`` blocks of the three
results: the transpose is the index maps'. Grid (batch, row tile, head
pair), the pairs innermost, so the float32 table and the rotary key head
change block once a row tile. The heads are walked by the grid, two in a
body (PERF.md section 7 row 47).

Every ``pl.pallas_call`` is behind a module-level ``jax.jit``: a step
lowers each once a distinct shape, however many layers and replays call
it (PERF.md section 6, PR 38).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _lanes

_F32 = jnp.float32
ROPE = 64                       # two rotary parts' products fill a lane tile
_ROW_TILES = (1024, 512, 256, 128)
_VMEM_BYTES = 12 * 2 ** 20      # a program's blocks, both buffers of each


def _block_bytes(rows, nope, v, itemsize):
    """Both buffers of every block of a program as VMEM holds them (a
    192-wide head in 256 lanes, the rotary key head in 128), the float32
    table's too, and the backward's float32 sum."""
    flat = 2 * (nope + ROPE) + 2 * (nope + v) + 128
    heads = 2 * (2 * _lanes(nope + ROPE) + v)
    return rows * (2 * ((flat + heads) * itemsize + 128 * 4) + 128 * 4)


def _rows(s, nope, v, itemsize, rows=None):
    """Rows of a program's tile: the largest row tile that divides ``s``
    and whose blocks VMEM holds twice."""
    if rows is not None:
        return rows
    for ts in _ROW_TILES:
        if s % ts == 0 and _block_bytes(ts, nope, v, itemsize) <= _VMEM_BYTES:
            return ts
    return None


def supported(q_shape, kv_shape, k_rope_shape, heads, nope, v, dtypes):
    """Whether the kernels' tiles fit: a rotary part of 64 lanes, the two
    other widths whole 128-lane tiles, heads in pairs, whole row tiles (S
    a multiple of 128), one floating dtype of two or four bytes."""
    if len(q_shape) != 3 or heads < 2 or heads % 2 or nope < 128 \
            or nope % 128 or v < 128 or v % 128:
        return False
    b, s = q_shape[:2]
    dtype = jnp.dtype(dtypes[0])
    return (tuple(q_shape) == (b, s, heads * (nope + ROPE))
            and tuple(kv_shape) == (b, s, heads * (nope + v))
            and tuple(k_rope_shape) == (b, s, ROPE)
            and all(jnp.dtype(t) == dtype for t in dtypes)
            and dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32))
            and _rows(s, nope, v, dtype.itemsize) is not None)


def operands(s, freq, dtype, backward=False):
    """The float32 table ``[S, 128]`` of positions 0 .. S - 1 and the 0/1
    matrix ``[64, 128]`` of a pass. Forward ``[cos | cos | -sin | sin]``
    and ``x @ M = [a | b | b | a]``; backward the same four interleaved
    pairwise, ``[C | S']`` with ``C[2j] = C[2j + 1] = cos_j``, ``S'[2j] =
    sin_j``, ``S'[2j + 1] = -sin_j``, and ``g @ M' = [E1 | E2]``: ``E1``
    the halves of ``g`` interleaved, ``E2`` the same with each pair
    swapped."""
    from ..nn_ops import _cos_sin
    half = ROPE // 2
    j = np.arange(half)
    m = np.zeros((ROPE, 128), np.float32)
    if backward:
        lanes, sign = np.repeat(freq, 2), np.tile([1.0, -1.0], half)
        m[j, 2 * j] = m[half + j, 2 * j + 1] = 1
        m[half + j, ROPE + 2 * j] = m[j, ROPE + 2 * j + 1] = 1
    else:
        lanes, sign = np.tile(freq, 2), np.repeat([-1.0, 1.0], half)
        m[2 * j, j] = m[2 * j + 1, half + j] = 1
        m[2 * j + 1, ROPE + j] = m[2 * j, ROPE + half + j] = 1
    # each pair's angle in the two lanes that read it: no relayout after
    cos, sin = _cos_sin(None, s, lanes)
    return (jnp.concatenate([cos, sin * sign.astype(np.float32)], -1),
            jnp.asarray(m, dtype))


def _turn(x, m, table):
    """A rotary part ``x`` [rows, 64] through one gather matrix and its
    table: float32 [rows, 64]."""
    # a float32 value is three bfloat16 pieces; times 1 and added to
    # zeros they give it back whole only at the highest precision
    spread = jnp.dot(x, m, preferred_element_type=_F32,
                     precision=jax.lax.Precision.HIGHEST
                     if x.dtype == _F32 else None)
    y = spread * table
    return (y + pltpu.roll(y, ROPE, 1))[:, :ROPE]


def _fwd_kernel(xq_ref, xkv_ref, kr_ref, table_ref, m_ref,
                q_ref, k_ref, v_ref, *, nope, v):
    m, table = m_ref[...], table_ref[...]
    dtype = q_ref.dtype
    k_rope = _turn(kr_ref[...], m, table).astype(dtype)
    for h in range(2):
        at = h * (nope + ROPE)
        q_ref[0, h, :, :nope] = xq_ref[:, at:at + nope]
        q_ref[0, h, :, nope:] = _turn(
            xq_ref[:, at + nope:at + nope + ROPE], m, table).astype(dtype)
        at = h * (nope + v)
        k_ref[0, h, :, :nope] = xkv_ref[:, at:at + nope]
        k_ref[0, h, :, nope:] = k_rope
        v_ref[0, h] = xkv_ref[:, at + nope:at + nope + v]


def _bwd_kernel(gq_ref, gk_ref, gv_ref, table_ref, m_ref,
                dxq_ref, dxkv_ref, dkr_ref, sum_ref, *, nope, v):
    m, table = m_ref[...], table_ref[...]
    dtype = dxq_ref.dtype
    pair = pl.program_id(2)

    @pl.when(pair == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    for h in range(2):
        at = h * (nope + ROPE)
        dxq_ref[:, at:at + nope] = gq_ref[0, h, :, :nope]
        dxq_ref[:, at + nope:at + nope + ROPE] = _turn(
            gq_ref[0, h, :, nope:], m, table).astype(dtype)
        at = h * (nope + v)
        dxkv_ref[:, at:at + nope] = gk_ref[0, h, :, :nope]
        dxkv_ref[:, at + nope:at + nope + v] = gv_ref[0, h]
        sum_ref[...] += gk_ref[0, h, :, nope:].astype(_F32)

    @pl.when(pair == pl.num_programs(2) - 1)
    def _():        # the broadcast's backward rounds its sum, then R^T
        dkr_ref[...] = _turn(sum_ref[...].astype(dtype), m,
                             table).astype(dtype)


_vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)


def _specs(batch, s, heads, nope, v, itemsize, rows):
    """The rows of a program, the grid (batch, row tile, head pair), a
    call's compiler parameters and its blocks."""
    ts = _rows(s, nope, v, itemsize, rows)
    nk = s // ts
    flat = lambda d: _vmem((ts, 2 * d), lambda b, k, j: (b * nk + k, j))
    head = lambda d: _vmem((1, 2, ts, d), lambda b, k, j: (b, j, k, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_block_bytes(ts, nope, v, itemsize) + 8 * 2 ** 20)
    return (ts, (batch, nk, heads // 2), params,
            [flat(nope + ROPE), flat(nope + v),
             _vmem((ts, ROPE), lambda b, k, j: (b * nk + k, 0))],
            [head(nope + ROPE), head(nope + ROPE), head(v)],
            [_vmem((ts, 128), lambda b, k, j: (k, 0)),          # a table
             _vmem((ROPE, 128), lambda b, k, j: (0, 0))])       # a matrix


@functools.partial(jax.jit, static_argnames=("heads", "nope", "v",
                                             "interpret", "rows"))
def _forward(xq, xkv, kr, table, m, *, heads, nope, v, interpret, rows=None):
    """``xq`` [B, S, H (nope + 64)], ``xkv`` [B, S, H (nope + v)], ``kr``
    [B, S, 64]; the forward table [S, 128] and matrix [64, 128]."""
    batch, s = xq.shape[:2]
    _, grid, params, flat, head, rest = _specs(batch, s, heads, nope, v,
                                               xq.dtype.itemsize, rows)
    shape = lambda d: jax.ShapeDtypeStruct((batch, heads, s, d), xq.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nope=nope, v=v),
        grid=grid, in_specs=flat + rest, out_specs=head,
        out_shape=[shape(nope + ROPE), shape(nope + ROPE), shape(v)],
        compiler_params=params, interpret=interpret, name="mla_heads_fwd",
    )(*(t.reshape(batch * s, -1) for t in (xq, xkv, kr)), table, m)


@functools.partial(jax.jit, static_argnames=("interpret", "rows"))
def _backward(gq, gk, gv, table, m, *, interpret, rows=None):
    """(d xq, d xkv, d kr) of the three results' gradients; the backward
    table and matrix."""
    batch, heads, s, d = gq.shape
    nope, v = d - ROPE, gv.shape[3]
    ts, grid, params, flat, head, rest = _specs(batch, s, heads, nope, v,
                                                gq.dtype.itemsize, rows)
    widths = (heads * d, heads * (nope + v), ROPE)
    got = pl.pallas_call(
        functools.partial(_bwd_kernel, nope=nope, v=v),
        grid=grid, in_specs=head + rest, out_specs=flat,
        out_shape=[jax.ShapeDtypeStruct((batch * s, n), gq.dtype)
                   for n in widths],
        scratch_shapes=[pltpu.VMEM((ts, ROPE), _F32)],
        compiler_params=params, interpret=interpret, name="mla_heads_bwd",
    )(gq, gk, gv, table, m)
    return tuple(t.reshape(batch, s, n) for t, n in zip(got, widths))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _heads(xq, xkv, kr, heads, nope, v, freq):
    from . import interpret_mode
    return tuple(_forward(
        xq, xkv, kr, *operands(xq.shape[1], freq, xq.dtype),
        heads=heads, nope=nope, v=v, interpret=interpret_mode()))


def _heads_fwd(xq, xkv, kr, heads, nope, v, freq):
    return _heads(xq, xkv, kr, heads, nope, v, freq), None


def _heads_bwd(heads, nope, v, freq, _, g):
    from . import interpret_mode
    gq, gk, gv = g
    return _backward(gq, gk, gv,
                     *operands(gq.shape[2], freq, gq.dtype, backward=True),
                     interpret=interpret_mode())


_heads.defvjp(_heads_fwd, _heads_bwd)


def mla_heads(xq, xkv, kr, *, heads, nope, v, freq):
    """``ops/nn_ops.py: _mla_heads`` through the kernels; same arguments,
    same results. The shapes have to be ``supported``."""
    return _heads(xq, xkv, kr, heads, nope, v, freq)
