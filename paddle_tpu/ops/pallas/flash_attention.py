"""Flash attention Pallas kernels — fused mask + attention dropout + fused
backward (reference: the fused attention stack the reference approximates
with paddle/fluid/operators/fused/fused_elemwise_activation_op.cu +
softmax_with_cross_entropy_op.cu; flash-style tiling is the TPU-native
formulation).

Forward: grid (batch*heads, q-blocks); each program walks k/v-blocks with
the online-softmax recurrence (running max m, normalizer l, accumulator
acc) so the S×S score matrix never hits HBM. A mask enters in one of
five ways: as an ARRAY (an additive key bias [B,1,1,Sk] or a full
[.,.,Sq,Sk] bias, added to the scores inside the kernel), as the causal
diagonal, as a SLIDING WINDOW beside it (below), as the BLOCK-DIFFUSION
STRUCTURE (below) — these three are facts of the call that cost no
operand and decide which tiles a program walks at all — or as a
data-dependent SELECTION shared by a sequence's heads (below): one narrow
operand, tested in both kernel bodies beside the diagonal.
Attention-probability dropout is drawn in-kernel from the
TPU PRNG, seeded per (bh, q-block, k-block) tile so the backward
regenerates the identical keep-mask without ever storing it.

Sliding window (PR 41; ``flash_attention(causal=True, window=W)``,
``sliding_window_mask`` is the rule: row i sees keys ``(i - W, i]``). The
same two kernel bodies take it (``window=`` in their static parameters;
without it they trace what they traced before; under it the calls are
named ``flash_win_fwd`` and ``flash_win_bwd``). A forward program walks
the k-tiles from the one that holds the first key its first row sees to
the diagonal's: 9 of them at most at 512 x 512 under a window of 4,096
(252 of 1,024 a head at 16,384 positions), the first crossed by the
window's lower edge and the last by the diagonal; a backward program walks
its k-block's q-tiles from the diagonal down to the last row that sees its
last key, and dq's accumulator takes parts from at most 9 k-blocks a
q-block. Positions inside a crossed tile come from iotas, as the
diagonal's. The side a program keeps whole is still the whole side (the
block rule at the end of this file says what that costs).

Selection (PR 47; ``flash_attention(causal=True, selected=...)``): an int8
array ``[B, 1, Sq, Sk]``, row t of sequence b reads the keys it marks
(``F.dsa_select`` makes one from a learned indexer's scores: the ``top_k``
best causal keys a row). The additive path would carry it as a float32
``[1, 1, 16384, 16384]`` bias, 1.07 GB a layer read once a head; as int8
it is 268 MB, and the same two kernel bodies take it (``selected=`` in
their static parameters; without it they trace what they traced before;
under it the calls are named ``flash_sel_fwd`` and ``flash_sel_bwd``): the
forward gets a q-block's rows of it as one more blocked operand (BQ x Sk
bytes a program, the same block for every head of a sequence), the
backward a k-block's rows of its TRANSPOSE (made once a backward call by
XLA), and every score tile is masked by its int8 tile, the tiles the
diagonal crosses by both. Every causal tile is walked: a token-level
selection empties no tile. The forward asks for the VMEM its shapes need
(``_sel_fwd_vmem``); the call also returns its two statistic rows, which
the indexer's loss reads.

Block-diffusion structure (PR 33; ``flash_attention(diffusion_block=B)``,
``block_diffusion_mask`` is the rule). q, k and v hold two copies of a
sequence of L positions, the noisy copy's rows in front of the clean
copy's. A clean row sees the clean blocks up to its own, a noisy row the
clean blocks before its own and its own noisy block: of the (2L)^2 score
tiles n (n + 1) + n hold an allowed pair (n = L / tile; 288 of 1,024 a
head at 512 x 512 and L = 8,192) and 3 n of them are crossed by the
structure. The same two kernel bodies take it (``bd=`` in their static
parameters; without it they trace what they traced before; under it the
calls are named ``flash_bd_fwd`` and ``flash_bd_bwd``): the side a
program keeps whole is ONE copy's L rows — the forward keeps the clean
copy's keys, and what any row sees of the noisy copy is its own block,
which arrives as one more blocked operand at the program's own positions —
so 2L rows cost the VMEM of L; the forward grid runs over both copies'
q-blocks, and the backward grid runs each clean k-block twice, once
against each copy's rows (the two parts of dK and dV are added outside;
dQ and its accumulator follow the copy), the noisy rows' program taking
the noisy k-block at the same positions with it. Positions inside a
crossed tile come from iotas and a shift by log2 B, as the causal
diagonal's. A copy is padded to whole tiles with zero rows; L is whole
diffusion blocks, so the structure itself keeps every true row off the
padding.

Backward: ONE kernel (PR 40; two until then, which made every score tile
twice: seven products a tile for the five the gradients need). Grid
(bh, k-blocks), the second axis sequential: a program owns a k-block and
loops q-blocks over the TRANSPOSED score tile s^T = K Q^T, accumulating
dv = pd^T @ dO and dk = ds^T @ Q in its carry, and adds the tile's part of
dQ, transposed too, dq^T[:, rows] += K^T @ ds^T, into a float32 (D, Sq)
accumulator that stays in VMEM from a head's first k-block (which zeroes
it) to its last (which transposes it once, scales, rounds and stores dq):
q-block by q-block the parts are added in ascending order of k-blocks, as
a loop over k-blocks would add them. No transpose of a score tile
anywhere; K^T is made once a program. The call's VMEM limit is computed
from its shapes (``_bwd_params``). It recomputes p = exp(s - m) / l from
the saved PER-ROW (max m, normalizer l) — deliberately NOT the folded
lse = m + log l: with a finite large-negative additive mask (the -1e9
convention) s and m are ~1e9-scale where f32 ulp is 64, so s − m
reproduces the forward's (and sdpa's) rounding exactly while
s − (m + log l) would silently lose the entire log-normalizer.
delta = rowsum(dO∘O) is one cheap XLA reduction outside the kernels (the
identity Σ_k p_k·dp_k = rowsum(dO∘O) holds under dropout too).

Inner loops (PR 32). A tile loaded from q, k, v or dO enters the MXU in
the dtype it was read in (bfloat16 as bfloat16, float32 as float32: read
off the call, no option) through products that contract both operands'
own last dimension, so no float32 copy of a K or V tile is made or
transposed; what a kernel makes itself (p, ds, p^T, ds^T) is rounded to
that dtype in front of its product, q is scaled in float32 and rounded
once; scores, soft-max, statistics and accumulators stay float32. On the
chip this is the parent's arithmetic bit for bit: the MXU took a float32
operand in one bfloat16 pass all along. Only the tiles the diagonal or a
true length crosses make positions and select: the others run a plain
body (the additive bias is still added, dropout still drawn), and where
the shapes say how many crossed tiles a program has (a square causal call
of whole blocks) they are straight-line code behind the plain loop, not a
second loop. An inner iteration costs about half a microsecond whatever
its tile holds, so the block rule at the end of this file takes the
largest tiles that fit, and holds a whole side too large to keep twice
(8,192 x 192 | 128) in one VMEM buffer to make them fit.

Row statistics (m, l, and the backward's 1/l and delta) cross HBM as ONE
f32 a (batch·head, row): (BH, 1, S) arrays with the sequence on the lane
axis — as a forward result, as the residual saved for the backward, and as
an operand of the backward kernel. Inside the forward a statistic is a
(BQ, 1) column (it broadcasts along a score tile's keys), relayed to a
(1, BQ) row once a q-block before the store (_stat_row): an in-VMEM
relayout, no HBM traffic. The backward kernel needs none: a (1, BQ) row
broadcasts down the sublanes of its transposed tile as it is. There is no
lane-replicated (…, 128) copy and no (…, 1) custom-call operand (whose
tiled layout pads the 1 to 128 lanes in HBM) on any path: at BERT-base's
seq-512 shapes those cost 50 MB an array — 650 MB a layer against 190 MB
of q/k/v/o/dO traffic, and 5.4 ms of a 74.8 ms step in the XLA ops that
made and sliced them (PERF.md §6, PR 26).

Saved results (PR 42). What a call's backward needs of its forward, o and
the two statistic rows, leaves the three vjp-forward rules under the names
``RESULT_NAMES`` (``jax.ad_checkpoint.checkpoint_name``: an identity
outside a ``jax.checkpoint``). A checkpoint whose policy keeps those names
(``jit.recompute``'s default, ``memory_plan.checkpoint_policy``) replays
its block without the forward kernel: the block's activations are made
again, the kernel's result is not.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# A row statistic changes between column (BQ, 1) and row (1, BQ) through
# one lane tile in VMEM: replicate, transpose, keep one. Of the forms Mosaic
# lowers (reshape, expand_dims, .T, this), this one leaves the column in the
# layout the score tiles broadcast from cheaply (PERF.md §6, PR 26).
_TILE = 128


def _stat_row(col):
    """(BQ, 1) carry of a row statistic -> the (1, BQ) lane-major row that
    crosses HBM."""
    return jnp.broadcast_to(col, (col.shape[0], _TILE)).T[:1, :]


def _stat_col(row):
    """(1, BK) lane-major row, as read from HBM -> a (BK, 1) column: the key
    bias of the backward kernel's transposed tile."""
    return jnp.broadcast_to(row, (_TILE, row.shape[1])).T[:, :1]


def _dropout_keep(seed_ref, bh, qi, j, shape, threshold):
    """Regeneratable dropout keep-mask for one (BQ, BK) score tile, drawn
    from the TPU PRNG seeded per tile (so the forward and the backward
    kernel regenerate the identical mask without storing it)."""
    # libtpu's tpu.prng_set_seed_32 takes at most TWO seed words, so fold
    # the (bh, qi, j) tile coordinates into one mixed word via a
    # murmur-style absorb (xor word, odd-constant multiply, logical
    # shift-xor) — avalanches all 32 bits, so no wrap-around collision
    # window at long sequences / large batch*heads (int32 ops wrap mod
    # 2^32 in XLA, which is exactly what the hash wants)
    mixed = seed_ref[1]
    for v in (bh, qi, j):
        mixed = (mixed ^ v) * jnp.int32(-1640531527)   # 0x9E3779B9
        mixed = mixed ^ ((mixed >> 15) & jnp.int32(0x1FFFF))
        mixed = mixed * jnp.int32(-1274126177)         # 0xB40E609F (odd)
        mixed = mixed ^ ((mixed >> 13) & jnp.int32(0x7FFFF))
    pltpu.prng_seed(seed_ref[0], mixed)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= jnp.uint32(threshold)


def _host_keep_mask(seed, bh, sq_pad, sk_pad, dropout_p):
    """Interpret-mode (CPU test) substitute: the TPU PRNG primitives have
    no CPU lowering, so precompute the whole keep-mask in XLA from the
    same seed (deterministic → fwd/bwd see identical masks) and thread it
    through as a kernel operand (0.0 = drop, 1.0 = keep)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed[0]), seed[1])
    u = jax.random.uniform(key, (bh, sq_pad, sk_pad))
    return (u >= dropout_p).astype(jnp.float32)


def _operand_dtype(*refs):
    """What tiles read from ``refs`` enter the MXU as: bfloat16 as they were
    read where both sides of a product are, float32 for everything else (a
    float32 caller keeps float32 products)."""
    return (jnp.bfloat16 if all(r.dtype == jnp.bfloat16 for r in refs)
            else jnp.float32)


def _dot(a, b):
    """(M, K) @ (K, N) -> float32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """(M, K) @ (N, K)^T -> float32, contracted over both operands' own
    last dimension: no transposed copy of a tile is made."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _k_tile_ranges(xp, qi, *, block_q, block_k, sq, sk, causal, window=None):
    """The k-blocks q-block ``qi`` of the forward kernel walks, as
    ``(first, plain_lo, plain_hi, n_needed)``: tiles ``[first, plain_lo)``
    are crossed by the window's lower edge (none without a window: both
    are 0), every position of tiles ``[plain_lo, plain_hi)`` is valid,
    tiles ``[plain_hi, n_needed)`` are crossed by the diagonal or by a true
    length, the rest lies wholly above the diagonal or below the window.
    Integer arithmetic; ``xp`` is ``jnp`` inside a kernel (``qi`` a program
    id) and ``np`` on the host (``qi`` every q-block at once,
    ``_tile_counts``)."""
    n_needed, n_plain = -(-sk // block_k), sk // block_k
    if causal:
        n_needed = xp.minimum(
            n_needed, ((qi + 1) * block_q + block_k - 1) // block_k)
        n_plain = xp.minimum(n_plain, (qi * block_q + 1) // block_k)
    if sq % block_q:        # the last q-block holds rows past sq
        n_plain = xp.where((qi + 1) * block_q <= sq, n_plain, 0)
    if window is None:
        return 0, 0, n_plain, n_needed
    # row i sees keys (i - window, i]: the first tile that holds one of the
    # block's first row, the first whose every key the block's last row sees
    first = xp.minimum(xp.maximum(qi * block_q - window + 1, 0) // block_k,
                       n_needed)
    whole = xp.maximum((qi + 1) * block_q - window + block_k - 1,
                       0) // block_k
    plain_lo = xp.minimum(xp.maximum(whole, first), n_needed)
    plain_hi = xp.minimum(xp.maximum(n_plain, plain_lo), n_needed)
    return first, plain_lo, plain_hi, n_needed


def _q_tile_ranges(xp, j, *, block_q, block_k, sq, sk, causal, window=None):
    """The q-blocks k-block ``j`` of the backward kernel walks, as ``(q_start,
    plain_lo, plain_hi, q_end)``: tiles ``[q_start, plain_lo)`` are crossed
    by the diagonal (two of them where ``block_q < block_k``), every
    position of ``[plain_lo, plain_hi)`` is valid, and ``[plain_hi, cdiv(sq,
    block_q))`` is the one tile with rows past ``sq``, if there is one.
    ``q_end`` is None without a ``window``; under one, tiles ``[plain_hi,
    q_end)`` are crossed by the window's lower edge (or hold rows past
    ``sq``), and no row from ``q_end`` on sees a key of the block."""
    q_start, plain_lo, plain_hi = 0, 0, sq // block_q
    if causal:
        q_start = (j * block_k) // block_q
        plain_lo = xp.minimum(
            plain_hi, ((j + 1) * block_k + block_q - 2) // block_q)
    if sk % block_k:        # the last k-block holds keys past sk
        plain_lo = xp.where((j + 1) * block_k <= sk, plain_lo, plain_hi)
    if window is None:
        return q_start, plain_lo, plain_hi, None
    # the block's last key is seen up to row (j + 1) block_k + window - 2;
    # its first key by every row of a tile that ends by j block_k + window
    q_end = xp.minimum(-(-sq // block_q),
                       ((j + 1) * block_k + window - 2) // block_q + 1)
    plain_lo = xp.minimum(plain_lo, q_end)
    plain_hi = xp.minimum(xp.maximum(
        xp.minimum(plain_hi, (j * block_k + window) // block_q), plain_lo),
        q_end)
    return q_start, plain_lo, plain_hi, q_end


def _tile_counts(bh, *, block_q, block_k, sq, sk, causal, window=None):
    """(score tiles, tiles that run the masked body) of one forward call:
    the kernel's own bounds, added up over its grid on the host."""
    qi = np.arange(-(-sq // block_q))
    first, plain_lo, plain_hi, n_needed = (
        np.broadcast_to(n, qi.shape) for n in _k_tile_ranges(
            np, qi, block_q=block_q, block_k=block_k, sq=sq, sk=sk,
            causal=causal, window=window))
    tiles = int(np.sum(n_needed - first))
    plain = int(np.sum(plain_hi - plain_lo))
    return bh * tiles, bh * (tiles - plain)


def _walk(lo, hi, body, carry):
    """``fori_loop`` over tiles ``[lo, hi)``; no loop where the bounds are
    the same number at trace time."""
    if isinstance(lo, int) and isinstance(hi, int) and lo >= hi:
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _crossed_tiles(own, other, *, block_q, block_k, sq, sk, causal,
                   window=None):
    """How many tiles the diagonal crosses in every program of a kernel
    whose programs own blocks of ``own`` positions and walk blocks of
    ``other``, where the shapes alone say it: a square causal call of
    whole blocks, one block size a multiple of the other. None where the
    count differs from program to program (a true length inside a block;
    a window so narrow that its lower edge reaches the diagonal's tiles)."""
    if (causal and sq == sk and sq % block_q == 0 and sk % block_k == 0
            and (own % other == 0 or other % own == 0)
            and (window is None or window >= block_q + block_k)):
        return max(1, own // other)
    return None


def _walk_crossed(lo, hi, body, carry, count):
    """The masked tiles ``[lo, hi)``. Where their number is a fact of the
    shapes (``count``) they are straight-line code behind the plain loop,
    which the scheduler overlaps with the program's epilogue; a second loop
    in their place costs more than the masks it saves (PERF.md section 6,
    PR 32)."""
    if count is None:
        return _walk(lo, hi, body, carry)
    for t in range(count):
        carry = body(lo + t, carry)
    return carry


def _valid(shape, q0, k0, q_axis, *, sq, sk, block_q, block_k, causal,
           window=None):
    """Which positions of a score tile that starts at query ``q0`` and key
    ``k0`` (queries along ``q_axis``) may attend. A length that is a
    multiple of its block has no position past it: a fact of the shapes.
    Under a ``window`` a row sees itself and the ``window - 1`` positions
    before it."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    valid = None
    for term in ((q_pos < sq) if sq % block_q else None,
                 (k_pos < sk) if sk % block_k else None,
                 (q_pos >= k_pos) if causal else None,
                 (q_pos - k_pos < window) if window is not None else None):
        if term is not None:
            valid = term if valid is None else valid & term
    return valid


def _and_selected(check, tile):
    """``check`` (None: every position) and a selection's int8 tile, as
    the ``valid`` a kernel's step takes."""
    def both(shape):
        keep = tile.astype(jnp.float32) > 0.5
        return keep if check is None else keep & check(shape)
    return both


def _bd_rows(g, per_copy):
    """Block ``g`` of the ``2 * per_copy`` blocks of a two-copy side, as
    ``(clean, block within the copy)``: the noisy copy's blocks come
    first."""
    clean = g // per_copy
    return clean, g - clean * per_copy


def _bd_k_tile_ranges(qi, *, block_q, block_k, bd):
    """``_k_tile_ranges`` under the block-diffusion structure ``bd =
    (shift, q-blocks a copy, k-blocks a copy)``, for q-block ``qi`` of the
    two copies' rows, over the CLEAN copy's k-blocks: a clean row sees the
    clean blocks up to its own, a noisy row those before its own. (A noisy
    q-block walks its own noisy positions besides: ``_bd_own_tiles``.)"""
    block = 1 << bd[0]
    clean, pj = _bd_rows(qi, bd[1])
    n_plain = (pj * block_q + clean * block) // block_k
    n_needed = ((pj + 1) * block_q - (1 - clean) * block
                + block_k - 1) // block_k
    return n_plain, n_needed


def _bd_q_tile_ranges(xp, g, *, block_q, block_k, bd):
    """``_q_tile_ranges`` under the block structure, for program ``g`` of
    the backward kernel: it owns CLEAN k-block ``g mod k-blocks`` and walks the
    q-blocks of ONE copy, the noisy one for ``g`` under ``k-blocks`` (whose
    rows see only earlier blocks), then the clean one. ``(q_start,
    plain_lo, plain_hi)`` in blocks of that copy."""
    block, n_q = 1 << bd[0], bd[1]
    clean, j = _bd_rows(g, bd[2])
    off = 1 - clean
    q_start = (j * block_k + (1 + off) * block + block_q - 1) // block_q - 1
    plain_lo = ((j + 1) * block_k - (1 - off) * block
                + block_q - 1) // block_q
    q_start = xp.minimum(q_start, n_q)
    return q_start, xp.minimum(xp.maximum(plain_lo, q_start), n_q), n_q


def _bd_own_tiles(own, other):
    """(tiles, their width along ``other``'s axis) in which a block of
    ``own`` positions meets the same positions of the noisy copy."""
    width = min(own, other)
    return own // width, width


def _bd_crossed(own, other, shift):
    """``_crossed_tiles`` under the block structure: tiles of the clean
    side that every program masks, where the shapes alone say it."""
    if (1 << shift) < min(own, other) and (own % other == 0
                                          or other % own == 0):
        return max(1, own // other)
    return None


def _bd_valid(shape, q0, k0, q_axis, shift, off):
    """Which positions of a score tile may attend under the block
    structure; ``q0`` / ``k0`` are positions within a copy. Clean keys:
    key block + ``off`` <= query block (``off`` 0 for a clean row, 1 for a
    noisy one). ``off`` None: the noisy copy's keys, the row's own block
    alone. Positions from iotas and a shift, as the causal diagonal's."""
    q_blk = (q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)) >> shift
    k_blk = (k0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                           1 - q_axis)) >> shift
    return k_blk == q_blk if off is None else k_blk + off <= q_blk


def _bd_tile_counts(bh, length, *, block_q, block_k, shift):
    """(score tiles walked, tiles that run the masked body, tiles of the
    two-copy rectangle) of one forward call under the block structure: the
    kernel's own bounds added up over its grid, as ``_tile_counts``."""
    lp = _bd_padded(length, block_q, block_k)
    bd = (shift, lp // block_q, lp // block_k)
    qi = np.arange(2 * bd[1])
    n_plain, n_needed = _bd_k_tile_ranges(qi, block_q=block_q,
                                          block_k=block_k, bd=bd)
    own = bd[1] * _bd_own_tiles(block_q, block_k)[0]
    tiles = int(np.sum(n_needed)) + own
    return (bh * tiles, bh * (tiles - int(np.sum(n_plain))),
            bh * 4 * bd[1] * bd[2])


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, keep_ref, *rest,
                block_q, block_k, sq, sk, causal, scale, mask_mode,
                dropout_p, threshold, drop_mode, bd=None, window=None,
                selected=False):
    # q_ref: (1, BQ, D); k_ref: (1, SKp, D); v_ref: (1, SKp, DV);
    # mask_ref: (1, {1, BQ}, SKp); o_ref: (1, BQ, DV). Under the block
    # structure k_ref / v_ref are the CLEAN copy's side and ``own`` the
    # noisy copy's (1, BQ, D | DV) block at this q-block's positions;
    # under a selection ``own`` is its (1, 1, BQ, SKp) int8 rows
    *own, o_ref, m_ref, l_ref = rest
    geom = dict(block_q=block_q, block_k=block_k, sq=sq, sk=sk,
                causal=causal, window=window)
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    # scaled in float32 once a q-block, then rounded to what the MXU takes
    q = (q_ref[0].astype(jnp.float32) * scale).astype(
        _operand_dtype(q_ref, k_ref))
    v_dtype = _operand_dtype(v_ref)

    m0 = jnp.full((q.shape[0], 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q.shape[0], 1), jnp.float32)
    acc0 = jnp.zeros((q.shape[0], v_ref.shape[2]), jnp.float32)

    def step(k, v, carry, j, cols, valid):
        """One score tile of the online soft-max; ``valid`` (None: every
        position) is made behind the product."""
        m, l, acc = carry
        s = _dot_nt(q, k)
        if mask_mode in ("key", "full"):
            # rows are already positioned by the BlockSpec: a (1, BK) key
            # bias broadcasts down, a (BQ, BK) full bias adds elementwise
            s = s + mask_ref[0, :, cols].astype(jnp.float32)
        if valid is not None:
            s = jnp.where(valid(s.shape), s, _NEG_INF)
            if sk % block_k:
                # zero padded v rows: p is 0 there, but 0 * NaN-padding
                # would still poison the accumulator
                row_pos = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, 1), 0)
                v = jnp.where(row_pos < sk, v, jnp.zeros_like(v))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        m_safe = m_new
        if valid is not None or mask_mode is not None:
            # rows with every key masked so far: keep the exp argument
            # finite. exp(_NEG_INF - m_safe) is 0 without a second select
            m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(m - m_safe)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            if drop_mode == "prng":
                keep = _dropout_keep(seed_ref, bh, qi, j, p.shape,
                                     threshold)
            else:
                keep = keep_ref[0, :, cols] > 0.5
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        return m_new, l_new, acc * corr + _dot(p.astype(v.dtype), v)

    if bd is None:
        def valid(shape, j):
            return _valid(shape, qi * block_q, j * block_k, 0, **geom)

        first, plain_lo, n_plain, n_needed = _k_tile_ranges(jnp, qi, **geom)
        crossed = _crossed_tiles(block_q, block_k, **geom)
    else:
        first = plain_lo = 0
        clean, pj = _bd_rows(qi, bd[1])

        def valid(shape, j):
            return _bd_valid(shape, pj * block_q, j * block_k, 0, bd[0],
                             1 - clean)

        n_plain, n_needed = _bd_k_tile_ranges(qi, block_q=block_q,
                                              block_k=block_k, bd=bd)
        crossed = _bd_crossed(block_q, block_k, bd[0])

    def tile(j, carry, masked):
        cols = pl.ds(j * block_k, block_k)
        check = (lambda shape: valid(shape, j)) if masked else None
        if selected:    # every tile reads its part of the selection
            check = _and_selected(check, own[0][0, 0, :, cols])
        return step(k_ref[0, cols, :].astype(q.dtype),
                    v_ref[0, cols, :].astype(v_dtype), carry, j, cols,
                    check)

    # under a window, first the tiles its lower edge crosses
    carry = _walk(first, plain_lo, functools.partial(tile, masked=True),
                  (m0, l0, acc0))
    carry = _walk(plain_lo, n_plain, functools.partial(tile, masked=False),
                  carry)
    carry = _walk_crossed(
        n_plain, n_needed, functools.partial(tile, masked=True), carry,
        crossed)
    if bd is not None:
        # a noisy q-block's own positions in the noisy copy
        n_own, width = _bd_own_tiles(block_q, block_k)

        def own_tile(t, carry):
            cols = pl.ds(t * width, width)
            return step(
                own[0][0, cols, :].astype(q.dtype),
                own[1][0, cols, :].astype(v_dtype), carry, t, cols,
                lambda shape: _bd_valid(shape, pj * block_q,
                                        pj * block_q + t * width, 0, bd[0],
                                        None))

        carry = _walk(0, (1 - clean) * n_own, own_tile, carry)
    m, l, acc = carry
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    m_fin = jnp.where(m <= _NEG_INF, 0.0, m)
    m_ref[0] = _stat_row(m_fin)
    l_ref[0] = _stat_row(l)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, keep_ref,
                m_ref, linv_ref, delta_ref, do_ref, *rest,
                block_q, block_k, sq, sk, causal, scale, mask_mode,
                dropout_p, threshold, drop_mode, bd=None, window=None,
                selected=False):
    # this program owns ONE k-block (grid (bh, k-blocks)) and loops
    # q-blocks. q_ref: (1, SQp, D); do_ref: (1, SQp, DV); k_ref: (1, BK, D);
    # v_ref: (1, BK, DV); mask_ref: (1, {1, SQp}, BK); m/linv/delta:
    # (1, 1, SQp); dq_ref: (1, SQp, D); dqt_ref: (D, SQp) float32 scratch.
    # The score tile is computed TRANSPOSED, s^T = K Q^T (BK, BQ): the
    # (1, BQ) row statistics broadcast down its sublanes as they are read
    # (no relayout), p^T, ds^T are already the left operands of
    # dv = p^T dO and dk = ds^T Q, and ds^T is the right operand of
    # dq^T = K^T ds^T (no transpose of a score tile). dq^T of the whole q
    # side stays in ``dqt_ref`` while the grid's second axis walks the
    # head's k-blocks in ascending order: the first zeroes it, every one
    # adds its tiles' parts, the last stores it as dq.
    # Under the block structure (grid (bh, 2 x k-blocks a copy)) q_ref,
    # do_ref, the statistics, dq_ref and dqt_ref are ONE copy's rows,
    # k_ref / v_ref the clean copy's k-block and ``kn_ref`` / ``vn_ref`` the
    # noisy copy's at the same positions; dk and dv are the clean block's
    # part from that copy's rows and the noisy block's gradient (zero from
    # the clean rows).
    if selected:    # the selection TRANSPOSED, this k-block's (1, 1, BK, SQp)
        sel_ref, dq_ref, dk_ref, dv_ref, dqt_ref, kt_ref = rest
    elif bd is None:
        dq_ref, dk_ref, dv_ref, dqt_ref, kt_ref = rest
    else:
        (kn_ref, vn_ref, dq_ref, dk_ref, dv_ref, dkn_ref, dvn_ref,
         dqt_ref, kt_ref) = rest
    geom = dict(block_q=block_q, block_k=block_k, sq=sq, sk=sk,
                causal=causal, window=window)
    bh = pl.program_id(0)
    j = pl.program_id(1)
    # this k-block within its head (its copy): the accumulator's life
    pj, n_k = (j, pl.num_programs(1)) if bd is None else (
        _bd_rows(j, bd[2])[1], bd[2])
    k = k_ref[0].astype(_operand_dtype(q_ref, k_ref))
    v = v_ref[0].astype(_operand_dtype(v_ref, do_ref))
    if mask_mode == "key":      # (1, BK) key bias -> (BK, 1), once
        kbias = _stat_col(mask_ref[0].astype(jnp.float32))

    @pl.when(pj == 0)
    def _():
        dqt_ref[...] = jnp.zeros(dqt_ref.shape, jnp.float32)

    def step(k, v, qi, carry, valid):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        # the forward's rounding of the scaled q, so that s^T is its s
        # (k scaled once a program, or the float32 tile scaled, is no
        # faster and another rounding: PERF.md section 6, PR 32)
        q = (q_ref[0, rows, :].astype(jnp.float32) * scale).astype(k.dtype)
        do = do_ref[0, rows, :].astype(v.dtype)
        mrow = m_ref[0, :, rows]            # (1, BQ)
        linv = linv_ref[0, :, rows]
        delta = delta_ref[0, :, rows]
        st = _dot_nt(k, q)
        if mask_mode == "key":
            st = st + kbias
        elif mask_mode == "full":
            st = st + mask_ref[0, rows, :].astype(jnp.float32).T
        if valid is not None:
            st = jnp.where(valid(st.shape), st, _NEG_INF)
        # p = exp(s - m)/l: same rounding as the forward recurrence even
        # for ~1e9-scale masked scores (see module docstring); 0 at
        # _NEG_INF, whose row's m is finite
        pt = jnp.exp(st - mrow) * linv
        dpt = _dot_nt(v, do)
        pdt = pt
        if dropout_p > 0.0:
            # the forward's (BQ, BK) keep tile, transposed
            if drop_mode == "prng":
                keep = jnp.where(_dropout_keep(seed_ref, bh, qi, j,
                                               (block_q, block_k),
                                               threshold), 1.0, 0.0)
            else:
                keep = keep_ref[0, rows, :]
            keep = keep.T > 0.5
            pdt = jnp.where(keep, pt / (1.0 - dropout_p), 0.0)
            dpt = jnp.where(keep, dpt / (1.0 - dropout_p), 0.0)
        dv = dv + _dot(pdt.astype(do.dtype), do)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        # q above is pre-scaled, so ds^T @ (q·scale) is already dk; dq is
        # scaled at its store
        dk = dk + _dot(dst, q)
        dqt_ref[:, rows] += _dot(kt_ref[...], dst)
        return dk, dv

    def zeros():
        return (jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32))

    if bd is None:
        def valid(shape, qi):
            return _valid(shape, qi * block_q, j * block_k, 1, **geom)

        q_start, plain_lo, plain_hi, q_end = _q_tile_ranges(jnp, j, **geom)
        count = _crossed_tiles(block_k, block_q, **geom)
    else:
        clean = _bd_rows(j, bd[2])[0]

        def valid(shape, qi):
            return _bd_valid(shape, qi * block_q, pj * block_k, 1, bd[0],
                             1 - clean)

        q_start, plain_lo, plain_hi = _bd_q_tile_ranges(
            jnp, j, block_q=block_q, block_k=block_k, bd=bd)
        count = _bd_crossed(block_k, block_q, bd[0])

    def transposed(ref):
        """A k-block as (D, BK), the left operand of every dq^T part of
        its walk, made once and kept in VMEM beside the accumulator. It
        comes off the MXU like everything else here: I K^T contracts both
        operands' own last dimension and is K^T exactly, with no
        transposition for Mosaic to lower at a head size of 64 or 192."""
        d = ref.shape[2]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
        kt_ref[...] = _dot_nt(eye.astype(k.dtype),
                              ref[0].astype(k.dtype)).astype(kt_ref.dtype)

    transposed(k_ref)

    def tile(qi, carry, masked):
        check = (lambda shape: valid(shape, qi)) if masked else None
        if selected:
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            check = _and_selected(check, sel_ref[0, 0, :, rows])
        return step(k, v, qi, carry, check)

    plain, crossed = (functools.partial(tile, masked=m)
                      for m in (False, True))
    carry = _walk_crossed(q_start, plain_lo, crossed, zeros(), count)
    carry = _walk(plain_lo, plain_hi, plain, carry)
    if window is not None:
        # the tiles the window's lower edge crosses, and rows past sq
        carry = _walk(plain_hi, q_end, crossed, carry)
    elif bd is None:
        if sq % block_q:        # the one q-block with rows past sq
            carry = _walk(jnp.maximum(plain_hi, q_start), plain_hi + 1,
                          crossed, carry)
    else:
        # the noisy copy's k-block at these positions: its own rows alone
        kn = kn_ref[0].astype(k.dtype)
        transposed(kn_ref)
        vn = vn_ref[0].astype(v.dtype)
        first = (pj * block_k) // block_q
        dkn, dvn = _walk(
            first, first + (1 - clean) * max(1, block_k // block_q),
            lambda qi, c: step(
                kn, vn, qi, c,
                lambda shape: _bd_valid(shape, qi * block_q, pj * block_k,
                                        1, bd[0], None)),
            zeros())
        dkn_ref[0] = dkn.astype(dkn_ref.dtype)
        dvn_ref[0] = dvn.astype(dvn_ref.dtype)
    dk, dv = carry
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(pj == n_k - 1)
    def _():
        def store(qi, _):
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_ref[0, rows, :] = (dqt_ref[:, rows].T * scale).astype(
                dq_ref.dtype)

        jax.lax.fori_loop(0, dq_ref.shape[1] // block_q, store, None)


def _mask_mode(mask_shape, b, h, sq, sk):
    """Static tiling decision from the mask's 4D-normalized shape:
    'key' (broadcasts over queries), 'full', or 'fallback'."""
    if mask_shape is None:
        return None
    shape = (1,) * (4 - len(mask_shape)) + tuple(mask_shape)
    if len(shape) != 4:
        return "fallback"
    mb, mh, msq, msk = shape
    if msk != sk or mb not in (1, b) or mh not in (1, h) or \
            msq not in (1, sq):
        return "fallback"
    return "key" if msq == 1 else "full"


def _canon_mask(m):
    """Numeric canonicalization: bool→additive, f32, 4D."""
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, _NEG_INF).astype(jnp.float32)
    else:
        m = m.astype(jnp.float32)
    while m.ndim < 4:
        m = m[None]
    return m


def _mask_operand(mask, mode, h, sq_pad, sk_pad):
    """mask (mb,mh,msq,msk) → ((G, {1|SQp}, SKp) array, bh→G index fn)."""
    mb, mh, msq, msk = mask.shape
    pad_q = (sq_pad - msq) if mode == "full" else 0
    m = jnp.pad(mask, [(0, 0), (0, 0), (0, pad_q), (0, sk_pad - msk)])
    m3 = m.reshape(mb * mh, m.shape[2], sk_pad)

    def bh_to_g(i):
        if mb == 1 and mh == 1:
            return 0
        if mb == 1:
            return i % h
        if mh == 1:
            return i // h
        return i

    return m3, bh_to_g


def _clamped_blocks(block_q, block_k, sq, sk):
    """The blocks a call runs with: no larger than its lengths."""
    return min(block_q, max(sq, 8)), min(block_k, sk)


def _whole_side(shape, single, index=lambda i, j: (i, 0, 0)):
    """BlockSpec of an operand a program keeps whole: one (batch, head)'s
    other side, which changes only with the grid's first axis (under the
    block structure one COPY's side, ``index``: it changes once more a
    head). ``single``: one buffer in VMEM and no prefetch of the next
    head's while this one's last block runs (``_single_buffered``)."""
    mode = {"pipeline_mode": pl.Buffered(1)} if single else {}
    return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM, **mode)


def _pad_axis(x, axis, new):
    if x.shape[axis] == new:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, new - x.shape[axis])
    return jnp.pad(x, pads)


def _flash_fwd_res(q, k, v, mask, mask_mode, seed, causal, scale, block_q,
                   block_k, dropout_p, window=None, interpret=None,
                   selected=None):
    from . import interpret_mode
    b, h, sq, d = q.shape
    sk, dvh = k.shape[2], v.shape[3]
    bq, bk = _clamped_blocks(block_q, block_k, sq, sk)
    single = _single_buffered(max(sq, sk), d, dvh, q.dtype.itemsize)
    # pad K/V up to a block multiple: a manual pl.ds read past the end
    # CLAMPS its start (dynamic-slice semantics) and would silently re-read
    # earlier rows; the kernels mask positions >= the true sk
    sk_pad = -(-sk // bk) * bk
    sq_pad = -(-sq // bq) * bq
    q3 = q.reshape(b * h, sq, d)
    k3 = _pad_axis(k.reshape(b * h, sk, d), 1, sk_pad)
    v3 = _pad_axis(v.reshape(b * h, sk, dvh), 1, sk_pad)
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    threshold = min(int(dropout_p * 4294967296.0), 4294967295)

    if mask_mode in ("key", "full"):
        m3, bh_to_g = _mask_operand(mask, mask_mode, h, sq_pad, sk_pad)
        if mask_mode == "key":
            mspec = pl.BlockSpec((1, 1, sk_pad),
                                 lambda i, j: (bh_to_g(i), 0, 0),
                                 memory_space=pltpu.VMEM)
        else:  # block the query dim: only (BQ, SKp) of bias in VMEM
            mspec = pl.BlockSpec((1, bq, sk_pad),
                                 lambda i, j: (bh_to_g(i), j, 0),
                                 memory_space=pltpu.VMEM)
    else:
        m3 = jnp.zeros((1, 1, sk_pad), jnp.float32)
        mspec = pl.BlockSpec((1, 1, sk_pad), lambda i, j: (0, 0, 0),
                             memory_space=pltpu.VMEM)
    seed2 = jnp.asarray(seed, jnp.int32).reshape(2)
    interp = interpret_mode() if interpret is None else interpret
    drop_mode = "mask" if (interp and dropout_p > 0.0) else "prng"
    if drop_mode == "mask":
        keep3 = _host_keep_mask(seed2, b * h, sq_pad, sk_pad, dropout_p)
        kspec = pl.BlockSpec((1, bq, sk_pad), lambda i, j: (i, j, 0),
                             memory_space=pltpu.VMEM)
    else:
        keep3 = jnp.zeros((1, 1, 1), jnp.float32)
        kspec = pl.BlockSpec((1, 1, 1), lambda i, j: (0, 0, 0),
                             memory_space=pltpu.VMEM)

    # row statistics leave as (BH, 1, SQ): one value a row, seq on lanes
    stat_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j),
                             memory_space=pltpu.VMEM)
    call = dict(
        grid=(b * h, pl.cdiv(sq, bq)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            _whole_side((1, sk_pad, d), single),
            _whole_side((1, sk_pad, dvh), single),
            mspec,
            kspec,
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dvh), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            stat_spec,
            stat_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, dvh), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        interpret=interp)
    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, sq=sq, sk=sk, causal=causal,
        scale=s, mask_mode=mask_mode, dropout_p=dropout_p,
        threshold=threshold, drop_mode=drop_mode, window=window)
    # one kernel body, and a name a call form: a trace tells them apart
    operands = [seed2, q3, k3, v3, m3, keep3]
    if selected is not None:
        # a q-block's rows of the selection, every key: one int8 block a
        # program, shared by the heads of a sequence
        call["in_specs"].append(pl.BlockSpec(
            (1, 1, bq, sk_pad), lambda i, j: (i // h, 0, j, 0),
            memory_space=pltpu.VMEM))
        call["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_sel_fwd_vmem(sk_pad, d, dvh, q.dtype.itemsize,
                                           bq, bk, single))
        operands.append(_pad_axis(_pad_axis(selected, 2, sq_pad), 3, sk_pad))
        run = pl.pallas_call(functools.partial(kernel, selected=True),
                             name="flash_sel_fwd", **call)
    elif window is None:
        run = pl.pallas_call(kernel, name="flash_fwd", **call)
    else:
        run = pl.pallas_call(kernel, name="flash_win_fwd", **call)
    out, mrow, lrow = run(*operands)
    return out.reshape(b, h, sq, dvh), mrow, lrow


def _flash_bwd(q, k, v, mask, mask_mode, seed, out, mrow, lrow, g, causal,
               scale, block_q, block_k, dropout_p, window=None,
               interpret=None, selected_t=None):
    from . import interpret_mode
    b, h, sq, d = q.shape
    sk, dvh = k.shape[2], v.shape[3]
    bq, bk = _clamped_blocks(block_q, block_k, sq, sk)
    single = _single_buffered(max(sq, sk), d, dvh, q.dtype.itemsize)
    sk_pad = -(-sk // bk) * bk
    sq_pad = -(-sq // bq) * bq
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    threshold = min(int(dropout_p * 4294967296.0), 4294967295)

    q3 = q.reshape(b * h, sq, d)
    k3 = _pad_axis(k.reshape(b * h, sk, d), 1, sk_pad)
    v3 = _pad_axis(v.reshape(b * h, sk, dvh), 1, sk_pad)
    do3 = g.reshape(b * h, sq, dvh)
    # delta_i = Σ_d dO_id·O_id (= Σ_k p_ik·dp_ik — valid under dropout too)
    delta = jnp.sum(do3.astype(jnp.float32) *
                    out.reshape(b * h, sq, dvh).astype(jnp.float32),
                    axis=-1)[:, None, :]
    linv = 1.0 / jnp.maximum(lrow, 1e-20)
    # (BH, 1, SQp), as the forward wrote them: no lane replication
    stats = [_pad_axis(x, 2, sq_pad) for x in (mrow, linv, delta)]
    stat_all = _whole_side((1, 1, sq_pad), single)

    if mask_mode in ("key", "full"):
        m3, bh_to_g = _mask_operand(mask, mask_mode, h, sq_pad, sk_pad)
    else:
        m3 = jnp.zeros((1, 1, sk_pad), jnp.float32)
        bh_to_g = lambda i: 0
    seed2 = jnp.asarray(seed, jnp.int32).reshape(2)
    msq_blk = 1 if mask_mode != "full" else sq_pad
    interp = interpret_mode() if interpret is None else interpret
    drop_mode = "mask" if (interp and dropout_p > 0.0) else "prng"
    if drop_mode == "mask":
        keep3 = _host_keep_mask(seed2, b * h, sq_pad, sk_pad, dropout_p)
        kspec = pl.BlockSpec((1, sq_pad, bk), lambda i, j: (i, 0, j),
                             memory_space=pltpu.VMEM)
    else:
        keep3 = jnp.zeros((1, 1, 1), jnp.float32)
        kspec = pl.BlockSpec((1, 1, 1), lambda i, j: (0, 0, 0),
                             memory_space=pltpu.VMEM)

    # the whole-Q operands padded to the block multiple
    q3p = _pad_axis(q3, 1, sq_pad)
    do3p = _pad_axis(do3, 1, sq_pad)

    def k_block(width):
        return pl.BlockSpec((1, bk, width), lambda i, j: (i, j, 0),
                            memory_space=pltpu.VMEM)

    call = dict(
        grid=(b * h, pl.cdiv(sk, bk)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _whole_side((1, sq_pad, d), single),
            k_block(d),
            k_block(dvh),
            pl.BlockSpec((1, msq_blk, bk), lambda i, j: (bh_to_g(i), 0, j),
                         memory_space=pltpu.VMEM),
            kspec,
            stat_all,
            stat_all,
            stat_all,
            _whole_side((1, sq_pad, dvh), single),
        ],
        # dq's block changes with the head alone: it is written once, by
        # the head's last k-block, from the accumulator
        out_specs=[
            pl.BlockSpec((1, sq_pad, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            k_block(d),
            k_block(dvh),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk_pad, dvh), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, sq_pad), jnp.float32),
                        pltpu.VMEM((d, bk), k.dtype)],
        compiler_params=_bwd_params(
            sq_pad, d, dvh, q.dtype.itemsize, bq, bk, single,
            extra=(msq_blk + (sq_pad if drop_mode == "mask" else 0)
                   + (0 if selected_t is None else sq_pad // 4)) * bk),
        interpret=interp)
    kernel = functools.partial(
        _bwd_kernel, block_q=bq, block_k=bk, sq=sq, sk=sk, causal=causal,
        scale=s, mask_mode=mask_mode, dropout_p=dropout_p,
        threshold=threshold, drop_mode=drop_mode, window=window)
    operands = [seed2, q3p, k3, v3, m3, keep3, *stats, do3p]
    if selected_t is not None:
        # the selection transposed: this k-block's keys, every row
        call["in_specs"].append(pl.BlockSpec(
            (1, 1, bk, sq_pad), lambda i, j: (i // h, 0, j, 0),
            memory_space=pltpu.VMEM))
        operands.append(_pad_axis(_pad_axis(selected_t, 2, sk_pad), 3,
                                  sq_pad))
        run = pl.pallas_call(functools.partial(kernel, selected=True),
                             name="flash_sel_bwd", **call)
    elif window is None:
        run = pl.pallas_call(kernel, name="flash_bwd", **call)
    else:
        run = pl.pallas_call(kernel, name="flash_win_bwd", **call)
    dq, dk, dv = run(*operands)
    dq = dq[:, :sq].reshape(b, h, sq, d)
    dk = dk[:, :sk].reshape(b, h, sk, d)
    dv = dv[:, :sk].reshape(b, h, sk, dvh)
    return dq, dk, dv


# (o, the two statistic rows): the names a checkpoint policy may keep.
RESULT_NAMES = ("flash_out", "flash_stats")


def _named_results(out, mrow, lrow):
    """A vjp-forward's results under ``RESULT_NAMES``. The rule returns the
    named ``out`` to its caller AND saves it for the backward, so a policy
    that keeps the name keeps the one value both read."""
    from jax.ad_checkpoint import checkpoint_name
    from ... import monitor
    monitor.counter("flash_attention.results_named").inc()
    o_name, stats_name = RESULT_NAMES
    return (checkpoint_name(out, o_name), checkpoint_name(mrow, stats_name),
            checkpoint_name(lrow, stats_name))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 6, 7, 8, 9, 10))
def _flash(q, k, v, mask, mask_mode, seed, causal, scale, block_q, block_k,
           dropout_p):
    out, _, _ = _flash_fwd_res(q, k, v, mask, mask_mode, seed, causal,
                               scale, block_q, block_k, dropout_p)
    return out


def _fwd(q, k, v, mask, mask_mode, seed, causal, scale, block_q, block_k,
         dropout_p):
    out, mrow, lrow = _named_results(*_flash_fwd_res(
        q, k, v, mask, mask_mode, seed, causal, scale, block_q, block_k,
        dropout_p))
    return out, (q, k, v, mask, seed, out, mrow, lrow)


def _count_backward(tiles):
    """One call site's backward is being traced: the one kernel that makes
    each of ``tiles`` score tiles once and takes five products from it."""
    from ... import monitor
    monitor.counter("flash_attention.backward_fused_traced").inc()
    monitor.counter("flash_attention.backward_products").inc(5 * tiles)


def _bwd(mask_mode, causal, scale, block_q, block_k, dropout_p, res, g):
    q, k, v, mask, seed, out, mrow, lrow = res
    bq, bk = _clamped_blocks(block_q, block_k, q.shape[2], k.shape[2])
    _count_backward(_tile_counts(q.shape[0] * q.shape[1], block_q=bq,
                                 block_k=bk, sq=q.shape[2], sk=k.shape[2],
                                 causal=causal)[0])
    dq, dk, dv = _flash_bwd(q, k, v, mask, mask_mode, seed, out, mrow,
                            lrow, g, causal, scale, block_q, block_k,
                            dropout_p)
    # mask is an input-derived bias — not differentiated (reference parity)
    dmask = None if mask is None else jnp.zeros_like(mask)
    dseed = np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, dmask, dseed


_flash.defvjp(_fwd, _bwd)


_NO_SEED = np.zeros((2,), np.int32)


# The windowed calls sit behind module-level ``jax.jit``s: a model has one
# call site a window layer, each traced twice where its block is recomputed
# (forward and backward; three times under ``policy="full"``, whose replay
# runs the forward again), and every ``pl.pallas_call`` instance is lowered
# to Mosaic in Python in every process's set-up. JAX lowers an inner jit
# once a module for equal shapes, so the twelve instances of six window
# layers become two (PERF.md section 6, PR 38; ``interpret`` is an argument
# because the cached trace outlives a change of the mode).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _win_fwd(q, k, v, window, scale, block_q, block_k, interpret):
    return _flash_fwd_res(q, k, v, None, None, _NO_SEED, True, scale,
                          block_q, block_k, 0.0, window=window,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11))
def _win_bwd(q, k, v, out, mrow, lrow, g, window, scale, block_q, block_k,
             interpret):
    return _flash_bwd(q, k, v, None, None, _NO_SEED, out, mrow, lrow, g,
                      True, scale, block_q, block_k, 0.0, window=window,
                      interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_win(q, k, v, window, scale, block_q, block_k):
    """Causal attention in which a row sees itself and the ``window - 1``
    positions before it: the kernels of ``_flash`` with one more fact of
    the call, named ``flash_win_fwd`` / ``flash_win_bwd``."""
    from . import interpret_mode
    return _win_fwd(q, k, v, window, scale, block_q, block_k,
                    interpret_mode())[0]


def _win_vjp_fwd(q, k, v, window, scale, block_q, block_k):
    from . import interpret_mode
    out, mrow, lrow = _named_results(*_win_fwd(
        q, k, v, window, scale, block_q, block_k, interpret_mode()))
    return out, (q, k, v, out, mrow, lrow)


def _win_vjp_bwd(window, scale, block_q, block_k, res, g):
    from . import interpret_mode
    q, k = res[:2]
    bq, bk = _clamped_blocks(block_q, block_k, q.shape[2], k.shape[2])
    _count_backward(_tile_counts(q.shape[0] * q.shape[1], block_q=bq,
                                 block_k=bk, sq=q.shape[2], sk=k.shape[2],
                                 causal=True, window=window)[0])
    return _win_bwd(*res, g, window, scale, block_q, block_k,
                    interpret_mode())


_flash_win.defvjp(_win_vjp_fwd, _win_vjp_bwd)


# Under a SELECTION (PR 47): causal attention in which row t reads the keys
# ``selected[b, 0, t, :]`` marks (int8, one array a sequence, shared by its
# heads; ``F.dsa_select`` makes it). The two kernel bodies with one more
# static parameter and one more operand, named ``flash_sel_fwd`` /
# ``flash_sel_bwd`` and behind module-level ``jax.jit``s as the windowed
# calls: the forward reads a q-block's rows of the selection (BQ x S bytes a
# program), the backward a k-block's rows of its TRANSPOSE (made once a
# backward call by XLA, S x S bytes each way), one tile beside each score
# tile. Every causal tile is walked: a token-level selection empties none.
# The call returns its statistics too: the indexer's loss reads them.
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _sel_fwd(q, k, v, selected, scale, block_q, block_k, interpret):
    return _flash_fwd_res(q, k, v, None, None, _NO_SEED, True, scale,
                          block_q, block_k, 0.0, interpret=interpret,
                          selected=selected)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _sel_bwd(q, k, v, selected, out, mrow, lrow, g, scale, block_q, block_k,
             interpret):
    return _flash_bwd(q, k, v, None, None, _NO_SEED, out, mrow, lrow, g,
                      True, scale, block_q, block_k, 0.0,
                      interpret=interpret,
                      selected_t=jnp.swapaxes(selected, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_sel(q, k, v, selected, scale, block_q, block_k):
    """``(o, m, l)`` of causal attention over the selected keys."""
    from . import interpret_mode
    return _sel_fwd(q, k, v, selected, scale, block_q, block_k,
                    interpret_mode())


def _sel_vjp_fwd(q, k, v, selected, scale, block_q, block_k):
    from . import interpret_mode
    out, mrow, lrow = _named_results(*_sel_fwd(
        q, k, v, selected, scale, block_q, block_k, interpret_mode()))
    return (out, mrow, lrow), (q, k, v, selected, out, mrow, lrow)


def _sel_vjp_bwd(scale, block_q, block_k, res, g):
    from . import interpret_mode
    q, k, v, selected = res[:4]
    bq, bk = _clamped_blocks(block_q, block_k, q.shape[2], k.shape[2])
    _count_backward(_tile_counts(q.shape[0] * q.shape[1], block_q=bq,
                                 block_k=bk, sq=q.shape[2], sk=k.shape[2],
                                 causal=True)[0])
    grads = _sel_bwd(*res, g[0], scale, block_q, block_k, interpret_mode())
    return (*grads, np.zeros(selected.shape, jax.dtypes.float0))


_flash_sel.defvjp(_sel_vjp_fwd, _sel_vjp_bwd)


def _sel_fwd_vmem(sk, d, dv, itemsize, block_q, block_k, single):
    """The VMEM a forward call under a selection may use: the whole k / v
    side (one buffer or two), two buffers of a q-block's int8 rows of the
    selection, its own blocks and a few float32 score tiles."""
    side = sk * (_lanes(d) + _lanes(dv)) * itemsize
    need = ((1 if single else 2) * side + 2 * block_q * sk
            + 4 * block_q * (_lanes(d) + _lanes(dv)) * itemsize
            + 8 * block_q * block_k * 4 + 4 * 1024 * 1024)
    return max(int(need), _DEFAULT_VMEM_BYTES)


def sliding_window_mask(length, window):
    """bool ``[length, length]``, True where row i may attend to key j:
    ``j <= i`` and ``i - j < window`` (a row sees itself and the ``window
    - 1`` positions before it). The kernels never build it: the portable
    path and the tests do."""
    at = np.arange(length, dtype=np.int32)
    gap = at[:, None] - at[None, :]
    return (gap >= 0) & (gap < window)


def block_diffusion_mask(length, block):
    """bool ``[2 length, 2 length]``, True where row r may attend to row s
    of a sequence laid out as its noisy copy (rows ``[0, length)``) in
    front of its clean copy: with position ``p = r mod length`` and block
    ``b = p // block``, a clean key of an EARLIER block, or a key of the
    row's own copy in its own block (Arriola et al., arXiv:2503.09573,
    section 3: block-diagonal, offset block-causal, block-causal). The
    kernels never build it: the portable path and the tests do."""
    at = np.arange(2 * length)
    clean, blk = at >= length, (at % length) // block
    return ((clean[None, :] & (blk[None, :] < blk[:, None]))
            | ((clean[None, :] == clean[:, None])
               & (blk[None, :] == blk[:, None])))


def _bd_blocks(block_q, block_k, length, shift):
    """The blocks a block-structured call runs with: no larger than a
    copy, whole diffusion blocks, one a multiple of the other."""
    block = 1 << shift
    bq, bk = (max(b // block * block, block)
              for b in _clamped_blocks(block_q, block_k, length, length))
    if bq % bk and bk % bq:
        bk = bq
    return bq, bk


def _bd_padded(length, block_q, block_k):
    """A copy's length in whole blocks of both sizes."""
    big = max(block_q, block_k)
    return -(-length // big) * big


def _bd_sides(x, lp):
    """(B, H, 2 L, D) -> (B H, 2 Lp, D): each copy padded with zero rows
    to ``lp``. No position past L needs a mask: L is whole diffusion
    blocks, so the structure keeps every true row off the padding's
    blocks, and a padded row's q and dO are zero."""
    b, h, s2, d = x.shape
    x = _pad_axis(x.reshape(b * h, 2, s2 // 2, d), 2, lp)
    return x.reshape(b * h, 2 * lp, d)


def _bd_unpad(x, b, h, length):
    """(B H, 2 Lp, D) -> (B, H, 2 L, D)."""
    bh, s2, d = x.shape
    return x.reshape(bh, 2, s2 // 2, d)[:, :, :length].reshape(
        b, h, 2 * length, d)


class _BdPlan:
    """What the three block-structured calls share: the blocks, a copy's
    padded length ``lp``, ``bd`` for the kernels, the block specs of the
    two-copy ``(B H, 2 Lp, width)`` arrays, and the unused seed / mask /
    dropout operands with their specs."""

    def __init__(self, q, v, shift, scale, block_q, block_k):
        d, length = q.shape[3], q.shape[2] // 2
        self.bq, self.bk = _bd_blocks(block_q, block_k, length, shift)
        self.lp = _bd_padded(length, self.bq, self.bk)
        self.n_q, self.n_k = self.lp // self.bq, self.lp // self.bk
        self.bd = (shift, self.n_q, self.n_k)
        self.scale = scale if scale is not None else 1.0 / np.sqrt(d)
        self.single = _single_buffered(length, d, v.shape[3],
                                       q.dtype.itemsize)
        self.unused = (jnp.zeros((2,), jnp.int32),
                       jnp.zeros((1, 1, 1), jnp.float32))
        self.seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        self.zero_spec = pl.BlockSpec((1, 1, 1), lambda i, j: (0, 0, 0),
                                      memory_space=pltpu.VMEM)
        self.stat_q = pl.BlockSpec((1, 1, self.bq), lambda i, j: (i, 0, j),
                                   memory_space=pltpu.VMEM)

    def body(self, kernel):
        return functools.partial(
            kernel, block_q=self.bq, block_k=self.bk, sq=self.lp, sk=self.lp,
            causal=False, scale=self.scale, mask_mode=None, dropout_p=0.0,
            threshold=0, drop_mode="prng", bd=self.bd)

    def block(self, rows, width, index=lambda i, j: (i, j, 0)):
        return pl.BlockSpec((1, rows, width), index,
                            memory_space=pltpu.VMEM)

    def own(self, width):
        """The noisy copy's block at a q-block's positions; a clean
        q-block keeps the last noisy one (no copy)."""
        last = self.n_q - 1
        return self.block(self.bq, width,
                          lambda i, j: (i, jnp.minimum(j, last), 0))

    def clean_side(self, width):
        return _whole_side((1, self.lp, width), self.single,
                           lambda i, j: (i, 1, 0))


def _bd_fwd_res(q, k, v, shift, scale, block_q, block_k):
    from . import interpret_mode
    b, h, _, d = q.shape
    dvh = v.shape[3]
    p = _BdPlan(q, v, shift, scale, block_q, block_k)
    q3, k3, v3 = (_bd_sides(x, p.lp) for x in (q, k, v))
    out, mrow, lrow = pl.pallas_call(
        p.body(_fwd_kernel), grid=(b * h, 2 * p.n_q),
        in_specs=[p.seed_spec, p.block(p.bq, d), p.clean_side(d),
                  p.clean_side(dvh), p.zero_spec, p.zero_spec, p.own(d),
                  p.own(dvh)],
        out_specs=[p.block(p.bq, dvh), p.stat_q, p.stat_q],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, 2 * p.lp, dvh), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, 2 * p.lp), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 1, 2 * p.lp), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="flash_bd_fwd",
    )(p.unused[0], q3, k3, v3, p.unused[1], p.unused[1], k3, v3)
    return _bd_unpad(out, b, h, q.shape[2] // 2), mrow, lrow


def _bd_bwd(q, k, v, out, mrow, lrow, g, shift, scale, block_q, block_k):
    from . import interpret_mode
    b, h, s2, d = q.shape
    dvh, length = v.shape[3], s2 // 2
    p = _BdPlan(q, v, shift, scale, block_q, block_k)
    lp, n_k = p.lp, p.n_k
    seed, zero = p.unused
    q3, k3, v3, do3, o3 = (_bd_sides(x, lp) for x in (q, k, v, g, out))
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]
    stats = [mrow, 1.0 / jnp.maximum(lrow, 1e-20), delta]   # (BH, 1, 2 Lp)
    interp = interpret_mode()

    # program g: clean k-block g mod n_k against the rows of copy g // n_k
    # (the noisy copy first), and for the noisy rows the noisy k-block at
    # the same positions; dq's block and its accumulator follow the copy
    def copy_side(width):
        return _whole_side((1, lp, width), p.single,
                           lambda i, j: (i, j // n_k, 0))

    stat_all = _whole_side((1, 1, lp), p.single,
                           lambda i, j: (i, 0, j // n_k))

    def clean_block(width):
        return p.block(p.bk, width, lambda i, j: (i, n_k + j % n_k, 0))

    def noisy_block(width):
        return p.block(p.bk, width, lambda i, j: (i, j % n_k, 0))

    dq, *parts = pl.pallas_call(
        p.body(_bwd_kernel), grid=(b * h, 2 * n_k),
        in_specs=[p.seed_spec, copy_side(d), clean_block(d),
                  clean_block(dvh), p.zero_spec, p.zero_spec, stat_all,
                  stat_all, stat_all, copy_side(dvh), noisy_block(d),
                  noisy_block(dvh)],
        out_specs=[p.block(lp, d, lambda i, j: (i, j // n_k, 0))]
        + [p.block(p.bk, d), p.block(p.bk, dvh)] * 2,
        out_shape=[jax.ShapeDtypeStruct((b * h, 2 * lp, d), q.dtype)]
        + [jax.ShapeDtypeStruct((b * h, 2 * lp, d), k.dtype),
           jax.ShapeDtypeStruct((b * h, 2 * lp, dvh), v.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((d, lp), jnp.float32),
                        pltpu.VMEM((d, p.bk), k.dtype)],
        compiler_params=_bwd_params(lp, d, dvh, q.dtype.itemsize, p.bq,
                                    p.bk, p.single, blocks=2),
        interpret=interp,
        name="flash_bd_bwd",
    )(seed, q3, k3, v3, zero, zero, *stats, do3, k3, v3)

    def both(clean_parts, noisy):
        """[noisy copy's gradient ; the clean copy's, its two parts (from
        the noisy and from the clean rows) added in float32]."""
        two = clean_parts.reshape(b * h, 2, lp, -1).astype(jnp.float32)
        clean = (two[:, 0] + two[:, 1]).astype(noisy.dtype)
        return jnp.concatenate([noisy[:, :lp], clean], 1)

    dk, dv = both(parts[0], parts[2]), both(parts[1], parts[3])
    return tuple(_bd_unpad(x, b, h, length) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bd(q, k, v, shift, scale, block_q, block_k):
    return _bd_fwd_res(q, k, v, shift, scale, block_q, block_k)[0]


def _bd_vjp_fwd(q, k, v, shift, scale, block_q, block_k):
    out, mrow, lrow = _named_results(*_bd_fwd_res(
        q, k, v, shift, scale, block_q, block_k))
    return out, (q, k, v, out, mrow, lrow)


def _bd_vjp_bwd(shift, scale, block_q, block_k, res, g):
    q = res[0]
    length = q.shape[2] // 2
    bq, bk = _bd_blocks(block_q, block_k, length, shift)
    _count_backward(_bd_tile_counts(q.shape[0] * q.shape[1], length,
                                    block_q=bq, block_k=bk, shift=shift)[0])
    return _bd_bwd(*res, g, shift, scale, block_q, block_k)


_flash_bd.defvjp(_bd_vjp_fwd, _bd_vjp_bwd)


# Each kernel keeps the whole other side of one (batch, head) in VMEM (the
# forward K and V; the backward Q, dO and three rows of statistics, and
# beside them dq's block and dq's float32 accumulator; under the
# block-diffusion structure ONE copy's side, so ``seq`` below is a copy's
# length), beside its own blocks and a few float32 (BQ, BK) score tiles. The
# forward lives in the 16 MiB a kernel may use unasked; the backward asks
# for what its shapes need (``_bwd_params``: 30.5 MiB allowed and 16.7 taken
# at 8,192 x 128, 34.3 and 23.9 at 8,192 x 192 | 128, 31.5 and 22.1 under
# the block structure, 38.5 and 28.7 at 16,384 x 128 (30.3 taken under a
# window, whose forward takes 12.0 of the default 16), the default at
# BERT's seq 512; a v5e core has 128). What a tile costs beyond its
# products is paid once an inner iteration (the fill and drain of the MXU
# and of the cross-lane reductions; about ten operations on (BQ, 1)
# columns), so the largest tiles that fit are the fastest: alone on a v5e,
# 32 heads x 8,192 causal, forward / backward ms at 192 | 128: 256 x 256
# 11.76 / 16.99, 256 x 512 7.45 / 16.78, 512 x 512 7.18 / 15.37; at
# 128 | 128: 256 x 256 9.22 / 10.65, 256 x 512 4.99 / 10.00, 512 x 512
# 4.74 / 9.16 (PERF.md section 7, row 29).
# Compiled for a v5e at 8,192 positions in bfloat16:
# * up to 4,096 positions at these head sizes (under 4 MiB a side) the
#   blocks stay as asked (512 x 1,024), the whole side double-buffered.
# * head size 128 for q/k and v (4 MiB a side): BK = 512, double-buffered.
#   (Under its own limit the backward takes BK = 1,024 too and is no faster,
#   9.43 ms; the forward is, 4.42: larger tiles are another change.)
# * head size 192 for q/k, 128 for v (multi-head latent attention; 5 MiB a
#   side as counted here, 6 in VMEM, where 192 lanes take two tiles of
#   128): double-buffered, nothing above 256 x 256 fits the forward inside
#   the joyai_llm_flash step. The whole side changes once in 16 to 32
#   programs, so it is held in ONE buffer there: the next head's 5 MiB are
#   fetched when the last block of this one is done (0.3 ms a forward call
#   of 7.2, measured), and 512 x 512 fits, alone and inside the step. At
#   4 MiB and under one buffer only costs (23.5 -> 24.2 ms at 128 | 128;
#   +12 % on the backward at BERT's seq 512, where every program has a new
#   side).
# * two copies of 8,192 x 128 under the block structure: a copy's side is
#   the 4 MiB of the causal 8k call and takes its answer, 512 x 512 with
#   two buffers. Alone on a v5e, forward / backward ms: 512 x 512 10.48 /
#   22.82, 256 x 512 10.88 / 24.62, 512 x 256 16.00 / 23.91.
# * 16,384 x 128 (8 MiB a side, one buffer), causal and under a sliding
#   window of 4,096 (28 heads; smallthinker's two kinds of layer): 512 x 512
#   in both. Alone on a v5e, forward / backward ms: causal 512 x 512 16.12 /
#   30.40, 256 x 512 16.57 / 33.33; window 512 x 512 8.39 / 15.57, 256 x 512
#   8.68 / 16.91, 512 x 256 12.92 / 16.23, 256 x 256 15.22 / 17.41. A
#   windowed program reads W + BQ rows of the side it holds whole, and the
#   whole side stays: over its q-blocks a head reads every row of it, so a
#   moving span would save only the exposed part of the one-buffer fetch
#   (8.4 MB a head, 0.29 ms of a forward call's 8.39 at the most) for manual
#   DMA of overlapping row ranges; a windowed call takes 0.52 of the causal
#   call's time for 0.477 of its tiles (PERF.md section 7, row 29).
_WHOLE_SIDE_BYTES = 4 * 1024 * 1024
_DEFAULT_VMEM_BYTES = 16 * 1024 * 1024      # what a kernel may use unasked


def _side_bytes(seq, d, dv, itemsize):
    return seq * (d + dv) * itemsize


def _blocks_that_fit(seq, d, dv, itemsize, block_q, block_k):
    """(block_q, block_k) as asked, or as large as scoped VMEM holds beside
    the whole other side of ``seq`` positions at head sizes ``d`` (q, k)
    and ``dv`` (v, o)."""
    if _side_bytes(seq, d, dv, itemsize) >= _WHOLE_SIDE_BYTES:
        block_k = min(block_k, 512)
    return block_q, block_k


def _single_buffered(seq, d, dv, itemsize):
    """Whether the kernels hold the whole other side in one VMEM buffer:
    where two of them would leave no room for 512 x 512 tiles."""
    return _side_bytes(seq, d, dv, itemsize) > _WHOLE_SIDE_BYTES


def _lanes(width):
    """A last dimension as VMEM holds it: whole 128-lane tiles."""
    return -(-width // _TILE) * _TILE


def _bwd_params(seq, d, dv, itemsize, block_q, block_k, single, blocks=1,
                extra=0):
    """Compiler parameters of the backward call: the grid's second axis
    carries dq's accumulator from one k-block of a head to the next, and
    the VMEM it may use is what its shapes need, not the default 16 MiB
    (a v5e core has 128): the whole q side of ``seq`` rows (q, dO, three
    statistic rows of 8 sublanes; one buffer or two), dq's block, its
    float32 accumulator, ``blocks`` sets of the k-block's operands and
    results, ``extra`` float32 of mask and keep-mask a block, and the
    float32 (BK, BQ) tiles a step holds."""
    side = seq * ((_lanes(d) + _lanes(dv)) * itemsize + 3 * 8 * 4)
    dq = 2 * seq * _lanes(d) * itemsize + -(-d // 8) * 8 * seq * 4
    own = 2 * 2 * blocks * block_k * (_lanes(d) + _lanes(dv)) * itemsize
    tiles = 8 * block_q * block_k * 4
    need = ((1 if single else 2) * side + dq + own + 2 * extra * 4 + tiles
            + 4 * 1024 * 1024)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(int(need), _DEFAULT_VMEM_BYTES))


def flash_attention(q, k, v, attn_mask=None, causal=False, scale=None,
                    block_q=512, block_k=1024, dropout_p=0.0,
                    training=False, force=False, diffusion_block=None,
                    window=None, selected=None, name=None):
    """Framework op: flash attention over q, k (B, H, S, D) and v (B, H,
    S, DV); the result is (B, H, Sq, DV). The kernels take any head sizes:
    ``DV`` may differ from ``D`` (multi-head latent attention: 192 and
    128), and a size that is no multiple of 128 lanes is a block's whole
    last dimension (BERT's 64; 192), nothing is padded in HBM by this op.
    The additive (or bool) attn_mask and attention-probability dropout are
    fused into the kernels; mask shapes the kernel can't tile
    (non-broadcastable to (B,H,Sq,Sk)) fall back to plain sdpa with
    identical semantics. Off-TPU the op also falls back to sdpa (the
    interpret-mode kernel is emulator-speed) unless force=True (kernel
    correctness tests).

    ``diffusion_block`` (a power of two) gives the block-diffusion
    structure in place of a mask: q, k and v hold TWO copies of a sequence
    of ``S / 2`` positions, the noisy one in rows ``[0, S / 2)`` and the
    clean one behind it, and a row attends as :func:`block_diffusion_mask`
    says. The structure is a fact of the call, not an array: the kernels
    hold the clean copy's side, walk only the tiles that hold an allowed
    pair and mask only those the structure crosses; the portable path
    builds the dense mask. No ``attn_mask``, ``causal`` or dropout beside
    it.

    ``window`` beside ``causal=True`` is a sliding window
    (:func:`sliding_window_mask`): a row sees itself and the ``window - 1``
    positions before it. A fact of the call too: the kernels (named
    ``flash_win_fwd`` / ``flash_win_bwd``) walk the tiles between the
    window's lower edge and the diagonal and mask those either crosses; a
    window that holds the whole sequence is a causal call. No
    ``attn_mask``, dropout or ``diffusion_block`` beside it.

    ``selected`` beside ``causal=True`` is a data-dependent SELECTION, an
    int8 (or bool) array ``[B, 1, S, S]`` shared by a sequence's heads:
    row t reads the keys ``selected[b, 0, t, :]`` marks, and of those the
    ones at or before it (``F.dsa_select`` makes one, a learned sparse
    attention's ``top_k`` keys a row). An operand, unlike the three facts
    above: the kernels (named ``flash_sel_fwd`` / ``flash_sel_bwd``) read
    one int8 tile beside each score tile and walk every causal tile. The
    result of such a call is ``(o, m, l)``: beside ``o`` the soft-max
    statistics ``[B * H, 1, S]`` float32 as the kernels keep them, a row's
    largest selected (scaled) score and the sum of ``exp(score - m)``,
    without a gradient (``F.dsa_indexer_loss`` reads them). No
    ``attn_mask``, dropout, ``window`` or ``diffusion_block`` beside it.
    Counters ``flash_attention.selected_tiles_walked`` (the forward
    kernel's own bounds added up, as ``flash_attention.tiles``) and
    ``.selected_tiles_causal`` (the tiles that hold a causal pair).

    ``monitor`` counters ``flash_attention.kernel_traced`` /
    ``flash_attention.xla_traced`` count the call sites that traced each
    path; per kernel call site ``flash_attention.tiles``, ``flash_attention.
    tiles_masked`` and ``flash_attention.tiles_skipped`` add the forward
    kernel's score tiles, those of them that run the masked body and the
    tiles of the whole rectangle it does not walk
    (``flash_attention.window_tiles`` / ``.window_tiles_skipped``: the
    windowed call sites' part of the first and the last;
    ``.window_tiles_needed``: the least tiles of the call's size that could
    hold the pairs its window allows),
    ``flash_attention.native_operands_traced`` counts the call sites whose
    products take bfloat16 operands, and where a call site's backward is
    traced ``flash_attention.backward_fused_traced`` counts it and
    ``flash_attention.backward_products`` adds the five products of each
    of its tiles (the one backward kernel walks the forward's tiles)."""
    from ...dispatch import apply
    from ... import monitor
    from ... import random as prandom
    from . import enabled

    b, h, sq, d = q.shape
    sk = k.shape[2]
    p_drop = float(dropout_p) if training else 0.0
    has_mask = attn_mask is not None
    held = max(sq, sk)          # the side a program keeps whole
    if diffusion_block is not None:
        block = int(diffusion_block)
        held = sq // 2
        if has_mask or causal or p_drop or sq != sk or sq % 2 \
                or block < 1 or block & (block - 1) or held % block:
            raise ValueError(
                f"flash_attention: diffusion_block={diffusion_block} takes "
                f"two copies of whole blocks of a power of two along S "
                f"(q {sq}, k {sk} rows) and no mask, causal or dropout")
    if window is not None:
        if has_mask or not causal or p_drop or sq != sk \
                or diffusion_block is not None or int(window) < 1:
            raise ValueError(
                f"flash_attention: window={window} takes causal=True over "
                f"one sequence (q {sq}, k {sk} rows) and no mask, dropout "
                f"or diffusion_block")
        window = int(window) if window < sq else None
    if selected is not None:
        if has_mask or not causal or p_drop or sq != sk \
                or diffusion_block is not None or window is not None \
                or tuple(selected.shape) != (b, 1, sq, sk):
            raise ValueError(
                f"flash_attention: selected {tuple(selected.shape)} takes "
                f"causal=True over one sequence ([{b}, 1, {sq}, {sk}]) and "
                f"no mask, dropout, window or diffusion_block")
    block_q, block_k = _window_blocks(window, *_blocks_that_fit(
        held, d, v.shape[3], q.dtype.itemsize, block_q, block_k))
    mode = _mask_mode(attn_mask.shape if has_mask else None, b, h, sq, sk)
    kernel = mode != "fallback" and (force or enabled(
        "flash_attention", seq_len=max(sq, sk)))
    monitor.counter("flash_attention.kernel_traced" if kernel
                    else "flash_attention.xla_traced").inc()
    if selected is not None:
        return _selected_call(q, k, v, selected, scale, block_q, block_k,
                              kernel)
    if not kernel:
        from ..nn_ops import scaled_dot_product_attention as sdpa
        if diffusion_block is not None:
            attn_mask = jnp.asarray(block_diffusion_mask(held, block))
        if window is not None:
            attn_mask = jnp.asarray(sliding_window_mask(sq, window))
        return sdpa(q, k, v, attn_mask=attn_mask, is_causal=causal,
                    scale=scale, dropout_p=p_drop, training=training)
    # how often the kernels' mechanisms engage, per call site: the forward
    # kernel's score tiles, those of them that run the masked body, the
    # tiles of the rectangle it leaves out; call sites whose products take
    # bfloat16 operands
    if diffusion_block is None:
        bq, bk = _clamped_blocks(block_q, block_k, sq, sk)
        tiles, masked = _tile_counts(b * h, block_q=bq, block_k=bk, sq=sq,
                                     sk=sk, causal=causal, window=window)
        whole = b * h * -(-sq // bq) * -(-sk // bk)
    else:
        shift = block.bit_length() - 1
        bq, bk = _bd_blocks(block_q, block_k, held, shift)
        tiles, masked, whole = _bd_tile_counts(
            b * h, held, block_q=bq, block_k=bk, shift=shift)
    monitor.counter("flash_attention.tiles").inc(tiles)
    monitor.counter("flash_attention.tiles_masked").inc(masked)
    monitor.counter("flash_attention.tiles_skipped").inc(whole - tiles)
    if window is not None:      # the windowed call sites' share of the two
        monitor.counter("flash_attention.window_tiles").inc(tiles)
        monitor.counter("flash_attention.window_tiles_skipped").inc(
            whole - tiles)
        # the least tiles of this size that could hold the allowed pairs
        allowed = window * (window + 1) // 2 + (sq - window) * window
        monitor.counter("flash_attention.window_tiles_needed").inc(
            b * h * -(-allowed // (bq * bk)))
    if q.dtype == k.dtype == v.dtype == jnp.bfloat16:
        monitor.counter("flash_attention.native_operands_traced").inc()

    def impl(q, k, v, *rest):
        if diffusion_block is not None:
            return _flash_bd(q, k, v, shift, scale, block_q, block_k)
        if window is not None:
            return _flash_win(q, k, v, window, scale, block_q, block_k)
        m = _canon_mask(rest[0]) if has_mask else None
        if p_drop > 0.0:
            raw = jnp.ravel(rest[-1])[:2]
            seed = jax.lax.bitcast_convert_type(raw, jnp.int32)
        else:
            seed = jnp.zeros((2,), jnp.int32)
        return _flash(q, k, v, m, mode, seed, causal, scale, block_q,
                      block_k, p_drop)

    args = (q, k, v)
    if has_mask:
        args = args + (attn_mask,)
    if p_drop > 0.0:
        args = args + (prandom.next_key_graph(),)
    return apply(impl, args, name="pallas_flash_attention")


def _selected_call(q, k, v, selected, scale, block_q, block_k, kernel):
    """``flash_attention(selected=...)`` behind its checks, ``(o, m, l)``:
    the kernels, or the definition route of ``ops/sparse_attention.py``."""
    from ...dispatch import apply
    from ... import monitor
    from ..sparse_attention import selected_attention
    if kernel:
        b, h, sq, _ = q.shape
        bq, bk = _clamped_blocks(block_q, block_k, sq, sq)
        walked, _ = _tile_counts(b * h, block_q=bq, block_k=bk, sq=sq, sk=sq,
                                 causal=True)
        # the tiles that hold a pair at or below the diagonal, counted
        # apart from the kernel's bounds: a token-level selection empties
        # none of them, a kernel that skipped some would walk fewer
        causal = b * h * sum(-(-min(i * bq + bq, sq) // bk)
                             for i in range(-(-sq // bq)))
        monitor.counter("flash_attention.selected_tiles_walked").inc(walked)
        monitor.counter("flash_attention.selected_tiles_causal").inc(causal)

        def impl(q, k, v, selected):
            o, m, l = _flash_sel(q, k, v, selected.astype(jnp.int8), scale,
                                 block_q, block_k)
            return o, jax.lax.stop_gradient(m), jax.lax.stop_gradient(l)
    else:
        def impl(q, k, v, selected):
            return selected_attention(q, k, v, selected, scale)

    o, m, l = apply(impl, (q, k, v, selected),
                    name="pallas_flash_attention")
    return o, m.detach(), l.detach()


def _window_blocks(window, block_q, block_k):
    """The block rule's clause for a window narrower than a k-block (at the
    end of the file: the call sites above keep their lines). A q-block's
    rows see ``window + block_q - 1`` keys, in whole k-blocks: under a
    window of 512 at 512 x 1,024 a head's sixteen programs walk 23 tiles
    of 1,024 keys, three times the pairs the window allows; with the
    k-block cut to the window's width (a power of two, a lane tile at
    least) they walk 31 of 512, twice the pairs. A window as wide as a
    k-block or wider keeps the blocks it had (smallthinker's 4,096 at 512
    x 512)."""
    if window is not None and window < block_k:
        block_k = max(_TILE, 1 << (int(window) - 1).bit_length())
    return block_q, block_k
