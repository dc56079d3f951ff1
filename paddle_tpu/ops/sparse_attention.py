"""paddle_tpu.ops.sparse_attention — a learned token-level selection in
front of attention (DeepSeek sparse attention: a "lightning indexer" scores
every causal key of every row, the ``top_k`` best are the keys the layer's
attention reads; DeepSeek-V3.2-Exp's report, section 2).

No reference counterpart in Paddle Fluid 1.7. Three ops, one layer's worth:

``dsa_select(qi, ki, w, top_k)``
    index scores ``I(t, s) = sum_j w[t, j] relu(qi[j, t] . ki[s])`` for
    ``s <= t``, the threshold ``tau[t]`` = the ``top_k``-th largest of a
    row's scores (``-inf`` while the row has no more than ``top_k`` causal
    keys) and the selection ``{s <= t : I(t, s) >= tau[t]}`` as an int8
    array ``[B, 1, S, S]`` that ``flash_attention(selected=...)`` reads.
    Written as a threshold so that ties keep every key at ``tau``. Also
    ``lse[t] = logsumexp`` of the selected scores, for the loss below. No
    gradient: a selection has none. Both routes make the selection as
    PACKED BITS, one a (row, key) pair (``_pack``: int8 ``[B, 1, S, S / 8]``,
    bit ``b`` of element ``[t, c]`` is key ``b * S / 8 + c``, so packing and
    unpacking move whole column chunks and nothing is laid out anew), and
    the op leaves that array under the name ``SELECTION_NAMES[0]`` and
    unpacks the int8 array from it. A recomputed block whose policy keeps
    the name (``jit.recompute``'s default) replays from the kept bits, 1/8
    of the int8 array, and makes no second selection: the backward of the
    flash call is the one reader the replay has, ``lse`` feeds a pass that
    is itself kept by name, the count a forward-only counter.

``flash_attention(q, k, v, causal=True, selected=...)``
    (``ops/pallas/flash_attention.py``) attention over the selected keys;
    ``selected_attention`` below is its definition route.

``dsa_indexer_loss(q, k, m, l, selected, qi, ki, w, lse)``
    the indexer's own loss, ``KL(P || R)`` a row, averaged over rows: ``P``
    the attention probabilities the layer's heads gave (from the flash
    call's kept statistics ``m``, ``l``), averaged over heads, ``R`` the
    soft-max of the index scores over the selected keys. Its gradient with
    respect to a score is ``(R - P) / rows`` on the selection, so ONE pass
    over the score tiles makes the loss and the gradients of ``qi``, ``ki``
    and ``w`` together: the op is a ``custom_vjp`` whose forward keeps the
    three gradients (under the name ``RESULT_NAMES[0]``, so that a
    recomputed block does not make them twice) and whose backward scales
    them. ``q``, ``k`` and the statistics get no gradient: the target is a
    constant of this loss.

Each op has the definition route below (plain ``jax.numpy`` in row blocks;
a CPU, a mesh, shapes the tiles do not fit) and, on one TPU, the kernels of
``ops/pallas/dsa.py``; counters ``dsa.select.kernel_traced`` /
``.xla_traced`` and ``dsa.kl.kernel_traced`` / ``.xla_traced`` say which a
call site traced, ``dsa.select.results_named`` that a selection's bits got
their name. Layouts are the kernels': ``qi`` ``[B, Hi, S, Di]``,
``ki`` ``[B, S, Di]``, ``w`` ``[B, S, Hi]``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..dispatch import apply
from .nn_ops import _pscope

__all__ = ["dsa_select", "dsa_indexer_loss", "selected_attention"]

_F32 = jnp.float32
# what a recomputed block keeps of the indexer's loss: the loss and the
# three gradients its one pass made
RESULT_NAMES = ("dsa_kl_results",)
# and of the selection: its packed bits
SELECTION_NAMES = ("dsa_selection_bits",)
PACK = 8                # keys an element of the packed selection
_ROW_BLOCK = 512        # rows a block of the definition routes


def _row_block(s, block=_ROW_BLOCK):
    """Rows a block: ``block`` where it divides ``s``, else the sequence."""
    return block if s % block == 0 else s


def index_scores(qi, ki, w):
    """``I`` [R, K] of rows ``qi`` [Hi, R, Di], ``w`` [R, Hi] against keys
    ``ki`` [K, Di]: the heads added up in order, in float32, as the kernels
    add them (so that both routes meet ties and borderline keys alike),
    and ``-0.0`` made ``0.0``."""
    acc = jnp.zeros((qi.shape[1], ki.shape[0]), _F32)
    for j in range(qi.shape[0]):
        dots = jax.lax.dot_general(qi[j], ki, (((1,), (1,)), ((), ())),
                                   preferred_element_type=_F32)
        acc = acc + w[:, j:j + 1].astype(_F32) * jnp.maximum(dots, 0.0)
    return acc + 0.0


def _causal(r0, rows, keys):
    at = r0 + jnp.arange(rows)[:, None]
    return jnp.arange(keys)[None, :] <= at


def packed_width(s):
    """Elements a row of the packed selection over ``s`` keys."""
    return -(-s // PACK)


def _pack(sel):
    """bool ``[..., R, S]`` -> int8 ``[..., R, packed_width(S)]``: bit ``b``
    of element ``[r, c]`` is key ``b * width + c`` of row ``r`` (keys past
    ``S`` read 0). Keys a width apart share an element, so the packed
    array is ``PACK`` column chunks shifted and or-ed, whole."""
    s = sel.shape[-1]
    width = packed_width(s)
    sel = jnp.pad(sel, [(0, 0)] * (sel.ndim - 1) + [(0, PACK * width - s)])
    packed = jnp.zeros((*sel.shape[:-1], width), jnp.int8)
    for b in range(PACK):
        chunk = sel[..., b * width:(b + 1) * width].astype(jnp.int8)
        packed = packed | (chunk << b)
    return packed


def _unpack(packed, s):
    """``_pack``'s inverse, as the int8 ``[..., R, s]`` the flash kernels
    and the loss read: ``PACK`` shifted-and-masked copies of the packed
    array side by side along the key axis."""
    sel = jnp.concatenate([(packed >> b) & 1 for b in range(PACK)], axis=-1)
    return sel[..., :s]


def _select_block(qi, ki, w, r0, top_k):
    """(selected bool [R, S], tau [R], lse [R]) of one block of rows."""
    rows, s = qi.shape[1], ki.shape[0]
    causal = _causal(r0, rows, s)
    scores = jnp.where(causal, index_scores(qi, ki, w), -jnp.inf)
    if top_k < s:
        kth = -jnp.sort(-scores, axis=-1)[:, top_k - 1]
        tau = jnp.where(r0 + jnp.arange(rows) + 1 <= top_k, -jnp.inf, kth)
    else:
        tau = jnp.full((rows,), -jnp.inf, _F32)
    selected = causal & (scores >= tau[:, None])
    lse = jax.nn.logsumexp(jnp.where(selected, scores, -jnp.inf), axis=-1)
    return selected, tau, lse


def _select(qi, ki, w, *, top_k):
    """The definition route of ``dsa_select``: a sort a block of rows.
    ``(bits`` int8 [B, 1, S, packed_width(S)] (``_pack``), ``lse`` [B, S],
    ``tau`` [B, S], ``pairs`` int32 [B]``)``."""
    b, _, s, _ = qi.shape
    block = _row_block(s)

    def sequence(args):
        qi, ki, w = args

        def rows(at):
            cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(
                t, at, block, axis)
            sel, tau, lse = _select_block(cut(qi, 1), ki, cut(w, 0), at,
                                          top_k)
            return _pack(sel), tau, lse, jnp.sum(sel, dtype=jnp.int32)

        bits, tau, lse, count = jax.lax.map(rows, jnp.arange(0, s, block))
        return (bits.reshape(s, -1), tau.reshape(s), lse.reshape(s),
                jnp.sum(count))

    bits, tau, lse, count = jax.lax.map(sequence, (qi, ki, w))
    return bits[:, None], lse, tau, count


def dsa_select(qi, ki, w, top_k, name=None):
    """The selection of a learned sparse attention (module docstring):
    ``qi`` [B, Hi, S, Di], ``ki`` [B, S, Di], ``w`` [B, S, Hi] ->
    ``(selected`` int8 [B, 1, S, S], ``lse`` [B, S], ``tau`` [B, S],
    ``pairs`` int32 [B]``)``, no gradient to anything."""
    if qi.ndim != 4 or ki.ndim != 3 or w.ndim != 3 or int(top_k) < 1 \
            or tuple(ki.shape) != (qi.shape[0], qi.shape[2], qi.shape[3]) \
            or tuple(w.shape) != (qi.shape[0], qi.shape[2], qi.shape[1]):
        raise ValueError(
            f"dsa_select: qi {tuple(qi.shape)} [B, Hi, S, Di], ki "
            f"{tuple(ki.shape)} [B, S, Di], w {tuple(w.shape)} [B, S, Hi], "
            f"top_k {top_k}")
    from jax.ad_checkpoint import checkpoint_name
    from .. import monitor
    from . import pallas
    kernel = pallas.enabled("dsa_select") and pallas.dsa_mod.select_supported(
        tuple(qi.shape))
    monitor.counter("dsa.select.kernel_traced" if kernel
                    else "dsa.select.xla_traced").inc()
    route = pallas.dsa_mod.select if kernel else _select

    def impl(*operands, top_k):
        # inside a recomputed block JAX differentiates the block whole: no
        # tangent may reach the kernel
        bits, lse, tau, pairs = route(
            *(jax.lax.stop_gradient(t) for t in operands), top_k=top_k)
        # the bits under their name, the int8 array from the NAMED value:
        # a checkpoint that keeps the name replays the unpacking alone
        monitor.counter("dsa.select.results_named").inc()
        bits = checkpoint_name(bits, SELECTION_NAMES[0])
        return tuple(jax.lax.stop_gradient(t) for t in (
            _unpack(bits, operands[0].shape[2]), lse, tau, pairs))

    with _pscope("F.dsa_select"):
        return apply(impl, (qi, ki, w), dict(top_k=int(top_k)),
                     nondiff=True, name="dsa_select")


# -- attention under a selection: the definition route -----------------------

def _scaled(q, scale):
    """The flash kernels' rounding of the scaled queries."""
    scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
    return (q.astype(_F32) * scale).astype(q.dtype)


def _head_scores(q, k):
    """[H, R, K] float32 of scaled queries [H, R, D] and keys [H, K, D]."""
    return jnp.einsum("hrd,hkd->hrk", q, k, preferred_element_type=_F32)


def selected_attention(q, k, v, selected, scale=None):
    """``(o, m, l)`` of attention over the selected keys, in row blocks:
    ``o`` [B, H, S, Dv] in ``q``'s dtype and the soft-max statistics as the
    flash kernels leave them, ``m`` the row's largest selected score and
    ``l`` the sum of ``exp(score - m)``, [B * H, 1, S] float32 (no gradient
    through them). A row selects at least one key, so no row is empty."""
    b, h, s, _ = q.shape
    block = _row_block(s)
    qs = _scaled(q, scale)

    def sequence(args):
        q, k, v, sel = args

        def rows(at):
            cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(
                t, at, block, axis)
            keep = cut(sel, 0) != 0
            scores = jnp.where(keep, _head_scores(cut(q, 1), k), -jnp.inf)
            m = jnp.max(scores, -1)
            p = jnp.exp(scores - m[..., None])
            l = jnp.sum(p, -1)
            o = jnp.einsum("hrk,hkd->hrd", (p / l[..., None]).astype(v.dtype),
                           v, preferred_element_type=_F32)
            return o.astype(q.dtype), m, l

        o, m, l = jax.lax.map(rows, jnp.arange(0, s, block))
        join = lambda t: jnp.moveaxis(t, 0, 1).reshape(h, s, *t.shape[3:])
        return join(o), join(m), join(l)

    o, m, l = jax.lax.map(sequence, (qs, k, v, selected[:, 0]))
    return o, *(jax.lax.stop_gradient(t.reshape(b * h, 1, s))
                for t in (m, l))


# -- the indexer's loss --------------------------------------------------------

def _kl_block(q, k, m, l, keep, qi, ki, w, lse, rows_total):
    """One block of rows against the keys it may see: (the block's part of
    the loss, d qi, d ki, d w). ``q`` [H, R, D] scaled, ``k`` [H, K, D],
    ``m`` / ``l`` [H, R], ``keep`` bool [R, K], ``lse`` [R]."""
    p = jnp.exp(_head_scores(q, k) - m[..., None]) / l[..., None]
    target = jnp.where(keep, jnp.mean(p, 0), 0.0)
    scores, back = jax.vjp(index_scores, qi, ki, w)
    log_r = scores - lse[:, None]
    part = jnp.sum(jnp.where(
        target > 0, target * (jnp.log(jnp.where(target > 0, target, 1.0))
                              - log_r), 0.0))
    d_scores = jnp.where(keep, jnp.exp(log_r) - target, 0.0) / rows_total
    return (part / rows_total, *back(d_scores))


def _kl_and_grads(q, k, m, l, selected, qi, ki, w, lse, scale):
    """The definition route of the loss's one pass: ``(L_I, d qi, d ki,
    d w)``, walked in blocks of rows, each against the keys up to its last
    row (the selection is causal), the sequences one at a time."""
    b, h, s, _ = q.shape
    block = _row_block(s, 256)
    qs = _scaled(q, scale)
    m, l = (t.reshape(b, h, s) for t in (m, l))
    rows_total = b * s

    def sequence(args):
        q, k, m, l, sel, qi, ki, w, lse = args
        loss = jnp.zeros((), _F32)
        d_qi, d_w = [], []
        d_ki = jnp.zeros(ki.shape, _F32)
        # a staircase of at most eight steps: the blocks of a step share
        # the step's keys, so that the shapes are few and the keys above
        # the diagonal of a step's last row are never made
        per_step = -(-(s // block) // 8) * block
        for r0 in range(0, s, per_step):
            r1 = min(r0 + per_step, s)
            keys = slice(0, r1)

            def rows(carry, at, keys=keys, r1=r1):
                loss, d_ki = carry
                cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(
                    t, at, block, axis)
                part, g_qi, g_ki, g_w = _kl_block(
                    cut(q, 1), k[:, keys], cut(m, 1), cut(l, 1),
                    cut(sel, 0)[:, keys] != 0, cut(qi, 1), ki[keys],
                    cut(w, 0), cut(lse, 0), rows_total)
                d_ki = d_ki.at[keys].add(g_ki.astype(_F32))
                return (loss + part, d_ki), (g_qi, g_w)

            (loss, d_ki), (g_qi, g_w) = jax.lax.scan(
                rows, (loss, d_ki), jnp.arange(r0, r1, block))
            d_qi.append(jnp.moveaxis(g_qi, 0, 1).reshape(
                qi.shape[0], r1 - r0, qi.shape[2]))
            d_w.append(g_w.reshape(r1 - r0, w.shape[1]))
        return (loss, jnp.concatenate(d_qi, 1), d_ki.astype(ki.dtype),
                jnp.concatenate(d_w, 0))

    loss, d_qi, d_ki, d_w = jax.lax.map(
        sequence, (qs, k, m, l, selected[:, 0], qi, ki, w, lse))
    return jnp.sum(loss), d_qi, d_ki, d_w


def _named(results):
    from jax.ad_checkpoint import checkpoint_name
    return tuple(checkpoint_name(t, RESULT_NAMES[0]) for t in results)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _indexer_loss(q, k, m, l, selected, qi, ki, w, lse, scale, kernel):
    return _kl_pass(kernel)(q, k, m, l, selected, qi, ki, w, lse, scale)[0]


def _kl_pass(kernel):
    if kernel:
        from .pallas import dsa
        return dsa.kl_and_grads
    return _kl_and_grads


def _indexer_loss_fwd(q, k, m, l, selected, qi, ki, w, lse, scale, kernel):
    loss, *grads = _named(_kl_pass(kernel)(q, k, m, l, selected, qi, ki, w,
                                           lse, scale))
    return loss, grads


def _indexer_loss_bwd(scale, kernel, grads, g):
    # None: no cotangent to the target's side, the selection or lse
    return (None,) * 5 + tuple((g * t.astype(_F32)).astype(t.dtype)
                               for t in grads) + (None,)


_indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def dsa_indexer_loss(q, k, m, l, selected, qi, ki, w, lse, scale=None,
                     name=None):
    """The indexer's loss of one layer (module docstring), a scalar:
    ``q``, ``k`` [B, H, S, D] as the flash call took them, ``m``, ``l``
    [B * H, 1, S] its statistics, ``selected`` [B, 1, S, S], ``qi``, ``ki``,
    ``w`` and ``lse`` as ``dsa_select`` took and gave them. Gradient to
    ``qi``, ``ki`` and ``w`` alone."""
    from .. import monitor
    from . import pallas
    kernel = pallas.enabled("dsa_kl") and pallas.dsa_mod.kl_supported(
        tuple(q.shape), tuple(qi.shape))
    monitor.counter("dsa.kl.kernel_traced" if kernel
                    else "dsa.kl.xla_traced").inc()
    with _pscope("F.dsa_indexer_loss"):
        return apply(_indexer_loss, (q, k, m, l, selected, qi, ki, w, lse),
                     dict(scale=scale, kernel=bool(kernel)),
                     name="dsa_indexer_loss")
