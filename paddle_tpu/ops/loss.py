"""paddle_tpu.ops.loss — loss functional ops.

TPU-native rebuild of the reference's loss operators
(reference: paddle/fluid/operators/{cross_entropy_op,
softmax_with_cross_entropy_op, sigmoid_cross_entropy_with_logits_op,
squared_l2_op, huber_loss_op, kldiv_loss_op, smooth_l1_loss_op,
margin_rank_loss_op, rank_loss_op, hinge_loss_op, bpr_loss_op,
log_loss_op}.cc; python surface in fluid/layers/loss.py).

softmax_with_cross_entropy is the fused hot path (the reference has a
dedicated CUDA kernel); here the XLA logsumexp formulation fuses it, and a
Pallas kernel (ops/pallas/softmax_xent.py) covers the flagship path.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from contextlib import nullcontext

from ..tensor import as_tensor
from ..dispatch import apply
from ..monitor import profile as _profile
from . import math as _math
from . import nn_ops as _nn


def _pscope(name):
    """named_scope(F.<name>) when profiling is armed, else a no-op —
    one flag check, so the disabled path stays free."""
    if _profile.live and _profile.armed():
        return _profile.scope(_profile.fscope(name))
    return nullcontext()


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def _picked_logp(logp, label, axis, ignore_index):
    """Gather log-probs at hard labels, masking label==ignore_index (any
    value, incl. negatives — indices are clamped before the gather so OOB
    labels can't alias a real class). Returns (loss, valid_mask)."""
    lbl = label
    ax = axis % logp.ndim
    if lbl.ndim == logp.ndim and lbl.shape[ax] == 1:
        lbl = jnp.squeeze(lbl, ax)
    valid = lbl != ignore_index
    nclass = logp.shape[ax]
    safe = jnp.clip(lbl, 0, nclass - 1).astype(jnp.int32)
    picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, ax), axis=ax)
    loss = jnp.where(jnp.expand_dims(valid, ax), -picked, 0.0)
    return loss, valid


def _fused_softmax_xent(x, label, ignore_index):
    """Per-position loss via the Pallas fused kernel when enabled, else
    None. The kernel scores every row ([N,V] softmax never hits HBM; an
    ignored/OOB label matches no column → loss=lse there); masking after
    also zeroes the cotangent into the kernel's backward at those rows.
    Returns (loss[lead+(1,)] in x.dtype, valid[lead])."""
    from .pallas import enabled
    if not enabled("softmax_xent"):
        return None
    from .pallas.softmax_xent import _softmax_xent2
    v = x.shape[-1]
    lbl = label
    if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
        lbl = jnp.squeeze(lbl, -1)
    valid = lbl != ignore_index
    loss = _softmax_xent2(
        x.reshape(-1, v), lbl.reshape(-1, 1).astype(jnp.int32)
    ).reshape(lbl.shape + (1,)).astype(x.dtype)
    return jnp.where(valid[..., None], loss, jnp.zeros((), x.dtype)), valid


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False, name=None):
    """Fused, numerically stable (reference: the fused CUDA kernel in
    softmax_with_cross_entropy_op.cu)."""
    def impl(logits, label, soft_label, ignore_index, axis, return_softmax):
        ax = axis % logits.ndim
        if not soft_label and not return_softmax and ax == logits.ndim - 1:
            fused = _fused_softmax_xent(logits, label, ignore_index)
            if fused is not None:
                return fused[0]
        lse = jax.scipy.special.logsumexp(logits, axis=axis, keepdims=True)
        logp = logits - lse
        if soft_label:
            loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
        else:
            loss, _ = _picked_logp(logp, label, axis, ignore_index)
        if return_softmax:
            return loss, jnp.exp(logp)
        return loss
    with _pscope("F.softmax_with_cross_entropy"):
        out = apply(impl, (logits, label),
                    dict(soft_label=soft_label, ignore_index=ignore_index,
                         axis=axis, return_softmax=return_softmax),
                    n_out=2 if return_softmax else 1,
                    name="softmax_with_cross_entropy")
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100,
                  reduction="mean", axis=-1, use_softmax=True,
                  weight=None, name=None):
    """paddle.nn.functional.cross_entropy parity: input is logits when
    use_softmax (default), else probabilities (reference cross_entropy_op).
    `weight` is a per-class weight vector; mean reduction normalizes by the
    summed weights of non-ignored positions (paddle semantics)."""
    def impl(x, label, *maybe_w, soft_label, ignore_index, axis, use_softmax,
             reduction):
        ax = axis % x.ndim
        if soft_label or not (use_softmax and ax == x.ndim - 1):
            fused = None
        else:
            fused = _fused_softmax_xent(x, label, ignore_index)
        if fused is None:
            if use_softmax:
                logp = x - jax.scipy.special.logsumexp(x, axis=axis,
                                                       keepdims=True)
            else:
                logp = jnp.log(jnp.clip(x, 1e-10, 1.0))
        if soft_label:
            loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
            denom_w = jnp.ones_like(loss)
        else:
            if fused is not None:
                loss, valid = fused
            else:
                loss, valid = _picked_logp(logp, label, axis, ignore_index)
            lbl = label
            if lbl.ndim == x.ndim and lbl.shape[ax] == 1:
                lbl = jnp.squeeze(lbl, ax)
            safe = jnp.clip(lbl, 0, x.shape[ax] - 1).astype(jnp.int32)
            if maybe_w:
                w = jnp.expand_dims(maybe_w[0][safe], ax)
                loss = loss * w
                denom_w = jnp.where(jnp.expand_dims(valid, ax), w, 0.0)
            else:
                denom_w = jnp.expand_dims(valid, ax).astype(loss.dtype)
        if reduction == "none":
            return loss
        if reduction == "sum":
            return jnp.sum(loss)
        return jnp.sum(loss) / jnp.maximum(jnp.sum(denom_w), 1e-12)

    args = (input, label) if weight is None else (input, label, weight)
    with _pscope("F.cross_entropy"):
        return apply(impl, args,
                     dict(soft_label=soft_label, ignore_index=ignore_index,
                          axis=axis, use_softmax=use_softmax,
                          reduction=reduction), name="cross_entropy")


def block_diffusion_loss(input, label, weight, name=None):
    """The masked-diffusion training loss (Sahoo et al., arXiv:2406.07524;
    by blocks, Arriola et al., arXiv:2503.09573): ``(1 / N) sum_i w_i CE(
    z_i, x_i)`` over all ``N`` positions of ``input`` (logits ``[..., V]``)
    against ``label`` ``[...]``, NO shift: the logit at a masked position
    predicts that position's own token. ``weight`` ``[...]`` is ``1 / t``
    at a position masked at noise level ``t`` and 0 elsewhere, so an
    all-zero weight gives 0 and ``weight = 1`` the plain mean cross
    entropy. float32 whatever the logits are."""
    def impl(z, y, w):
        z = z.astype(jnp.float32)
        picked = jnp.take_along_axis(z, y[..., None].astype(jnp.int32),
                                     -1)[..., 0]
        ce = jax.scipy.special.logsumexp(z, axis=-1) - picked
        return jnp.sum(w.astype(jnp.float32) * ce) / ce.size

    with _pscope("F.block_diffusion_loss"):
        return apply(impl, (input, label, weight),
                     name="block_diffusion_loss")


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      normalize=False, name=None):
    """reference: sigmoid_cross_entropy_with_logits_op.cc"""
    def impl(x, label, ignore_index, normalize):
        loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
        mask = label != ignore_index
        loss = jnp.where(mask, loss, 0.0)
        if normalize:
            loss = loss / jnp.maximum(jnp.sum(mask), 1)
        return loss
    return apply(impl, (x, label), dict(ignore_index=ignore_index,
                                        normalize=normalize),
                 name="sigmoid_cross_entropy_with_logits")


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    def impl(p, label, *maybe_w, reduction):
        p = jnp.clip(p, 1e-12, 1 - 1e-12)
        loss = -(label * jnp.log(p) + (1 - label) * jnp.log1p(-p))
        if maybe_w:
            loss = loss * maybe_w[0]
        return _reduce(loss, reduction)
    args = (input, label) if weight is None else (input, label, weight)
    return apply(impl, args, dict(reduction=reduction), name="bce")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    # log-sigmoid formulation: loss = -pos_weight*y*log(sigmoid(x))
    #                                 - (1-y)*log(1-sigmoid(x)),  then *weight
    has_w = weight is not None
    has_pw = pos_weight is not None

    def impl(x, label, *extra, reduction, has_w, has_pw):
        log_sig = -jnp.log1p(jnp.exp(-jnp.abs(x))) + jnp.minimum(x, 0)
        log_one_minus = log_sig - x  # log(1 - sigmoid(x)) = log_sigmoid(-x)
        idx = 0
        pw = 1.0
        if has_pw:
            pw = extra[idx + (1 if has_w else 0)]
        loss = -(pw * label * log_sig + (1 - label) * log_one_minus)
        if has_w:
            loss = loss * extra[0]
        return _reduce(loss, reduction)

    args = [logit, label]
    if has_w:
        args.append(weight)
    if has_pw:
        args.append(pos_weight)
    return apply(impl, tuple(args),
                 dict(reduction=reduction, has_w=has_w, has_pw=has_pw),
                 name="bce_with_logits")


def square_error_cost(input, label, name=None):
    """reference: squared_l2_distance / square_error_cost"""
    return apply(lambda x, y: jnp.square(x - y), (input, label),
                 name="square_error_cost")


def mse_loss(input, label, reduction="mean", name=None):
    return apply(lambda x, y, reduction: _reduce(jnp.square(x - y), reduction),
                 (input, label), dict(reduction=reduction), name="mse_loss")


def l1_loss(input, label, reduction="mean", name=None):
    return apply(lambda x, y, reduction: _reduce(jnp.abs(x - y), reduction),
                 (input, label), dict(reduction=reduction), name="l1_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    """reference: smooth_l1_loss_op.cc (huber form)."""
    def impl(x, y, reduction, delta):
        d = x - y
        a = jnp.abs(d)
        loss = jnp.where(a < delta, 0.5 * d * d / delta, a - 0.5 * delta)
        return _reduce(loss, reduction)
    return apply(impl, (input, label), dict(reduction=reduction, delta=delta),
                 name="smooth_l1_loss")


def huber_loss(input, label, delta=1.0, name=None):
    def impl(x, y, delta):
        d = x - y
        a = jnp.abs(d)
        return jnp.where(a <= delta, 0.5 * d * d, delta * (a - 0.5 * delta))
    return apply(impl, (input, label), dict(delta=delta), name="huber_loss")


def kl_div(input, label, reduction="mean", name=None):
    """reference: kldiv_loss_op.cc — input is log-probabilities."""
    def impl(logp, y, reduction):
        loss = jnp.where(y > 0, y * (jnp.log(jnp.maximum(y, 1e-30)) - logp),
                         0.0)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)
    return apply(impl, (input, label), dict(reduction=reduction),
                 name="kl_div")


def log_loss(input, label, epsilon=1e-4, name=None):
    """reference: log_loss_op.cc"""
    def impl(p, y, epsilon):
        return -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon)
    return apply(impl, (input, label), dict(epsilon=epsilon), name="log_loss")


def hinge_loss(input, label, name=None):
    """reference: hinge_loss_op.cc (labels in {0,1})."""
    def impl(x, y):
        return jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * x)
    return apply(impl, (input, label), name="hinge_loss")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    """reference: margin_rank_loss_op.cc"""
    def impl(x1, x2, y, margin, reduction):
        return _reduce(jnp.maximum(0.0, -y * (x1 - x2) + margin), reduction)
    return apply(impl, (input, other, label),
                 dict(margin=margin, reduction=reduction),
                 name="margin_ranking_loss")


def rank_loss(label, left, right, name=None):
    """reference: rank_loss_op.cc (RankNet pairwise loss)."""
    def impl(label, left, right):
        d = left - right
        return jnp.log1p(jnp.exp(d)) - label * d
    return apply(impl, (label, left, right), name="rank_loss")


def bpr_loss(input, label, name=None):
    """reference: bpr_loss_op.cc (Bayesian Personalized Ranking)."""
    def impl(x, label):
        pos = jnp.take_along_axis(x, label.reshape(-1, 1).astype(jnp.int32),
                                  axis=1)
        diff = x - pos
        n = x.shape[1]
        loss = jnp.sum(jnp.log1p(jnp.exp(diff)), axis=1, keepdims=True) / (n - 1)
        return loss
    return apply(impl, (input, label), name="bpr_loss")


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    def impl(logp, label, *maybe_w, ignore_index, reduction):
        valid = label != ignore_index
        safe = jnp.clip(label, 0, logp.shape[-1] - 1).astype(jnp.int32)
        picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        loss = jnp.where(valid, -picked, 0.0)
        if maybe_w:
            w = maybe_w[0][safe]
            loss = loss * w
            denom = jnp.sum(jnp.where(valid, w, 0.0))
        else:
            denom = jnp.sum(valid)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
        return _reduce(loss, reduction)
    args = (input, label) if weight is None else (input, label, weight)
    return apply(impl, args, dict(ignore_index=ignore_index,
                                  reduction=reduction), name="nll_loss")


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def impl(x1, x2, axis, eps):
        n1 = jnp.sqrt(jnp.sum(x1 * x1, axis=axis))
        n2 = jnp.sqrt(jnp.sum(x2 * x2, axis=axis))
        return jnp.sum(x1 * x2, axis=axis) / jnp.maximum(n1 * n2, eps)
    return apply(impl, (x1, x2), dict(axis=axis, eps=eps),
                 name="cosine_similarity")
