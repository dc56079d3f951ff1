"""The harness of tests/family_contract.py against a counting stand-in for
a reference module and a two-leaf model: a ``Reference`` asks the module
for the seed's weights, the forward results, the gradient and the training
run once each however many checks read them, and no check can pass on a
leaf that is off by more than its tolerance, on a forward result that is,
on an empty parameter list, or on a share of an expert layer that holds
another share's experts."""
import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import nn, ops
from benchmark.reference.bert_pretrain import adamw_update
from benchmark.reference.common import diff_norms
from benchmark.reference import sdar_moe
from family_contract import (Family, Reference, check_adamw_step,
                             check_expert_shares_add_up,
                             check_matches_reference,
                             check_trains_through_to_static, load,
                             routed_share)

VOCAB, WIDTH = 32, 8


class Config:
    def __init__(self, vocab=VOCAB, width=WIDTH, recompute=False):
        self.vocab, self.width, self.recompute = vocab, width, recompute


class TwoLeaves(nn.Layer):
    """logits = embed[ids] @ head; the loss is position i's cross entropy
    against id i."""

    def __init__(self, config):
        super().__init__()
        self.embed = nn.Embedding(config.vocab, config.width)
        self.head = nn.Linear(config.width, config.vocab, bias_attr=False)

    def forward(self, ids):
        return self.head(self.embed(ids))

    def loss(self, logits, ids):
        return ops.loss.cross_entropy(logits, ids)


class NoLeaves(nn.Layer):
    def __init__(self, config):
        super().__init__()


class StandIn:
    """The reference module's five functions for ``TwoLeaves``, counting
    their calls; ``off`` adds to one element of the gradient of
    ``head.weight`` and ``logit_off`` to one forward result."""

    def __init__(self, off=0.0, logit_off=0.0):
        self.calls = collections.Counter()
        self.off, self.logit_off = off, logit_off

    def init_weights(self, cfg, seed):
        self.calls["init_weights"] += 1
        return self._weights(cfg, seed)

    def forward(self, cfg, p, ids):
        self.calls["forward"] += 1
        return self._logits(p, ids).at[0, 0, 0].add(self.logit_off)

    def loss_fn(self, cfg, p, batch):
        self.calls["loss_fn"] += 1          # once a trace
        return self._loss(p, batch)

    def train(self, cfg, hyper, seed, batches):
        self.calls["train"] += 1
        start = p = self._weights(cfg, seed)
        m, v = (jax.tree.map(jnp.zeros_like, p) for _ in range(2))
        losses = []
        for t, batch in enumerate(batches, 1):
            arrays = tuple(jnp.asarray(a) for a in batch)
            loss, g = jax.value_and_grad(lambda q: self._loss(q, arrays))(p)
            new = {k: adamw_update(hyper, p[k], g[k], m[k], v[k], float(t))
                   for k in p}
            p, m, v = ({k: n[i] for k, n in new.items()} for i in range(3))
            losses.append(float(loss))
        return {"loss": losses, "delta_norm": {
            k: float(x) for k, x in diff_norms(p, start).items()}}

    # the arithmetic, uncounted

    @staticmethod
    def _weights(cfg, seed):
        k = jax.random.split(jax.random.key(seed), 2)
        return {"embed.weight": jax.random.normal(
                    k[0], (cfg["vocab"], cfg["width"])),
                "head.weight": jax.random.normal(
                    k[1], (cfg["width"], cfg["vocab"]))}

    @staticmethod
    def _logits(p, ids):
        return p["embed.weight"][ids] @ p["head.weight"]

    def _loss(self, p, batch):
        (ids,) = batch
        logits = self._logits(p, ids)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, ids[..., None], -1)[..., 0]
        # moves no value and one element of one leaf's gradient
        w = p["head.weight"][0, 0]
        return jnp.mean(ce) + self.off * (w - jax.lax.stop_gradient(w))


def _ids(seed):
    return (np.random.default_rng(seed).integers(
        0, VOCAB, (2, 6)).astype(np.int32),)


def _reference(model_cls=TwoLeaves, **kw):
    standin = StandIn(**kw)
    return standin, Reference(Family(standin, model_cls, Config, batch=_ids))


def test_the_reference_is_asked_once_however_many_checks_read_it():
    standin, reference = _reference()
    for _ in range(2):
        for recompute in (False, True):
            seen = check_matches_reference(reference, recompute)
            assert sorted(n for n, _ in seen.model.named_parameters()) == [
                "embed.weight", "head.weight"]
        check_trains_through_to_static(reference, rtol=3e-2)
    assert standin.calls == {"init_weights": 1, "forward": 1, "loss_fn": 1,
                             "train": 1}
    # another batch is another training run, and is kept as well
    for _ in range(2):
        check_adamw_step(reference)
    assert standin.calls == {"init_weights": 1, "forward": 1, "loss_fn": 1,
                             "train": 2}
    # a fresh model each time, from the kept weights
    a, b = reference.model()[0], reference.model()[0]
    assert a is not b and standin.calls["init_weights"] == 1
    np.testing.assert_array_equal(a.head.weight.numpy(),
                                  b.head.weight.numpy())
    # a configuration of its own is a reference of its own
    check_matches_reference(reference, False, width=4)
    assert standin.calls["init_weights"] == 2 and standin.calls["forward"] == 2


# the gradient of head.weight is about 0.05 at its largest: an element off
# by ``off`` is off by 20 x off of the leaf's size, against 2e-5
@pytest.mark.parametrize("off,fails", [(1e-4, True), (1e-8, False)],
                         ids=["off", "within"])
def test_a_check_fails_on_one_leaf_off_by_more_than_its_tolerance(off,
                                                                   fails):
    _, reference = _reference(off=off)
    if not fails:
        check_matches_reference(reference, False)
        return
    with pytest.raises(AssertionError, match="head.weight"):
        check_matches_reference(reference, False)
    # ... and passes the leaf at a tolerance that holds it
    check_matches_reference(reference, False, grad_rel=1e-1)


@pytest.mark.parametrize("off,fails", [(1e-4, True), (1e-7, False)],
                         ids=["off", "within"])
def test_a_check_fails_on_one_forward_result_off_by_more_than_its_tolerance(
        off, fails):
    _, reference = _reference(logit_off=off)
    if fails:
        with pytest.raises(AssertionError, match="logits"):
            check_matches_reference(reference, False)
    else:
        check_matches_reference(reference, False)


def test_a_check_fails_on_a_training_run_that_is_another():
    standin, reference = _reference()
    train = standin.train
    standin.train = lambda cfg, hyper, seed, batches: train(
        cfg, dict(hyper, learning_rate=10 * hyper["learning_rate"]), seed,
        batches)
    with pytest.raises(AssertionError):
        check_trains_through_to_static(reference, rtol=3e-2)
    with pytest.raises(AssertionError, match="weight"):
        check_adamw_step(reference)


def test_no_check_passes_on_an_empty_parameter_list():
    standin, reference = _reference(NoLeaves)
    standin.init_weights = lambda cfg, seed: {}
    with pytest.raises(AssertionError):
        check_matches_reference(reference, False)
    with pytest.raises(AssertionError):
        load(NoLeaves, Config(), standin)
    # names that differ are no match either
    standin, reference = _reference()
    weights = StandIn._weights(dict(vars(Config())), 5)
    weights["lm_head.weight"] = weights.pop("head.weight")
    with pytest.raises(AssertionError):
        load(TwoLeaves, Config(), standin, weights=weights)


def test_the_shares_check_fails_on_a_share_that_holds_anothers_experts():
    """8 experts in 4 shares, top-2, against the sdar reference's layer:
    a share loaded with its neighbour's weights is no part of the whole."""
    key = jax.random.key(3)
    u = 0.5 * jax.random.normal(key, (2, 12, 32))

    def make(held):
        return nn.RoutedMoE(32, 16, 8, 2, gated=True, scoring="softmax",
                            experts_held=held)

    whole = make(None)
    for i, (_, p) in enumerate(whole.named_parameters()):
        p.set_value(0.2 * jax.random.normal(jax.random.fold_in(key, i + 1),
                                            tuple(p.shape)))
    w = {k: p.data for k, p in whole.named_parameters()}

    def ref_cfg(first, n):
        return dict(num_experts=n, num_experts_published=8,
                    num_experts_per_tok=2, first_expert_held=first)

    def layer(first, n, shift=0):
        if n == 8:
            return whole
        share = routed_share(make, w, first, n)
        if shift and first == 2:
            share.experts_up.set_value(w["experts_up"][4:6])
        return share

    check_expert_shares_add_up(sdar_moe, w, layer, ref_cfg, u, experts=8,
                               held=2)
    with pytest.raises(AssertionError):
        check_expert_shares_add_up(
            sdar_moe, w, lambda first, n: layer(first, n, shift=1), ref_cfg,
            u, experts=8, held=2)
