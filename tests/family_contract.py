"""What a causal family has to show to be on the training path, held once.

A family's test file (``tests/test_{nemotron_h,joyai_llm_flash,sdar_moe,
smallthinker,lfm2}.py``) describes the family as a ``Family`` - its model
and configuration classes, its plain float32 reference module under
``benchmark/reference`` (which imports nothing of paddle_tpu), how a batch
is made and what of it the forward and the loss read - and calls the checks
below from tests that keep their own names. A new family's file does the
same; it does not copy this one.

A ``Reference`` builds what the reference gives for a configuration and a
batch ONCE and keeps it for the file (a module-scoped fixture): the seed's
weights, ``R.forward``'s results, ``R.loss_fn``'s value and gradient tree,
``R.train``'s losses. ``[plain]`` and ``[recompute]`` compare against the
same kept tree, and a test that needs a fresh model gets a newly built one
loaded from the kept weights. The model's own eager forward and backward
through the tape are run by every check: they are the code under test.

Not collected (no ``test_`` in its name); ``tests/test_family_contract.py``
tests it against a counting stand-in for a reference module.
"""
import contextlib
import dataclasses
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                         # noqa: E402
from paddle_tpu import amp, jit                                 # noqa: E402
from paddle_tpu import optimizer as opt                         # noqa: E402

HYPER = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8,
             weight_decay=0.1)
# one value each in every family's file: the seed of the reference's
# weights, the steps of a training run, the batch of the one-step check,
# and the tolerances no family differs in
SEED = 5
STEPS = 3
ADAMW_BATCH_SEED = 3
LOSS_TOL = 1e-5
DELTA_REL = 1e-4
WHOLE_ATOL = 2e-6


def plain(spec, a, b):
    """The ``ein`` a reference's layer functions take, at float32."""
    return jnp.einsum(spec, a, b)


def ids(rows=2, seq=24, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, seq)).astype(np.int32)


def rel(got, ref):
    """The largest difference over the reference's largest value."""
    got, ref = (np.asarray(t, np.float32) for t in (got, ref))
    return np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12)


def load(model_cls, config, R, weights=None, **cfg_extra):
    """(model holding the reference's seeded weights, cfg dict, weights);
    ``weights`` given, they are loaded and ``R.init_weights`` is not
    called."""
    cfg = dict(vars(config), **cfg_extra)
    model = model_cls(config)
    if weights is None:
        weights = R.init_weights(cfg, SEED)
    params = dict(model.named_parameters())
    assert params and set(params) == set(weights)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_value(weights[name])
    return model, cfg, weights


def _first(batch):
    return batch[:1]


def _causal_loss(model, outputs, batch):
    return model.loss(outputs[0], batch[0])


@dataclasses.dataclass(frozen=True)
class Family:
    """A family as the checks need it. ``batch(seed)`` is the reference's
    batch, a tuple of numpy arrays; ``inputs(batch)`` what the model's and
    the reference's forward read of it, in their order; ``loss(model,
    outputs, batch)`` the model's loss from its outputs (a tuple) and the
    batch as tensors; ``outputs`` names what the forward returns."""
    R: object
    model_cls: type
    tiny: object
    batch: object = lambda seed: (ids(seed=seed),)
    inputs: object = _first
    loss: object = _causal_loss
    outputs: tuple = ("logits",)
    cfg_extra: dict = dataclasses.field(default_factory=dict)


def _tensors(arrays):
    return tuple(pt.to_tensor(np.asarray(a)) for a in arrays)


def _tuple(out):
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


def _batch_key(batch):
    return tuple((a.shape, a.dtype.str, a.tobytes())
                 for a in map(np.asarray, batch))


class Reference:
    """What ``family.R`` gives from ``SEED``, each computed once and kept
    by the configuration (``recompute`` left out: no reference reads it)
    and the batch."""

    def __init__(self, family):
        self.family, self._kept = family, {}

    def once(self, key, make):
        """``make()`` the first time ``key`` is asked for, its result
        after: a family file keeps its own reference results here too."""
        if key not in self._kept:
            self._kept[key] = make()
        return self._kept[key]

    @staticmethod
    def _cfg_key(cfg):
        return tuple(sorted((k, repr(v)) for k, v in cfg.items()
                            if k != "recompute"))

    def weights(self, cfg):
        return self.once(("weights", self._cfg_key(cfg)),
                         lambda: self.family.R.init_weights(cfg, SEED))

    def model(self, **kw):
        """A newly constructed model holding the kept weights."""
        f = self.family
        config = f.tiny(**kw)
        weights = self.weights(dict(vars(config), **f.cfg_extra))
        return load(f.model_cls, config, f.R, weights, **f.cfg_extra)

    def forward(self, cfg, batch):
        f = self.family
        return self.once(
            ("forward", self._cfg_key(cfg), _batch_key(batch)),
            lambda: _tuple(f.R.forward(cfg, self.weights(cfg), *(
                jnp.asarray(a) for a in f.inputs(batch)))))

    def loss_and_grad(self, cfg, batch):
        """``R.loss_fn``'s value and its gradient for every leaf, one
        compiled program."""
        R = self.family.R
        arrays = tuple(jnp.asarray(a) for a in batch)
        return self.once(
            ("grad", self._cfg_key(cfg), _batch_key(batch)),
            lambda: jax.jit(jax.value_and_grad(
                lambda q: R.loss_fn(cfg, q, arrays)))(self.weights(cfg)))

    def train(self, cfg, hyper, batches):
        return self.once(
            ("train", self._cfg_key(cfg), tuple(sorted(hyper.items())),
             tuple(_batch_key(b) for b in batches)),
            lambda: self.family.R.train(cfg, hyper, SEED, batches))


@dataclasses.dataclass
class Compared:
    """What ``check_matches_reference`` built and compared, for the
    assertions that are a family's own."""
    model: object
    cfg: dict
    weights: dict
    batch: tuple
    outputs: tuple
    loss: object
    want_grad: dict


def check_matches_reference(reference, recompute, *, outputs_atol=2e-6,
                            grad_rel=2e-5, grad_atol=None, **config):
    """The model's forward results, loss and EVERY parameter's gradient
    (through the tape) against the reference's. ``grad_atol``: {part of a
    name: the largest difference} for the leaves whose gradient is a sum
    that all but cancels, so that both sides read rounding beside it (a
    key bias adds one number to a whole soft-max row: zero in exact
    arithmetic): an absolute bound there, added to the relative one."""
    f = reference.family
    model, cfg, weights = reference.model(recompute=recompute, **config)
    batch = f.batch(0)
    outputs = _tuple(model(*_tensors(f.inputs(batch))))
    want = reference.forward(cfg, batch)
    assert len(outputs) == len(want) == len(f.outputs) > 0
    for name, got, ref in zip(f.outputs, outputs, want):
        assert tuple(got.shape) == tuple(ref.shape), name
        np.testing.assert_allclose(got.numpy(), ref, atol=outputs_atol,
                                   err_msg=name)
    loss = f.loss(model, outputs, _tensors(batch))
    want_loss, want_grad = reference.loss_and_grad(cfg, batch)
    assert abs(float(loss.numpy()) - float(want_loss)) < LOSS_TOL
    loss.backward()
    params = dict(model.named_parameters())
    assert params and set(params) == set(want_grad)
    for name, p in params.items():
        assert p._grad is not None, name
        atol = [a for part, a in (grad_atol or {}).items() if part in name]
        want = np.asarray(want_grad[name])
        assert np.abs(np.asarray(p._grad) - want).max() \
            < grad_rel * (np.abs(want).max() + 1e-12) + sum(atol[:1]), name
    return Compared(model, cfg, weights, batch, outputs, loss, want_grad)


def _training_step(family, model, o, autocast):
    def step(*batch):
        with amp.auto_cast(dtype="bfloat16") if autocast \
                else contextlib.nullcontext():
            outputs = _tuple(model(*family.inputs(batch)))
        if autocast:
            outputs = tuple(t.astype("float32") for t in outputs)
        loss = family.loss(model, outputs, batch)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    return jit.to_static(step, models=[model], optimizers=[o])


def check_trains_through_to_static(reference, *, rtol, **config):
    """``STEPS`` AdamW steps under bfloat16 autocast through
    ``jit.to_static`` against the reference's float32 ones: the losses
    agree to bfloat16's rounding. Returns (the losses, the batches)."""
    f = reference.family
    model, cfg, _ = reference.model(recompute=True, **config)
    o = opt.AdamW(parameters=model.parameters(), **HYPER)
    compiled = _training_step(f, model, o, autocast=True)
    batches = [f.batch(s) for s in range(STEPS)]
    got = [float(compiled(*_tensors(b)).numpy()) for b in batches]
    want = reference.train(cfg, HYPER, batches)["loss"]
    assert len(got) == len(want) == STEPS
    np.testing.assert_allclose(got, want, rtol=rtol)
    return got, batches


def check_adamw_step(reference, **config):
    """float32 through ``jit.to_static``: the loss, and every parameter's
    change after one AdamW step, leaf by leaf."""
    f = reference.family
    model, cfg, weights = reference.model(recompute=True, **config)
    o = opt.AdamW(parameters=model.parameters(), **HYPER)
    batch = f.batch(ADAMW_BATCH_SEED)
    got = float(_training_step(f, model, o, autocast=False)(
        *_tensors(batch)).numpy())
    want = reference.train(cfg, HYPER, [batch])
    assert abs(got - want["loss"][0]) < LOSS_TOL
    params = dict(model.named_parameters())
    assert params and set(params) == set(want["delta_norm"])
    for name, p in params.items():
        moved = float(jnp.sqrt(jnp.sum(jnp.square(p.data - weights[name]))))
        assert abs(moved - want["delta_norm"][name]) \
            <= DELTA_REL * want["delta_norm"][name] + 1e-9, name


STACKED = ("experts_gate", "experts_up", "experts_down")


def routed_share(make, weights, first, n, stacked=STACKED):
    """``make(range(first, first + n))``, a ``nn.RoutedMoE`` without a
    shared expert, holding the router and its share of the ``stacked``
    leaves of ``weights``."""
    share = make(range(first, first + n))
    share.router.weight.set_value(weights["router.weight"])
    for k in stacked:
        getattr(share, k).set_value(weights[k][first:first + n])
    return share


def check_expert_shares_add_up(R, weights, make_layer, ref_cfg, x, *,
                               experts, held, router_input=None,
                               stacked=STACKED, shared=None, sum_atol=3e-6,
                               part_atol=None):
    """The shares ``range(0, held) ... range(experts - held, experts)`` of
    one expert layer add up to the layer that holds all ``experts``, in
    the program and in the reference.

    ``weights``: the whole layer's, under the reference's names, the
    ``stacked`` leaves with every expert; ``make_layer(first, n)``: the
    program's layer holding experts ``first .. first + n`` of them;
    ``ref_cfg(first, n)``: the reference's configuration of that layer;
    ``x`` (and ``router_input``): what the experts (and the router) read,
    ``[rows, seq, d]``. Without a shared expert the program's layer of all
    experts is built too: it is the reference's to ``WHOLE_ATOL`` and both
    sums are their whole to ``sum_atol``. With one, ``shared(layer, t)`` is
    what it adds to every share: counted once, the program's sum is the
    reference's whole layer to ``sum_atol``. ``part_atol``: each share
    against the reference's of that share. Returns the slots the shares
    routed to their held experts (``stats[0]``, added up)."""
    assert experts % held == 0 and experts // held > 1
    d = x.shape[-1]
    xt = pt.to_tensor(np.asarray(x))
    flat = [jnp.reshape(x, (-1, d))]
    call = {}
    if router_input is not None:
        call["router_input"] = pt.to_tensor(np.asarray(router_input))
        flat.insert(0, jnp.reshape(router_input, (-1, d)))

    def ref_layer(first, n):
        part = dict(weights, **{k: weights[k][first:first + n]
                                for k in stacked})
        return np.asarray(R._moe(ref_cfg(first, n), part, *flat,
                                 plain)).reshape(x.shape)

    ref_whole = ref_layer(0, experts)
    once = 0.0 if shared is None \
        else shared(make_layer(0, held), xt).numpy()
    total, ref_total, routed = once, 0.0, 0
    for first in range(0, experts, held):
        layer = make_layer(first, held)
        part = layer(xt, **call).numpy()
        assert np.abs(part).max() > 0
        ref_part = ref_layer(first, held)
        if part_atol is not None:
            np.testing.assert_allclose(part, ref_part, atol=part_atol)
        total = total + (part - once)
        ref_total = ref_total + ref_part
        routed += int(layer.stats.numpy()[0])
    if shared is None:
        want = make_layer(0, experts)(xt, **call).numpy()
        np.testing.assert_allclose(want, ref_whole, atol=WHOLE_ATOL)
        np.testing.assert_allclose(total, want, atol=sum_atol)
        np.testing.assert_allclose(ref_total, ref_whole, atol=sum_atol)
    else:
        np.testing.assert_allclose(total, ref_whole, atol=sum_atol)
    return routed
