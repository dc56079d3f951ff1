"""Optimizer update rules vs closed form (SURVEY §4)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt


def quad_param(v=None):
    return pt.Parameter(np.asarray(v if v is not None else [1.0, 2.0], "f4"))


def step_once(o, w):
    loss = (w * w).sum()
    loss.backward()
    o.step()
    o.clear_grad()


def test_sgd_closed_form():
    w = quad_param()
    o = opt.SGD(learning_rate=0.1, parameters=[w])
    step_once(o, w)  # w -= lr * 2w
    np.testing.assert_allclose(w.numpy(), [0.8, 1.6], atol=1e-6)


def test_momentum_closed_form():
    w = quad_param()
    o = opt.Momentum(learning_rate=0.1, momentum=0.9, parameters=[w])
    step_once(o, w)
    np.testing.assert_allclose(w.numpy(), [0.8, 1.6], atol=1e-6)
    step_once(o, w)
    # v2 = 0.9*[2,4] + 2*[0.8,1.6]; w2 = w1 - 0.1*v2
    np.testing.assert_allclose(w.numpy(), [0.8 - 0.1 * (1.8 + 1.6),
                                           1.6 - 0.1 * (3.6 + 3.2)],
                               atol=1e-5)


def test_adam_closed_form():
    w = quad_param([1.0])
    o = opt.Adam(learning_rate=0.1, parameters=[w])
    step_once(o, w)
    # first adam step ≈ -lr * sign(g)
    np.testing.assert_allclose(w.numpy(), [1.0 - 0.1], atol=1e-4)


def test_adamw_decoupled_decay():
    w = quad_param([1.0])
    o = opt.AdamW(learning_rate=0.1, parameters=[w], weight_decay=0.1)
    step_once(o, w)
    np.testing.assert_allclose(w.numpy(), [1.0 - 0.1 - 0.1 * 0.1 * 1.0],
                               atol=1e-4)


def _adam_reference(p, g, m, v, t, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    """Adam / AdamW in NumPy float64; ``t`` counts this leaf's own steps."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = lr * (m / (1 - b1 ** t)) / (np.sqrt(v / (1 - b2 ** t)) + eps)
    return p - step - lr * wd * p, m, v


@pytest.mark.parametrize("layout", ["per_leaf", "flat_arena"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cls,wd", [(opt.Adam, 0.0), (opt.AdamW, 0.1)],
                         ids=["Adam", "AdamW"])
def test_adam_rule_follows_a_float64_reference(cls, wd, dtype, layout):
    """Five compiled steps on seeded gradients: the parameters follow the
    float64 rule to their dtype's rounding, keep their dtype (the float32
    lr must not promote a bfloat16 leaf), and the step compiles once."""
    import jax.numpy as jnp
    from paddle_tpu import jit, monitor, nn
    rng = np.random.RandomState(0)
    shapes = [(5, 3), (7,)]

    def rounded(x):  # to values the dtype holds exactly
        return np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))

    p0 = [rounded(rng.randn(*s)) for s in shapes]
    grads = [[rounded(rng.randn(*s)) for s in shapes] for _ in range(5)]
    ws = [pt.Parameter(jnp.asarray(p, dtype)) for p in p0]
    holder = nn.Layer()
    holder.w0, holder.w1 = ws
    kw = {"weight_decay": wd} if cls is opt.AdamW else {}
    o = cls(learning_rate=0.1, parameters=ws,
            flat_arena=(layout == "flat_arena"), **kw)

    def step(g0, g1):
        loss = (ws[0] * g0).sum() + (ws[1] * g1).sum()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    monitor.enable(None)
    try:
        compiles0 = monitor.counter("jit.compile")._value
        recompiles0 = monitor.counter("jit.recompile")._value
        fn = jit.to_static(step, models=[holder], optimizers=[o])
        ref = [(p.astype("f8"), np.zeros_like(p, "f8"),
                np.zeros_like(p, "f8")) for p in p0]
        # a bfloat16 leaf rounds to 2^-8 of its size at every step
        tol = {"float32": 2e-5, "bfloat16": 5 * 2.0 ** -8}[dtype]
        for t, gs in enumerate(grads, 1):
            fn(*[pt.to_tensor(jnp.asarray(g, dtype)) for g in gs])
            ref = [_adam_reference(p, g.astype("f8"), m, v, t, 0.1, wd)
                   for (p, m, v), g in zip(ref, gs)]
            for w, (p, _, _) in zip(ws, ref):
                assert str(w.numpy().dtype) == dtype
                np.testing.assert_allclose(
                    np.asarray(w.numpy(), "f8"), p, rtol=tol, atol=tol)
        assert monitor.counter("jit.compile")._value == compiles0 + 1
        assert monitor.counter("jit.recompile")._value == recompiles0
    finally:
        monitor.disable(flush_counters=False)
    assert (o._arena is not None) == (layout == "flat_arena")


def test_a_parameter_that_skips_steps_keeps_its_own_bias_correction():
    """Per leaf, each parameter carries its own beta-pows: a leaf whose
    gradient is None on alternate steps is corrected by ITS step count,
    not by its neighbour's (what a shared correction would get wrong)."""
    rng = np.random.RandomState(1)
    a = pt.Parameter(rng.randn(4).astype("f4"))
    b = pt.Parameter(rng.randn(4).astype("f4"))
    o = opt.Adam(learning_rate=0.05, parameters=[a, b])
    ref = {k: (w.numpy().astype("f8"), np.zeros(4), np.zeros(4), 0)
           for k, w in (("a", a), ("b", b))}
    for i in range(6):
        ga, gb = rng.randn(4).astype("f4"), rng.randn(4).astype("f4")
        loss = (a * pt.to_tensor(ga)).sum()
        live = {"a": ga}
        if i % 2 == 0:
            loss = loss + (b * pt.to_tensor(gb)).sum()
            live["b"] = gb
        loss.backward()
        o.step()
        o.clear_grad()
        for k, g in live.items():
            p, m, v, t = ref[k]
            ref[k] = _adam_reference(p, g.astype("f8"), m, v, t + 1,
                                     0.05, 0.0) + (t + 1,)
    assert ref["a"][3] == 6 and ref["b"][3] == 3
    np.testing.assert_allclose(a.numpy(), ref["a"][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(b.numpy(), ref["b"][0], rtol=1e-5, atol=1e-6)
    slots = o._accumulators[id(b)]
    np.testing.assert_allclose(float(slots["beta1_pow"].numpy()), 0.9 ** 3,
                               rtol=1e-6)


def test_adagrad_rmsprop_adadelta_run():
    for cls in [opt.Adagrad, opt.RMSProp, opt.Adadelta, opt.Adamax,
                opt.Lamb, opt.Ftrl, opt.DecayedAdagrad, opt.LarsMomentum]:
        w = quad_param()
        o = cls(learning_rate=0.01, parameters=[w])
        before = w.numpy().copy()
        step_once(o, w)
        assert not np.allclose(w.numpy(), before), cls.__name__


def test_convergence_sgd_quadratic():
    w = quad_param([5.0, -3.0])
    o = opt.SGD(learning_rate=0.2, parameters=[w])
    for _ in range(50):
        step_once(o, w)
    np.testing.assert_allclose(w.numpy(), [0.0, 0.0], atol=1e-3)


def test_regularization_l2():
    w = quad_param([1.0])
    o = opt.SGD(learning_rate=0.1, parameters=[w],
                weight_decay=pt.regularizer.L2Decay(0.5))
    # grad = 2w + 0.5w = 2.5
    step_once(o, w)
    np.testing.assert_allclose(w.numpy(), [1.0 - 0.25], atol=1e-6)


def test_grad_clip_global_norm():
    w = quad_param([3.0, 4.0])  # grad = [6, 8], norm 10
    o = opt.SGD(learning_rate=1.0, parameters=[w],
                grad_clip=pt.ClipGradByGlobalNorm(1.0))
    step_once(o, w)
    np.testing.assert_allclose(w.numpy(), [3.0 - 0.6, 4.0 - 0.8], atol=1e-5)


def test_lr_scheduler_wiring():
    w = quad_param()
    sched = opt.lr.StepDecay(learning_rate=0.1, step_size=2, gamma=0.1)
    o = opt.SGD(learning_rate=sched, parameters=[w])
    assert abs(o.get_lr() - 0.1) < 1e-8
    sched.step()
    sched.step()
    assert abs(o.get_lr() - 0.01) < 1e-8
    # the device-side lr tensor followed
    assert abs(float(o._lr_tensor.numpy()) - 0.01) < 1e-8


@pytest.mark.parametrize("cls,kw", [
    (opt.lr.NoamDecay, dict(d_model=512, warmup_steps=100)),
    (opt.lr.ExponentialDecay, dict(learning_rate=0.1, gamma=0.9)),
    (opt.lr.PolynomialDecay, dict(learning_rate=0.1, decay_steps=10)),
    (opt.lr.CosineAnnealingDecay, dict(learning_rate=0.1, T_max=10)),
    (opt.lr.PiecewiseDecay, dict(boundaries=[2, 4], values=[0.1, 0.01, 0.001])),
    (opt.lr.MultiStepDecay, dict(learning_rate=0.1, milestones=[2, 4])),
    (opt.lr.LinearWarmup, dict(learning_rate=0.1, warmup_steps=5,
                               start_lr=0.0, end_lr=0.1)),
])
def test_schedulers_produce_positive_lrs(cls, kw):
    s = cls(**kw)
    vals = [s.step() for _ in range(6)]
    assert all(v >= 0 for v in vals)


def test_ema():
    w = quad_param([1.0])
    ema = opt.ExponentialMovingAverage(decay=0.5)
    ema.update([w])
    w.set_value(np.array([3.0], "f4"))
    ema.update([w])
    with ema.apply([w]):
        # shadow ≈ between 1 and 3
        assert 1.0 <= float(w.numpy()[0]) <= 3.0
    np.testing.assert_allclose(w.numpy(), [3.0])


def test_lookahead():
    w = quad_param([2.0])
    inner = opt.SGD(learning_rate=0.1, parameters=[w])
    la = opt.LookAhead(inner, alpha=0.5, k=2)
    for _ in range(4):
        loss = (w * w).sum()
        loss.backward()
        la.step()
        la.clear_grad()
    assert float(w.numpy()[0]) < 2.0


def test_optimizer_tail_untested():
    """Closed-form checks for the optimizers nothing else exercised:
    Dpsgd (clipped + noisy step moves params), ModelAverage (window
    average apply/restore), RecomputeOptimizer (delegates to inner).
    (DGCMomentum==Momentum lives in test_namespace_parity.)"""
    # Dpsgd: params move and stay finite (stochastic by design)
    pt.seed(0)
    w = pt.Parameter(np.ones((8,), "f4"))
    od = opt.Dpsgd(learning_rate=0.05, clip=1.0, sigma=0.1,
                   parameters=[w])
    before = w.numpy().copy()
    (w * w).sum().backward()
    od.step()
    od.clear_grad()
    assert np.isfinite(w.numpy()).all()
    assert not np.allclose(before, w.numpy())

    # ModelAverage: apply() swaps in the window average, restore() undoes
    w = pt.Parameter(np.zeros((2,), "f4"))
    ma = opt.ModelAverage(0.15)
    seen = []
    for step_val in (1.0, 2.0, 3.0):
        w.set_value(np.full((2,), step_val, "f4"))
        ma.update([w])
        seen.append(step_val)
    cur = w.numpy().copy()
    with ma.apply([w]):
        np.testing.assert_allclose(w.numpy(), np.mean(seen), atol=1e-6)
    np.testing.assert_allclose(w.numpy(), cur, atol=0)

    # RecomputeOptimizer: duck-types the inner optimizer
    w = pt.Parameter(np.ones((3,), "f4"))
    ro = opt.RecomputeOptimizer(opt.SGD(learning_rate=0.5,
                                        parameters=[w]))
    (w * w).sum().backward()
    ro.step()
    ro.clear_grad()
    np.testing.assert_allclose(w.numpy(), 0.0, atol=1e-6)


def test_lr_scheduler_tail_untested():
    """Closed-form checks for the schedulers nothing else exercised."""
    s = opt.lr.NaturalExpDecay(1.0, gamma=0.5)
    vals = []
    for _ in range(3):
        vals.append(s())
        s.step()
    np.testing.assert_allclose(vals, [1.0, np.exp(-0.5), np.exp(-1.0)],
                               rtol=1e-6)

    s = opt.lr.InverseTimeDecay(1.0, gamma=1.0)
    vals = []
    for _ in range(3):
        vals.append(s())
        s.step()
    np.testing.assert_allclose(vals, [1.0, 0.5, 1 / 3], rtol=1e-6)

    s = opt.lr.LambdaDecay(2.0, lr_lambda=lambda e: 0.9 ** e)
    vals = []
    for _ in range(3):
        vals.append(s())
        s.step()
    np.testing.assert_allclose(vals, [2.0, 1.8, 2.0 * 0.81], rtol=1e-6)

    # ReduceOnPlateau: lr drops by factor after patience non-improvements
    s = opt.lr.ReduceOnPlateau(1.0, factor=0.5, patience=2, cooldown=0)
    lrs = []
    for loss in (1.0, 1.0, 1.0, 1.0, 1.0):
        s.step(loss)
        lrs.append(s())
    # deterministic: exactly one halving after patience=2 bad epochs
    assert abs(lrs[-1] - 0.5) < 1e-6, lrs
