"""jit.to_static: compiled train step parity with eager (SURVEY §3)."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import nn, optimizer as opt, jit


def make_model():
    pt.seed(42)
    return nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 2))


def run_steps(model, o, compiled, n=5):
    pt.seed(7)
    losses = []
    xs = [np.random.RandomState(i).randn(8, 4).astype("f4") for i in range(n)]
    ys = [np.random.RandomState(100 + i).randn(8, 2).astype("f4")
          for i in range(n)]

    def step(x, y):
        out = model(x)
        loss = (out - y).square().mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o]) if compiled \
        else step
    for x, y in zip(xs, ys):
        losses.append(float(fn(pt.to_tensor(x), pt.to_tensor(y)).numpy()))
    return losses


def test_to_static_matches_eager():
    m1, m2 = make_model(), make_model()
    for (k1, v1), (k2, v2) in zip(sorted(m1.state_dict().items()),
                                  sorted(m2.state_dict().items())):
        np.testing.assert_allclose(v1.numpy(), v2.numpy())
    o1 = opt.Adam(learning_rate=0.01, parameters=m1.parameters())
    o2 = opt.Adam(learning_rate=0.01, parameters=m2.parameters())
    eager = run_steps(m1, o1, compiled=False)
    static = run_steps(m2, o2, compiled=True)
    np.testing.assert_allclose(eager, static, rtol=2e-3)
    # params also match after training
    for (_, v1), (_, v2) in zip(sorted(m1.state_dict().items()),
                                sorted(m2.state_dict().items())):
        np.testing.assert_allclose(v1.numpy(), v2.numpy(), atol=2e-4)


def test_to_static_caches_compilation():
    model = make_model()
    o = opt.SGD(learning_rate=0.01, parameters=model.parameters())

    calls = {"n": 0}

    def step(x):
        calls["n"] += 1
        loss = model(x).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    x = pt.to_tensor(np.random.randn(8, 4).astype("f4"))
    fn(x)
    fn(x)
    fn(x)
    assert calls["n"] == 1  # traced once, replayed compiled
    # new shape -> retrace
    fn(pt.to_tensor(np.random.randn(16, 4).astype("f4")))
    assert calls["n"] == 2


def test_to_static_dropout_rng_advances():
    model = nn.Sequential(nn.Dropout(0.5))
    model.train()
    fn = jit.to_static(lambda x: model(x), models=[model], optimizers=[])
    x = pt.to_tensor(np.ones((100,), "f4"))
    a = fn(x).numpy()
    b = fn(x).numpy()
    assert not np.allclose(a, b)  # key advanced between compiled calls


def test_to_static_closure_discovery():
    model = make_model()
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())

    @jit.to_static
    def step(x):
        loss = model(x).square().mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    x = pt.to_tensor(np.random.randn(4, 4).astype("f4"))
    l1 = float(step(x).numpy())
    l2 = float(step(x).numpy())
    assert l2 < l1  # params actually updated through compiled state carry


def test_to_static_batchnorm_stats_carry():
    model = nn.Sequential(nn.Linear(4, 8), nn.BatchNorm1D(8))
    model.train()
    fn = jit.to_static(lambda x: model(x).mean(), models=[model],
                       optimizers=[])
    bn = model[1]
    before = bn._mean.numpy().copy()
    fn(pt.to_tensor(np.random.randn(16, 8, 1).astype("f4")[:, :4, 0]))
    after = bn._mean.numpy()
    assert not np.allclose(before, after)


def test_recompute_matches_plain():
    pt.seed(0)
    block = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 4))
    x = pt.to_tensor(np.random.randn(2, 4).astype("f4"), stop_gradient=False)
    out = jit.recompute(block, x)
    loss = out.square().mean()
    loss.backward()
    g_remat = x.grad

    x2 = pt.to_tensor(x.numpy(), stop_gradient=False)
    loss2 = block(x2).square().mean()
    loss2.backward()
    np.testing.assert_allclose(np.asarray(g_remat), np.asarray(x2.grad),
                               atol=1e-5)


def test_to_static_multi_step_unrolled_matches_sequential():
    """A step function may run `inner` REAL optimizer steps inside ONE
    compiled call (dispatch amortization); the unrolled trace must produce
    bit-comparable params to running the steps one compiled call each."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer as opt, jit

    rng = np.random.RandomState(0)
    xs = rng.randn(3, 8, 4).astype("f4")
    ys = rng.randn(3, 8, 1).astype("f4")

    def make():
        pt.seed(0)
        m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
        o = opt.Adam(learning_rate=0.05, parameters=m.parameters())
        return m, o

    def body(m, o, xb, yb):
        loss = ((m(xb) - yb) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    # A: one call per step
    m1, o1 = make()
    f1 = jit.to_static(lambda xb, yb: body(m1, o1, xb, yb),
                       models=[m1], optimizers=[o1])
    for i in range(3):
        l1 = f1(pt.to_tensor(xs[i]), pt.to_tensor(ys[i]))

    # B: all three steps unrolled in one call
    m2, o2 = make()

    def step3(x_k, y_k):
        loss = None
        for i in range(3):
            loss = body(m2, o2, x_k[i], y_k[i])
        return loss

    f3 = jit.to_static(step3, models=[m2], optimizers=[o2])
    l3 = f3(pt.to_tensor(xs), pt.to_tensor(ys))

    np.testing.assert_allclose(float(l1.numpy()), float(l3.numpy()),
                               rtol=1e-5)
    for p1, p2 in zip(m1.parameters(), m2.parameters()):
        np.testing.assert_allclose(p1.numpy(), p2.numpy(), atol=1e-6)


def test_bert_recompute_matches_plain():
    """use_recompute=True (per-layer jax.checkpoint, RNG threaded
    explicitly through the checkpointed region) must be bit-comparable to
    the plain path with dropout off, and train with dropout on."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu import optimizer as opt, jit

    kw = dict(use_flash_attention=False, hidden_dropout_prob=0.0,
              attention_probs_dropout_prob=0.0)
    pt.seed(0)
    m1 = BertForPretraining(BertConfig.tiny(use_recompute=True, **kw))
    pt.seed(0)
    m2 = BertForPretraining(BertConfig.tiny(**kw))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 1024, (2, 16)).astype("i4")
    mask = np.ones((2, 16), "i4")
    mask[1, 10:] = 0
    mlm = np.full((2, 16), -1, "i4")
    mlm[:, 3] = 5
    nsp = np.zeros((2,), "i4")

    def mk(m):
        o = opt.Adam(learning_rate=1e-3, parameters=m.parameters())

        def step(i, msk, ml, ns):
            lo, nl = m(i, attention_mask=msk)
            loss = m.loss(lo, nl, ml, ns)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss
        return jit.to_static(step, models=[m], optimizers=[o])

    f1, f2 = mk(m1), mk(m2)
    args = [pt.to_tensor(a) for a in (ids, mask, mlm, nsp)]
    a = [float(f1(*args).numpy()) for _ in range(3)]
    b = [float(f2(*args).numpy()) for _ in range(3)]
    np.testing.assert_allclose(a, b, rtol=1e-5)
    assert a[-1] < a[0]  # actually training

    # dropout on: different (valid) mask stream, still trains
    pt.seed(1)
    m3 = BertForPretraining(BertConfig.tiny(use_recompute=True,
                                            use_flash_attention=False))
    f3 = mk(m3)
    c = [float(f3(*args).numpy()) for _ in range(3)]
    assert c[-1] < c[0]


def test_state_cache_sees_unfreeze():
    """Unfreezing a parameter AFTER a compiled step must invalidate the
    cached state map (stop_gradient is part of the validity key): the
    optimizer lazily creates slots for newly-trainable params inside
    _collect_state, so a stale cache would silently never train them."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer as opt, jit

    pt.seed(0)
    m = nn.Linear(4, 4)
    m.weight.stop_gradient = True
    o = opt.Adam(learning_rate=0.1, parameters=m.parameters())
    x = pt.to_tensor(np.random.RandomState(0).randn(8, 4).astype("f4"))

    def step(x):
        loss = (m(x) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    fn = jit.to_static(step, models=[m], optimizers=[o])
    fn(x)
    frozen = m.weight.numpy().copy()
    fn(x)
    np.testing.assert_array_equal(frozen, m.weight.numpy())

    m.weight.stop_gradient = False
    fn(x)
    assert not np.allclose(frozen, m.weight.numpy()), \
        "unfrozen weight never trained: stale jit state cache"


def test_the_steps_argument_order_does_not_follow_addresses():
    """The sorted state names are the compiled step's argument order: two
    builds of one model (other objects at other addresses, as in another
    process) have to name their optimizer slots alike, parameter by
    parameter, or each process lowers a module of its own and the
    persistent compile cache misses (PERF.md section 7 row 33)."""
    from paddle_tpu.jit import _collect_state

    def build(keep):
        m = make_model()
        keep.append([np.zeros(n) for n in range(1, 40)])    # move the heap
        o = opt.AdamW(learning_rate=0.01, parameters=m.parameters())
        holders = _collect_state([m], [o])
        slot_of = {id(t): name for name, t in holders.items()}
        params = {id(p): n for n, p in m.named_parameters()}
        # which parameter each optimizer slot name stands for
        return {slot_of[id(t)]: (params[pid], sname)
                for pid, slots in o._accumulators.items()
                for sname, t in slots.items()}

    keep = []
    a, b = build(keep), build(keep)
    assert len(a) >= 8 and a == b
