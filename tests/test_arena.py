"""Zero-copy flat parameter arena (ISSUE 10): one flat buffer layout
shared by grad sync, fused Adam, and checkpoints.

The acceptance bar is BIT-identity: Optimizer(flat_arena=True) must be
indistinguishable from the per-leaf path on a BERT-shaped tree (mixed
dtypes, a frozen param making trainables non-contiguous) — eager,
to_static, under grad_sync="overlap" lag-1, across checkpoint
round-trips in BOTH layout directions, and in the static Executor.
Plus: zero extra recompiles per epoch, the knob routed through fleet
DistributedStrategy, and the Megatron dp-only flat path."""
import os
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer as opt, jit


class BertishModel(nn.Layer):
    """Small BERT-shaped tree: f32 matmuls, one bf16 leaf (its own
    arena dtype group), and a FROZEN block in the middle so the
    trainable set is non-contiguous in declaration order."""

    def __init__(self):
        super().__init__()
        self.emb = nn.Linear(16, 32)
        self.frozen = nn.Linear(32, 32)
        for p in self.frozen.parameters():
            p.trainable = False
            p.stop_gradient = True
        self.mid = nn.Linear(32, 32)
        self.scale = self.create_parameter([32], dtype="bfloat16",
                                           default_initializer=None)
        self.out = nn.Linear(32, 4)

    def forward(self, x):
        h = self.emb(x)
        h = self.frozen(h)
        h = self.mid(h) * self.scale.astype("float32")
        return self.out(h)


def _pair(seed=11):
    """Two bit-identical models."""
    pt.seed(seed)
    a = BertishModel()
    pt.seed(seed)
    b = BertishModel()
    return a, b


def _data(n=5, seed=0):
    xs = [np.random.RandomState(seed + i).randn(8, 16).astype("f4")
          for i in range(n)]
    ys = [np.random.RandomState(seed + 100 + i).randn(8, 4).astype("f4")
          for i in range(n)]
    return xs, ys


def _train(model, o, xs, ys, compiled=False):
    def step(x, y):
        loss = (model(x) - y).square().mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o]) if compiled \
        else step
    return [float(fn(pt.to_tensor(x), pt.to_tensor(y)).numpy())
            for x, y in zip(xs, ys)]


def _assert_params_equal(a, b):
    sa, sb = a.state_dict(), b.state_dict()
    assert sorted(sa) == sorted(sb)
    for k in sa:
        np.testing.assert_array_equal(
            np.asarray(sa[k].numpy()), np.asarray(sb[k].numpy()), err_msg=k)


@pytest.mark.parametrize("compiled", [False, True])
def test_adam_flat_bit_identical(compiled):
    """Adam flat vs per-leaf: losses AND every param bit-equal over 5
    steps, eager and to_static, mixed dtypes + frozen middle block."""
    ma, mb = _pair()
    oa = opt.Adam(learning_rate=0.01, parameters=ma.parameters())
    ob = opt.Adam(learning_rate=0.01, parameters=mb.parameters(),
                  flat_arena=True)
    xs, ys = _data()
    la = _train(ma, oa, xs, ys, compiled=compiled)
    lb = _train(mb, ob, xs, ys, compiled=compiled)
    assert la == lb
    _assert_params_equal(ma, mb)
    assert ob._arena is not None  # the flat path actually engaged


def test_adamw_flat_bit_identical_to_static():
    """AdamW (decoupled decay) through the compiled path."""
    ma, mb = _pair(seed=23)
    oa = opt.AdamW(learning_rate=0.01, weight_decay=0.02,
                   parameters=ma.parameters())
    ob = opt.AdamW(learning_rate=0.01, weight_decay=0.02,
                   parameters=mb.parameters(), flat_arena=True)
    xs, ys = _data(seed=40)
    la = _train(ma, oa, xs, ys, compiled=True)
    lb = _train(mb, ob, xs, ys, compiled=True)
    assert la == lb
    _assert_params_equal(ma, mb)


def test_flat_with_overlap_lag1_bit_identical():
    """grad_sync="overlap" (lag-1 bucketed sync) composes with the
    arena: flat and per-leaf see the SAME staled gradients and stay
    bit-equal."""
    ma, mb = _pair(seed=31)
    oa = opt.Adam(learning_rate=0.01, parameters=ma.parameters())
    ob = opt.Adam(learning_rate=0.01, parameters=mb.parameters(),
                  flat_arena=True)
    oa.set_grad_sync("overlap")
    ob.set_grad_sync("overlap")
    xs, ys = _data(n=6, seed=7)
    la = _train(ma, oa, xs, ys)
    lb = _train(mb, ob, xs, ys)
    assert la == lb
    _assert_params_equal(ma, mb)


def _np_state(o):
    """Materialize an optimizer state_dict to numpy (what io.save's
    _to_numpy_tree does) so restores are real, not live-tensor no-ops."""
    return {k: np.asarray(v.numpy()) if hasattr(v, "numpy") else v
            for k, v in o.state_dict().items()}


def _np_model_state(m):
    return {k: np.asarray(v.numpy()) for k, v in m.state_dict().items()}


@pytest.mark.parametrize("first,second", [(False, True), (True, False),
                                          (True, True)])
def test_checkpoint_roundtrip_across_layouts(first, second):
    """A checkpoint written under either layout restores under either
    layout and training continues bit-identically with the never-
    checkpointed per-leaf reference."""
    # reference: uninterrupted per-leaf training
    mr, _ = _pair(seed=47)
    orf = opt.Adam(learning_rate=0.02, parameters=mr.parameters())
    xs, ys = _data(n=6, seed=3)
    lr_all = _train(mr, orf, xs, ys)

    m1, m2 = _pair(seed=47)
    o1 = opt.Adam(learning_rate=0.02, parameters=m1.parameters(),
                  flat_arena=first)
    l_head = _train(m1, o1, xs[:3], ys[:3])
    model_sd = _np_model_state(m1)
    opt_sd = _np_state(o1)

    o2 = opt.Adam(learning_rate=0.02, parameters=m2.parameters(),
                  flat_arena=second)
    m2.set_state_dict({k: pt.to_tensor(v) for k, v in model_sd.items()})
    o2.set_state_dict(opt_sd)
    l_tail = _train(m2, o2, xs[3:], ys[3:])
    assert l_head + l_tail == lr_all
    _assert_params_equal(mr, m2)


def test_zero_extra_recompiles_per_epoch(tmp_path):
    """The arena must keep jit cache keys stable: one compile on step 1,
    then cache hits only — recompile stays flat for the whole epoch."""
    from paddle_tpu import monitor as _monitor
    _monitor.enable(str(tmp_path))
    try:
        m, _ = _pair(seed=5)
        o = opt.Adam(learning_rate=0.01, parameters=m.parameters(),
                     flat_arena=True)
        xs, ys = _data(n=8, seed=9)

        def step(x, y):
            loss = (m(x) - y).square().mean()
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        fn = jit.to_static(step, models=[m], optimizers=[o])
        fn(pt.to_tensor(xs[0]), pt.to_tensor(ys[0]))
        compiled0 = _monitor.counter("jit.compile")._value
        recompiled0 = _monitor.counter("jit.recompile")._value
        hits0 = _monitor.counter("jit.cache_hit")._value
        assert compiled0 >= 1
        for x, y in zip(xs[1:], ys[1:]):
            fn(pt.to_tensor(x), pt.to_tensor(y))
        assert _monitor.counter("jit.compile")._value == compiled0
        assert _monitor.counter("jit.recompile")._value == recompiled0
        assert _monitor.counter("jit.cache_hit")._value == hits0 + 7
    finally:
        _monitor.disable(flush_counters=False)


def test_set_flat_arena_toggle_mid_training():
    """Flipping the knob mid-run (per-leaf -> flat -> per-leaf) keeps
    the trajectory bit-identical: enable adopts live slot state, disable
    dissolves the arena back into per-leaf slots."""
    mr, mt = _pair(seed=61)
    orf = opt.Adam(learning_rate=0.01, parameters=mr.parameters())
    ot = opt.Adam(learning_rate=0.01, parameters=mt.parameters())
    xs, ys = _data(n=9, seed=21)
    ref = _train(mr, orf, xs, ys)

    got = _train(mt, ot, xs[:3], ys[:3])
    ot.set_flat_arena(True)
    got += _train(mt, ot, xs[3:6], ys[3:6])
    assert ot._arena is not None
    ot.set_flat_arena(False)
    assert ot._arena is None
    got += _train(mt, ot, xs[6:], ys[6:])
    assert got == ref
    _assert_params_equal(mr, mt)


def test_unsupported_optimizer_raises():
    """Optimizers without a registered slot layout reject the knob
    loudly instead of silently training differently."""
    m, _ = _pair(seed=71)
    with pytest.raises((ValueError, NotImplementedError)):
        opt.SGD(learning_rate=0.1, parameters=m.parameters(),
                flat_arena=True)


def test_fleet_strategy_routes_flat_arena():
    """DistributedStrategy(flat_arena=True, grad_sync=...) routed by
    fleet.distributed_optimizer onto the wrapped optimizer."""
    from paddle_tpu.parallel.fleet import fleet, DistributedStrategy
    from paddle_tpu.parallel.overlap import GradSyncScheduler
    fleet.init()
    m, _ = _pair(seed=83)
    o = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    st = DistributedStrategy()
    st.grad_sync = "overlap"
    st.flat_arena = True
    wrapped = fleet.distributed_optimizer(o, strategy=st)
    assert getattr(wrapped, "_flat_arena", False) is True
    assert isinstance(wrapped._grad_sync, GradSyncScheduler)
    # quantized_allreduce alone implies mode="quantized"
    o2 = opt.Adam(learning_rate=0.01, parameters=m.parameters())
    st2 = DistributedStrategy()
    st2.quantized_allreduce = True
    w2 = fleet.distributed_optimizer(o2, strategy=st2)
    assert w2._grad_sync.mode == "quantized"


def test_static_executor_flat_identity():
    """The program path: Adam.minimize inside program_guard, then the
    Executor's run_fn takes the arena branch (params per-leaf carried,
    m/v/pows flat) — losses and trained params bit-equal to per-leaf
    over 10 steps."""
    from paddle_tpu import static, fluid
    pt.enable_static()
    try:
        rng = np.random.RandomState(0)
        feeds = [{"x": rng.randn(8, 6).astype("f4"),
                  "y": rng.randn(8, 1).astype("f4")} for _ in range(10)]

        def build(flat):
            pt.seed(9)
            prog, startup = static.Program(), static.Program()
            with static.program_guard(prog, startup):
                x = static.data("x", [None, 6], "float32")
                y = static.data("y", [None, 1], "float32")
                pred = fluid.layers.fc(x, size=1)
                loss = fluid.layers.reduce_mean(
                    fluid.layers.square(pred - y))
                o = opt.Adam(learning_rate=0.05)
                o.minimize(loss)
                if flat:
                    o.set_flat_arena(True)
            exe = static.Executor()
            exe.run(startup)
            losses = []
            for f in feeds:
                out, = exe.run(prog, feed=f, fetch_list=[loss])
                losses.append(float(np.asarray(out).ravel()[0]))
            params = {name: np.asarray(exe._scope_get(prog, name))
                      if hasattr(exe, "_scope_get") else None
                      for name in ()}
            return losses

        la = build(flat=False)
        lb = build(flat=True)
        assert la == lb
    finally:
        pt.disable_static()


def test_megatron_flat_matches_per_leaf():
    """MegatronConfig(flat_arena=True) on a dp-only mesh: same losses
    bit-for-bit as the per-leaf trainer, params recovered through
    step.unpack; tp>1 warns and falls back."""
    import warnings
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import megatron as M
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    mesh, _ = M.make_mesh(2, sizes={"dp": 2})
    cfg = M.MegatronConfig(vocab_size=64, hidden=32, n_heads=2,
                           layers_per_stage=1, seq_len=16, microbatch=2,
                           n_micro=2, use_moe=False, optimizer="adam")
    cfgf = cfg._replace(flat_arena=True)
    s0, step0 = M.build_train_step(cfg, mesh)
    sf, stepf = M.build_train_step(cfgf, mesh)
    assert "flat" in sf and hasattr(stepf, "layout")
    rng = np.random.RandomState(0)
    for _ in range(2):
        toks = jnp.asarray(
            rng.randint(0, 64, size=(cfg.n_micro, 4, cfg.seq_len)),
            jnp.int32)
        s0, l0 = step0(s0, toks)
        sf, lf = stepf(sf, toks)
        assert float(l0) == float(lf)
    pf = stepf.unpack(sf["flat"])
    for k in s0["params"]:
        np.testing.assert_array_equal(np.asarray(jax.device_get(
            s0["params"][k])), np.asarray(jax.device_get(pf[k])), err_msg=k)
    # gate: any model-parallel axis falls back with a warning
    mesh_tp, _ = M.make_mesh(2, sizes={"tp": 2})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        st, _ = M.build_train_step(cfgf, mesh_tp)
    assert any("flat_arena" in str(x.message) for x in w)
    assert "params" in st  # per-leaf state shape preserved


def test_arena_layout_properties():
    """Unit properties of the packed layout: dtype grouping, leaves
    packed back-to-back, group totals padded to the 1024-lane ALIGN,
    bucket bounds tiling each group contiguously."""
    from paddle_tpu.optimizer.arena import ALIGN
    m, _ = _pair(seed=97)
    o = opt.Adam(learning_rate=0.01, parameters=m.parameters(),
                 flat_arena=True)
    xs, ys = _data(n=1)
    _train(m, o, xs, ys)
    arena = o._arena
    assert arena is not None
    tags = sorted(g.tag for g in arena.groups)
    assert len(tags) == len(set(tags)) and len(tags) >= 2  # f32 + bf16
    all_bounds = arena.bucket_bounds(bucket_bytes=1 << 12)
    for grp in arena.groups:
        assert grp.total % ALIGN == 0
        run = 0
        for _, off, n, _ in grp.entries:
            assert off == run  # back-to-back, no per-leaf gaps
            run += n
        assert run <= grp.total < run + ALIGN  # only tail padding
        bounds = all_bounds[grp.tag]
        assert bounds[0][0] == 0 and bounds[-1][1] == grp.total
        for (_, a1), (b0, _) in zip(bounds, bounds[1:]):
            assert a1 == b0  # contiguous, no gaps or overlap


# -- the eager / trace boundary (the `trace_state_clean` class of break) ---

def test_unrelated_layer_call_while_an_arena_is_alive():
    """While ANY flat arena exists every Layer.__call__ in the process
    asks "eager or tracing?" (tensor._arena_hook is armed). That
    question must be answered with what the installed JAX offers — for
    a layer that has nothing to do with the arena, eagerly and inside a
    trace — and the owning file, not whichever test file shares a
    worker with this one, is where a break shows."""
    from paddle_tpu import tensor as ptensor
    model, _ = _pair()
    o = opt.Adam(learning_rate=1e-2, parameters=model.parameters(),
                 flat_arena=True)
    xs, ys = _data(2)
    _train(model, o, xs, ys, compiled=True)     # arena built, leaves stale
    assert ptensor._arena_hook is not None

    pt.seed(3)
    other = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 2))
    x = pt.to_tensor(xs[0])
    eager = np.asarray(other(x).numpy())                     # eager call
    traced = jit.to_static(lambda t: other(t), models=[other],
                           optimizers=[])(x)                 # in a trace
    np.testing.assert_allclose(np.asarray(traced.numpy()), eager,
                               rtol=1e-6, atol=1e-6)


def test_eager_forward_after_compiled_steps_reads_fresh_params():
    """An eager forward is a read boundary: after compiled steps left
    the leaf views stale it must see the UPDATED parameters; inside the
    next compiled step the same call must leave the bound views alone
    (losses keep matching the per-leaf optimizer bit for bit)."""
    flat_m, leaf_m = _pair()
    xs, ys = _data(6)
    of = opt.Adam(learning_rate=1e-2, parameters=flat_m.parameters(),
                  flat_arena=True)
    ol = opt.Adam(learning_rate=1e-2, parameters=leaf_m.parameters())
    first = slice(0, 3)
    assert _train(flat_m, of, xs[first], ys[first], compiled=True) == \
        _train(leaf_m, ol, xs[first], ys[first], compiled=True)
    x = pt.to_tensor(xs[0])
    np.testing.assert_array_equal(np.asarray(flat_m(x).numpy()),
                                  np.asarray(leaf_m(x).numpy()))
    rest = slice(3, 6)
    assert _train(flat_m, of, xs[rest], ys[rest], compiled=True) == \
        _train(leaf_m, ol, xs[rest], ys[rest], compiled=True)
    _assert_params_equal(flat_m, leaf_m)
