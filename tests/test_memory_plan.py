"""paddle_tpu.memory_plan — budget-driven rematerialization, overlapped
optimizer-state host offload, bf16 master weights, and the predicted-peak
auto-picker: every mechanism on every surface, with the exactness each
one claims (remat/offload bit-identical, bf16-master tolerance-gated)."""
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import hapi, jit, monitor, nn, optimizer as opt
from paddle_tpu import memory_plan as mp
from paddle_tpu.io import TensorDataset
from paddle_tpu.monitor import memory, profile, trace


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """memory_plan + monitor are process-global; start dark."""
    for var in ("PADDLE_TPU_HBM_LIMIT_BYTES", "PADDLE_TPU_HBM_GB",
                "PADDLE_TPU_HOST_MEM_LIMIT_BYTES",
                "PADDLE_TPU_HOST_LINK_GBPS"):
        monkeypatch.delenv(var, raising=False)
    monitor.disable(flush_counters=False)
    monitor.reset()
    profile.disable()
    profile.reset()
    memory.reset()
    mp.reset()
    trace.disable()
    trace.clear()
    yield
    monitor.disable(flush_counters=False)
    monitor.reset()
    profile.disable()
    profile.reset()
    memory.reset()
    mp.reset()
    trace.disable()
    trace.clear()


# -- policy resolution --------------------------------------------------------

def test_resolve_coercions():
    assert mp.resolve(None) is None
    assert mp.resolve("auto") == "auto"
    p = mp.resolve("full")
    assert p.remat == "full" and not p.offload and not p.master_weights
    p = mp.resolve("offload")
    assert p.offload and p.remat is None
    p = mp.resolve({"remat": "dots", "offload": True,
                    "master_weights": True})
    assert p.remat == "dots" and p.offload and p.master_weights
    rules = (("Linear_0", "full"), (".*", "none"))
    p = mp.resolve(rules)
    assert isinstance(p.remat, tuple) and p.remat[0][0] == "Linear_0"
    existing = mp.MemoryPolicy(remat="full")
    assert mp.resolve(existing) is existing
    with pytest.raises(ValueError):
        mp.resolve("activation_checkpointing")
    with pytest.raises(ValueError):
        mp.resolve({"remat": "full", "bogus_knob": 1})


def test_policy_key_stable_and_canonical():
    assert mp.policy_key(None) == "none"
    assert mp.policy_key("auto") == "auto"
    # an all-defaults policy is the same cache key as no policy
    assert mp.policy_key(mp.resolve({"remat": "none"})) == "none"
    assert mp.policy_key(mp.resolve("full")) == "remat=full"
    assert mp.policy_key(mp.resolve("offload")) == "remat=none,offload"
    k = mp.policy_key(mp.resolve((("fc", "dots"),)))
    assert "rules:" in k and "fc->dots" in k
    # MemoryPolicy is immutable + hashable (it rides in cache keys)
    p = mp.resolve("full")
    with pytest.raises(AttributeError):
        p.remat = "dots"
    hash(p)


# -- shared fixtures ----------------------------------------------------------

class _MLP(nn.Layer):
    def __init__(self, remat=None):
        super().__init__(remat=remat)
        self.l1 = nn.Linear(8, 32)
        self.l2 = nn.Linear(32, 8)

    def forward(self, x):
        return self.l2(nn.functional.relu(self.l1(x)))


def _toy(n=64, d=8, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, classes)
    x = rng.randn(n, d).astype("f4")
    y = (x @ w).argmax(-1).astype("i4")
    return x, y


def _model(seed=0, lr=0.05):
    pt.seed(seed)
    x, y = _toy()
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 3))
    m = hapi.Model(net)
    m.prepare(optimizer=opt.Adam(learning_rate=lr,
                                 parameters=m.parameters()),
              loss_function=hapi.CrossEntropy())
    return m, x, y


# -- rematerialization: eager + to_static, bit-exact --------------------------

def test_layer_remat_eager_grads_match():
    pt.seed(0)
    m1 = _MLP()
    m2 = _MLP(remat="full")
    m2.set_state_dict(m1.state_dict())
    x = pt.to_tensor(np.random.RandomState(0).randn(4, 8).astype("f4"))
    y1, y2 = m1(x), m2(x)
    np.testing.assert_array_equal(np.asarray(y1.numpy()),
                                  np.asarray(y2.numpy()))
    (y1 * y1).sum().backward()
    (y2 * y2).sum().backward()
    np.testing.assert_array_equal(np.asarray(m1.l1.weight.grad),
                                  np.asarray(m2.l1.weight.grad))


def _tostatic_losses(remat, steps=4):
    pt.seed(0)
    m = _MLP()
    o = opt.Adam(learning_rate=1e-2, parameters=m.parameters())

    def step(xb, yb):
        loss = ((m(xb) - yb) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    sf = jit.to_static(step, models=[m], optimizers=[o], remat=remat)
    out = []
    for i in range(steps):
        rng = np.random.RandomState(42 + i)
        out.append(float(np.asarray(sf(
            pt.to_tensor(rng.randn(4, 8).astype("f4")),
            pt.to_tensor(rng.randn(4, 8).astype("f4"))).numpy())))
    return out


def test_to_static_remat_bit_identical():
    base = _tostatic_losses(None)
    assert _tostatic_losses("full") == base
    assert _tostatic_losses("dots") == base
    # per-layer rules path compiles and matches too
    assert _tostatic_losses((("Linear_0", "full"),)) == base


def test_to_static_remat_marks_hlo(tmp_path):
    monitor.enable(str(tmp_path / "m.jsonl"))
    profile.enable()
    _tostatic_losses("full", steps=1)
    txt = monitor.xla.hlo_text("jit.step")
    assert txt and ("rematted_computation" in txt
                    or "jvp(checkpoint)" in txt)
    rep = memory.report(label="jit.step", emit_records=False)
    assert rep["by_class"].get("remat", 0) > 0
    # the by-class report stays honest: remat bytes came OUT of the
    # stored-activation class, and attribution does not degrade
    profile.reset()
    _tostatic_losses(None, steps=1)
    rep0 = memory.report(label="jit.step", emit_records=False)
    assert (rep["by_class"]["activation"]
            < rep0["by_class"]["activation"])
    # (to a hundredth: which few unscoped bytes — the RNG key split —
    # are live at the peak instruction is the compiler's schedule)
    assert rep["attributed_frac"] >= rep0["attributed_frac"] - 0.01


# -- fit(memory=): toggle + auto ----------------------------------------------

def _compiles():
    c = monitor.registry().get("jit.compile")
    return int(c.value) if c is not None else 0


def test_fit_memory_toggle_recompiles_exactly_once(tmp_path):
    monitor.enable(str(tmp_path / "m.jsonl"))
    m, x, y = _model()
    ds = TensorDataset(x, y)
    m.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False)
    c0 = _compiles()
    m.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False,
          memory="full")
    assert _compiles() - c0 == 1
    c1 = _compiles()
    m.fit(ds, batch_size=16, epochs=1, verbose=0, shuffle=False,
          memory="full")
    assert _compiles() - c1 == 0  # same policy: cache hit


def test_fit_memory_auto_picks_none_when_it_fits(tmp_path):
    monitor.enable(str(tmp_path / "m.jsonl"))
    profile.enable()
    m, x, y = _model()
    m.fit(TensorDataset(x, y), batch_size=16, epochs=1, verbose=0,
          shuffle=False, memory="auto")
    d = mp.last_decision()
    assert d is not None and d["kind"] == "memory_plan"
    assert d["picked"] == "none"  # no HBM limit on CPU: all feasible
    assert mp.policy_key(m._memory) == "none"


# -- offload ------------------------------------------------------------------

def _fit_offload(patched, epochs=2, grad_sync=None, seed=0):
    m, x, y = _model(seed=seed)
    h = m.fit(TensorDataset(x, y), batch_size=16, epochs=epochs,
              verbose=0, shuffle=False, memory="offload",
              grad_sync=grad_sync)
    return m, h["loss"]


def test_offload_bit_identical_to_split_without_paging(monkeypatch):
    """The exactness offload claims: paging the arena's slot buffers to
    host and back changes NOTHING numerically. Both runs use the same
    split fwd/bwd + eager-apply step; only the paging differs."""
    _, on = _fit_offload(False)

    class _Noop(mp.ArenaOffloader):
        def collect(self, arena, count_exposed=True):
            pass

        def page_out(self, arena):
            pass

    real = mp.ArenaOffloader
    monkeypatch.setattr(mp, "ArenaOffloader", _Noop)
    try:
        _, off = _fit_offload(True)
    finally:
        monkeypatch.setattr(mp, "ArenaOffloader", real)
    assert on == off


def test_offload_pages_and_spans_on_own_track():
    trace.enable()
    m, _ = _fit_offload(False, epochs=1)
    off = m._optimizer._offloader
    assert off is not None and off.steps >= 3
    assert off.bytes_out > 0 and off.bytes_in == off.bytes_out
    evs = trace.events()
    d2h = [e for e in evs if e[1] == "offload.d2h"]
    h2d = [e for e in evs if e[1] == "offload.h2d"]
    fit_tids = {e[2] for e in evs if e[1] == "fit.step"}
    assert d2h and h2d
    # worker-thread spans land on their own track, not the step loop's
    assert {e[2] for e in d2h} - fit_tids


def test_offload_checkpoint_resumes_bit_identical(tmp_path):
    """Save mid-training with state offloaded (incl. grad_sync="overlap"
    lag-1 in-flight grads) — restore must produce the exact next step."""
    x, y = _toy()
    for gs in (None, "overlap"):
        m, _ = _fit_offload(False, epochs=1, grad_sync=gs)
        p = str(tmp_path / f"ck_{gs}")
        m.save(p)
        h_a = m.fit(TensorDataset(x, y), batch_size=16, epochs=1,
                    verbose=0, shuffle=False, memory="offload",
                    grad_sync=gs)

        m2, _, _ = _model(seed=1)
        m2.load(p)
        h_b = m2.fit(TensorDataset(x, y), batch_size=16, epochs=1,
                     verbose=0, shuffle=False, memory="offload",
                     grad_sync=gs)
        assert h_a["loss"] == h_b["loss"], f"grad_sync={gs}"


def test_offload_detach_materializes_and_toggles_back():
    m, _ = _fit_offload(False, epochs=1)
    o = m._optimizer
    assert o._offloader is not None
    m.fit(TensorDataset(*_toy()), batch_size=16, epochs=1, verbose=0,
          shuffle=False, memory="none")
    assert o._offloader is None
    assert not m._train_step_split
    # all slot buffers back on device (numpy works, values finite)
    for grp in o._arena.groups:
        for t in grp.slots.values():
            assert np.isfinite(np.asarray(t.numpy())).all()


# -- bf16 master weights ------------------------------------------------------

def test_master_weights_tolerance_and_fp32_checkpoint():
    m_a, x, y = _model()
    h_a = m_a.fit(TensorDataset(x, y), batch_size=16, epochs=2,
                  verbose=0, shuffle=False, flat_arena=True)
    m_b, x, y = _model()
    h_b = m_b.fit(TensorDataset(x, y), batch_size=16, epochs=2,
                  verbose=0, shuffle=False,
                  memory={"master_weights": True})
    for a, b in zip(h_a["loss"], h_b["loss"]):
        assert abs(a - b) < 0.05  # bf16 compute, fp32 master: close
    # outside the trace the leaves are the exact fp32 master
    for p in m_b._optimizer._parameter_list:
        assert str(p.data.dtype) == "float32"
    sd = m_b.network.state_dict()
    for v in sd.values():
        assert str(np.asarray(v.numpy()).dtype) == "float32"


# -- static Executor surface --------------------------------------------------

def _exe_losses(memory, steps=3):
    import paddle_tpu.fluid as fluid
    fluid.enable_static()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            pt.seed(0)
            x_in = fluid.data("x", [None, 8], "float32")
            y_in = fluid.data("y", [None, 1], "float32")
            h = fluid.layers.fc(x_in, size=16, act="relu")
            p = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean((p - y_in) * (p - y_in))
            fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        xb = rng.randn(16, 8).astype("f4")
        yb = rng.randn(16, 1).astype("f4")
        out = []
        for _ in range(steps):
            (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                            fetch_list=[loss], memory=memory)
            out.append(float(np.asarray(lv)))
        return out
    finally:
        fluid.disable_static()


def test_executor_remat_bit_identical():
    base = _exe_losses(None)
    assert _exe_losses("full") == base
    assert _exe_losses("dots") == base


def test_executor_offload_falls_back_with_warning():
    base = _exe_losses(None)
    with pytest.warns(RuntimeWarning, match="offload"):
        got = _exe_losses("offload")
    assert got == base  # remat part only (none here): byte-identical


def test_executor_auto_is_loop_level():
    with pytest.raises(ValueError, match="loop-level"):
        _exe_losses("auto", steps=1)


# -- megatron -----------------------------------------------------------------

def test_megatron_remat_tracks_baseline():
    from paddle_tpu.parallel import megatron as M
    mesh, sizes = M.make_mesh(len(__import__("jax").devices()))
    cfg = M.MegatronConfig(hidden=32, n_heads=2, vocab_size=64,
                           seq_len=16, lr=1e-2, use_moe=False)
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size,
        (cfg.n_micro, cfg.microbatch * sizes["dp"],
         cfg.seq_len)).astype("i4")

    def run(remat):
        state, step = M.build_train_step(cfg._replace(remat=remat), mesh)
        out = []
        for _ in range(3):
            state, loss = step(state, toks)
            out.append(float(loss))
        return out

    base = run(None)
    got = run("full")
    np.testing.assert_allclose(got, base, rtol=1e-5)


# -- the auto-picker ----------------------------------------------------------

def _captured_report(tmp_path):
    monitor.enable(str(tmp_path / "m.jsonl"))
    profile.enable()
    _tostatic_losses(None, steps=1)
    return memory.report(label="jit.step", emit_records=False)


def test_plan_memory_ladder(tmp_path):
    rep = _captured_report(tmp_path)
    peak = rep["predicted_peak_bytes"]
    act = (rep["by_class"]["activation"]
           + rep["by_class"].get("remat", 0))
    # generous: everything fits -> "none", zero overhead
    d = mp.plan_memory(auto=True, label="jit.step", limit=int(peak * 10))
    assert d["picked"] == "none" and d["overhead_s"] == 0.0
    # between dots and none -> cheapest fitting is dots
    d = mp.plan_memory(auto=True, label="jit.step",
                       limit=int(peak - 0.4 * act))
    assert d["picked"] == "dots"
    assert d["predicted_peak_bytes"] <= d["hbm_limit_bytes"]
    # nothing fits -> refuse with actionable error
    with pytest.raises(ValueError, match="exceeds the budget"):
        mp.plan_memory(auto=True, label="jit.step", limit=1024)
    # decision recorded in the monitor ledger like planner.plan
    assert mp.last_decision()["kind"] == "memory_plan"
    c = monitor.registry().get("memory_plan.auto_pick")
    assert c is not None and int(c.value) >= 2


def test_plan_memory_refuses_host_over_budget(tmp_path, monkeypatch):
    rep = _captured_report(tmp_path)
    peak = rep["predicted_peak_bytes"]
    act = (rep["by_class"]["activation"]
           + rep["by_class"].get("remat", 0))
    opt_b = rep["by_class"]["opt_state"]
    only_offload_fits = int(peak - 0.9 * act - opt_b + 1)
    monkeypatch.setenv("PADDLE_TPU_HOST_MEM_LIMIT_BYTES", "1")
    with pytest.raises(ValueError):
        mp.plan_memory(auto=True, label="jit.step",
                       limit=only_offload_fits)
    # with host room it picks the offload rung instead
    monkeypatch.setenv("PADDLE_TPU_HOST_MEM_LIMIT_BYTES",
                       str(64 << 30))
    d = mp.plan_memory(auto=True, label="jit.step",
                       limit=only_offload_fits)
    assert d["policy"].offload


def test_host_headroom_gauge_published(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_HOST_MEM_LIMIT_BYTES",
                       str(1 << 40))
    from paddle_tpu.monitor import sampler
    reg = monitor.registry()
    sampler.sample_once(reg)
    g = reg.get("mem.host.headroom_bytes")
    assert g is not None
    assert 0 < g.value < (1 << 40)


def test_advise_gains_memory_columns():
    from paddle_tpu.parallel import planner
    from paddle_tpu.parallel.megatron import MegatronConfig
    cfg = MegatronConfig(hidden=32, n_heads=2, vocab_size=64,
                         seq_len=16, use_moe=False)
    rows = planner.advise(n_devices=8, cfg=cfg)
    assert rows
    for r in rows:
        assert r["remat"] in ("none", "dots", "full")
        assert isinstance(r["offload"], bool)
        assert r["mem_overhead_s"] >= 0.0
    # no limit -> everything fits as-is -> advisory columns all "none"
    assert all(r["remat"] == "none" for r in rows
               if r["hbm_limit_bytes"] is None)
    # squeeze: under a tight budget the advisory suggests a rung and
    # feasible/rank semantics stay the as-is verdict
    tight = min(r["peak_hbm_bytes"] for r in rows) * 0.5
    rows2 = planner.advise(n_devices=8, cfg=cfg, hbm_limit=tight)
    assert any(r["remat"] != "none" or r["offload"] for r in rows2)
    assert all(r["feasible"] is False for r in rows2
               if r["peak_hbm_bytes"] > tight)
