"""paddle_tpu.monitor — registry semantics, JSONL round-trip, dispatch /
collective / executor / optimizer instrumentation, StepMonitor MFU, and
the zero-cost-when-disabled contract."""
import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import monitor, nn, optimizer as opt
from paddle_tpu.monitor.registry import (Counter, Gauge, Histogram,
                                         JsonlSink, Registry, read_jsonl)
from paddle_tpu.parallel import collective


@pytest.fixture(autouse=True)
def _clean_monitor():
    """The monitor is process-global: every test starts disabled/empty
    and leaves nothing behind for its neighbours."""
    monitor.disable(flush_counters=False)
    monitor.reset()
    yield
    monitor.disable(flush_counters=False)
    monitor.reset()


@pytest.fixture
def mon(tmp_path):
    path = monitor.enable(str(tmp_path))
    yield path
    monitor.disable(flush_counters=False)


@pytest.fixture
def mesh8():
    mesh = collective.make_mesh({"dp": 8})
    yield mesh
    collective.set_mesh(None)


# -- registry -----------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    r = Registry()
    c = r.counter("a.b")
    c.inc()
    c.inc(3)
    assert r.value("a.b") == 4
    with pytest.raises(ValueError):
        c.inc(-1)

    g = r.gauge("g")
    g.set(2.5)
    g.set(1.5)
    assert r.value("g") == 1.5

    h = r.histogram("h")
    for v in (0.5, 2.0, 64.0):
        h.observe(v)
    snap = r.snapshot()["h"]
    assert snap["count"] == 3
    assert snap["sum"] == pytest.approx(66.5)
    assert snap["min"] == 0.5 and snap["max"] == 64.0

    # one name, one kind
    with pytest.raises(TypeError):
        r.gauge("a.b")

    assert set(r.snapshot(prefix="a.")) == {"a.b"}
    r.reset()
    assert r.snapshot() == {}


def test_counter_thread_safety():
    r = Registry()
    c = r.counter("n")

    def work():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 8000


def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "out" / "ev.jsonl"
    sink = JsonlSink(str(path))
    sink.emit({"kind": "x", "v": 1})
    sink.emit({"kind": "y", "v": [1, 2], "arr": np.float32(2.0)})
    sink.close()
    recs = read_jsonl(str(path))
    assert [r["kind"] for r in recs] == ["x", "y"]
    assert all("ts" in r for r in recs)
    assert recs[1]["v"] == [1, 2]


def test_read_jsonl_skips_truncated_final_line(tmp_path):
    """A run killed mid-write leaves a torn last line; reading the
    stream back must keep every complete record and warn, not raise."""
    path = tmp_path / "ev.jsonl"
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(json.dumps({"kind": "a", "v": 1}) + "\n")
        fh.write(json.dumps({"kind": "b", "v": 2}) + "\n")
        fh.write('{"kind": "c", "v"')       # killed mid-write
    with pytest.warns(UserWarning, match="line 3"):
        recs = read_jsonl(str(path))
    assert [r["kind"] for r in recs] == ["a", "b"]


def test_registry_value_counter_gauge_histogram_matrix():
    """Registry.value must answer for every metric kind: scalar for
    counter/gauge, snapshot dict for histogram (which has no single
    value), default for a missing name."""
    r = Registry()
    r.counter("c").inc(5)
    r.gauge("g").set(2.5)
    h = r.histogram("h")
    h.observe(1.0)
    h.observe(3.0)
    assert r.value("c") == 5
    assert r.value("g") == 2.5
    hv = r.value("h")
    assert isinstance(hv, dict)
    assert hv["count"] == 2 and hv["sum"] == pytest.approx(4.0)
    assert hv["min"] == 1.0 and hv["max"] == 3.0
    assert r.value("missing") == 0
    assert r.value("missing", default=None) is None


# -- dispatch hook ------------------------------------------------------------

def test_dispatch_counts_known_op_sequence(mon):
    a = pt.to_tensor(np.ones((3, 3), np.float32))
    b = pt.to_tensor(np.ones((3, 3), np.float32))
    before = dict(monitor.snapshot("dispatch."))
    for _ in range(3):
        c = a + b
    _ = a * b
    snap = monitor.snapshot("dispatch.")
    assert snap.get("dispatch.add", 0) - before.get("dispatch.add", 0) == 3
    assert snap.get("dispatch.multiply", 0) \
        - before.get("dispatch.multiply", 0) == 1


def test_dispatch_grad_split(mon):
    p = pt.to_tensor(np.ones((2, 2), np.float32), stop_gradient=False)
    q = pt.to_tensor(np.ones((2, 2), np.float32))
    _ = p + q            # on the tape
    with pt.no_grad():
        _ = p + q        # not on the tape
    snap = monitor.snapshot("dispatch.")
    assert snap.get("dispatch.add") == 2
    assert snap.get("dispatch.grad.add") == 1


def test_disabled_mode_no_overhead_state():
    """The contract ISSUE.md asks a test to assert: with the monitor
    off, dispatch carries NO hook (one `is None` flag check and nothing
    else — no per-op dict writes, no registry mutation, no tape of
    metric state)."""
    from paddle_tpu import dispatch
    assert dispatch._monitor_hook is None
    a = pt.to_tensor(np.ones((4,), np.float32))
    b = pt.to_tensor(np.ones((4,), np.float32))
    for _ in range(5):
        _ = a + b
    assert monitor.snapshot() == {}
    assert not monitor.enabled()
    assert monitor.jsonl_path() is None


def test_enable_disable_installs_and_removes_hook(tmp_path):
    from paddle_tpu import dispatch
    monitor.enable(str(tmp_path))
    assert dispatch._monitor_hook is not None
    monitor.disable()
    assert dispatch._monitor_hook is None


def test_enable_twice_closes_previous_sink(tmp_path):
    """Re-enabling with a new path must close the old sink's file
    handle (the leak: N enables -> N open fds) and route subsequent
    events to the new file only."""
    import paddle_tpu.monitor as M
    p1 = monitor.enable(str(tmp_path / "one.jsonl"))
    first_sink = M._sink
    assert first_sink is not None and first_sink._fh is not None
    p2 = monitor.enable(str(tmp_path / "two.jsonl"))
    assert p1 != p2
    assert first_sink._fh is None          # old handle closed
    monitor.emit(kind="after_switch")
    monitor.disable(flush_counters=False)
    assert not any(r.get("kind") == "after_switch"
                   for r in read_jsonl(p1))
    assert any(r.get("kind") == "after_switch"
               for r in read_jsonl(p2))


# -- collectives --------------------------------------------------------------

def test_collective_byte_accounting_under_shard_map(mon, mesh8):
    def f(x):
        y = collective.all_reduce(pt.Tensor(x), op="sum", axis_name="dp")
        return y.data

    xs = jnp.ones((8, 16), jnp.float32)
    out = shard_map(f, mesh=mesh8, in_specs=P("dp"),
                    out_specs=P("dp"))(xs)
    np.testing.assert_allclose(np.asarray(out), 8.0)
    snap = monitor.snapshot("collective.")
    assert snap["collective.c_allreduce_sum.dp.calls"] >= 1
    # per-shard payload: (1, 16) f32 = 64 bytes per traced issue
    assert snap["collective.c_allreduce_sum.dp.bytes"] % 64 == 0
    assert snap["collective.c_allreduce_sum.dp.bytes"] >= 64


def test_collective_identity_fallback_not_counted(mon):
    # outside any SPMD region the op is an eager identity — no record
    _ = collective.all_reduce(pt.to_tensor(np.ones(4, np.float32)),
                              op="sum", axis_name="dp")
    assert monitor.snapshot("collective.") == {}


def test_axis_size_compat(mesh8):
    def f(x):
        return jnp.full_like(x, collective.axis_size("dp"))

    out = shard_map(f, mesh=mesh8, in_specs=P("dp"),
                    out_specs=P("dp"))(jnp.zeros((8,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 8.0)
    assert not collective.in_spmd_context("dp")  # outside: no axis bound


# -- executor -----------------------------------------------------------------

def test_executor_counters(mon):
    from paddle_tpu import static
    static.reset_default_programs()
    pt.enable_static()
    try:
        model = nn.Linear(4, 2)
        x = static.data("x", [None, 4], "float32")
        out = model(x)
        exe = static.Executor()
        xv = np.random.randn(3, 4).astype("f4")
        exe.run(feed={"x": xv}, fetch_list=[out])
        exe.run(feed={"x": xv}, fetch_list=[out])
    finally:
        pt.disable_static()
        static.reset_default_programs()
    snap = monitor.snapshot("executor.")
    assert snap["executor.run"] == 2
    assert snap["executor.cache_miss"] == 1
    assert snap["executor.cache_hit"] == 1
    assert snap["executor.compile"] == 1


# -- optimizer ----------------------------------------------------------------

def test_optimizer_step_counter(mon):
    model = nn.Linear(2, 2)
    o = opt.SGD(learning_rate=0.1, parameters=model.parameters())
    loss = model(pt.to_tensor(np.ones((1, 2), np.float32))).sum()
    loss.backward()
    o.step()
    assert monitor.snapshot("optimizer.")["optimizer.step.SGD"] == 1


def test_linear_lr_warmup_init_peek_leaves_inner_untouched():
    from paddle_tpu.fluid.dygraph_lr import (LinearLrWarmup,
                                             NaturalExpDecay)
    inner = NaturalExpDecay(0.1, decay_steps=10, decay_rate=0.5, begin=0)
    warm = LinearLrWarmup(inner, warmup_steps=4, start_lr=0.0, end_lr=0.1)
    model = nn.Linear(2, 2)
    o = opt.SGD(learning_rate=warm, parameters=model.parameters())
    # constructing the optimizer reads the init lr via peek(): neither
    # the warmup's nor the WRAPPED decay's step_num may advance
    assert inner.step_num == 0
    assert warm.step_num == 1
    assert o.get_lr() == pytest.approx(warm.lr_ratio_before_warmup * 1)
    # past warmup, peek() forwards to the inner schedule without advancing
    warm.step_num = 10
    lr_peek = warm.peek()
    assert inner.step_num == 0
    assert lr_peek == pytest.approx(inner.peek())


# -- one_hot eager range check ------------------------------------------------

def test_one_hot_eager_raises_out_of_range():
    from paddle_tpu.fluid.input import one_hot
    ids = pt.to_tensor(np.array([[0], [5]], np.int32))
    with pytest.raises(ValueError, match="out of range"):
        one_hot(ids, depth=4)


def test_one_hot_allow_out_of_range_zero_rows():
    from paddle_tpu.fluid.input import one_hot
    ids = pt.to_tensor(np.array([1, 7], np.int32))
    out = one_hot(ids, depth=4, allow_out_of_range=True)
    arr = np.asarray(out.numpy())
    np.testing.assert_allclose(arr[0], [0, 1, 0, 0])
    np.testing.assert_allclose(arr[1], [0, 0, 0, 0])  # zero-row semantics


def test_one_hot_traced_ids_keep_zero_row_semantics():
    from paddle_tpu.ops.manip import one_hot as raw_one_hot

    @jax.jit
    def f(ids):
        t = raw_one_hot(pt.Tensor(ids), 4)
        return t.data if hasattr(t, "data") else t

    out = np.asarray(f(jnp.array([1, 9], jnp.int32)))
    np.testing.assert_allclose(out[1], [0, 0, 0, 0])


# -- StepMonitor + end-to-end -------------------------------------------------

def test_step_monitor_mfu_math():
    assert monitor.mfu(100e12, 1.0, peak_flops=200e12) == \
        pytest.approx(0.5)
    assert monitor.mfu(100e12, 1.0, peak_flops=None) is None
    assert monitor.transformer_train_flops_per_token(110e6) == \
        pytest.approx(6.6e8)


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12),     # must NOT match the "TPU v5p" entry
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v4", 275e12),
    ("TPU v6e", 918e12),
    ("TPU v2", 46e12),
    ("NVIDIA A100", None),       # unknown kind -> None, never invented
    ("", None),
])
def test_peak_flops_device_kind_substring_ordering(kind, peak, monkeypatch):
    """The table is substring-matched in order: 'TPU v5 lite' and
    'TPU v5e' are distinct spellings of the same 197e12 chip and neither
    may fall through to the v5p row."""
    monkeypatch.delenv("PADDLE_TPU_FLOPS_CEILING", raising=False)
    assert monitor.peak_flops_for_device(_FakeDevice(kind)) == peak


def test_peak_flops_ceiling_env_override(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLOPS_CEILING", "123e9")
    assert monitor.peak_flops_for_device(_FakeDevice("TPU v4")) == 123e9
    # empty string is "unset", not a parse error: table takes over
    monkeypatch.setenv("PADDLE_TPU_FLOPS_CEILING", "")
    assert monitor.peak_flops_for_device(_FakeDevice("TPU v4")) == 275e12
    assert monitor.peak_flops_for_device(_FakeDevice("mystery")) is None


def test_toy_training_loop_jsonl_stream(tmp_path, mesh8):
    """The ISSUE.md acceptance scenario: a 3-step toy loop with
    monitoring on yields a JSONL stream holding (a) per-op dispatch
    counts, (b) >= 1 collective byte record under an SPMD mesh, and
    (c) a step record carrying throughput and mfu."""
    path = monitor.enable(str(tmp_path))
    model = nn.Linear(8, 8)
    o = opt.SGD(learning_rate=0.01, parameters=model.parameters())
    x = pt.to_tensor(np.random.randn(16, 8).astype("f4"))

    # one SPMD collective so the stream holds a byte record
    def f(v):
        y = collective.all_reduce(pt.Tensor(v), op="sum", axis_name="dp")
        return y.data

    mesh = collective.get_mesh()
    shard_map(f, mesh=mesh, in_specs=P("dp"),
              out_specs=P("dp"))(jnp.ones((8, 4), jnp.float32))

    sm = monitor.StepMonitor(items_per_step=16, flops_per_step=1e9,
                             peak_flops=197e12, item="images")
    sm.start()
    for _ in range(3):
        loss = model(x).sum()
        loss.backward()
        o.step()
        o.clear_grad()
        sm.step(loss=float(loss.numpy()))
    sm.report(print_table=False)
    monitor.disable()

    recs = read_jsonl(path)
    kinds = [r["kind"] for r in recs]
    steps = [r for r in recs if r["kind"] == "step"]
    assert len(steps) == 3
    assert all(r["items_per_sec"] > 0 and r["mfu"] is not None
               for r in steps)
    assert any(r["kind"] == "collective" and r["bytes"] > 0
               for r in recs)
    # final counters snapshot carries the per-op dispatch counts
    counters = [r for r in recs if r["kind"] == "counters"][-1]
    dispatch_counts = {k: v for k, v in counters["counters"].items()
                       if k.startswith("dispatch.")}
    assert dispatch_counts.get("dispatch.linear", 0) >= 3
    assert "step_summary" in kinds
