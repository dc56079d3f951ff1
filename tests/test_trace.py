"""paddle_tpu.monitor.trace / monitor.xla — span tracer semantics,
Chrome-trace export, the flight recorder, XLA-measured cost capture,
measured-MFU reporting, and the zero-cost-when-disabled contract."""
import json
import os
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import trace, xla


@pytest.fixture(autouse=True)
def _clean():
    """Tracer + monitor are process-global: every test starts disabled
    and empty, and leaves nothing behind."""
    monitor.disable(flush_counters=False)
    monitor.reset()
    trace.disable()
    trace.clear()
    yield
    monitor.disable(flush_counters=False)
    monitor.reset()
    trace.disable()
    trace.clear()


# -- disabled-mode contract ---------------------------------------------------

def test_disabled_span_is_shared_null_and_records_nothing():
    # ONE flag check, one shared object — no allocation per call site
    assert trace.span("a") is trace._NULL
    assert trace.span("b", k=1) is trace._NULL
    with trace.span("x"):
        pass
    trace.instant("marker")
    trace.complete("op", 0.0, 1.0)

    @trace.traced
    def f():
        return 42

    assert f() == 42
    assert trace.events() == []
    assert not trace.enabled()


# -- recording ----------------------------------------------------------------

def test_span_records_nested_begin_end_pairs():
    trace.enable()
    with trace.span("outer", step=1):
        with trace.span("inner"):
            pass
    evs = trace.events()
    assert [(e[0], e[1]) for e in evs] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer")]
    assert evs[0][4] == {"step": 1}     # args ride the begin event
    # timestamps are monotone non-decreasing within the thread
    ts = [e[3] for e in evs]
    assert ts == sorted(ts)


def test_complete_and_instant_events():
    trace.enable()
    t0 = time.perf_counter()
    trace.complete("dispatch.add", t0, t0 + 1e-3, n=2)
    trace.instant("collective.c_allreduce_sum", axis="dp")
    kinds = [e[0] for e in trace.events()]
    assert kinds == ["X", "I"]
    x = trace.events()[0]
    assert x[1] == "dispatch.add" and x[4] == pytest.approx(1e-3)


def test_traced_decorator_bare_and_named():
    trace.enable()

    @trace.traced
    def plain():
        return 1

    @trace.traced("custom.label")
    def named():
        return 2

    assert plain() == 1 and named() == 2
    names = [e[1] for e in trace.events() if e[0] == "B"]
    assert any("plain" in n for n in names)
    assert "custom.label" in names


def test_ring_buffer_is_bounded():
    trace.enable(buffer_size=8)
    try:
        for i in range(20):
            trace.instant(f"m{i}")
        evs = trace.events()
        assert len(evs) == 8
        assert evs[-1][1] == "m19"      # oldest fell off, newest kept
        assert trace.events(last=3)[0][1] == "m17"
    finally:
        trace.enable(buffer_size=trace.DEFAULT_BUFFER)


def test_disable_keeps_buffer_clear_empties_it():
    trace.enable()
    trace.instant("kept")
    trace.disable()
    assert [e[1] for e in trace.events()] == ["kept"]
    trace.clear()
    assert trace.events() == []


def _count_annotations(monkeypatch):
    """Stand in for jax.profiler.TraceAnnotation: every span that enters
    one is listed by name."""
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_annotation", Annotation)
    return entered


def test_bridge_annotation_smoke(monkeypatch):
    # the tracer on: a span is an annotation on the profiler's clock AND
    # an event pair in the ring, with no second switch; an annotation
    # that fails never breaks span recording
    entered = _count_annotations(monkeypatch)
    trace.enable()
    with trace.span("bridged"):
        pass
    assert entered == ["bridged"]
    assert [e[0] for e in trace.events()] == ["B", "E"]
    assert not hasattr(trace, "_bridge")
    with pytest.raises(TypeError):
        trace.enable(bridge=True)

    def broken(name):
        raise RuntimeError("no profiler here")
    monkeypatch.setattr(trace, "_annotation", broken)
    with trace.span("still_recorded"):
        pass
    assert [e[1] for e in trace.events()][-2:] == ["still_recorded"] * 2


def test_monitor_on_tracer_off_annotates_and_records_nothing(monkeypatch):
    entered = _count_annotations(monkeypatch)
    assert trace.span("dark") is trace._NULL      # both off: one flag check
    monitor.enable()
    assert not trace.enabled()
    with trace.span("jit.collect"):
        pass

    @trace.traced("decorated")
    def f():
        return 7

    assert f() == 7
    assert entered == ["jit.collect", "decorated"]
    assert trace.events() == []
    # a span that was entered while the ring was off stays out of it
    sp = trace.span("straddles")
    with sp:
        trace.enable()
    assert [e[1] for e in trace.events()] == []
    trace.disable()
    monitor.disable(flush_counters=False)
    assert trace.span("dark_again") is trace._NULL


def test_the_real_annotation_is_entered_without_a_profiler_session():
    monitor.enable()
    with trace.span("jit.execute"):      # jax.profiler.TraceAnnotation
        pass
    assert trace.events() == []


def test_compiled_step_call_and_host_read_are_nested_spans(monkeypatch):
    entered = _count_annotations(monkeypatch)
    from paddle_tpu import jit, nn
    monitor.enable()
    trace.enable()
    layer = nn.Linear(4, 4)
    step = jit.to_static(lambda x: layer(x), models=[layer], optimizers=[])
    x = pt.to_tensor(np.ones((2, 4), "float32"))
    step(x)                     # compiles
    trace.clear()
    del entered[:]
    step(x).numpy()
    assert entered == ["jit.<lambda>", "jit.collect", "jit.execute",
                       "jit.writeback", "tensor.to_host"]
    # each of the three closes before the next opens, all inside jit.<fn>
    assert [(e[0], e[1]) for e in trace.events()] == [
        ("B", "jit.<lambda>"), ("B", "jit.collect"), ("E", "jit.collect"),
        ("B", "jit.execute"), ("E", "jit.execute"),
        ("B", "jit.writeback"), ("E", "jit.writeback"),
        ("E", "jit.<lambda>"),
        ("B", "tensor.to_host"), ("E", "tensor.to_host")]


# -- export -------------------------------------------------------------------

def test_export_chrome_trace_thread_tracks(tmp_path):
    trace.enable()

    def worker():
        with trace.span("producer.work"):
            time.sleep(0.005)

    t = threading.Thread(target=worker, name="producer-thread")
    with trace.span("main.loop"):
        t.start()
        t.join()

    doc = trace.export_chrome_trace()
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    tnames = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert "producer-thread" in tnames
    real = [e for e in evs if e["ph"] != "M"]
    assert len({e["tid"] for e in real}) >= 2    # two tracks
    for e in real:                               # loadable trace-event JSON
        assert {"ph", "pid", "tid", "name", "ts"} <= set(e)

    # a directory gets trace-<pid>.json; explicit *.json paths verbatim
    p = trace.export_chrome_trace(str(tmp_path))
    assert p == os.path.join(str(tmp_path), f"trace-{os.getpid()}.json")
    with open(p, encoding="utf-8") as fh:
        assert json.load(fh)["traceEvents"]
    p2 = trace.export_chrome_trace(str(tmp_path / "custom.json"))
    assert p2.endswith("custom.json") and os.path.exists(p2)


def test_dispatch_timer_feeds_complete_events(tmp_path):
    monitor.enable(str(tmp_path), time_dispatch=True)
    trace.enable()
    (pt.to_tensor(np.ones(4, "f4")) + 1).numpy()
    names = [e[1] for e in trace.events() if e[0] == "X"]
    assert any(n.startswith("dispatch.") for n in names)


def test_monitor_enable_env_turns_trace_on(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
    monitor.enable(str(tmp_path))
    assert trace.enabled()


# -- flight recorder ----------------------------------------------------------

def test_flight_record_contents(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path / "fl"))
    path = monitor.enable(str(tmp_path))
    trace.enable()
    monitor.counter("unit.counter").inc(3)
    with trace.span("hung.phase"):
        d = trace.flight_record("unit_test", step=7, extra={"k": "v"})
    assert d and os.path.isdir(d)

    with open(os.path.join(d, "meta.json"), encoding="utf-8") as fh:
        meta = json.load(fh)
    assert meta["reason"] == "unit_test" and meta["step"] == 7
    assert meta["extra"] == {"k": "v"}

    with open(os.path.join(d, "counters.json"), encoding="utf-8") as fh:
        counters = json.load(fh)
    assert counters["unit.counter"] == 3

    with open(os.path.join(d, "trace.json"), encoding="utf-8") as fh:
        tr = json.load(fh)
    begins = [e["name"] for e in tr["traceEvents"] if e["ph"] == "B"]
    assert "hung.phase" in begins
    # the in-flight span is UNCLOSED in the dump — that's the evidence
    # of which phase was running when the recorder fired
    assert not any(e["ph"] == "E" and e["name"] == "hung.phase"
                   for e in tr["traceEvents"])

    recs = [r for r in monitor.read_jsonl(path)
            if r.get("kind") == "flight_record"]
    assert recs and recs[0]["path"] == d


def test_flight_record_includes_hlo_of_captured_executable(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path / "fl"))
    monitor.enable(str(tmp_path))
    trace.enable()
    fn = jax.jit(lambda x: x * 2.0)
    xla.aot_capture(fn, "unit.hlo", (jnp.ones((4,), jnp.float32),))
    d = trace.flight_record("with_hlo")
    assert d is not None
    hlo_files = [f for f in os.listdir(d) if f.startswith("hlo-")]
    assert hlo_files, os.listdir(d)
    with open(os.path.join(d, hlo_files[0]), encoding="utf-8") as fh:
        assert "HloModule" in fh.read()


def test_flight_record_rate_cap(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_MAX", "2")
    trace.enable()
    assert trace.flight_record("capped") is not None
    assert trace.flight_record("capped") is not None
    assert trace.flight_record("capped") is None    # budget spent


def test_flight_record_never_raises(tmp_path, monkeypatch):
    # an unwritable base dir must yield None, not a second crash
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR",
                       os.path.join(str(tmp_path), "file-not-dir", "x"))
    with open(os.path.join(str(tmp_path), "file-not-dir"), "w") as fh:
        fh.write("block")
    trace.enable()
    assert trace.flight_record("doomed") is None


def test_watchdog_stall_writes_flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path / "fl"))
    from paddle_tpu.resilience.watchdog import Watchdog
    path = monitor.enable(str(tmp_path))
    trace.enable()
    wd = Watchdog(min_deadline=0.05, poll=0.01).start()
    try:
        with wd.step(3):
            with trace.span("stuck.phase"):
                time.sleep(0.4)
    finally:
        wd.stop()
    dumps = [r for r in monitor.read_jsonl(path)
             if r.get("kind") == "watchdog_dump"]
    assert dumps and dumps[0]["flight_dir"]
    assert os.path.isdir(dumps[0]["flight_dir"])


def test_fit_crash_writes_flight_record(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path / "fl"))
    from paddle_tpu import hapi, io, nn, optimizer as opt
    path = monitor.enable(str(tmp_path))
    trace.enable()
    rng = np.random.RandomState(0)
    ds = io.TensorDataset(rng.randn(32, 4).astype("f4"),
                          rng.randint(0, 2, (32,)).astype("i4"))
    m = hapi.Model(nn.Sequential(nn.Linear(4, 2)))

    def boom(outs, labels):
        raise RuntimeError("boom")

    m.prepare(optimizer=opt.SGD(learning_rate=0.1,
                                parameters=m.parameters()),
              loss_function=boom)
    with pytest.raises(RuntimeError, match="boom"):
        m.fit(ds, batch_size=8, epochs=1, verbose=0, shuffle=False)
    recs = [r for r in monitor.read_jsonl(path)
            if r.get("kind") == "flight_record"]
    assert any(r["reason"] == "fit_crash" for r in recs)


# -- monitor.xla --------------------------------------------------------------

class _FakeMem:
    argument_size_in_bytes = 100.0
    output_size_in_bytes = 50.0
    temp_size_in_bytes = 30.0
    alias_size_in_bytes = 20.0
    generated_code_size_in_bytes = 10.0


class _FakeCompiled:
    def cost_analysis(self):
        return [{"flops": 1e9, "bytes accessed": 2e6,
                 "transcendentals": 5.0}]

    def memory_analysis(self):
        return _FakeMem()

    def as_text(self):
        return "HloModule fake"


def test_xla_capture_and_accessors(tmp_path):
    path = monitor.enable(str(tmp_path))
    info = xla.capture("fake", _FakeCompiled())
    assert info["flops"] == 1e9
    assert info["bytes_accessed"] == 2e6
    assert info["peak_memory"] == 100 + 50 + 30 - 20
    assert xla.flops("fake") == 1e9
    assert xla.flops() == 1e9                   # None label -> newest
    assert xla.bytes_accessed() == 2e6
    assert xla.peak_memory() == 160.0
    assert xla.labels() == ["fake"]
    assert xla.last()[0] == "fake"
    assert "HloModule" in xla.hlo_text()
    assert monitor.registry().value("xla.flops.fake") == 1e9
    recs = [r for r in monitor.read_jsonl(path)
            if r.get("kind") == "xla_cost"]
    assert recs and recs[0]["label"] == "fake"
    assert xla.measured_mfu(1.0, peak_flops=1e10) == pytest.approx(0.1)


def test_xla_eviction_keeps_newest():
    for i in range(xla.MAX_ENTRIES + 5):
        xla.capture(f"e{i}", _FakeCompiled())
    labels = xla.labels()
    assert len(labels) == xla.MAX_ENTRIES
    assert labels[-1] == f"e{xla.MAX_ENTRIES + 4}"
    assert "e0" not in labels


def test_aot_capture_real_jit_and_fallback():
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    args = (jnp.ones((8,), jnp.float32),)
    compiled = xla.aot_capture(fn, "unit.jit", args)
    assert hasattr(compiled, "cost_analysis")   # swapped for Compiled
    np.testing.assert_allclose(np.asarray(compiled(*args)),
                               np.full((8,), 3.0, "f4"))
    assert xla.get("unit.jit") is not None
    # an already-compiled object is captured in place
    assert xla.aot_capture(compiled, "unit.jit2", args) is compiled
    assert "unit.jit2" in xla.labels()
    # any failure returns the original callable untouched
    sentinel = object()
    assert xla.aot_capture(sentinel, "nope", args) is sentinel
    assert "nope" not in xla.labels()


def test_executor_captures_cost_on_cache_miss(tmp_path):
    monitor.enable(str(tmp_path))
    pt.enable_static()
    try:
        from paddle_tpu import static
        from paddle_tpu.fluid import layers as FL
        prog, sprog = static.Program(), static.Program()
        with static.program_guard(prog, sprog):
            x = static.data("x", [4, 8], "float32")
            y = FL.fc(x, 2)
        exe = static.Executor()
        exe.run(sprog)
        exe.run(prog, feed={"x": np.ones((4, 8), "f4")}, fetch_list=[y])
        labels = xla.labels()
        assert any(lb.startswith("exec.p") for lb in labels)
    finally:
        pt.disable_static()


def test_to_static_captures_cost_on_compile(tmp_path):
    monitor.enable(str(tmp_path))
    from paddle_tpu import jit as pjit

    def double(x):
        return x * 2

    fn = pjit.to_static(double)
    fn(pt.to_tensor(np.ones(4, "f4"))).numpy()
    assert "jit.double" in xla.labels()


# -- StepMonitor measured MFU -------------------------------------------------

def test_step_monitor_reports_measured_mfu_and_flags_divergence(tmp_path):
    monitor.enable(str(tmp_path))
    sm = monitor.StepMonitor(items_per_step=4, flops_per_step=1e6,
                             peak_flops=1e12, label="t",
                             measured_flops_per_step=2e6)
    sm.start()
    time.sleep(0.002)
    with pytest.warns(UserWarning, match="diverges"):
        rec = sm.step()
    assert rec["mfu_measured"] is not None
    assert rec["flops_measured_ratio"] == pytest.approx(2.0)
    assert monitor.registry().value("xla.mfu_divergence") == 1
    time.sleep(0.002)
    rec2 = sm.step()                    # warns ONCE, keeps flagging
    assert rec2["flops_measured_ratio"] == pytest.approx(2.0)
    s = sm.summary()
    assert s["mfu_measured"] is not None
    assert s["flops_per_step_measured"] == 2e6
    assert monitor.registry().value(
        "step.t.mfu_measured") == pytest.approx(rec2["mfu_measured"],
                                                rel=0.5)


def test_step_monitor_pulls_flops_from_xla_capture(tmp_path):
    monitor.enable(str(tmp_path))
    xla.capture("stepexe", _FakeCompiled())     # 1e9 flops
    sm = monitor.StepMonitor(flops_per_step=1e9, peak_flops=1e12,
                             label="x", xla_label="stepexe")
    sm.start()
    time.sleep(0.002)
    rec = sm.step()
    assert rec.get("mfu_measured") is not None
    # identical analytic/measured counts -> no divergence flag
    assert "flops_measured_ratio" not in rec


def test_step_monitor_no_measured_without_capture(tmp_path):
    monitor.enable(str(tmp_path))
    sm = monitor.StepMonitor(flops_per_step=1e6, peak_flops=1e12,
                             label="bare")
    sm.start()
    rec = sm.step()
    assert "mfu_measured" not in rec
