"""Set-up from the inside (PERF.md section 6, PR 39): the first call of a
compiled step as three staged spans with the cache's verdict, the Pallas
instances counted from the jaxpr, and every program the process compiled,
tiny on the CPU. This file clears JAX's caches and points the persistent
cache at a ``tmp_path``; ``--dist loadfile`` keeps that away from the other
files, and the fixture puts both settings back.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax._src.monitoring as jax_monitoring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                         # noqa: E402
from paddle_tpu import jit, monitor, nn, static                 # noqa: E402
from paddle_tpu import optimizer as opt                         # noqa: E402
from paddle_tpu.monitor import xla                              # noqa: E402
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from paddle_tpu.ops import pallas as P                          # noqa: E402

STAGES = ("trace_s", "lower_s", "backend_s")
SPANS = ("xla.trace", "xla.lower", "xla.backend_compile")
CACHE_SETTINGS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


def _reset_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


@pytest.fixture()
def cache_dir(tmp_path):
    """A persistent cache of this test's own that keeps every program."""
    before = {k: getattr(jax.config, k) for k in CACHE_SETTINGS}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_persistent_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    _reset_persistent_cache()
    jax.clear_caches()


@pytest.fixture()
def traced_monitor():
    """Monitor and ring on, from nothing; off and empty afterwards."""
    monitor.disable()
    monitor.reset()
    monitor.trace.clear()
    monitor.enable()
    monitor.trace.enable()
    yield monitor.registry()
    monitor.trace.disable()
    monitor.trace.clear()
    monitor.disable()
    monitor.reset()


def _train_step():
    pt.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.LayerNorm(16),
                          nn.Linear(16, 1))
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def train_step(x):
        loss = (model(x) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    return jit.to_static(train_step, models=[model], optimizers=[o])


def _run_to_static():
    _train_step()(pt.to_tensor(np.ones((4, 8), np.float32)))
    return "jit.train_step", "jit.aot_capture"


def _run_executor():
    pt.enable_static()
    try:
        from paddle_tpu.fluid import layers
        main, start = static.Program(), static.Program()
        with static.program_guard(main, start):
            y = layers.fc(static.data("x", [4, 8], "float32"), 2)
        exe = static.Executor()
        exe.run(start)
        exe.run(main, feed={"x": np.ones((4, 8), np.float32)},
                fetch_list=[y])
    finally:
        pt.disable_static()
    label = next(l for l in reversed(xla.labels()) if l.startswith("exec."))
    return label, "executor.aot_capture"


def _intervals(events, names):
    """{name: [(begin, end), ...]} of the ring's B/E pairs."""
    open_at, out = {}, {n: [] for n in names}
    for e in events:
        if e[1] in out and e[0] == "B":
            open_at[e[1]] = e[3]
        elif e[1] in out and e[0] == "E":
            out[e[1]].append((open_at.pop(e[1]), e[3]))
    return out


# -- (a) the three stages, through both entries -----------------------------

@pytest.mark.parametrize("run", [_run_to_static, _run_executor],
                         ids=["to_static", "executor"])
def test_a_first_call_leaves_three_stages_inside_its_capture_span(
        traced_monitor, run):
    label, outer = run()
    record = xla.get(label)
    assert all(record[k] > 0 for k in STAGES), record
    assert record["pallas_instances"] == record["pallas_traces"] == 0
    assert record["cache_hit"] is None          # no cache configured
    seen = _intervals(monitor.trace.events(), SPANS + (outer,))
    begin, end = seen[outer][-1]
    inside = [seen[name][-1] for name in SPANS]
    assert begin <= inside[0][0] and inside[-1][1] <= end
    assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))
    # a record's seconds are read inside its span, and the three are all
    # but the whole of the capture
    for key, (b, e) in zip(STAGES, inside):
        assert record[key] <= e - b
    assert sum(record[k] for k in STAGES) <= end - begin
    if outer == "jit.aot_capture":
        assert record["at_step_calls"] == 1
        assert traced_monitor.value("jit.compile_s") >= \
            sum(record[k] for k in STAGES)


# -- (b) the cache's verdict ------------------------------------------------

def test_the_same_step_built_twice_misses_the_cache_then_hits_it(
        cache_dir, traced_monitor):
    x = pt.to_tensor(np.ones((4, 8), np.float32))
    verdicts = []
    for _ in range(2):
        jax.clear_caches()
        before = {k: traced_monitor.value(f"xla.programs.{k}", 0)
                  for k in ("cache_hits", "cache_misses")}
        _train_step()(x)
        record = xla.get("jit.train_step")
        moved = {k: traced_monitor.value(f"xla.programs.{k}", 0) - v
                 for k, v in before.items()}
        verdicts.append((record["cache_hit"], moved))
    (first, moved1), (second, moved2) = verdicts
    assert first is False and moved1["cache_misses"] >= 1
    assert second is True and moved2["cache_hits"] >= 1 \
        and moved2["cache_misses"] == 0
    assert record["cache_retrieval_s"] > 0
    steps = [p for p in xla.programs() if p["fun_name"] == "jit(train_step)"]
    assert [p["cache_hit"] for p in steps] == [False, True]
    assert [p["at_step_calls"] for p in steps] == [1, 2]
    assert steps[1]["backend_s"] > 0
    assert traced_monitor.value("xla.programs.backend_s") >= \
        sum(p["backend_s"] for p in steps)
    assert traced_monitor.value("xla.programs.lower_s") > 0
    assert traced_monitor.value("xla.programs.cache_retrieval_s") >= \
        record["cache_retrieval_s"]
    assert len(xla.programs()) <= xla.MAX_PROGRAMS


# -- (c) the instances, held to the StableHLO -------------------------------

class _Stack(nn.Layer):
    """One kernel at two call sites directly (the layer norms) and one
    behind a module-level ``jax.jit`` (the experts' row scatter-add) under
    ``jit.recompute`` and the ``lax.switch`` over its rungs."""

    def __init__(self, d, wide=32):
        super().__init__()
        self.norm_in, self.norm_out = nn.LayerNorm(d), nn.LayerNorm(d)
        self.blocks = nn.LayerList([
            nn.RoutedMoE(d, wide, 8, 2, experts_held=range(4), gated=True,
                         scoring="softmax") for _ in range(2)])

    def forward(self, u):
        u = self.norm_in(u)
        for block in self.blocks:
            u = u + jit.recompute(block, u)
        return self.norm_out(u)


@pytest.fixture(params=["ladder", "grouped"])
def experts_path(request):
    """``F.moe_experts``' two kernel paths: the per-expert ladder with the
    scatter-add in its branches, and (PR 44) the grouped products."""
    P.configure(moe_grouped=request.param == "grouped")
    yield request.param
    P.configure(moe_grouped=None)


def test_pallas_instances_are_the_custom_calls_of_the_lowered_step(
        monkeypatch, experts_path):
    """Lowered for a TPU from this process (``lowering_platforms``: Mosaic
    is lowered in Python, no chip and no TPU library are asked for)."""
    class Lowered(Exception):
        pass

    make_entry = jit.StaticFunction._make_entry

    def lowering_entry(self, *args, **kwargs):
        entry = make_entry(self, *args, **kwargs)
        jitted = entry["jitted"]

        def lower(state, arrays):
            shapes = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
                (state, arrays))
            traced = jitted.trace(*shapes)
            raise Lowered(xla.count_pallas(traced.jaxpr), traced.lower(
                lowering_platforms=("tpu",)).as_text())
        entry["jitted"] = lower
        return entry

    monkeypatch.setattr(jit.StaticFunction, "_make_entry", lowering_entry)
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    monkeypatch.setattr(moe_ops, "MIN_ROWS", 16)
    pt.seed(0)
    model = _Stack(128, 128 if experts_path == "grouped" else 32)
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def step(u):
        loss = (model(u) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    with pytest.raises(Lowered) as e:
        jit.to_static(step, models=[model], optimizers=[o])(
            pt.to_tensor(np.ones((1, 64, 128), np.float32)))
    (instances, traces), text = e.value.args
    if experts_path == "grouped":
        # the layer norms' 4; hidden, the two forms of gmm, hidden_bwd, one
        # tgmm (the three gradients are one shape here) and the
        # scatter-add, which the forward's re-staged jaxpr holds once more:
        # one instance a shape, whatever the layers, rounds and call sites
        assert instances == text.count("tpu_custom_call") == 4 + 7
        assert traces == 4 + 6
        assert text.count("call @tgmm") == 2 * 3
        return
    assert instances == text.count("tpu_custom_call") == 10
    # 2 layer norms x (forward + backward), each traced at its own site;
    # the scatter-add's 3 rungs once for the forwards and once for the
    # backwards, with one traced body a rung
    assert traces == 4 + 3
    call_sites = text.count("call @scatter_add") + 4
    assert call_sites > instances


# -- (d) traced once, (e) nothing new on the second call --------------------

def test_a_first_call_traces_the_step_once_and_a_second_call_emits_nothing_new(
        traced_monitor):
    traces = []

    def on_duration(event, duration, **kw):
        if event.endswith("jaxpr_trace_duration") \
                and kw.get("fun_name") == "train_step":
            traces.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        step = _train_step()
        x = pt.to_tensor(np.ones((4, 8), np.float32))
        step(x)
        assert len(traces) == 1
        n_events, n_programs = len(monitor.trace.events()), len(xla.programs())
        counters = monitor.snapshot("xla.")
        step(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    second = monitor.trace.events()[n_events:]
    assert [(e[0], e[1]) for e in second] == [
        ("B", "jit.train_step"), ("B", "jit.collect"), ("E", "jit.collect"),
        ("B", "jit.execute"), ("E", "jit.execute"),
        ("B", "jit.writeback"), ("E", "jit.writeback"),
        ("E", "jit.train_step")]
    assert len(xla.programs()) == n_programs
    assert monitor.snapshot("xla.") == counters
    assert len(traces) == 1


# -- the fallback says what failed ------------------------------------------

class _FailsAt:
    """A jitted callable whose AOT path raises at one stage."""

    def __init__(self, stage):
        self.stage = stage

    def _step(self, stage, then):
        if self.stage == stage:
            raise RuntimeError(f"no {stage} today")
        return then

    def trace(self, *args):
        return self._step("trace", self)

    @property
    def jaxpr(self):
        return jax.make_jaxpr(lambda x: x)(1.0)

    def lower(self):
        return self._step("lower", self)

    def compile(self):
        return self._step("backend_compile", self)


@pytest.mark.parametrize("stage", ["trace", "lower", "backend_compile"])
def test_a_failed_capture_returns_the_callable_and_names_the_stage(
        traced_monitor, tmp_path, stage):
    monitor.enable(str(tmp_path))
    fn = _FailsAt(stage)
    assert xla.aot_capture(fn, "unit.fails", ()) is fn
    assert traced_monitor.value("xla.capture_failed") == 1
    assert xla.get("unit.fails") is None
    path = monitor.jsonl_path()
    monitor.disable()
    failed = [r for r in monitor.read_jsonl(path)
              if r["kind"] == "xla_capture_failed"]
    assert len(failed) == 1
    assert (failed[0]["label"], failed[0]["stage"]) == ("unit.fails", stage)
    assert f"no {stage} today" in failed[0]["error"]
    begun = [e for e in monitor.trace.events()
             if e[0] == "B" and e[1].startswith("xla.")]
    assert begun[-1][1] == f"xla.{stage}"
    assert begun[-1][4] == {"label": "unit.fails", "failed": stage}


# -- (f) the listener's lifetime, (g) the import's cost ----------------------

def _listeners():
    return (list(jax_monitoring._event_listeners),
            list(jax_monitoring._event_duration_secs_listeners))


def test_disable_leaves_jaxs_listener_lists_as_enable_found_them():
    monitor.disable()
    before = _listeners()
    monitor.enable()
    monitor.enable()                        # idempotent: one pair, once
    during = _listeners()
    assert [len(d) - len(b) for d, b in zip(during, before)] == [1, 1]
    monitor.disable()
    assert _listeners() == before
    monitor.disable()                       # and again: nothing to take out
    assert _listeners() == before


@pytest.fixture(scope="module")
def fresh_process():
    """What a process that imported the package and never enabled the
    monitor holds."""
    code = ("import paddle_tpu, jax._src.monitoring as m\n"
            "from paddle_tpu import monitor\n"
            "print(len(m._event_listeners),"
            " len(m._event_duration_secs_listeners),"
            " monitor.enabled(), monitor.registry().value("
            "'runtime.import_s', None))")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PADDLE_TPU_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_a_process_that_never_enabled_the_monitor_registered_nothing(
        fresh_process):
    assert fresh_process[:3] == ["0", "0", "False"]


def test_import_seconds_are_a_positive_gauge(fresh_process):
    assert 0 < float(fresh_process[3]) < 300
    gauge = monitor.registry().get("runtime.import_s")
    # this process's own, unless an earlier test reset the registry
    assert gauge is None or (gauge.kind == "gauge" and gauge.value > 0)
