"""paddle_tpu.monitor.xla — XLA-measured cost of compiled executables.

The analytic MFU numbers (monitor.step's 6N flops/token, the ResNet
3×fwd constant) are *conventions*; XLA knows what it actually compiled.
A jax AOT ``Compiled`` object exposes ``cost_analysis()`` (flops, bytes
accessed) and ``memory_analysis()`` (argument/output/temp/alias bytes)
— this module pulls both into the monitor as per-executable gauges
(``xla.flops.<label>``, ``xla.bytes_accessed.<label>``,
``xla.peak_memory.<label>``) plus one ``xla_cost`` JSONL record, and
keeps the executables around so the flight recorder can dump HLO text.

``StepMonitor`` reports **measured MFU** (XLA-counted
flops ÷ step time ÷ peak) next to the analytic number, flagging >20%
divergence between the two flop counts — the cross-check the fusion
cost-model literature insists on (hand-rolled ceilings drift; the
compiler's own count doesn't).

Capture is free-riding, not double-compiling: :func:`aot_capture`
replaces a ``jax.jit`` callable with its AOT-compiled form
(``.trace(*args).lower().compile()`` — the one compile the first call
would have paid anyway), records the analysis, and falls back to the
original callable on ANY failure, so instrumentation can never break a
step. ``Executor.run``/``warmup`` and ``jit.to_static`` call it on
their cache-miss paths when the monitor is enabled.

Where a first call's time goes: the three stages run under the spans
``xla.trace`` (the step function under JAX's tracer), ``xla.lower``
(jaxpr -> StableHLO, every Pallas instance to Mosaic) and
``xla.backend_compile`` (the cache key, then XLA's compile or the
persistent cache's load), and their durations stand in the executable's
record (``trace_s``, ``lower_s``, ``backend_s``) beside the cache's
verdict (``cache_hit``, ``cache_retrieval_s``) and the Pallas instances
the step holds (``pallas_instances``, ``pallas_traces``). While the
monitor is on, one ``jax.monitoring`` listener also keeps every program
the process compiled or loaded (:func:`programs`, counters
``xla.programs.*``): it fires only when JAX traces, lowers, compiles or
loads a program, never in a steady step.
"""
from __future__ import annotations

import collections
import threading
import time

__all__ = [
    "analyze", "capture", "aot_capture", "get", "flops",
    "bytes_accessed", "peak_memory", "labels", "last", "hlo_text",
    "executable", "measured_mfu", "reset", "programs", "count_pallas",
]

MAX_ENTRIES = 64
MAX_PROGRAMS = 256

_CLOCK = time.perf_counter

_lock = threading.Lock()
_entries = {}       # label -> analysis dict
_execs = {}         # label -> the Compiled object (for HLO dumps)
_order = []         # labels, oldest first (insertion/refresh order)
_programs = collections.deque(maxlen=MAX_PROGRAMS)  # newest last
_listening = False
# the cache's verdict on the program this thread is compiling: JAX says
# hit or miss before it reports the backend compile's duration
_pending = threading.local()


def analyze(compiled):
    """Best-effort cost+memory extraction from an AOT Compiled object.
    Returns a (possibly empty) dict; never raises. Negative values
    (XLA's "unknown" marker on some backends) are dropped."""
    info = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if ca:
        d = ca[0] if isinstance(ca, (list, tuple)) else ca
        if isinstance(d, dict):
            for src, dst in (("flops", "flops"),
                             ("bytes accessed", "bytes_accessed"),
                             ("transcendentals", "transcendentals")):
                v = d.get(src)
                if v is not None and float(v) >= 0:
                    info[dst] = float(v)
    try:
        ms = compiled.memory_analysis()
    except Exception:
        ms = None
    if ms is not None:
        for attr, dst in (("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("temp_size_in_bytes", "temp_bytes"),
                          ("alias_size_in_bytes", "alias_bytes"),
                          ("generated_code_size_in_bytes", "code_bytes")):
            try:
                v = getattr(ms, attr, None)
            except Exception:
                v = None
            if v is not None and float(v) >= 0:
                info[dst] = float(v)
        peak = (info.get("argument_bytes", 0.0)
                + info.get("output_bytes", 0.0)
                + info.get("temp_bytes", 0.0)
                - info.get("alias_bytes", 0.0))
        if peak > 0:
            info["peak_memory"] = float(peak)
    return info


def capture(label, compiled, **stages):
    """Analyze + store under ``label`` (newest entry becomes
    :func:`last`), set the ``xla.*`` gauges and emit one ``xla_cost``
    JSONL record when the monitor is enabled. ``stages`` are what
    :func:`aot_capture` saw of the first call (``trace_s`` ...), kept in
    the same record. Returns the analysis dict (may hold the stages
    alone on exotic backends)."""
    label = str(label)
    info = analyze(compiled)
    info.update(stages)
    with _lock:
        if label in _order:
            _order.remove(label)
        _order.append(label)
        _entries[label] = info
        _execs[label] = compiled
        while len(_order) > MAX_ENTRIES:
            old = _order.pop(0)
            _entries.pop(old, None)
            _execs.pop(old, None)
    from . import emit, enabled, gauge
    if enabled():
        for key, series in (("flops", "xla.flops"),
                            ("bytes_accessed", "xla.bytes_accessed"),
                            ("peak_memory", "xla.peak_memory")):
            if key in info:
                gauge(f"{series}.{label}").set(info[key])
        emit(kind="xla_cost", label=label, **info)
    return info


def _stage(name, label, call):
    """One stage of a first call under its span ``xla.<name>``: (what
    ``call`` returned, its seconds). A stage that raises says so on the
    span's args."""
    from . import trace
    span = trace.span(f"xla.{name}", label=label)
    with span:
        t0 = _CLOCK()
        try:
            return call(), _CLOCK() - t0
        except Exception:
            if getattr(span, "args", None) is not None:
                span.args["failed"] = name
            raise


def aot_capture(fn, label, args):
    """AOT-compile ``fn`` at ``args`` (a tuple of the exact call
    arguments — lowering does NOT execute them) in three timed stages,
    capture the analysis with them, and return the Compiled callable; an
    already-compiled object is captured in place. Any failure returns
    ``fn`` untouched — the caller keeps its working jitted entry — and
    leaves one ``xla_capture_failed`` record that names the stage."""
    stage = "capture"
    try:
        if hasattr(fn, "cost_analysis"):       # already AOT-compiled
            capture(label, fn)
            return fn
        stage = "trace"
        traced, trace_s = _stage(stage, label, lambda: fn.trace(*args))
        instances, traces = count_pallas(traced.jaxpr)
        stage = "lower"
        lowered, lower_s = _stage(stage, label, traced.lower)
        stage = "backend_compile"
        _pending.program = None
        compiled, backend_s = _stage(stage, label, lowered.compile)
        # the listener's record of that compile, made on this thread
        # inside lowered.compile(); there is none while the monitor is off
        program = getattr(_pending, "program", None) or {}
        stage = "capture"
        from . import registry
        capture(label, compiled, trace_s=trace_s, lower_s=lower_s,
                backend_s=backend_s, pallas_instances=instances,
                pallas_traces=traces,
                cache_hit=program.get("cache_hit"),
                cache_retrieval_s=program.get("cache_retrieval_s"),
                at_step_calls=_step_calls(registry()))
        return compiled
    except Exception as e:
        from . import counter, emit, enabled
        if enabled():
            counter("xla.capture_failed").inc()
            emit(kind="xla_capture_failed", label=str(label), stage=stage,
                 error=repr(e))
        return fn


def count_pallas(jaxpr):
    """(instances, traces) of a step's jaxpr: the ``pallas_call``
    equations that will each be lowered to Mosaic once, and the distinct
    kernel bodies among them (each traced once). The walk enters every
    sub-jaxpr an equation holds (cond branches, scan and while bodies,
    ``checkpoint``, custom-vjp) and a jaxpr that several equations share
    through an inner ``jax.jit`` once, as JAX lowers it once a module; a
    kernel's own body is not entered."""
    instances, bodies, shared = 0, set(), set()
    todo = [getattr(jaxpr, "jaxpr", jaxpr)]
    while todo:
        for eqn in todo.pop().eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                instances += 1
                bodies.add(id(eqn.params.get("jaxpr")))
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if not hasattr(inner, "eqns"):
                        continue
                    if name == "jit":
                        # JAX's key for the one lowering a module
                        key = (eqn.params.get("name"), id(sub))
                        if key in shared:
                            continue
                        shared.add(key)
                    todo.append(inner)
    return instances, len(bodies)


# ---------------------------------------------------------------------------
# every program the process compiled or loaded (jax.monitoring)

_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _step_calls(registry):
    """How many compiled-step calls the process has made."""
    return int(registry.value("jit.compile", 0)) + \
        int(registry.value("jit.cache_hit", 0))


def _on_event(event, **kwargs):
    if event == _HIT_EVENT or event == _MISS_EVENT:
        from . import counter
        _pending.cache_hit = event == _HIT_EVENT
        counter("xla.programs.cache_hits" if _pending.cache_hit
                else "xla.programs.cache_misses").inc()


def _on_duration(event, duration, **kwargs):
    # JAX reports every traced function here, thousands a step: the
    # three events kept are told from the rest before anything else
    if event == _LOWER_EVENT:
        from . import counter
        counter("xla.programs.lower_s").inc(duration)
    elif event == _RETRIEVAL_EVENT:
        from . import counter
        counter("xla.programs.cache_retrieval_s").inc(duration)
        _pending.retrieval_s = duration
    elif event == _BACKEND_EVENT:
        from . import counter, registry
        counter("xla.programs.backend_s").inc(duration)
        record = {"fun_name": kwargs.get("fun_name"),
                  "backend_s": duration,
                  "cache_hit": getattr(_pending, "cache_hit", None),
                  "cache_retrieval_s": getattr(_pending, "retrieval_s",
                                               None),
                  "at_step_calls": _step_calls(registry())}
        _pending.cache_hit = _pending.retrieval_s = None
        _pending.program = record
        _programs.append(record)


def listen(on):
    """``monitor.enable()`` / ``monitor.disable()`` register and
    unregister the one ``jax.monitoring`` listener pair here; a process
    that never enabled the monitor registered nothing."""
    global _listening
    if bool(on) == _listening:
        return
    from jax import monitoring
    if on:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
    else:
        for unregister, callback in (
                (monitoring.unregister_event_listener, _on_event),
                (monitoring.unregister_event_duration_listener,
                 _on_duration)):
            try:
                unregister(callback)
            except (AssertionError, ValueError):
                pass    # someone cleared JAX's lists: nothing to take out
    _listening = bool(on)


def programs():
    """One record a backend compile the process made while the monitor
    was on, oldest first, the newest ``MAX_PROGRAMS``: ``fun_name``,
    ``backend_s`` (XLA's compile, or the cache key and the load),
    ``cache_hit`` (None where no persistent cache is configured),
    ``cache_retrieval_s`` and ``at_step_calls``, the compiled-step calls
    (``jit.compile`` + ``jit.cache_hit``) the process had made by then."""
    return [dict(r) for r in list(_programs)]


def get(label=None):
    """The analysis dict for ``label`` (default: the most recently
    captured executable), or None."""
    with _lock:
        if label is None:
            if not _order:
                return None
            label = _order[-1]
        return _entries.get(str(label))


def flops(label=None):
    info = get(label)
    return info.get("flops") if info else None


def bytes_accessed(label=None):
    info = get(label)
    return info.get("bytes_accessed") if info else None


def peak_memory(label=None):
    info = get(label)
    return info.get("peak_memory") if info else None


def labels():
    with _lock:
        return list(_order)


def last():
    """(label, analysis) of the most recent capture, or None."""
    with _lock:
        if not _order:
            return None
        label = _order[-1]
        return label, _entries.get(label)


def executable(label=None):
    """The captured Compiled object for ``label`` (default: newest), or
    None — monitor.profile pulls untruncated HLO through this."""
    with _lock:
        if label is None:
            if not _order:
                return None
            label = _order[-1]
        return _execs.get(str(label))


def hlo_text(label=None, max_bytes=2_000_000):
    """HLO of a captured executable (default: newest), truncated to
    ``max_bytes``; None when unavailable. Truncation lands on a line
    boundary with an explicit ``... [truncated N bytes]`` tail so a
    flight-recorder dump stays parseable."""
    exe = executable(label)
    if exe is None:
        return None
    try:
        txt = exe.as_text()
    except Exception:
        return None
    if txt and max_bytes and len(txt) > max_bytes:
        cut = txt.rfind("\n", 0, max_bytes)
        if cut <= 0:
            cut = max_bytes
        dropped = len(txt) - cut
        txt = txt[:cut] + f"\n... [truncated {dropped} bytes]\n"
    return txt or None


def measured_mfu(step_time_s, label=None, peak_flops=None):
    """MFU from XLA-counted flops (vs. the analytic convention fed to
    StepMonitor). None when flops, peak or step time are unknown."""
    f = flops(label)
    if peak_flops is None:
        from .step import peak_flops_for_device
        peak_flops = peak_flops_for_device()
    if not f or not peak_flops or not step_time_s:
        return None
    return f / step_time_s / peak_flops


def reset():
    with _lock:
        _entries.clear()
        _execs.clear()
        _order.clear()
    _programs.clear()
