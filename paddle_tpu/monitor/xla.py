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
(``.lower(*args).compile()`` — the one compile the first call would
have paid anyway), records the analysis, and falls back to the
original callable on ANY failure, so instrumentation can never break a
step. ``Executor.run``/``warmup`` and ``jit.to_static`` call it on
their cache-miss paths when the monitor is enabled.
"""
from __future__ import annotations

import threading

__all__ = [
    "analyze", "capture", "aot_capture", "get", "flops",
    "bytes_accessed", "peak_memory", "labels", "last", "hlo_text",
    "executable", "measured_mfu", "reset",
]

MAX_ENTRIES = 64

_lock = threading.Lock()
_entries = {}       # label -> analysis dict
_execs = {}         # label -> the Compiled object (for HLO dumps)
_order = []         # labels, oldest first (insertion/refresh order)


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


def capture(label, compiled):
    """Analyze + store under ``label`` (newest entry becomes
    :func:`last`), set the ``xla.*`` gauges and emit one ``xla_cost``
    JSONL record when the monitor is enabled. Returns the analysis dict
    (may be empty on exotic backends)."""
    label = str(label)
    info = analyze(compiled)
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


def aot_capture(fn, label, args):
    """AOT-compile ``fn`` at ``args`` (a tuple of the exact call
    arguments — lowering does NOT execute them), capture the analysis,
    and return the Compiled callable; an already-compiled object is
    captured in place. Any failure returns ``fn`` untouched — the
    caller keeps its working jitted entry."""
    try:
        if hasattr(fn, "cost_analysis"):       # already AOT-compiled
            capture(label, fn)
            return fn
        compiled = fn.lower(*args).compile()
        capture(label, compiled)
        return compiled
    except Exception:
        from . import counter, enabled
        if enabled():
            counter("xla.capture_failed").inc()
        return fn


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
