"""paddle_tpu.monitor.trace — thread-aware span tracing + flight recorder.

The reference stack answered "where did the step's time go" with a
per-op CUDA timeline (reference: paddle/fluid/platform/profiler.cc,
device_tracer.cc, exported through chrome://tracing). This is the TPU
rebuild's equivalent: nested ``span("name")`` context managers record
begin/end events into a bounded ring buffer, one logical track per
thread, and :func:`export_chrome_trace` writes Chrome trace-event JSON
that Perfetto / chrome://tracing loads directly — the prefetch producer
thread, the host step loop and the watchdog each get their own track,
so pipeline overlap is *observed*, not inferred from counters.

Cost discipline (same contract as the dispatch hook): with the tracer
and the monitor both off — the default — ``span()`` does ONE module-flag
check and returns a shared null context manager; no event tuple, no clock
read, no dict. The tracer costs one ``perf_counter()`` + one deque append
per span edge (appends on ``collections.deque`` are atomic in CPython, so
producer threads never contend on a lock).

One clock with the device: whenever the tracer OR the monitor is on,
``span()`` also enters a ``jax.profiler.TraceAnnotation`` of the same
name, so the program's spans lie in a captured profiler trace beside the
device's operations (``/host:CPU``, the calling thread's line) with no
second switch. With no profiler session that is one small object per
span. The ring records only while the tracer is on.

Usage::

    from paddle_tpu.monitor import trace

    trace.enable()                       # or PADDLE_TPU_TRACE=1
    with trace.span("epoch", epoch=0):
        ...
    trace.export_chrome_trace("/tmp/run.trace.json")   # open in Perfetto

Span sites wired by this package: ``Executor.run`` phases
(feed_prep/compile/execute/fetch), ``jit.<fn>`` compiled-step calls,
``prefetch.produce`` producer iterations, ``dataloader.assemble``,
``optimizer.step``, ``checkpoint.save``/``restore``,
``resilience.backoff`` waits, ``fit.step``; ``dispatch.<op>`` complete
events ride the existing ``time_dispatch`` opt-in, and collectives
appear as instant events. A compiled step's call is ``jit.<fn>`` with
``jit.collect``, ``jit.execute`` and ``jit.writeback`` inside it;
``Tensor.numpy()`` reads the device under ``tensor.to_host``.

The flight recorder (:func:`flight_record`) turns "it hung at step
4017" into an artifact: on a watchdog stall, a NaN-guard rollback or an
unhandled crash in ``fit``/``Executor.run`` it dumps the last buffered
spans (as a loadable Chrome trace), the full counter snapshot, and the
HLO text of the most recently captured executable (monitor.xla) into a
timestamped directory.
"""
from __future__ import annotations

import collections
import functools
import json
import os
import re
import tempfile
import threading
import time

__all__ = [
    "enable", "disable", "enabled", "clear", "span", "complete",
    "instant", "counter", "traced", "events", "export_chrome_trace",
    "flight_record", "last_flight", "flow_start", "flow_step",
    "flow_end", "lane_complete", "lane_instant", "lanes",
]

DEFAULT_BUFFER = 65536

_CLOCK = time.perf_counter

_active = False             # the ring records
_monitor_on = False         # monitor.enable() told us (note_monitor)
_live = False               # what span() reads: _active or _monitor_on
_events = collections.deque(maxlen=DEFAULT_BUFFER)
_thread_names = {}          # thread ident -> name (first event wins)
_t0 = 0.0                   # perf_counter origin for export timestamps
_wall0 = 0.0                # wall clock at enable (for correlation)
_flight_lock = threading.Lock()
_flight_dumps = 0
_last_flight = None         # newest flight-recorder dir (/snapshot shows it)

# synthetic tracks ("lanes") that belong to a resource rather than a
# thread — KV slots, pools. Their tids sit in a range no pthread ident
# (a pointer-sized value) occupies, so each lane renders as its own
# named row in Perfetto.
_LANE_BASE = 1 << 20
_lanes = {}                 # lane name -> synthetic tid
_lane_lock = threading.Lock()


def last_flight():
    """Path of the most recent flight-recorder dump this process wrote,
    or None — the /snapshot health endpoint's pointer to post-mortem
    evidence."""
    return _last_flight


# ---------------------------------------------------------------------------
# lifecycle

def enabled():
    return _active


def enable(buffer_size=None):
    """Turn span recording on. ``buffer_size`` resizes the ring buffer
    (default 65536 events ≈ 32k spans — old events fall off the front).
    Idempotent."""
    global _active, _live, _events, _t0, _wall0
    if buffer_size:
        _events = collections.deque(_events, maxlen=int(buffer_size))
    if not _active:
        _t0 = _CLOCK()
        _wall0 = time.time()
        _active = _live = True
    _note_thread(threading.get_ident())


def disable():
    """Stop recording. The buffer is KEPT so a post-run
    export_chrome_trace() still works; clear() empties it."""
    global _active, _live
    _active = False
    _live = _monitor_on


def note_monitor(on):
    """``monitor.enable()`` / ``monitor.disable()`` say so here: with the
    monitor on, spans are profiler annotations even while the ring is
    off."""
    global _monitor_on, _live
    _monitor_on = bool(on)
    _live = _active or _monitor_on


def clear():
    global _flight_dumps, _last_flight
    _events.clear()
    _thread_names.clear()
    with _lane_lock:
        _lanes.clear()
    _flight_dumps = 0
    _last_flight = None


def _note_thread(tid):
    if tid not in _thread_names:
        _thread_names[tid] = threading.current_thread().name


# ---------------------------------------------------------------------------
# recording

class _NullSpan:
    """The shared disabled-mode context manager: nothing allocated,
    nothing recorded."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


_annotation_cls = None


def _annotation(name):
    global _annotation_cls
    if _annotation_cls is None:
        import jax.profiler
        _annotation_cls = jax.profiler.TraceAnnotation
    return _annotation_cls(name)


class _Span:
    __slots__ = ("name", "args", "_ann", "_recorded")

    def __init__(self, name, args):
        self.name = name
        self.args = args
        self._ann = None
        self._recorded = False

    def __enter__(self):
        if _active:
            tid = threading.get_ident()
            if tid not in _thread_names:
                _note_thread(tid)
            _events.append(("B", self.name, tid, _CLOCK(), self.args))
            self._recorded = True
        try:
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        except Exception:
            self._ann = None
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
            self._ann = None
        if self._recorded:
            _events.append(("E", self.name, threading.get_ident(),
                            _CLOCK()))
        return False


def span(name, **args):
    """``with trace.span("executor.execute", step=i): ...`` — a profiler
    annotation of that name on the calling thread whenever the tracer or
    the monitor is on, and a begin/end event pair in the ring while the
    tracer is. With both off it returns the shared null context manager
    after one flag check."""
    if not _live:
        return _NULL
    return _Span(name, args or None)


def complete(name, t0, t1=None, **args):
    """Record an already-timed interval (the dispatch hook's path: t0
    was stamped by the time_dispatch machinery, so the span costs no
    extra clock read at the start)."""
    if not _active:
        return
    t1 = _CLOCK() if t1 is None else t1
    tid = threading.get_ident()
    if tid not in _thread_names:
        _note_thread(tid)
    _events.append(("X", name, tid, t0, t1 - t0, args or None))


def instant(name, **args):
    """A zero-duration marker (collective issue sites, fault firings)."""
    if not _active:
        return
    tid = threading.get_ident()
    if tid not in _thread_names:
        _note_thread(tid)
    _events.append(("I", name, tid, _CLOCK(), args or None))


def counter(name, values=None, ts=None, **kw):
    """A Chrome counter ("C") sample: ``values`` (dict) and/or keyword
    series render as a stacked counter track in Perfetto —
    ``trace.counter("hbm", bytes_in_use=x)``. ``ts=`` back/forward
    dates the sample on the perf_counter timeline (memory.report uses
    it to lay the predicted-occupancy curve out as one synthetic
    microsecond per schedule slot). Disabled mode is one flag check."""
    if not _active:
        return
    vals = dict(values) if values else {}
    if kw:
        vals.update(kw)
    if not vals:
        return
    tid = threading.get_ident()
    if tid not in _thread_names:
        _note_thread(tid)
    _events.append(("C", name, tid, _CLOCK() if ts is None else ts,
                    vals))


def _flow(kind, name, fid, args):
    if not _active:
        return
    tid = threading.get_ident()
    if tid not in _thread_names:
        _note_thread(tid)
    _events.append((kind, name, tid, _CLOCK(), int(fid), args or None))


def flow_start(name, fid, **args):
    """Open a flow (Perfetto arrow chain) with numeric id ``fid``. Flow
    events anchor to the innermost OPEN span on the calling thread, so
    emit them inside a ``span()`` — that is the slice the arrow leaves
    from."""
    _flow("FS", name, fid, args)


def flow_step(name, fid, **args):
    """Continue flow ``fid`` on the current thread (arrow lands on the
    enclosing slice, then leaves it again)."""
    _flow("FT", name, fid, args)


def flow_end(name, fid, **args):
    """Terminate flow ``fid`` at the enclosing slice."""
    _flow("FF", name, fid, args)


def _lane_tid(lane):
    with _lane_lock:
        tid = _lanes.get(lane)
        if tid is None:
            tid = _LANE_BASE + len(_lanes)
            _lanes[lane] = tid
            _thread_names[tid] = lane
        return tid


def lanes():
    """Registered lane names -> synthetic track ids."""
    with _lane_lock:
        return dict(_lanes)


def lane_complete(lane, name, t0, t1=None, **args):
    """Record a pre-timed interval on a named resource lane (a KV slot's
    occupied-by-request interval, a prefill admission) rather than on
    the calling thread's track. ``t0``/``t1`` are perf_counter stamps —
    the same clock ``span()`` uses, so lanes and thread tracks line up
    in one timeline."""
    if not _active:
        return
    t1 = _CLOCK() if t1 is None else t1
    _events.append(("X", name, _lane_tid(lane), t0, t1 - t0,
                    args or None))


def lane_instant(lane, name, ts=None, **args):
    """A zero-duration marker on a resource lane (pool growth pads)."""
    if not _active:
        return
    _events.append(("I", name, _lane_tid(lane),
                    _CLOCK() if ts is None else ts, args or None))


def traced(name=None):
    """Decorator form: ``@trace.traced`` or ``@trace.traced("label")``.
    Disabled mode adds one flag check per call."""
    def deco(fn):
        label = name if isinstance(name, str) else \
            getattr(fn, "__qualname__", getattr(fn, "__name__", "fn"))

        @functools.wraps(fn)
        def wrapped(*a, **k):
            if not _live:
                return fn(*a, **k)
            with _Span(label, None):
                return fn(*a, **k)
        return wrapped
    if callable(name):       # bare @traced
        return deco(name)
    return deco


def events(last=None):
    """Snapshot of the ring buffer (tuples; newest last). ``last=N``
    returns only the trailing N events."""
    evs = list(_events)
    return evs[-int(last):] if last else evs


# ---------------------------------------------------------------------------
# export

def _us(t):
    return round((t - _t0) * 1e6, 3)


def export_chrome_trace(path=None, last=None):
    """Render the buffer as Chrome trace-event JSON (the "JSON Array
    Format" with metadata): one ``pid`` per process, one ``tid`` track
    per thread (named via ``thread_name`` metadata events), ``B``/``E``
    pairs for spans, ``X`` complete events for pre-timed intervals
    (dispatch ops), ``i`` instants for markers. Load the file in
    https://ui.perfetto.dev or chrome://tracing.

    ``path=None`` returns the dict; a directory gets a
    ``trace-<pid>.json`` inside; any other path is written verbatim.
    Returns the written path (or the dict)."""
    pid = os.getpid()
    out = [{"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": f"paddle_tpu[{pid}]"}}]
    for tid, tname in sorted(_thread_names.items()):
        out.append({"ph": "M", "pid": pid, "tid": tid,
                    "name": "thread_name", "args": {"name": tname}})
    for ev in events(last=last):
        kind = ev[0]
        if kind == "B":
            _, name, tid, t, args = ev
            rec = {"ph": "B", "pid": pid, "tid": tid, "name": name,
                   "ts": _us(t), "cat": "span"}
        elif kind == "E":
            _, name, tid, t = ev
            rec = {"ph": "E", "pid": pid, "tid": tid, "name": name,
                   "ts": _us(t), "cat": "span"}
            args = None
        elif kind == "X":
            _, name, tid, t, dur, args = ev
            rec = {"ph": "X", "pid": pid, "tid": tid, "name": name,
                   "ts": _us(t), "dur": round(max(0.0, dur) * 1e6, 3),
                   "cat": "op"}
        elif kind == "C":
            _, name, tid, t, args = ev
            rec = {"ph": "C", "pid": pid, "tid": tid, "name": name,
                   "ts": _us(t), "cat": "counter"}
        elif kind in ("FS", "FT", "FF"):
            _, name, tid, t, fid, args = ev
            rec = {"ph": {"FS": "s", "FT": "t", "FF": "f"}[kind],
                   "pid": pid, "tid": tid, "name": name,
                   "ts": _us(t), "id": fid, "cat": "flow"}
            if kind == "FF":
                # bind to the enclosing slice even if no event starts
                # exactly at the arrow head
                rec["bp"] = "e"
        else:
            _, name, tid, t, args = ev
            rec = {"ph": "i", "pid": pid, "tid": tid, "name": name,
                   "ts": _us(t), "s": "t", "cat": "marker"}
        if args:
            rec["args"] = args
        out.append(rec)
    doc = {"traceEvents": out, "displayTimeUnit": "ms",
           "otherData": {"epoch_wall_s": _wall0, "pid": pid}}
    if path is None:
        return doc
    p = str(path)
    if not p.endswith(".json"):
        os.makedirs(p, exist_ok=True)
        p = os.path.join(p, f"trace-{pid}.json")
    else:
        parent = os.path.dirname(os.path.abspath(p))
        if parent:
            os.makedirs(parent, exist_ok=True)
    with open(p, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, default=str)
    return os.path.abspath(p)


# ---------------------------------------------------------------------------
# flight recorder

def flight_record(reason, step=None, directory=None, extra=None):
    """Dump post-mortem evidence to a timestamped directory and return
    its path (None when rate-capped or anything fails — the recorder
    must never add a second crash on top of the first).

    Layout::

        <base>/<stamp>-<reason>-<pid>-<n>/
            meta.json       reason / step / pid / sink path / extra
            counters.json   full registry snapshot
            trace.json      the span ring buffer as a Chrome trace
            hlo-<label>.txt HLO of the last captured executable (if any)
            op_ledger.json  monitor.profile per-op cost ledger (if any)
            memory_report.json  monitor.memory peak-contributor ledger

    ``base`` is ``directory=``, else $PADDLE_TPU_FLIGHT_DIR, else a
    ``flight/`` sibling of the monitor JSONL sink, else the system temp
    dir. At most $PADDLE_TPU_FLIGHT_MAX (default 8) dumps per process —
    a crash loop leaves evidence, not a full disk. Triggered by the
    resilience watchdog (stall), NaNGuard (rollback), and the crash
    handlers in ``hapi.Model.fit`` / ``Executor.run``."""
    global _flight_dumps
    try:
        from . import emit as _memit
        from . import jsonl_path as _mpath
        from . import snapshot as _msnap
        try:
            cap = int(os.environ.get("PADDLE_TPU_FLIGHT_MAX", "8") or 8)
        except ValueError:
            cap = 8
        with _flight_lock:
            if _flight_dumps >= cap:
                return None
            _flight_dumps += 1
            n = _flight_dumps
        base = directory or os.environ.get("PADDLE_TPU_FLIGHT_DIR")
        if not base:
            jp = _mpath()
            base = (os.path.join(os.path.dirname(jp), "flight") if jp
                    else os.path.join(tempfile.gettempdir(),
                                      "paddle_tpu_flight"))
        stamp = time.strftime("%Y%m%d-%H%M%S")
        safe_reason = re.sub(r"[^A-Za-z0-9_.-]+", "_", str(reason))
        d = os.path.join(base, f"{stamp}-{safe_reason}-{os.getpid()}-{n}")
        os.makedirs(d, exist_ok=True)

        meta = {"reason": str(reason), "step": step, "ts": time.time(),
                "pid": os.getpid(), "jsonl": _mpath(),
                "trace_enabled": _active, "events_buffered": len(_events)}
        if extra:
            meta["extra"] = {str(k): v for k, v in dict(extra).items()}
        with open(os.path.join(d, "meta.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(meta, fh, default=str, indent=1)
        with open(os.path.join(d, "counters.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(_msnap(), fh, default=str, indent=1)
        export_chrome_trace(os.path.join(d, "trace.json"))

        try:
            from . import xla as _xla
            hlo = _xla.hlo_text()
            if hlo:
                last = _xla.last()
                label = re.sub(r"[^A-Za-z0-9_.-]+", "_",
                               last[0] if last else "executable")
                with open(os.path.join(d, f"hlo-{label}.txt"), "w",
                          encoding="utf-8") as fh:
                    fh.write(hlo)
        except Exception:
            pass

        # the per-op cost ledger next to its HLO: the cached report if
        # one exists, else a fresh parse of the captured executable —
        # still inside the outer try, never a second crash
        try:
            from . import profile as _profile
            ledger = _profile.last_report()
            if ledger is None:
                ledger = _profile.report(emit_records=False)
            if ledger:
                with open(os.path.join(d, "op_ledger.json"), "w",
                          encoding="utf-8") as fh:
                    json.dump(ledger, fh, default=str, indent=1)
        except Exception:
            pass

        # the memory report + peak-contributor ledger next to the op
        # ledger (an OOM postmortem is exactly this pair): cached if
        # one exists, else a fresh simulation of the same executable
        try:
            from . import memory as _memory
            mrep = _memory.last_report()
            if mrep is None:
                mrep = _memory.report(emit_records=False)
            if mrep:
                with open(os.path.join(d, "memory_report.json"), "w",
                          encoding="utf-8") as fh:
                    json.dump(mrep, fh, default=str, indent=1)
        except Exception:
            pass

        # the slow-request exemplar ring next to the op/memory ledgers:
        # the N worst ttft/tpot waterfalls with full stage breakdowns —
        # "why was serving slow" evidence for a serving-side postmortem.
        # Lazy via sys.modules so telemetry never imports serving.
        try:
            import sys as _sys
            _rq = _sys.modules.get("paddle_tpu.serving.reqtrace")
            if _rq is not None:
                ex = _rq.exemplars()
                if ex.get("worst_ttft") or ex.get("worst_tpot"):
                    with open(os.path.join(d, "slow_requests.json"), "w",
                              encoding="utf-8") as fh:
                        json.dump(ex, fh, default=str, indent=1)
        except Exception:
            pass

        _memit(kind="flight_record", reason=str(reason), step=step,
               path=d)
        global _last_flight
        _last_flight = d
        return d
    except Exception:
        return None
