"""paddle_tpu.utils.profiler — profiling.

TPU-native rebuild of reference python/paddle/fluid/profiler.py (+
platform/profiler.cc). The reference collects per-op CUDA timings; on TPU
the equivalent signal is an XLA trace viewable in TensorBoard/Perfetto,
captured via jax.profiler. A lightweight host-side timer table covers the
start/stop/print surface of the reference API.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict

import jax

_records = defaultdict(lambda: [0.0, 0])
_trace_dir = None
_profiling_active = False  # the reference's core.is_profiler_enabled()


def start_profiler(state="All", tracer_option=None, trace_dir=None):
    """reference: profiler.start_profiler. Starts a jax.profiler trace."""
    global _trace_dir, _profiling_active
    _trace_dir = trace_dir or "/tmp/paddle_tpu_trace"
    jax.profiler.start_trace(_trace_dir)
    _profiling_active = True


def stop_profiler(sorted_key=None, profile_path=None):
    global _profiling_active
    _profiling_active = False
    jax.profiler.stop_trace()
    print(f"[paddle_tpu.profiler] XLA trace written to {_trace_dir} "
          "(open with TensorBoard / Perfetto)")
    if _records:
        print_stats()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None):
    """reference: fluid.profiler.profiler context manager."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def scope(name):
    """Host-side named timer + device annotation (StepTraceAnnotation)."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    dt = time.perf_counter() - t0
    _records[name][0] += dt
    _records[name][1] += 1


record_event = scope


def print_stats():
    print(f"{'name':<40}{'calls':>8}{'total_s':>12}{'avg_ms':>12}")
    for name, (total, calls) in sorted(_records.items(),
                                       key=lambda kv: -kv[1][0]):
        print(f"{name:<40}{calls:>8}{total:>12.4f}"
              f"{1000 * total / max(calls, 1):>12.4f}")


def reset_profiler():
    _records.clear()


def summarize_trace(trace_dir, top=20, steps=1):
    """Aggregate DEVICE op time from a jax.profiler trace directory
    (the Chrome-format .trace.json.gz jax writes) into per-op-family
    totals — "where does my step go?" without leaving the terminal.

    Returns a list of (family, total_ms / steps) sorted descending;
    also prints a table. `steps` divides totals by the number of steps
    captured inside the trace window. Host-side python frames, jit
    wrappers and transfer bookkeeping are excluded; op names are
    grouped by their XLA fusion family (e.g. every `multiply_reduce
    _fusion.N` variant aggregates into `multiply_reduce_fusion`).

    This is the tool the round-4 ResNet diagnosis used to find batch
    norm's reduce chains at ~70% of step time while convs ran at peak
    (docs/performance.md)."""
    import collections
    import glob
    import gzip
    import json
    import os

    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not files:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {trace_dir!r} — pass the "
            "directory given to start_profiler()/jax.profiler.trace")
    if len(files) > 1:
        print(f"[summarize_trace] {len(files)} trace files found; "
              f"reading newest: {files[-1]}")
    skip = ("$", "jit_", "PjitFunction", "np.asarray", "trace",
            "ArrayImpl", "ParseArguments", "PythonRefManager",
            "PJRT_", "copy-start", "slice-start")
    tot = collections.Counter()
    with gzip.open(files[-1]) as fh:
        data = json.load(fh)
    events = data.get("traceEvents", [])
    # Identify the device lanes from the trace's process metadata (ph=M
    # process_name events whose name carries the device identity, e.g.
    # "/device:TPU:0 ..."), so host-side 'X' events can't inflate op
    # totals regardless of their names (r4 advisor finding).
    device_pids = {
        e.get("pid") for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and any(tag in str(e.get("args", {}).get("name", ""))
                for tag in ("/device:", "TPU", "GPU", "XLA"))
    }
    if not device_pids:
        print("[summarize_trace] no device lanes in process metadata; "
              "falling back to name-substring host filtering "
              "(approximate)")
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        n = e.get("name", "?")
        if any(s in n for s in skip) or n.isdigit():
            continue
        tot[n.split(".")[0]] += e["dur"]
    fams = [(name, d / 1e3 / max(steps, 1))
            for name, d in tot.most_common(top)]
    total = sum(d for _, d in fams)
    print(f"{'op family':<44}{'ms/step':>10}")
    for name, ms in fams:
        print(f"{name[:43]:<44}{ms:>10.2f}")
    print(f"{'TOTAL (top ' + str(top) + ')':<44}{total:>10.2f}")
    return fams


# --- paddle.utils.profiler parity (reference: utils/profiler.py) -----------

import sys as _sys


class ProfilerOptions:
    """reference utils/profiler.py:ProfilerOptions — option dict with
    'none' → None resolution."""

    def __init__(self, options=None):
        self.options = {
            "state": "All",
            "sorted_key": "default",
            "tracer_level": "Default",
            "batch_range": [0, _sys.maxsize],
            "output_thread_detail": False,
            "profile_path": "none",
            "timeline_path": "none",
            "op_summary_path": "none",
        }
        if options is not None:
            for key in self.options:
                if options.get(key, None) is not None:
                    self.options[key] = options[key]

    def with_state(self, state):
        self.options["state"] = state
        return self

    def __getitem__(self, name):
        if self.options.get(name, None) is None:
            raise ValueError(
                f"ProfilerOptions does not have an option named {name}.")
        v = self.options[name]
        return None if isinstance(v, str) and v == "none" else v


_current_profiler = None


class Profiler:
    """reference utils/profiler.py:Profiler — context-manager +
    batch-range driver over start/stop_profiler."""

    def __init__(self, enabled=True, options=None):
        self.profiler_options = options if options is not None \
            else ProfilerOptions()
        self.batch_id = 0
        self.enabled = enabled

    def __enter__(self):
        global _current_profiler
        self.previous_profiler = _current_profiler
        _current_profiler = self
        if self.enabled and self.profiler_options["batch_range"][0] == 0:
            self.start()
        return self

    def __exit__(self, exception_type, exception_value, traceback):
        global _current_profiler
        _current_profiler = self.previous_profiler
        if self.enabled:
            self.stop()

    def start(self):
        if not self.enabled:
            return
        import warnings
        try:
            start_profiler(
                state=self.profiler_options["state"],
                tracer_option=self.profiler_options["tracer_level"])
        except Exception as e:  # pragma: no cover
            warnings.warn("Profiler is not enabled because following "
                          f"exception:\n{e}")

    def stop(self):
        if not self.enabled or not _profiling_active:
            return
        import warnings
        try:
            stop_profiler(
                sorted_key=self.profiler_options["sorted_key"],
                profile_path=self.profiler_options["profile_path"])
        except Exception as e:  # pragma: no cover
            warnings.warn("Profiler is not disabled because following "
                          f"exception:\n{e}")

    def reset(self):
        if self.enabled and self.profiler_options["state"] != "Off":
            reset_profiler()

    def record_step(self, change_profiler_status=True):
        if not self.enabled:
            return
        self.batch_id += 1
        if not change_profiler_status:
            return
        lo, hi = self.profiler_options["batch_range"]
        if self.batch_id == lo:
            # reference gate: core.is_profiler_enabled() — reset a trace
            # that is already running, start one otherwise
            if _profiling_active:
                self.reset()
            else:
                self.start()
        if self.batch_id == hi:
            self.stop()


def get_profiler():
    """reference utils/profiler.py:get_profiler — the active Profiler,
    creating a disabled default if none is in scope."""
    global _current_profiler
    if _current_profiler is None:
        _current_profiler = Profiler(enabled=False)
    return _current_profiler
