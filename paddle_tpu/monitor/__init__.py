"""paddle_tpu.monitor — framework-wide metrics & tracing runtime.

The observability subsystem every hot path reports through
(reference analogue: paddle/fluid/platform/profiler.cc — but that was
per-op CUDA timings printed at exit; this is a structured, queryable
record):

* ``dispatch.apply``      — per-op call counts (eager/static, grad/no-grad,
                            optional host timing), behind one flag check
* ``parallel.collective`` — per-collective issue counts + payload bytes
                            by mesh axis
* ``static.Executor``     — program run/compile counts, cache hits
* ``optimizer.step``      — step entries per optimizer class
* ``StepMonitor``         — step time, items/sec, device memory, MFU

Step-pipelining series (docs/performance.md "Step pipelining"):

* ``executor.recompile`` / ``jit.recompile`` — cache misses for a
  program/function whose earlier shapes already compiled (avoidable,
  shape-driven recompiles — the number bucketing drives to zero)
* ``executor.bucket_pad`` / ``jit.bucket_pad`` — ragged batches padded
  up to a bucket instead of minting a new executable
* ``executor.fetch_async`` / ``executor.fetch_skipped`` /
  ``executor.fetch_blocking`` — async-fetch mode accounting (blocking
  must stay 0 when ``async_fetch=True``)
* ``executor.aot_warmup``  — executables compiled ahead of time
* ``prefetch.batches`` / ``prefetch.stall_seconds`` — device-prefetch
  throughput and consumer starvation time

Resilience series (docs/robustness.md; ``paddle_tpu.resilience``):

* ``resilience.retry``          — transient-error retries (loader,
  prefetch, checkpoint I/O), with per-site JSONL events
* ``resilience.nan_skip`` / ``resilience.rollback`` /
  ``resilience.nan_raise`` — NaN-guard policy applications
* ``resilience.watchdog_stall`` — steps past the rolling deadline
  (each also emits a ``watchdog_dump`` event with a counter snapshot)
* ``resilience.preempt_save`` / ``resilience.auto_resume`` —
  preemption checkpoints and resumed runs
* ``resilience.ckpt_quarantine`` — corrupt checkpoints set aside
* ``resilience.fault_injected`` / ``resilience.drop`` — chaos-test
  injections and batches dropped after retry exhaustion
  (``prefetch.drops`` counts the same at the prefetch site)

Sharded-checkpoint series (docs/robustness.md "Sharded & elastic
checkpoints"; ``paddle_tpu.io.sharded``):

* ``ckpt.shard_bytes`` (counter) / ``ckpt.shard_seconds`` (histogram)
  — bytes written and per-shard write latency of sharded saves
* ``ckpt.restore_resharded``    — restores that landed on a mesh with
  a different topology than the one that saved (each also emits a
  ``ckpt`` JSONL event with both mesh signatures)
* ``ckpt.quorum_fallback``      — sharded checkpoints rejected by the
  quorum rule (≥1 missing/corrupt shard) during restore's fallback
  scan; the ``checkpoint.save``/``checkpoint.restore`` trace spans
  carry a ``sharded`` attribute on the sharded path
* ``resilience.elastic_attempt`` / ``elastic_restart`` /
  ``elastic_resize`` / ``elastic_preempt_stop`` — the elastic
  recovery loop's state transitions (``resilience.elastic``)

Serving series (docs/serving.md; ``paddle_tpu.serving``):

* ``serving.requests`` / ``serving.rows`` / ``serving.batches`` —
  submitted requests, their example rows, and coalesced batches
* ``serving.qps`` (gauge) / ``serving.latency_ms`` (histogram) —
  rolling completed-requests/sec and submit→resolve latency
* ``serving.queue_depth`` / ``serving.rejected`` /
  ``serving.deadline_expired`` — admission control in action
* ``serving.batch_fill`` (requests per batch) /
  ``serving.batch_occupancy`` (real rows ÷ bucket rows) /
  ``serving.pad_rows`` — how well dynamic batching amortizes
* ``serving.compiles`` — executables minted by the serving path
  (must stop growing after ``ServingEngine.warmup``)
* ``serving.retries`` / ``serving.isolated`` / ``serving.poisoned`` —
  the RetryPolicy-classified failure path
* ``serving.decode.*`` — the continuous-batching decode tier:
  ``ticks``/``tokens``/``slot_occupancy`` (fused-step cadence and how
  full the decode batch runs), ``prefills``/``prefill_tokens``/
  ``prefill_ms``/``prefill_ratio`` (prompt-ingest side of the
  prefill/decode split), ``compiles`` (decode executables minted —
  must stop growing after ``GenerateEngine.warmup``), and
  ``cache_bytes``/``cache_capacity``/``cache_headroom``/
  ``cache_grows`` (the KV pool's live footprint vs the device budget)
* ``slo.tokens_per_s`` / ``slo.decode_p99_ms`` — the rolling decode
  window the supervisor's ``tokens_floor`` scaling reads
* ``inference.{compile,cache_hit,aot_warmup,bucket_pad}`` — the
  underlying Predictor's executable-cache accounting

Gradient-communication series (docs/performance.md "Communication
overlap & quantized sync"; ``paddle_tpu.parallel.overlap``):

* ``comm.bytes_logical`` / ``comm.bytes_wire`` — f32 payload bytes of
  each gradient sync vs the bytes of its wire representation (f32 for
  exact/overlap, int8/packed-int4 + per-hop scales for quantized) —
  the quantization saving is their ratio
* ``comm.buckets`` / ``comm.bucket_compile`` / ``comm.reduce_launch``
  — bucket plan size, distinct bucket-reduce executables minted (must
  stop growing after the first step of each mode), and launched bucket
  reduces
* ``comm.exposed_wait_s`` (histogram) / ``comm.exposed_wait_s_total``
  — seconds the step loop spent *blocked* on unfinished reduces: the
  exposed wire time overlap mode is built to remove
  (``scripts/comm_smoke.py`` gates on it)
* ``comm.sync.<mode>`` / ``comm.lag_warmup`` — sync calls per mode and
  lag-1 warm-up steps that had no previous grads to apply
* ``comm.bucket_reduce`` / ``comm.wait`` trace spans — bucket reduces
  (on the ``comm-worker`` thread track in overlap mode, where their
  overlap with backward compute is *visible* in the Chrome export) and
  the blocking collect

Auto-sharding planner series (docs/parallelism.md;
``paddle_tpu.parallel.planner``):

* ``planner.plan`` / ``planner.auto_pick`` — plans built, and how many
  let the advisor pick the mesh (``plan(auto=True)``)
* ``planner.candidates`` (gauge) / ``planner.predicted_step_s``
  (gauge) — size of the last advisor table and the winner's predicted
  step time; each decision also lands as one ``kind="planner"`` JSONL
  record (chosen sizes, ranked table head, rule hash) cross-linked to
  the profiler's current top hotspot region, and as the ``planner``
  block of ``/snapshot``
* ``layout.degraded`` — dims a requested spec could not shard on the
  actual mesh (non-divisible or missing axes) and replicated instead;
  warned once per (param, dim), counted every time — the advisor's
  degradation penalty reads the same signal
* ``arena.flat_fallback`` — flat-arena requests that fell back to the
  per-leaf path because the layout shards params (tp/pp/ep > 1);
  warned once per config, counted every time

Span tracing & XLA-measured cost (PR 4's additions):

* ``monitor.trace``  — thread-aware span tracer (``span()`` context
  managers, ring buffer, Chrome-trace/Perfetto export, flight
  recorder). ``PADDLE_TPU_TRACE=1`` arms it alongside ``enable()``.
* ``monitor.xla``    — ``cost_analysis()``/``memory_analysis()`` of
  compiled executables as ``xla.flops.<label>`` /
  ``xla.bytes_accessed.<label>`` / ``xla.peak_memory.<label>`` gauges
  plus ``xla_cost`` JSONL records; feeds the measured-MFU columns in
  StepMonitor. A compiled step's first call runs under the spans
  ``xla.trace`` / ``xla.lower`` / ``xla.backend_compile``, whose
  durations, the cache's verdict and the step's Pallas instances stand
  in the same record (``monitor.xla.get(label)``)
* ``xla.programs.{cache_hits,cache_misses,backend_s,cache_retrieval_s,
  lower_s}`` / ``monitor.xla.programs()`` — every program the process
  compiled or loaded while the monitor was on (one ``jax.monitoring``
  listener, registered by ``enable()`` and taken out by ``disable()``)
* ``runtime.import_s`` (gauge) — what ``import paddle_tpu`` cost this
  process

Memory-observability series (docs/observability.md "Memory
attribution & budget"; ``paddle_tpu.monitor.memory``):

* ``memory.predicted_peak_bytes.<label>`` /
  ``memory.attributed_frac.<label>`` — the HLO buffer-liveness
  model's simulated peak HBM and the fraction of live-at-peak bytes
  credited to a registered framework scope (``memory.report()``)
* ``mem.device.<id>.hbm_headroom_bytes`` / ``mem.hbm_headroom_bytes``
  — sampler-published per-device and total headroom (limit − in-use)
* ``memory.oom`` — OOM-shaped crashes the Executor/``hapi.fit``
  handlers caught; each leaves a flight-recorder dump bundling the
  memory report + peak-contributor ledger next to the op ledger

Everything funnels into one process-global :class:`Registry` and,
when a sink is configured (``PADDLE_TPU_MONITOR_DIR`` or an explicit
path to ``enable()``), a JSONL event stream.

Cost discipline: when disabled (the default), the ONLY overhead on the
dispatch fast path is a single ``_monitor_hook is None`` check inside
``dispatch.apply`` — no dict writes, no allocation (asserted by
tests/test_monitor.py). Collective/executor/optimizer sites check
``monitor.enabled()`` once per call, off any per-element loop.

Usage::

    import paddle_tpu as pt
    from paddle_tpu import monitor

    monitor.enable("/tmp/run1")          # or PADDLE_TPU_MONITOR=1 in env
    ... train ...
    print(monitor.snapshot("dispatch."))  # per-op counts
    monitor.disable()                     # flushes a counters snapshot
"""
from __future__ import annotations

import os
import time

from .registry import Registry, JsonlSink, read_jsonl  # noqa: F401
from .step import (StepMonitor, mfu, peak_flops_for_device,  # noqa: F401
                   transformer_train_flops_per_token,
                   device_memory_stats, GoodputLedger,
                   GOODPUT_CATEGORIES,
                   BERT_BASE_PARAMS, RESNET50_TRAIN_FLOPS_PER_IMAGE)

__all__ = [
    "enable", "disable", "enabled", "registry", "counter", "gauge",
    "histogram", "emit", "snapshot", "reset", "jsonl_path",
    "record_collective", "StepMonitor", "mfu", "peak_flops_for_device",
    "transformer_train_flops_per_token", "device_memory_stats",
    "GoodputLedger", "GOODPUT_CATEGORIES",
    "read_jsonl", "trace", "xla", "serve", "export", "sampler",
    "profile", "memory", "fleet", "alerts", "device_counters",
]

_registry = Registry()
_sink = None
_enabled = False
_time_dispatch = False


# ---------------------------------------------------------------------------
# lifecycle

def enabled():
    return _enabled


def registry() -> Registry:
    return _registry


def jsonl_path():
    """The active sink file, or None (enabled() can be true with no sink
    — counters still collect in memory)."""
    return _sink.path if _sink is not None else None


def _resolve_sink_path(path):
    p = str(path)
    if p.endswith(".jsonl"):
        return p
    os.makedirs(p, exist_ok=True)
    return os.path.join(p, f"events-{os.getpid()}.jsonl")


def enable(path=None, time_dispatch=None, max_bytes=None,
           telemetry_dir=None):
    """Turn monitoring on. `path` is a directory (an events-<pid>.jsonl
    file is created inside) or a *.jsonl file path; default is
    $PADDLE_TPU_MONITOR_DIR, and with neither the registry collects
    in-memory only. time_dispatch=True additionally histograms host-side
    per-op dispatch latency ($PADDLE_TPU_MONITOR_TIME_DISPATCH).
    max_bytes caps the JSONL sink — past it the file rotates to
    ``.1``/``.2`` instead of growing unbounded
    ($PADDLE_TPU_MONITOR_MAX_BYTES). telemetry_dir arms the fleet
    snapshot publisher: this process periodically drops an atomic
    metrics snapshot a FleetAggregator in any process can merge
    ($PADDLE_TPU_TELEMETRY_DIR; see monitor/fleet.py). Without it, no
    publisher thread starts and no snapshot files are written.
    Returns the JSONL path (or None). Idempotent; a new path replaces
    the old sink."""
    global _enabled, _sink, _time_dispatch
    if time_dispatch is None:
        time_dispatch = os.environ.get(
            "PADDLE_TPU_MONITOR_TIME_DISPATCH", "") not in ("", "0")
    _time_dispatch = bool(time_dispatch)
    if max_bytes is None:
        env = os.environ.get("PADDLE_TPU_MONITOR_MAX_BYTES", "")
        max_bytes = int(env) if env else None

    target = path or os.environ.get("PADDLE_TPU_MONITOR_DIR")
    if target:
        fp = _resolve_sink_path(target)
        if (_sink is None or _sink.path != os.path.abspath(fp)
                or _sink.max_bytes != max_bytes):
            # close the previous sink BEFORE installing the new one — a
            # re-enable with a new path must not leak the old file handle
            old, _sink = _sink, None
            if old is not None:
                old.close()
            _sink = JsonlSink(fp, max_bytes=max_bytes)
    _enabled = True
    trace.note_monitor(True)
    xla.listen(True)

    telemetry_target = telemetry_dir or os.environ.get(
        "PADDLE_TPU_TELEMETRY_DIR")
    if telemetry_target:
        fleet.start_publisher(telemetry_target)

    if os.environ.get("PADDLE_TPU_TRACE", "") not in ("", "0"):
        trace.enable()
    if os.environ.get("PADDLE_TPU_PROFILE", "") not in ("", "0"):
        profile.enable()

    from .. import dispatch
    dispatch.install_monitor_hook(_dispatch_hook, time_ops=_time_dispatch)
    emit(kind="monitor", action="enable", pid=os.getpid(),
         time_dispatch=_time_dispatch)

    # zero-code telemetry plane: PADDLE_TPU_METRICS_PORT=9464 (or =0
    # for ephemeral) arms the /metrics HTTP server + sampler from env
    if os.environ.get("PADDLE_TPU_METRICS_PORT", "") != "":
        serve()
    return jsonl_path()


def disable(flush_counters=True):
    """Turn monitoring off: uninstall the dispatch hook (restoring the
    zero-overhead fast path), tear down the telemetry plane (export
    server socket closed + thread joined, sampler joined), emit a final
    counters snapshot, and close the sink. The registry keeps its
    values for post-run inspection — reset() clears them."""
    global _enabled, _sink
    if flush_counters and _enabled:
        emit(kind="counters", counters=snapshot())
    from .. import dispatch
    dispatch.install_monitor_hook(None)
    sampler.stop()
    export.stop()
    fleet.stop_publisher()
    fleet.stop_server()
    _enabled = False
    trace.note_monitor(False)
    xla.listen(False)
    if _sink is not None:
        _sink.close()
        _sink = None


def serve(port=None, host="127.0.0.1", **kw):
    """Start the live telemetry HTTP server (/metrics /healthz
    /snapshot) + periodic sampler. port=None reads
    $PADDLE_TPU_METRICS_PORT, else binds port 0 (ephemeral; read
    ``.port`` off the returned server). See monitor/export.py."""
    return export.serve(port=port, host=host, **kw)


# ---------------------------------------------------------------------------
# metric + event surface

def counter(name):
    return _registry.counter(name)


def gauge(name):
    return _registry.gauge(name)


def histogram(name, buckets=None):
    return _registry.histogram(name, buckets=buckets)


def snapshot(prefix=""):
    return _registry.snapshot(prefix)


def reset():
    _registry.reset()
    xla.reset()


def emit(kind="event", **fields):
    """Append one JSONL record (no-op without a sink)."""
    if _sink is not None:
        rec = {"ts": time.time(), "kind": kind}
        rec.update(fields)
        _sink.emit(rec)


# ---------------------------------------------------------------------------
# instrumentation hooks (called by dispatch / collective / executor /
# optimizer — each call site is behind its own enabled() gate)

def _dispatch_hook(name, grad, t0, static=False):
    """Installed into paddle_tpu.dispatch while enabled. Must stay
    allocation-light: two counter incs, plus one histogram observe (and
    one trace event when span tracing is on) when host timing is on."""
    op = name or "anon"
    _registry.counter(f"dispatch.{op}").inc()
    if static:
        _registry.counter(f"dispatch.static.{op}").inc()
    elif grad:
        _registry.counter(f"dispatch.grad.{op}").inc()
    if t0 is not None:
        t1 = time.perf_counter()
        _registry.histogram(f"dispatch_ms.{op}").observe((t1 - t0) * 1e3)
        # per-op timeline rides the same time_dispatch opt-in: the t0
        # stamp already paid the clock read the span needs
        trace.complete(f"dispatch.{op}", t0, t1)


def record_collective(op, axis_name, nbytes):
    """Per-collective accounting (parallel/collective.py calls this
    after its SPMD gate, so pure-eager identity paths don't count).
    `nbytes` is the per-shard payload at the issue site; inside a jitted
    region the count is per trace, not per device execution — see
    docs/observability.md."""
    axis = axis_name or "none"
    _registry.counter(f"collective.{op}.{axis}.calls").inc()
    _registry.counter(f"collective.{op}.{axis}.bytes").inc(int(nbytes))
    trace.instant(f"collective.{op}", axis=axis, bytes=int(nbytes))
    emit(kind="collective", op=op, axis=axis, bytes=int(nbytes))


# imported last: the submodules reach back into this namespace
# (gauge/emit/snapshot), which is fully populated by this point
from . import (trace, xla, export, sampler, profile,  # noqa: E402,F401
               memory, fleet, alerts, device_counters)
