"""paddle_tpu.monitor.step — training-loop instrumentation + MFU.

``StepMonitor`` wraps a training loop and reports, per step window:
step time, items/sec (tokens or images), device memory stats
(``jax.local_devices()[i].memory_stats()``), and MFU against a
configurable flops ceiling. Each step emits a JSONL ``step`` record
through the monitor sink, and ``report()`` prints a summary table plus a
final ``counters`` snapshot event.

MFU here is the standard model-flops utilization: model flops per step
(NOT hardware flops — rematerialization and padding don't count) divided
by step time, over the chip's peak. The ceiling resolves, in order:
an explicit ``peak_flops=``, ``PADDLE_TPU_FLOPS_CEILING``, then a
device-kind table of per-chip dense bf16 peaks. Unknown device (e.g. the
CPU test mesh) leaves ``mfu`` null rather than inventing a number.
"""
from __future__ import annotations

import os
import time

# per-chip dense bf16 peak FLOP/s by jax device_kind substring
_PEAK_FLOPS_BF16 = (
    ("TPU v6", 918e12),
    ("TPU v5p", 459e12),
    ("TPU v5 lite", 197e12),
    ("TPU v5e", 197e12),
    ("TPU v4", 275e12),
    ("TPU v3", 123e12),
    ("TPU v2", 46e12),
)

# per-chip HBM bandwidth (GB/s) by the same substrings — the other half
# of the roofline monitor.profile classifies against
_PEAK_HBM_GBPS = (
    ("TPU v6", 1640.0),
    ("TPU v5p", 2765.0),
    ("TPU v5 lite", 819.0),
    ("TPU v5e", 819.0),
    ("TPU v4", 1228.0),
    ("TPU v3", 900.0),
    ("TPU v2", 700.0),
)

_ceilings_cache = {}


def ceilings_for_kind(kind):
    """The single cached (peak_flops, hbm_bytes_per_sec) table lookup
    for a device_kind string; either half is None when the kind is
    unknown. Env overrides live in the callers (peak_flops_for_device,
    profile.roofline_ceilings) so the cache never captures them."""
    kind = str(kind)
    hit = _ceilings_cache.get(kind)
    if hit is None:
        flops = next((p for tag, p in _PEAK_FLOPS_BF16 if tag in kind),
                     None)
        bw = next((b * 1e9 for tag, b in _PEAK_HBM_GBPS if tag in kind),
                  None)
        hit = _ceilings_cache[kind] = (flops, bw)
    return hit

# BERT-base has ~110M params; training flops/token ~= 6N (fwd 2N + bwd 4N)
BERT_BASE_PARAMS = 110e6
# ResNet-50 fwd @224 is ~4.1 GMACs = 8.2 GFLOPs; training ~= 3x fwd
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 8.2e9


def transformer_train_flops_per_token(n_params):
    """6N flops/token (Kaplan/PaLM accounting: fwd 2N + bwd 4N)."""
    return 6.0 * float(n_params)


def peak_flops_for_device(device=None):
    """Per-chip flops ceiling, or None when the device is unknown.
    PADDLE_TPU_FLOPS_CEILING (flops/s) overrides the table."""
    env = os.environ.get("PADDLE_TPU_FLOPS_CEILING")
    if env:
        return float(env)
    if device is None:
        import jax
        try:
            device = jax.local_devices()[0]
        except Exception:
            return None
    kind = str(getattr(device, "device_kind", ""))
    return ceilings_for_kind(kind)[0]


def peak_hbm_bandwidth_for_device(device=None):
    """Per-chip HBM bandwidth ceiling in bytes/s, or None when unknown.
    PADDLE_TPU_HBM_GBPS (GB/s) overrides the table."""
    env = os.environ.get("PADDLE_TPU_HBM_GBPS")
    if env:
        return float(env) * 1e9
    if device is None:
        import jax
        try:
            device = jax.local_devices()[0]
        except Exception:
            return None
    kind = str(getattr(device, "device_kind", ""))
    return ceilings_for_kind(kind)[1]


def mfu(flops_per_step, step_time_s, peak_flops=None):
    """Model-flops utilization, or None if the ceiling is unknown."""
    peak = peak_flops if peak_flops is not None else peak_flops_for_device()
    if not peak or not flops_per_step or not step_time_s:
        return None
    return flops_per_step / step_time_s / peak


#: the goodput ledger's loss categories: every second of a run that is
#: NOT compute, attributed from series the subsystems already emit
#: (counter values or histogram sums, all in seconds). ``compute`` is
#: the residual — wall time no category claims — so by construction
#: compute + losses reconcile to wall time exactly (the telemetry
#: smoke gate still checks the reconciliation end-to-end, which catches
#: a category double-counting overlapped time).
GOODPUT_CATEGORIES = (
    ("input_stall", ("prefetch.stall_seconds",)),
    ("comm_exposed", ("comm.exposed_wait_s_total",)),
    ("offload_wait", ("mem.offload.exposed_wait_s_total",)),
    ("compile", ("executor.compile_s", "jit.compile_s")),
    ("checkpoint", ("ckpt.save_s",)),
    ("restart_rollback", ("ckpt.restore_s",)),
)


def _series_seconds(reg, name):
    """Seconds held by one series right now: a counter's value, a
    histogram's sum, 0.0 when the series doesn't exist (the subsystem
    never ran)."""
    m = reg.get(name)
    if m is None:
        return 0.0
    if m.kind == "histogram":
        return float(m.sum)
    v = m.value
    return float(v) if v is not None else 0.0


class GoodputLedger:
    """Attributes a run's wall time across :data:`GOODPUT_CATEGORIES`.

    ``begin()`` snapshots every input series; ``finish()`` diffs them
    against the snapshot, subtracts the per-category losses from wall
    time, and reports ``goodput_fraction`` (= compute ÷ wall) plus the
    ranked time-loss table. StepMonitor runs one per monitored loop;
    it is also usable standalone around any timed region::

        ledger = monitor.GoodputLedger().begin()
        ... run ...
        print(ledger.finish()["goodput_fraction"])
    """

    def __init__(self, registry=None):
        if registry is None:
            from .. import monitor as _mon
            registry = _mon.registry()
        self._reg = registry
        self._t0 = None
        self._base = None

    def _read(self):
        return {name: _series_seconds(self._reg, name)
                for _, series in GOODPUT_CATEGORIES for name in series}

    def begin(self):
        self._t0 = time.perf_counter()
        self._base = self._read()
        return self

    def finish(self, wall_s=None):
        """The ledger dict: wall/compute seconds, goodput fraction, and
        ``lost`` — one row per category with attributed seconds, ranked
        worst-first (zero-loss categories included, at the tail: "this
        was measured and clean" reads differently from "not measured")."""
        if self._t0 is None:
            raise RuntimeError("GoodputLedger.finish() before begin()")
        wall = (time.perf_counter() - self._t0
                if wall_s is None else float(wall_s))
        cur = self._read()
        base = self._base
        lost = []
        for category, series in GOODPUT_CATEGORIES:
            seconds = sum(cur[n] - base[n] for n in series)
            seconds = max(0.0, seconds)
            lost.append({"category": category,
                         "seconds": round(seconds, 6),
                         "fraction": (round(seconds / wall, 4)
                                      if wall > 0 else None),
                         "series": list(series)})
        lost.sort(key=lambda row: -row["seconds"])
        total_lost = sum(row["seconds"] for row in lost)
        compute = max(0.0, wall - total_lost)
        out = {"wall_s": round(wall, 6),
               "compute_s": round(compute, 6),
               "lost_s": round(total_lost, 6),
               "goodput_fraction": (round(compute / wall, 4)
                                    if wall > 0 else None),
               "lost": lost}
        from . import emit, enabled, gauge
        if enabled():
            if out["goodput_fraction"] is not None:
                gauge("goodput.fraction").set(out["goodput_fraction"])
            for row in lost:
                gauge(f"goodput.lost_s.{row['category']}").set(
                    row["seconds"])
            emit(kind="goodput", **out)
        return out


_mem_stats_warned = False


def device_memory_stats():
    """bytes_in_use / peak_bytes_in_use / bytes_limit per local device.
    A backend that exposes nothing (CPU's ``memory_stats()`` returns
    None; some return dicts missing the HBM keys) contributes an EMPTY
    per-device dict instead of being dropped or raising — callers can
    still enumerate devices, and the degradation is warned exactly once
    per process."""
    global _mem_stats_warned
    import jax
    out = {}
    try:
        devices = jax.local_devices()
    except Exception:
        return out
    for d in devices:
        entry = {}
        try:
            stats = d.memory_stats()
            if stats:
                entry = {k: stats[k]
                         for k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit") if k in stats}
        except Exception:
            entry = {}
        if not entry and not _mem_stats_warned:
            _mem_stats_warned = True
            import warnings
            try:
                backend = jax.default_backend()
            except Exception:
                backend = "?"
            warnings.warn(
                f"device_memory_stats: backend '{backend}' platform "
                f"'{getattr(d, 'platform', '?')}' device {d} "
                f"({getattr(d, 'device_kind', '?')}) exposes no memory "
                "stats (expected on CPU backends); its entries will be "
                "empty dicts")
        out[str(d.id)] = entry
    return out


class StepMonitor:
    """Wraps a training loop:

        mon = monitor.StepMonitor(items_per_step=batch * seq,
                                  flops_per_step=6 * n_params * batch * seq,
                                  item="tokens", label="bert")
        for batch in loader:
            loss = train_step(batch)
            mon.step(loss=loss)
        mon.report()

    ``step()`` stamps the wall-clock since the previous step (call it
    AFTER the device sync your loop already does — an async dispatch
    makes any host timer lie), updates throughput/mfu gauges, and emits
    one JSONL ``step`` record per ``window`` steps (default every step).
    """

    def __init__(self, items_per_step=None, flops_per_step=None,
                 peak_flops=None, item="items", label="train", window=1,
                 memory_every=10, measured_flops_per_step=None,
                 xla_label=None, goodput=True):
        self.items_per_step = items_per_step
        self.flops_per_step = flops_per_step
        self.peak_flops = (peak_flops if peak_flops is not None
                           else peak_flops_for_device())
        self.item = item
        self.label = label
        self.window = max(1, int(window))
        self.memory_every = max(1, int(memory_every))
        # XLA-measured flops: explicit value, or pulled per step from
        # monitor.xla (xla_label=None means "most recently captured
        # executable" — right for a loop driving one compiled step)
        self.measured_flops_per_step = measured_flops_per_step
        self.xla_label = xla_label
        self.steps = 0
        self.total_time = 0.0
        self.records = []
        self._last = None
        self._divergence_warned = False
        self._mem_peaks = {}     # device id -> last seen peak watermark
        # the goodput ledger (category definitions above): armed at
        # start(), settled at report() — two registry reads per run
        self._goodput = GoodputLedger() if goodput else None
        self._goodput_report = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.report()

    def start(self):
        self._last = time.perf_counter()
        if self._goodput is not None:
            self._goodput.begin()
        return self

    def step(self, items=None, loss=None, **extra):
        """Mark one completed step; returns the record dict."""
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            # a loop that skipped start() still gets a ledger window
            # (first step marks its opening edge)
            if self._goodput is not None and self._goodput._t0 is None:
                self._goodput.begin()
            return None
        dt = now - self._last
        self._last = now
        self.steps += 1
        self.total_time += dt

        items = items if items is not None else self.items_per_step
        rate = (items / dt) if (items and dt > 0) else None
        step_mfu = mfu(self.flops_per_step, dt, self.peak_flops)

        from . import emit, enabled, gauge
        rec = {"kind": "step", "label": self.label, "step": self.steps,
               "step_time_s": round(dt, 6),
               f"{self.item}_per_sec": round(rate, 2) if rate else None,
               "items_per_sec": round(rate, 2) if rate else None,
               "mfu": round(step_mfu, 4) if step_mfu is not None else None}
        measured = self._measured_flops()
        mfu_measured = None
        if measured:
            mfu_measured = mfu(measured, dt, self.peak_flops)
            if mfu_measured is not None:
                rec["mfu_measured"] = round(mfu_measured, 4)
            if self.flops_per_step:
                ratio = measured / self.flops_per_step
                if abs(ratio - 1.0) > 0.2:
                    # the analytic convention and XLA's count disagree
                    # by >20% — one of them is lying; say so once
                    rec["flops_measured_ratio"] = round(ratio, 3)
                    if not self._divergence_warned:
                        self._divergence_warned = True
                        import warnings
                        warnings.warn(
                            f"StepMonitor[{self.label}]: XLA-measured "
                            f"flops/step ({measured:.3e}) diverges "
                            f"{(ratio - 1.0):+.0%} from the analytic "
                            f"figure ({self.flops_per_step:.3e}); the "
                            f"reported mfu uses the analytic number")
                        if enabled():
                            from . import counter
                            counter("xla.mfu_divergence").inc()
        if loss is not None:
            try:
                rec["loss"] = float(loss.numpy() if hasattr(loss, "numpy")
                                    else loss)
            except Exception:
                pass
        rec.update(extra)
        if self.steps % self.memory_every == 0 or self.steps == 1:
            mem = device_memory_stats()
            if any(mem.values()):  # all-empty dicts (CPU) stay out
                rec["device_memory"] = mem
                # the delta since the last sampled step is the signal
                # (a watermark that keeps climbing is a leak; a raw
                # snapshot alone can't show that)
                deltas = {}
                for did, stats in mem.items():
                    peak = stats.get("peak_bytes_in_use")
                    if peak is None:
                        continue
                    prev = self._mem_peaks.get(did)
                    if prev is not None:
                        deltas[did] = peak - prev
                    self._mem_peaks[did] = peak
                if deltas:
                    rec["device_memory_peak_delta"] = deltas
        self.records.append(rec)
        if enabled():
            gauge(f"step.{self.label}.time_s").set(dt)
            if rate:
                gauge(f"step.{self.label}.items_per_sec").set(rate)
            if step_mfu is not None:
                gauge(f"step.{self.label}.mfu").set(step_mfu)
            if mfu_measured is not None:
                gauge(f"step.{self.label}.mfu_measured").set(mfu_measured)
            if self.steps % self.window == 0:
                emit(**rec)
        return rec

    def _measured_flops(self):
        """XLA-counted flops/step: the explicit override, else the
        monitor.xla capture for xla_label (None -> newest)."""
        if self.measured_flops_per_step is not None:
            return self.measured_flops_per_step
        from . import xla as _xla
        return _xla.flops(self.xla_label)

    def _settle_goodput(self):
        """Finish the ledger exactly once (summary() and report() both
        want it; a second finish would re-window nothing)."""
        if (self._goodput is not None and self._goodput._t0 is not None
                and self._goodput_report is None):
            self._goodput_report = self._goodput.finish()
        return self._goodput_report

    # -- summary ------------------------------------------------------------
    def summary(self):
        if not self.steps:
            return {"label": self.label, "steps": 0}
        avg_dt = self.total_time / self.steps
        rate = (self.items_per_step / avg_dt
                if self.items_per_step and avg_dt > 0 else None)
        out = {
            "label": self.label, "steps": self.steps,
            "avg_step_time_s": round(avg_dt, 6),
            f"{self.item}_per_sec": round(rate, 2) if rate else None,
            "mfu": (round(mfu(self.flops_per_step, avg_dt,
                              self.peak_flops), 4)
                    if mfu(self.flops_per_step, avg_dt,
                           self.peak_flops) is not None else None),
            "peak_flops_ceiling": self.peak_flops,
        }
        measured = self._measured_flops()
        if measured:
            m = mfu(measured, avg_dt, self.peak_flops)
            if m is not None:
                out["mfu_measured"] = round(m, 4)
            out["flops_per_step_measured"] = measured
        g = self._settle_goodput()
        if g is not None:
            out["goodput"] = g
        return out

    def report(self, print_table=True):
        """Print the summary table and emit it (plus a full counters
        snapshot) to the JSONL sink; returns the summary dict."""
        s = self.summary()
        if print_table and self.steps:
            rate = s.get(f"{self.item}_per_sec")
            rows = [("steps", s["steps"]),
                    ("avg step time", f"{s['avg_step_time_s'] * 1e3:.2f} ms"),
                    (f"{self.item}/sec", f"{rate:,.1f}" if rate else "n/a"),
                    ("mfu", f"{s['mfu']:.1%}" if s["mfu"] is not None
                     else "n/a (no flops ceiling)")]
            if s.get("mfu_measured") is not None:
                rows.append(("mfu (xla-measured)",
                             f"{s['mfu_measured']:.1%}"))
            g = s.get("goodput")
            if g is not None and g.get("goodput_fraction") is not None:
                rows.append(("goodput", f"{g['goodput_fraction']:.1%}"))
                for row in g["lost"][:3]:
                    if row["seconds"] > 0:
                        rows.append((f"  lost: {row['category']}",
                                     f"{row['seconds'] * 1e3:.1f} ms "
                                     f"({row['fraction']:.1%})"))
            width = max(len(k) for k, _ in rows)
            print(f"[paddle_tpu.monitor] {self.label}")
            for k, v in rows:
                print(f"  {k:<{width}}  {v}")
        from . import emit, enabled, snapshot
        if enabled():
            emit(kind="step_summary", **s)
            emit(kind="counters", counters=snapshot())
        return s
