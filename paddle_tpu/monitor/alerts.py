"""paddle_tpu.monitor.alerts — SLO burn-rate alerting + anomaly detection.

The fleet plane (monitor/fleet.py) answers "what is the fleet's p99";
this module answers "should a human (or the supervisor) care". Two
mechanisms, both first-class event streams:

**Burn-rate rules** (:class:`BurnRateRule` + :class:`AlertManager`) —
the multi-window pattern from SRE practice: an SLO with target ``t``
(say 99% of TTFT samples under 500 ms) has an error budget of
``1 - t``; the *burn rate* over a window is the observed breach
fraction divided by that budget. A rule fires only when BOTH a fast
window (default 60 s — "it is happening right now") and a slow window
(default 1800 s — "it has been happening long enough to matter") burn
above the threshold; it resolves when the fast window is clean again.
That combination pages quickly on hard outages and stays quiet through
one-sample blips — a single bad scrape can never page. States walk
``pending`` (fast breaching, slow not yet) → ``firing`` → ``resolved``,
each transition emitted as a ``kind="alert"`` JSONL event and mirrored
in ``alerts.firing`` / ``alerts.fired`` metrics.

**Anomaly findings** (:class:`AnomalyDetector`) — the failure shapes
the chaos suites already induce, detected from per-source snapshot
deltas, each finding naming the offending source/series:

* *compile storm* — post-warmup growth of the compile counters
  (``executor.compile``/``executor.recompile``/``jit.compile``/
  ``jit.recompile``/``serving.decode.compiles``): a steady-state
  server minting executables is re-tracing every batch.
* *straggler* — one source's mean decode-step time z-scored against
  the *other* sources (leave-one-out, with a floored sigma — with a
  four-replica fleet a plain fleet-wide z-score mathematically cannot
  exceed 1.5, so it would never fire).
* *accept-rate collapse* — ``serving.decode.accept_rate`` falling
  under a floor after having been healthy (a speculative draft gone
  cold mid-run, not one that never warmed).
* *queue-depth divergence* — one source's queue depth a multiple of
  the fleet median: traffic is routing to a replica that can't drain.

Findings promote straight to ``firing`` through
:meth:`AlertManager.raise_finding` (anomalies are edge-detected, not
budget-burned) and resolve once the detector stops reporting them.
The currently-active findings are published module-globally
(:func:`active_findings`) so ``ServingSupervisor`` can cite the
anomaly behind a drain/scale decision — see serving/supervisor.py.

Nothing here polls on its own: an AlertManager/AnomalyDetector ticks
only when its owner (the telemetry smoke's aggregator loop, a test, an
operator script) calls it. Zero cost when unused.
"""
from __future__ import annotations

import threading
import time

__all__ = [
    "BurnRateRule", "Alert", "AlertManager", "AnomalyDetector",
    "active_findings", "set_active_findings", "clear_findings",
    "DEFAULT_RULES", "default_rules",
]

#: compile counters whose post-warmup growth constitutes a storm
COMPILE_SERIES = ("executor.compile", "executor.recompile",
                  "jit.compile", "jit.recompile",
                  "serving.decode.compiles")


# ---------------------------------------------------------------------------
# burn-rate rules

class BurnRateRule:
    """One SLO burn-rate rule over a scalar series.

    ``direction="above"`` means a sample breaches when it exceeds
    ``objective`` (latency-style); ``"below"`` when it falls under
    (throughput/goodput-style). ``budget`` is the allowed breach
    fraction (0.01 = a 99% SLO); ``burn_threshold`` is how many times
    budget both windows must burn before the rule fires."""

    def __init__(self, name, series, objective, direction="above",
                 budget=0.01, burn_threshold=2.0,
                 fast_window_s=60.0, slow_window_s=1800.0):
        if direction not in ("above", "below"):
            raise ValueError(f"direction {direction!r}")
        self.name = str(name)
        self.series = str(series)
        self.objective = float(objective)
        self.direction = direction
        self.budget = float(budget)
        self.burn_threshold = float(burn_threshold)
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)

    def breaches(self, value):
        v = float(value)
        return v > self.objective if self.direction == "above" \
            else v < self.objective


def default_rules(ttft_p99_objective_ms=500.0, tokens_floor=1.0,
                  goodput_target=0.9, **kw):
    """The stock rule catalogue over the serving SLO surface (see
    docs/observability.md for the burn-rate math)."""
    return [
        BurnRateRule("slo-ttft-p99", "slo.ttft_p99_ms",
                     ttft_p99_objective_ms, direction="above", **kw),
        BurnRateRule("slo-tokens-per-s", "slo.tokens_per_s",
                     tokens_floor, direction="below", **kw),
        BurnRateRule("slo-goodput", "slo.goodput",
                     goodput_target, direction="below", **kw),
    ]


DEFAULT_RULES = default_rules


class Alert:
    """Lifecycle record for one rule/finding: pending → firing →
    resolved, with timestamps for each edge (the detection-latency
    evidence ``scripts/telemetry_smoke.py`` reports)."""

    def __init__(self, name, series=None, source=None, context=None):
        self.name = name
        self.series = series
        self.source = source
        self.context = dict(context or {})
        self.state = "pending"
        self.pending_at = None
        self.fired_at = None
        self.resolved_at = None

    def as_dict(self):
        return {"name": self.name, "series": self.series,
                "source": self.source, "state": self.state,
                "pending_at": self.pending_at,
                "fired_at": self.fired_at,
                "resolved_at": self.resolved_at,
                "context": dict(self.context)}


class AlertManager:
    """Evaluates burn-rate rules against a value source and hosts
    finding-driven alerts. ``source`` is ``fn(series) -> value|None``
    — defaulting to the process registry, or wire it to a
    ``FleetAggregator.value`` for fleet-level alerting. Call
    :meth:`tick` once per evaluation interval."""

    def __init__(self, rules=None, source=None,
                 finding_resolve_after_s=5.0):
        self.rules = list(rules if rules is not None else [])
        self._source = source
        self.finding_resolve_after_s = float(finding_resolve_after_s)
        self._lock = threading.Lock()
        self._samples = {}      # rule.name -> deque[(t, breached)]
        self._alerts = {}       # alert key -> Alert
        self._finding_seen = {}  # alert key -> last raise_finding ts
        self.history = []       # every state transition, bounded

    # -- sampling ---------------------------------------------------------

    def _default_source(self, series):
        from .. import monitor as _mon
        v = _mon.registry().value(series, default=None)
        return v if isinstance(v, (int, float)) else None

    def feed(self, rule_name, value, now=None):
        """Inject one sample for a rule (tests / push-style feeds)."""
        now = time.time() if now is None else now
        rule = next((r for r in self.rules if r.name == rule_name), None)
        if rule is None:
            raise KeyError(rule_name)
        self._append(rule, value, now)

    def _append(self, rule, value, now):
        import collections
        with self._lock:
            dq = self._samples.get(rule.name)
            if dq is None:
                dq = self._samples[rule.name] = collections.deque()
            dq.append((now, bool(rule.breaches(value))))
            horizon = max(rule.fast_window_s, rule.slow_window_s)
            while dq and now - dq[0][0] > horizon:
                dq.popleft()

    def burn_rates(self, rule, now=None):
        """(fast_burn, slow_burn) — breach fraction per window divided
        by budget; None when the window holds no samples yet."""
        now = time.time() if now is None else now
        with self._lock:
            dq = list(self._samples.get(rule.name, ()))
        out = []
        for window in (rule.fast_window_s, rule.slow_window_s):
            sub = [b for t, b in dq if now - t <= window]
            if not sub:
                out.append(None)
                continue
            frac = sum(sub) / len(sub)
            out.append(frac / rule.budget if rule.budget > 0
                       else (float("inf") if frac else 0.0))
        return tuple(out)

    # -- evaluation -------------------------------------------------------

    def tick(self, now=None):
        """One evaluation pass: pull a sample per rule (when a source
        yields one), walk every alert's state machine, age out
        finding-driven alerts the detector stopped reporting. Returns
        the list of currently firing alerts."""
        now = time.time() if now is None else now
        src = self._source or self._default_source
        for rule in self.rules:
            try:
                v = src(rule.series)
            except Exception:
                v = None
            if v is not None:
                self._append(rule, v, now)
            self._evaluate_rule(rule, now)
        self._age_findings(now)
        self._publish(now)
        return self.firing()

    def _evaluate_rule(self, rule, now):
        fast, slow = self.burn_rates(rule, now)
        key = f"rule:{rule.name}"
        alert = self._alerts.get(key)
        fast_hot = fast is not None and fast >= rule.burn_threshold
        slow_hot = slow is not None and slow >= rule.burn_threshold
        ctx = {"fast_burn": fast, "slow_burn": slow,
               "objective": rule.objective,
               "direction": rule.direction,
               "burn_threshold": rule.burn_threshold}
        if alert is None or alert.state == "resolved":
            if fast_hot:
                alert = Alert(rule.name, series=rule.series, context=ctx)
                alert.pending_at = now
                self._alerts[key] = alert
                self._transition(alert, "pending", now)
                if slow_hot:
                    alert.state = "firing"
                    alert.fired_at = now
                    self._transition(alert, "firing", now)
            return
        alert.context.update(ctx)
        if alert.state == "pending":
            if not fast_hot:
                # a blip that never reached the slow window dissolves
                # without ever firing — that's the point of the pattern
                del self._alerts[key]
            elif slow_hot:
                alert.state = "firing"
                alert.fired_at = now
                self._transition(alert, "firing", now)
        elif alert.state == "firing" and not fast_hot:
            alert.state = "resolved"
            alert.resolved_at = now
            self._transition(alert, "resolved", now)

    # -- finding-driven alerts -------------------------------------------

    def raise_finding(self, finding, now=None):
        """Promote an anomaly finding straight to ``firing`` (one alert
        per finding key; re-raising refreshes it). Returns the Alert."""
        now = time.time() if now is None else now
        key = f"finding:{finding['name']}"
        self._finding_seen[key] = now
        alert = self._alerts.get(key)
        if alert is not None and alert.state != "resolved":
            alert.context.update(finding)
            return alert
        alert = Alert(finding["name"], series=finding.get("series"),
                      source=finding.get("source"), context=finding)
        alert.pending_at = alert.fired_at = now
        alert.state = "firing"
        self._alerts[key] = alert
        self._transition(alert, "firing", now)
        return alert

    def _age_findings(self, now):
        for key, alert in list(self._alerts.items()):
            if not key.startswith("finding:") or alert.state != "firing":
                continue
            last = self._finding_seen.get(key, 0.0)
            if now - last > self.finding_resolve_after_s:
                alert.state = "resolved"
                alert.resolved_at = now
                self._transition(alert, "resolved", now)

    # -- bookkeeping ------------------------------------------------------

    def _transition(self, alert, state, now):
        rec = dict(alert.as_dict(), state=state, ts=now)
        self.history.append(rec)
        del self.history[:-200]
        from .. import monitor as _mon
        if _mon.enabled():
            if state == "firing":
                _mon.counter("alerts.fired").inc()
            taken = {"kind", "name", "state", "series", "source", "ts"}
            _mon.emit(kind="alert", name=alert.name, state=state,
                      series=alert.series, source=alert.source,
                      **{k: v for k, v in alert.context.items()
                         if k not in taken
                         and isinstance(v, (int, float, str, bool,
                                            type(None)))})

    def _publish(self, now):
        from .. import monitor as _mon
        if _mon.enabled():
            _mon.gauge("alerts.firing").set(len(self.firing()))

    def alerts(self):
        return [a.as_dict() for a in self._alerts.values()]

    def firing(self):
        return [a.as_dict() for a in self._alerts.values()
                if a.state == "firing"]


# ---------------------------------------------------------------------------
# anomaly detection

def _hist_stats(snap, name):
    h = snap.get("histograms", {}).get(name)
    if not h:
        return None
    return float(h["sum"]), int(h["count"])


class AnomalyDetector:
    """Diffs per-source snapshots tick-over-tick and reports findings
    for the chaos-suite failure shapes. Feed it
    ``FleetAggregator.source_snapshots()`` (or hand-built equivalents)
    via :meth:`update`; it returns the current findings and publishes
    them to :func:`active_findings` (and, when given a ``manager``, as
    firing alerts)."""

    def __init__(self, manager=None, warmup_ticks=2,
                 compile_delta_threshold=3, compile_window_s=3.0,
                 z_threshold=3.0, sigma_floor_frac=0.10, min_sources=3,
                 accept_rate_floor=0.2, queue_ratio=4.0,
                 queue_min_depth=8):
        self.manager = manager
        self.warmup_ticks = int(warmup_ticks)
        self.compile_delta_threshold = int(compile_delta_threshold)
        self.compile_window_s = float(compile_window_s)
        self.z_threshold = float(z_threshold)
        self.sigma_floor_frac = float(sigma_floor_frac)
        self.min_sources = int(min_sources)
        self.accept_rate_floor = float(accept_rate_floor)
        self.queue_ratio = float(queue_ratio)
        self.queue_min_depth = int(queue_min_depth)
        self._ticks = {}        # source -> ticks seen
        self._compiles = {}     # source -> last total compile count
        self._compile_win = {}  # source -> deque[(ts, delta)]
        self._step_hist = {}    # source -> (sum, count) last seen
        self._accept_ok = set()  # sources that were ever healthy
        self.findings = []

    def update(self, snapshots, now=None):
        now = time.time() if now is None else now
        findings = []
        by_source = {}
        for snap in snapshots:
            src = str(snap.get("source"))
            by_source[src] = snap
            self._ticks[src] = self._ticks.get(src, 0) + 1
        findings += self._compile_storms(by_source, now)
        findings += self._stragglers(by_source, now)
        findings += self._accept_collapse(by_source, now)
        findings += self._queue_divergence(by_source, now)
        self.findings = findings
        set_active_findings(findings)
        if self.manager is not None:
            for f in findings:
                self.manager.raise_finding(f, now=now)
        return findings

    # -- the shapes -------------------------------------------------------

    def _compile_storms(self, by_source, now):
        # a real storm's compiles take wall time each, so one burst
        # lands spread across scrape ticks — the verdict sums deltas
        # over compile_window_s, not per tick (an instantaneous burst
        # still trips it: the current delta is in the window)
        import collections
        out = []
        for src, snap in by_source.items():
            counters = snap.get("counters", {})
            per_series = {s: int(counters.get(s, 0))
                          for s in COMPILE_SERIES}
            total = sum(per_series.values())
            prev = self._compiles.get(src)
            self._compiles[src] = total
            if prev is None or self._ticks.get(src, 0) <= self.warmup_ticks:
                continue  # warmup compiles are the plan, not a storm
            win = self._compile_win.setdefault(src, collections.deque())
            delta = total - prev
            if delta > 0:
                win.append((now, delta))
            while win and now - win[0][0] > self.compile_window_s:
                win.popleft()
            windowed = sum(d for _, d in win)
            if windowed >= self.compile_delta_threshold:
                series = max((s for s in COMPILE_SERIES),
                             key=lambda s: per_series[s])
                out.append({"name": f"compile_storm({src})",
                            "kind": "compile_storm", "source": src,
                            "series": series, "delta": windowed,
                            "window_s": self.compile_window_s,
                            "total": total, "ts": now})
        return out

    def _stragglers(self, by_source, now):
        # current-tick mean decode step time per source, from the
        # histogram's sum/count delta since the last tick (lifetime
        # means would dilute a straggler that turned slow mid-run)
        means = {}
        for src, snap in by_source.items():
            cur = _hist_stats(snap, "serving.decode.step_ms")
            if cur is None:
                continue
            prev = self._step_hist.get(src)
            self._step_hist[src] = cur
            if prev is None:
                d_sum, d_count = cur
            else:
                d_sum, d_count = cur[0] - prev[0], cur[1] - prev[1]
            if d_count > 0:
                means[src] = d_sum / d_count
        if len(means) < self.min_sources:
            return []
        out = []
        for src, mean in means.items():
            others = [m for s, m in means.items() if s != src]
            mu = sum(others) / len(others)
            var = sum((m - mu) ** 2 for m in others) / len(others)
            sigma = max(var ** 0.5, self.sigma_floor_frac * mu, 1e-9)
            z = (mean - mu) / sigma
            if z > self.z_threshold:
                out.append({"name": f"straggler({src})",
                            "kind": "straggler", "source": src,
                            "series": "serving.decode.step_ms",
                            "mean_ms": round(mean, 3),
                            "fleet_mean_ms": round(mu, 3),
                            "z": round(z, 2), "ts": now})
        return out

    def _accept_collapse(self, by_source, now):
        out = []
        for src, snap in by_source.items():
            rate = snap.get("gauges", {}).get(
                "serving.decode.accept_rate")
            if rate is None:
                continue
            if rate >= self.accept_rate_floor:
                self._accept_ok.add(src)
            elif src in self._accept_ok:
                out.append({"name": f"accept_collapse({src})",
                            "kind": "accept_collapse", "source": src,
                            "series": "serving.decode.accept_rate",
                            "accept_rate": round(float(rate), 4),
                            "floor": self.accept_rate_floor, "ts": now})
        return out

    def _queue_divergence(self, by_source, now):
        depths = {}
        for src, snap in by_source.items():
            d = snap.get("gauges", {}).get("serving.queue_depth")
            if d is not None:
                depths[src] = float(d)
        if len(depths) < self.min_sources:
            return []
        ordered = sorted(depths.values())
        median = ordered[len(ordered) // 2]
        out = []
        for src, depth in depths.items():
            if (depth >= self.queue_min_depth
                    and depth >= self.queue_ratio * (median + 1.0)):
                out.append({"name": f"queue_divergence({src})",
                            "kind": "queue_divergence", "source": src,
                            "series": "serving.queue_depth",
                            "depth": depth, "fleet_median": median,
                            "ts": now})
        return out


# ---------------------------------------------------------------------------
# the module-global finding board (what the supervisor reads)

_findings_lock = threading.Lock()
_active = {}     # finding name -> finding dict


def set_active_findings(findings):
    """Replace the board with the detector's current view (called by
    :meth:`AnomalyDetector.update` each tick)."""
    with _findings_lock:
        _active.clear()
        for f in findings:
            _active[f["name"]] = dict(f)


def active_findings():
    """The anomalies currently in force, for decision-context citation
    (ServingSupervisor attaches these to its verdicts)."""
    with _findings_lock:
        return list(_active.values())


def clear_findings():
    """Empty the board (test isolation)."""
    with _findings_lock:
        _active.clear()
