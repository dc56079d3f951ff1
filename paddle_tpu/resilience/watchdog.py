"""paddle_tpu.resilience.watchdog — hung-step detection.

A deadlocked collective, a stuck host callback, or an input pipeline
hang all look the same from outside: the step just never ends. The
watchdog is a daemon thread that knows when each step started and flags
any step exceeding a rolling deadline — ``factor`` × the p99 of recent
step times once enough history exists, never below ``min_deadline``.
On a stall it emits ``resilience.watchdog_stall`` plus a one-shot
monitor state dump (every counter/gauge, so the post-mortem shows what
the run was doing when it stuck) and calls the optional ``on_stall``
hook. It never kills the step itself — detection and evidence, not
preemption.

Usage::

    wd = Watchdog(min_deadline=30.0).start()
    for i, batch in enumerate(loader):
        with wd.step(i):
            train_step(batch)
    wd.stop()

``hapi.Model.fit(watchdog=True)`` wires this around its train loop.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

from .. import monitor as _monitor
from ._common import record

# every started watchdog, for the /healthz endpoint — weak so an
# abandoned watchdog never outlives its owner through this set
_ACTIVE = weakref.WeakSet()


def health():
    """Health snapshots of every running watchdog (the monitor export
    server's /healthz feed): a list of :meth:`Watchdog.health` dicts.
    Empty list = no watchdog armed (liveness only, no stall signal)."""
    return [wd.health() for wd in list(_ACTIVE)]


class Watchdog:
    """See module docstring.

    min_deadline — floor (and the deadline until ``warmup`` steps of
    history exist); factor × rolling p99 takes over after warmup.
    """

    def __init__(self, min_deadline=30.0, factor=4.0, warmup=5,
                 poll=0.05, history=256, on_stall=None):
        self.min_deadline = float(min_deadline)
        self.factor = float(factor)
        self.warmup = int(warmup)
        self.poll = float(poll)
        self.on_stall = on_stall
        self._durations = collections.deque(maxlen=history)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        self._current = None      # (step_id, t0) while a step runs
        self._flagged = None      # step_id already reported this pass
        self.stall_count = 0

    # -- deadline -----------------------------------------------------------

    def deadline(self):
        with self._lock:
            if len(self._durations) < self.warmup:
                return self.min_deadline
            ordered = sorted(self._durations)
            p99 = ordered[min(len(ordered) - 1,
                              int(0.99 * (len(ordered) - 1) + 0.999))]
        return max(self.min_deadline, self.factor * p99)

    # -- step bracketing ------------------------------------------------------

    class _StepScope:
        def __init__(self, wd, step_id):
            self._wd = wd
            self._step_id = step_id

        def __enter__(self):
            wd = self._wd
            with wd._lock:
                wd._current = (self._step_id, time.monotonic())
            return self

        def __exit__(self, *exc):
            wd = self._wd
            with wd._lock:
                cur = wd._current
                wd._current = None
                if cur is not None:
                    wd._durations.append(time.monotonic() - cur[1])
            return False

    def step(self, step_id=None):
        """Context manager bracketing one training step."""
        return Watchdog._StepScope(self, step_id)

    # -- health introspection -------------------------------------------------

    def health(self):
        """Point-in-time health: whether a step is in flight, how long
        it has run vs the current deadline, and the cumulative stall
        count. ``stalled`` is live (the in-flight step is past deadline
        RIGHT NOW), independent of whether the watcher thread has
        flagged it yet — /healthz must flip the moment the SLA is
        blown, not a poll interval later."""
        with self._lock:
            cur = self._current
        deadline = self.deadline()
        out = {"running": self._thread is not None
               and self._thread.is_alive(),
               "stall_count": self.stall_count,
               "deadline_s": deadline,
               "in_step": cur is not None,
               "stalled": False}
        if cur is not None:
            step_id, t0 = cur
            elapsed = time.monotonic() - t0
            out.update(step=step_id, elapsed_s=elapsed,
                       stalled=elapsed > deadline)
        return out

    # -- the watcher thread ---------------------------------------------------

    def start(self):
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._watch, name="paddle_tpu-watchdog", daemon=True)
            self._thread.start()
        _ACTIVE.add(self)
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        _ACTIVE.discard(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _watch(self):
        while not self._stop.wait(self.poll):
            with self._lock:
                cur = self._current
            if cur is None:
                continue
            step_id, t0 = cur
            if self._flagged == (step_id, t0):
                continue  # one report per hung step
            elapsed = time.monotonic() - t0
            deadline = self.deadline()
            if elapsed > deadline:
                self._flagged = (step_id, t0)
                self.stall_count += 1
                record("watchdog_stall", step=step_id, elapsed=elapsed,
                       deadline=deadline)
                if _monitor.enabled():
                    # the post-mortem payload: everything the run was
                    # doing — counters inline, plus a flight-recorder
                    # directory (spans + counters + active HLO) whose
                    # path rides in the same JSONL record
                    flight = _monitor.trace.flight_record(
                        "watchdog_stall", step=step_id,
                        extra={"elapsed": elapsed, "deadline": deadline})
                    _monitor.emit(kind="watchdog_dump", step=step_id,
                                  elapsed=elapsed, deadline=deadline,
                                  flight_dir=flight,
                                  counters=_monitor.snapshot())
                if self.on_stall is not None:
                    try:
                        self.on_stall(step_id, elapsed, deadline)
                    except Exception:
                        pass  # a broken hook must not kill the watcher
