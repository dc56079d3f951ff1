"""Self-healing serving (ISSUE 14): circuit breakers, serving fault
kinds, the shed ladder's edge cases, stranded-future guarantees on
close(drain=False), retry-after plumbing, and routing around an open
breaker. All CPU, all fast; the end-to-end failover/hedge/overload
story lives in scripts/serving_chaos_smoke.py."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import inference, nn, serving
from paddle_tpu.resilience import faults, retry
from paddle_tpu.resilience.deadline import Deadline
from paddle_tpu.serving import (AdmissionController, CircuitBreaker,
                                DeadlineExpired, MultiDeviceEngine,
                                QueueFullError, ShedError)
from paddle_tpu.serving.batcher import DynamicBatcher, Request
from paddle_tpu.serving.multi import NoHealthyReplicaError


@pytest.fixture
def mon():
    from paddle_tpu import monitor
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


@pytest.fixture(autouse=True)
def _no_fault_leak():
    faults.clear()
    yield
    faults.clear()


def _mlp():
    pt.seed(0)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))


def _req(n=1, priority=1, deadline=None, sig="s"):
    return Request((np.zeros((n, 4), "f4"),), n, sig,
                   deadline=deadline, priority=priority)


# ---------------------------------------------------------------------------
# CircuitBreaker: the full lifecycle on a fake clock

def test_breaker_lifecycle_fake_clock():
    t = [100.0]
    b = CircuitBreaker("r0", failure_threshold=2, cooldown_s=5.0,
                       half_open_probes=1, clock=lambda: t[0])
    assert b.state == "closed" and b.allow()
    b.record_failure("boom")
    assert b.state == "closed"          # 1 of 2: not yet
    b.record_failure("boom")
    assert b.state == "open" and b.open_count == 1
    assert not b.allow()                # open: nothing routed
    t[0] = 104.9
    assert b.state == "open"            # cooldown not elapsed
    t[0] = 105.0
    assert b.state == "half_open"       # promoted on read
    assert b.allow()                    # consumes the one probe slot
    assert not b.allow()                # probe budget spent
    b.record_success()
    assert b.state == "closed" and b.allow()


def test_breaker_success_resets_consecutive_count():
    b = CircuitBreaker(failure_threshold=3)
    b.record_failure()
    b.record_failure()
    b.record_success()                  # streak broken
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"          # 2 < 3 since the reset
    b.record_failure()
    assert b.state == "open"


def test_breaker_half_open_failure_reopens():
    t = [0.0]
    b = CircuitBreaker(failure_threshold=1, cooldown_s=1.0,
                       clock=lambda: t[0])
    b.record_failure()
    t[0] = 1.0
    assert b.state == "half_open"
    b.record_failure("probe")
    assert b.state == "open" and b.open_count == 2
    t[0] = 1.5
    assert b.state == "open"            # cooldown restarted at reopen
    t[0] = 2.0
    assert b.state == "half_open"


def test_breaker_trip_records_gauge_and_counters(mon):
    t = [0.0]
    b = CircuitBreaker("rX", cooldown_s=1.0, clock=lambda: t[0])
    b.trip("hung")
    reg = mon.registry()
    assert reg.value("serving.breaker_state.rX") == 2
    assert reg.value("serving.breaker_open", 0) == 1
    t[0] = 1.0
    assert b.allow()                    # half-open probe
    b.record_success()
    assert reg.value("serving.breaker_state.rX") == 0
    assert reg.value("serving.breaker_closed", 0) == 1


def test_breaker_threshold_validation():
    with pytest.raises(ValueError):
        CircuitBreaker(failure_threshold=0)


# ---------------------------------------------------------------------------
# serving fault kinds: replica targeting + behaviours

def test_fault_replica_targeting():
    spec = faults.inject("replica_error", replica=1, times=1)
    faults.maybe_serving_fault(0)       # wrong replica: no fire
    assert spec.fired == 0
    with pytest.raises(retry.TransientError):
        faults.maybe_serving_fault(1)
    assert spec.fired == 1
    faults.maybe_serving_fault(1)       # times budget spent
    assert spec.fired == 1


def test_fault_replica_list_targeting():
    spec = faults.inject("replica_error", replica=[0, 2], times=None)
    with pytest.raises(retry.TransientError):
        faults.maybe_serving_fault(0)
    faults.maybe_serving_fault(1)
    with pytest.raises(retry.TransientError):
        faults.maybe_serving_fault(2)
    assert spec.fired == 2


def test_fault_replica_slow_sleeps_delay():
    faults.inject("replica_slow", replica=0, delay=0.05)
    t0 = time.monotonic()
    faults.maybe_serving_fault(0)
    assert time.monotonic() - t0 >= 0.04


def test_fault_replica_hang_honours_explicit_delay():
    # default hang is 30s (only supervision resolves it); an explicit
    # delay keeps unit tests fast
    faults.inject("replica_hang", delay=0.05)
    t0 = time.monotonic()
    faults.maybe_serving_fault(3)       # untargeted spec: any replica
    assert 0.04 <= time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# DynamicBatcher: no future is ever lost, not even mid-dispatch

def test_close_nodrain_resolves_dispatched_future():
    release = threading.Event()

    def process(group):
        release.wait(10.0)              # a "hung replica"
        for r in group:
            r.resolve_result(None)

    b = DynamicBatcher(process, AdmissionController(), max_batch=4,
                       timeout_ms=1.0)
    b.start()
    r = _req()
    b.submit(r)
    for _ in range(200):                # wait for dispatch
        if b.inflight_token() is not None:
            break
        time.sleep(0.005)
    assert b.inflight_token() is not None
    b.close(drain=False, timeout=0.2)   # bounded join, thread is stuck
    assert r.future.done()
    with pytest.raises(RuntimeError, match="still dispatched"):
        r.future.result()
    release.set()                       # let the stuck thread exit


def test_close_nodrain_leaves_disowned_inflight_alone():
    release = threading.Event()

    def process(group):
        release.wait(10.0)

    b = DynamicBatcher(process, AdmissionController(), max_batch=4,
                       timeout_ms=1.0)
    b.start()
    r = _req()
    b.submit(r)
    for _ in range(200):
        if b.inflight_token() is not None:
            break
        time.sleep(0.005)
    taken = b.disown_inflight()         # failover took ownership
    assert taken == [r]
    b.close(drain=False, timeout=0.2)
    assert not r.future.done()          # new owner resolves it, not close
    r.resolve_result("rescued")
    release.set()
    assert r.future.result() == "rescued"


# ---------------------------------------------------------------------------
# the shed ladder

def test_shed_ladder_priorities_and_retry_after():
    a = AdmissionController(max_queue_depth=100, slo_goodput_floor=None)
    # level 1 (depth >= 50): low shed, normal + high admitted
    with pytest.raises(ShedError) as ei:
        a.admit(_req(priority=2), depth=50)
    assert ei.value.level == 1 and ei.value.priority == 2
    assert ei.value.retry_after_ms == 25.0
    assert abs(ei.value.retry_after_s - 0.025) < 1e-9
    assert retry.is_transient(ei.value)
    a.admit(_req(priority=1), depth=50)
    a.admit(_req(priority=0), depth=50)
    # level 2 (depth >= 75): normal shed too, retry-after doubles
    with pytest.raises(ShedError) as ei:
        a.admit(_req(priority=1), depth=75)
    assert ei.value.level == 2 and ei.value.retry_after_ms == 50.0
    a.admit(_req(priority=0), depth=75)
    # level 3 (depth >= 90): even high shed, doubled again
    with pytest.raises(ShedError) as ei:
        a.admit(_req(priority=0), depth=90)
    assert ei.value.level == 3 and ei.value.retry_after_ms == 100.0
    # hard cap: QueueFullError, itself a retryable ShedError
    with pytest.raises(QueueFullError) as ei:
        a.admit(_req(priority=0), depth=100)
    assert isinstance(ei.value, ShedError)
    assert retry.is_transient(ei.value)
    assert ei.value.retry_after_ms == 100.0


def test_shed_disabled_admits_everyone_below_cap():
    a = AdmissionController(max_queue_depth=100, shed=False)
    a.admit(_req(priority=2), depth=99)
    with pytest.raises(QueueFullError):
        a.admit(_req(priority=0), depth=100)


def test_effective_max_batch_shrinks_with_the_ladder():
    a = AdmissionController(max_queue_depth=100, slo_goodput_floor=None)
    assert a.effective_max_batch(32, depth=0) == 32
    assert a.effective_max_batch(32, depth=50) == 32    # level 1: no cut
    assert a.effective_max_batch(32, depth=75) == 16    # level 2: halved
    assert a.effective_max_batch(32, depth=90) == 8     # level 3: quartered
    assert a.effective_max_batch(2, depth=90) == 1      # floor at 1


def test_equal_priority_fifo_preserved_under_shed():
    """A shrunken cap must shorten flushes, never reorder or skip-fill
    within a signature."""
    groups = []

    def process(group):
        groups.append(list(group))
        for r in group:
            r.resolve_result(None)

    a = AdmissionController(max_queue_depth=8, slo_goodput_floor=None)
    b = DynamicBatcher(process, a, max_batch=8, timeout_ms=1.0)
    reqs = [_req(n=2, priority=0) for _ in range(7)]
    for r in reqs:
        b.submit(r)                     # high priority: admitted to depth 7
    # depth 7/8 = 0.875 -> ladder level 2 -> first pick caps at 8//2 = 4
    b.start()
    for r in reqs:
        r.future.result(timeout=5)
    b.close()
    flat = [r for g in groups for r in g]
    assert flat == reqs                 # FIFO survived the shrunken cap
    assert len(groups[0]) == 2          # 2 reqs x 2 rows = the level-2 cap


def test_expired_never_counted_as_shed(mon):
    events = []
    a = AdmissionController(max_queue_depth=8)
    a.on_event = events.append
    b = DynamicBatcher(lambda g: [r.resolve_result(None) for r in g], a,
                       max_batch=8, timeout_ms=1.0)
    dead = _req(deadline=Deadline.after_ms(0))   # expired before dispatch
    b.submit(dead)
    b.start()
    with pytest.raises(DeadlineExpired):
        dead.future.result(timeout=5)
    b.close()
    assert events == ["expired"]
    reg = mon.registry()
    assert reg.value("serving.deadline_expired", 0) == 1
    assert reg.value("serving.shed", 0) == 0


def test_retry_call_honours_retry_after_floor():
    calls = []

    def flaky():
        calls.append(time.monotonic())
        if len(calls) == 1:
            raise ShedError("shed", retry_after_ms=80.0)
        return "ok"

    # policy backoff alone would wait ~1ms; the shed hint floors it
    policy = retry.RetryPolicy(max_attempts=2, base_delay=0.001,
                               max_delay=0.001, jitter=0.0)
    assert retry.retry_call(flaky, policy=policy) == "ok"
    assert calls[1] - calls[0] >= 0.07


# ---------------------------------------------------------------------------
# fleet routing: an open breaker takes a replica out of rotation

def test_multi_engine_routes_around_open_breaker():
    import jax
    eng = MultiDeviceEngine(
        inference.Predictor(_mlp()), devices=jax.local_devices()[:2],
        max_batch=8, timeout_ms=1.0, supervise=False, hedge_ms=0)
    try:
        eng._replicas[0].breaker.trip("test")
        x = np.random.RandomState(0).rand(2, 16).astype("f4")
        before = eng._replicas[0].engine.stats()["submitted"]
        for _ in range(6):
            eng.run(x, timeout=10)
        assert eng._replicas[0].engine.stats()["submitted"] == before
        assert eng._replicas[1].engine.stats()["submitted"] >= 6
        assert eng.stats()["breakers"][0] == "open"
        # second breaker opens too: no capacity, retryable, with a hint
        eng._replicas[1].breaker.trip("test")
        with pytest.raises(NoHealthyReplicaError) as ei:
            eng.submit(x)
        assert retry.is_transient(ei.value)
        assert ei.value.retry_after_ms > 0
        assert eng.health()["all_open"]
    finally:
        eng.close(drain=False, timeout=2.0)


def test_healthz_degrades_to_503_when_fleet_all_open(mon):
    import jax
    from paddle_tpu.monitor import export
    eng = MultiDeviceEngine(
        inference.Predictor(_mlp()), devices=jax.local_devices()[:2],
        max_batch=8, timeout_ms=1.0, supervise=False, hedge_ms=0)
    try:
        status, payload = export.health_payload()
        assert status == 200
        assert payload["serving"][0]["all_open"] is False
        for rep in eng._replicas:
            rep.breaker.trip("test")
        status, payload = export.health_payload()
        assert status == 503 and payload["status"] == "degraded"
        assert payload["serving"][0]["all_open"] is True
    finally:
        eng.close(drain=False, timeout=2.0)
