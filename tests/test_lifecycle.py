"""Zero-downtime serving lifecycle (ISSUE 18): process-level preempt
broadcast (subscribe/notify, stacked-handler LIFO uninstall,
multi-callback attach), graceful replica + fleet drain with zero-loss
migration, rolling live weight hot-swap (live tree and validated
sharded checkpoint sources, corrupt-publish quarantine, whole-roll
unwind on probe failure, version stamping into reqtrace records), the
supervisor's ``preempt_replica`` drain decision, and the /healthz +
snapshot surfaces. All CPU, all fast; the end-to-end story (bit-exact
streams through a drain, chaos soak) lives in
scripts/lifecycle_smoke.py and scripts/soak_chaos.py."""
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import inference, nn, serving
from paddle_tpu.resilience import faults, preempt
from paddle_tpu.serving import MultiDeviceEngine
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


def _mlp(seed=0):
    pt.seed(seed)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))


def _fleet(n=2, seed=0, **kw):
    import jax
    kw.setdefault("max_batch", 8)
    kw.setdefault("timeout_ms", 1.0)
    kw.setdefault("supervise", False)
    kw.setdefault("hedge_ms", 0)
    return MultiDeviceEngine(inference.Predictor(_mlp(seed)),
                             devices=jax.local_devices()[:n], **kw)


# ---------------------------------------------------------------------------
# preempt.py as a process-level lifecycle signal


def test_preempt_subscribe_notify_unsubscribe(mon):
    got = []
    cb1 = preempt.subscribe(lambda sig: got.append(("a", sig)))
    cb2 = preempt.subscribe(lambda sig: got.append(("b", sig)))
    try:
        preempt.notify(signal.SIGTERM)
        assert got == [("a", signal.SIGTERM), ("b", signal.SIGTERM)]
        assert mon.registry().value("resilience.preempt.notice", 0) == 1
        preempt.unsubscribe(cb1)
        preempt.unsubscribe(cb1)            # idempotent
        preempt.notify(None)
        assert got[-1] == ("b", None) and len(got) == 3
    finally:
        preempt.unsubscribe(cb1)
        preempt.unsubscribe(cb2)


def test_preempt_broken_subscriber_does_not_block_others(mon):
    got = []

    def boom(sig):
        raise RuntimeError("subscriber bug")

    cb1 = preempt.subscribe(boom)
    cb2 = preempt.subscribe(lambda sig: got.append(sig))
    try:
        with pytest.warns(UserWarning, match="subscriber"):
            preempt.notify(signal.SIGTERM)
        assert got == [signal.SIGTERM]
    finally:
        preempt.unsubscribe(cb1)
        preempt.unsubscribe(cb2)


def test_preempt_handler_request_broadcasts(mon):
    got = []
    cb = preempt.subscribe(lambda sig: got.append(sig))
    h = preempt.PreemptionHandler(signals=())
    try:
        h.request(signal.SIGTERM)
        assert got == [signal.SIGTERM] and h.triggered
        h.request(signal.SIGTERM)           # latched: one broadcast
        assert len(got) == 1
    finally:
        preempt.unsubscribe(cb)


def test_preempt_multi_attach_accumulates_save_fns():
    h = preempt.PreemptionHandler(signals=())
    calls = []

    def save_a(step):
        calls.append(("a", step))

    h.attach(save_fn=save_a)
    h.attach(save_fn=save_a)                # dedup: registered once
    h.attach(save_fn=lambda step: calls.append(("b", step)))
    h.notify_step(7)
    h.request(signal.SIGTERM)
    assert calls == [("a", 7), ("b", 7)]
    assert h.flushed_step == 7
    h.detach(save_fn=save_a)
    assert len(h._save_fns) == 1


def test_preempt_stacked_handlers_uninstall_lifo_safe():
    """Two handlers chain on the same signal; removing the FIRST one
    must splice it out of the chain instead of clobbering the second's
    registration."""
    h1 = preempt.PreemptionHandler(signals=(signal.SIGUSR2,))
    h1.install()
    h2 = preempt.PreemptionHandler(signals=(signal.SIGUSR2,))
    h2.install()
    try:
        h1.uninstall()                      # out of order: splice
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5.0
        while not h2.triggered and time.monotonic() < deadline:
            time.sleep(0.01)
        assert h2.triggered and not h1.triggered
    finally:
        h2.uninstall()


# ---------------------------------------------------------------------------
# graceful drain


def test_drain_replica_migrates_and_refuses_then_readmits():
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    x = np.random.RandomState(0).rand(2, 16).astype("f4")
    try:
        futs = [eng.submit(x) for _ in range(4)]
        moved = eng.drain_replica(0, reason="test")
        assert eng._replicas[0].draining
        assert eng._replicas[0].state == "draining"
        assert eng._replicas[0].breaker.state != "open"
        for f in futs:
            f.result(10)                    # zero loss through the drain
        before = eng._replicas[0].engine.stats()["submitted"]
        for _ in range(4):
            eng.run(x, timeout=10)
        assert eng._replicas[0].engine.stats()["submitted"] == before
        assert eng.stats()["draining_replicas"] == 1
        assert eng._lifecycle["event"] == "drain" or moved >= 0
        eng.undrain_replica(0, reason="test")
        assert not eng._replicas[0].draining
        eng.run(x, timeout=10)
    finally:
        eng.close(drain=False, timeout=2.0)


def test_drain_fleet_finishes_inflight_then_sheds():
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    x = np.random.RandomState(1).rand(2, 16).astype("f4")
    try:
        futs = [eng.submit(x) for _ in range(6)]
        eng.drain_fleet(reason="test")
        for f in futs:
            f.result(10)                    # in-flight completes
        assert eng.drain_wait(timeout_s=10.0)
        with pytest.raises(NoHealthyReplicaError):
            eng.submit(x)                   # post-drain: shed, not hang
        assert eng.health()["all_open"]     # fully drained reads as
    finally:                                # refusing traffic
        eng.close(drain=False, timeout=2.0)


def test_sigterm_broadcast_drains_fleet_and_close_unsubscribes():
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    h = preempt.PreemptionHandler(signals=())
    try:
        h.request(signal.SIGTERM)
        assert all(r.draining for r in eng._replicas)
        assert eng._lifecycle["event"] == "drain_fleet"
        assert "preempt" in eng._lifecycle["reason"]
    finally:
        eng.close(drain=False, timeout=2.0)
    # closed fleet is unsubscribed: a later notify must not touch it
    h2 = preempt.PreemptionHandler(signals=())
    h2.request(signal.SIGTERM)              # would explode on a dead ref


# ---------------------------------------------------------------------------
# live weight hot-swap


def test_swap_weights_live_tree_changes_outputs_zero_compiles():
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    x = np.random.RandomState(2).rand(2, 16).astype("f4")
    try:
        y0 = np.asarray(eng.run(x, timeout=10))
        execs = [len(r.predictor._compiled) for r in eng._replicas]
        v = eng.swap_weights(inference.Predictor(_mlp(seed=7)).state)
        assert v == 1 and eng.weights_version == 1
        assert [e.weights_version for e in eng.engines] == [1, 1]
        y1 = np.asarray(eng.run(x, timeout=10))
        assert not np.allclose(y0, y1)      # new weights actually serve
        assert [len(r.predictor._compiled)
                for r in eng._replicas] == execs
        assert eng.stats()["weights_version"] == 1
        assert eng.health()["weights_version"] == 1
        assert not any(r.draining for r in eng._replicas)
        assert eng._lifecycle["event"] == "swap"
    finally:
        eng.close(drain=False, timeout=2.0)


def test_swap_weights_checkpoint_source_validates_quorum():
    import jax
    from paddle_tpu.io import sharded
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    try:
        with tempfile.TemporaryDirectory() as d:
            ck = os.path.join(d, "pub-1.sharded")
            sharded.save_state(
                ck, jax.device_get(inference.Predictor(_mlp(5)).state))
            assert eng.swap_weights(ck) == 1
            assert eng.weights_version == 1
    finally:
        eng.close(drain=False, timeout=2.0)


def test_corrupt_publish_refused_quarantined_version_unchanged(mon):
    import jax
    from paddle_tpu.io import sharded
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    try:
        with tempfile.TemporaryDirectory() as d:
            ck = os.path.join(d, "pub-bad.sharded")
            sharded.save_state(
                ck, jax.device_get(inference.Predictor(_mlp(5)).state))
            faults.inject("publish_corrupt", times=1)
            with pytest.raises(ValueError, match="quorum"):
                eng.swap_weights(ck)
            assert os.path.isdir(ck + ".corrupt")   # quarantined
            assert not os.path.isdir(ck)
        assert eng.weights_version == 0
        assert [e.weights_version for e in eng.engines] == [0, 0]
        assert eng._lifecycle["event"] == "swap_refused"
        assert mon.registry().value(
            "serving.lifecycle.swap_refused", 0) >= 1
        x = np.random.RandomState(3).rand(2, 16).astype("f4")
        eng.run(x, timeout=10)              # fleet kept serving
    finally:
        eng.close(drain=False, timeout=2.0)


def test_swap_shape_mismatch_refused():
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    pt.seed(9)
    other = nn.Sequential(nn.Linear(16, 64), nn.ReLU(),
                          nn.Linear(64, 4))
    try:
        with pytest.raises(ValueError, match="shape"):
            eng.swap_weights(inference.Predictor(other).state)
        assert eng.weights_version == 0
    finally:
        eng.close(drain=False, timeout=2.0)


def test_swap_probe_failure_unwinds_the_whole_roll(monkeypatch):
    """Replica 0 swaps clean, replica 1's probe rejects the new
    weights: the roll must unwind replica 0 too — a fleet serving
    mixed weights would break bit-reproducibility."""
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    x = np.random.RandomState(4).rand(2, 16).astype("f4")
    try:
        y0 = np.asarray(eng.run(x, timeout=10))
        monkeypatch.setattr(eng.engines[1], "probe",
                            lambda timeout_s=None: False)
        with pytest.raises(RuntimeError, match="unwound"):
            eng.swap_weights(inference.Predictor(_mlp(seed=7)).state)
        assert eng.weights_version == 0
        assert [e.weights_version for e in eng.engines] == [0, 0]
        assert eng._lifecycle["event"] == "swap_failed"
        y1 = np.asarray(eng.run(x, timeout=10))
        np.testing.assert_allclose(y0, y1, rtol=1e-6)  # old weights on
    finally:                                           # EVERY replica
        eng.close(drain=False, timeout=2.0)


def test_decode_swap_stamps_weights_version_into_records(mon):
    import jax
    from paddle_tpu.serving import reqtrace
    reqtrace.reset()
    model = serving.demo_model(vocab=32, dim=16, heads=2, layers=2,
                               max_len=64, seed=1)
    eng = serving.MultiDecodeEngine(
        model, devices=jax.local_devices()[:2], slots=2, page=16,
        max_len=32, prompt_buckets=(16,), supervise=False)
    eng.warmup()
    eng.start()
    try:
        eng.submit([5, 3, 9], max_new_tokens=4, seed=1).result(30)
        swap_to = serving.demo_model(vocab=32, dim=16, heads=2,
                                     layers=2, max_len=64, seed=2)
        assert eng.swap_weights(swap_to.state) == 1
        eng.submit([5, 3, 9], max_new_tokens=4, seed=1).result(30)
        versions = [r.get("weights_version")
                    for r in reqtrace.recent()
                    if r.get("reqkind") == "decode"]
        assert 0 in versions and 1 in versions
    finally:
        eng.close(drain=False, timeout=2.0)


# ---------------------------------------------------------------------------
# supervisor: the preempt_replica fault becomes a drain decision


def test_supervisor_preempt_fault_drains_replica():
    eng = _fleet(3, supervise=True, supervisor_interval_s=0.05)
    eng.warmup([((16,), "float32")])
    x = np.random.RandomState(5).rand(2, 16).astype("f4")
    try:
        faults.inject("preempt_replica", replica=1, times=1)
        deadline = time.monotonic() + 10.0
        while (not eng._replicas[1].draining
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert eng._replicas[1].draining
        assert "drain" in [d["decision"]
                           for d in eng.supervisor.decisions]
        eng.run(x, timeout=10)              # peers keep serving
        h = eng.health()
        assert h["replicas"][1]["state"] == "draining"
        assert h["all_open"] is False
    finally:
        eng.close(drain=False, timeout=2.0)


# ---------------------------------------------------------------------------
# /healthz + snapshot surfaces


def test_healthz_draining_distinct_from_open_and_snapshot(mon):
    from paddle_tpu.monitor import export
    eng = _fleet(2)
    eng.warmup([((16,), "float32")])
    try:
        eng.drain_replica(0, reason="maintenance")
        status, payload = export.health_payload()
        rep = payload["serving"][0]["replicas"][0]
        assert rep["state"] == "draining"
        assert rep["draining"] is True
        assert rep["breaker"] != "open"
        assert status == 200                # a peer still admits
        snap = export.snapshot_payload()
        last = snap["serving"]["last_lifecycle"]
        assert last["event"] == "drain" and last["reason"] \
            == "maintenance"
    finally:
        eng.close(drain=False, timeout=2.0)


# ---------------------------------------------------------------------------
# the short chaos soak, end to end (~40s wall)


def test_soak_chaos_short_mode_holds_invariants(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "soak_chaos.py"),
         "--out-dir", str(tmp_path), "--duration", "15"],
        capture_output=True, text=True, timeout=500, env=env)
    assert proc.returncode == 0, (proc.stdout or "")[-800:] + \
        (proc.stderr or "")[-800:]
