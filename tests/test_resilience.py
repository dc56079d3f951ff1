"""Fault-tolerant training runtime (paddle_tpu.resilience): fault
injection, retry/backoff, NaN guard, watchdog, preemption-safe
checkpointing and auto-resume — every fault class driven end-to-end."""
import os
import pickle
import signal
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import hapi, io, monitor, nn, optimizer as popt
from paddle_tpu.io import CheckpointManager, TensorDataset
from paddle_tpu.resilience import (NaNGuard, NonFiniteError,
                                   PreemptionHandler, RetryExhausted,
                                   RetryPolicy, TransientError, Watchdog,
                                   faults, retry)
from paddle_tpu.resilience.faults import FaultSpec


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def jsonl(tmp_path):
    path = str(tmp_path / "events.jsonl")
    monitor.enable(path)
    yield path
    monitor.disable()


# -- retry/backoff ----------------------------------------------------------

def test_retry_recovers_transient():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientError("hiccup")
        return "ok"

    fast = RetryPolicy(max_attempts=3, base_delay=0.0)
    assert retry.retry_call(flaky, policy=fast) == "ok"
    assert len(calls) == 3


def test_retry_terminal_propagates_immediately():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("a bug, not flakiness")

    with pytest.raises(ValueError):
        retry.retry_call(broken, policy=RetryPolicy(max_attempts=5,
                                                    base_delay=0.0))
    assert len(calls) == 1


def test_retry_exhaustion_chains_cause():
    def always():
        raise TransientError("persistent")

    with pytest.raises(RetryExhausted) as ei:
        retry.retry_call(always, policy=RetryPolicy(max_attempts=2,
                                                    base_delay=0.0))
    assert isinstance(ei.value.__cause__, TransientError)


def test_retry_never_retries_keyboard_interrupt():
    calls = []

    def interrupted():
        calls.append(1)
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        retry.retry_call(interrupted,
                         policy=RetryPolicy(max_attempts=5, base_delay=0.0))
    assert len(calls) == 1


def test_backoff_schedule_deterministic():
    a = RetryPolicy(max_attempts=5, base_delay=0.1, seed=42)
    b = RetryPolicy(max_attempts=5, base_delay=0.1, seed=42)
    assert [a.delay(i) for i in range(5)] == [b.delay(i) for i in range(5)]


# -- fault injection --------------------------------------------------------

def test_fault_fires_at_exact_steps_with_budget():
    spec = faults.inject("loader", step=[2, 5], times=2)
    fired = [i for i in range(8) if faults.fire("loader", i)]
    assert fired == [2, 5]
    assert spec.fired == 2
    assert faults.fire("loader", 2) is None  # budget spent


def test_fault_probability_deterministic():
    a = FaultSpec("x", probability=0.5, times=None, seed=123)
    b = FaultSpec("x", probability=0.5, times=None, seed=123)
    assert [a.should_fire(i) for i in range(50)] == \
        [b.should_fire(i) for i in range(50)]


def test_faults_from_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULTS",
                       '[{"kind": "loader", "step": 3}]')
    specs = faults.load_env()
    assert len(specs) == 1 and specs[0].steps == frozenset((3,))
    with pytest.raises(TransientError):
        faults.maybe_raise("loader", step=3)


# -- DataLoader / prefetch producer recovery --------------------------------

def _range_dataset(n=16, d=4):
    rng = np.random.RandomState(0)
    return TensorDataset(rng.randn(n, d).astype("f4"),
                         np.arange(n, dtype="i4"))


def test_dataloader_retries_injected_loader_fault():
    spec = faults.inject("loader", step=0, times=2)
    dl = io.DataLoader(_range_dataset(), batch_size=4)
    batches = list(dl)
    assert len(batches) == 4  # both transient raises absorbed
    assert spec.fired == 2


def test_dataloader_retry_exhaustion_is_terminal():
    faults.inject("loader", step=0, times=10)
    dl = io.DataLoader(_range_dataset(), batch_size=4)
    with pytest.raises(RetryExhausted):
        list(dl)


def test_dataloader_retry_false_disables():
    faults.inject("loader", step=0, times=1)
    dl = io.DataLoader(_range_dataset(), batch_size=4, retry=False)
    with pytest.raises(TransientError):
        list(dl)


def test_prefetch_producer_survives_transient_fault(jsonl):
    from paddle_tpu.io.prefetch import prefetch_to_device
    spec = faults.inject("loader", step=1, times=2)
    src = [np.full((4,), i, "f4") for i in range(5)]
    out = list(prefetch_to_device(iter(src), size=2))
    assert [int(b[0]) for b in out] == [0, 1, 2, 3, 4]
    assert spec.fired == 2
    assert monitor.counter("resilience.retry").value >= 2


def test_prefetch_drops_after_budget_then_continues(jsonl):
    from paddle_tpu.io.prefetch import prefetch_to_device
    # enough budget to exhaust retries at slot 1: the slot is dropped
    # (counted) and the stream keeps going — no permanent stall
    faults.inject("loader", step=1, times=3)
    src = [np.full((4,), i, "f4") for i in range(5)]
    out = list(prefetch_to_device(iter(src), size=2))
    assert [int(b[0]) for b in out] == [0, 1, 2, 3, 4]
    assert monitor.counter("prefetch.drops").value == 1


def test_prefetch_terminal_error_propagates():
    from paddle_tpu.io.prefetch import prefetch_to_device

    def gen():
        yield np.zeros((4,), "f4")
        raise ValueError("terminal")

    with pytest.raises(ValueError):
        list(prefetch_to_device(gen(), size=2))


# -- checkpoint hardening ---------------------------------------------------

class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 2)

    def forward(self, x):
        return self.fc(x)


def test_checkpoint_save_is_atomic(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(3, model=_Net())
    files = sorted(os.listdir(tmp_path))
    assert files == ["ckpt-3.pkl", "ckpt-3.pkl.sha256"]  # no stray .tmp
    with open(tmp_path / "ckpt-3.pkl", "rb") as f:
        state = pickle.load(f)
    assert state["step"] == 3 and "model" in state


def test_truncated_checkpoint_never_wins(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    net = _Net()
    cm.save(1, model=net)
    cm.save(2, model=net)
    with open(cm._path(2), "wb") as f:
        f.write(b"\x80truncated-mid-write")  # simulated SIGKILL mid-save
    with pytest.warns(UserWarning, match="skipping"):
        assert cm.latest_step() == 1


def test_restore_quarantines_corrupt_and_falls_back(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    net = _Net()
    cm.save(1, model=net)
    w1 = net.fc.weight.numpy().copy()
    net.fc.weight.set_value(w1 + 1.0)
    cm.save(2, model=net)
    with open(cm._path(2), "ab") as f:
        f.write(b"garbage")  # checksum mismatch
    with pytest.warns(UserWarning, match="quarantining"):
        state = cm.restore(model=net)
    assert state["step"] == 1
    np.testing.assert_array_equal(net.fc.weight.numpy(), w1)
    assert os.path.exists(cm._path(2) + ".corrupt")
    assert not os.path.exists(cm._path(2))


def test_restore_explicit_corrupt_step_raises(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, model=_Net())
    with open(cm._path(1), "wb") as f:
        f.write(b"junk")
    with pytest.warns(UserWarning):
        with pytest.raises(ValueError):
            cm.restore(model=_Net(), step=1)


def test_checkpoint_without_sidecar_validates_by_unpickle(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    cm.save(4, model=_Net())
    os.remove(cm._path(4) + ".sha256")  # crash between data and sidecar
    assert cm.latest_step() == 4


# -- NaN guard --------------------------------------------------------------

def _sgd_step(net, x, y):
    o = popt.SGD(learning_rate=0.1, parameters=net.parameters())
    pred = net(pt.to_tensor(x))
    loss = (pred - pt.to_tensor(y)).square().mean()
    loss.backward()
    o.step()
    o.clear_grad()
    return loss


def test_guard_skip_leaves_params_unchanged():
    net = _Net()
    w0 = net.fc.weight.numpy().copy()
    x = np.full((4, 4), np.nan, "f4")
    y = np.zeros((4, 2), "f4")
    with NaNGuard("skip") as g:
        _sgd_step(net, x, y)
    np.testing.assert_array_equal(net.fc.weight.numpy(), w0)
    assert g.total_nonfinite == 1
    # a finite step afterwards still applies
    _sgd_step(net, np.ones((4, 4), "f4"), y)
    assert not np.array_equal(net.fc.weight.numpy(), w0)


def test_guard_raise_policy():
    net = _Net()
    x = np.full((4, 4), np.nan, "f4")
    with NaNGuard("raise"):
        with pytest.raises(NonFiniteError):
            _sgd_step(net, x, np.zeros((4, 2), "f4"))


def test_guard_max_consecutive_bounds_skip():
    net = _Net()
    x = np.full((4, 4), np.nan, "f4")
    y = np.zeros((4, 2), "f4")
    with NaNGuard("skip", max_consecutive=2) as g:
        _sgd_step(net, x, y)
        _sgd_step(net, x, y)
        with pytest.raises(NonFiniteError):
            _sgd_step(net, x, y)
    assert g.total_nonfinite == 3


def test_guard_skip_vs_rollback_parity(tmp_path):
    """Static-graph parity: a skipped NaN step leaves params exactly at
    their pre-step values; a rollback restores exactly the checkpoint."""
    from paddle_tpu import static

    static.reset_default_programs()
    pt.enable_static()
    try:
        net = nn.Linear(3, 1)
        x = static.data("x", [None, 3], "float32")
        y = static.data("y", [None, 1], "float32")
        loss = (net(x) - y).square().mean()
        popt.SGD(learning_rate=0.1).minimize(loss)
        exe = static.Executor()
        main = static.default_main_program()
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(4, 3).astype("f4"),
                "y": rng.randn(4, 1).astype("f4")}
        bad = dict(feed, x=np.full((4, 3), np.nan, "f4"))

        g = NaNGuard("skip")
        exe.run(feed=feed, fetch_list=[loss], nan_guard=g)
        before = {n: np.asarray(p.data) for n, p in main.param_vars.items()}
        exe.run(feed=bad, fetch_list=[loss], nan_guard=g)
        for n, v in before.items():
            np.testing.assert_array_equal(
                v, np.asarray(main.param_vars[n].data))
        assert g.total_nonfinite == 1

        cm = CheckpointManager(str(tmp_path))
        cm.save(7, program=main)
        ckpt = {n: np.asarray(p.data) for n, p in main.param_vars.items()}
        exe.run(feed=feed, fetch_list=[loss], nan_guard=g)  # params move on
        g2 = NaNGuard("rollback_to_last_ckpt", checkpoint_manager=cm)
        exe.run(feed=bad, fetch_list=[loss], nan_guard=g2)
        for n, v in ckpt.items():
            np.testing.assert_array_equal(
                v, np.asarray(main.param_vars[n].data))
        assert g2.total_nonfinite == 1
    finally:
        pt.disable_static()
        static.reset_default_programs()


# -- hapi fit end-to-end ----------------------------------------------------

def _toy():
    rng = np.random.RandomState(0)
    w = rng.randn(8, 3)
    x = rng.randn(64, 8).astype("f4")
    y = (x @ w).argmax(-1).astype("i4")
    return TensorDataset(x, y)


def _model():
    pt.seed(7)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 3))
    m = hapi.Model(net)
    m.prepare(optimizer=popt.SGD(learning_rate=0.05,
                                 parameters=m.parameters()),
              loss_function=hapi.CrossEntropy())
    return m


def test_fit_nan_skip_keeps_loss_finite(jsonl):
    spec = faults.inject("nan_grad", step=1)
    g = NaNGuard("skip")
    h = _model().fit(_toy(), batch_size=16, epochs=2, verbose=0,
                     shuffle=False, nan_guard=g)
    assert spec.fired == 1
    assert g.total_nonfinite == 1
    assert np.isfinite(h["loss"]).all()
    events = [r["event"] for r in monitor.read_jsonl(jsonl)
              if r.get("kind") == "resilience"]
    assert "nan_skip" in events and "fault_injected" in events


def test_fit_nan_rollback_restores_checkpoint(tmp_path):
    faults.inject("nan_grad", step=2)
    g = NaNGuard("rollback_to_last_ckpt")
    h = _model().fit(_toy(), batch_size=16, epochs=1, verbose=0,
                     shuffle=False, checkpoint=str(tmp_path),
                     save_steps=1, nan_guard=g)
    assert g.total_nonfinite == 1
    assert np.isfinite(h["loss"]).all()


def test_fit_preempt_fault_saves_and_resumes(tmp_path, jsonl):
    # 4 steps/epoch; preempt at global step 5 = epoch 1, batch 1
    faults.inject("preempt", step=5)
    cm = CheckpointManager(str(tmp_path))
    m = _model()
    m.fit(_toy(), batch_size=16, epochs=4, verbose=0, shuffle=False,
          checkpoint=cm)
    assert m.stop_training
    assert cm.latest_step() == 5
    w_saved = m.network[0].weight.numpy().copy()

    faults.clear()
    m2 = _model()
    h = m2.fit(_toy(), batch_size=16, epochs=4, verbose=0, shuffle=False,
               checkpoint=cm, auto_resume=True)
    assert np.isfinite(h["loss"]).all()
    records = [r for r in monitor.read_jsonl(jsonl)
               if r.get("kind") == "resilience"]
    events = {r["event"] for r in records}
    assert {"preempt_save", "auto_resume"} <= events
    resume = next(r for r in records if r["event"] == "auto_resume")
    assert resume["step"] == 6  # continues at the step AFTER the save
    # the resumed run picked up the preempted run's weights, then trained
    assert not np.array_equal(m2.network[0].weight.numpy(), w_saved)


def test_fit_real_sigterm_triggers_cooperative_save(tmp_path):
    cm = CheckpointManager(str(tmp_path))

    class _Preempt(hapi.Callback):
        def on_train_batch_end(self, step, logs=None):
            if step == 1:
                signal.raise_signal(signal.SIGTERM)

    m = _model()
    m.fit(_toy(), batch_size=16, epochs=2, verbose=0, shuffle=False,
          checkpoint=cm, callbacks=[_Preempt()])
    assert m.stop_training
    assert cm.latest_step() == 1  # saved at the signalled step's boundary
    # handler restored: a later SIGTERM must not be swallowed
    assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL


def test_executor_train_from_dataset_resumes(tmp_path):
    from paddle_tpu import static

    static.reset_default_programs()
    pt.enable_static()
    try:
        class _Ds:
            def __init__(self, n):
                self.n = n

            def _batches(self):
                rng = np.random.RandomState(0)
                for _ in range(self.n):
                    yield {"x": rng.randn(4, 3).astype("f4"),
                           "y": rng.randn(4, 1).astype("f4")}

        net = nn.Linear(3, 1)
        x = static.data("x", [None, 3], "float32")
        y = static.data("y", [None, 1], "float32")
        loss = (net(x) - y).square().mean()
        popt.SGD(learning_rate=0.05).minimize(loss)
        exe = static.Executor()
        faults.inject("preempt", step=2)
        exe.train_from_dataset(dataset=_Ds(6), fetch_list=[loss],
                               checkpoint=str(tmp_path))
        cm = CheckpointManager(str(tmp_path))
        assert cm.latest_step() == 2
        faults.clear()
        exe.train_from_dataset(dataset=_Ds(6), fetch_list=[loss],
                               checkpoint=cm, auto_resume=True,
                               nan_guard="skip")
    finally:
        pt.disable_static()
        static.reset_default_programs()


# -- watchdog ---------------------------------------------------------------

def test_watchdog_flags_slow_step(jsonl):
    """Counts, not clocks: the fast step ends at once under a deadline no
    scheduler's hiccup reaches; the hung one waits (bounded) until the
    watcher has flagged it and written its dump."""
    flagged = threading.Event()
    wd = Watchdog(min_deadline=60.0, poll=0.01,
                  on_stall=lambda *a: flagged.set()).start()
    try:
        with wd.step(0):
            pass  # fast: no stall
        assert wd.stall_count == 0
        wd.min_deadline = 0.05
        with wd.step(1):
            assert flagged.wait(60.0)  # hung until flagged
            assert wd.stall_count == 1
    finally:
        wd.stop()
    assert wd.stall_count == 1
    dumps = [r for r in monitor.read_jsonl(jsonl)
             if r.get("kind") == "watchdog_dump"]
    assert dumps and dumps[0]["step"] == 1 and "counters" in dumps[0]


def test_watchdog_deadline_tracks_p99():
    wd = Watchdog(min_deadline=0.01, factor=4.0, warmup=3)
    assert wd.deadline() == 0.01
    for _ in range(10):
        wd._durations.append(0.1)
    assert wd.deadline() == pytest.approx(0.4)


def test_fit_watchdog_on_injected_slow_step():
    faults.inject("slow_step", step=2, delay=0.5)
    wd = Watchdog(min_deadline=10.0, poll=0.02)
    # force a tiny deadline only for the injected stall: min_deadline
    # high enough that compile steps don't trip it would make the test
    # slow, so drive the deadline directly
    wd.min_deadline = 0.25
    _model().fit(_toy(), batch_size=16, epochs=1, verbose=0, shuffle=False,
                 watchdog=wd)
    assert wd.stall_count >= 1


# -- preemption handler unit ------------------------------------------------

def test_preemption_handler_chains_and_restores():
    seen = []
    prev = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        h = PreemptionHandler(signals=(signal.SIGTERM,)).install()
        signal.raise_signal(signal.SIGTERM)
        assert h.triggered
        assert seen == [signal.SIGTERM]  # previous handler still ran
        h.uninstall()
        signal.raise_signal(signal.SIGTERM)
        assert seen == [signal.SIGTERM, signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_preemption_request_without_signal():
    h = PreemptionHandler()
    assert not h.triggered
    h.request()
    assert h.triggered
