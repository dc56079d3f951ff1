"""paddle_tpu.serving (ISSUE 5): dynamic batching, SLA deadlines,
admission control, replica fan-out — plus the Predictor executable-cache
and compile_report satellites. All CPU, all fast."""
import threading
import time
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import inference, nn, serving
from paddle_tpu.io.bucketing import split_rows, unpad
from paddle_tpu.resilience import Deadline, TransientError
from paddle_tpu.serving import (DeadlineExpired, MultiDeviceEngine,
                                QueueFullError, ServingEngine)


@pytest.fixture
def mon():
    from paddle_tpu import monitor
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


def _mlp(out_dim=4):
    pt.seed(0)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                         nn.Linear(32, out_dim))


class _TwoHead(nn.Layer):
    def __init__(self):
        super().__init__()
        self.a = nn.Linear(16, 4)
        self.b = nn.Linear(16, 2)

    def forward(self, x):
        return self.a(x), self.b(x)


def _assert_bit_exact(got, ref, x, buckets):
    """The batcher's promise: a request's rows are BIT-identical to
    ``Predictor.run`` on the lone request at the padded shape its flush
    ran at — which engine bucket that was depends on who shared the
    flush — and within float32 rounding of the unpadded run (XLA picks
    kernels by shape: a 1-row batch takes its matrix-vector kernel,
    1 ulp off the batched one under jax 0.9.0)."""
    def same(a, b):
        if isinstance(a, list):
            return all(np.array_equal(u, v) for u, v in zip(a, b))
        return np.array_equal(a, b)

    assert any(same(got, ref.run(x, buckets=[b]))
               for b in buckets if b >= x.shape[0])
    want = ref.run(x)
    for g, w in zip(*((got, want) if isinstance(got, list)
                      else ([got], [want]))):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)


def _reqs(sizes, rng=None, dim=16):
    rng = rng or np.random.RandomState(0)
    return [rng.rand(n, dim).astype("f4") for n in sizes]


# ---------------------------------------------------------------------------
# bucketing helpers (new this PR)

def test_split_rows_and_unpad():
    a = np.arange(20, dtype="f4").reshape(10, 2)
    parts = split_rows(a, [1, 3, 4])      # trailing 2 pad rows dropped
    assert [p.shape[0] for p in parts] == [1, 3, 4]
    np.testing.assert_array_equal(parts[1], a[1:4])
    np.testing.assert_array_equal(unpad(a, 7), a[:7])
    assert unpad(a, 10) is a              # no-op at exact size
    assert unpad(np.float32(3.0), 2) == np.float32(3.0)
    with pytest.raises(ValueError):
        split_rows(a, [8, 8])


# ---------------------------------------------------------------------------
# resilience.Deadline

def test_deadline_semantics():
    t = [100.0]
    d = Deadline(0.5, clock=lambda: t[0])
    assert not d.expired() and abs(d.remaining() - 0.5) < 1e-9
    t[0] = 100.6
    assert d.expired() and d.remaining() < 0
    assert Deadline.after_ms(0, clock=lambda: t[0]).expired()
    assert "expired" in repr(d)


# ---------------------------------------------------------------------------
# Predictor satellites: cache keys, warmup, bucket-aware run, report

def test_predictor_cache_shared_across_input_kinds(mon):
    p = inference.Predictor(_mlp())
    x = np.random.RandomState(0).rand(3, 16).astype("f4")
    r1 = p.run(x)                          # numpy -> compile
    r2 = p.run(pt.to_tensor(x))            # Tensor -> cache hit
    r3 = p.run(jnp.asarray(x))             # device array -> cache hit
    assert len(p._compiled) == 1
    reg = mon.registry()
    assert reg.value("inference.compile", 0) == 1
    assert reg.value("inference.cache_hit", 0) == 2
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(r1, r3)


def test_predictor_float64_canonicalizes_to_same_entry(mon):
    p = inference.Predictor(_mlp())
    x = np.random.RandomState(0).rand(3, 16)          # float64
    p.run(x.astype("f4"))
    p.run(x)                                          # canonicalized f32
    assert len(p._compiled) == 1
    assert mon.registry().value("inference.compile", 0) == 1


def test_predictor_warmup_aot(mon):
    p = inference.Predictor(_mlp())
    keys = p.warmup([((8, 16), "float32")], [((4, 16), "float32")])
    assert len(keys) == 2 and len(p._compiled) == 2
    reg = mon.registry()
    assert reg.value("inference.aot_warmup", 0) == 2
    assert reg.value("inference.compile", 0) == 0
    p.run(np.zeros((8, 16), "f4"))        # warmed: no new compile
    assert reg.value("inference.compile", 0) == 0
    assert len(p._compiled) == 2


def test_predictor_bucket_aware_run(mon):
    p = inference.Predictor(_mlp())
    p.warmup([((8, 16), "float32")])
    x = np.random.RandomState(0).rand(5, 16).astype("f4")
    out = p.run(x, buckets=[8])
    assert out.shape == (5, 4)
    assert mon.registry().value("inference.compile", 0) == 0
    assert mon.registry().value("inference.bucket_pad", 0) == 1
    ref = inference.Predictor(_mlp()).run(np.asarray(
        np.concatenate([x, np.tile(x[-1:], (3, 1))]), "f4"))
    np.testing.assert_array_equal(out, ref[:5])


def test_compile_report_routes_through_xla(mon):
    p = inference.Predictor(_mlp())
    x = np.zeros((2, 16), "f4")
    rep = p.compile_report(x)
    assert rep.get("flops", 0) > 0
    # landed in monitor.xla under the predictor label
    assert any(lbl.startswith("predictor.") for lbl in mon.xla.labels())
    snap = mon.snapshot("xla.flops.predictor")
    assert snap


def test_compile_report_warns_once_on_empty(monkeypatch):
    import paddle_tpu.inference as inf
    p = inference.Predictor(_mlp())
    x = np.zeros((2, 16), "f4")
    monkeypatch.setattr(inf, "_COST_WARNED", False)
    from paddle_tpu.monitor import xla as mxla
    monkeypatch.setattr(mxla, "capture", lambda label, exe: {})
    with pytest.warns(RuntimeWarning, match="no cost"):
        assert p.compile_report(x) == {}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert p.compile_report(x) == {}   # second call: silent


def test_export_and_build_share_one_body():
    # the dedup satellite: both paths go through _infer_fn and agree
    from paddle_tpu.inference import _infer_fn
    m = _mlp()
    p = inference.Predictor(m)
    x = np.random.RandomState(0).rand(2, 16).astype("f4")
    from paddle_tpu.nn.layer import state_pytree
    st = state_pytree(m.eval())
    closed = _infer_fn(m, state=st)
    open_fn = _infer_fn(m)
    np.testing.assert_array_equal(np.asarray(closed(x)),
                                  np.asarray(open_fn(st, x)))
    np.testing.assert_array_equal(np.asarray(closed(x)), p.run(x))


# ---------------------------------------------------------------------------
# ServingEngine: coalescing, bit-exactness, warmup, flush policy

def test_ragged_requests_coalesce_bit_exact(mon):
    m = _mlp()
    eng = ServingEngine(inference.Predictor(m), buckets=[8, 32],
                        max_batch=32, timeout_ms=20.0)
    eng.warmup([((16,), "float32")])
    xs = _reqs([1, 3, 7, 13])
    futs = [eng.submit(x) for x in xs]
    outs = [f.result(5) for f in futs]
    ref = inference.Predictor(m)
    for x, o in zip(xs, outs):
        assert o.shape == (x.shape[0], 4)
        _assert_bit_exact(o, ref, x, [32])  # 24 rows pad to bucket 32
    st = eng.stats()
    assert st["batches"] == 1              # all four rode one flush
    assert st["coalesced_rows"] == 24 and st["padded_rows"] == 8
    eng.close()


def test_zero_compiles_after_warmup(mon):
    eng = ServingEngine(inference.Predictor(_mlp()), buckets=[8, 32],
                        max_batch=32, timeout_ms=2.0)
    warmed = eng.warmup([((16,), "float32")])
    assert warmed == 2                     # one per bucket
    reg = mon.registry()
    after_warmup = reg.value("serving.compiles", 0)
    assert after_warmup == warmed
    rng = np.random.RandomState(1)
    for sizes in ([2, 5], [8], [1, 1, 1], [13, 13], [32]):
        futs = [eng.submit(x) for x in _reqs(sizes, rng)]
        for f in futs:
            f.result(5)
    assert reg.value("serving.compiles", 0) == after_warmup
    assert eng.stats()["compiles"] == warmed
    eng.close()


def test_flush_on_max_batch_rows():
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=16,
                        timeout_ms=500.0)   # timeout too long to matter
    xs = _reqs([8, 8, 8, 8])
    t0 = time.monotonic()
    futs = [eng.submit(x) for x in xs]
    for f in futs:
        f.result(5)
    assert time.monotonic() - t0 < 2.0      # row cap, not timeout, flushed
    assert eng.stats()["batches"] == 2
    eng.close()


def test_flush_on_timeout_for_partial_batch():
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=32,
                        timeout_ms=30.0)
    f = eng.submit(_reqs([2])[0])
    out = f.result(5)                       # lone request still resolves
    assert out.shape == (2, 4)
    eng.close()


def test_multi_output_model_scatter(mon):
    m = _TwoHead().eval()
    eng = ServingEngine(inference.Predictor(m), max_batch=8,
                        timeout_ms=10.0)
    xs = _reqs([2, 3])
    futs = [eng.submit(x) for x in xs]
    ref = inference.Predictor(m)
    for x, f in zip(xs, futs):
        got = f.result(5)
        want = ref.run(x)
        assert isinstance(got, list) and len(got) == 2
        assert isinstance(want, list) and len(want) == 2
        _assert_bit_exact(got, ref, x, eng.buckets)
    eng.close()


def test_signature_groups_do_not_mix():
    m = _mlp()
    eng = ServingEngine(inference.Predictor(m), max_batch=32,
                        timeout_ms=10.0)
    a = np.random.RandomState(0).rand(3, 16).astype("f4")
    b = np.random.RandomState(1).rand(2, 16).astype("f8")  # -> f4 canon
    c = np.random.RandomState(2).rand(2, 16).astype("f4")
    fa, fb, fc = eng.submit(a), eng.submit(b), eng.submit(c)
    ref = inference.Predictor(m)
    np.testing.assert_array_equal(fa.result(5), ref.run(a))
    np.testing.assert_array_equal(fb.result(5),
                                  ref.run(b.astype("f4")))
    np.testing.assert_array_equal(fc.result(5), ref.run(c))
    eng.close()


def test_run_blocking_and_context_manager():
    with ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                       timeout_ms=5.0) as eng:
        out = eng.run(_reqs([3])[0], timeout=5)
        assert out.shape == (3, 4)
    with pytest.raises(RuntimeError):
        eng.submit(_reqs([1])[0])           # closed


def test_submit_validation():
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                        timeout_ms=5.0, start=False)
    with pytest.raises(ValueError):
        eng.submit()                        # no inputs
    with pytest.raises(ValueError):
        eng.submit(np.float32(1.0))         # no batch dim
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0, 16), "f4"))  # empty
    with pytest.raises(ValueError):
        eng.submit(np.zeros((9, 16), "f4"))  # > max_batch
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 16), "f4"),
                   np.zeros((3, 1), "f4"))  # inconsistent leading dims
    eng.close()


def test_close_drains_pending_requests():
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=32,
                        timeout_ms=5000.0, start=False)
    futs = [eng.submit(x) for x in _reqs([2, 3])]
    eng.start()
    eng.close(drain=True)                   # drain flushes immediately
    for f in futs:
        assert f.result(5).shape[1] == 4
    assert eng.stats()["completed"] == 2


def test_close_without_drain_fails_futures_not_lost():
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=32,
                        timeout_ms=5000.0, start=False)
    futs = [eng.submit(x) for x in _reqs([2, 3])]
    eng.close(drain=False)                  # no worker ever ran
    for f in futs:
        with pytest.raises(RuntimeError, match="closed"):
            f.result(1)


# ---------------------------------------------------------------------------
# admission control: backpressure + deadlines

def test_full_queue_fast_rejects(mon):
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                        timeout_ms=5.0, queue_depth=3, start=False)
    xs = _reqs([1, 1, 1, 1])
    futs = [eng.submit(x) for x in xs[:3]]
    t0 = time.perf_counter()
    with pytest.raises(QueueFullError):
        eng.submit(xs[3])
    assert time.perf_counter() - t0 < 0.05  # synchronous, no future made
    assert mon.registry().value("serving.rejected", 0) == 1
    assert eng.stats()["rejected"] == 1
    eng.start()
    for f in futs:
        f.result(5)
    eng.close()


def test_expired_deadline_never_occupies_batch_slot(mon):
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=32,
                        timeout_ms=5.0, start=False)
    dead = eng.submit(_reqs([7])[0], deadline_ms=0)   # born expired
    live = eng.submit(_reqs([3], np.random.RandomState(9))[0])
    time.sleep(0.01)
    eng.start()
    with pytest.raises(DeadlineExpired):
        dead.result(5)
    assert live.result(5).shape == (3, 4)
    st = eng.stats()
    # the expired request's 7 rows never reached a batch
    assert st["coalesced_rows"] == 3
    assert st["expired"] == 1 and st["completed"] == 1
    assert mon.registry().value("serving.deadline_expired", 0) == 1
    eng.close()


def test_default_deadline_stamped_by_engine():
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                        timeout_ms=5.0, deadline_ms=0.0, start=False)
    f = eng.submit(_reqs([1])[0])           # engine default: expires now
    time.sleep(0.005)
    eng.start()
    with pytest.raises(DeadlineExpired):
        f.result(5)
    eng.close()


# ---------------------------------------------------------------------------
# failure triage: retry vs isolation

def test_transient_batch_failure_retries(mon):
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                        timeout_ms=10.0, start=False)
    real = eng.predictor.run_device
    calls = {"n": 0}

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise TransientError("injected hiccup")
        return real(*a, **k)

    eng.predictor.run_device = flaky
    futs = [eng.submit(x) for x in _reqs([2, 3])]
    eng.start()
    for f in futs:
        assert f.result(5).shape[1] == 4
    assert eng.stats()["retries"] == 1
    assert mon.registry().value("serving.retries", 0) == 1
    eng.close()


def test_poisoned_request_fails_only_its_own_future(mon):
    m = _mlp()
    eng = ServingEngine(inference.Predictor(m), max_batch=32,
                        timeout_ms=10.0, start=False)
    real = eng.predictor.run_device

    def guarded(*arrays, **k):
        # host-side poison: any batch containing a NaN row fails the
        # whole executable call, the way a bad feed would
        if any(np.isnan(np.asarray(a)).any() for a in arrays):
            raise ValueError("poisoned feed")
        return real(*arrays, **k)

    eng.predictor.run_device = guarded
    rng = np.random.RandomState(3)
    good1, good2 = _reqs([2, 3], rng)
    poison = np.full((1, 16), np.nan, "f4")
    f1, fp, f2 = eng.submit(good1), eng.submit(poison), eng.submit(good2)
    eng.start()
    ref = inference.Predictor(m)
    np.testing.assert_array_equal(f1.result(5), ref.run(good1))
    np.testing.assert_array_equal(f2.result(5), ref.run(good2))
    with pytest.raises(ValueError, match="poisoned"):
        fp.result(5)
    st = eng.stats()
    assert st["failed"] == 1 and st["completed"] == 2
    reg = mon.registry()
    assert reg.value("serving.poisoned", 0) == 1
    assert reg.value("serving.isolated", 0) == 3
    eng.close()


# ---------------------------------------------------------------------------
# observability

def test_serving_metric_series(mon):
    eng = ServingEngine(inference.Predictor(_mlp()), buckets=[8],
                        max_batch=8, timeout_ms=10.0)
    eng.warmup([((16,), "float32")])
    futs = [eng.submit(x) for x in _reqs([1, 2, 3])]
    for f in futs:
        f.result(5)
    eng.close()
    reg = mon.registry()
    assert reg.value("serving.requests", 0) == 3
    assert reg.value("serving.rows", 0) == 6
    assert reg.value("serving.batches", 0) >= 1
    fill = reg.value("serving.batch_fill")
    assert fill and fill["count"] >= 1
    assert fill["sum"] / fill["count"] > 1     # requests coalesced
    occ = reg.value("serving.batch_occupancy")
    assert occ and 0 < occ["sum"] / occ["count"] <= 1
    lat = reg.value("serving.latency_ms")
    assert lat and lat["count"] == 3
    assert reg.value("serving.qps") > 0


def test_serving_spans_in_trace(mon):
    from paddle_tpu.monitor import trace
    trace.enable()
    try:
        eng = ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                            timeout_ms=5.0)
        eng.warmup([((16,), "float32")])
        eng.run(_reqs([3])[0], timeout=5)
        eng.close()
        names = {e[1] for e in trace.events()}
        for want in ("serving.enqueue", "serving.batch_assemble",
                     "serving.execute", "serving.scatter",
                     "serving.warmup"):
            assert any(n.startswith(want) for n in names), want
    finally:
        trace.disable()
        trace.clear()


def test_metrics_noop_when_monitor_disabled():
    from paddle_tpu import monitor
    assert not monitor.enabled()
    eng = ServingEngine(inference.Predictor(_mlp()), max_batch=8,
                        timeout_ms=5.0)
    eng.run(_reqs([2])[0], timeout=5)       # must not touch the registry
    eng.close()
    assert monitor.registry().value("serving.requests", 0) == 0


# ---------------------------------------------------------------------------
# concurrency + multi-device fan-out

def test_concurrent_clients_all_resolve():
    m = _mlp()
    eng = ServingEngine(inference.Predictor(m), buckets=[8, 32],
                        max_batch=32, timeout_ms=2.0, queue_depth=512)
    eng.warmup([((16,), "float32")])
    ref = inference.Predictor(m)
    errors = []

    def client(k):
        rng = np.random.RandomState(k)
        for i in range(10):
            x = rng.rand(1 + (k + i) % 13, 16).astype("f4")
            try:
                _assert_bit_exact(eng.run(x, timeout=10), ref, x,
                                  eng.buckets)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = eng.stats()
    assert st["completed"] == 60 and st["submitted"] == 60
    assert st["batches"] <= 60              # some coalescing happened
    eng.close()


def test_replicate_places_state_per_device():
    p = inference.Predictor(_mlp())
    devs = jax.local_devices()[:2]
    reps = serving.replicate(p, devs)
    assert len(reps) == 2
    for r, d in zip(reps, devs):
        assert r.device == d
        leaf = next(iter(r.state.values()))
        assert list(leaf.devices()) == [d]
        assert r._compiled == {} and r.model is p.model


def test_multi_device_round_robin(mon):
    m = _mlp()
    me = MultiDeviceEngine(inference.Predictor(m),
                           devices=jax.local_devices()[:2],
                           max_batch=8, timeout_ms=5.0)
    me.warmup([((16,), "float32")])
    ref = inference.Predictor(m)
    xs = _reqs([2, 3, 1, 4], np.random.RandomState(7))
    futs = [me.submit(x) for x in xs]
    for x, f in zip(xs, futs):
        _assert_bit_exact(f.result(5), ref, x, [1, 2, 4, 8])
    st = me.stats()
    assert st["completed"] == 4 and len(st["replicas"]) == 2
    # round robin: both replicas saw traffic
    assert all(r["submitted"] == 2 for r in st["replicas"])
    me.close()
