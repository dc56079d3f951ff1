"""Live telemetry plane: OpenMetrics exporter endpoints, the periodic
sampler, /healthz stall semantics, teardown hygiene, serving SLO
rollups + qps decay, and device-memory hardening."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor import export, sampler
from paddle_tpu.monitor.registry import Registry
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.watchdog import Watchdog
from paddle_tpu.serving import metrics as smetrics


@pytest.fixture(autouse=True)
def _clean_plane():
    """Exporter/sampler/windows are process-global: every test starts
    and ends with the whole plane down and empty."""
    monitor.disable(flush_counters=False)
    monitor.reset()
    faults.clear()
    smetrics.reset_windows()
    yield
    faults.clear()
    smetrics.reset_windows()
    monitor.disable(flush_counters=False)
    monitor.reset()


def _serve():
    srv = monitor.serve(port=0, sampler=False)
    assert srv.port > 0
    return srv


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode("utf-8"), \
            r.headers.get("Content-Type", "")


def _parse_openmetrics(text):
    """{series_name: value} for every sample line; histogram bucket
    lines keep their le label in the key."""
    assert text.rstrip().endswith("# EOF"), "missing OpenMetrics EOF"
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        assert key not in out, f"duplicate sample {key}"
        out[key] = float(val)
    return out


# ---------------------------------------------------------------------------
# renderer semantics

def test_counter_and_gauge_render():
    reg = Registry()
    reg.counter("executor.run").inc(7)
    reg.gauge("step.toy.mfu").set(0.375)
    reg.gauge("never.set")  # None gauge must be skipped, not rendered
    text = export.render_openmetrics(reg)
    samples = _parse_openmetrics(text)
    assert samples["executor_run_total"] == 7
    assert samples["step_toy_mfu"] == 0.375
    assert not any(k.startswith("never_set") for k in samples)
    assert "# TYPE executor_run counter" in text
    assert "# TYPE step_toy_mfu gauge" in text


def test_histogram_openmetrics_bucket_semantics():
    reg = Registry()
    h = reg.histogram("lat", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0, 1e6):  # last lands past all bounds
        h.observe(v)
    samples = _parse_openmetrics(export.render_openmetrics(reg))
    # cumulative le ladder: 2 <=1, 3 <=10, 4 <=100, +Inf == count
    assert samples['lat_bucket{le="1"}'] == 2
    assert samples['lat_bucket{le="10"}'] == 3
    assert samples['lat_bucket{le="100"}'] == 4
    assert samples['lat_bucket{le="+Inf"}'] == 5
    assert samples["lat_count"] == 5
    assert samples["lat_sum"] == pytest.approx(0.5 + 0.7 + 5 + 50 + 1e6)


def test_name_sanitization_and_collision():
    reg = Registry()
    reg.counter("a.b-c").inc(1)
    reg.counter("a.b_c").inc(99)  # sanitizes to the same name
    samples = _parse_openmetrics(export.render_openmetrics(reg))
    # first (sorted) wins; the scrape stays parseable either way
    assert samples["a_b_c_total"] in (1, 99)
    assert sum(1 for k in samples if k == "a_b_c_total") == 1


# ---------------------------------------------------------------------------
# endpoints

def test_metrics_endpoint_live_and_content_type():
    monitor.enable()
    monitor.counter("executor.run").inc(3)
    srv = _serve()
    status, text, ctype = _get(srv.port, "/metrics")
    assert status == 200
    assert "openmetrics-text" in ctype
    assert _parse_openmetrics(text)["executor_run_total"] == 3
    # a scrape is live, not a snapshot: bump and re-scrape
    monitor.counter("executor.run").inc(2)
    _, text2, _ = _get(srv.port, "/metrics")
    assert _parse_openmetrics(text2)["executor_run_total"] == 5


def test_unknown_path_404():
    srv = _serve()
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(srv.port, "/nope")
    assert e.value.code == 404


def test_snapshot_endpoint():
    monitor.enable()
    monitor.counter("executor.run").inc(11)
    srv = _serve()
    status, body, ctype = _get(srv.port, "/snapshot")
    assert status == 200 and "json" in ctype
    snap = json.loads(body)
    assert snap["monitor_enabled"] is True
    assert snap["counters"]["executor.run"] == 11
    assert "flight_dir" in snap


def test_scrape_under_load_parses_and_is_monotonic():
    """8 writer threads hammer counters + a histogram while the main
    thread scrapes; every scrape must parse and every counter must be
    monotonic scrape-over-scrape."""
    monitor.enable()
    srv = _serve()
    stop = threading.Event()

    def writer(k):
        while not stop.is_set():
            monitor.counter(f"load.c{k % 4}").inc()
            monitor.histogram("load.h").observe(float(k))

    threads = [threading.Thread(target=writer, args=(k,), daemon=True)
               for k in range(8)]
    for t in threads:
        t.start()
    try:
        prev = {}
        for _ in range(25):
            _, text, _ = _get(srv.port, "/metrics")
            samples = _parse_openmetrics(text)  # asserts parseability
            for key, val in samples.items():
                if key.endswith("_total") or key.endswith("_count") \
                        or "_bucket{" in key:
                    assert val >= prev.get(key, 0), key
                    prev[key] = val
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
    assert prev.get("load_c0_total", 0) > 0
    assert prev.get("load_h_count", 0) > 0


def test_healthz_flips_on_injected_slow_step_stall():
    """A resilience.faults slow_step injection that overruns the
    watchdog deadline must flip /healthz to 503/stalled while the step
    is stuck, and back to 200/ok once it completes."""
    monitor.enable()
    srv = _serve()
    wd = Watchdog(min_deadline=0.2, poll=0.02)
    wd.start()
    faults.inject("slow_step", step=0, delay=1.2)
    try:
        status0, body0, _ = _get(srv.port, "/healthz")
        assert status0 == 200 and json.loads(body0)["status"] == "ok"

        def stuck_step():
            with wd.step(0):
                faults.maybe_sleep("slow_step", 0)

        t = threading.Thread(target=stuck_step, daemon=True)
        t.start()
        saw_stalled = None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            try:
                _get(srv.port, "/healthz")
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    saw_stalled = json.loads(e.read().decode())
                    break
            time.sleep(0.05)
        assert saw_stalled is not None, "healthz never went 503"
        assert saw_stalled["status"] == "stalled"
        stalled_wd = [w for w in saw_stalled["watchdogs"]
                      if w.get("stalled")]
        assert stalled_wd and stalled_wd[0]["elapsed_s"] > 0.2
        t.join(timeout=5)
        status1, body1, _ = _get(srv.port, "/healthz")
        assert status1 == 200 and json.loads(body1)["status"] == "ok"
    finally:
        wd.stop()


def test_healthz_reports_nan_guard_trips():
    from paddle_tpu.resilience.guard import total_trips
    monitor.enable()
    srv = _serve()
    before = total_trips()
    _, body, _ = _get(srv.port, "/healthz")
    assert json.loads(body)["nan_guard"]["trips"] == before


# ---------------------------------------------------------------------------
# lifecycle: serve/disable, env autostart, zero-cost-off

def test_disable_tears_down_server_and_sampler():
    monitor.enable()
    srv = monitor.serve(port=0)  # sampler=True path
    port = srv.port
    assert export.active() is not None and sampler.active() is not None
    _get(port, "/healthz")
    monitor.disable()
    assert export.active() is None and sampler.active() is None
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _get(port, "/healthz")
    time.sleep(0.1)
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith(("paddle_tpu-metrics",
                                      "paddle_tpu-sampler"))]


def test_serve_is_idempotent():
    srv1 = _serve()
    srv2 = monitor.serve(port=0)
    assert srv2 is srv1


def test_env_port_autostarts_with_enable(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_METRICS_PORT", "0")
    monitor.enable()
    assert export.active() is not None
    _get(export.port(), "/metrics")


def test_no_plane_threads_when_not_served():
    monitor.enable()
    monitor.counter("executor.run").inc()
    assert export.active() is None and sampler.active() is None
    assert not [t for t in threading.enumerate()
                if t.name.startswith(("paddle_tpu-metrics",
                                      "paddle_tpu-sampler"))]


# ---------------------------------------------------------------------------
# sampler

def test_sample_once_publishes_mem_and_rss():
    reg = Registry()
    sampler.sample_once(reg)
    names = reg.names()
    assert any(n == "mem.host.rss_bytes" for n in names)
    assert reg.value("mem.host.rss_bytes") > 0


def test_sampler_provider_lifecycle():
    reg = Registry()
    calls = {"n": 0}

    def provider():
        calls["n"] += 1
        return {"toy.depth": 3}

    key = sampler.register_provider("toy", provider)
    sampler.sample_once(reg)
    assert reg.value("toy.depth") == 3 and calls["n"] == 1
    sampler.unregister_provider(key)
    sampler.sample_once(reg)
    assert calls["n"] == 1  # gone

    # a provider returning None (owner died) is dropped after one poll
    sampler.register_provider("dead", lambda: None)
    sampler.sample_once(reg)
    sampler.register_provider("boom",
                              lambda: (_ for _ in ()).throw(ValueError()))
    sampler.sample_once(reg)
    with sampler._providers_lock:
        assert "dead" not in sampler._providers
        assert "boom" not in sampler._providers


def test_prefetch_registers_queue_depth_provider():
    from paddle_tpu.io.prefetch import prefetch_to_device
    reg = Registry()
    it = prefetch_to_device(iter([np.ones((4,), "f4")] * 3), size=2)
    next(it)
    sampler.sample_once(reg)
    assert reg.value("prefetch.queue_depth", None) is not None
    it.close()
    # provider unregisters with the generator: no stale keys left
    with sampler._providers_lock:
        assert not any(k.startswith("prefetch-")
                       for k in sampler._providers)


def test_sampler_thread_samples_and_joins():
    monitor.enable()
    s = sampler.start(interval_s=0.05)
    time.sleep(0.2)
    assert s.running()
    assert monitor.registry().value("mem.host.rss_bytes", 0) > 0
    sampler.stop()
    assert not s.running()


# ---------------------------------------------------------------------------
# serving rollups: qps decay + SLO window

def test_qps_decays_to_zero_when_traffic_stops():
    monitor.enable()
    smetrics.record_completed(5, [1.0] * 5)
    assert monitor.registry().value("serving.qps") > 0
    # the sampler's sweep, 20 simulated seconds later: window empty
    val = smetrics.qps_now(now=time.monotonic() + 20.0)
    assert val == 0.0
    assert monitor.registry().value("serving.qps") == 0.0


def test_slo_rollup_goodput_and_percentiles():
    monitor.enable()
    now = time.monotonic()
    for _ in range(10):
        smetrics.record_submit(1)
    smetrics.record_completed(8, [float(i + 1) for i in range(8)],
                              within_sla=[True] * 6 + [False] * 2)
    smetrics.record_expired()  # 9th outcome: counted against goodput
    out = smetrics.slo_rollup(now=now)
    assert out["submitted"] == 10
    assert out["completed"] == 8          # expired has no latency
    assert out["within_sla"] == 6
    assert out["goodput"] == pytest.approx(0.6)
    assert out["p50_ms"] == pytest.approx(4.0, abs=1.01)
    assert out["p99_ms"] == pytest.approx(8.0)
    reg = monitor.registry()
    assert reg.value("slo.goodput") == pytest.approx(0.6)
    assert reg.value("slo.window_submitted") == 10
    # the window ages out: an hour later everything is gone
    out2 = smetrics.slo_rollup(now=now + 3600.0)
    assert out2["submitted"] == 0 and out2["goodput"] is None


def test_slo_series_reach_the_scrape():
    monitor.enable()
    srv = _serve()
    smetrics.record_submit(4)
    smetrics.record_completed(1, [2.5], within_sla=[True])
    smetrics.publish_rollups()
    _, text, _ = _get(srv.port, "/metrics")
    samples = _parse_openmetrics(text)
    assert samples["slo_goodput"] == pytest.approx(1.0)
    assert "serving_qps" in samples


# ---------------------------------------------------------------------------
# device_memory_stats hardening (satellite: CPU backends)

def test_device_memory_stats_cpu_returns_empty_dicts():
    import jax
    stats = monitor.device_memory_stats()
    assert set(stats) == {str(d.id) for d in jax.local_devices()}
    if jax.local_devices()[0].platform == "cpu":
        assert all(v == {} for v in stats.values())


def test_step_monitor_omits_empty_device_memory():
    import jax
    if jax.local_devices()[0].platform != "cpu":
        pytest.skip("CPU-only: needs a backend without memory stats")
    sm = monitor.StepMonitor(items_per_step=8, label="t",
                             memory_every=1).start()
    rec = sm.step()
    rec = sm.step()
    assert rec is not None and "device_memory" not in rec
