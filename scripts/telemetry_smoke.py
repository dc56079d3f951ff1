"""Fleet telemetry smoke: 4-process decode fleet, 2 injected anomalies.

The gate behind docs/observability.md "Fleet telemetry": four worker
processes run real decode load against small ``GenerateEngine``s, each
publishing versioned metric snapshots into a shared telemetry directory
(``monitor/fleet.py``). One worker is a straggler (a ``replica_slow``
fault sleeps inside its batch tick), one mints a burst of post-warmup
compiles (a genuine ``jit.to_static`` shape storm). The parent runs the
consumer side of the plane — ``FleetAggregator`` + ``AnomalyDetector``
+ ``AlertManager`` + a (stub-owned) ``ServingSupervisor`` — and asserts
the ISSUE's acceptance bar end to end:

* merged counters equal the per-worker oracle (ints exactly, float
  counters to 1e-9 — summation order is the only difference);
* merged p50/p99 land within one histogram bucket of the nearest-rank
  percentile over the union of every worker's raw events, for both a
  seeded oracle histogram and the live ``serving.ttft_ms`` traffic;
* exactly the two expected alerts fire AND resolve —
  ``straggler(worker-1)`` and ``compile_storm(worker-2)`` — each naming
  the offending source + series, and both appear in the supervisor's
  decision ledger (``anomaly`` decisions / ``anomalies`` context);
* the goodput ledger reconciles to wall time within 5% around a loop
  with a real checkpoint save and a measured input stall;
* snapshot publishing costs <= 1% of a worker's wall time
  (``fleet_agg_overhead_pct``, reported along with
  ``alert_detection_latency_s``);
* with the monitor disabled nothing publishes: zero files, no thread.

Prints one JSON result line (last stdout line).

Usage::

    python scripts/telemetry_smoke.py [--out-dir DIR]
    python scripts/telemetry_smoke.py --fast   # shorter phases
"""
import argparse
import json
import math
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_WORKERS = 4
STRAGGLER = 1           # worker-1 drags its decode ticks
STORM = 2               # worker-2 mints a compile burst mid-run
STRAGGLER_DELAY_S = 0.03
STORM_SHAPES = 16       # distinct shapes -> that many jit.compile
ORACLE_SERIES = "fleetsmoke.latency_ms"
ORACLE_EVENTS = 200     # seeded observations per worker


# ---------------------------------------------------------------------------
# worker side


def _drip(eng, rng, until, ttfts, slow_tick=False):
    """Submit single small requests back-to-back until the deadline —
    every worker stays *continuously* active so the detector always has
    >= min_sources live decode series to compare."""
    while time.perf_counter() < until:
        plen = int(rng.randint(1, 13))
        prompt = rng.randint(1, 31, size=plen).tolist()
        new = int(rng.randint(2, 7))
        r = eng.make_request(prompt, max_new_tokens=new, eos_token=None)
        eng.submit_request(r)
        r.future.result(timeout=120)
        rec = (r.trace.ctx.record() if r.trace is not None else None)
        if rec and rec.get("ttft_ms") is not None:
            ttfts.append(float(rec["ttft_ms"]))


def _mint_compile_storm():
    """A real compile storm: one tiny jitted fn called across
    STORM_SHAPES distinct input shapes, each a fresh executable."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import jit

    fn = jit.to_static(lambda x: (x * 2.0 + 1.0).mean())
    for n in range(3, 3 + STORM_SHAPES):
        fn(pt.to_tensor(np.zeros((1, n), dtype="float32")))


def worker_main(args):
    import random

    import numpy as np
    from paddle_tpu import monitor, serving
    from paddle_tpu.monitor import fleet
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import metrics as smetrics
    from paddle_tpu.serving.metrics import LATENCY_BUCKETS_MS

    idx = args.worker
    tdir = args.telemetry_dir
    monitor.enable(telemetry_dir=tdir)      # source via env, set by parent
    wall0 = time.perf_counter()

    # the seeded oracle histogram: raw values dumped alongside so the
    # parent can nearest-rank the union and check the merged estimate
    rnd = random.Random(1000 + idx)
    raw = [round(math.exp(rnd.gauss(2.0, 1.2)), 6)
           for _ in range(ORACLE_EVENTS)]
    h = monitor.histogram(ORACLE_SERIES, buckets=LATENCY_BUCKETS_MS)
    for v in raw:
        h.observe(v)

    model = serving.demo_model(vocab=64, dim=64, heads=2, layers=1,
                               max_len=48, seed=1)
    smetrics.reset_windows()
    eng = serving.GenerateEngine(
        model, slots=4, page=16, factor=2.0, max_len=48,
        prompt_buckets=(4, 16), queue_depth=64, refill="continuous",
        shed=False, start=True)
    eng.warmup()

    # barrier: warmup compiles land *before* the parent arms the
    # detector, so the only post-go compile burst is the injected one
    with open(os.path.join(tdir, f"ready-{idx}"), "w") as fh:
        fh.write(str(os.getpid()))
    go = os.path.join(tdir, "go")
    deadline = time.perf_counter() + 120
    while not os.path.exists(go):
        if time.perf_counter() > deadline:
            raise RuntimeError("parent never opened the barrier")
        time.sleep(0.05)

    rng = np.random.RandomState(100 + idx)
    ttfts = []

    # phase A: anomalous
    if idx == STRAGGLER:
        faults.inject("replica_slow", delay=STRAGGLER_DELAY_S,
                      times=None)
    t_a = time.perf_counter() + args.phase_s
    storm_at = time.perf_counter() + min(1.0, args.phase_s / 3.0)
    stormed = False
    while time.perf_counter() < t_a:
        _drip(eng, rng, min(t_a, time.perf_counter() + 0.5), ttfts)
        if idx == STORM and not stormed \
                and time.perf_counter() >= storm_at:
            _mint_compile_storm()
            stormed = True
    faults.clear()

    # phase B: clean tail — the anomalies must RESOLVE, not just fire
    _drip(eng, rng, time.perf_counter() + args.phase_s, ttfts)
    eng.close()

    wall_s = time.perf_counter() - wall0
    stats = fleet.publisher_stats() or {"writes": 0, "write_cpu_s": 0.0}
    export = monitor.registry().export_snapshot()
    result = {
        "worker": idx,
        "wall_s": round(wall_s, 3),
        "publisher": stats,
        # CPU burned publishing vs run wall: the wall span of a write
        # on a saturated box mostly measures waiting for the GIL, i.e.
        # time the process spent doing useful decode work
        "overhead_pct": round(100.0 * stats["write_cpu_s"]
                              / max(wall_s, 1e-9), 4),
        "oracle_raw": raw,
        "ttfts": ttfts,
        "counters": export["counters"],
    }
    tmp = args.result + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(result, fh)
    monitor.disable()       # final snapshot lands before the rename:
    os.replace(tmp, args.result)  # result visible => snapshot final
    return 0


# ---------------------------------------------------------------------------
# parent side


class _StubOwner:
    """The minimum MultiDeviceEngine surface a non-scaling supervisor
    tick touches — lets the smoke run the REAL decision ledger without
    standing up a replica fleet in the parent."""
    inflight_timeout_s = 1.0
    _replicas = ()

    def _refresh_hedge_delay(self, p99_ms):
        pass


def _bucket_index(bounds, v):
    for i, b in enumerate(bounds):
        if v <= b:
            return i
    return len(bounds)


def _nearest_rank(values, q):
    s = sorted(values)
    i = min(len(s) - 1, int(round(q * (len(s) - 1))))
    return s[i]


def _check(checks, name, ok, detail):
    checks[name] = {"ok": bool(ok), "detail": detail}
    tag = "ok" if ok else "FAIL"
    print(f"[telemetry_smoke] {tag:>4}  {name}: {detail}",
          file=sys.stderr)


def _check_disabled_mode(checks):
    """Monitor never enabled => the fleet plane must not exist: no
    snapshot files, no publisher thread."""
    with tempfile.TemporaryDirectory() as d:
        code = (
            "import os, threading, paddle_tpu.monitor as m,"
            " paddle_tpu.monitor.fleet as f\n"
            "m.counter('x').inc(); m.emit(kind='noop')\n"
            "assert not m.enabled()\n"
            "assert not f.publisher_active()\n"
            "assert f.publisher_stats() is None\n"
            "threads = [t.name for t in threading.enumerate()]\n"
            "assert not any('telemetry' in n or 'fleet' in n"
            " for n in threads), threads\n"
            f"print(len(os.listdir({d!r})))\n")
        out = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PADDLE_TPU_TELEMETRY_DIR": d},
            capture_output=True, text=True, timeout=120)
        ok = out.returncode == 0 and out.stdout.strip() == "0"
        _check(checks, "disabled_zero_files", ok,
               f"rc={out.returncode} files={out.stdout.strip()!r} "
               f"{out.stderr.strip()[-200:]}")


def _check_counters(checks, agg, results):
    """Merged counters vs the oracle: the sum of every worker's final
    export. Integers exactly; float counters to 1e-9 (the aggregator
    and the oracle sum in different orders)."""
    oracle = {}
    for res in results:
        for name, v in res["counters"].items():
            oracle[name] = oracle.get(name, 0) + v
    bad = []
    for name, want in sorted(oracle.items()):
        got = agg.value(name, default=None)
        if got is None:
            bad.append(f"{name}: missing from merge")
        elif isinstance(want, int) and isinstance(got, int):
            if got != want:
                bad.append(f"{name}: {got} != {want}")
        elif not math.isclose(float(got), float(want), rel_tol=1e-9,
                              abs_tol=1e-9):
            bad.append(f"{name}: {got} !~ {want}")
    _check(checks, "merged_counters_exact", not bad,
           f"{len(oracle)} counters" if not bad else "; ".join(bad[:5]))


def _check_percentiles(checks, agg, results):
    from paddle_tpu.serving.metrics import LATENCY_BUCKETS_MS
    bounds = list(LATENCY_BUCKETS_MS)
    for label, key, series in (
            ("oracle", "oracle_raw", ORACLE_SERIES),
            ("ttft", "ttfts", "serving.ttft_ms")):
        union = [v for res in results for v in res[key]]
        h = agg.histogram(series)
        if h is None or not union:
            _check(checks, f"percentile_{label}", False,
                   f"{series}: no merged histogram / no events")
            continue
        details, ok = [], True
        if label == "oracle":
            exact = (h["count"] == len(union)
                     and math.isclose(h["sum"], sum(union),
                                      rel_tol=1e-6))
            ok &= exact
            details.append(f"count/sum exact={exact}")
        for q in (0.50, 0.99):
            want = _nearest_rank(union, q)
            got = agg.percentile(series, q)
            di = abs(_bucket_index(bounds, got)
                     - _bucket_index(bounds, want))
            ok &= di <= 1
            details.append(f"p{int(q * 100)} est={got:.3g} "
                           f"true={want:.3g} d_bucket={di}")
        _check(checks, f"percentile_{label}", ok, "; ".join(details))


def _run_goodput_check(checks):
    """The ledger around a real mini train loop: sleep-compute, one
    measured input stall, one real CheckpointManager save. Wall time
    must reconcile against compute + the ranked losses within 5%."""
    import numpy as np
    from paddle_tpu import io, monitor

    with tempfile.TemporaryDirectory() as ckdir:
        mon = monitor.StepMonitor(items_per_step=1, label="goodput_smoke",
                                  goodput=True)
        cm = io.CheckpointManager(ckdir, max_to_keep=1)
        state = {"w": np.zeros((64, 64), dtype="float32")}
        for step in range(6):
            t0 = time.perf_counter()
            time.sleep(0.02)                      # "compute"
            if step == 2:                         # measured input stall
                s0 = time.perf_counter()
                time.sleep(0.05)
                monitor.counter("prefetch.stall_seconds").inc(
                    time.perf_counter() - s0)
            if step == 3:                         # real checkpoint save
                cm.save(step, extra={"state": state})
            mon.step()
            del t0
        summary = mon.summary()
    g = summary.get("goodput") or {}
    wall = g.get("wall_s", 0.0)
    recon = abs(wall - (g.get("compute_s", 0.0) + g.get("lost_s", 0.0)))
    ok = wall > 0 and recon <= 0.05 * wall
    cats = {row["category"]: row["seconds"] for row in g.get("lost", [])}
    ok &= cats.get("checkpoint", 0.0) > 0.0
    ok &= cats.get("input_stall", 0.0) >= 0.04
    ok &= 0.0 < g.get("goodput_fraction", 0.0) < 1.0
    _check(checks, "goodput_reconciles", ok,
           f"wall={wall:.3f}s residual={recon:.4f}s "
           f"goodput={g.get('goodput_fraction')} "
           f"ckpt={cats.get('checkpoint', 0):.4f}s "
           f"stall={cats.get('input_stall', 0):.4f}s")


def parent_main(args):
    from paddle_tpu import monitor
    from paddle_tpu.monitor import alerts, fleet
    from paddle_tpu.serving.supervisor import ServingSupervisor

    checks = {}
    out_dir = args.out_dir or tempfile.mkdtemp(prefix="telemetry_smoke_")
    os.makedirs(out_dir, exist_ok=True)
    tdir = os.path.join(out_dir, "telemetry")
    os.makedirs(tdir, exist_ok=True)

    # the parent is the consumer, not a source: no publisher here
    os.environ.pop("PADDLE_TPU_TELEMETRY_DIR", None)
    monitor.enable(os.path.join(out_dir, "telemetry_smoke.jsonl"))

    # -- spawn the fleet -------------------------------------------------
    procs, result_paths = [], []
    for i in range(N_WORKERS):
        rpath = os.path.join(out_dir, f"worker-{i}.json")
        result_paths.append(rpath)
        env = {**os.environ,
               "JAX_PLATFORMS": "cpu",
               "PADDLE_TPU_TELEMETRY_SOURCE": f"worker-{i}",
               "PADDLE_TPU_TELEMETRY_INTERVAL_S": "0.2"}
        env.pop("PADDLE_TPU_TELEMETRY_DIR", None)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", str(i), "--telemetry-dir", tdir,
             "--result", rpath, "--phase-s", str(args.phase_s)],
            env=env, stdout=subprocess.DEVNULL))

    # barrier: wait for every engine to warm up, then open the gate —
    # detection latency is measured from HERE (anomalies start with go)
    deadline = time.time() + 300
    while len([p for p in os.listdir(tdir)
               if p.startswith("ready-")]) < N_WORKERS:
        if time.time() > deadline:
            for p in procs:
                p.kill()
            raise RuntimeError("workers never reached the barrier")
        if any(p.poll() not in (None, 0) for p in procs):
            raise RuntimeError("a worker died before the barrier")
        time.sleep(0.1)
    with open(os.path.join(tdir, "go"), "w") as fh:
        fh.write("go")
    t_go = time.perf_counter()

    # -- the consumer plane ---------------------------------------------
    agg = fleet.FleetAggregator(tdir, staleness_ttl_s=60.0)
    mgr = alerts.AlertManager(rules=[], finding_resolve_after_s=2.0)
    # queue/accept shapes are unit-tested; this gate is straggler +
    # storm, exactly — thresholds park the other two out of reach
    det = alerts.AnomalyDetector(
        manager=mgr, warmup_ticks=1, compile_delta_threshold=6,
        compile_window_s=4.0, z_threshold=3.0, min_sources=3,
        accept_rate_floor=-1.0, queue_min_depth=10 ** 9)
    owner = _StubOwner()
    sup = ServingSupervisor(owner, start=False, scale=False)

    first_fired = {}
    t_end = time.time() + 240
    while time.time() < t_end:
        agg.scrape()
        det.update(agg.source_snapshots())
        firing = mgr.tick()
        sup.tick(owner)
        for a in firing:
            first_fired.setdefault(a["name"],
                                   time.perf_counter() - t_go)
        workers_done = all(p.poll() is not None for p in procs)
        states = [a["state"] for a in mgr.alerts()]
        if workers_done and first_fired \
                and all(s == "resolved" for s in states):
            break
        time.sleep(0.25)
    for p in procs:
        p.wait(timeout=60)
    alerts.clear_findings()

    rcs = [p.returncode for p in procs]
    _check(checks, "workers_exit_clean", all(rc == 0 for rc in rcs),
           f"rcs={rcs}")
    results = []
    for rpath in result_paths:
        with open(rpath) as fh:
            results.append(json.load(fh))

    # -- the acceptance bar ----------------------------------------------
    agg.scrape()        # final snapshots (written at worker disable)
    _check_counters(checks, agg, results)
    _check_percentiles(checks, agg, results)

    expected = {f"straggler(worker-{STRAGGLER})",
                f"compile_storm(worker-{STORM})"}
    hist = mgr.history
    fired = [h for h in hist if h["state"] == "firing"]
    resolved = {h["name"] for h in hist if h["state"] == "resolved"}
    names = {h["name"] for h in fired}
    ok = (names == expected and len(fired) == 2
          and expected <= resolved)
    _check(checks, "exactly_two_alerts_fire_and_resolve", ok,
           f"fired={sorted(names)} x{len(fired)} "
           f"resolved={sorted(resolved & expected)}")

    ok = all(any(h["name"] == n and h.get("source") and h.get("series")
                 for h in fired) for n in expected)
    _check(checks, "alerts_name_replica_and_series", ok,
           str([{k: h.get(k) for k in ('name', 'source', 'series')}
                for h in fired]))

    anomaly_decisions = {d.get("anomaly") for d in sup.decisions
                         if d["decision"] == "anomaly"}
    _check(checks, "supervisor_decision_context",
           expected <= anomaly_decisions,
           f"anomaly decisions={sorted(anomaly_decisions)}")

    overhead = max(res["overhead_pct"] for res in results)
    _check(checks, "aggregation_overhead", overhead <= 1.0,
           f"max worker publish overhead {overhead:.4f}% (<= 1%)")

    detect_s = min(first_fired.values()) if first_fired else None
    _run_goodput_check(checks)
    _check_disabled_mode(checks)

    n_ok = sum(1 for c in checks.values() if c["ok"])
    result = {
        "ok": n_ok == len(checks),
        "checks_passed": n_ok,
        "checks_total": len(checks),
        "checks": {k: v["ok"] for k, v in checks.items()},
        "fleet_agg_overhead_pct": round(overhead, 4),
        "alert_detection_latency_s": (round(detect_s, 3)
                                      if detect_s is not None else None),
        "sources": len(agg.sources()),
        "fired": sorted(names),
    }
    monitor.emit(kind="telemetry_smoke", **{
        k: v for k, v in result.items() if k != "checks"})
    monitor.disable()
    print(json.dumps(result))
    return 0 if result["ok"] else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None)
    ap.add_argument("--telemetry-dir", default=None)
    ap.add_argument("--result", default=None)
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--phase-s", type=float, default=3.5,
                    help="seconds per phase (anomalous, then clean)")
    ap.add_argument("--fast", action="store_true",
                    help="shorter phases (CI smoke)")
    args = ap.parse_args()
    if args.fast:
        args.phase_s = min(args.phase_s, 2.5)
    if args.worker is not None:
        return worker_main(args)
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
