"""Mixed-fault chaos soak over the full serving lifecycle (CPU-safe).

Closed-loop seeded Poisson decode load against a 3-replica
:class:`MultiDecodeEngine` while a seeded chaos schedule mixes every
lifecycle disturbance the stack claims to survive:

* ``replica_hang``    — a replica hangs mid-step long enough to trip
  the supervisor's hang failover
* ``replica_slow``    — straggler injections
* ``preempt_replica`` — the supervisor drains + migrates the replica,
  then the schedule readmits it
* live weight hot-swap — rolling ``swap_weights`` between two
  same-shape weight publishes
* corrupt publish      — a garbled checkpoint swap attempt that quorum
  validation must refuse (and quarantine) without interrupting service

Invariants gated at the end:

* goodput >= 0.90 (completed / offered; sheds + failures count against)
* zero lost futures (every submitted future resolves)
* exactly one ``serving.request`` record per admitted request (parsed
  back out of the soak's own monitor JSONL — no double-finalize, no
  silent loss across drain/failover/swap hops)
* zero post-warmup compiles (same-shape swaps ride the
  state-as-argument jit contract; per-engine executable counts must
  not move)
* seeded bit-reproducibility: a quiet epilogue batch on the soaked
  fleet is bit-identical to a fresh single engine holding the final
  weights version
* corrupt publishes refused, never swapped in; final version reflects
  only the successful swaps

Short mode (the default, ``--duration 60``) is the tier-1 gate; crank
``--duration`` for a real soak. Prints one JSON line; exit 0 iff all
invariants hold.
"""
import argparse
import collections
import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

WEIGHT_SEEDS = (1, 9)    # the two same-shape publishes the soak rolls
VOCAB, DIM = 32, 32


def _model(seed):
    from paddle_tpu import serving
    return serving.demo_model(vocab=VOCAB, dim=DIM, heads=2, layers=2,
                              max_len=64, seed=seed)


def _request(rid, base_seed):
    """Deterministic (prompt, max_new, seed) for request `rid` — the
    same function drives the soak clients and the replay oracle."""
    rng = np.random.RandomState((base_seed * 100003 + rid) % (2 ** 31))
    plen = int(rng.randint(4, 13))
    prompt = rng.randint(1, VOCAB - 1, size=plen).astype(np.int32)
    return prompt, 8 + int(rng.randint(0, 5)), 50000 + rid


def run_soak(args):
    import jax
    from paddle_tpu import monitor, serving
    from paddle_tpu.io import sharded
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import reqtrace

    reqtrace.reset()
    eng = serving.MultiDecodeEngine(
        _model(WEIGHT_SEEDS[0]), devices=jax.local_devices()[:3],
        slots=4, page=16, max_len=48, prompt_buckets=(16,),
        queue_depth=256, supervisor_interval_s=0.05,
        inflight_timeout_ms=2500.0, breaker_cooldown_s=0.8)
    eng.warmup()
    eng.start()
    execs0 = [e.executables()[0] for e in eng.engines]

    counts = collections.Counter()
    lost = []
    admitted_rids = set()     # client traces that reached an engine —
    client_rids = set()       # the exactly-one-record census universe
    rid_counter = [0]
    rid_lock = threading.Lock()
    stop = threading.Event()

    def one_request(rid, retries=5):
        """One logical request: shed/failure retries share ONE
        RequestTrace, so the done-latch keeps the terminal record
        unique however many hops it takes. Returns True iff the
        request ultimately completed."""
        prompt, max_new, seed = _request(rid, args.seed)
        tr = reqtrace.RequestTrace(kind="decode")
        with rid_lock:
            client_rids.add(tr.rid)
        for _ in range(retries):
            try:
                fut = eng.submit(prompt, max_new_tokens=max_new,
                                 seed=seed, trace=tr,
                                 sampling={"temperature": 0.8})
            except serving.NoHealthyReplicaError:
                with rid_lock:
                    counts["shed_attempts"] += 1
                time.sleep(0.08)
                continue
            with rid_lock:
                admitted_rids.add(tr.rid)
            try:
                fut.result(45)
                with rid_lock:
                    counts["ok"] += 1
                return True
            except Exception as e:   # noqa: BLE001 - tallied + retried
                with rid_lock:
                    counts["failed_attempts"] += 1
                    counts[f"err:{type(e).__name__}"] += 1
                if not fut.done():
                    lost.append(tr.rid)
                time.sleep(0.05)
        with rid_lock:
            counts["gave_up"] += 1
        return False

    def client(k):
        rng = np.random.RandomState(args.seed * 7919 + k)
        while not stop.is_set():
            with rid_lock:
                rid = rid_counter[0]
                rid_counter[0] += 1
            one_request(rid)
            time.sleep(float(rng.exponential(0.01)))

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(args.clients)]
    for t in threads:
        t.start()

    # -- the seeded chaos schedule ---------------------------------------
    chaos = np.random.RandomState(args.seed)
    events = collections.Counter()
    deadline = time.monotonic() + args.duration
    weight_idx = 0          # index into WEIGHT_SEEDS of the live tree
    refusals = 0
    swap_errors = []
    with tempfile.TemporaryDirectory() as tmp:
        while time.monotonic() < deadline:
            time.sleep(float(chaos.uniform(1.2, 2.4)))
            if time.monotonic() >= deadline:
                break
            # readmit anything a previous preempt left draining
            for r in eng._replicas:
                if r.draining:
                    eng.undrain_replica(r, reason="chaos_readmit")
            kind = chaos.choice(["hang", "slow", "preempt", "swap",
                                 "corrupt"])
            replica = int(chaos.randint(0, 3))
            events[kind] += 1
            if kind == "hang":
                faults.inject("replica_hang", replica=replica,
                              delay=1.2, times=1)
            elif kind == "slow":
                faults.inject("replica_slow", replica=replica,
                              delay=0.12, times=3)
            elif kind == "preempt":
                faults.inject("preempt_replica", replica=replica,
                              times=1)
            elif kind == "swap":
                nxt = (weight_idx + 1) % len(WEIGHT_SEEDS)
                try:
                    eng.swap_weights(_model(WEIGHT_SEEDS[nxt]).state,
                                     drain_timeout_s=30.0,
                                     probe_timeout_s=10.0)
                    weight_idx = nxt
                except RuntimeError as e:   # unwound roll: still v_old
                    swap_errors.append(repr(e))
            elif kind == "corrupt":
                ck = os.path.join(tmp, f"bad-{events['corrupt']}.sharded")
                sharded.save_state(
                    ck, jax.device_get(_model(WEIGHT_SEEDS[1]).state))
                faults.inject("publish_corrupt", times=1)
                try:
                    eng.swap_weights(ck)
                except ValueError:
                    refusals += 1
                faults.clear("publish_corrupt")

        # -- quiesce: stop chaos, readmit everyone, let load drain -------
        faults.clear()
        stop.set()
        for t in threads:
            t.join(timeout=60)
        for r in eng._replicas:
            if r.draining:
                eng.undrain_replica(r, reason="chaos_done")
        eng.drain_fleet(reason="soak_epilogue")
        eng.drain_wait(timeout_s=60.0)
        for r in eng._replicas:
            eng.undrain_replica(r, reason="soak_epilogue")

    # -- epilogue: seeded bit-reproducibility on the final version -------
    epi_base = rid_counter[0] + 1000
    epi = [_request(epi_base + i, args.seed) for i in range(args.replay)]
    epi_traces = [reqtrace.RequestTrace(kind="decode") for _ in epi]
    for tr in epi_traces:
        client_rids.add(tr.rid)
        admitted_rids.add(tr.rid)
    epi_futs = [eng.submit(p, max_new_tokens=m, seed=s, trace=tr,
                           sampling={"temperature": 0.8})
                for (p, m, s), tr in zip(epi, epi_traces)]
    epi_tokens = [np.asarray(f.result(45)).tolist() for f in epi_futs]

    execs1 = [e.executables()[0] for e in eng.engines]
    final_version = eng.weights_version
    stats = eng.stats()
    eng.close(drain=False, timeout=5.0)

    ref_eng = serving.GenerateEngine(
        _model(WEIGHT_SEEDS[weight_idx]), slots=4, page=16, max_len=48,
        prompt_buckets=(16,), queue_depth=256)
    ref_eng.warmup()
    ref = [np.asarray(
        ref_eng.submit(p, max_new_tokens=m, seed=s,
                       sampling={"temperature": 0.8}).result(45)).tolist()
           for p, m, s in epi]
    ref_eng.close()
    replay_identical = sum(1 for a, b in zip(epi_tokens, ref) if a == b)

    # -- exactly-one reqtrace record per admitted logical request --------
    # (census restricted to client-owned rids: probes and warmup also
    # trace, legitimately, and must not skew the count)
    rid_records = collections.Counter()
    jsonl = monitor.jsonl_path()
    if jsonl and os.path.exists(jsonl):
        with open(jsonl) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if (rec.get("kind") == "serving.request"
                        and rec.get("rid") in client_rids):
                    rid_records[rec["rid"]] += 1
    dupes = {r: c for r, c in rid_records.items() if c != 1}
    missing = [r for r in admitted_rids if r not in rid_records]
    requests = len(client_rids)
    completed = counts["ok"] + len(epi)
    goodput = completed / requests if requests else 0.0

    result = {
        "duration_s": args.duration,
        "seed": args.seed,
        "requests": requests,
        "admitted": len(admitted_rids),
        "completed": completed,
        "gave_up": counts["gave_up"],
        "shed_attempts": counts["shed_attempts"],
        "failed_attempts": counts["failed_attempts"],
        "errors": {k[4:]: v for k, v in counts.items()
                   if k.startswith("err:")},
        "events": dict(events),
        "swap_errors": swap_errors[:3],
        "corrupt_refusals": refusals,
        "goodput": round(goodput, 4),
        "final_version": final_version,
        "failovers": stats.get("failovers", 0),
        "records": sum(rid_records.values()),
        "record_dupes": len(dupes),
        "records_missing": len(missing),
        "replay_identical": replay_identical,
        "replay_total": len(epi),
        "execs_before": execs0,
        "execs_after": execs1,
        "gates": {
            "goodput_floor": goodput >= 0.90,
            "zero_lost_futures": not lost,
            "exactly_one_record": not dupes and not missing,
            "zero_postwarmup_compiles": execs1 == execs0,
            "replay_bit_identical": replay_identical == len(epi),
            "corrupt_never_swapped": refusals == events["corrupt"],
            "load_actually_ran": completed >= args.duration * 2,
        },
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_soak_chaos")
    ap.add_argument("--duration", type=float, default=60.0,
                    help="chaos phase length in seconds (short mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--replay", type=int, default=6,
                    help="epilogue bit-replay batch size")
    args = ap.parse_args()

    from paddle_tpu import monitor

    os.makedirs(args.out_dir, exist_ok=True)
    jsonl = os.path.join(args.out_dir, "soak_chaos.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)   # the sink appends; stale records would
                           # corrupt the exactly-one-record census
    monitor.enable(jsonl)
    t0 = time.perf_counter()
    result = run_soak(args)
    result["wall_s"] = round(time.perf_counter() - t0, 3)
    result["ok_gate"] = all(result["gates"].values())
    monitor.emit(kind="soak_chaos", **result)
    monitor.disable()
    print(json.dumps(result))
    return 0 if result["ok_gate"] else 1


if __name__ == "__main__":
    sys.exit(main())
