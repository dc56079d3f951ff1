"""Disaggregated serving gate (tier-1-safe: tiny models, CPU).

Four phases against the PR 20 split topology, gating the acceptance
criteria:

* **parity** — mixed greedy/sampled traffic through (prefill pool →
  priced handoff → decode pool), with a mid-stream drain of the seated
  decode replica. Gates: every stream byte-identical to the
  single-engine oracle, zero post-warmup executables in BOTH pools,
  recorded handoff bytes exactly equal the comm-model prediction
  (per-token KV spec bytes × prompt bucket), decode pool never runs
  prefill.
* **prefix** — head-heavy traffic at >= 50% reuse against the shared
  PrefixCache. Gates: a hit skips prefill entirely (prefill count ==
  cache misses), hit TTFT p50 <= 0.5x miss TTFT p50, zero new
  executables after warmup (a hit never mints a shape).
* **autoscale** — each pool held at 1-of-2 active replicas under load.
  Gates: the prefill supervisor scales up on ITS SLO (the decision
  carries ``queue_depth``/``queue_depth_ceiling``, never a goodput or
  tokens context) and the decode supervisor scales up on ITS SLO (the
  decision carries ``tokens_floor``); both pools end at 2 active.
* **hang** — one of two prefill replicas hangs mid-prefill
  (``replica_hang``). Gates: the supervisor fails the work over to the
  healthy peer and goodput stays >= 0.90 with zero lost futures.

Prints one JSON result line; exit code 0 iff every gate passes.
Run via scripts/disagg_smoke.sh (which forces the CPU topology before
jax imports).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _model(dim=32, seed=1, max_len=64, vocab=32, heads=2):
    from paddle_tpu import serving
    return serving.demo_model(vocab=vocab, dim=dim, heads=heads,
                              layers=2, max_len=max_len, seed=seed)


def _oracle(model, jobs, **kw):
    """Fault-free single-engine run: the bit-identity oracle."""
    from paddle_tpu.serving.generate import GenerateEngine
    eng = GenerateEngine(model, **kw)
    eng.warmup()
    futs = [eng.submit(p, max_new_tokens=n, sampling=sp, seed=s)
            for p, n, sp, s in jobs]
    out = [[int(t) for t in f.result(timeout=30)] for f in futs]
    eng.close()
    return out


def _execs(srv):
    return tuple(r.engine.executables()
                 for pool in (srv.prefill_pool, srv.decode_pool)
                 for r in pool._replicas)


def phase_parity(args):
    """Split-topology streams == oracle streams, through a mid-stream
    decode drain, with priced handoffs and zero fresh executables."""
    from paddle_tpu import serving
    from paddle_tpu.serving import reqtrace

    model = _model()
    rng = np.random.RandomState(3)
    jobs = []
    for i in range(args.requests):
        plen = int(rng.randint(2, 25))
        prompt = rng.randint(1, 31, size=plen).tolist()
        sp = {"temperature": 0.9, "top_k": 8} if i % 2 else None
        jobs.append((prompt, 8 + int(rng.randint(0, 5)), sp,
                     500 + i if sp else None))
    jobs.append((jobs[0][0], 8, None, None))    # repeat → prefix hit
    kw = dict(slots=4, page=16, factor=2.0, max_len=64,
              prompt_buckets=(8, 32))
    want = _oracle(model, jobs, **kw)

    srv = serving.DisaggServer(model, prefill_replicas=1,
                               decode_replicas=2, supervise=False, **kw)
    srv.warmup()
    ex0 = _execs(srv)
    reqtrace.reset()
    t_load = time.perf_counter()
    futs = [srv.submit(p, max_new_tokens=n, sampling=sp, seed=s)
            for p, n, sp, s in jobs]

    # drain whichever decode replica seated work first: its streams
    # must move (KV and all) and resume bit-identically on the peer
    victim, deadline = None, time.monotonic() + 10
    while victim is None and time.monotonic() < deadline:
        for r in srv.decode_pool._replicas:
            if r.engine.stats()["kv_imports"] > 0:
                victim = r.index
                break
        time.sleep(0.005)
    moved = srv.drain_decode_replica(victim, reason="smoke") \
        if victim is not None else 0

    got = [[int(t) for t in f.result(timeout=30)] for f in futs]
    load_wall = time.perf_counter() - t_load
    tokens = sum(len(g) for g in got)
    handoffs_ms = sorted(r["handoff_ms"] for r in reqtrace.recent()
                         if r.get("handoff_ms") is not None)
    handoff_p50 = handoffs_ms[len(handoffs_ms) // 2] if handoffs_ms \
        else None
    fresh = sum((b[0] - a[0]) + (b[1] - a[1])
                for a, b in zip(ex0, _execs(srv)))
    st = srv.stats()
    planned_bytes = sum(srv.planned_handoff_ms(len(p))[0]
                        for p, _n, _sp, _s in jobs)
    srv.close()

    identical = sum(1 for a, b in zip(want, got) if a == b)
    return {
        "requests": len(jobs),
        "identical": identical,
        "drained_moved": moved,
        "post_warmup_compiles": fresh,
        "handoffs": st["handoffs"],
        "handoff_bytes": st["handoff_bytes"],
        "handoff_p50_ms": round(handoff_p50, 3)
        if handoff_p50 is not None else None,
        "tokens_per_s": round(tokens / load_wall, 1),
        "planned_bytes": planned_bytes,
        "prefix_hits": st["prefix"]["hits"],
        "gates": {
            "bit_identical": identical == len(jobs),
            "zero_fresh_executables": fresh == 0,
            "handoff_bytes_match_plan":
                st["handoff_bytes"] == planned_bytes,
            "every_request_handed_off": st["handoffs"] == len(jobs),
            "decode_pool_never_prefills": st["decode"]["prefills"] == 0,
            "drain_moved_inflight": moved >= 1,
        },
    }


def phase_prefix(args):
    """>=50% reuse on shared heads: hits skip prefill and halve TTFT."""
    from paddle_tpu import serving
    from paddle_tpu.serving import reqtrace

    # prefill cost must dominate the hit path's standalone sample, so
    # the TTFT split is physics, not noise: wide model, long heads
    model = _model(dim=256, heads=4, vocab=64, max_len=96)
    srv = serving.DisaggServer(model, prefill_replicas=1,
                               decode_replicas=1, slots=4, page=16,
                               factor=2.0, max_len=96,
                               prompt_buckets=(16, 64),
                               supervise=False)
    srv.warmup()
    ex0 = _execs(srv)

    rng = np.random.RandomState(5)
    heads = [rng.randint(1, 63, size=48).tolist() for _ in range(2)]
    for h in heads:                     # warm the cache: one miss each
        srv.run(h, max_new_tokens=2, timeout=30)

    reqtrace.reset()
    n_hit = n_miss = args.requests // 2
    plan = ([(heads[i % 2], True) for i in range(n_hit)]
            + [(rng.randint(1, 63, size=48).tolist(), False)
               for _ in range(n_miss)])
    rng.shuffle(plan)
    # sequential closed loop: TTFT measures the service path (lookup +
    # sample vs full prefill), not queueing behind the previous request
    for prompt, _is_hit in plan:
        srv.run(prompt, max_new_tokens=2, timeout=30)

    recs = [r for r in reqtrace.recent() if r["outcome"] == "ok"]
    hit_ttft = sorted(r["ttft_ms"] for r in recs if r["prefix_hit"])
    miss_ttft = sorted(r["ttft_ms"] for r in recs if not r["prefix_hit"])
    fresh = sum((b[0] - a[0]) + (b[1] - a[1])
                for a, b in zip(ex0, _execs(srv)))
    st = srv.stats()
    srv.close()

    def p50(xs):
        return xs[len(xs) // 2] if xs else None

    hit_p50, miss_p50 = p50(hit_ttft), p50(miss_ttft)
    hit_rate = len(hit_ttft) / max(len(recs), 1)
    return {
        "requests": len(recs),
        "hit_rate": round(hit_rate, 4),
        "ttft_hit_p50_ms": round(hit_p50, 3) if hit_p50 else None,
        "ttft_miss_p50_ms": round(miss_p50, 3) if miss_p50 else None,
        "prefills": st["prefill"]["prefills"],
        "cache": st["prefix"],
        "post_warmup_compiles": fresh,
        "gates": {
            "reuse_ge_half": hit_rate >= 0.5,
            "hit_ttft_le_half_miss":
                hit_p50 is not None and miss_p50 is not None
                and hit_p50 <= 0.5 * miss_p50,
            "hits_skip_prefill":
                st["prefill"]["prefills"] == st["prefix"]["misses"],
            "zero_fresh_executables": fresh == 0,
        },
    }


def phase_autoscale(args):
    """Each pool scales on its own SLO: prefill on queue depth / TTFT,
    decode on the tokens/s floor — never on the generic goodput rung."""
    from paddle_tpu import serving

    model = _model()
    # both pools pinned to 1-of-2 active; ceilings set so any real
    # traffic breaches them (the gate is WHICH branch fired, not when)
    srv = serving.DisaggServer(
        model, prefill_replicas=2, decode_replicas=2, slots=2,
        page=16, factor=2.0, max_len=64, prompt_buckets=(8, 32),
        supervise=True, supervisor_interval_s=0.05,
        queue_depth_ceiling=1, tokens_floor=10_000_000.0,
        prefill_initial_active=1, decode_initial_active=1)
    srv.warmup()
    rng = np.random.RandomState(11)
    futs = []
    for _ in range(args.requests):
        plen = int(rng.randint(2, 25))
        futs.append(srv.submit(rng.randint(1, 31, size=plen).tolist(),
                               max_new_tokens=8))
    for f in futs:
        f.result(timeout=30)
    # let the decode supervisor observe the now-filled tokens/s window
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if any(d["decision"] == "scale_up"
               for d in srv.decode_supervisor.decisions):
            break
        time.sleep(0.05)

    pre = [d for d in srv.prefill_supervisor.decisions
           if d["decision"] == "scale_up"]
    dec = [d for d in srv.decode_supervisor.decisions
           if d["decision"] == "scale_up"]
    pre_active = srv.prefill_pool._active_count()
    dec_active = srv.decode_pool._active_count()
    srv.close()

    return {
        "prefill_scale_ups": len(pre),
        "decode_scale_ups": len(dec),
        "prefill_decision": pre[0] if pre else None,
        "decode_decision": dec[0] if dec else None,
        "gates": {
            "prefill_scaled_on_own_slo":
                bool(pre) and "queue_depth_ceiling" in pre[0]
                and "goodput" not in pre[0]
                and "tokens_floor" not in pre[0],
            "decode_scaled_on_own_slo":
                bool(dec) and "tokens_floor" in dec[0]
                and "goodput" not in dec[0]
                and "queue_depth_ceiling" not in dec[0],
            "prefill_pool_grew": pre_active == 2,
            "decode_pool_grew": dec_active == 2,
        },
    }


def phase_hang(args):
    """One of two prefill replicas hangs mid-prefill: failover keeps
    goodput >= 0.90 with zero lost futures."""
    from paddle_tpu import serving
    from paddle_tpu.resilience import faults

    model = _model()
    srv = serving.DisaggServer(
        model, prefill_replicas=2, decode_replicas=1, slots=4,
        page=16, factor=2.0, max_len=64, prompt_buckets=(8, 32),
        supervise=True, supervisor_interval_s=0.05,
        prefill_inflight_timeout_ms=250.0)
    srv.warmup()
    spec = faults.inject("replica_hang", replica=0, delay=1.5, times=1,
                         site="prefill")

    rng = np.random.RandomState(17)
    futs, errors = [], []
    for i in range(args.requests):
        plen = int(rng.randint(2, 25))
        futs.append(srv.submit(rng.randint(1, 31, size=plen).tolist(),
                               max_new_tokens=8, seed=900 + i,
                               sampling={"temperature": 0.8}))
        time.sleep(float(rng.exponential(0.004)))

    ok = lost = 0
    for f in futs:
        try:
            f.result(timeout=30)
            ok += 1
        except Exception as e:   # noqa: BLE001 - counted
            errors.append(repr(e))
        if not f.done():
            lost += 1
    srv.close()
    faults.clear()

    goodput = ok / len(futs) if futs else 0.0
    return {
        "submitted": len(futs),
        "ok": ok,
        "lost": lost,
        "errors": errors[:3],
        "goodput": round(goodput, 4),
        "fault_fired": spec.fired,
        "gates": {
            "fault_injected": spec.fired >= 1,
            "goodput_ge_090": goodput >= 0.90,
            "zero_lost_futures": lost == 0,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_disagg_smoke")
    ap.add_argument("--requests", type=int, default=16,
                    help="per-phase request scale")
    args = ap.parse_args()

    from paddle_tpu import monitor
    from paddle_tpu.serving import metrics as smetrics

    os.makedirs(args.out_dir, exist_ok=True)
    jsonl = monitor.enable(os.path.join(args.out_dir,
                                        "disagg_smoke.jsonl"))

    result = {"jsonl": jsonl}
    t0 = time.perf_counter()
    for name, fn in (("parity", phase_parity),
                     ("prefix", phase_prefix),
                     ("autoscale", phase_autoscale),
                     ("hang", phase_hang)):
        smetrics.reset_windows()
        result[name] = fn(args)
    result["wall_s"] = round(time.perf_counter() - t0, 3)
    # flat copies of the headline numbers
    result["prefix_hit_rate"] = result["prefix"]["hit_rate"]
    result["ttft_hit_p50_ms"] = result["prefix"]["ttft_hit_p50_ms"]
    result["ttft_miss_p50_ms"] = result["prefix"]["ttft_miss_p50_ms"]
    result["handoff_p50_ms"] = result["parity"]["handoff_p50_ms"]
    result["tokens_per_s"] = result["parity"]["tokens_per_s"]

    gates = {}
    for name in ("parity", "prefix", "autoscale", "hang"):
        for g, v in result[name]["gates"].items():
            gates[f"{name}.{g}"] = bool(v)
    result["gates"] = gates
    result["ok"] = all(gates.values())
    monitor.emit(kind="disagg_smoke",
                 **{k: v for k, v in result.items() if k != "jsonl"})
    monitor.disable()
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
