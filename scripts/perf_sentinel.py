"""Perf regression sentinel — the ledger's tripwire.

The repo banks real on-chip measurements (``BENCH_r0*.json`` round
artifacts, ``docs/bench_r04_measured.json`` /
``docs/bench_latest_measured.json`` committed snapshots, and the
``$PADDLE_TPU_BENCH_JSONL`` running artifact bench.py appends to). This
script compares the NEWEST candidate measurement against the newest
*committed* baseline, per metric, with per-metric tolerance bands — and
exits nonzero iff something actually regressed.

Three verdicts per metric, and the distinction is the whole point:

* ``regression`` — a real number moved past its tolerance band in the
  bad direction. Exit 1.
* ``ok`` / ``improved`` — within band, or moved the good way. A better
  candidate also prints a nudge to re-bank the baseline.
* ``outage``  — the candidate is an error line (``value == 0`` with an
  ``error`` field: a run that failed before it measured anything).
  That is NOT a perf regression — the metric is SKIPPED, loudly, and
  does not fail the gate. Zero-throughput-without-error still trips: a
  silent zero is a regression, not an outage.

Only the newest round is a candidate: older rounds are history (they
were legitimately slower than today's baseline) and serve solely as
baseline sources. A candidate older than the baseline it would be
judged against is skipped for the same reason.

Usage::

    python scripts/perf_sentinel.py                  # audit the repo
    python scripts/perf_sentinel.py --candidate f.json --baseline g.json
    python scripts/perf_sentinel.py --jsonl /tmp/bench.jsonl
    python scripts/perf_sentinel.py --tolerance 0.2  # widen every band

Exit codes: 0 clean (incl. outage-skips), 1 regression(s) — or no
records at all: a gate that finds nothing to compare has not passed —
2 bad invocation/unreadable input.

The repository root carries no record any more (the ``BENCH_r0*.json``
rounds predate the current toolchain and were removed); until the
benchmark PR retires this script (ROADMAP C1) a bare run reports "no
records" and fails.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

# (name, candidate keys tried in order, baseline keys tried in order,
#  direction, default tolerance). Throughputs get the ISSUE's 10%;
# serving latency/qps run wider — a shared CI box breathes harder than
# an MXU.
METRICS = [
    ("bert_tokens_per_sec",
     ("bert_base_seq128_tokens_per_sec", "value"),
     ("bert_base_seq128_tokens_per_sec", "value"), "higher", 0.10),
    ("resnet50_images_per_sec",
     ("resnet50_images_per_sec",), ("resnet50_images_per_sec",),
     "higher", 0.10),
    ("loader_images_per_sec",
     ("loader_images_per_sec", "loader_only_images_per_sec"),
     ("loader_images_per_sec", "loader_only_images_per_sec"),
     "higher", 0.15),
    ("bert_seq512_tokens_per_sec",
     ("bert_seq512_tokens_per_sec",), ("bert_seq512_tokens_per_sec",),
     "higher", 0.10),
    ("bert_seq2048_tokens_per_sec",
     ("bert_seq2048_tokens_per_sec",), ("bert_seq2048_tokens_per_sec",),
     "higher", 0.10),
    ("serving_qps", ("serving_qps", "qps"), ("serving_qps", "qps"),
     "higher", 0.25),
    ("serving_p99_ms", ("serving_p99_ms", "p99_ms"),
     ("serving_p99_ms", "p99_ms"), "lower", 0.50),
    # degraded-serving stage (bench_serving_degraded): what the fleet
    # keeps while broken. Goodputs are floors (tight — they're ratios,
    # not wall-clock); the hedge fraction is a ceiling (wide — a few
    # extra hedges on a loaded box is noise, 3x the budget is a bug)
    ("serving_degraded_goodput",
     ("serving_degraded_goodput",), ("serving_degraded_goodput",),
     "higher", 0.10),
    ("serving_degraded_high_goodput",
     ("serving_degraded_high_goodput",),
     ("serving_degraded_high_goodput",), "higher", 0.10),
    ("serving_degraded_hedge_frac",
     ("serving_degraded_hedge_frac",),
     ("serving_degraded_hedge_frac",), "lower", 1.00),
    # gradient-communication stage (bench_collective_overlap): exposed
    # wire seconds breathe with CI load (wide bands); bucket count and
    # wire bytes are deterministic functions of the model + bucket size
    # (tight bands — drift means the bucketing or wire format changed)
    ("collective_overlap_exposed_wire_s",
     ("collective_overlap_exposed_wire_s",),
     ("collective_overlap_exposed_wire_s",), "lower", 1.00),
    ("collective_overlap_ratio",
     ("collective_overlap_ratio",), ("collective_overlap_ratio",),
     "lower", 0.75),
    ("collective_overlap_bucket_count",
     ("collective_overlap_bucket_count",),
     ("collective_overlap_bucket_count",), "lower", 0.10),
    ("comm_bytes_wire_int8",
     ("comm_bytes_wire_int8",), ("comm_bytes_wire_int8",),
     "lower", 0.10),
    ("comm_wire_reduction_int4_x",
     ("comm_wire_reduction_int4_x",), ("comm_wire_reduction_int4_x",),
     "higher", 0.10),
    # fused-optimizer stage (bench_fused_optimizer / arena_smoke): the
    # opt.* byte ledger is a deterministic function of the model layout
    # and the arena packing (tight bands — drift means the packing, the
    # multi-tensor baseline, or the scope attribution changed); the
    # post-compile step wall time breathes with CI load (very wide)
    ("fused_optimizer_opt_bytes_flat",
     ("fused_optimizer_opt_bytes_flat",),
     ("fused_optimizer_opt_bytes_flat",), "lower", 0.10),
    ("fused_optimizer_bytes_reduction",
     ("fused_optimizer_bytes_reduction",),
     ("fused_optimizer_bytes_reduction",), "higher", 0.10),
    ("fused_optimizer_step_time_s",
     ("fused_optimizer_step_time_s",),
     ("fused_optimizer_step_time_s",), "lower", 1.00),
    # hotspot stage (bench_hotspot): the ranked fusion menu and the
    # attributed fraction are deterministic functions of the step HLO
    # (tight bands — shrinkage means scope labels or the parser broke);
    # the top region's headroom is a modeled time (very wide band)
    ("hotspot_count", ("hotspot_count",), ("hotspot_count",),
     "higher", 0.10),
    ("hotspot_attributed_frac",
     ("hotspot_attributed_frac",), ("hotspot_attributed_frac",),
     "higher", 0.10),
    ("hotspot_top_headroom_s",
     ("hotspot_top_headroom_s",), ("hotspot_top_headroom_s",),
     "lower", 1.00),
    # planner stage (bench_planner / plan_smoke): the candidate count
    # is a deterministic function of the device count and axis set
    # (tight band — drift means the factorization enumeration changed);
    # the winner's predicted step time is a modeled quantity fed by the
    # cost model's constants (very wide band)
    ("planner_candidates", ("planner_candidates",),
     ("planner_candidates",), "higher", 0.10),
    ("planner_predicted_step_s", ("planner_predicted_step_s",),
     ("planner_predicted_step_s",), "lower", 1.00),
    # memory stage (bench_memory / mem_smoke): the liveness model's
    # agreement with memory_analysis() and the attributed fraction are
    # deterministic functions of the step HLO (tight bands — drift
    # means the parser or the scope labels broke); the absolute peak
    # moves with any legitimate model change (wide band)
    ("memory_reconciliation",
     ("memory_reconciliation",), ("memory_reconciliation",),
     "higher", 0.10),
    ("memory_attributed_frac",
     ("memory_attributed_frac",), ("memory_attributed_frac",),
     "higher", 0.10),
    ("memory_predicted_peak_bytes",
     ("memory_predicted_peak_bytes",), ("memory_predicted_peak_bytes",),
     "lower", 0.50),
    # memory-plan stage (bench_memory_plan / remat_smoke): how far past
    # the no-remat ceiling the picked policy trains is the headline
    # capability (tight band — it must not quietly shrink below 4x);
    # the picked rung's predicted peak moves with any legitimate model
    # change (wide band); the offload exposed-wait fraction and the
    # warm step timings are CPU wall-clock (very wide bands)
    ("memory_plan_ceiling_multiple",
     ("memory_plan_ceiling_multiple",), ("memory_plan_ceiling_multiple",),
     "higher", 0.10),
    ("memory_plan_predicted_peak_bytes",
     ("memory_plan_predicted_peak_bytes",),
     ("memory_plan_predicted_peak_bytes",), "lower", 0.50),
    ("memory_plan_offload_exposed_frac",
     ("memory_plan_offload_exposed_frac",),
     ("memory_plan_offload_exposed_frac",), "lower", 1.00),
    ("memory_plan_step_s_remat",
     ("memory_plan_step_s_remat",), ("memory_plan_step_s_remat",),
     "lower", 1.00),
    # generative-decode stage (bench_decode / decode_smoke): tokens/s
    # and step latencies are shared-box wall-clock (very wide bands);
    # the continuous-vs-drain speedup and the decode-batch occupancy
    # are scheduling ratios — tight bands, a drop means the refill
    # discipline or slot accounting regressed, not the weather
    ("decode_tokens_per_s",
     ("decode_tokens_per_s",), ("decode_tokens_per_s",),
     "higher", 1.00),
    ("decode_speedup_x",
     ("decode_speedup_x",), ("decode_speedup_x",), "higher", 0.20),
    ("decode_batch_occupancy",
     ("decode_batch_occupancy",), ("decode_batch_occupancy",),
     "higher", 0.10),
    ("decode_prefill_p50_ms",
     ("decode_prefill_p50_ms",), ("decode_prefill_p50_ms",),
     "lower", 1.00),
    ("decode_p99_ms",
     ("decode_p99_ms",), ("decode_p99_ms",), "lower", 1.00),
    # per-request SLO attribution (reqtrace serving.request records):
    # TTFT/TPOT are end-to-end wall-clock under shared-box load — wide
    # bands; they exist to catch order-of-magnitude attribution bugs
    # (e.g. first-token stamped at submit instead of prefill exit), not
    # scheduler noise
    ("decode_ttft_p99_ms",
     ("decode_ttft_p99_ms",), ("decode_ttft_p99_ms",), "lower", 1.00),
    ("decode_tpot_p99_ms",
     ("decode_tpot_p99_ms",), ("decode_tpot_p99_ms",), "lower", 1.00),
    # speculative-decode stage (bench_spec_decode / spec_smoke): the
    # spec-vs-plain speedup divides two shared-box clocks — wide band;
    # the accept rate is pure verify-ledger arithmetic on fixed seeds —
    # tight band, a drop means the accept-prefix rule or the draft
    # distillation regressed, not the weather
    ("decode_spec_speedup_x",
     ("decode_spec_speedup_x",), ("decode_spec_speedup_x",),
     "higher", 1.00),
    ("decode_spec_speedup_k8_x",
     ("decode_spec_speedup_k8_x",), ("decode_spec_speedup_k8_x",),
     "higher", 1.00),
    ("decode_accept_rate",
     ("decode_accept_rate",), ("decode_accept_rate",), "higher", 0.10),
    ("decode_spec_tokens_per_s",
     ("decode_spec_tokens_per_s",), ("decode_spec_tokens_per_s",),
     "higher", 1.00),
    # serving-lifecycle stage (bench_lifecycle): fleet drain latency is
    # CPU decode wall-clock (very wide band); swap drops and the chaos
    # soak's goodput are correctness ratios — tight bands, any drift
    # means the drain/migrate/swap discipline itself regressed
    ("lifecycle_drain_p99_ms",
     ("lifecycle_drain_p99_ms",), ("lifecycle_drain_p99_ms",),
     "lower", 1.00),
    ("lifecycle_swap_dropped",
     ("lifecycle_swap_dropped",), ("lifecycle_swap_dropped",),
     "lower", 0.10),
    ("lifecycle_soak_goodput",
     ("lifecycle_soak_goodput",), ("lifecycle_soak_goodput",),
     "higher", 0.10),
    # fleet telemetry stage (bench_fleet_telemetry): both are
    # wall-clock on a loaded shared box — publish overhead is CPU-time
    # divided by worker wall, detection latency rides the scrape and
    # snapshot cadences — so the bands are very wide; the hard
    # correctness bar (merge oracle, exactly-two-alerts, goodput
    # reconciliation) is the smoke gate itself, not the sentinel
    ("fleet_agg_overhead_pct",
     ("fleet_agg_overhead_pct",), ("fleet_agg_overhead_pct",),
     "lower", 1.00),
    ("alert_detection_latency_s",
     ("alert_detection_latency_s",), ("alert_detection_latency_s",),
     "lower", 1.00),
    # disaggregated-serving stage (bench_disagg / disagg_smoke): the
    # prefix hit rate is pure workload arithmetic on fixed seeds —
    # tight band, a drop means the full-prompt keying or the insert
    # path regressed, not the weather; TTFT/handoff/tokens-per-s are
    # shared-box wall-clock (very wide bands) — the hard bars (hit
    # TTFT <= 0.5x miss, handoff bytes == plan, bit-parity) live in
    # the smoke's gates, folded into disagg_gates_pass
    ("disagg_prefix_hit_rate",
     ("disagg_prefix_hit_rate",), ("disagg_prefix_hit_rate",),
     "higher", 0.10),
    ("disagg_ttft_hit_p50_ms",
     ("disagg_ttft_hit_p50_ms",), ("disagg_ttft_hit_p50_ms",),
     "lower", 1.00),
    ("disagg_handoff_ms",
     ("disagg_handoff_ms",), ("disagg_handoff_ms",), "lower", 1.00),
    ("disagg_tokens_per_s",
     ("disagg_tokens_per_s",), ("disagg_tokens_per_s",),
     "higher", 1.00),
]


def _load_json(path):
    with open(path) as fh:
        return json.load(fh)


def _first(blob, keys):
    for k in keys:
        v = blob.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            return float(v)
    return None


def _is_outage(blob):
    """An error line whose numbers are the zeros of a run that never
    measured, not of slow code: headline value 0/absent AND an explicit
    error."""
    if not blob.get("error"):
        return False
    return not _first(blob, ("value",
                             "bert_base_seq128_tokens_per_sec"))


def _measurement_blob(raw):
    """Normalize any supported artifact into one flat metric dict.

    * driver round files ({n, cmd, rc, tail, parsed}) -> parsed (which
      may be None: rc!=0 with no JSON line — treated as an outage line)
    * bench stdout/JSONL lines and committed snapshots -> as-is
    """
    if not isinstance(raw, dict):
        return None
    if "parsed" in raw and "cmd" in raw:
        parsed = raw.get("parsed")
        if parsed is None:
            # the round produced no JSON line at all (e.g. BENCH_r03's
            # raw-traceback round): outage-shaped by construction
            return {"value": 0.0,
                    "error": f"round emitted no parseable result "
                             f"(rc={raw.get('rc')})"}
        return parsed
    return raw


def _last_jsonl_line(path):
    last = None
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                last = json.loads(line)
            except ValueError:
                continue
    return last


def _round_files(root):
    """BENCH_r*.json sorted oldest->newest by round number."""
    def key(p):
        import re
        m = re.search(r"_r(\d+)", os.path.basename(p))
        return int(m.group(1)) if m else -1
    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")), key=key)


def discover_baseline(root):
    """The newest committed measurement, searched newest-first:
    ``last_committed_measurement`` banked inside the newest round
    files, then docs/bench_latest_measured.json, then the r4 snapshot.
    Returns (blob, provenance-string) or (None, None)."""
    for path in reversed(_round_files(root)):
        try:
            blob = _measurement_blob(_load_json(path))
        except Exception:
            continue
        if not blob:
            continue
        lcm = blob.get("last_committed_measurement")
        if isinstance(lcm, dict) and _first(
                lcm, ("bert_base_seq128_tokens_per_sec", "value")):
            src = blob.get("last_committed_measurement_file") or path
            return lcm, f"{os.path.basename(path)} -> {src}"
        # a round that itself measured real numbers IS the baseline
        if not _is_outage(blob) and _first(
                blob, ("value", "bert_base_seq128_tokens_per_sec")):
            return blob, os.path.basename(path)
    for rel in ("docs/bench_latest_measured.json",
                "docs/bench_r04_measured.json"):
        path = os.path.join(root, rel)
        if os.path.exists(path):
            try:
                return _load_json(path), rel
            except Exception:
                continue
    return None, None


def discover_candidate(root, jsonl_paths=()):
    """The newest measurement to judge: the last line of any given
    JSONL artifact (newest file wins), else $PADDLE_TPU_BENCH_JSONL,
    else the newest BENCH_r*.json round. Returns (blob, provenance)."""
    paths = [p for p in jsonl_paths if p and os.path.exists(p)]
    env = os.environ.get("PADDLE_TPU_BENCH_JSONL", "")
    if not paths and env and os.path.exists(env):
        paths = [env]
    if paths:
        newest = max(paths, key=os.path.getmtime)
        blob = _last_jsonl_line(newest)
        if blob is not None:
            return _measurement_blob(blob), newest
    rounds = _round_files(root)
    if rounds:
        path = rounds[-1]
        try:
            return _measurement_blob(_load_json(path)), \
                os.path.basename(path)
        except Exception as e:
            raise SystemExit(f"perf_sentinel: unreadable {path}: {e}")
    return None, None


def compare(candidate, baseline, tolerance=None):
    """Per-metric verdicts. Returns a list of dicts
    {metric, verdict, candidate, baseline, band} where verdict is one
    of regression/ok/improved/outage/no_data."""
    out = []
    outage = _is_outage(candidate)
    for name, ckeys, bkeys, direction, tol in METRICS:
        tol = tolerance if tolerance is not None else tol
        base = _first(baseline, bkeys)
        cand = _first(candidate, ckeys)
        row = {"metric": name, "candidate": cand, "baseline": base,
               "direction": direction, "tolerance": tol}
        if base is None or cand is None:
            row["verdict"] = "no_data"
        elif outage and not cand:
            # zero riding an error line: the run died, the code
            # didn't get slower — skip, don't fail
            row["verdict"] = "outage"
        elif direction == "higher":
            floor = base * (1.0 - tol)
            row["band"] = round(floor, 3)
            row["verdict"] = ("regression" if cand < floor else
                              "improved" if cand > base else "ok")
        else:
            ceil = base * (1.0 + tol)
            row["band"] = round(ceil, 3)
            row["verdict"] = ("regression" if cand > ceil else
                              "improved" if cand < base else "ok")
        out.append(row)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo-root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--candidate", default=None,
                    help="explicit candidate measurement JSON file "
                         "(default: newest JSONL artifact / round file)")
    ap.add_argument("--baseline", default=None,
                    help="explicit baseline JSON file (default: newest "
                         "committed measurement)")
    ap.add_argument("--jsonl", action="append", default=[],
                    help="bench/smoke JSONL artifact; last parseable "
                         "line is the candidate (repeatable)")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override every per-metric band (fraction, "
                         "e.g. 0.1)")
    args = ap.parse_args(argv)
    root = args.repo_root

    try:
        if args.baseline:
            baseline, base_src = _load_json(args.baseline), args.baseline
        else:
            baseline, base_src = discover_baseline(root)
        if args.candidate:
            candidate = _measurement_blob(_load_json(args.candidate))
            cand_src = args.candidate
        else:
            candidate, cand_src = discover_candidate(root, args.jsonl)
    except SystemExit:
        raise
    except Exception as e:
        print(f"perf_sentinel: cannot read inputs: {e}", file=sys.stderr)
        return 2

    if baseline is None or candidate is None:
        missing = "baseline" if baseline is None else "candidate"
        print(json.dumps({"sentinel": "perf", "ok": False,
                          "note": f"no records: no {missing} "
                                  f"measurement found — nothing was "
                                  f"compared, so nothing passed"}))
        return 1

    rows = compare(candidate, baseline, tolerance=args.tolerance)
    regressions = [r for r in rows if r["verdict"] == "regression"]
    improved = [r for r in rows if r["verdict"] == "improved"]
    outages = [r for r in rows if r["verdict"] == "outage"]

    for r in rows:
        if r["verdict"] == "no_data":
            continue
        mark = {"regression": "FAIL", "outage": "SKIP",
                "improved": "  up", "ok": "  ok"}[r["verdict"]]
        band = f" (band {r.get('band')})" if "band" in r else ""
        print(f"[{mark}] {r['metric']}: {r['candidate']} vs baseline "
              f"{r['baseline']}{band}", file=sys.stderr)
    if outages:
        err = str(candidate.get("error", ""))[:160]
        print(f"[note] outage-shaped candidate (error: {err}) — "
              f"{len(outages)} metric(s) skipped, not failed",
              file=sys.stderr)
    if improved and not regressions:
        print("[note] candidate beats the baseline — consider re-banking "
              "docs/bench_latest_measured.json", file=sys.stderr)

    print(json.dumps({
        "sentinel": "perf", "ok": not regressions,
        "candidate": cand_src, "baseline": base_src,
        "regressions": regressions,
        "verdicts": {r["metric"]: r["verdict"] for r in rows
                     if r["verdict"] != "no_data"},
    }))
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
