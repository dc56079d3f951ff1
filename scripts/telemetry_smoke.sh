#!/usr/bin/env bash
# CI gate for the fleet telemetry plane (monitor/fleet.py + alerts.py):
# a 4-process decode fleet publishes versioned metric snapshots into a
# shared directory while one worker drags its ticks (replica_slow
# fault) and one mints a post-warmup compile burst. The parent's
# FleetAggregator + AnomalyDetector + AlertManager must: merge counters
# to the per-worker oracle exactly, land merged p50/p99 within one
# histogram bucket of the union-of-events percentile, fire AND resolve
# exactly the two expected alerts (straggler + compile storm, each
# naming source and series, both cited in the supervisor's decision
# ledger), reconcile the goodput ledger to wall time within 5%, keep
# publish overhead <= 1% of worker wall, and publish NOTHING with the
# monitor disabled. CPU-only, ~1 min.
#
# Usage: scripts/telemetry_smoke.sh [out_dir]
# The last stdout line is one JSON result record.
set -euo pipefail
cd "$(dirname "$0")/.."
OUT_DIR="${1:-/tmp/paddle_tpu_telemetry_smoke}"
JAX_PLATFORMS=cpu \
python scripts/telemetry_smoke.py --out-dir "$OUT_DIR"
