#!/usr/bin/env bash
# CI gate for the live telemetry plane: train a tiny hapi.Model with
# fit(metrics_port=0), scrape /metrics + /healthz + /snapshot MID-RUN
# (must parse as OpenMetrics with executor counters, at least one
# sampled mem_* gauge, and live watchdog/NaN-guard health), and prove
# monitor.disable() frees the port and every thread.
# Tier-1-safe: tiny MLP, CPU, seconds.
#
# Usage: scripts/export_smoke.sh [out_dir]
# The monitor JSONL lands in out_dir (default
# /tmp/paddle_tpu_export_smoke); the last stdout line is one JSON
# result record.
set -euo pipefail
cd "$(dirname "$0")/.."
OUT_DIR="${1:-/tmp/paddle_tpu_export_smoke}"
JAX_PLATFORMS=cpu python scripts/export_smoke.py --out-dir "$OUT_DIR"
