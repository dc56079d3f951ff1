#!/usr/bin/env bash
# CI gate for the zero-copy flat parameter arena: the same MLP + Adam
# to_static step, per-leaf vs flat_arena=True, must be bit-identical,
# leave zero concat/gather/scatter attributed to the optimizer scope,
# and compile exactly once with zero recompiles over the run.
# Tier-1-safe: small MLP, CPU, seconds.
#
# Usage: scripts/arena_smoke.sh [out_dir]
# The monitor JSONL lands in out_dir (default
# /tmp/paddle_tpu_arena_smoke); the last stdout line is one JSON
# result record.
set -euo pipefail
cd "$(dirname "$0")/.."
OUT_DIR="${1:-/tmp/paddle_tpu_arena_smoke}"
JAX_PLATFORMS=cpu python scripts/arena_smoke.py --out-dir "$OUT_DIR"
