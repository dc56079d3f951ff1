#!/usr/bin/env bash
# Full test battery: overrides pytest.ini's `-m "not slow"` default so
# the slow-marked gold parity suites (SPMD 8-dev shard_map tests,
# long-seq kernels) actually run, with the monitor runtime enabled so
# the run leaves a JSONL evidence stream behind.
#
#   scripts/run_full_suite.sh [extra pytest args...]
#
# Env: PADDLE_TPU_SUITE_PLATFORM=cpu|tpu (default cpu) picks the jax
# backend; the monitor sink lands in ${PADDLE_TPU_MONITOR_DIR:-/tmp/paddle_tpu_suite}.
set -u
cd "$(dirname "$0")/.."

PLATFORM="${PADDLE_TPU_SUITE_PLATFORM:-cpu}"
MONITOR_DIR="${PADDLE_TPU_MONITOR_DIR:-/tmp/paddle_tpu_suite}"
mkdir -p "$MONITOR_DIR"

JAX_PLATFORMS="$PLATFORM" \
PADDLE_TPU_MONITOR=1 \
PADDLE_TPU_MONITOR_DIR="$MONITOR_DIR" \
python -m pytest tests/ -q -m "" \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:randomly \
    "$@"
rc=$?

# span-tracer gate: Perfetto-valid export, separate producer/step
# tracks, overlapping prefetch/step spans, disabled mode records nothing
echo ""
echo "-- trace smoke gate --"
bash scripts/trace_smoke.sh "$MONITOR_DIR/trace_smoke"
trc=$?
[ $trc -ne 0 ] && rc=$((rc == 0 ? trc : rc))

# serving gate: 200 concurrent requests must coalesce (batch_fill > 1),
# mint zero post-warmup executables, lose no futures, record p99 JSONL
echo ""
echo "-- serving smoke gate --"
bash scripts/serving_smoke.sh "$MONITOR_DIR/serving_smoke"
srv=$?
[ $srv -ne 0 ] && rc=$((rc == 0 ? srv : rc))

# serving chaos gate: self-healing fleet under injected faults —
# replica-hang failover (goodput >= 0.90, breaker re-closes via
# half-open probe), hedge-win under a straggler inside the 5% budget,
# 2x-overload priority shed (high goodput >= 0.95, every shed error
# retryable with retry-after), zero lost futures throughout
echo ""
echo "-- serving chaos smoke gate --"
bash scripts/serving_chaos_smoke.sh "$MONITOR_DIR/serving_chaos_smoke"
svc=$?
[ $svc -ne 0 ] && rc=$((rc == 0 ? svc : rc))

# telemetry gate: scrape /metrics + /healthz mid-fit (OpenMetrics with
# executor/prefetch/mem_* series, live watchdog state), clean teardown
echo ""
echo "-- export smoke gate --"
bash scripts/export_smoke.sh "$MONITOR_DIR/export_smoke"
exp=$?
[ $exp -ne 0 ] && rc=$((rc == 0 ? exp : rc))

# chaos gate: every injected fault class absorbed end to end — loader
# retry, NaN skip, preempt save/resume, quarantine, plus the sharded
# trio (preempt-triggered sharded save, mesh-resize resume at the exact
# next step, corrupt-one-shard-never-wins quorum fallback)
echo ""
echo "-- chaos smoke gate --"
bash scripts/chaos_smoke.sh "$MONITOR_DIR/chaos_smoke"
chs=$?
[ $chs -ne 0 ] && rc=$((rc == 0 ? chs : rc))

# comm gate: bucketed/overlapped/quantized grad collectives on 8
# virtual CPU devices — overlap hides wire time (<=60% of exact,
# reduce spans overlap backward in the Chrome trace), no compile tax,
# int8/int4 wire-byte honesty, lag-1 resumes bit-identical
echo ""
echo "-- comm smoke gate --"
bash scripts/comm_smoke.sh "$MONITOR_DIR/comm_smoke"
cms=$?
[ $cms -ne 0 ] && rc=$((rc == 0 ? cms : rc))

# profile gate: the 2-layer to_static step must attribute >=90% of its
# flops to named scopes, reconcile with cost_analysis() within 1%, and
# rank a non-empty hotspot menu with one JSONL record per region
echo ""
echo "-- profile smoke gate --"
bash scripts/profile_smoke.sh "$MONITOR_DIR/profile_smoke"
prf=$?
[ $prf -ne 0 ] && rc=$((rc == 0 ? prf : rc))

# arena gate: per-leaf vs flat_arena Adam must be bit-identical, cut
# opt.* bytes >=40% vs the multi-tensor baseline, leave zero
# concat/gather/scatter in the optimizer scope, and compile exactly
# once with zero recompiles
echo ""
echo "-- arena smoke gate --"
bash scripts/arena_smoke.sh "$MONITOR_DIR/arena_smoke"
arn=$?
[ $arn -ne 0 ] && rc=$((rc == 0 ? arn : rc))

# planner gate: MegatronConfig(mesh_plan=MEGATRON_RULES) reproduces the
# hand dp/tp layout bit-identically, fit(mesh_plan=) mints zero extra
# executables, the advisor table is non-empty + rank-stable, and its
# predicted-fastest layout is the measured-fastest in the dp8-vs-dp2tp4
# A/B on 8 virtual devices
echo ""
echo "-- plan smoke gate --"
bash scripts/plan_smoke.sh "$MONITOR_DIR/plan_smoke"
pln=$?
[ $pln -ne 0 ] && rc=$((rc == 0 ? pln : rc))

# memory gate: the to_static step's simulated HBM peak must reconcile
# with memory_analysis() within 10% and attribute >=90% of live-at-peak
# bytes, an injected RESOURCE_EXHAUSTED must leave the full oom flight
# bundle, and the planner must never auto-pick an over-budget layout
echo ""
echo "-- mem smoke gate --"
bash scripts/mem_smoke.sh "$MONITOR_DIR/mem_smoke"
mem=$?
[ $mem -ne 0 ] && rc=$((rc == 0 ? mem : rc))

# decode gate: continuous-batching generative decode — slot churn with
# zero lost futures and zero post-warmup compiles, KV-pool bytes equal
# to the closed-form budget prediction under a virtual HBM limit,
# continuous refill >= 2x the drain run-to-completion baseline's
# tokens/s, and a tokens_floor supervisor scale-up off the live decode
# SLO window
echo ""
echo "-- decode smoke gate --"
bash scripts/decode_smoke.sh "$MONITOR_DIR/decode_smoke"
dcd=$?
[ $dcd -ne 0 ] && rc=$((rc == 0 ? dcd : rc))

# spec gate: sampled + speculative decoding — greedy spec bit-identical
# to non-spec, sampled self-draft bit-identical with every proposal
# accepted, seed-reproducible streams across admission orders, and the
# loadgen A/B on the distilled pair (>= 1.5x at k=4, >= 2.0x at k=8,
# accept >= 0.9, zero post-warmup compiles in every arm)
echo ""
echo "-- spec smoke gate --"
bash scripts/spec_smoke.sh "$MONITOR_DIR/spec_smoke"
spc=$?
[ $spc -ne 0 ] && rc=$((rc == 0 ? spc : rc))

# memory-plan gate: under a virtual HBM budget, a model 4x past the
# no-remat ceiling trains under the auto-picked policy (predicted peak
# under the limit pre-flight), offload spans ride their own track with
# exposed wait <=40% of the transfer, the picker never chooses an
# infeasible or host-over-budget rung, remat/offload bit-identical
echo ""
echo "-- remat smoke gate --"
bash scripts/remat_smoke.sh "$MONITOR_DIR/remat_smoke"
rmt=$?
[ $rmt -ne 0 ] && rc=$((rc == 0 ? rmt : rc))

# request-tracing gate: under injected straggler + hung-replica faults,
# every request (hedged, failed-over, shed-then-retried included) emits
# exactly one serving.request record whose stage waterfall reconciles
# with the measured e2e within 5%; slo.ttft/tpot p99 gauges live on
# /metrics; per-KV-slot occupancy lanes + linked flow arrows in the
# Chrome export; disabled mode records nothing
echo ""
echo "-- request smoke gate --"
bash scripts/request_smoke.sh "$MONITOR_DIR/request_smoke"
rqs=$?
[ $rqs -ne 0 ] && rc=$((rc == 0 ? rqs : rc))

# serving-lifecycle gate: an injected preemption drains its replica and
# migrates queued + in-flight decode streams with zero loss and
# bit-identical outputs; SIGTERM drains the whole fleet (in-flight
# completes, post-drain submits shed); a rolling weight hot-swap lands
# under load with zero dropped requests and zero new executables; a
# corrupt publish is refused by quorum validation and quarantined
echo ""
echo "-- lifecycle smoke gate --"
bash scripts/lifecycle_smoke.sh "$MONITOR_DIR/lifecycle_smoke"
lcy=$?
[ $lcy -ne 0 ] && rc=$((rc == 0 ? lcy : rc))

# fleet telemetry: a 4-process decode fleet publishes snapshots into a
# shared directory; the aggregator's merged counters/percentiles must
# match the per-worker oracle, exactly the two injected anomalies
# (straggler + compile storm) must fire and resolve as alerts citing
# source and series — and land in the supervisor's decision ledger —
# the goodput ledger must reconcile to wall time, and publishing must
# cost <= 1% of worker wall (zero files with the monitor disabled)
echo ""
echo "-- telemetry smoke gate --"
bash scripts/telemetry_smoke.sh "$MONITOR_DIR/telemetry_smoke"
tlm=$?
[ $tlm -ne 0 ] && rc=$((rc == 0 ? tlm : rc))

# disaggregated-serving gate: prefill/decode split streams bit-identical
# to the single-engine oracle through a mid-stream decode drain, handoff
# bytes exactly equal the comm-model prediction, prefix hits skip
# prefill with hit TTFT <= 0.5x miss and zero new executables, each
# pool's supervisor scales on its own SLO (prefill: queue depth / TTFT
# ceiling; decode: tokens/s floor), and goodput holds >= 0.90 with one
# prefill replica hung
echo ""
echo "-- disagg smoke gate --"
bash scripts/disagg_smoke.sh "$MONITOR_DIR/disagg_smoke"
dsg=$?
[ $dsg -ne 0 ] && rc=$((rc == 0 ? dsg : rc))

latest=$(ls -t "$MONITOR_DIR"/events-*.jsonl 2>/dev/null | head -1)
echo ""
echo "monitor JSONL: ${latest:-<none written>} (dir: $MONITOR_DIR)"
exit $rc
