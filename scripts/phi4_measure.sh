#!/bin/bash
# What the phi4_mini_flash.causal_pretrain cell's limits and PERF.md's
# numbers for it were read with (PR 50), one phase a word, in the order
# given:
#
#   chiprun --timeout 1500 -- bash scripts/phi4_measure.sh first
#   chiprun --timeout 3300 -- bash scripts/phi4_measure.sh readings tune
#   chiprun --timeout 3000 -- bash scripts/phi4_measure.sh parent faults
#   chiprun --timeout 3000 -- bash scripts/phi4_measure.sh archive
#
# (261, 849 and 319 s on 2026-10-05; .benchmark_work/parent.tar and
# .benchmark_work/committed are made off the chip, before the call: git
# archive <parent> -o ..., git archive $(git write-tree) | tar -x -C ...)
#
# first     the scan pair alone (scripts/bench_selective_scan.py) and the
#           cell's first traced run           -> chiprun_out/phi4/first_*
# readings  benchmark/control.py on twelve seeds through one compiled step
#           (the program against the float32 reference) and the float8
#           control on three of them          -> chiprun_out/phi4/control.*
# tune      scripts/tune_flash.py --cells phi4 -> chiprun_out/phi4/tune.txt
# parent    the new cell on the parent commit with this tree's
#           BENCHMARK.json and benchmark/ laid over it, as the driver has
#           it: it has to fail at once        -> chiprun_out/phi4/parent.txt
# faults    scripts/cell_faults.py, the five planted faults at the cell's
#           own size                          -> chiprun_out/phi4/faults.*
# archive   the cell's own command from .benchmark_work/committed, a
#           traced run and six untraced, a seed each: `correct` against
#           the COMMITTED limits              -> chiprun_out/phi4/final_*.log
# Every phase prints its runs' last lines; a phase that fails does not stop
# the next.
cd "$(dirname "$0")/.." || exit 2
ROOT=$PWD
CELL=phi4_mini_flash.causal_pretrain
OUT=$ROOT/chiprun_out/phi4
mkdir -p "$OUT"

one_run() {     # <tree> <seed> <trace> <log>
  local t0=$(date +%s)
  (cd "$1" && python3 benchmark/run.py --workload $CELL --seed "$2" \
      --seconds 20 --trace "$3") > "$4" 2>&1
  echo "RC=$? tree=$1 seed=$2 trace=$3 wall=$(( $(date +%s) - t0 ))s"
  grep -E "^\[(setup|correct|result|phases\] kernel)" "$4" | cut -c1-300
  tail -1 "$4" | cut -c1-3000
}

for phase in "$@"; do
  echo "=== $phase $(date -u +%H:%M:%S)"
  case $phase in
  first)
    python3 scripts/bench_selective_scan.py --iters 10 --chunks 32,64,128 \
      > "$OUT/first_bench.txt" 2>&1
    tail -4 "$OUT/first_bench.txt"
    one_run "$ROOT" 3000050001 1 "$OUT/first_trace.txt"
    ;;
  readings)
    python3 benchmark/control.py --workload $CELL \
      --seeds 3000050101,3000050102,3000050103,3000050104,3000050105,3000050106,3000050107,3000050108,3000050109,3000050110,3000050111,3000050112 \
      --control-seeds 3000050101,3000050102,3000050103 \
      --out "$OUT/control.json" > "$OUT/control.log" 2>&1
    echo "RC=$?"; grep -E "^\[(summary|control)\]" "$OUT/control.log" | cut -c1-600
    ;;
  tune)
    python3 -u scripts/tune_flash.py --cells phi4 --steps 10 \
      > "$OUT/tune.txt" 2>&1
    echo "RC=$?"; tail -20 "$OUT/tune.txt" | cut -c1-200
    ;;
  parent)
    rm -rf .benchmark_work/parent && mkdir -p .benchmark_work/parent \
      && tar -x -C .benchmark_work/parent -f .benchmark_work/parent.tar \
      && cp -r benchmark BENCHMARK.json .benchmark_work/parent/
    (cd .benchmark_work/parent && timeout 600 python3 benchmark/run.py \
        --workload $CELL --seed 3000050201 --seconds 20 --trace 0) \
      > "$OUT/parent.txt" 2>&1
    echo "parent_rc=$?"; tail -2 "$OUT/parent.txt" | cut -c1-300
    ;;
  faults)
    python3 scripts/cell_faults.py --workload $CELL --seed 3000050701 \
      --faults lambda_taken_as_zero,diff_window_ignored,memory_behind_the_gate,cross_reads_its_own_stream,bfloat16_scan_state \
      --out "$OUT/faults.json" > "$OUT/faults.log" 2>&1
    echo "RC=$?"; grep -E "^\[fault\]" "$OUT/faults.log" | cut -c1-400
    ;;
  archive)
    tree=$ROOT/.benchmark_work/committed
    one_run "$tree" 3000050301 1 "$OUT/final_trace.log"
    for seed in 3000050302 3000050303 3000050304 3000050305 3000050306 \
        3000050307; do
      one_run "$tree" $seed 0 "$OUT/final_$seed.log"
    done
    ;;
  *) echo "unknown phase $phase";;
  esac
done
