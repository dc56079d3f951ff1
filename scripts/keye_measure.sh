#!/bin/bash
# What the keye_vl2_30b_a3b.sparse_causal_16k cell's limits and PERF.md's
# numbers for it were read with, one phase a word, in the order given:
#
#   chiprun --timeout 3500 -- bash scripts/keye_measure.sh readings archive faults
#   chiprun --timeout 2400 -- bash scripts/keye_measure.sh old_cells
#   chiprun --timeout 1500 -- bash scripts/keye_measure.sh final
#
# (2,797 s and 934 s on 2026-10-03, PR 47's second round; both directories
# under .benchmark_work/ are made off the chip, before the call.)
#
# readings   benchmark/control.py on twelve seeds through one compiled step
#            (the program against the float32 reference) and the float8
#            control on two of them            -> chiprun_out/keye/control2.*
# faults     scripts/cell_faults.py, the three planted faults at the
#            cell's own size                   -> chiprun_out/keye/faults.*
# archive    the cell's own command from .benchmark_work/committed (made
#            off the chip: git archive $(git write-tree) | tar -x -C ...),
#            one traced run and six untraced, a seed each: `correct`
#            against the COMMITTED limits      -> chiprun_out/keye/final_*.log
# final      the same from the tree as handed in, a traced run and five
#            untraced on seeds of their own    -> chiprun_out/keye/final_*.log
# old_cells  sdar and smallthinker, traced: the parent (.benchmark_work/
#            parent: git archive HEAD, with this tree's BENCHMARK.json and
#            benchmark/ laid over it, as the driver's traced runs have it)
#            then this tree, and the new cell on that parent, which has to
#            fail at once                      -> chiprun_out/keye/old_*.log
# Every phase prints its runs' last lines; a phase that fails does not stop
# the next.
cd "$(dirname "$0")/.." || exit 2
ROOT=$PWD
CELL=keye_vl2_30b_a3b.sparse_causal_16k
OUT=$ROOT/chiprun_out/keye
mkdir -p "$OUT"

one_run() {     # <tree> <cell> <seed> <trace> <log>
  local t0=$(date +%s)
  (cd "$1" && python3 benchmark/run.py --workload "$2" --seed "$3" \
      --seconds 20 --trace "$4") > "$5" 2>&1
  echo "RC=$? tree=$1 $2 seed=$3 trace=$4 wall=$(( $(date +%s) - t0 ))s"
  grep -E "^\[(setup|correct|phases\] kernel)" "$5" | cut -c1-300
  tail -1 "$5" | cut -c1-3000
}

for phase in "$@"; do
  echo "=== $phase $(date -u +%H:%M:%S)"
  case $phase in
  readings)
    python3 benchmark/control.py --workload $CELL \
      --seeds 3000004731,3000004732,3000004733,3000004734,3000004735,3000004736,3000004737,3000004738,3000004739,3000004740,3000004741,3000004742 \
      --control-seeds 3000004731,3000004732 \
      --out "$OUT/control2.json" > "$OUT/control2.log" 2>&1
    echo "RC=$?"; grep -E "^\[(summary|control)\]" "$OUT/control2.log" | cut -c1-600
    ;;
  faults)
    python3 scripts/cell_faults.py --workload $CELL --seed 3000004751 \
      --faults selection_left_out,indexer_loss_left_out,position_rows_collapsed \
      --out "$OUT/faults.json" > "$OUT/faults.log" 2>&1
    echo "RC=$?"; grep -E "^\[fault\]" "$OUT/faults.log" | cut -c1-400
    ;;
  archive)
    one_run "$ROOT/.benchmark_work/committed" $CELL 3000004761 1 "$OUT/final_3000004761.t1.log"
    for seed in 3000004762 3000004763 3000004764 3000004765 3000004766 3000004767; do
      one_run "$ROOT/.benchmark_work/committed" $CELL $seed 0 "$OUT/final_$seed.log"
    done
    ;;
  final)
    one_run "$ROOT/.benchmark_work/committed" $CELL 3000004781 1 "$OUT/final_3000004781.t1.log"
    for seed in 3000004782 3000004783 3000004784 3000004785 3000004786; do
      one_run "$ROOT/.benchmark_work/committed" $CELL $seed 0 "$OUT/final_$seed.log"
    done
    ;;
  old_cells)
    for cell in sdar_30b_a3b_chat.block_diffusion_8k smallthinker_21b_a3b.causal_pretrain_16k; do
      one_run "$ROOT/.benchmark_work/parent" $cell 3000004771 1 "$OUT/old_parent.$cell.log"
      one_run "$ROOT" $cell 3000004771 1 "$OUT/old_change.$cell.log"
    done
    # the new cell on the parent under this tree's benchmark files: it has
    # to fail at once
    one_run "$ROOT/.benchmark_work/parent" $CELL 3000004771 1 "$OUT/old_parent.$CELL.log"
    ;;
  *) echo "no phase $phase"; exit 2 ;;
  esac
done
