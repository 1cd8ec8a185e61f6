#!/bin/bash
# Two sets of runs of one cell with the same seeds in both, then traced
# runs, from the directory it is started in (a checkout). Results go to
# $OUT (default chiprun_out), one file per run; a summary line per run
# on standard output.
#   usage: measure_sets.sh <workload> <tag> "<seeds>" "<trace seeds>" [seconds]
W=$1; T=$2; SEEDS=$3; TRACED=$4; S=${5:-20}
OUT=${OUT:-chiprun_out}; mkdir -p "$OUT"
for set in 1 2; do for seed in $SEEDS; do
  f="$OUT/final_${T}_set${set}_${seed}"
  python3 benchmarks/run.py --workload "$W" --seed "$seed" --seconds "$S" --trace 0 > "$f.out" 2> "$f.err"
  echo "rc=$? set=$set seed=$seed $(tail -n 1 "$f.out" | cut -c1-330)"
done; done
for seed in $TRACED; do
  f="$OUT/final_${T}_trace_${seed}"
  python3 benchmarks/run.py --workload "$W" --seed "$seed" --seconds "$S" --trace 1 > "$f.out" 2> "$f.err"
  echo "rc=$? trace seed=$seed $(tail -n 1 "$f.out" | cut -c1-700)"
done
