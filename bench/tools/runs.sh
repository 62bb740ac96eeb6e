#!/bin/bash
# Run one cell several times in one call, one seed per run, and keep
# each run's result line and the end of its standard error.
#   bash bench/tools/runs.sh <workload> <seconds> <trace> <out dir> <seed>...
w=$1; secs=$2; tr=$3; out=$4; shift 4
mkdir -p "$out"
for seed in "$@"; do
  s=$(date +%s)
  python3 bench/run.py --workload "$w" --seed "$seed" --seconds "$secs" \
    --trace "$tr" > "$out/$seed.out" 2> "$out/$seed.err"
  rc=$?
  echo "seed=$seed rc=$rc wall=$(( $(date +%s) - s ))s"
  grep '^bench: {' "$out/$seed.err" | cut -c1-400
  tail -n 1 "$out/$seed.out" | cut -c1-700
done
