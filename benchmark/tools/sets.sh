#!/bin/bash
# One process per run, as the driver runs them; every run's whole output is
# kept under chiprun_out/<label>/ and its result line is echoed.
#   chiprun --chips 1 -- bash benchmark/tools/sets.sh <label> <cell> <seconds> <trace 0|1> <seed>...
# The numbers of PERF.md come from calls of this script (and calibrate.py);
# spread.py reads the logs it leaves.
set -u
label=$1; cell=$2; seconds=$3; trace=$4; shift 4
out=chiprun_out/$label
mkdir -p "$out"
for seed in "$@"; do
  t0=$(date +%s)
  python3 -m benchmark.run --workload "$cell" --seed "$seed" --seconds "$seconds" \
      --trace "$trace" > "$out/$cell.t$trace.$seed.log" 2>&1
  rc=$?
  echo "== $cell seed $seed trace $trace rc=$rc $(( $(date +%s) - t0 ))s"
  grep -E '^\[(setup|segments|batches|leaf|correct)' "$out/$cell.t$trace.$seed.log" | cut -c1-700
  tail -n 1 "$out/$cell.t$trace.$seed.log" | cut -c1-1500
done
