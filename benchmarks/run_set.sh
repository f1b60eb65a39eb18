#!/bin/bash
# One set of runs of one cell, in one process after another, each with
# another seed; every run's stdout (its last line is the result) goes
# into <out_dir>.  Meant to be ONE chip-tool command:
#   chiprun --chips 1 --timeout 3000 -- \
#     bash benchmarks/run_set.sh ec83_write chiprun_out/setA 51 2147483700 6 0
set -u
workload=$1 out=$2 seconds=$3 seed0=$4 n=$5 trace=${6:-0}
mkdir -p "$out"
for ((i = 0; i < n; i++)); do
  f="$out/$workload.t$trace.$((seed0 + i))"
  python3 benchmarks/run.py --workload "$workload" --seed $((seed0 + i)) \
    --seconds "$seconds" --trace "$trace" > "$f.out" 2> "$f.err"
  echo "rc=$? $(tail -n 1 "$f.out" | cut -c1-1200)"
done
