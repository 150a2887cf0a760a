#!/bin/sh
# Runs cells of the benchmark one after another, one process per run, the
# way a check does; each run's output goes to $RUNS_DIR/<tag>.{out,err}
# (default ./runs).
#   sh bench/tools/runs.sh "<cell> <seed> <seconds> <trace>" ...
dir=${RUNS_DIR:-runs}
mkdir -p "$dir"
for spec in "$@"; do
  set -- $spec
  tag="$1.s$2.t$4"
  start=$(date +%s)
  timeout -k 10 1200 python3 bench/run.py --workload "$1" --seed "$2" \
    --seconds "$3" --trace "$4" > "$dir/$tag.out" 2> "$dir/$tag.err"
  rc=$?
  end=$(date +%s)
  echo "$tag rc=$rc wall=$((end - start))s"
  tail -n 1 "$dir/$tag.out" | cut -c1-1500
  tail -n 9 "$dir/$tag.err"
done
