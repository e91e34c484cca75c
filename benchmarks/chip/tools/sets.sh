#!/usr/bin/env bash
# Run one cell as a benchmark check does: two sets of the same seeds, then
# traced runs on fresh seeds; one line per run in <out>/<cell>.runs.
#   benchmarks/chip/tools/sets.sh <cell> <seconds> "<set seeds>" "<traced seeds>" <out>
set -u
cell=$1 seconds=$2 seeds=$3 traced=$4 out=$5
mkdir -p "$out"
one() {  # seed trace set
  local t0=$SECONDS
  python3 benchmarks/chip/run.py --workload "$cell" --seed "$1" \
    --seconds "$seconds" --trace "$2" > "$out/$cell.$3.$1.out" \
    2> "$out/$cell.$3.$1.err"
  echo "{\"set\": \"$3\", \"seed\": $1, \"rc\": $?, \"wall_s\": $((SECONDS - t0)), \"line\": $(tail -1 "$out/$cell.$3.$1.out" | grep '^{' || echo null)}" >> "$out/$cell.runs"
  grep -v arn "$out/$cell.$3.$1.err" | tail -3
}
for s in $seeds; do one "$s" 0 a; done
for s in $seeds; do one "$s" 0 b; done
for s in $traced; do one "$s" 1 t; done
