#!/bin/bash
# One cell, several seeds, in one call on the chip:
#   chiprun --chips 1 --timeout 3000 -- bash benchmark/tools/run_set.sh <tag> <workload> <seconds> <trace> <seed>...
# Each run's standard output goes to chiprun_out/<tag>.<seed>.out, its errors
# to .err, its side file to chiprun_out/<tag>.side/. Then
#   python3 benchmark/tools/spread.py chiprun_out/<tag>.*.out
# gives medians and spreads as the bounds' rule reads them.
tag=$1; workload=$2; seconds=$3; trace=$4; shift 4
mkdir -p chiprun_out/$tag.side
for seed in "$@"; do
  s0=$(date +%s)
  python3 benchmark/run.py --workload "$workload" --seed "$seed" --seconds "$seconds" --trace "$trace" \
    > "chiprun_out/$tag.$seed.out" 2> "chiprun_out/$tag.$seed.err"
  echo "seed $seed rc=$? wall=$(( $(date +%s) - s0 ))s"
  tail -n 1 "chiprun_out/$tag.$seed.out" | cut -c1-1500
  grep "NOT WITHIN\|Error" "chiprun_out/$tag.$seed.err" | tail -n 3
done
cp benchmark_out/*.json "chiprun_out/$tag.side/" 2>/dev/null
exit 0
