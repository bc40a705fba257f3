#!/bin/bash
# One set of runs of a cell, in one call on the chip (from the repo's root):
#   bash benchmarks/tools/run_set.sh <workload> <tag> <seconds> <trace_seed|0> <seed>...
# Each run's output goes to chiprun_out/<tag>_<seed>.log; the result lines
# are echoed. A last run with --trace 1 is made on <trace_seed> unless 0.
W=$1; TAG=$2; SEC=$3; TS=$4; shift 4
mkdir -p chiprun_out
for s in "$@"; do
  python3 benchmarks/run.py --workload "$W" --seed "$s" --seconds "$SEC" --trace 0 \
    > "chiprun_out/${TAG}_$s.log" 2>&1
  echo "rc=$?" >> "chiprun_out/${TAG}_$s.log"
  grep -h '"phase": "window"\|^{"correct"\|rc=' "chiprun_out/${TAG}_$s.log" | cut -c1-900
done
if [ "$TS" != "0" ]; then
  python3 benchmarks/run.py --workload "$W" --seed "$TS" --seconds "$SEC" --trace 1 \
    > "chiprun_out/${TAG}_trace_$TS.log" 2>&1
  echo "rc=$?" >> "chiprun_out/${TAG}_trace_$TS.log"
  grep -h '"phase": "window"\|"phase": "end_to\|^{"correct"\|rc=' \
    "chiprun_out/${TAG}_trace_$TS.log" | cut -c1-3500
fi
