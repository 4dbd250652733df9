#!/bin/sh
# Run several cells in one chip call: tools/chain.sh <outdir> <cell>:<seed>:<seconds>:<trace> ...
out=chiprun_out/$1; shift
mkdir -p "$out"
n=0
for spec in "$@"; do
  cell=${spec%%:*}; rest=${spec#*:}; seed=${rest%%:*}; rest=${rest#*:}; secs=${rest%%:*}; trace=${rest#*:}
  n=$((n + 1)); log="$out/$cell.s$seed.r$(printf %02d $n).t$trace.log"
  start=$(date +%s)
  python3 perfbench/run.py --workload "$cell" --seed "$seed" --seconds "$secs" --trace "$trace" >"$log" 2>"$log.err"
  rc=$?
  echo "== $spec rc=$rc wall=$(( $(date +%s) - start ))s"
  tail -n 1 "$log" | cut -c1-3000
  grep -v "Transparent hugepages\|warnings.warn" "$log.err" | tail -n 8
done
