#!/bin/bash
# Runs of one cell, each a process of its own, results under chiprun_out/:
#   chiprun --timeout 2400 -- bash benchmark/tools/sets.sh <workload> <tag> <seconds> <seed>...
# Two sets (A, B) on the same seeds, as the builder's contract sets a bound
# (then `python3 benchmark/tools/spread.py chiprun_out/<tag>`); SETS=A for
# one set, e.g. short runs on seeds no set has had.
w=$1; tag=$2; secs=$3; shift 3
mkdir -p "$(dirname chiprun_out/$tag)"    # <tag> may name a directory of the call's own
for set in ${SETS:-A B}; do for s in "$@"; do
  out=chiprun_out/${tag}_${set}_$s
  python3 benchmark/run.py --workload $w --seed $s --seconds $secs --trace ${TRACE:-0} > $out.out 2> $out.err
  echo "rc=$? set=$set seed=$s $(tail -1 $out.out | cut -c1-400)"
  grep -E '"phase": "(work|check_summary)"' $out.out | cut -c1-900
done; done
