#!/bin/bash
# Two sets of runs of one cell, the same seeds in both, as the bounds are
# set from:  [SETS=A] bash chipbench/tools/sets.sh <cell> <seconds> <seed>...
# Every JSON line of every run goes to chiprun_out/sets/<cell>.<set>.log.
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out/sets
for set in ${SETS:-A B}; do
  for seed in "$@"; do
    log=chiprun_out/sets/$cell.$set.log
    echo "{\"run\": \"$cell $set\", \"seed\": $seed}" >> $log
    python3 -m chipbench.run --workload $cell --seed $seed --seconds $seconds --trace 0 2>&1 \
      | grep '^{\|Error\|Traceback\|  File\|chipbench:' >> $log
    echo "rc=$? $cell $set $seed $(tail -n 1 $log | cut -c1-420)"
  done
done
