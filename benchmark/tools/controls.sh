#!/bin/bash
# The lower-precision control at the cell's own size, one run per seed.
# Usage: bash benchmark/tools/controls.sh <cell> <seconds> <seed> [seed ...]
cell=$1; secs=$2; shift 2
out=chiprun_out/controls/$cell
mkdir -p $out
for seed in "$@"; do
  python3 benchmark/tools/probe.py --control fp8 --workload $cell --seed $seed \
    --seconds $secs --trace 0 > $out/seed_$seed.log 2> $out/seed_$seed.err
  echo "$cell control seed=$seed rc=$?"
  grep -E '^(CONTROL|check |reference|losses)' $out/seed_$seed.log | cut -c1-300
done
