#!/bin/bash
# Runs of one cell in one chip call, each with another seed, one line each.
# Usage: bash benchmark/tools/sets.sh <cell> <seconds> <label> <seed> [seed ...]
cell=$1; secs=$2; label=$3; shift 3
out=chiprun_out/sets/$cell.$label
mkdir -p $out
for seed in "$@"; do
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs \
    --trace 0 > $out/seed_$seed.log 2> $out/seed_$seed.err
  echo "$cell $label seed=$seed rc=$? $(grep -E '^compile cache events' $out/seed_$seed.log)"
  grep -E '^(window|check widest|check first|check param|gate decisions)' $out/seed_$seed.log | cut -c1-260
  tail -n 1 $out/seed_$seed.log
done
