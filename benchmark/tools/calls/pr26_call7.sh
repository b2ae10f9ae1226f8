#!/bin/bash
# PR 26, chip call 7 (one chip): one traced run of the new cell from the
# final tree as git would commit it (after call 6 the engine's hook became a
# weak reference and documents changed; nothing else):
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/change
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 900 -- bash benchmark/tools/calls/pr26_call7.sh
cd .bench_scratch/change && python3 benchmark/run.py \
  --workload lfm2_8b_a1b_serve.decode_closed128 --seed 2147493021 --seconds 40 --trace 1 \
  2> chiprun_err.txt | grep -E '^(window|check |compile cache events|\{)' | cut -c1-2500
echo rc=${PIPESTATUS[0]}; tail -2 chiprun_err.txt | cut -c1-300
