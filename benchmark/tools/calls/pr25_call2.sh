#!/bin/bash
# PR 25, chip call 2 (one chip): the final tree from what git would commit.
#   git add -A; rm -rf .bench_scratch/{parent,final}; mkdir -p .bench_scratch/{parent,final}
#   git archive abbff34dda0c | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/final
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr25_call2.sh
# Six untraced runs of each serving cell, each with a seed of its own (the
# spread of every end-to-end metric); the parent on two of those seeds in
# decode_closed64, once before and once after the change's run of the seed;
# one traced run of each serving cell on a seed above 2**31; chip_smoke.py
# (prefill_tail on the chip, the fluid op's rank-4 form, the gate's rows).
repo=$PWD
out=$repo/chiprun_out/pr25/call2
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
one() {  # tree cell seed trace
  local tree=$1 cell=$2 seed=$3 trace=$4
  local log=$out/$cell.$tree.t$trace.seed_$seed.log
  (cd $repo/.bench_scratch/$tree && python3 benchmark/tools/span_report.py \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? $(grep -E '^compile cache events' $log | cut -c1-70)"
  grep -E '^(window|gap percentiles|ttft percentiles)' $log | cut -c1-200
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for op in (d.get("breakdown") or {}).get("device_ops", [])[:6]:
    print("   op", json.dumps(op)[:200])'
  grep -E '^SPANS' $log | grep -o '"stall_steps": [0-9.]*, "stalled": \[[^]]*\]' | cut -c1-400
}
closed=gpt_1p3b_serve.decode_closed64
open_=gpt_1p3b_serve.mixed_open
one parent $closed 2510001 0
for seed in 2510001 2510002 2510003 2510004 2510005 2510006; do
  one final $closed $seed 0
done
one parent $closed 2510002 0
for seed in 2510011 2510012 2510013 2510014 2510015 2510016; do
  one final $open_ $seed 0
done
one final $closed 2147491001 1
one final $open_ 2147491002 1
(cd .bench_scratch/final && python3 chip_smoke.py) > $out/smoke.log 2> $out/smoke.err
echo "== chip_smoke rc=$?"; tail -n 1 $out/smoke.log | cut -c1-1500
tail -n 3 $out/*.err | grep -v "hugepage\|warnings.warn\|^$\|==>" | tail -n 20
