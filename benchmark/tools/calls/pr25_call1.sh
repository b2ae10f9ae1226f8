#!/bin/bash
# PR 25, chip call 1 (one chip): is the per-layer copy of the pool gone, and
# what did it cost. Both trees are unpacked from git first:
#   git add -A; rm -rf .bench_scratch/{parent,change}; mkdir -p .bench_scratch/{parent,change}
#   git archive abbff34dda0c | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3000 -- bash benchmark/tools/calls/pr25_call1.sh
# (this PR edits nothing under benchmark/, so the parent's benchmark is the
# change's). One compile cache for all. First the change's traced run of
# decode_closed64 with its top device operations; then parent, change,
# change, parent on two seeds in each serving cell; a traced run of each
# tree in mixed_open and of the parent in decode_closed64; the one-chip
# training cell on both trees as the control.
repo=$PWD
out=$repo/chiprun_out/pr25/call1
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
  grep -E '^(window|ttft percentiles|gap percentiles|gate decisions)' $log | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for op in (d.get("breakdown") or {}).get("device_ops", []):
    print("   op", json.dumps(op)[:260])'
  grep -E '^SPANS' $log | cut -c1-1800
}
closed=gpt_1p3b_serve.decode_closed64
open_=gpt_1p3b_serve.mixed_open
train=gpt_350m_train.b16s1024
one change $closed 2500001 1
one parent $closed 2500011 0
one change $closed 2500011 0
one change $closed 2500012 0
one parent $closed 2500012 0
one parent $open_ 2500021 0
one change $open_ 2500021 0
one change $open_ 2500022 0
one parent $open_ 2500022 0
one change $open_ 2500031 1
one parent $open_ 2500031 1
one parent $closed 2500001 1
one parent $train 2500041 0
one change $train 2500041 0
tail -n 3 $out/*.err | grep -v "hugepage\|warnings.warn\|^$\|==>" | tail -n 20
