#!/bin/bash
# PR 26, chip call 5 (one chip): the final tree from what git would commit,
# against the parent with this PR's benchmark laid over it as the driver does:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive f877db88caf9 | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr26_call5.sh
# 1. both GPT serving cells, parent against change: untraced parent, change,
#    change, parent in decode_closed64 and parent, change in mixed_open; one
#    traced run of each tree in each cell; gpt_350m_train once on the change;
# 2. the new cell on the change: one traced run and twelve more seeds.
repo=$PWD
out=$repo/chiprun_out/pr26/call5
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
t0=$SECONDS
one() {  # tree cell seed trace
  local tree=$1 cell=$2 seed=$3 trace=$4
  local log=$out/$cell.$tree.t$trace.seed_$seed.log
  (cd $repo/.bench_scratch/$tree && python3 benchmark/run.py \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|gap percentiles|check widest|reference)' $log | cut -c1-260
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})'
  tail -n 1 ${log%.log}.err | cut -c1-200
}
closed=gpt_1p3b_serve.decode_closed64
open_=gpt_1p3b_serve.mixed_open
train=gpt_350m_train.b16s1024
lfm=lfm2_8b_a1b_serve.decode_closed128
one parent $closed 2147492001 0
one change $closed 2147492001 0
one change $closed 2147492002 0
one parent $closed 2147492002 0
one parent $open_ 2147492003 0
one change $open_ 2147492003 0
one parent $closed 2147492004 1
one change $closed 2147492004 1
one parent $open_ 2147492005 1
one change $open_ 2147492005 1
one change $train 2147492006 0
one change $lfm 2147492010 1
for i in $(seq 1 12); do one change $lfm $((2147492010 + i)) 0; done
cp $JAX_COMPILATION_CACHE_DIR/autobench_gate.json $out/ 2>/dev/null
