#!/bin/bash
# PR 24, chip call 2 (one chip): what tracing costs end to end, and the
# parent beside the change. Both trees are unpacked from git first:
#   git add -A; rm -rf .bench_scratch/{parent,change}; mkdir -p .bench_scratch/{parent,change}
#   git archive 43ffd529c0e8 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   cp BENCHMARK.json .bench_scratch/parent/; cp -r benchmark/. .bench_scratch/parent/benchmark/
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr24_call2.sh
# (the parent gets this PR's benchmark files laid over it, as the driver
# lays them). One compile cache for all, so that every run executes the
# same programs. Per cell: six seeds, each run with PADDLE_TPU_TRACE=0 and
# at the default, in the order off on on off; then parent, change, change,
# parent on two more seeds; then the parent's traced run of each cell with
# this PR's readers.
repo=$PWD
out=$repo/chiprun_out/pr24/call2
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
one() {  # tree cell seed label trace [env...]
  local tree=$1 cell=$2 seed=$3 label=$4 trace=$5; shift 5
  local log=$out/$cell.$label.seed_$seed.log
  local cmd=benchmark/run.py    # the change's runs also say what its spans saw
  [ $tree = change ] && cmd=benchmark/tools/span_report.py
  (cd $repo/.bench_scratch/$tree && env "$@" python3 $cmd \
     --workload $cell --seed $seed --seconds ${secs:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "$cell $label seed=$seed rc=$? $(grep -E '^compile cache events' $log | cut -c1-60) $(grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), {k: v["value"] for k, v in d.get("metrics", {}).items()})')"
  grep -E '^SPANS' $log | cut -c1-2500
}
for cell in gpt_1p3b_serve.decode_closed64 gpt_350m_train.b16s1024; do
  secs=5 one change $cell 2410000 warm 0 X=1 > /dev/null  # fills the caches
  i=0
  for seed in 2410001 2410002 2410003 2410004 2410005 2410006; do
    if [ $((i % 2)) = 0 ]; then
      one change $cell $seed trace_off 0 PADDLE_TPU_TRACE=0
      one change $cell $seed trace_on 0 X=1
    else
      one change $cell $seed trace_on 0 X=1
      one change $cell $seed trace_off 0 PADDLE_TPU_TRACE=0
    fi
    i=$((i + 1))
  done
  one parent $cell 2410011 parent 0 X=1
  one change $cell 2410011 change 0 X=1
  one change $cell 2410012 change 0 X=1
  one parent $cell 2410012 parent 0 X=1
done
cell=gpt_1p3b_serve.mixed_open
one parent $cell 2410021 parent 0 X=1
one change $cell 2410021 change 0 X=1
one change $cell 2410022 change 0 X=1
one parent $cell 2410022 parent 0 X=1
for cell in gpt_1p3b_serve.mixed_open gpt_350m_train.b16s1024; do
  one parent $cell 2410031 parent_traced 1 X=1
done
# StepSampler's reading of the same phases beside the spans'
python3 benchmark/tools/span_report.py --workload gpt_1p3b_serve.decode_closed64 \
  --seed 2410041 --seconds 40 --trace 0 > $out/sampler.log 2> $out/sampler.err
grep -E '^SPANS' $out/sampler.log
tail -n 3 $out/*.err | grep -v "hugepage\|warnings.warn\|^$\|==>" | tail -n 20
