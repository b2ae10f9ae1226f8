#!/bin/bash
# PR 28 (simplicity), the chip calls: parent against change, both from git.
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive d8b97eaa9523 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   cp scripts/lowered_serving_programs.py .bench_scratch/parent/scripts/
#   chiprun --timeout 3000 -- bash scripts/pr28_chip_call.sh gpt      # call 1
#   chiprun --timeout 2400 -- bash scripts/pr28_chip_call.sh lfm2     # call 2
#   chiprun --timeout 1500 -- bash scripts/pr28_chip_call.sh smoke    # call 3
# One compile cache and one gate cache for both trees. A Pallas kernel's
# serialized body keeps the Python call stack of its pallas_call (paths,
# function names, lines), and the body is part of the compile cache's key:
# by default no program that holds a kernel is ever shared between two
# directories, whatever they hold. With full tracebacks off and each tree's
# root stripped, a body names the kernel's own file and lines only; then a
# hit says the programs are the same and a miss says they are not. Both
# settings change debug locations and nothing that runs.
repo=$PWD
what=${1:-gpt}
out=$repo/chiprun_out/pr28/$what
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
export JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=0
export JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX='^.*/\.bench_scratch/(parent|change)/'
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
entries() { ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l; }
one() {  # tree cell seed trace tool
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  local log=$out/$cell.$tree.t$trace.seed_$seed.log before=$(entries)
  (cd $repo/.bench_scratch/$tree && python3 $tool \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60); cache entries $before -> $(entries)"
  grep -E '^(window|gap percentiles|ttft percentiles|check widest|gate decisions)' $log | cut -c1-400
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})'
  grep -E '^SPANS' $log | cut -c1-1500
  tail -n 1 ${log%.log}.err | cut -c1-200
}
lowered() {  # tree: the engine's own programs as the gate makes them here
  (cd $repo/.bench_scratch/$1 && python3 scripts/lowered_serving_programs.py \
     --engine --out $out/lowered.$1) > $out/lowered.$1.log 2> $out/lowered.$1.err
  echo "== lowered $1 rc=$? at $((SECONDS - t0))s"; tail -n 2 $out/lowered.$1.log
  rm -f $out/lowered.$1/*.mlir.gz; gzip -f $out/lowered.$1/*.mlir
}
case $what in
gpt)
  closed=gpt_1p3b_serve.decode_closed64
  mixed=gpt_1p3b_serve.mixed_open
  lowered parent
  lowered change
  diff $out/lowered.parent/SHA256 $out/lowered.change/SHA256 \
    && echo "LOWERED: the engine's 9 programs are byte for byte the parent's"
  one parent $closed 2147493301 0     # cold: compiles and fills the cache
  one change $closed 2147493301 0     # on the parent's cache
  one change $closed 2147493302 0
  one parent $closed 2147493302 0
  one parent $mixed 2147493311 0
  one change $mixed 2147493311 0
  one change $mixed 2147493312 0
  one parent $mixed 2147493312 0
  one change $closed 2147493303 1 benchmark/tools/span_report.py
  one parent $closed 2147493303 1 benchmark/tools/span_report.py
  ;;
lfm2)
  lfm=lfm2_8b_a1b_serve.decode_closed128
  one parent $lfm 2147493321 0
  one change $lfm 2147493321 0
  one change $lfm 2147493322 0
  one parent $lfm 2147493322 0
  train=gpt_350m_train.b16s1024
  one parent $train 2147493331 0
  one change $train 2147493331 0
  ;;
smoke)      # the final tree, from what git would commit (_chip/archive)
  unset JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX
  (cd _chip/archive && python3 chip_smoke.py) > $out/smoke.log 2> $out/smoke.err
  echo "== chip_smoke rc=$? at $((SECONDS - t0))s"; tail -n 1 $out/smoke.log | cut -c1-600
  ;;
esac
cp $PADDLE_TPU_AUTOBENCH_CACHE $out/ 2>/dev/null
echo "done at $((SECONDS - t0))s; cache entries $(entries)"
