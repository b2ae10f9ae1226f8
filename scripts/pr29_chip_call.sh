#!/bin/bash
# PR 29 (perf_opt: the paged kernels walk live pages only), the chip calls:
# parent against change, both from git.
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 62e68cec715d | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   cp scripts/paged_kernel_step0.py .bench_scratch/parent/scripts/
#   chiprun --timeout 1200 -- bash scripts/pr29_chip_call.sh step0   # the kernel alone
#   chiprun --timeout 3000 -- bash scripts/pr29_chip_call.sh gpt
#   chiprun --timeout 2400 -- bash scripts/pr29_chip_call.sh lfm2
#   chiprun --timeout 3000 -- bash scripts/pr29_chip_call.sh seeds   # the change alone, six seeds a cell
#   chiprun --timeout 1500 -- bash scripts/pr29_chip_call.sh smoke   # _chip/archive = git archive $(git write-tree)
# One compile cache and one gate cache for both trees, as on the driver's
# machine: the gate's key for the paged kernels carries "live_pages", so the
# parent's decisions (key "stacked") and the change's sit side by side.
repo=$PWD
what=${1:-gpt}
out=$repo/chiprun_out/pr29/$what
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
one() {  # tree cell seed trace tool
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  local log=$out/$cell.$tree.t$trace.seed_$seed.log
  (cd $repo/.bench_scratch/$tree && python3 $tool \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|gap percentiles|ttft percentiles|check widest|gate decisions)' $log | cut -c1-400
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})'
  grep -E '^SPANS' $log | cut -c1-1500
  tail -n 1 ${log%.log}.err | cut -c1-200
}
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
case $what in
step0)
  (cd .bench_scratch/parent && python3 scripts/paged_kernel_step0.py \
     --out $out/parent.json) 2>&1 | grep -E '^(gpt|lfm2)'
  echo "== change"
  (cd .bench_scratch/change && python3 scripts/paged_kernel_step0.py \
     --out $out/change.json) 2>&1 | grep -E '^(gpt|lfm2)'
  ;;
gpt)
  one parent $closed 2147494301 0     # cold: compiles and fills the cache
  one change $closed 2147494301 0     # times the new gate keys once
  one change $closed 2147494302 0
  one parent $closed 2147494302 0
  one parent $mixed 2147494311 0
  one change $mixed 2147494311 0
  one change $mixed 2147494312 0
  one parent $mixed 2147494312 0
  one change $closed 2147494303 1 benchmark/tools/span_report.py
  one change $mixed 2147494313 1 benchmark/tools/span_report.py
  one parent $closed 2147494303 1
  ;;
lfm2)
  one parent $lfm 2147494321 0
  one change $lfm 2147494321 0
  one change $lfm 2147494322 0
  one parent $lfm 2147494322 0
  one change $lfm 2147494323 1 benchmark/tools/span_report.py
  train=gpt_350m_train.b16s1024
  one parent $train 2147494331 0
  one change $train 2147494331 0
  ;;
seeds)      # the change alone: the spread of each claimed metric
  for i in 1 2 3 4 5 6; do one change $closed $((2147494340 + i)) 0; done
  for i in 1 2 3 4 5 6; do one change $mixed $((2147494350 + i)) 0; done
  for i in 1 2 3 4 5 6; do one change $lfm $((2147494360 + i)) 0; done
  # the span ring at its new size: the 13 span-read metrics are on the line
  one change $mixed 2147494357 1 benchmark/tools/span_report.py
  ;;
smoke)      # the final tree, from what git would commit (_chip/archive)
  (cd _chip/archive && python3 chip_smoke.py) > $out/smoke.log 2> $out/smoke.err
  echo "== chip_smoke rc=$? at $((SECONDS - t0))s"; tail -n 1 $out/smoke.log | cut -c1-600
  grep -E "paged|prefill_tail|logits" $out/smoke.log | cut -c1-300 | head -n 20
  ;;
esac
cp $PADDLE_TPU_AUTOBENCH_CACHE $out/ 2>/dev/null
echo "done at $((SECONDS - t0))s"
