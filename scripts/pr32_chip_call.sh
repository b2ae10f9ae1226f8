#!/bin/bash
# PR 32 (model_config: kanana-2-30b-a3b's block served through
# LatentDecodeModel), the chip calls. Parent and change both from git:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 1efb0c861191 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   # the benchmark as this PR leaves it over the parent too, as the driver lays it
#   cp BENCHMARK.json .bench_scratch/parent/; cp -r benchmark/. .bench_scratch/parent/benchmark/
#   chiprun --timeout 3500 -- bash scripts/pr32_chip_call.sh step0 first seeds_if_sound   # call 1: the kernel alone; the cell once and traced, the parent on it; six seeds
#   chiprun --timeout 3500 -- bash scripts/pr32_chip_call.sh controls spans others   # call 2: the fp8 control and the three faults; the spans and a cut of the trace; every older one-chip cell, parent beside change
#   chiprun --timeout 1500 -- bash scripts/pr32_chip_call.sh final           # call 3: the final tree
# One compile cache and one gate cache for all trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
one() {  # tree cell seed trace [tool [tool's arguments]]
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  shift 5 2>/dev/null || shift $#
  local tag=$(basename $tool .py)$(echo "$*" | tr -c 'a-zA-Z0-9_\n' '_')
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$tag.log
  (cd $repo/$tree && timeout 1200 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds ${SECS:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|check widest|reference:|CONTROL|requests:|weights:|warm-up:)' $log | cut -c1-400
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()}, d.get("control"))
for row in d.get("breakdown", {}).get("device_ops", [])[:14]: print("   ", round(row[1], 4), row[0][:260])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-400
}
new=kanana2_30b_a3b_serve.longdoc_closed128
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
ouro=ouro_2p6b_serve.decode_closed32
train=gpt_350m_train.b16s1024
S=.bench_scratch
phases=("${@:-first}")
while [ ${#phases[@]} -gt 0 ]; do
what=${phases[0]}; phases=("${phases[@]:1}")
out=$repo/chiprun_out/pr32/$what
mkdir -p $out
case $what in
step0)      # the latent kernel alone: three layouts of the 576
  (cd $S/change && python3 scripts/latent_kernel_step0.py --out $out/latent_step0.json) > $out/step0.log 2> $out/step0.err
  echo "== step0 rc=$? at $((SECONDS - t0))s"; grep -E '^\{' $out/step0.log | cut -c1-500; tail -n 2 $out/step0.err | cut -c1-300
  ;;
first)      # the cell once (a checkout's first run: gate and compiles), once traced; the parent must fail at once
  one $S/change $new 2147496101 0
  one $S/change $new 2147496102 1
  one $S/parent $new 2147496101 0
  ;;
seeds_if_sound)   # chips are scarce: go on in the same call, but only from a sound first run
  if grep -q '"correct": true' $repo/chiprun_out/pr32/first/$new.change.t0.seed_2147496101.run.log; then
    phases=(seeds "${phases[@]}")
  else echo "== the first run was not correct: stopping here"; phases=(); fi
  ;;
seeds)
  for i in 1 2 3 4 5 6; do one $S/change $new $((2147496110 + i)) 0; done
  ;;
controls)   # the precision below, and the three faults: each must read correct false
  one $S/change $new 2147496121 0 benchmark/tools/probe.py --control fp8
  for f in no_shared scale_576 kr_unrotated; do
    one $S/change $new 2147496122 0 benchmark/tools/probe_latent_fault.py --fault $f
  done
  ;;
others)     # every older one-chip cell once, parent beside change, the same seed on both sides
  for c in $lfm $closed $mixed $ouro $train; do
    one $S/parent $c 2147496131 0
    one $S/change $c 2147496131 0
  done
  ;;
spans)      # where a window's time goes by phase of Engine.step, and two decode programs of its trace for benchmark/tests/data (the 5 s stay there)
  BENCH_KEEP_TRACE=$out/trace one $S/change $new 2147496151 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*.log | cut -c1-3000
  python3 scripts/pr32_cut_trace.py $out/trace/trace.json $out/kanana_two_steps.json > $out/cut.log 2>&1; rm -rf $out/trace
  head -n 70 $out/cut.log | cut -c1-260
  ;;
final)      # the final tree, from what git would commit
  one $S/change $new 2147496141 1
  one $S/change $new 2147496142 0
  one $S/change $lfm 2147496143 0
  one $S/parent $lfm 2147496143 0
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
