#!/bin/bash
# PR 46 (model_config: one expert-parallel rank of Mellum2-12B-A2.5B
# trained), the chip calls. Trees from git, so that a call measures what a
# checkout holds:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive c1065dd16f15 | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/      # this PR's benchmark files over the parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 1800 -- bash benchmark/tools/calls/pr46_call.sh first          # calls 1-2: the working tree, traced, the trace kept (call 2 with `controls`)
#   chiprun --timeout 1500 -- env LRS=1e-5,1e-6,0 bash benchmark/tools/calls/pr46_call.sh held
#       # calls 3-8: the held share by seed (the tool then also took scales of the embedding's rows and
#       # of wo / w2, and call 8 ran six seeds at lr 1e-6: PERF.md section 6)
#   chiprun --timeout 3300 -- bash benchmark/tools/calls/pr46_call.sh sound controls faults   # call 9
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr46_call.sh parent final others      # call 10: what git would commit
# The tool does not hand the environment on: variables go inside the command (`env X=.. bash ..`).
# One compile cache and one gate cache for both trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
ROUND=${ROUND:-0}
one() {  # tree cell seed trace [tool [tool's arguments]]
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  shift 5 2>/dev/null || shift $#
  local tag=$(basename $tool .py)$(echo "$*" | tr -c 'a-zA-Z0-9_\n' '_')
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$tag.log
  (cd $repo/$tree && timeout 1500 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds ${WINDOW:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check |gate decisions|CONTROL|FAULT|window |tally|reference:|trainer built|first 3)' $log | cut -c1-700
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:14]: print("   ", round(row[1], 4), row[0][:260])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-600
}
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
new=mellum2_12b_a2p5b_train.b2s8192
S=.bench_scratch
for what in "${@:-first}"; do
out=$repo/chiprun_out/pr46/$what
mkdir -p $out
case $what in
first)      # the working tree: a traced run, the trace kept and its operations listed
  PADDLE_TPU_AUTOBENCH_VERBOSE=1 BENCH_KEEP_TRACE=$out/trace \
    one . $new $((2147500011 + ROUND)) 1
  python3 benchmark/tools/routed_train_ops.py $out/trace/trace.json > $out/trace_ops.txt 2>&1
  head -n 60 $out/trace_ops.txt | cut -c1-400
  gzip -f $out/trace/trace.json
  ;;
sound)      # six seeds untraced: every limit's sound reading, the rate's spread, the held share
  for i in 1 2 3 4 5 6; do one ${TREE:-.} $new $((2147510000 + 7919 * i + ROUND)) 0; done
  python3 benchmark/tools/summarize.py $out/*.log 2>/dev/null | tail -n 12
  ;;
controls)   # the lower-precision control of the reference (a short window: its rate is not read)
  WINDOW=8 one ${TREE:-.} $new $((2147520001 + ROUND)) 0 benchmark/tools/probe.py --control fp8
  WINDOW=8 one ${TREE:-.} $new $((2147520002 + ROUND)) 0 benchmark/tools/probe.py --control bf16
  ;;
faults)     # the program broken, one fault a run: each beside the limit that catches it
  i=0
  for f in ${FAULTS:-band_short window_whole yarn_on_sliding yarn_off_full no_attention_factor sigmoid_router no_normalise wrong_experts no_qk_norm}; do
    i=$((i + 1))
    WINDOW=8 one ${TREE:-.} $new $((2147530000 + i + ROUND)) 0 benchmark/tools/probe_routed_train_fault.py --fault $f
  done
  ;;
held)       # the held share and the step by seed, for several learning rates (calls 3-8 also tried scales of the embedding's rows and of wo / w2: PERF.md section 6)
  python3 benchmark/tools/routed_train_held.py --lrs ${LRS:-1e-4,1e-6,0} --seeds ${SEEDS:-4} --steps ${STEPS:-8} 2>&1 | grep -E "^lr|Error|error" | tee $out/held.txt
  ;;
parent)     # the parent under this PR's benchmark files: must fail at once
  one $S/parent $new $((2147500001 + ROUND)) 0
  ;;
final)      # what git would commit: a traced run (`sound` with TREE=$S/change has the untraced six)
  one $S/change $new $((2147540001 + ROUND)) 1
  ;;
oldtraced)  # an accepted cell traced on the parent under this PR's benchmark files: what this PR adds must not break it
  one $S/parent gpt_350m_train.b16s1024 $((2147545001 + ROUND)) 1
  ;;
others)     # the cells that share code with this change, parent and change in pairs
  for cell in ${CELLS:-gpt_350m_train.b16s1024 lfm2_8b_a1b_serve.decode_closed128 trinity_mini_serve.shortlong_closed128}; do
    pair $cell $((2147550001 + ROUND))
  done
  ;;
esac
done
echo "total $((SECONDS - t0))s"
