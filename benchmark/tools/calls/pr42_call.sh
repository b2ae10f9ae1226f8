#!/bin/bash
# PR 42 (model_config: AI21-Jamba2-3B served whole), the chip calls. Trees
# from git, so that a call measures what a checkout holds:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive f4b006fdb884 | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/      # this PR's benchmark files over the parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 2400 -- bash benchmark/tools/calls/pr42_call.sh parent first
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr42_call.sh sound        # seeds, for the limit
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr42_call.sh controls faults
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr42_call.sh seeded        # the epochs' order from the seed
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr42_call.sh final others
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
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check widest|gate decisions|CONTROL|slot state|window )' $log | cut -c1-600
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:14]: print("   ", round(row[1], 4), row[0][:260])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-400
}
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
new=jamba2_3b_serve.chat_closed512
S=.bench_scratch
# a run whose reference replays fewer requests: for rates, not for limits
quick="--set config.correct.sample_requests=1"
for what in "${@:-first}"; do
out=$repo/chiprun_out/pr42/$what
mkdir -p $out
case $what in
parent)     # the parent under this PR's benchmark files: must fail at once
  one $S/parent $new $((2147500001 + ROUND)) 0
  ;;
first)      # the change: a traced run (the trace kept), then an untraced one
  PADDLE_TPU_AUTOBENCH_VERBOSE=1 BENCH_KEEP_TRACE=$out/trace \
    one $S/change $new $((2147500011 + ROUND)) 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*span_report.log | cut -c1-1500
  python3 scripts/pr42_trace_ops.py $out/trace/trace.json > $out/trace_ops.txt 2>&1
  head -n 80 $out/trace_ops.txt
  gzip -f $out/trace/*.json
  one $S/change $new $((2147500012 + ROUND)) 0
  ;;
sound)      # seeds for the limit, the whole sample of 4
  for i in ${SEEDS:-1 2 3 4 5 6 7 8 9 10}; do one $S/change $new $((2147500100 + ROUND + i)) 0; done
  ;;
controls)   # the reference in the precision below beside the program
  one $S/change $new $((2147500201 + ROUND)) 0 benchmark/tools/probe.py --control fp8
  one $S/change $new $((2147500202 + ROUND)) 0 benchmark/tools/probe.py --control bf16
  ;;
faults)     # each fault must read not correct
  for f in ${FAULTS:-state_bf16 padding_advances stale_state taps_from_bucket_end no_dt_norm no_b_norm no_c_norm no_d_skip}; do
    one $S/change $new $((2147500301 + ROUND)) 0 benchmark/tools/probe_recurrent_fault.py --fault $f --set config.correct.sample_requests=2
  done
  ;;
seeded)     # the epochs' order from the seed
  for i in ${SEEDED:-1 2 3 4 5 6 7 8 9 10 11 12}; do
    one $S/change $new $((2147500400 + ROUND + i)) 0 benchmark/tools/probe.py $quick --set 'traffic.order="seed"'
  done
  ;;
draw)       # the file's order, another draw
  for i in 1 2 3 4 5 6; do
    one $S/change $new $((2147500500 + ROUND + i)) 0 benchmark/tools/probe.py $quick --set traffic.order_draw=${DRAW:-1}
  done
  ;;
final)      # the committed tree: six seeds, the last traced
  for i in 1 2 3 4 5; do one $S/change $new $((2147500600 + ROUND + i)) 0; done
  one $S/change $new $((2147500606 + ROUND)) 1
  ;;
others)     # the cells of before, parent beside change on one seed
  for cell in ${CELLS:-lfm2_8b_a1b_serve.decode_closed128 trinity_mini_serve.shortlong_closed128 gpt_1p3b_serve.decode_closed64 kanana2_30b_a3b_serve.longdoc_closed128 gpt_1p3b_serve.mixed_open ouro_2p6b_serve.decode_closed32}; do
    pair $cell $((2147500701 + ROUND))
  done
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
