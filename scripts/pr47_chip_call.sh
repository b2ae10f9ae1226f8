#!/bin/bash
# PR 47 (perf_opt: an expert-parallel rank's sorted arrays at a bound's
# rows), the chip calls. Trees from git, so that a call measures what a
# checkout holds:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive ab48eac60b0a | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/      # this PR's benchmark files over the parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3600 -- bash scripts/pr47_chip_call.sh first claim parenttraced held
#       # call 1 (bound at 3/2): the working tree traced, the trace's operations listed; six pairs in the claimed
#       # cell; the parent traced under this PR's benchmark files; the held share by layer and seed
#   chiprun --timeout 3000 -- env PAIRS="7 8 9" bash scripts/pr47_chip_call.sh final others claim
#       # call 2 (3/2): the committed files traced; the cells that share the changed code, in pairs; three more pairs
#   chiprun --timeout 2400 -- env PAIRS="10 11 12" ROUND=3 bash scripts/pr47_chip_call.sh final claim
#       # call 3 (the 2 handed in): the committed files traced, three pairs
# The tool does not hand the environment on: variables go inside the command (`env X=.. bash ..`).
# One compile cache and one gate cache for both trees, as on the driver's machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
ROUND=${ROUND:-0}
one() {  # tree cell seed trace
  local tree=$1 cell=$2 seed=$3 trace=$4
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.log
  (cd $repo/$tree && timeout 1500 python3 benchmark/run.py \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check |gate decisions|window |tally|reference:|trainer built|first 3)' $log | cut -c1-400
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:12]: print("   ", round(row[1], 4), row[0][:220])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-600
}
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
traced() {  # tree tag seed: a traced run, the trace kept and its operations listed
  BENCH_KEEP_TRACE=$out/trace_$2 one $1 $claimed $3 1
  python3 benchmark/tools/routed_train_ops.py $out/trace_$2/trace.json 400 > $out/trace_ops_$2.txt 2>&1
  head -n 70 $out/trace_ops_$2.txt | cut -c1-330
  rm -rf $out/trace_$2
}
claimed=mellum2_12b_a2p5b_train.b2s8192
S=.bench_scratch
for what in "${@:-first}"; do
out=$repo/chiprun_out/pr47/$what
mkdir -p $out
case $what in
first)         # the working tree traced
  traced . change $((2147600011 + ROUND))
  ;;
held)          # the held share by layer and seed at the cell's rate (benchmark/tools/routed_train_held.py)
  python3 benchmark/tools/routed_train_held.py --seeds ${SEEDS:-4} --steps ${STEPS:-8} 2>&1 | grep -E "^lr|Error|error" | tee $out/held.txt
  ;;
parenttraced)  # the parent under this PR's benchmark files, traced: the new reader finds nothing and says nothing
  traced $S/parent parent $((2147600021 + ROUND))
  ;;
claim)         # the claimed cell: pairs on one seed each
  for i in ${PAIRS:-1 2 3 4 5 6}; do pair $claimed $((2147610000 + 7919 * i + ROUND)); done
  ;;
others)        # the cells that share the changed code, parent and change in pairs
  for cell in ${CELLS:-gpt_350m_train.b16s1024 lfm2_8b_a1b_serve.decode_closed128}; do
    pair $cell $((2147650001 + ROUND))
  done
  ;;
final)         # what git would commit, traced
  traced $S/change final $((2147640001 + ROUND))
  ;;
esac
done
echo "total $((SECONDS - t0))s"
