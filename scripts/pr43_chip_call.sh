#!/bin/bash
# PR 43 (perf_opt: Jamba's one-step update as a kernel over the stacked,
# donated state), the chip calls. Parent and change both from git, one call
# measures both:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive ce2c50fa302c | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 900 -- python3 scripts/ssm_step_step0.py --out chiprun_out/pr43/step0.json
#   chiprun --timeout 3000 -- bash scripts/pr43_chip_call.sh first       # the change traced (the trace's operations kept), then a pair
#   chiprun --timeout 3500 -- bash scripts/pr43_chip_call.sh trial claim # the gate's key decided anew in a traced run; pairs on a seed each, the side that runs first alternating
#   chiprun --timeout 3000 -- bash scripts/pr43_chip_call.sh faults others
# One compile cache and one gate cache for both trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
grep -c selective_step $PADDLE_TPU_AUTOBENCH_CACHE 2>/dev/null | sed 's/^/gate cache lines naming selective_step: /'
t0=$SECONDS
one() {  # tree cell seed trace [tool [tool's arguments]]
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  shift 5 2>/dev/null || shift $#
  local tag=$(basename $tool .py)$(echo "$*" | tr -c 'a-zA-Z0-9_\n' '_')
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$tag.log
  (cd $repo/$tree && timeout 1500 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check widest|gate decisions|slot state)' $log | cut -c1-600
  grep -E 'selective_step' ${log%.log}.err | cut -c1-300 | head -n 4
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
for what in "${@:-first}"; do
out=$repo/chiprun_out/pr43/$what
mkdir -p $out
case $what in
first)      # the change traced, its operations listed; then a pair
  PADDLE_TPU_AUTOBENCH_VERBOSE=1 BENCH_KEEP_TRACE=$out/trace \
    one $S/change $new 2147501011 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*span_report.log | cut -c1-1500
  python3 scripts/pr42_trace_ops.py $out/trace/trace.json > $out/trace_ops.txt 2>&1
  head -n 60 $out/trace_ops.txt
  rm -rf $out/trace
  pair $new 2147501012
  ;;
trial)      # the gate's key decided anew: the trial's timings, and what it adds to the peak
  (cd $S/change && python3 -m paddle_tpu.ops.autobench invalidate --match selective_step)
  PADDLE_TPU_AUTOBENCH_VERBOSE=1 one $S/change $new 2147501021 1
  ;;
claim)      # the claimed cell: pairs
  for i in ${PAIRS:-1 2 3 4}; do pair $new $((2147501100 + i)); done
  ;;
ptrace)     # the parent traced on the seed of the change's traced run
  one $S/parent $new 2147501011 1
  ;;
faults)     # the program broken must read not correct, through the step's either form
  for f in ${FAULTS:-no_d_skip state_bf16}; do
    one $S/change $new 2147501301 0 benchmark/tools/probe_recurrent_fault.py --fault $f --set config.correct.sample_requests=2
  done
  ;;
final)      # the committed tree: the change alone, the last traced
  for i in ${FINAL:-1 2}; do one $S/change $new $((2147501600 + i)) 0; done
  one $S/change $new 2147501606 1
  ;;
others)     # cells whose programs must not have moved, a pair each
  for cell in ${CELLS:-lfm2_8b_a1b_serve.decode_closed128 gpt_1p3b_serve.decode_closed64}; do
    pair $cell 2147501701
  done
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
