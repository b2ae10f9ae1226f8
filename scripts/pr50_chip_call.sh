#!/bin/bash
# PR 50 (perf_opt: Trinity's prefill attends through the flash kernel), the
# chip calls. Parent and change both from git, one call measures both:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive dcda2a112b74 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3550 -- bash scripts/pr50_chip_call.sh step0 claim traced   # Step 0; trinity, the file's order: pairs; both trees traced on one seed
#   (faults: the two window faults on the kernel's path; mellum: a pair and the change traced; seeded: trinity's epochs ordered by the seed, both sides on the same seeds)
#   chiprun --timeout 3550 -- bash scripts/pr50_chip_call.sh faults mellum gmm seeded   # call 2 as made: with the decode's grouped products forced to `gmm`, one traced run (ROADMAP Queue 1 item 8)
#   chiprun --timeout 3000 -- env PAIRS="5 6" SEEDED="7 8 9 10 11 12" bash scripts/pr50_chip_call.sh claim seeded   # call 3, the tree as handed in: two more pairs, six more seeded orders
# One compile cache and one gate cache for both trees, as on the driver's
# machine within a checkout.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
# the gate's milliseconds a candidate, in a run's .err
export PADDLE_TPU_AUTOBENCH_VERBOSE=1
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
one() {  # tree cell seed trace [tool [tool's arguments]]
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  shift 5 2>/dev/null || shift $#
  local tag=$(basename $tool .py)$(echo "$*" | tr -c 'a-zA-Z0-9_\n' '_')
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$tag.log
  local at=$SECONDS
  (cd $repo/$tree && timeout 1500 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? took $((SECONDS - at))s at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check widest|gate decisions|WINDOW)' $log | cut -c1-900
  grep -E 'flash_(band|full)_gqa.* -> ' ${log%.log}.err | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("attempted"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:14]: print("   ", round(row[1], 4), row[0][:240])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  grep -vE 'autobench|^$' ${log%.log}.err | tail -n 3 | cut -c1-400
}
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
new=trinity_mini_serve.shortlong_closed128
mellum=mellum2_12b_a2p5b_train.b2s8192
S=.bench_scratch
# a run whose reference replays fewer requests: for rates, not for limits
quick="--set config.correct.sample_requests=1"
for what in "${@:-claim}"; do
out=$repo/chiprun_out/pr50/$what
mkdir -p $out
case $what in
step0)      # the two calls alone, kernel and XLA (the change's tree)
  (cd $S/change && timeout 800 python3 scripts/prefill_flash_step0.py \
     --out $out/step0.jsonl) 2> $out/step0.err | cut -c1-700
  echo "== step0 rc=${PIPESTATUS[0]} at $((SECONDS - t0))s"
  ;;
claim)      # the claimed cell, the file's order
  for i in ${PAIRS:-1 2 3 4}; do pair $new $((2147500100 + 7919 * i)); done
  ;;
traced)     # both trees traced on one seed, the change's trace kept for the report of its spans
  BENCH_KEEP_TRACE=$out/trace one $S/change $new 2147590109 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*span_report.log | cut -c1-3000
  python3 scripts/pr50_prefill_ops.py $out/trace/trace.json > $out/prefill_ops.change.txt 2>&1
  head -n 24 $out/prefill_ops.change.txt | cut -c1-300
  rm -rf $out/trace
  BENCH_KEEP_TRACE=$out/trace one $S/parent $new 2147590109 1
  python3 scripts/pr50_prefill_ops.py $out/trace/trace.json > $out/prefill_ops.parent.txt 2>&1
  head -n 24 $out/prefill_ops.parent.txt | cut -c1-300
  rm -rf $out/trace
  ;;
seeded)     # the epochs' order from the seed, both sides on the same seeds
  for i in ${SEEDED:-1 2 3 4 5 6}; do
    for t in parent change; do
      one $S/$t $new $((2147520000 + 7919 * i)) 0 benchmark/tools/window_account.py --set traffic.order='"seed"' $quick
    done
  done
  ;;
faults)     # the window broken where window_faults.py breaks it: correct must read false
  for f in window_whole full_windowed; do
    one $S/change $new $((2147530000 + ${#f})) 0 benchmark/tools/probe_window_fault.py --fault $f
  done
  ;;
gmm)        # ROADMAP Queue 1 item 8: the decode's grouped products forced to `gmm`, traced
  PADDLE_TPU_AUTOBENCH_FORCE=gmm one $S/change $new 2147590109 1
  ;;
mellum)     # the other cell whose program calls the function that moved
  pair $mellum 2147540001
  one $S/change $mellum 2147540009 1
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
