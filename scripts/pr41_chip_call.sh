#!/bin/bash
# PR 41 (perf_opt: the grouped-query paged kernel multiplies on the MXU), the
# chip calls. Parent and change both from git, one call measures both:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive e611c39958b1 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 1500 -- python3 scripts/grouped_kernel_step0.py --out chiprun_out/pr41/step0.json
#   chiprun --timeout 3500 -- bash scripts/pr41_chip_call.sh claim       # trinity, the file's order: pairs, the last of the change traced
#   chiprun --timeout 3500 -- bash scripts/pr41_chip_call.sh seeded      # trinity, six seeded orders a side
#   chiprun --timeout 3500 -- bash scripts/pr41_chip_call.sh ptrace lfm others  # the parent traced; lfm2 pairs and its traced run; the G = 1 cells and kanana, a pair each
#   chiprun --timeout 1500 -- env CELLS=ouro_2p6b_serve.decode_closed32 SEEDS="2147498152 2147498153" bash scripts/pr41_chip_call.sh others  # ouro again: its first pair held a stall
#   chiprun --timeout 2400 -- env PAIRS="5 6" bash scripts/pr41_chip_call.sh claim  # the final tree: two more pairs, traced again
# One compile cache and one gate cache for both trees, as on the driver's
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
  (cd $repo/$tree && timeout 1500 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check widest|gate decisions)' $log | cut -c1-420
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:12]: print("   ", round(row[1], 4), row[0][:240])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-400
}
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
new=trinity_mini_serve.shortlong_closed128
lfm=lfm2_8b_a1b_serve.decode_closed128
S=.bench_scratch
# a run whose reference replays fewer requests: for rates, not for limits
quick="--set config.correct.sample_requests=1"
for what in "${@:-claim}"; do
out=$repo/chiprun_out/pr41/$what
mkdir -p $out
case $what in
claim)      # the claimed cell, the file's order
  for i in ${PAIRS:-1 2 3 4}; do pair $new $((2147498100 + i)); done
  BENCH_KEEP_TRACE=$out/trace one $S/change $new 2147498109 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*span_report.log | cut -c1-1500
  rm -rf $out/trace
  ;;
seeded)     # the epochs' order from the seed, six a side on the same seeds
  for i in ${SEEDED:-1 2 3 4 5 6}; do
    for t in parent change; do
      one $S/$t $new $((2147498120 + i)) 0 benchmark/tools/window_account.py --set traffic.order='"seed"' $quick
    done
  done
  ;;
lfm)        # the other cell that runs the grouped kernel
  for i in 1 2; do pair $lfm $((2147498140 + i)); done
  one $S/change $lfm 2147498149 1
  ;;
ptrace)     # the parent traced on the seed of the change's traced run
  one $S/parent $new 2147498109 1
  ;;
others)     # the cells that bypass it: G = 1 (GPT, ouro) and the latent kernel
  for c in ${CELLS:-gpt_1p3b_serve.decode_closed64 kanana2_30b_a3b_serve.longdoc_closed128 ouro_2p6b_serve.decode_closed32 gpt_1p3b_serve.mixed_open}; do
    for seed in ${SEEDS:-2147498151}; do pair $c $seed; done
  done
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
