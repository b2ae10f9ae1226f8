#!/bin/bash
# PR 44 (perf_opt: a prefill's first token is read behind the step's decode),
# the chip calls. Parent and change both from git, one call measures both:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive d180c51839fc | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 1500 -- bash scripts/pr44_chip_call.sh first      # the change traced in the claimed cell, its spans' report; then a pair
#   chiprun --timeout 3000 -- bash scripts/pr44_chip_call.sh claim      # the claimed cell, the file's order: pairs on a seed each, the side that runs first alternating
#   chiprun --timeout 3000 -- bash scripts/pr44_chip_call.sh gpt        # decode_closed64 and mixed_open: two pairs and the change traced, each
#   chiprun --timeout 3500 -- bash scripts/pr44_chip_call.sh seeded     # the claimed cell, six seeded orders a side
#   chiprun --timeout 3500 -- bash scripts/pr44_chip_call.sh others     # the four other serving cells, a pair each
#   chiprun --timeout 2400 -- bash scripts/pr44_chip_call.sh final traces  # the final tree's committed files: two runs and a traced one in the claimed cell; the four other cells traced
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
  grep -E '^check widest' $log | cut -c1-200
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 2 ${log%.log}.err | cut -c1-300
}
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
traced() {  # cell seed: the change traced, with the spans' report
  one $S/change $1 $2 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/$1.change.t1.seed_$2.*.log | cut -c1-2500
}
jamba=jamba2_3b_serve.chat_closed512
S=.bench_scratch
# a run whose reference replays fewer requests: for rates, not for limits
quick="--set config.correct.sample_requests=1"
for what in "${@:-first}"; do
out=$repo/chiprun_out/pr44/$what
mkdir -p $out
case $what in
first)      # the change traced, then a pair
  traced $jamba 2147502011
  pair $jamba 2147502012
  ;;
claim)      # the claimed cell: pairs
  for i in ${PAIRS:-1 2 3 4}; do pair $jamba $((2147502100 + i)); done
  ;;
gpt)        # the two GPT serving cells: two pairs and the change traced, each
  for c in gpt_1p3b_serve.decode_closed64 gpt_1p3b_serve.mixed_open; do
    for i in ${PAIRS:-1 2}; do pair $c $((2147502200 + i)); done
    traced $c 2147502209
  done
  ;;
seeded)     # the epochs' order from the seed, six a side on the same seeds
  for i in ${SEEDED:-1 2 3 4 5 6}; do
    for t in parent change; do
      one $S/$t $jamba $((2147502300 + i)) 0 benchmark/tools/window_account.py --set traffic.order='"seed"' $quick
    done
  done
  ;;
others)     # the other serving cells, a pair each
  for c in ${CELLS:-lfm2_8b_a1b_serve.decode_closed128 ouro_2p6b_serve.decode_closed32 kanana2_30b_a3b_serve.longdoc_closed128 trinity_mini_serve.shortlong_closed128}; do
    for seed in ${SEEDS:-2147502401}; do pair $c $seed; done
  done
  ;;
traces)     # the other serving cells: the change traced, for the idle split
  for c in ${CELLS:-lfm2_8b_a1b_serve.decode_closed128 ouro_2p6b_serve.decode_closed32 kanana2_30b_a3b_serve.longdoc_closed128 trinity_mini_serve.shortlong_closed128}; do
    traced $c 2147502509
  done
  ;;
final)      # the committed tree: the change alone, the last traced
  for i in ${FINAL:-1 2}; do one $S/change $jamba $((2147502600 + i)) 0; done
  traced $jamba 2147502606
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
