#!/bin/bash
# PR 40 (model_config: Trinity-Mini's afmoe block served through
# WindowedDecodeModel: a ring of pages a slot for the window layers beside
# the request's table), the chip calls. Parent and change both from git:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 8817f51828b1 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   # the benchmark as this PR leaves it over the parent too, as the driver lays it
#   cp BENCHMARK.json .bench_scratch/parent/; cp -r benchmark/. .bench_scratch/parent/benchmark/
#   chiprun --timeout 3500 -- bash scripts/pr40_chip_call.sh step0 first          # the kernels alone; the parent on the cell; the cell traced
#   chiprun --timeout 3500 -- bash scripts/pr40_chip_call.sh controls             # the fp8 control and the six faults
#   chiprun --timeout 3500 -- bash scripts/pr40_chip_call.sh seeded draw          # seeded orders, then six seeds of the file's draw
#   chiprun --timeout 3500 -- bash scripts/pr40_chip_call.sh others final         # older cells, parent beside change; the final tree's set
# and after the review (the second tree: PERF.md section 6):
#   chiprun --timeout 2100 -- env ROUND=1000 CELLS="gpt_1p3b_serve.decode_closed64 gpt_1p3b_serve.mixed_open" \
#     FAULTS="ring_short full_windowed" bash scripts/pr40_chip_call.sh final others controls
#   chiprun --timeout 560 -- env ROUND=1000 AGAIN="1 2 3" bash scripts/pr40_chip_call.sh again       # the committed files, three more seeds
# (ROUND: added to the seeds of those three phases, so that no run shares a seed with the first tree's)
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
  (cd $repo/$tree && timeout 1500 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds ${SECS:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|check widest|reference:|CONTROL|requests:|weights:|warm-up:|sample:|pages:|gate decisions)' $log | cut -c1-420
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()}, d.get("control"))
for row in d.get("breakdown", {}).get("device_ops", [])[:16]: print("   ", round(row[1], 4), row[0][:240])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-400
}
new=trinity_mini_serve.shortlong_closed128
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
S=.bench_scratch
# a run whose reference replays fewer requests: for rates, not for limits
quick="--set config.correct.sample_requests=1"
phases=("${@:-first}")
while [ ${#phases[@]} -gt 0 ]; do
what=${phases[0]}; phases=("${phases[@]:1}")
out=$repo/chiprun_out/pr40/$what
mkdir -p $out
case $what in
step0)      # the two paged calls alone, Pallas beside the XLA gather
  (cd $S/change && python3 scripts/window_kernel_step0.py --out $out/window_step0.json) > $out/step0.log 2> $out/step0.err
  echo "== step0 rc=$? at $((SECONDS - t0))s"; grep -E '^\{' $out/step0.log | cut -c1-1500; tail -n 2 $out/step0.err | cut -c1-300
  ;;
first)      # the parent must fail at once; the cell traced (a checkout's first run: gate and compiles)
  one $S/parent $new 2147496101 0
  BENCH_KEEP_TRACE=$out/trace one $S/change $new 2147496102 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*.log | cut -c1-3000
  ;;
sound)      # a sound run with the control computed beside it
  one $S/change $new ${SEED0:-2147496111} 0 benchmark/tools/probe.py --control fp8
  ;;
controls)   # the six faults: each must read correct false
  for f in ${FAULTS:-window_whole full_windowed rope_on_full no_gate no_shared ring_short}; do
    one $S/change $new $((2147496122 + ${ROUND:-0})) 0 benchmark/tools/probe_window_fault.py --fault $f --set config.correct.sample_requests=2
  done
  ;;
seeded)     # the epochs' order from the seed: what one fixed order is a draw of
  for i in ${SEEDED:-1 2 3 4 5 6}; do
    one $S/change $new $((2147496130 + i)) 0 benchmark/tools/probe.py --set traffic.order='"seed"' $quick
  done
  ;;
draw)       # six seeds of one draw of the file's order
  for i in 1 2 3 4 5 6; do
    one $S/change $new $((2147496140 + 10 * ${DRAW:-2} + i)) 0 benchmark/tools/probe.py --set traffic.order_draw=${DRAW:-2} $quick
  done
  ;;
others)     # the older cells most at risk, parent beside change, the same seed on both sides
  for c in ${CELLS:-$closed $mixed $lfm}; do
    one $S/parent $c $((2147496171 + ${ROUND:-0})) 0
    one $S/change $c $((2147496171 + ${ROUND:-0})) 0
  done
  ;;
final)      # the final tree, from what git would commit: six seeds, the last traced
  for i in 1 2 3 4 5; do one $S/change $new $((2147496180 + ${ROUND:-0} + i)) 0; done
  BENCH_KEEP_TRACE=$out/trace one $S/change $new $((2147496186 + ${ROUND:-0})) 1 benchmark/tools/span_report.py
  grep -E '^SPANS' $out/*span_report.log | cut -c1-1200
  # two decode programs of the trace for benchmark/tests/data (the 5 s stay there)
  python3 scripts/pr32_cut_trace.py $out/trace/trace.json $out/trinity_two_steps.json 900 > $out/cut.log 2>&1
  gzip -9 $out/trinity_two_steps.json; cp $out/trace/program_spans.json $out/ 2>/dev/null; rm -rf $out/trace
  head -n 40 $out/cut.log | cut -c1-300
  ;;
again)      # one more set of six, seeds never used
  for i in ${AGAIN:-1 2 3 4 5 6}; do one $S/change $new $((2147496190 + ${ROUND:-0} + i)) 0; done
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
