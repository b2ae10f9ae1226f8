#!/bin/bash
# PR 30 (model_config: Ouro-2.6B served whole), the chip calls: parent
# against change, both from git.
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,laid,change}
#   git archive 9f8356ef5c99 | tar -x -C .bench_scratch/parent
#   git archive 9f8356ef5c99 | tar -x -C .bench_scratch/laid     # the parent under THIS PR's benchmark files,
#   git archive $(git write-tree) BENCHMARK.json benchmark | tar -x -C .bench_scratch/laid   # as the driver lays them
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3000 -- bash scripts/pr30_chip_call.sh cell      # calls 1-2: the new cell: parent fails at once; 3 seeds, 1 traced; the controls
#   chiprun --timeout 3000 -- bash scripts/pr30_chip_call.sh old       # call 3: the four older one-chip cells, parent beside change
#   chiprun --timeout 3000 -- bash scripts/pr30_chip_call.sh seeds final   # call 4, the final tree (change = git archive $(git write-tree)):
#                                                                      # six more seeds and the controls again, then one traced run and an old cell traced
#   chiprun --chips 4 --timeout 1500 -- bash scripts/pr30_chip_call.sh pp  # call 5: the four-chip cell, parent beside change
#   chiprun --timeout 1500 -- bash scripts/pr30_chip_call.sh final      # call 6: the tree as committed, `final` once more (the same seeds: a repeat)
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
  local tag=$(echo "$tool $*" | tr -c 'a-zA-Z0-9_\n' '_' | cut -c1-60)
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$tag.log
  (cd $repo/$tree && timeout 900 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds ${SECS:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(weights|warm-up|window|check widest|CONTROL|gate decisions|reference:)' $log | cut -c1-600
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", []): print("   ", round(row[1], 4), row[0])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  grep -E '^SPANS' $log | cut -c1-1500
  tail -n 2 ${log%.log}.err | cut -c1-300
}
new=ouro_2p6b_serve.decode_closed32
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
train=gpt_350m_train.b16s1024
pp=gpt_1p3b_train_pp2tp2.mb2x8s1024
S=.bench_scratch
for what in "${@:-cell}"; do
out=$repo/chiprun_out/pr30/$what
mkdir -p $out
case $what in
cell)
  one $S/parent $new 2147495001 0       # no such workload: exit 2 at once
  one $S/laid $new 2147495001 0         # the workload, no such model: fails at once
  BENCH_KEEP_TRACE=$out/trace one $S/change $new 2147495001 1 benchmark/tools/span_report.py
  python3 scripts/pr30_cut_trace.py $out/trace/trace.json $out/two_steps.json | head -n 3; rm -rf $out/trace
  one $S/change $new 2147495002 0
  one $S/change $new 2147495003 0
  one $S/change $new 2147495004 0 benchmark/tools/probe.py --control fp8
  one $S/change $new 2147495007 0 benchmark/tools/probe.py --control bf16
  one $S/change $new 2147495005 0 benchmark/tools/probe_loop_fault.py --fault three_passes
  one $S/change $new 2147495006 0 benchmark/tools/probe_loop_fault.py --fault attend_pass_0
  ;;
old)
  one $S/parent $closed 2147495011 0
  one $S/change $closed 2147495011 0
  one $S/change $closed 2147495012 0
  one $S/parent $closed 2147495012 0
  one $S/parent $mixed 2147495021 0
  one $S/change $mixed 2147495021 0
  one $S/change $lfm 2147495031 0
  one $S/parent $lfm 2147495031 0
  one $S/parent $train 2147495041 0
  one $S/change $train 2147495041 0
  one $S/change $closed 2147495013 1    # an old cell traced under this PR's benchmark files
  one $S/laid $closed 2147495013 1      # and the parent under them, as the driver runs it
  ;;
seeds)
  for i in 1 2 3 4 5 6; do one $S/change $new $((2147495050 + i)) 0; done
  one $S/change $new 2147495057 0 benchmark/tools/probe.py --control fp8
  one $S/change $new 2147495058 0 benchmark/tools/probe_loop_fault.py --fault three_passes
  one $S/change $new 2147495059 0 benchmark/tools/probe_loop_fault.py --fault attend_pass_0
  ;;
pp)
  one $S/parent $pp 2147495061 0
  one $S/change $pp 2147495061 0
  ;;
final)      # the final tree, from what git would commit
  BENCH_KEEP_TRACE=$out/trace one $S/change $new 2147495071 1 benchmark/tools/span_report.py
  python3 scripts/pr30_cut_trace.py $out/trace/trace.json $out/two_steps.json | head -n 3; rm -rf $out/trace
  one $S/change $closed 2147495073 1
  one $S/laid $new 2147495071 0         # and the parent under this PR's benchmark files still fails at once
  ;;
esac
cp $PADDLE_TPU_AUTOBENCH_CACHE $out/ 2>/dev/null
done
echo "done at $((SECONDS - t0))s"
