#!/bin/bash
# PR 31 (perf_opt: the step loop dispatches decode k+1 before it reads
# decode k), the chip calls: parent against change, both from git.
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 11218223f83d | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3500 -- bash scripts/pr31_chip_call.sh step0 claimed   # call 1: Step 0, then the three claimed cells
#   chiprun --timeout 3000 -- bash scripts/pr31_chip_call.sh probe others    # call 2: the widest gaps' streams; the looped cell, a training cell, more seeds
#   chiprun --timeout 2400 -- bash scripts/pr31_chip_call.sh final           # call 3: the final tree once more
# One compile cache and one gate cache for all trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
one() {  # tree cell seed trace [tool]
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$(basename $tool .py).log
  (cd $repo/$tree && timeout 900 python3 $tool \
     --workload $cell --seed $seed --seconds ${SECS:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree $tool trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|check widest|gap percentiles|requests:)' $log | cut -c1-400
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:6]: print("   ", round(row[1], 4), row[0])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  grep -E '^(SPANS|GAPS|AHEAD)' $log | cut -c1-2400
  tail -n 2 ${log%.log}.err | cut -c1-300
}
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
ouro=ouro_2p6b_serve.decode_closed32
train=gpt_350m_train.b16s1024
S=.bench_scratch
pair() {  # cell, first seed: parent, change, change, parent, then both traced
  one $S/parent $1 $2 0
  one $S/change $1 $2 0
  one $S/change $1 $(($2 + 1)) 0
  one $S/parent $1 $(($2 + 1)) 0
  one $S/change $1 $(($2 + 2)) 1 benchmark/tools/span_report.py
  one $S/parent $1 $(($2 + 2)) 1 benchmark/tools/span_report.py
}
for what in "${@:-claimed}"; do
out=$repo/chiprun_out/pr31/$what
mkdir -p $out
case $what in
step0)
  (cd $S/change && python3 scripts/pr31_step0.py) > $out/step0.log 2> $out/step0.err
  echo "== step0 rc=$? at $((SECONDS - t0))s"; grep -E '^STEP0' $out/step0.log; tail -n 2 $out/step0.err | cut -c1-300
  ;;
claimed)
  pair $closed 2147495111
  pair $mixed 2147495121
  pair $lfm 2147495131
  ;;
probe)      # which stream waits, and over which steps (call 1 read one gap of 1.9-2.1 s a run in the lfm2 cell)
  one $S/change $lfm 2147495131 0 scripts/pr31_gap_probe.py
  one $S/change $closed 2147495111 0 scripts/pr31_gap_probe.py
  ;;
others)
  one $S/parent $ouro 2147495141 0
  one $S/change $ouro 2147495141 0
  one $S/change $ouro 2147495142 1 benchmark/tools/span_report.py
  one $S/parent $train 2147495151 0
  one $S/change $train 2147495151 0
  for i in 4 5 6; do one $S/change $closed $((2147495110 + i)) 0; done
  for i in 4 5 6; do one $S/change $mixed $((2147495120 + i)) 0; done
  for i in 4 5; do one $S/change $lfm $((2147495130 + i)) 0; done
  ;;
final)      # the final tree, from what git would commit
  one $S/change $closed 2147495161 1
  one $S/parent $closed 2147495161 0
  one $S/change $mixed 2147495162 1
  one $S/parent $mixed 2147495162 0
  one $S/change $lfm 2147495163 1
  one $S/parent $lfm 2147495163 0
  one $S/change $ouro 2147495164 0
  one $S/change $closed 2147495165 0 scripts/pr31_gap_probe.py     # `decodes_ahead` over `steps`
  one $S/change $lfm 2147495166 0 scripts/pr31_gap_probe.py
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
