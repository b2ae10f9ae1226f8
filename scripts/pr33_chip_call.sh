#!/bin/bash
# PR 33 (perf_opt: the sampler searches, no [S, V] sort), the chip calls.
# Parent and change both from git:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive ef190da801a5 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 1500 -- python3 scripts/sampler_step0.py --out chiprun_out/pr33/step0_call1.json   # call 1: Step 0, every candidate
#   chiprun --timeout 3500 -- bash scripts/pr33_chip_call.sh step0 probe claimed   # call 2: the shipped spelling alone, PR 27's probe, the two claimed cells
#   chiprun --timeout 3500 -- bash scripts/pr33_chip_call.sh others              # call 3: every other one-chip cell
#   chiprun --timeout 2400 -- bash scripts/pr33_chip_call.sh final               # call 4: the tree before the sampler got a jit of its own
#   chiprun --timeout 3000 -- bash scripts/pr33_chip_call.sh setup final2        # call 5: warm set-up in turn, and the final tree
# One compile cache and one gate cache for all trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
one() {  # tree cell seed trace
  local tree=$1 cell=$2 seed=$3 trace=$4
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.log
  local keep=$out/trace.$(basename $tree)
  (cd $repo/$tree && BENCH_KEEP_TRACE=$keep timeout 1200 python3 benchmark/run.py \
     --workload $cell --seed $seed --seconds ${SECS:-40} --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|check widest|requests:)' $log | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:16]: print("   ", round(row[1], 4), row[0][:230])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 2 ${log%.log}.err | cut -c1-300
  if [ -f $keep/trace.json ]; then   # the sampler's own time, from the kept trace
    python3 scripts/pr33_sampler_ops.py $keep/trace.json ${SV[$cell]} 2>&1 | cut -c1-260 \
      | tee ${log%.log}.sampler_ops.txt
    rm -rf $keep
  fi
}
pair() {  # cell seed seed: parent, change, change, parent; then both traced
  one $S/parent $1 $2 0; one $S/change $1 $2 0
  one $S/change $1 $3 0; one $S/parent $1 $3 0
}
kanana=kanana2_30b_a3b_serve.longdoc_closed128
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
ouro=ouro_2p6b_serve.decode_closed32
train=gpt_350m_train.b16s1024
declare -A SV=([$kanana]="64 128256" [$lfm]="64 65536" [$closed]="32 50304" [$mixed]="32 50304" [$ouro]="16 49152" [$train]="0 0")
S=.bench_scratch
phases=("${@:-step0}")
for what in "${phases[@]}"; do
out=$repo/chiprun_out/pr33/$what
mkdir -p $out
case $what in
step0)      # the sampler alone: the sort, what ships, its nearest losers
  python3 scripts/sampler_step0.py --candidates sort,shipped,b1.fc,b2 \
    --out $out/step0.json > $out/step0.log 2> $out/step0.err
  echo "== step0 rc=$? at $((SECONDS - t0))s"; grep -E '^STEP0' $out/step0.log | cut -c1-2500; tail -n 2 $out/step0.err | cut -c1-300
  ;;
probe)      # PR 27's probe, as it is: the parent's draws beside the change's
  python3 benchmark/tools/calls/pr27_sampler_probe.py $S/parent $S/change \
    > $out/probe.log 2> $out/probe.err
  echo "== probe rc=$? at $((SECONDS - t0))s"; grep -E '^PROBE' $out/probe.log | cut -c1-900; tail -n 2 $out/probe.err | cut -c1-300
  ;;
claimed)    # the two cells of the claim: two pairs each, then both sides traced
  pair $kanana 2147497101 2147497102
  one $S/change $kanana 2147497103 1; one $S/parent $kanana 2147497103 1
  pair $lfm 2147497111 2147497112
  one $S/change $lfm 2147497113 1; one $S/parent $lfm 2147497113 1
  ;;
others)     # every other one-chip cell: a pair, and the serving ones traced
  for c in $closed $mixed; do
    one $S/parent $c 2147497121 0; one $S/change $c 2147497121 0
    one $S/change $c 2147497122 1; one $S/parent $c 2147497122 1
  done
  one $S/parent $ouro 2147497131 0; one $S/change $ouro 2147497131 0
  one $S/change $ouro 2147497132 1
  one $S/parent $train 2147497141 0; one $S/change $train 2147497141 0
  ;;
setup)      # warm set-up, parent beside change in turn (the sampler is traced once a shape, not once a program)
  for i in 1 2 3; do SECS=10 one $S/change $closed $((2147497160 + i)) 0; SECS=10 one $S/parent $closed $((2147497160 + i)) 0; done
  for i in 1 2; do SECS=10 one $S/change $mixed $((2147497170 + i)) 0; SECS=10 one $S/parent $mixed $((2147497170 + i)) 0; done
  for i in 1 2; do one $S/change $lfm $((2147497180 + i)) 0; one $S/parent $lfm $((2147497180 + i)) 0; done
  grep -oE 'set-up so far [0-9.]+s' $out/*.log
  ;;
final2)     # the final tree again (the sampler under a jit of its own): the cell with most to lose
  one $S/change $kanana 2147497191 1
  one $S/change $kanana 2147497192 0
  ;;
final)      # the final tree, from what git would commit: new seeds, a third pair of each claimed cell
  one $S/change $kanana 2147497151 1
  one $S/change $kanana 2147497152 0; one $S/parent $kanana 2147497152 0
  one $S/change $lfm 2147497153 0; one $S/parent $lfm 2147497153 0
  one $S/change $lfm 2147497154 1
  one $S/change $closed 2147497155 0
  one $S/change $mixed 2147497156 0
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
