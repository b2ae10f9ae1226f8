#!/bin/bash
# PR 34 (tracing: the admission path accounts for itself), the chip calls.
# Parent and change both from git:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 4b5ec509 | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3000 -- bash scripts/pr34_chip_call.sh cost onoff     # call 1: a span's cost, the lowered programs, tracing on and off in the two GPT cells
#   chiprun --timeout 3500 -- bash scripts/pr34_chip_call.sh traced overlay pairs onoff2  # call 2: the four out_tok_s cells traced, the parent under this PR's benchmark files, the other configurations parent beside change
#   chiprun --timeout 2400 -- bash scripts/pr34_chip_call.sh final onoff3   # call 3: the final tree, and six more pairs of decode_closed64
#   chiprun --timeout 2400 -- bash scripts/pr34_chip_call.sh review         # call 4, after the review: no fence, stamp or counter in a prefill, the tails on one clock
#   chiprun --timeout 1800 -- bash scripts/pr34_chip_call.sh kanana3        # call 5: three more pairs of the kanana cell
# One compile cache and one gate cache for all trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
S=.bench_scratch
one() {  # tree cell seed trace [PADDLE_TPU_TRACE]: a traced run goes through span_report.py (the same run, and a SPANS line)
  local tree=$1 cell=$2 seed=$3 trace=$4 rec=${5:-1}
  local log=$out/$cell.$(basename $tree).t$trace.rec$rec.seed_$seed.log
  local keep=$out/keep.$(basename $tree)
  local cmd=benchmark/run.py
  [ $trace = 1 ] && cmd=benchmark/tools/span_report.py
  (cd $repo/$tree && PADDLE_TPU_TRACE=$rec BENCH_KEEP_TRACE=$([ $trace = 1 ] && echo $keep) \
     timeout 1500 python3 $cmd --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace PADDLE_TPU_TRACE=$rec seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|check widest|requests:)' $log | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  [ $trace = 1 ] && grep -E '^SPANS' $log | python3 -c '
import json,sys
d=json.loads((sys.stdin.read() or "SPANS {}")[6:])
print("   SPANS", {k: d.get(k) for k in ("dropped", "spans_in_ring", "steps", "cover_p50", "host_ms_p50", "step_ms", "stall_steps", "step_sampler_last_ms", "between_steps_ms", "profiler_start_gap_ms", "idle_share")})' 2>/dev/null
  tail -n 2 ${log%.log}.err | cut -c1-300
  if [ -f $keep/program_spans.json ]; then
    python3 scripts/pr34_admission_account.py $keep/program_spans.json 2>&1 | cut -c1-1500 \
      | tee ${log%.log}.account.txt
    rm -rf $keep
  fi
}
kanana=kanana2_30b_a3b_serve.longdoc_closed128
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
ouro=ouro_2p6b_serve.decode_closed32
phases=("${@:-cost}")
for what in "${phases[@]}"; do
out=$repo/chiprun_out/pr34/$what
mkdir -p $out
case $what in
cost)       # what a span costs on this host, and the engine's nine programs of both trees
  (cd $S/change && python3 benchmark/tools/span_report.py --cost) 2> $out/cost.err | tee $out/cost.log | cut -c1-600
  for t in parent change; do
    (cd $S/$t && python3 scripts/lowered_serving_programs.py --engine --out $out/lowered.$t) > $out/lowered.$t.log 2> $out/lowered.$t.err
    echo "== lowered $t rc=$? at $((SECONDS - t0))s"; rm -f $out/lowered.$t/*.mlir
  done
  diff $out/lowered.parent/SHA256 $out/lowered.change/SHA256 && echo "LOWERED the same: $(wc -l < $out/lowered.change/SHA256) programs"
  ;;
onoff)      # tracing on (the default the driver measures) against off, parent and change, a seed a group, sides in turn
  for c in $mixed $closed; do
    i=0
    for seed in ${SEEDS:-2147498111 2147498112}; do
      if [ $((i % 2)) = 0 ]; then
        one $S/parent $c $seed 0 1; one $S/change $c $seed 0 1; one $S/change $c $seed 0 0; one $S/parent $c $seed 0 0
      else
        one $S/change $c $seed 0 0; one $S/parent $c $seed 0 0; one $S/parent $c $seed 0 1; one $S/change $c $seed 0 1
      fi
      i=$((i + 1))
    done
  done
  ;;
traced)     # the four cells that report out_tok_s, traced: the five new readings
  one $S/change $closed 2147498121 1
  one $S/change $lfm 2147498122 1
  one $S/change $ouro 2147498123 1
  one $S/change $kanana 2147498124 1
  ;;
overlay)    # the parent's program under this PR's benchmark files, as the driver lays them: a traced run must not fail
  rm -rf $S/overlay; cp -r $S/parent $S/overlay
  cp $S/change/BENCHMARK.json $S/overlay/BENCHMARK.json
  rm -rf $S/overlay/benchmark; cp -r $S/change/benchmark $S/overlay/benchmark
  one $S/overlay $closed 2147498121 1
  one $S/overlay $mixed 2147498125 1
  ;;
pairs)      # the other configurations that run engine.py: parent beside change, untraced
  one $S/parent $lfm 2147498131 0; one $S/change $lfm 2147498131 0
  one $S/change $ouro 2147498132 0; one $S/parent $ouro 2147498132 0
  one $S/parent $kanana 2147498133 0; one $S/change $kanana 2147498133 0
  ;;
onoff2)     # decode_closed64 again with tracing on, two more seeds: call 1's same-seed runs fell in two groups 1% apart on both sides
  one $S/parent $closed 2147498151 0; one $S/change $closed 2147498151 0
  one $S/change $closed 2147498152 0; one $S/parent $closed 2147498152 0
  ;;
onoff3)     # six more same-seed pairs of decode_closed64, tracing on, sides in turn: is the 0.3% of calls 1-2 there
  for i in 1 2 3; do
    one $S/parent $closed $((2147498160 + i)) 0; one $S/change $closed $((2147498160 + i)) 0
    one $S/change $closed $((2147498170 + i)) 0; one $S/parent $closed $((2147498170 + i)) 0
  done
  ;;
review)     # after the review (the prefill's fence, its stamp and the two counters out; the readers' tails on one clock):
            # the four cells traced on the change, the parent under this PR's benchmark files, and the two cells where a prefill weighs most, parent beside change
  one $S/change $closed 2147498181 1
  one $S/change $lfm 2147498182 1
  one $S/change $ouro 2147498183 1
  one $S/change $kanana 2147498184 1
  rm -rf $S/overlay; cp -r $S/parent $S/overlay
  cp $S/change/BENCHMARK.json $S/overlay/BENCHMARK.json
  rm -rf $S/overlay/benchmark; cp -r $S/change/benchmark $S/overlay/benchmark
  one $S/overlay $closed 2147498181 1
  one $S/parent $kanana 2147498185 0; one $S/change $kanana 2147498185 0
  one $S/change $closed 2147498186 0; one $S/parent $closed 2147498186 0
  ;;
kanana3)    # the review call's kanana pair read 2,263.4 -> 2,096.9 with one 2 s slice of the change's at 267.5 tok/s (a stall): three more same-seed pairs, sides in turn
  one $S/change $kanana 2147498191 0; one $S/parent $kanana 2147498191 0
  one $S/parent $kanana 2147498192 0; one $S/change $kanana 2147498192 0
  one $S/change $kanana 2147498193 0; one $S/parent $kanana 2147498193 0
  ;;
final)      # the final tree, from what git would commit: a new seed
  one $S/change $closed 2147498141 1
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
