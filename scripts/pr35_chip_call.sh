#!/bin/bash
# PR 35 (perf_opt: the GPT serving tail is the compiler's chain over the scan's slice), the chip calls.
# Parent and change both from git:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 6371e61c | tar -x -C .bench_scratch/parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 2400 -- bash scripts/pr35_chip_call.sh step0 lowered traced   # call 1: Step 0's table, the engine's programs of both trees, decode_closed64 traced on both
#   chiprun --timeout 3000 -- bash scripts/pr35_chip_call.sh pairs mixed            # call 2: the claimed cell in same-seed pairs, mixed_open traced and in pairs
#   chiprun --timeout 2400 -- bash scripts/pr35_chip_call.sh final others pairs2    # call 3: the final tree, the trainer that shares decoder_tail and one other serving configuration, three more pairs
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
one() {  # tree cell seed trace
  local tree=$1 cell=$2 seed=$3 trace=$4
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.log
  (cd $repo/$tree && timeout 1500 python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|check widest|requests:)' $log | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
b=d.get("breakdown", {})
if b.get("device_ops"):
    print("   busy_s", b.get("busy_s"), "window_s", b.get("window_s"))
    for op in b["device_ops"][:12]: print("   op", op)' 2>/dev/null
  tail -n 2 ${log%.log}.err | cut -c1-300
}
gates() {  # what the gate holds for the decoder tail's keys, after the runs so far
  (cd $repo/$S/change && python3 -m paddle_tpu.ops.autobench list --path $PADDLE_TPU_AUTOBENCH_CACHE 2>/dev/null) \
    | grep -E "fused_(out|ffn)_ln" | sed 's/^/   gate /' | cut -c1-200
}
closed=gpt_1p3b_serve.decode_closed64
mixed=gpt_1p3b_serve.mixed_open
lfm=lfm2_8b_a1b_serve.decode_closed128
train=gpt_350m_train.b16s1024
phases=("${@:-step0}")
for what in "${phases[@]}"; do
out=$repo/chiprun_out/pr35/$what
mkdir -p $out
case $what in
step0)      # decoder_tail alone in a scan over 24 stacked layers: kernels forced, the chain, the gate's draw
  (cd $S/change && python3 scripts/pr35_tail_step0.py --out $out/step0.json) 2> $out/step0.err | tee $out/step0.log | cut -c1-400
  echo "== step0 rc=${PIPESTATUS[0]} at $((SECONDS - t0))s"; tail -n 3 $out/step0.err | cut -c1-300
  ;;
lowered)    # the engine's nine programs of both trees, the gate deciding: on the chip they differ (the parent's hold the tail's kernels)
  for t in parent change; do
    (cd $S/$t && python3 scripts/lowered_serving_programs.py --engine --out $out/lowered.$t) > $out/lowered.$t.log 2> $out/lowered.$t.err
    echo "== lowered $t rc=$? at $((SECONDS - t0))s"
    for f in $out/lowered.$t/*.mlir; do
      echo "   $(basename $f): $(grep -c tpu_custom_call $f) tpu_custom_call"
    done
    rm -f $out/lowered.$t/*.mlir
  done
  diff $out/lowered.parent/SHA256 $out/lowered.change/SHA256 && echo "LOWERED the same: $(wc -l < $out/lowered.change/SHA256) programs"
  gates
  ;;
traced)     # the claimed cell traced, the same seed on both trees: decode_device_ms, the copies, the rooflines
  one $S/parent $closed 2147499011 1
  one $S/change $closed 2147499011 1
  gates
  ;;
pairs)      # the claimed cell, untraced, same-seed pairs, sides in turn
  for i in 1 2 3; do
    one $S/parent $closed $((2147499020 + i)) 0; one $S/change $closed $((2147499020 + i)) 0
    one $S/change $closed $((2147499030 + i)) 0; one $S/parent $closed $((2147499030 + i)) 0
  done
  gates
  ;;
pairs2)     # three more pairs of the claimed cell: two of the change's six runs in `pairs` held a stall, none of the parent's
  one $S/change $closed 2147499071 0; one $S/parent $closed 2147499071 0
  one $S/parent $closed 2147499072 0; one $S/change $closed 2147499072 0
  one $S/change $closed 2147499073 0; one $S/parent $closed 2147499073 0
  ;;
mixed)      # mixed_open: traced on both trees, then untraced pairs
  one $S/parent $mixed 2147499041 1
  one $S/change $mixed 2147499041 1
  one $S/change $mixed 2147499042 0; one $S/parent $mixed 2147499042 0
  one $S/parent $mixed 2147499043 0; one $S/change $mixed 2147499043 0
  one $S/change $mixed 2147499044 0; one $S/parent $mixed 2147499044 0
  gates
  ;;
others)     # configurations that share serving/model.py (their classes are not edited) and the trainer that shares decoder_tail
  one $S/parent $train 2147499051 0; one $S/change $train 2147499051 0
  one $S/change $lfm 2147499052 0; one $S/parent $lfm 2147499052 0
  ;;
final)      # the final tree, from what git would commit: new seeds, both GPT cells traced
  one $S/change $closed 2147499061 1
  one $S/change $mixed 2147499062 1
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
