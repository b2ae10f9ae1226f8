#!/bin/bash
# PR 49 (model_config: Xing4.0-29B-A4B served at its widths, 6 of 40 layers),
# the chip calls. Trees from git, so that a call measures what a checkout holds:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive 9a597eb7b0fd | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/      # this PR's benchmark files over the parent
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3000 -- bash benchmark/tools/calls/pr49_call.sh step0 parent first     # call 1 (an earlier tree)
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr49_call.sh step0 first sound faults  # call 2
#   CELLS=kanana2_30b_a3b_serve.longdoc_closed128 chiprun --timeout 3400 -- \
#     bash benchmark/tools/calls/pr49_call.sh seeded draw0 draw1 final2 oldtraced others        # call 3
#   chiprun --timeout 3450 -- bash benchmark/tools/calls/pr49_call.sh choose   # call 4 (after REVIEW.md)
# One compile cache and one gate cache for both trees, as on the driver's
# machine.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
ROUND=${ROUND:-0}
one() {  # tree cell seed trace [tool [tool's arguments]]
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  shift 5 2>/dev/null || shift $#
  local tag=$(basename $tool .py)$(echo "$*" | tr -c 'a-zA-Z0-9_\n' '_')
  local log=$out/$cell.$(basename $tree).t$trace.seed_$seed.$tag.log
  last_log=$log
  local at=$SECONDS
  (cd $repo/$tree && timeout 1500 python3 $tool "$@" \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  last_rc=$?
  echo "== $cell $tree $tool $* trace=$trace seed=$seed rc=$last_rc took $((SECONDS - at))s at $((SECONDS - t0))s; $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(check widest|gate decisions|CONTROL|window |reference|warm-up)' $log | cut -c1-600
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("attempted"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for row in d.get("breakdown", {}).get("device_ops", [])[:14]: print("   ", round(row[1], 4), row[0][:260])
print("   idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
  tail -n 3 ${log%.log}.err | cut -c1-400
}
rate() {  # a run's log: its out_tok_s (0 where the run gave no line)
  grep -E '^\{' $1 | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("metrics", {}).get("out_tok_s", {}).get("value", 0) if d.get("correct") else 0)'
}
fits() {  # target rate...: the rates' median, its distance, and whether that is within 1.25%
  python3 -c '
import statistics,sys
target, rates = float(sys.argv[1]), [float(r) for r in sys.argv[2:]]
mid = statistics.median(rates)
print(f"median {mid:.2f} of {rates}: {100 * (mid / target - 1):+.2f}% of {target}", file=sys.stderr)
sys.exit(0 if abs(mid / target - 1) <= 0.0125 else 1)' "$@"
}
left() { echo $(( ${BUDGET:-3300} - SECONDS + t0 )); }   # seconds of the call's budget
pair() {  # cell seed: parent and change on one seed, the side that runs first alternating
  if [ $(( $2 % 2 )) -eq 0 ]; then one $S/parent $1 $2 0; one $S/change $1 $2 0
  else one $S/change $1 $2 0; one $S/parent $1 $2 0; fi
}
new=xing4_29b_a4b_serve.longin_closed64
S=${S:-.bench_scratch}
# a run whose reference replays fewer requests: for rates, not for limits
quick="--set config.correct.sample_requests=1"
for what in "${@:-first}"; do
out=$repo/chiprun_out/pr49/$what
mkdir -p $out
case $what in
step0)      # the stream's steps of one sub-layer alone, against their bytes
  (cd $repo/$S/change && python3 scripts/hyper_step0.py --out $out) 2> $out/step0.err | tee $out/step0.log
  tail -n 3 $out/step0.err | cut -c1-400
  ;;
parent)     # the parent under this PR's benchmark files: must fail at once
  one $S/parent $new $((2147490001 + ROUND)) 0
  ;;
first)      # the change: a traced run with the fp8 control (the trace kept)
  PADDLE_TPU_AUTOBENCH_VERBOSE=1 BENCH_KEEP_TRACE=$out/trace \
    one $S/change $new $((2147490011 + ROUND)) 1 benchmark/tools/probe.py --control fp8
  python3 scripts/pr42_trace_ops.py $out/trace/trace.json 70 > $out/trace_ops.txt 2>&1
  head -n 150 $out/trace_ops.txt | cut -c1-330
  gzip -f $out/trace/*.json
  # a first run that did not reach its result line: nothing after it would
  if [ $last_rc -ne 0 ]; then echo "first run failed: stopping"; exit 1; fi
  ;;
sound)      # seeds for the limits, the whole sample of 4
  for i in ${SEEDS:-1 2 3 4 5 6}; do one $S/change $new $((2147490100 + ROUND + i)) 0; done
  ;;
controls)   # the reference in the precision below beside the program
  one $S/change $new $((2147490201 + ROUND)) 0 benchmark/tools/probe.py --control fp8 --set config.correct.sample_requests=2
  ;;
faults)     # each fault must read not correct
  for f in ${FAULTS:-sinkhorn_1 post_unscaled q_norm_dropped mscale_dropped streams_mean_in}; do
    one $S/change $new $((2147490301 + ROUND)) 0 benchmark/tools/probe_hyper_fault.py --fault $f --set config.correct.sample_requests=2
  done
  ;;
seeded)     # the epochs' order from the seed
  for i in ${SEEDED:-1 2 3 4 5 6 7 8 9 10 11 12}; do
    one $S/change $new $((2147490400 + ROUND + i)) 0 benchmark/tools/probe.py $quick --set 'traffic.order="seed"'
  done
  ;;
draw[0-9])  # the file's order, another draw (draw0, draw1, ...): three seeds
  for i in ${DRAWN:-1 2 3}; do
    one $S/change $new $((2147490500 + ROUND + i)) 0 benchmark/tools/probe.py $quick --set traffic.order_draw=${what#draw}
  done
  ;;
final)      # the committed tree: six seeds, the last traced
  for i in 1 2 3 4 5; do one $S/change $new $((2147490600 + ROUND + i)) 0; done
  one $S/change $new $((2147490606 + ROUND)) 1
  ;;
final2)     # a second set of six, untraced
  for i in 1 2 3 4 5 6; do one $S/change $new $((2147490700 + ROUND + i)) 0; done
  ;;
others)     # the cells whose code the change touches, parent beside change on one seed
  for cell in ${CELLS:-kanana2_30b_a3b_serve.longdoc_closed128 lfm2_8b_a1b_serve.decode_closed128 trinity_mini_serve.shortlong_closed128}; do
    pair $cell $((2147490801 + ROUND))
  done
  ;;
choose)     # section 2's rule, then the committed file's runs, as far as the budget goes:
  # the smallest draw (2 was measured: 3.4% under) whose two screening seeds and then its set
  # of six lie within 1.25% of the seeded orders' median (call 3: 399.9); a second set of six
  # and a traced run on that file; the largest bucket both ways; draw 2 once more (the tree
  # changed after call 3: one turn of `lax.map` round the small buckets' feed-forward)
  target=${TARGET:-399.9} chosen=
  file=$repo/$S/change/benchmark/traffic/$(echo $new | cut -d. -f2).json
  for d in ${DRAWS:-0 1 3 4 5 6 7}; do
    [ $(left) -lt 1050 ] && { echo "no time for another draw: $(left)s left"; break; }
    rates=()
    for i in 1 2; do
      one $S/change $new $((2147491000 + 100 * d + i)) 0 benchmark/tools/probe.py $quick --set traffic.order_draw=$d
      rates+=($(rate $last_log))
    done
    echo "draw $d, screening:"; fits $target ${rates[@]} || continue
    sed -i "s/\"order_draw\": [0-9]*/\"order_draw\": $d/" $file; grep '"order_draw":' $file
    rates=()
    for i in 1 2 3 4 5 6; do
      one $S/change $new $((2147492000 + 100 * d + i)) 0; rates+=($(rate $last_log))
    done
    echo "draw $d, the file's first set of six:"
    if fits $target ${rates[@]}; then chosen=$d; break; fi
  done
  echo "CHOSEN order_draw: ${chosen:-none}"
  if [ -n "$chosen" ] && [ $(left) -gt 800 ]; then
    rates=()
    for i in 1 2 3 4 5 6; do
      one $S/change $new $((2147493000 + 100 * chosen + i)) 0; rates+=($(rate $last_log))
    done
    echo "draw $chosen, the file's second set of six:"; fits $target ${rates[@]}
  fi
  if [ -n "$chosen" ] && [ $(left) -gt 170 ]; then
    BENCH_KEEP_TRACE=$out/trace one $S/change $new $((2147494000 + chosen)) 1
    gzip -f $out/trace/*.json
  fi
  if [ $(left) -gt 330 ]; then
    (cd $repo/$S/change && timeout 600 python3 scripts/pr49_bucket_both_ways.py) 2> $out/both_ways.err | tee $out/both_ways.log
    tail -n 3 $out/both_ways.err | cut -c1-400
  fi
  if [ $(left) -gt 150 ]; then
    one $S/change $new 2147490101 0 benchmark/tools/probe.py $quick --set traffic.order_draw=2
    echo "draw 2 on this tree, seed 2147490101 (call 2 read 384.98 there): $(rate $last_log)"
  fi
  ;;
oldtraced)  # one old cell traced, on the parent under this PR's benchmark files
  one $S/parent kanana2_30b_a3b_serve.longdoc_closed128 $((2147490901 + ROUND)) 1
  ;;
esac
done
echo "done at $((SECONDS - t0))s"
