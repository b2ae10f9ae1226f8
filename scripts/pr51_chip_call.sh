#!/bin/bash
# PR 51 (tracing: the pause spans and the stall account), the chip calls.
# The parent from git, the change as it stands on disk:
#   rm -rf .bench_scratch; mkdir -p .bench_scratch/parent .bench_scratch/overlay
#   git archive ba32606b422e | tar -x -C .bench_scratch/parent
#   git archive ba32606b422e | tar -x -C .bench_scratch/overlay
#   cp -r BENCHMARK.json benchmark .bench_scratch/overlay/     # the parent under this PR's benchmark files
#   chiprun --timeout 3550 -- bash scripts/pr51_chip_call.sh traced64 inject overlay64 cost64 huntouro setuptrain huntxing
#   chiprun --timeout 3550 -- bash scripts/pr51_chip_call.sh costjamba setupkanana huntxing
#   chiprun --chips 4 --timeout 1500 -- bash scripts/pr51_chip_call.sh setuppp
# Machines are scarce (a call may be the only one): the phases come in
# the order of what PERF.md needs most, and a run that would not end
# before LIMIT seconds of the call is left out and said so.
# One compile cache and one gate cache for both trees, as on the driver's
# machine within a checkout.
repo=$PWD
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
export PADDLE_TPU_AUTOBENCH_CACHE=$JAX_COMPILATION_CACHE_DIR/autobench_gate.json
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
echo "compile cache $JAX_COMPILATION_CACHE_DIR: $(ls $JAX_COMPILATION_CACHE_DIR 2>/dev/null | wc -l) entries came with the machine"
t0=$SECONDS
: ${LIMIT:=3350}
fits() {  # seconds a run is expected to take: is there room for it
  if [ $((SECONDS - t0 + $1)) -gt $LIMIT ]; then
    echo "== LEFT OUT for time at $((SECONDS - t0))s: $2"; return 1; fi
}
out=$repo/chiprun_out/pr51
mkdir -p $out
d64=gpt_1p3b_serve.decode_closed64
jamba=jamba2_3b_serve.chat_closed512
quick="--set config.correct.sample_requests=1"

hunt() {  # cell runs seed0 tag [more arguments of the hunt script]
  local cell=$1 runs=$2 seed=$3 tag=$4; shift 4
  local at=$SECONDS
  fits $((runs * EST)) "hunt $cell $tag x$runs" || return
  python3 scripts/pr51_stall_hunt.py --workload $cell --runs $runs \
    --seed0 $seed --tag $tag "$@" 2>&1 | grep -E '^(run |   stall |caught|HUNT-SUMMARY)' | cut -c1-1800
  echo "== hunt $cell $tag $* rc=${PIPESTATUS[0]} took $((SECONDS - at))s at $((SECONDS - t0))s"
}
parent() {  # cell seed tag: the parent's own untraced run
  local cell=$1 seed=$2 tag=$3 at=$SECONDS
  fits $EST "parent $cell $tag" || return
  local log=$out/$cell.parent.$seed.$tag.log
  (cd $repo/.bench_scratch/parent && python3 benchmark/tools/probe.py $quick \
     --workload $cell --seed $seed --seconds 40 --trace 0) > $log 2> ${log%.log}.err
  echo "== parent $cell $tag seed=$seed rc=$? took $((SECONDS - at))s at $((SECONDS - t0))s"
  line $log
}
line() {  # the result line of a log, short
  grep -E '^\{' $1 | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print("   ", d.get("correct"), d.get("attempted"), d.get("failed"), json.dumps({k: v["value"] for k, v in d.get("metrics", {}).items()}))
print("    idle gaps", d.get("breakdown", {}).get("idle_gaps"))' 2>/dev/null
}
traced() {  # tree cell seed: `benchmark/run.py --trace 1`, the line whole
  local tree=$1 cell=$2 seed=$3 at=$SECONDS
  fits $((EST + 15)) "traced $tree $cell" || return
  local log=$out/$cell.$(basename $tree).traced.$seed.log
  (cd $repo/$tree && python3 benchmark/run.py --workload $cell --seed $seed \
     --seconds 40 --trace 1) > $log 2> ${log%.log}.err
  echo "== traced $tree $cell seed=$seed rc=$? took $((SECONDS - at))s at $((SECONDS - t0))s"
  line $log
  tail -n 3 ${log%.log}.err | cut -c1-300
}
cost() {  # cell seed triples: parent, change with spans, change without, the order turning
  local cell=$1 seed=$2 n=$3
  hunt $cell 1 $seed cold --quick          # fills the caches; a hunt run too
  for i in $(seq 1 $n); do
    for k in 0 1 2; do
      case $(( (i + k) % 3 )) in
        0) parent $cell $seed p$i ;;
        1) hunt $cell 1 $seed on$i --quick ;;
        2) hunt $cell 1 $seed off$i --quick --env PADDLE_TPU_TRACE=0 ;;
      esac
    done
  done
}
# EST: seconds a warm run of the cell takes, set-up to the reference
for what in "$@"; do
case $what in
cost64)     EST=85; cost $d64 2147500651 ${TRIPLES:-6} ;;
costjamba)  EST=140; cost $jamba 2147500652 ${TRIPLES:-4} ;;
inject)     # one stall of 0.5 s armed for one step of the window
  EST=110; hunt $d64 1 2147500700 inject --quick --inject-ms 500 ;;
traced64)   EST=130; traced . $d64 2147500710 ;;
overlay64)  # the parent under this PR's benchmark files: the new metrics absent, nothing raised
  EST=110; traced .bench_scratch/overlay $d64 2147500710 ;;
huntxing)   # one run at a time, so that what fits is run
  EST=140
  for k in $(seq 0 $((${RUNS:-12} - 1))); do
    hunt xing4_29b_a4b_serve.longin_closed64 1 $((2147500800 + k)) hunt --quick | tee $out/.last
    grep -q 'stall_longest_ms": [0-9]\{4,\}' $out/.last && { echo "== a stall of a second or more: the hunt ends"; break; }
  done ;;
huntouro)   EST=95
  for k in $(seq 0 $((${RUNS:-4} - 1))); do
    hunt ouro_2p6b_serve.decode_closed32 1 $((2147500900 + k)) hunt --quick; done ;;
setupkanana)  EST=190
  for k in 0 1; do hunt kanana2_30b_a3b_serve.longdoc_closed128 1 $((2147501000 + k)) setup$k --quick; done ;;
setuptrain) EST=110
  for k in 0 1; do hunt gpt_350m_train.b16s1024 1 $((2147501100 + k)) setup$k; done
  traced . gpt_350m_train.b16s1024 2147501110 ;;
setuppp)    EST=260
  for k in 0 1; do hunt gpt_1p3b_train_pp2tp2.mb2x8s1024 1 $((2147501200 + k)) setup$k; done ;;
*) echo "unknown phase $what" ;;
esac
done
echo "== done at $((SECONDS - t0))s"
