#!/bin/bash
# PR 27, chip call 2 (one chip): both GPT serving cells, parent against
# change, trees unpacked as for call 1 (pr27_call1.sh):
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr27_call2.sh
# decode_closed64: the contract's pair through pr27_streams.py (same seed,
# every request's tokens compared; the decode programs' optimised HLO is
# kept), then change-parent and parent-change on two more seeds and a traced
# run of each tree through tools/span_report.py. mixed_open: parent-change,
# change-parent, parent-change and the same two traced runs.
repo=$PWD
out=$repo/chiprun_out/pr27/call2
mkdir -p $out/hlo
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
t0=$SECONDS
one() {  # tree cell seed trace tool
  local tree=$1 cell=$2 seed=$3 trace=$4 tool=${5:-benchmark/run.py}
  local log=$out/$cell.$tree.t$trace.seed_$seed.log
  (cd $repo/.bench_scratch/$tree && python3 $tool \
     --workload $cell --seed $seed --seconds 40 --trace $trace) \
    > $log 2> ${log%.log}.err
  echo "== $cell $tree trace=$trace seed=$seed rc=$? at $((SECONDS - t0))s $(grep -E '^compile cache events' $log | cut -c1-60)"
  grep -E '^(window|gap percentiles|ttft percentiles|check widest|reference|streams)' $log | cut -c1-260
  grep -E '^\{' $log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for op in (d.get("breakdown") or {}).get("device_ops", []):
    print("   op", json.dumps(op)[:200])'
  grep -E '^SPANS' $log | cut -c1-1800
  tail -n 1 ${log%.log}.err | cut -c1-200
}
streams() {  # tree cell seed: one run with its streams and its decode HLO
  local tree=$1 cell=$2 seed=$3
  local d=$out/hlo/$cell.$tree
  mkdir -p $d
  BENCH_STREAMS=$out/streams.$cell.$tree.json \
  XLA_FLAGS="--xla_dump_to=$d --xla_dump_hlo_as_text --xla_dump_hlo_module_re=jit_decode" \
    one $tree $cell $seed 0 benchmark/tools/calls/pr27_streams.py
  # keep the optimised module's text only (all of it, if none is so named)
  find $d -type f -printf '%s %p\n' > $d.files
  if ls $d/*after_optimizations.txt > /dev/null 2>&1; then
    find $d -mindepth 1 ! -name '*after_optimizations.txt' -delete
  else
    find $d -type f -size +8M -delete
  fi
  ls -la $d | tail -n 3
}
closed=gpt_1p3b_serve.decode_closed64
open_=gpt_1p3b_serve.mixed_open
streams parent $closed 2147493201
streams change $closed 2147493201
python3 benchmark/tools/calls/pr27_streams.py --compare \
  $out/streams.$closed.parent.json $out/streams.$closed.change.json
one change $closed 2147493202 0
one parent $closed 2147493202 0
one parent $closed 2147493203 0
one change $closed 2147493203 0
one parent $open_ 2147493211 0
one change $open_ 2147493211 0
one change $open_ 2147493212 0
one parent $open_ 2147493212 0
one parent $open_ 2147493213 0
one change $open_ 2147493213 0
one change $closed 2147493204 1 benchmark/tools/span_report.py
one parent $closed 2147493204 1 benchmark/tools/span_report.py
one change $open_ 2147493214 1 benchmark/tools/span_report.py
one parent $open_ 2147493214 1 benchmark/tools/span_report.py
cp $JAX_COMPILATION_CACHE_DIR/autobench_gate.json $out/ 2>/dev/null
