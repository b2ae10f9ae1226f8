#!/bin/bash
# PR 27, chip call 1 (one chip): the lfm2 cell, parent against change, both
# from git, with this PR's benchmark files laid over the parent as the
# driver does (this PR adds only the files under benchmark/tools/calls/):
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/{parent,change}
#   git archive b62aa673b0a5 | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr27_call1.sh
# 1. Step 0 of ISSUE 27, folded into the parent's first run (it has to
#    compile every program anyway): XLA writes the optimised HLO of each
#    `jit_decode` it compiles; the change's first run does the same. The
#    dump flags are not part of the compile cache's key.
# 2. The contract: that pair of runs (same seed) through pr27_streams.py,
#    every request's tokens written out and compared token for token.
# 3. Two more pairs, change-parent and parent-change, other seeds.
# 4. A traced run of each tree through tools/span_report.py, same seed.
repo=$PWD
out=$repo/chiprun_out/pr27/call1
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
  mkdir -p $out/hlo/$cell.$tree
  BENCH_STREAMS=$out/streams.$cell.$tree.json \
  XLA_FLAGS="--xla_dump_to=$out/hlo/$cell.$tree --xla_dump_hlo_as_text --xla_dump_hlo_module_re=jit_decode" \
    one $tree $cell $seed 0 benchmark/tools/calls/pr27_streams.py
  # keep the optimised module's text only (all of it, if none is so named)
  local d=$out/hlo/$cell.$tree
  find $d -type f -printf '%s %p\n' > $d.files
  if ls $d/*after_optimizations.txt > /dev/null 2>&1; then
    find $d -mindepth 1 ! -name '*after_optimizations.txt' -delete
  else
    find $d -type f -size +8M -delete
  fi
  ls -la $d | tail -n 3
}
same() {  # cell
  python3 benchmark/tools/calls/pr27_streams.py --compare \
    $out/streams.$1.parent.json $out/streams.$1.change.json
}
lfm=lfm2_8b_a1b_serve.decode_closed128
streams parent $lfm 2147493101
streams change $lfm 2147493101
same $lfm
one change $lfm 2147493102 0
one parent $lfm 2147493102 0
one parent $lfm 2147493103 0
one change $lfm 2147493103 0
one change $lfm 2147493104 1 benchmark/tools/span_report.py
one parent $lfm 2147493104 1 benchmark/tools/span_report.py
for t in parent change; do
  f=$(ls $out/hlo/$lfm.$t/*after_optimizations.txt 2>/dev/null | head -n 1)
  echo "-- $t: $f"
  [ -n "$f" ] && grep -cE ' (sort|gather)\(' $f
  [ -n "$f" ] && grep -E '^ *(ROOT )?%?[a-z_.0-9-]+ = .*(f32|s32)\[(4194304|64,65536)\]' $f | grep -E 'fusion\(|sort\(|gather\(' | cut -c1-220 | head -n 40
done
cp $JAX_COMPILATION_CACHE_DIR/autobench_gate.json $out/ 2>/dev/null
