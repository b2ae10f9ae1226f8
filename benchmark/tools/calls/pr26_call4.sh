#!/bin/bash
# PR 26, chip call 4 (one chip): the new cell with the gate offered `gmm`
# and `dense`: six seeds of the benchmark's own command, then a traced run.
#   chiprun --timeout 2400 -- bash benchmark/tools/calls/pr26_call4.sh
repo=$PWD
out=$repo/chiprun_out/pr26/call4
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
cell=lfm2_8b_a1b_serve.decode_closed128
t0=$SECONDS
show() {  # log
  grep -E '^(window|gap percentiles|reference|check widest|requests:|gate decisions)' $1 | cut -c1-1200
  grep -E '^\{' $1 | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})
for op in (d.get("breakdown") or {}).get("device_ops", []): print("   op", json.dumps(op)[:200])'
  tail -n 2 ${1%.log}.err | cut -c1-300
}
for i in 1 2 3 4 5 6; do
  python3 benchmark/run.py --workload $cell --seed $((2147491320 + i)) --seconds 40 --trace 0 \
    > $out/seed_$i.log 2> $out/seed_$i.err
  echo "== seed $i rc=$? at $((SECONDS - t0))s"; show $out/seed_$i.log
done
python3 benchmark/run.py --workload $cell --seed 2147491330 \
  --seconds 40 --trace 1 > $out/traced.log 2> $out/traced.err
echo "== traced rc=$? at $((SECONDS - t0))s"; show $out/traced.log
cp $JAX_COMPILATION_CACHE_DIR/autobench_gate.json $out/ 2>/dev/null
