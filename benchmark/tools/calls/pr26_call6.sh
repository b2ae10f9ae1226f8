#!/bin/bash
# PR 26, chip call 6 (one chip; the session that answered REVIEW.md): the
# final tree from what git would commit, and the faults at the committed
# limits:
#   git add -A; rm -rf .bench_scratch; mkdir -p .bench_scratch/change
#   git archive $(git write-tree) | tar -x -C .bench_scratch/change
#   chiprun --timeout 2400 -- bash benchmark/tools/calls/pr26_call6.sh
# 1. the new cell, one traced run (the per-layer metrics added in this
#    session must be on its line) and two more seeds;
# 2. the program with its expert layer broken (tools/faults.py), 40 s each,
#    through the harness at the limits in the configuration's file:
#    `weigh_by_biased` on three seeds (the fault nearest the gap's limit),
#    the other three once. Each must read `correct` false.
repo=$PWD
out=$repo/chiprun_out/pr26/call6
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
cell=lfm2_8b_a1b_serve.decode_closed128
t0=$SECONDS
cd $repo/.bench_scratch/change || exit 1
show() {  # log
  grep -E '^(window|check |compile cache events)' $1 | cut -c1-330
  grep -E '^\{' $1 | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})'
  tail -n 2 ${1%.log}.err | cut -c1-300
}
python3 benchmark/run.py --workload $cell --seed 2147493001 --seconds 40 --trace 1 \
  > $out/traced.log 2> $out/traced.err
echo "== traced rc=$? at $((SECONDS - t0))s"; show $out/traced.log
for i in 2 3; do
  python3 benchmark/run.py --workload $cell --seed $((2147493000 + i)) --seconds 40 --trace 0 \
    > $out/seed_$i.log 2> $out/seed_$i.err
  echo "== seed $i rc=$? at $((SECONDS - t0))s"; show $out/seed_$i.log
done
i=10
for f in weigh_by_biased weigh_by_biased weigh_by_biased select_on_s no_normalise drop_pair; do
  i=$((i + 1))
  python3 benchmark/tools/probe_fault.py --fault $f --workload $cell --seed $((2147493000 + i)) \
    --seconds 40 --trace 0 > $out/fault_${f}_$i.log 2> $out/fault_${f}_$i.err
  echo "== fault $f seed $((2147493000 + i)) rc=$? at $((SECONDS - t0))s"; show $out/fault_${f}_$i.log
done
