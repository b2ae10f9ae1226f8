#!/bin/bash
# PR 24, chip call 4 (one chip): the final tree from what git would commit,
# one traced run of each serving cell on seeds above 2**31.
#   git add -A; rm -rf .bench_scratch/final; mkdir -p .bench_scratch/final
#   git archive $(git write-tree) | tar -x -C .bench_scratch/final
#   cp benchmark/tools/calls/pr24_call4.sh .bench_scratch/final_proof.sh
#   chiprun --timeout 1200 -- bash .bench_scratch/final_proof.sh
repo=$PWD
out=$repo/chiprun_out/pr24/final
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
cd .bench_scratch/final || exit 1
for pair in gpt_1p3b_serve.mixed_open:2147490001 gpt_1p3b_serve.decode_closed64:2147490002; do
  cell=${pair%%:*}; seed=${pair##*:}
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace 1 > $out/$cell.log 2> $out/$cell.err
  echo "== $cell seed=$seed rc=$?"
  grep -E '^\{' $out/$cell.log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read())
print(d["correct"], d["failed"], len(d["metrics"]), {k: round(v["value"],4) for k,v in d["metrics"].items()})'
done
tail -n 3 $out/*.err | grep -v "hugepage\|warnings.warn\|^$\|==>" | tail
