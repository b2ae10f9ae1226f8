#!/bin/bash
# PR 24, chip call 3 (four chips): gpt_1p3b_train_pp2tp2 once traced (its
# `train_dispatch_ms_p50` and the other per-layer metrics) and once untraced
# (its end-to-end line beside the ledger's), through tools/span_report.py.
#   chiprun --chips 4 --timeout 1500 -- bash benchmark/tools/calls/pr24_call3.sh
out=chiprun_out/pr24/call3
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$PWD/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
cell=gpt_1p3b_train_pp2tp2.mb2x8s1024
for t in 1 0; do
  log=$out/$cell.trace$t.log
  python3 benchmark/tools/span_report.py --workload $cell --seed 242000$t \
    --seconds 40 --trace $t > $log 2> ${log%.log}.err
  echo "== $cell trace=$t rc=$? $(grep -E '^compile cache events' $log)"
  grep -E '^window' $log | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | cut -c1-2500
  grep -E '^SPANS' $log
done
tail -n 3 $out/*.err | grep -v "hugepage\|warnings.warn\|^$\|==>" | tail -n 10
