#!/bin/bash
# PR 24, chip call 1 (one chip): what a span costs on the chip's host, then
# one traced run of each one-chip cell through tools/span_report.py (the
# result line, then the line SPANS), and mixed_open untraced on the traced
# runs' seeds, to set queue wait beside TTFT with and without the profiler.
#   chiprun --timeout 3000 -- bash benchmark/tools/calls/pr24_call1.sh
out=chiprun_out/pr24/call1
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$PWD/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
python3 benchmark/tools/span_report.py --cost 2> $out/cost.err | tee $out/cost.log
one() {  # cell seed trace label
  local log=$out/$1.$4.seed_$2.log
  BENCH_KEEP_TRACE=$5 python3 benchmark/tools/span_report.py --workload $1 \
    --seed $2 --seconds 40 --trace $3 > $log 2> ${log%.log}.err
  echo "== $1 $4 seed=$2 rc=$? $(grep -E '^compile cache events' $log)"
  grep -E '^(window|ttft percentiles|gap percentiles|queue depth)' $log | cut -c1-300
  grep -E '^\{' $log | tail -n 1 | cut -c1-6000
  grep -E '^SPANS' $log
}
one gpt_1p3b_serve.mixed_open 2400001 1 traced $out/keep
one gpt_1p3b_serve.mixed_open 2400001 0 untraced
one gpt_1p3b_serve.decode_closed64 2400002 1 traced
one gpt_350m_train.b16s1024 2400003 1 traced
one gpt_1p3b_serve.mixed_open 2400004 1 traced
one gpt_1p3b_serve.mixed_open 2400004 0 untraced
gzip -f $out/keep/*.json 2>/dev/null
ls -la $out $out/keep
tail -n 3 $out/*.err | grep -v "hugepage\|warnings.warn\|^$" | tail -n 30
