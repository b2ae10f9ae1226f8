#!/bin/bash
# PR 27, chip call 3 (one chip): two controls for call 2's finding that 7
# of 60 sampled requests of decode_closed64 drew one token differently in
# the change (no greedy one; none of 71 in the lfm2 cell). Trees as for
# call 1; the parent's streams of call 2 copied where the machine sees them:
#   cp chiprun_out/pr27/call2/streams.gpt_1p3b_serve.decode_closed64.parent.json .bench_scratch/
#   chiprun --timeout 1500 -- bash benchmark/tools/calls/pr27_call3.sh
# 1. The parent again on call 2's seed: is a request's stream a function of
#    the request alone within ONE tree (another run, other timings)?
# 2. The two spellings of the sampler alone, same inputs, stage by stage.
# Run a second time with PROBE_ONLY=1 after the probe learnt to compare both
# with the arithmetic behind barriers (the first run's part 1 stands).
repo=$PWD
out=$repo/chiprun_out/pr27/call3
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
closed=gpt_1p3b_serve.decode_closed64
[ -n "$PROBE_ONLY" ] || {
(cd .bench_scratch/parent && BENCH_STREAMS=$out/streams.$closed.parent2.json \
   python3 benchmark/tools/calls/pr27_streams.py --workload $closed \
   --seed 2147493201 --seconds 40 --trace 0) > $out/parent2.log 2> $out/parent2.err
echo "parent again rc=$?"; grep -E '^(window|streams|compile cache events)' $out/parent2.log | cut -c1-200
tail -n 1 $out/parent2.log | cut -c1-400
python3 benchmark/tools/calls/pr27_streams.py --compare \
  .bench_scratch/streams.$closed.parent.json $out/streams.$closed.parent2.json
}
# third run, PROBE_ONLY=3 PROBE_MODE=variants: barriers round the sorted rows
python3 benchmark/tools/calls/pr27_sampler_probe.py .bench_scratch/parent \
  .bench_scratch/change $PROBE_MODE > $out/probe$PROBE_ONLY.log 2> $out/probe$PROBE_ONLY.err
echo "probe rc=$?"; grep -E '^(device|PROBE|VARIANTS)' $out/probe$PROBE_ONLY.log; tail -n 3 $out/probe$PROBE_ONLY.err | cut -c1-300
