#!/bin/bash
# Step 0 of ISSUE 23: where does the run-to-run noise come from?
# Four runs of one cell as four processes with one compile cache and NO gate
# cache (every process times every gate key again), then four with the gate
# cache the harness places, then one traced run that keeps its trace.
# Usage: chiprun --timeout 3000 -- bash benchmark/tools/step0.sh [cell] [seconds]
cell=${1:-gpt_1p3b_serve.decode_closed64}
secs=${2:-20}
out=chiprun_out/step0
mkdir -p $out
for i in 1 2 3 4; do
  PADDLE_TPU_AUTOBENCH_CACHE=0 python3 benchmark/run.py --workload $cell \
    --seed $((1000 + i)) --seconds $secs --trace 0 > $out/nogate_$i.log 2> $out/nogate_$i.err
  echo "nogate $i rc=$?"; grep -E "^(window|gate decisions|compile cache events|warm-up|check )" $out/nogate_$i.log; tail -n 1 $out/nogate_$i.log
done
for i in 1 2 3 4; do
  python3 benchmark/run.py --workload $cell \
    --seed $((1000 + i)) --seconds $secs --trace 0 > $out/gate_$i.log 2> $out/gate_$i.err
  echo "gate $i rc=$?"; grep -E "^(window|gate decisions|compile cache events|warm-up|check )" $out/gate_$i.log; tail -n 1 $out/gate_$i.log
done
BENCH_KEEP_TRACE=$out/trace python3 benchmark/run.py --workload $cell \
  --seed 1009 --seconds $secs --trace 1 > $out/traced.log 2> $out/traced.err
echo "traced rc=$?"; tail -n 3 $out/traced.log
tail -n 5 $out/*.err | tail -n 60
