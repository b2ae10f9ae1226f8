#!/bin/bash
# PR 26, chip call 2 (one chip): the readings that set the new cell's limits,
# and its spreads.
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr26_call2.sh
# 1. the program with its expert layer broken four ways (tools/faults.py),
#    40 s each: what the gap and the shortfall read where they must fail;
# 2. six seeds of the benchmark's own command, untraced: spreads, `correct`;
# 3. one traced run that keeps a cut of its trace (two decode steps) for
#    benchmark/tests/data;
# 4. twelve more seeds at 20 s: the sound readings of gap and shortfall.
repo=$PWD
out=$repo/chiprun_out/pr26/call2
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
cell=lfm2_8b_a1b_serve.decode_closed128
t0=$SECONDS
show() {  # log
  grep -E '^(window|gap percentiles|reference|CONTROL|check widest)' $1 | cut -c1-330
  grep -E '^\{' $1 | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()})'
  tail -n 2 ${1%.log}.err | cut -c1-300
}
i=0
for f in select_on_s weigh_by_biased no_normalise drop_pair; do
  i=$((i + 1))
  python3 benchmark/tools/probe_fault.py --fault $f --workload $cell --seed $((2147491210 + i)) \
    --seconds 40 --trace 0 > $out/fault_$f.log 2> $out/fault_$f.err
  echo "== fault $f rc=$? at $((SECONDS - t0))s"; show $out/fault_$f.log
done
for i in 1 2 3 4 5 6; do
  python3 benchmark/run.py --workload $cell --seed $((2147491220 + i)) --seconds 40 --trace 0 \
    > $out/seed_$i.log 2> $out/seed_$i.err
  echo "== seed $i rc=$? at $((SECONDS - t0))s"; show $out/seed_$i.log
done
BENCH_KEEP_TRACE=$out/trace python3 benchmark/run.py --workload $cell --seed 2147491230 \
  --seconds 40 --trace 1 > $out/traced.log 2> $out/traced.err
echo "== traced rc=$? at $((SECONDS - t0))s"; show $out/traced.log
grep -E '^\{' $out/traced.log | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
for op in (d.get("breakdown") or {}).get("device_ops", []): print("   op", json.dumps(op)[:200])
print("   gaps", (d.get("breakdown") or {}).get("idle_gaps"))'
python3 - <<PY
import json, os
d = json.load(open("$out/trace/trace.json"))
host = [e for p in d["planes"] if p["name"] == "/host:CPU" for l in p["lines"] for e in l["events"]]
steps = sorted(e for e in host if e[0] == "bench.step")
start, stop = steps[0][1], steps[2][1]          # two whole steps
for p in d["planes"]:
    for l in p["lines"]:
        l["events"] = [[e[0][:400], e[1] - start, e[2]] for e in l["events"] if start <= e[1] and e[1] + e[2] <= stop]
json.dump(d, open("$out/trace_two_steps.json", "w"))
os.remove("$out/trace/trace.json")
PY
ls -la $out | head -5
for i in $(seq 1 12); do
  python3 benchmark/run.py --workload $cell --seed $((2147491240 + i)) --seconds 20 --trace 0 \
    > $out/short_$i.log 2> $out/short_$i.err
  echo "== short $i rc=$? at $((SECONDS - t0))s"; show $out/short_$i.log | grep -E "check widest|^(True|False)" | cut -c1-260
done
