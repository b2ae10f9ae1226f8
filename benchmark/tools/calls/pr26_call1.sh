#!/bin/bash
# PR 26, chip call 1 (one chip): step 0 of the new cell, before anything is
# timed. The parent is unpacked first, with this PR's benchmark laid over it
# as the driver does:
#   rm -rf .bench_scratch/parent; mkdir -p .bench_scratch/parent
#   git archive f877db88caf9 | tar -x -C .bench_scratch/parent
#   cp -r BENCHMARK.json benchmark .bench_scratch/parent/
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr26_call1.sh
# 1. a sound run with the fp8 control: the gap under the program's routing,
#    the control's, the gap against the reference's OWN routing, how often
#    the two choose differently, the shortfall;
# 2. a traced run that keeps (a cut of) its trace, for the readers' patterns;
# 3. the program with its expert layer broken (tools/faults.py);
# 4. the parent on the new cell: it has to fail at once.
repo=$PWD
out=$repo/chiprun_out/pr26/call1
mkdir -p $out
: ${JAX_COMPILATION_CACHE_DIR:=$repo/.jax_cache}
export JAX_COMPILATION_CACHE_DIR
cell=lfm2_8b_a1b_serve.decode_closed128
show() {  # log
  grep -E '^(weights|warm-up|window|gap percentiles|gate decisions|reference|CONTROL|check |compile cache events)' $1 | cut -c1-700
  grep -E '^\{' $1 | tail -n 1 | python3 -c '
import json,sys
d=json.loads(sys.stdin.read() or "{}")
print(d.get("correct"), d.get("failed"), d.get("device"), {k: v["value"] for k, v in d.get("metrics", {}).items()}, d.get("control"))
for op in (d.get("breakdown") or {}).get("device_ops", []):
    print("   op", json.dumps(op)[:300])'
  tail -n 4 ${1%.log}.err | cut -c1-400
}
t0=$SECONDS
python3 benchmark/tools/probe.py --control fp8 --workload $cell --seed 2147491101 \
  --seconds 40 --trace 0 > $out/step0.log 2> $out/step0.err
echo "== step0 rc=$? at $((SECONDS - t0))s"; show $out/step0.log
BENCH_KEEP_TRACE=$out/trace python3 benchmark/run.py --workload $cell --seed 2147491102 \
  --seconds 40 --trace 1 > $out/traced.log 2> $out/traced.err
echo "== traced rc=$? at $((SECONDS - t0))s"; show $out/traced.log
python3 - <<PY
import json
d = json.load(open("$out/trace/trace.json"))
# keep the first 400 ms after the first bench.step for the readers' tests
host = [e for p in d["planes"] if p["name"] == "/host:CPU" for l in p["lines"] for e in l["events"]]
start = min(e[1] for e in host)
stop = start + 400_000_000
for p in d["planes"]:
    for l in p["lines"]:
        l["events"] = [[e[0][:600], e[1] - start, e[2]] for e in l["events"] if start <= e[1] and e[1] + e[2] <= stop]
json.dump(d, open("$out/trace_cut.json", "w"))
import os; os.remove("$out/trace/trace.json")
PY
ls -la $out
i=0
for f in select_on_s weigh_by_biased no_normalise drop_pair; do
  i=$((i + 1))
  python3 benchmark/tools/probe_fault.py --fault $f --workload $cell --seed $((2147491110 + i)) \
    --seconds 12 --trace 0 > $out/fault_$f.log 2> $out/fault_$f.err
  echo "== fault $f rc=$? at $((SECONDS - t0))s"; show $out/fault_$f.log
done
(cd $repo/.bench_scratch/parent && timeout 600 python3 benchmark/run.py --workload $cell \
   --seed 2147491120 --seconds 40 --trace 0 > $out/parent.log 2> $out/parent.err; \
 echo "== parent on the new cell rc=$? at $((SECONDS - t0))s"; tail -n 3 $out/parent.err | cut -c1-300)
