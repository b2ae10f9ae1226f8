#!/bin/bash
# PR 23, chip call 10 (one chip), from what git would commit:
#   git add -A && rm -rf .bench_scratch/archive && mkdir -p .bench_scratch/archive \
#     && git archive $(git write-tree) | tar -x -C .bench_scratch/archive
#   chiprun --timeout 2400 -- bash .bench_scratch/archive/benchmark/tools/calls/pr23_call10.sh
# A new machine and a new checkout: set F of gpt_350m_train with set E's
# seeds (call 9), set C of mixed_open with four of the seeds of its sets A
# and B (call 6), the first run of each compiling, and the refusal in a
# bare directory.
repo=$PWD
cd .bench_scratch/archive || exit 1
unset JAX_COMPILATION_CACHE_DIR
cell=gpt_350m_train.b16s1024
bash benchmark/tools/sets.sh $cell 40 F 3001 3002 3003 3004 3005 3006
bash benchmark/tools/sets.sh gpt_1p3b_serve.mixed_open 40 C 3001 3002 3003 3004
python3 benchmark/tools/summarize.py chiprun_out/sets/$cell.F chiprun_out/sets/gpt_1p3b_serve.mixed_open.C
mkdir -p ../bare && cp -r BENCHMARK.json benchmark ../bare/
(cd ../bare && python3 benchmark/run.py --workload $cell --seed 2147485001 --seconds 5 --trace 0 > bare.log 2>&1; \
  echo "bare directory rc=$? result lines=$(grep -c '^{' bare.log)"; tail -n 2 bare.log)
tail -n 3 chiprun_out/sets/*/*.err | grep -v "hugepage\|warnings.warn\|^$" | tail -n 20
mkdir -p $repo/chiprun_out && cp -r chiprun_out/* $repo/chiprun_out/
