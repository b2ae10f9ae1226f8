#!/bin/bash
# PR 26, chip call 3 (one chip, three short calls of the same command while
# the spellings were settled): the grouped expert products of one layer,
# every spelling at every row count the engine uses.
#   chiprun --timeout 900 -- bash benchmark/tools/calls/pr26_call3.sh
python3 benchmark/tools/moe_bench.py --rows 64,128,256,512,1024,2048
