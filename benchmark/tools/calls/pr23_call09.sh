#!/bin/bash
# PR 23, chip call 9 (one chip): gpt_350m_train at the paper's 2048
# positions. Set E of six 40 s runs (the first compiles), then the fp8
# control on three new seeds with a 2 s window (training's readings need
# no measured window).
#   chiprun --timeout 1500 -- bash benchmark/tools/calls/pr23_call09.sh
cell=gpt_350m_train.b16s1024
bash benchmark/tools/sets.sh $cell 40 E 3001 3002 3003 3004 3005 3006
bash benchmark/tools/controls.sh $cell 2 2011 2012 2013
python3 benchmark/tools/summarize.py chiprun_out/sets/$cell.E
tail -n 3 chiprun_out/sets/$cell.E/*.err | grep -v "hugepage\|warnings.warn\|^$" | tail -n 20
