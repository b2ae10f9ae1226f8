#!/usr/bin/env python3
"""One run of a serving cell as `benchmark/run.py` makes it, with every
token's time, engine step and slot kept beside the harness's own stamps:
after the result line, the widest gaps between two tokens of one request,
each with the steps it spans, and a line `AHEAD` with the engine's count of
decode programs and of those dispatched behind one still running (PR 31: is a wide gap one long step, or one
stream left out of many?).

    python3 scripts/pr31_gap_probe.py --workload <cell> --seed <n> --seconds 40
        [--rehearse]        (here, on the CPU, at the cell's tiny sizes)
"""
import time

_T0 = time.perf_counter()

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402
from paddle_tpu.serving import engine as engine_mod  # noqa: E402
from paddle_tpu.serving import scheduler as sched_mod  # noqa: E402

TOKENS = {}     # request id -> [(time, engine step, slot)]
STEPS = {}      # engine step -> (start, end, admitted)
ENGINES = []


def main():
    init = engine_mod.Engine.__init__
    step = engine_mod.Engine.step
    record = sched_mod.Scheduler.record_token

    def __init__(self, *a, **kw):
        init(self, *a, **kw)
        ENGINES.append(self)

    def timed_step(self):
        t0 = time.perf_counter()
        out = step(self)
        STEPS[self._step_no] = (t0, time.perf_counter())
        return out

    def record_token(self, req, token):
        TOKENS.setdefault(req.id, []).append(
            (time.perf_counter(), ENGINES[-1]._step_no, req.slot,
             int(req.prompt.size), req.max_new_tokens))
        return record(self, req, token)

    engine_mod.Engine.__init__ = __init__
    engine_mod.Engine.step = timed_step
    sched_mod.Scheduler.record_token = record_token
    argv = [a for a in sys.argv[1:] if a != "--rehearse"]
    rc = harness.main(argv, t_start=_T0, rehearsal=len(argv) < len(
        sys.argv) - 1)
    # the window is the run's last `--seconds`: warm-up compiles before it
    t_end = max(e for _s, e in STEPS.values())
    seconds = float(argv[argv.index("--seconds") + 1])
    gaps = []
    for rid, toks in TOKENS.items():
        for j in range(1, len(toks)):
            if toks[j - 1][0] >= t_end - seconds:
                gaps.append((toks[j][0] - toks[j - 1][0], rid, j))
    gaps.sort(reverse=True)
    rows = []
    for gap, rid, j in gaps[:8]:
        a, b = TOKENS[rid][j - 1], TOKENS[rid][j]
        between = [1e3 * (STEPS[s][1] - STEPS[s][0])
                   for s in range(a[1], b[1] + 1) if s in STEPS]
        rows.append({"gap_ms": 1e3 * gap, "request": rid, "token": j,
                     "of": b[4], "prompt": b[3], "slot": (a[2], b[2]),
                     "steps": (a[1], b[1]),
                     "longest_step_ms": max(between, default=None),
                     "sum_steps_ms": sum(between),
                     "run_ends_at_step": max(STEPS)})
    print("GAPS " + json.dumps(rows))
    eng = ENGINES[-1]
    print("AHEAD " + json.dumps({
        "steps": int(eng._m_steps.value),
        "decodes_ahead": eng._decodes_ahead,
        "tokens_discarded": eng._tokens_discarded}))
    return rc


if __name__ == "__main__":
    sys.exit(main())
