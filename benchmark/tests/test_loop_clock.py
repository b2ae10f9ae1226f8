"""TTFT runs from the time the arrival was DUE, not from when it was sent,
and every gap between two tokens of one stream is one sample."""
import collections

import numpy as np

from benchmark.runners.serve import Loop


class FakeReq:
    def __init__(self, n):
        self.generated, self.max_new, self.status = [], n, "running"

    def done(self):
        return self.status == "done"


class FakeScheduler:
    def record_token(self, req, token):
        req.generated.append(token)
        if len(req.generated) >= req.max_new:
            req.status = "done"


class FakePool:
    def stats(self):
        return {"used_pages": 3}


class FakeEngine:
    """Every step costs `step_s` on the fake clock and gives each request
    one token."""

    def __init__(self, clock, step_s):
        self.scheduler, self.pool = FakeScheduler(), FakePool()
        self.reqs, self.clock, self.step_s = [], clock, step_s

    def submit(self, prompt, max_new, **kw):
        r = FakeReq(max_new)
        self.reqs.append(r)
        return r

    def step(self):
        self.clock.t += self.step_s
        for r in self.reqs:
            if not r.done():
                self.scheduler.record_token(r, 7)
        return True


class Clock:
    t = 100.0

    def __call__(self):
        return self.t


def _null(_name):
    import contextlib
    return contextlib.nullcontext()


def test_ttft_runs_from_the_due_time_and_gaps_are_per_token():
    clock = Clock()
    eng = FakeEngine(clock, step_s=0.25)
    loop = Loop(eng, stream=None, tr={"loop": "open"}, annotate=_null)
    loop.clock = clock
    loop._hook()                      # stamps with the fake clock
    item = {"prompt": np.zeros(4, np.int32), "prompt_len": 4, "max_new": 3,
            "seed": 0, "temperature": 0.0, "top_k": 0, "top_p": 1.0}
    clock.t = 101.0                   # the generator is a whole second late
    loop.send(item, due=100.0)
    for _ in range(3):
        loop.step()
    # first token at 101.25: 1.25 s after it was due, 0.25 after it was sent
    assert loop.ttft == [(100.0, 1.25)]
    assert loop.late == [(100.0, 1.0)]
    assert [round(g, 6) for _at, g in loop.gaps] == [0.25, 0.25]
    assert len(loop.finished) == 1 and loop.finished[0].end_t == 101.75
