"""The rest of a run with the timed path broken underneath: `correct` has
to come out false. These skip the harness's look for a chip (a rehearsal on
the CPU at tiny sizes) and drive everything else."""
import json
import time

import pytest

from benchmark.lib import harness


def _run(capsys, workload, **kw):
    rc = harness.main(["--workload", workload, "--seed", "3", "--seconds",
                       "3"], time.perf_counter(), rehearsal=True, **kw)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_serving_sound_then_a_token_altered_where_it_is_produced(
        capsys, monkeypatch):
    assert _run(capsys, "gpt_1p3b_serve.decode_closed64")["correct"] is True

    from paddle_tpu.serving.scheduler import Scheduler
    sound = Scheduler.record_token

    def altered(self, req, token):
        # every fifth token of a stream is replaced where it is produced
        if len(req.generated) % 5 == 4:
            token = (int(token) + 17) % 512
        return sound(self, req, token)
    monkeypatch.setattr(Scheduler, "record_token", altered)
    line = _run(capsys, "gpt_1p3b_serve.decode_closed64")
    assert line["correct"] is False
    failed = [c[0] for c in line["checks"] if not c[3]]
    assert any("widest gap" in name for name in failed)


def test_serving_a_request_that_ends_short_is_not_correct(capsys,
                                                          monkeypatch):
    from paddle_tpu.serving.scheduler import Scheduler
    sound = Scheduler.record_token

    def short(self, req, token):
        if len(req.generated) + 2 == req.max_new_tokens:
            req.max_new_tokens -= 1       # ends one token early
        return sound(self, req, token)
    monkeypatch.setattr(Scheduler, "record_token", short)
    line = _run(capsys, "gpt_1p3b_serve.mixed_open")
    assert line["correct"] is False and line["failed"] > 0


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_training_step_that_keeps_its_state_or_drops_rows(capsys,
                                                          monkeypatch, fault):
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep
    sound = HybridParallelTrainStep.__call__

    def frozen(self, ids):
        params = self.params
        import jax
        keep = jax.tree_util.tree_map(lambda a: a + 0, params)
        loss = sound(self, ids)
        self.params = keep                # the step returns its state
        return loss

    def half_batch(self, ids):
        ids = ids.copy()
        ids[len(ids) // 2:] = ids[:len(ids) // 2]   # half the rows left out
        return sound(self, ids)
    monkeypatch.setattr(HybridParallelTrainStep, "__call__",
                        {"frozen": frozen, "half_batch": half_batch}[fault])
    line = _run(capsys, "gpt_350m_train.b16s1024")
    assert line["correct"] is False
    failed = " ".join(c[0] for c in line["checks"] if not c[3])
    assert ("change" in failed) if fault == "frozen" else ("loss" in failed
                                                           or "gradient" in failed)


def test_training_sound(capsys):
    assert _run(capsys, "gpt_350m_train.b16s1024")["correct"] is True
