"""Zero-downtime weight swap (ISSUE 12): engine/ping version
stamping, subscriber hot swap, the router's staggered fleet rollout
with automatic rollback, and the swap-under-load drill — live traffic
over a 2-replica fleet while a new version publishes and rolls out,
with no dropped requests, contiguous streamed tokens across the flip,
and post-swap outputs identical to a fresh engine on the new weights.
The module's in-process tests re-run under PADDLE_TPU_LOCKCHECK=1."""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.fleet.runtime.rpc import RpcClient
from paddle_tpu.publish import Publisher, VersionRegistry, \
    VersionSubscriber
from paddle_tpu.serving import (Engine, GPTDecodeModel,
                                InProcessReplica, LoadGenerator,
                                Router, ServingClient, ServingServer,
                                TrafficConfig, slo_report)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_KW = dict(num_slots=4, num_pages=64, page_size=4, max_seq_len=64)


def _tiny_cfg():
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig.tiny(num_layers=1)


@pytest.fixture(scope="module")
def ckpt_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("swap") / "gpt")
    GPTDecodeModel(_tiny_cfg(), seed=0).save_checkpoint(root)
    return root


def _wait_for(pred, timeout=30.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return False


def _publish_seed(pub_root: str, seed: int, step: int) -> dict:
    """Publish a fresh model's weights as one servable version."""
    return Publisher(pub_root).publish_model(
        GPTDecodeModel(_tiny_cfg(), seed=seed), step=step)


def _expected_after_swap(ckpt_root, pub_root, version, prompt, mnt):
    """Reference output: a FRESH engine warm-started onto the
    published version — what every post-swap replica must emit."""
    eng = Engine.from_checkpoint(ckpt_root, **ENGINE_KW)
    with eng:
        eng.warm_start(pub_root, step=version, version=version)
        return eng.generate(prompt, mnt, timeout=60).tolist()


# ---------------------------------------------------------------------------
# version identity on the wire
# ---------------------------------------------------------------------------

def test_stats_ping_and_adopt_version_carry_model_version(ckpt_root,
                                                          tmp_path):
    pub = str(tmp_path / "pub")
    rec = _publish_seed(pub, seed=1, step=50)
    assert rec["version"] == 1
    eng = Engine.from_checkpoint(ckpt_root, **ENGINE_KW)
    assert eng.stats()["model_version"] == 0
    with eng, ServingServer(eng, "127.0.0.1:0",
                            publish_root=pub) as srv:
        cli = ServingClient(srv.endpoint)
        try:
            assert cli.ping_info()["model_version"] == 0
            rep = cli.adopt_version(1)
            assert rep == {"adopted": 1, "model_version": 1}
            assert cli.ping_info()["model_version"] == 1
            assert eng.stats()["model_version"] == 1
            # serving the adopted weights, not just stamping them
            assert eng.generate([1, 2, 3], 8, timeout=60).tolist() \
                == _expected_after_swap(ckpt_root, pub, 1, [1, 2, 3], 8)
        finally:
            cli.close()


def test_adopt_version_requires_configured_root(ckpt_root):
    """Repo rule: restore paths are server configuration, never
    wire-chosen — with no publish root the verb is refused."""
    from paddle_tpu.distributed.fleet.runtime.rpc import PSRemoteError
    eng = Engine.from_checkpoint(ckpt_root, **ENGINE_KW)
    with eng, ServingServer(eng, "127.0.0.1:0") as srv:
        cli = ServingClient(srv.endpoint)
        try:
            with pytest.raises(PSRemoteError, match="publish_root"):
                cli.adopt_version(1)
        finally:
            cli.close()


# ---------------------------------------------------------------------------
# subscriber hot swap (single engine, file-poll transport)
# ---------------------------------------------------------------------------

def test_subscriber_file_poll_swaps_and_skips_bad_versions(ckpt_root,
                                                           tmp_path):
    pub = str(tmp_path / "pub")
    eng = Engine.from_checkpoint(ckpt_root, **ENGINE_KW)
    sub = VersionSubscriber(pub, engine=eng, poll=0.05)
    with eng:
        sub.start()
        _publish_seed(pub, seed=1, step=10)
        assert _wait_for(lambda: sub.current_version == 1)
        assert eng.stats()["model_version"] == 1
        # a torn/bogus publication fails its swap ONCE and is memoized
        Publisher(pub).publish_arrays({"junk": np.zeros(4)}, step=11,
                                      kind="gpt-decode")
        assert _wait_for(lambda: 2 in sub.failed_versions)
        assert sub.current_version == 1       # still on good weights
        # the next good version (the recovery path) adopts normally
        _publish_seed(pub, seed=2, step=12)
        assert _wait_for(lambda: sub.current_version == 3)
        assert eng.generate([4, 5], 6, timeout=60).tolist() \
            == _expected_after_swap(ckpt_root, pub, 3, [4, 5], 6)
        sub.stop()


# ---------------------------------------------------------------------------
# router: staggered rollout + automatic rollback
# ---------------------------------------------------------------------------

def _fleet(ckpt_root, pub_root, n=2, **router_kw):
    reps = []
    for i in range(n):
        r = InProcessReplica(ckpt_root, name=f"rep{i}",
                             engine_kw=ENGINE_KW,
                             publish_root=pub_root)
        r.start()
        reps.append(r)
    kw = dict(ping_interval=0.1, ping_timeout=1.0, suspect_after=1,
              dead_after=2, token_stall=5.0, respawn_cooldown=0.2,
              publish_root=pub_root)
    kw.update(router_kw)
    router = Router("127.0.0.1:0", replicas=[r.spec() for r in reps],
                    **kw)
    return router, reps


def test_rollout_staggers_fleet_and_bad_version_rolls_back(ckpt_root,
                                                           tmp_path):
    pub = str(tmp_path / "pub")
    router, reps = _fleet(ckpt_root, pub)
    try:
        with router:
            _publish_seed(pub, seed=1, step=100)
            # drive the rollout over the ROUTER'S OWN WIRE
            rc = RpcClient(router.endpoint)
            try:
                rep = rc.call({"op": "rollout"}, timeout=120,
                              deadline=120)
                assert rep["adopted"] == 1
                assert sorted(rep["replicas"]) == ["rep0", "rep1"]
                # every replica answers with the adopted identity
                for r in reps:
                    assert r.engine.stats()["model_version"] == 1
                # pin the known-good version, then publish a junk one:
                # the rollout must fail on the FIRST replica, rewind
                # the fleet, and rewind the registry pointer
                VersionRegistry(pub).pin(1)
                Publisher(pub).publish_arrays(
                    {"junk": np.zeros(3)}, step=110, kind="gpt-decode")
                rep2 = rc.call({"op": "rollout"}, timeout=120,
                               deadline=120)
                assert rep2["adopted"] is None
                assert rep2["version"] == 2
                assert rep2["failed_on"] == "rep0"
                assert rep2["rolled_back"] == 1   # registry rewound
                assert VersionRegistry(pub).latest() == 1
                assert router.rollout_rollbacks == 1
                for r in reps:
                    assert r.engine.stats()["model_version"] == 1
                # and the fleet still serves, on the good weights
                cli = ServingClient(router.endpoint)
                try:
                    out = cli.generate([1, 2, 3], 8, timeout=60)
                    assert out["status"] == "done"
                    assert np.asarray(out["tokens"]).tolist() == \
                        _expected_after_swap(ckpt_root, pub, 1,
                                             [1, 2, 3], 8)
                finally:
                    cli.close()
            finally:
                rc.close()
    finally:
        for r in reps:
            r.stop()


def test_router_publish_watch_rolls_out_automatically(ckpt_root,
                                                      tmp_path):
    """publish_watch=True closes the loop with NO operator verb: the
    publication itself triggers the staggered fleet rollout."""
    pub = str(tmp_path / "pub")
    router, reps = _fleet(ckpt_root, pub, publish_watch=True)
    try:
        with router:
            _publish_seed(pub, seed=1, step=100)
            assert _wait_for(
                lambda: all(r.engine.stats()["model_version"] == 1
                            for r in reps), timeout=60)
            # counted once the last replica's probe has answered, which
            # is after its engine says so
            assert _wait_for(lambda: router.rollouts >= 1, timeout=60)
    finally:
        for r in reps:
            r.stop()


# ---------------------------------------------------------------------------
# the acceptance drill: hot swap under live traffic
# ---------------------------------------------------------------------------

def test_zero_downtime_swap_under_load(ckpt_root, tmp_path):
    pub = str(tmp_path / "pub")
    router, reps = _fleet(ckpt_root, pub)
    flip = {}
    try:
        with router:
            cli = ServingClient(router.endpoint)
            gen = LoadGenerator(TrafficConfig(
                rate=6.0, duration=6.0, seed=11,
                prompt_lens={4: 2, 8: 1}, output_lens={2: 2, 4: 1},
                deadlines={0: 60.0, 1: 60.0, 2: 60.0}))
            stream_frames = []
            stream_rep = {}

            def spanning_stream():
                # one long streamed generate launched right before the
                # flip — its token frames must stay contiguous across
                # the swap (no dropped, no duplicated index)
                c2 = ServingClient(router.endpoint)
                try:
                    stream_rep.update(c2.generate(
                        [9, 8, 7], 24, timeout=90, stream=True,
                        on_token=lambda t, i:
                        stream_frames.append((i, list(t)))))
                finally:
                    c2.close()

            def mid_run_publish():
                time.sleep(2.0)
                th = threading.Thread(target=spanning_stream)
                th.start()
                time.sleep(0.2)
                _publish_seed(pub, seed=1, step=200)
                flip["t"] = time.monotonic()
                flip["result"] = router.rollout_version()
                flip["done_t"] = time.monotonic()
                th.join(90)

            pub_thread = threading.Thread(target=mid_run_publish)
            pub_thread.start()
            try:
                res = gen.run_client(cli, timeout=60)
                pub_thread.join(120)
                assert res.wait(120)
            finally:
                cli.close()
            assert flip["result"]["adopted"] == 1

            # ZERO drops: every offered request was admitted and ran
            # to completion through the flip
            assert res.rejected == []
            statuses = [h.status for _a, h in res.handles]
            assert statuses and all(s == "done" for s in statuses), \
                statuses
            # streamed tokens stayed contiguous across the swap
            assert stream_rep["status"] == "done"
            streamed = []
            for idx, toks in stream_frames:
                assert idx == len(streamed)       # no gap, no dup
                streamed.extend(int(t) for t in toks)
            assert streamed == np.asarray(
                stream_rep["tokens"]).tolist()
            assert len(streamed) == 24
            # the flip is invisible to SLO attainment: pre-swap and
            # post-swap windows agree within the 0.1 band
            flip_rel = flip["t"] - res.started_at
            pre = slo_report(res, window=(0.0, flip_rel), gen="pre")
            post = slo_report(res, window=(flip_rel, float("inf")),
                              gen="post")
            assert pre["offered"] > 0 and post["offered"] > 0
            assert abs(pre["attainment"] - post["attainment"]) <= 0.1
            # post-swap outputs are the NEW weights', bit-for-bit what
            # a fresh engine on the published version produces
            cli2 = ServingClient(router.endpoint)
            try:
                for r in reps:
                    assert r.engine.stats()["model_version"] == 1
                out = cli2.generate([1, 2, 3], 8, timeout=60,
                                    session="post-swap")
                assert out["status"] == "done"
                assert np.asarray(out["tokens"]).tolist() == \
                    _expected_after_swap(ckpt_root, pub, 1,
                                         [1, 2, 3], 8)
            finally:
                cli2.close()
    finally:
        for r in reps:
            r.stop()


# ---------------------------------------------------------------------------
# tier-1 dynamic validation
# ---------------------------------------------------------------------------

def test_online_swap_module_clean_under_lockcheck():
    """Hot swap under the step lock + rollout under the router lock is
    exactly the cross-subsystem lock surface this PR adds: re-run the
    module's in-process tests with every paddle_tpu lock
    order-checked."""
    if os.environ.get("PADDLE_TPU_LOCKCHECK") == "1":
        pytest.skip("already running under the sanitizer")
    res = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(REPO, "tests", "test_online_swap.py"),
         "-q", "-x", "-k", "not subprocess and not lockcheck",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        capture_output=True, text=True, timeout=420, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PADDLE_TPU_LOCKCHECK="1"))
    assert res.returncode == 0, \
        res.stdout[-4000:] + res.stderr[-2000:]
