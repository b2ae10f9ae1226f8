"""Multiplexed RPC transport (PR 11): out-of-order replies over one
socket, pooled channels, zero-copy pull path, frame-granular fault
isolation, head-of-line regression, stream cancel, exactly-once over
the mux wire, and PS push-invalidation staleness."""
import os
import socketserver
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401
from paddle_tpu.distributed.fleet.runtime import fault_injection as fi
from paddle_tpu.distributed.fleet.runtime import rpc
from paddle_tpu.distributed.fleet.runtime.parameter_server_runtime \
    import PSClient, PSServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_injector():
    fi.reset_injector(fi.FaultInjector())
    yield
    fi.reset_injector(fi.FaultInjector())


def _mval(metric, **labels) -> float:
    """Sum a metric family's series matching a label subset."""
    names = metric.labelnames
    total = 0.0
    for vals, child in metric._series():
        kv = dict(zip(names, vals))
        if all(kv.get(k) == v for k, v in labels.items()):
            total += child.value
    return total


# ---------------------------------------------------------------------------
# stub dispatch server: minimal op surface over serve_connection
# ---------------------------------------------------------------------------

class _StubServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, secret=None):
        self.applied: list = []
        self._apply_lock = threading.Lock()
        state = rpc.RpcServerState(
            read_ops=frozenset({"ping", "slow", "pull", "gen"}),
            secret=secret)
        outer = self

        class H(socketserver.BaseRequestHandler):
            def handle(self):
                rpc.serve_connection(self.request, outer._dispatch,
                                     state)

        super().__init__(("127.0.0.1", 0), H)
        self.endpoint = f"127.0.0.1:{self.server_address[1]}"
        threading.Thread(target=self.serve_forever, daemon=True).start()

    def _dispatch(self, req):
        op = req["op"]
        if op == "ping":
            return "pong"
        if op == "slow":
            time.sleep(float(req.get("s", 0.3)))
            return {"ok": True}
        if op == "pull":
            n, d = int(req["n"]), int(req["d"])
            return {"rows": np.arange(n * d, dtype=np.float32)
                    .reshape(n, d)}
        if op == "gen":
            def g():
                for i in range(int(req["n"])):
                    time.sleep(float(req.get("gap", 0.05)))
                    yield {"i": i}
                return {"done": True}
            return g()
        if op == "apply":
            with self._apply_lock:
                self.applied.append(req["x"])
                return {"n": len(self.applied)}
        raise ValueError(f"unknown op {op!r}")

    def stop(self):
        self.shutdown()
        self.server_close()


@pytest.fixture()
def stub():
    srv = _StubServer()
    yield srv
    srv.stop()


# ---------------------------------------------------------------------------
# multiplexing semantics
# ---------------------------------------------------------------------------

def test_out_of_order_reply_overtakes_slow_call(stub):
    """One socket, two in-flight calls: the fast ping's reply arrives
    while the slow call is still executing — the defining mux
    behavior a one-call-per-channel transport cannot exhibit."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        ooo0 = _mval(rpc._MUX_OUT_OF_ORDER)
        slow_done = []
        th = threading.Thread(
            target=lambda: slow_done.append(
                cli.call({"op": "slow", "s": 0.5}, timeout=5)))
        th.start()
        time.sleep(0.1)          # slow call is in flight on the socket
        t0 = time.monotonic()
        assert cli.call({"op": "ping"}, timeout=5) == "pong"
        ping_t = time.monotonic() - t0
        th.join(timeout=10)
        assert slow_done and slow_done[0] == {"ok": True}
        assert ping_t < 0.3, \
            f"ping serialized behind slow call ({ping_t:.3f}s)"
        assert _mval(rpc._MUX_OUT_OF_ORDER) > ooo0
    finally:
        cli.close()


def test_legacy_mode_serializes_one_call_per_channel(stub):
    """mux=False restores the pre-PR-11 shape: with a single exclusive
    channel the ping queues behind the slow call."""
    cli = rpc.RpcClient(stub.endpoint, mux=False, pool_size=1)
    try:
        th = threading.Thread(
            target=lambda: cli.call({"op": "slow", "s": 0.4}, timeout=5))
        th.start()
        time.sleep(0.1)
        t0 = time.monotonic()
        assert cli.call({"op": "ping"}, timeout=5) == "pong"
        ping_t = time.monotonic() - t0
        th.join(timeout=10)
        assert ping_t > 0.2, \
            f"legacy mode did not serialize ({ping_t:.3f}s)"
    finally:
        cli.close()


def test_zero_copy_pull_skips_body_assembly_copy(stub):
    """The mux read path lands ndarray segments in pooled buffers and
    hands out views: per-call bytes-copied must stay near the header+
    skeleton size, far below the payload (the legacy path copies the
    whole body)."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        n, d = 512, 64
        payload = n * d * 4
        c0 = _mval(rpc._MUX_BYTES_COPIED, path="mux")
        rep = cli.call({"op": "pull", "n": n, "d": d}, timeout=10)
        rows = rep["rows"]
        assert rows.shape == (n, d)
        assert float(rows[3, 5]) == float(3 * d + 5)
        copied = _mval(rpc._MUX_BYTES_COPIED, path="mux") - c0
        assert copied < payload / 10, \
            f"pull copied {copied}B of a {payload}B payload"
    finally:
        cli.close()


def test_buffer_pool_reclaims_after_views_die(stub):
    """Pooled receive buffers are leased while numpy views are alive
    and reclaimed once the reply is dropped — repeated pulls must not
    grow the pool without bound."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        for _ in range(8):
            rep = cli.call({"op": "pull", "n": 256, "d": 16},
                           timeout=10)
            assert rep["rows"].shape == (256, 16)
            del rep
        st = rpc._BUFFER_POOL.stats()
        assert st["hits"] >= 1, f"no buffer reuse: {st}"
    finally:
        cli.close()


def test_stream_and_pings_interleave_on_one_channel(stub):
    """Head-of-line regression (the PR-9 symptom): N streamed
    generates plus short pings on ONE shared client; ping p99 stays
    bounded while every stream is mid-flight."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        results = []

        def consume():
            toks = []
            gen = cli.call_stream({"op": "gen", "n": 8, "gap": 0.08},
                                  timeout=10, stream_timeout=10)
            for f in gen:
                toks.append(f["i"])
            results.append(toks)

        threads = [threading.Thread(target=consume) for _ in range(3)]
        for th in threads:
            th.start()
        time.sleep(0.1)          # all three streams are in flight
        lats = []
        for _ in range(10):
            t0 = time.monotonic()
            assert cli.call({"op": "ping"}, timeout=5) == "pong"
            lats.append(time.monotonic() - t0)
        for th in threads:
            th.join(timeout=30)
        assert len(results) == 3
        assert all(toks == list(range(8)) for toks in results)
        p99 = sorted(lats)[-1]
        assert p99 < 0.25, \
            f"ping p99 {p99:.3f}s — head-of-line queueing behind streams"
    finally:
        cli.close()


def test_abandoned_stream_cancels_and_channel_survives(stub):
    """Dropping a stream generator sends F_CANCEL for that id only:
    the shared channel keeps serving and is NOT reconnected."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        gen = cli.call_stream({"op": "gen", "n": 50, "gap": 0.05},
                              timeout=10, stream_timeout=10)
        assert next(gen)["i"] == 0
        gen.close()              # abandon mid-stream -> F_CANCEL
        for _ in range(3):
            assert cli.call({"op": "ping"}, timeout=5) == "pong"
        assert cli.stats.as_dict()["reconnects"] == 0
    finally:
        cli.close()


def test_exactly_once_with_pinned_req_id_over_mux(stub):
    """The dedup contract rides the mux wire unchanged: re-sending a
    mutating op with the SAME req_id applies once and replays the
    memoized reply."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        rid = (0x5EED << 32) | 7
        r1 = cli.call({"op": "apply", "x": 1}, req_id=rid, timeout=5)
        r2 = cli.call({"op": "apply", "x": 1}, req_id=rid, timeout=5)
        assert r1 == r2 == {"n": 1}
        assert stub.applied == [1]
    finally:
        cli.close()


# ---------------------------------------------------------------------------
# frame-granular fault injection
# ---------------------------------------------------------------------------

def test_corrupt_one_frame_fails_only_its_call(stub):
    """Corrupting ONE mux frame by request id poisons exactly that
    call (wire-error reply -> client retry) while a concurrent call on
    the SAME socket completes untouched and the connection never
    reconnects."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        rid = (0xF00D << 32) | 42
        fi.injector().set_frame_fault("corrupt", req=str(rid),
                                      side="client")
        slow_done = []
        th = threading.Thread(
            target=lambda: slow_done.append(
                cli.call({"op": "slow", "s": 0.4}, timeout=10)))
        th.start()
        time.sleep(0.05)
        rep = cli.call({"op": "ping"}, req_id=rid, timeout=10)
        assert rep == "pong"
        th.join(timeout=15)
        assert slow_done == [{"ok": True}]
        snap = cli.stats.as_dict()
        assert snap["corrupt_frames"] >= 1
        assert snap["retries"] >= 1
        assert snap["reconnects"] == 0, \
            "a single corrupted frame must not kill the shared channel"
        assert fi.injector().counters["frame_faults"] == 1
    finally:
        cli.close()


def test_delay_one_frame_lets_later_frames_overtake(stub):
    """Delaying one frame holds only that request back: a frame sent
    AFTER it completes first (per-frame reordering, not a stalled
    pipe)."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        rid = (0xCAFE << 32) | 9
        fi.injector().set_frame_fault("delay", req=str(rid), delay=0.4,
                                      side="client")
        delayed_done = []
        th = threading.Thread(
            target=lambda: delayed_done.append(
                cli.call({"op": "ping"}, req_id=rid, timeout=10)))
        th.start()
        time.sleep(0.05)
        t0 = time.monotonic()
        assert cli.call({"op": "ping"}, timeout=10) == "pong"
        overtake_t = time.monotonic() - t0
        th.join(timeout=10)
        assert delayed_done == ["pong"]
        assert overtake_t < 0.3, \
            f"later frame queued behind the delayed one ({overtake_t:.3f}s)"
    finally:
        cli.close()


def test_drop_one_frame_retries_and_succeeds(stub):
    """Dropping one outgoing frame times out only its own call; the
    retry (same request id) goes through."""
    cli = rpc.RpcClient(stub.endpoint, pool_size=1, timeout=0.5,
                        deadline=10.0)
    try:
        fi.injector().set_frame_fault("drop", side="client")
        assert cli.call({"op": "ping"}) == "pong"
        snap = cli.stats.as_dict()
        assert snap["retries"] >= 1
        assert fi.injector().counters["frame_faults"] == 1
    finally:
        cli.close()


# ---------------------------------------------------------------------------
# PS invalidation pushes (server-push frames)
# ---------------------------------------------------------------------------

def test_push_invalidation_fixes_cached_staleness():
    """Staleness regression: a hot-row cache serving from local memory
    must pick up ANOTHER worker's push via the server's invalidation
    stream — without it the cached rows stay stale forever (no flush
    here: flush_every is huge)."""
    from paddle_tpu.distributed.fleet.fleet_wrapper import FleetWrapper
    from paddle_tpu.distributed.fleet.boxps_cache import BoxPSWrapper
    srv = PSServer("127.0.0.1:0")
    srv.serve_in_thread()
    fw = FleetWrapper([srv.endpoint])
    box = BoxPSWrapper(fw, flush_every=10_000)
    other = PSClient([srv.endpoint])
    try:
        assert box.attach_invalidations()
        ids = np.arange(16)
        v0 = box.pull_sparse("emb", ids, 8, init_std=0.0)
        assert np.allclose(v0, 0.0)
        # another worker pushes grad=-1 at lr=1 -> rows become +1
        other.push("emb", 8, ids, -np.ones((16, 8), np.float32))
        deadline = time.time() + 15
        v = v0
        while time.time() < deadline:
            v = box.pull_sparse("emb", ids, 8, init_std=0.0)
            if np.allclose(v, 1.0):
                break
            time.sleep(0.05)
        assert np.allclose(v, 1.0), "cache stayed stale after push"
        assert box.stale_refreshes >= 16
        assert srv.inval_published >= 1
    finally:
        box.detach_invalidations()
        fw.stop()
        other.close()
        srv.shutdown()
        srv.server_close()


def test_invalidation_refresh_keeps_read_your_writes():
    """A refresh triggered by a remote push must re-apply THIS
    worker's unflushed local delta on top of the authoritative rows
    (local view = PS value - pending delta)."""
    from paddle_tpu.distributed.fleet.fleet_wrapper import FleetWrapper
    from paddle_tpu.distributed.fleet.boxps_cache import BoxPSWrapper
    srv = PSServer("127.0.0.1:0")
    srv.serve_in_thread()
    fw = FleetWrapper([srv.endpoint])
    box = BoxPSWrapper(fw, flush_every=10_000)
    other = PSClient([srv.endpoint])
    try:
        box.attach_invalidations()
        ids = np.arange(8)
        box.pull_sparse("emb", ids, 4, init_std=0.0)
        # local unflushed update: +1 (grad=-1, lr=1)
        box.push_sparse("emb", ids, -np.ones((8, 4), np.float32), 4)
        # remote worker lands +1 on the PS
        other.push("emb", 4, ids, -np.ones((8, 4), np.float32))
        deadline = time.time() + 15
        v = None
        while time.time() < deadline:
            v = box.pull_sparse("emb", ids, 4, init_std=0.0)
            if np.allclose(v, 2.0):   # PS(1) + local pending(1)
                break
            time.sleep(0.05)
        assert np.allclose(v, 2.0), \
            f"read-your-writes lost across refresh: {v[0]}"
    finally:
        box.detach_invalidations()
        fw.stop()
        other.close()
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# observability + tier-1 dynamic validation
# ---------------------------------------------------------------------------

def test_mux_metric_families_registered(stub):
    from paddle_tpu.observability import registry as _obs
    cli = rpc.RpcClient(stub.endpoint, pool_size=1)
    try:
        cli.call({"op": "ping"}, timeout=5)
    finally:
        cli.close()
    text = _obs.prometheus_text()
    for name in ("paddle_tpu_rpc_mux_inflight",
                 "paddle_tpu_rpc_mux_channels",
                 "paddle_tpu_rpc_mux_bytes_copied_total",
                 "paddle_tpu_rpc_mux_out_of_order_total"):
        assert name in text, f"{name} missing from exposition"


def test_rpc_mux_module_clean_under_lockcheck():
    """Writer/reader threads + channel pool + waiter queues are the
    multi-lock shape the runtime sanitizer polices: re-run this
    module's tests with every paddle_tpu lock order-checked."""
    if os.environ.get("PADDLE_TPU_LOCKCHECK") == "1":
        pytest.skip("already running under the sanitizer")
    res = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(REPO, "tests", "test_rpc_mux.py"),
         "-q", "-x", "-k", "not lockcheck",
         "-p", "no:cacheprovider", "-p", "no:randomly"],
        capture_output=True, text=True, timeout=420, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PADDLE_TPU_LOCKCHECK="1"))
    assert res.returncode == 0, \
        res.stdout[-4000:] + res.stderr[-2000:]
