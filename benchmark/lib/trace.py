"""Reduction of a profiler trace to busy/idle, operation times, span cover
and gap attribution.

`load_xplane` reads the profiler's `.xplane.pb` with nothing but JAX into
plain lists; everything else works on those lists, so the tests run the
same reduction on a small recorded JSON (`tests/data/`).

    trace = {"planes": [{"name": str, "lines": [
                {"name": str, "events": [[name, start_ns, dur_ns], ...]}]}]}

What the TPU profiler writes (jax 0.9.0 / libtpu 0.0.34, looked at by
hand, PR 23): one plane per chip named "/device:TPU:<n>" whose line
"XLA Ops" holds one event per executed HLO operation and whose line
"XLA Modules" holds one event per executed program; host threads live in
the plane "/host:CPU", where `jax.profiler.TraceAnnotation` spans appear
under their own name on the line of the thread that opened them. Device
and host events share one clock.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|\bsend\b|\brecv\b|send-done|recv-done")


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load_xplane(path: str, keep_host=lambda name: True) -> dict:
    """The trace as plain lists. Host events are kept only where
    `keep_host(name)` holds (a host plane has hundreds of thousands of
    python and runtime events; the harness wants its own spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        if not is_dev and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                   for ev in line.events
                   if is_dev or keep_host(ev.name)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def union(intervals):
    """Merge [start, end) pairs; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals, cover) -> int:
    """Length of `intervals` (disjoint, sorted) lying inside `cover`
    (disjoint, sorted)."""
    n, j = 0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            n += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return n


def self_times(events):
    """(name, own nanoseconds) of each event of one line: its duration
    less that of the events nested inside it."""
    out, stack = [], []             # stack of [name, end, own]
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([n, s + d, d])
    out.extend((n, own) for n, _e, own in stack)
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

class Reduced:
    """Everything the readers ask of one traced window."""

    def __init__(self, trace: dict, span_prefix: str = "bench."):
        self.devices = {}          # chip index -> {"ops": [...], "modules": [...]}
        self.spans = []            # [name, start, end] of harness spans
        for plane in trace["planes"]:
            m = DEVICE_PLANE.match(plane["name"])
            if m:
                dev = self.devices.setdefault(
                    int(m.group(1)), {"ops": [], "modules": []})
                for line in plane["lines"]:
                    kind = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                        line["name"])
                    if kind:
                        dev[kind].extend(line["events"])
            elif plane["name"] == HOST_PLANE:
                for line in plane["lines"]:
                    for name, s, d in line["events"]:
                        if name.startswith(span_prefix):
                            self.spans.append([name, s, s + d])
        self.spans.sort(key=lambda x: x[1])
        # the window: first to last harness span where there are any (the
        # profiler starts and stops outside them), else the device events
        if self.spans:
            self.t0 = self.spans[0][1]
            self.t1 = max(e for _n, _s, e in self.spans)
        else:
            evs = [ev for d in self.devices.values() for ev in d["ops"]]
            self.t0 = min((s for _n, s, _d in evs), default=0)
            self.t1 = max((s + d for _n, s, d in evs), default=0)
        self.busy = {i: clip(union([s, s + d] for _n, s, d in dev["ops"]),
                             self.t0, self.t1)
                     for i, dev in self.devices.items()}

    # -- device ----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over the chips."""
        if not self.busy:
            return 0.0
        return sum(total(b) for b in self.busy.values()) \
            / len(self.busy) / 1e9

    def idle_share(self) -> float | None:
        if not self.busy or self.t1 <= self.t0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def _in_window(self, events):
        """Events wholly inside the window that took time (the profiler
        also lists layout pseudo-operations of zero length)."""
        return [(n, s, d) for n, s, d in events
                if d > 0 and s >= self.t0 and s + d <= self.t1]

    def op_seconds(self, pattern: str, kind: str = "ops") -> float:
        """Summed device time of events whose name matches, mean over the
        chips."""
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        return sum(d for dev in self.devices.values()
                   for n, _s, d in self._in_window(dev[kind])
                   if rx.search(n)) / len(self.devices) / 1e9

    def matching(self, pattern: str, kind: str = "ops"):
        """(chip, regex match, seconds) of every event inside the window
        whose name matches."""
        rx = re.compile(pattern)
        out = []
        for chip, dev in self.devices.items():
            for n, _s, d in self._in_window(dev[kind]):
                m = rx.search(n)
                if m:
                    out.append((chip, m, d / 1e9))
        return out

    def op_count(self, pattern: str, kind: str = "ops") -> float:
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        return sum(1 for dev in self.devices.values()
                   for n, _s, _d in self._in_window(dev[kind])
                   if rx.search(n)) / len(self.devices)

    def top_ops(self, k: int = 10):
        """[name, seconds] of the operations with most device time of
        their own (mean over the chips), largest first. A `while` or a
        call holds its body's operations as events of their own inside its
        span, so each event counts its span less the events nested in it."""
        acc = {}
        for dev in self.devices.values():
            for n, d in self_times(self._in_window(dev["ops"])):
                acc[n] = acc.get(n, 0) + d
        n_dev = max(1, len(self.devices))
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[name[:120], ns / n_dev / 1e9] for name, ns in rows]

    # -- host against device ---------------------------------------------
    def span_count(self, name: str) -> int:
        return sum(1 for n, _s, _e in self.spans if n == name)

    def span_host_share(self, name: str) -> float | None:
        """Share of the time inside spans `name` during which no
        operation ran on the device, mean over the chips."""
        spans = union([s, e] for n, s, e in self.spans if n == name)
        if not spans or not self.busy:
            return None
        tot = total(spans)
        shares = [1.0 - covered(spans, b) / tot for b in self.busy.values()]
        return sum(shares) / len(shares)

    def idle_gaps(self, k: int = 5):
        """[span name, seconds] of the longest gaps between device
        operations (chip 0), each named by the harness span that covers
        its middle, or "none"."""
        if not self.busy:
            return []
        busy = self.busy[min(self.busy)]
        edges = [self.t0] + [x for s, e in busy for x in (s, e)] + [self.t1]
        gaps = [(edges[i + 1] - edges[i], edges[i])
                for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(reverse=True)
        out = []
        for length, start in gaps[:k]:
            mid = start + length / 2
            name = next((n for n, s, e in self.spans if s <= mid < e),
                        "none")
            out.append([name, length / 1e9])
        return out

    def collective_exposed_share(self) -> float | None:
        """Collective time during which no other operation runs on that
        chip, over the window, mean over the chips."""
        shares = []
        for dev in self.devices.values():
            ops = [e for e in self._in_window(dev["ops"])
                   if not CONTAINER.search(e[0])]
            kind = lambda n: n.split(" = ", 1)[0]
            coll = union([s, s + d] for n, s, d in ops
                         if COLLECTIVE.search(kind(n)))
            comp = union([s, s + d] for n, s, d in ops
                         if not COLLECTIVE.search(kind(n)))
            if self.t1 > self.t0:
                shares.append((total(coll) - covered(coll, comp))
                              / (self.t1 - self.t0))
        return sum(shares) / len(shares) if shares else None

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(10), "idle_gaps": self.idle_gaps(5)}
