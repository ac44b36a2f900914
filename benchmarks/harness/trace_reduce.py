"""From a profiler trace to device busy time, idle share, top ops, gaps.

Two steps, so that the arithmetic is checked without a chip:
`read_xplane` turns the profiler's `.xplane.pb` into plain lists of
(name, start_ns, duration_ns), and `reduce_events` turns those into the
numbers. `benchmarks/tests/recorded_trace.json` is the first step's
output for a short window on the chip, and the tests hold the second
step to it.

Busy time is the union of the intervals in which an operation ran on
the device (the device plane's "XLA Ops" line), averaged over the
chips used (device planes with an operation in the window); an operation's own time leaves out the operations nested
in it; the window is the harness's own `bench:window`
annotation on the host plane. An idle gap is named by the statement
annotation and the innermost host event that cover its midpoint.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

WINDOW = "bench:window"
STATEMENT = "bench:stmt:"
OPS_LINE = "XLA Ops"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> dict:
    """{'devices': {plane: [[name, start_ns, dur_ns], ...]},
        'host': [[name, start_ns, dur_ns], ...],
        'lines': {plane: [line names]}}"""
    from jax.profiler import ProfileData
    devices, host, lines = {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in ln.events if e.duration_ns > 0)
    return {"devices": devices, "host": host, "lines": lines}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def op_name(event_name: str) -> str:
    """'%fusion.60 = (u32[6000000]...) fusion(...)' -> 'fusion.60': XLA's
    own instruction name, until the program names its regions."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def _self_times(ops):
    """Each op's time less that of the ops nested in it (a `while` holds
    its body's ops on the same line), summed by name."""
    own, stack = {}, []
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= e - s
        own[name] = own.get(name, 0) + (e - s)
        stack.append((e, name))
    return own


class _HostLabels:
    """Names an instant by the statement annotation and the innermost
    (shortest) other host event that cover it."""

    def __init__(self, host):
        host = [h for h in host if h[0] != WINDOW]
        self.names = [h[0] for h in host]
        self.start = np.array([h[1] for h in host], dtype=np.int64)
        self.dur = np.array([h[2] for h in host], dtype=np.int64)
        self.is_stmt = np.array([n.startswith(STATEMENT)
                                 for n in self.names], dtype=bool)

    def at(self, instant: int) -> str:
        covers = (self.start <= instant) & (instant < self.start + self.dur)
        stmt, inner = "between_statements", "host_python"
        for i in np.flatnonzero(covers & self.is_stmt)[:1]:
            stmt = self.names[i][len(STATEMENT):]
        others = np.flatnonzero(covers & ~self.is_stmt)
        if len(others):
            inner = self.names[others[np.argmin(self.dur[others])]]
        return f"{stmt}/{inner}"


def reduce_events(events: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle_pct, device_ops, idle_gaps of one trace."""
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    if not events["devices"]:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line: {events.get('lines')}")
    busy_ns, op_ns, gaps, labels = 0, {}, {}, _HostLabels(events["host"])
    n = 0  # chips used: device planes with an operation in the window
    for ops in events["devices"].values():
        clipped = []
        for name, s, d in ops:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                clipped.append((s, e, op_name(name)))
        for name, ns in _self_times(clipped).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        if not clipped:
            continue  # a chip the cell holds and does not use
        n += 1
        merged = _union((s, e) for s, e, _ in clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for se in merged for t in se] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = labels.at((g0 + g1) // 2)
                gaps[label] = gaps.get(label, 0) + (g1 - g0)
    if busy_ns <= 0:
        raise ValueError("no device operation ran inside the traced window")

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    busy_s, window_s = busy_ns / n / 1e9, (w1 - w0) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": ranked(op_ns), "idle_gaps": ranked(gaps)}

