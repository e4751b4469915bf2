"""Reduction of a profiler trace (``.xplane.pb``) to intervals and sums.

Reads with ``jax.profiler.ProfileData`` and nothing else. A TPU's plane is
named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation (a ``while`` and the operations of its body both, nested), its
line ``XLA Modules`` one event per executed program, named
``jit_<function>(<fingerprint>)``. Host threads are lines of ``/host:CPU``,
where ``jax.profiler.TraceAnnotation`` ranges land (``Trace.host`` names a
second and third line of one name ``<name>#2``, ``<name>#3``). All times are
nanoseconds on one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Line:
    names: list
    start: np.ndarray       # int64 ns
    dur: np.ndarray         # int64 ns

    def __len__(self):
        return len(self.names)

    def matching(self, pattern: str) -> "Line":
        rx = re.compile(pattern)
        keep = np.fromiter((bool(rx.search(n)) for n in self.names),
                           dtype=bool, count=len(self.names))
        return Line([n for n, k in zip(self.names, keep) if k],
                    self.start[keep], self.dur[keep])

    def total_s(self) -> float:
        return float(self.dur.sum()) * 1e-9


EMPTY = Line([], np.zeros(0, np.int64), np.zeros(0, np.int64))


def _line(events) -> Line:
    names, start, dur = [], [], []
    for ev in events:
        names.append(ev.name)
        start.append(int(ev.start_ns))
        dur.append(int(ev.duration_ns))
    return Line(names, np.asarray(start, np.int64), np.asarray(dur, np.int64))


@dataclasses.dataclass
class Trace:
    devices: dict       # device ordinal -> {line name: Line}
    host: dict          # thread line name -> Line

    def device_line(self, name: str, ordinal: int = 0) -> Line:
        return self.devices.get(ordinal, {}).get(name, EMPTY)

    def ops(self, ordinal: int = 0) -> Line:
        return self.device_line(OPS_LINE, ordinal)

    def modules(self, ordinal: int = 0) -> Line:
        return self.device_line(MODULES_LINE, ordinal)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(2))] = {
                line.name: _line(line.events) for line in plane.lines}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                # threads of one name (``python``) each keep their line
                name, k = line.name, 1
                while name in host:
                    k += 1
                    name = "%s#%d" % (line.name, k)
                host[name] = _line(line.events)
    return Trace(devices, host)


def union_s(line: Line, lo_ns=None, hi_ns=None) -> float:
    """Seconds covered by at least one of the line's events, clipped to
    ``[lo_ns, hi_ns]`` where given."""
    return sum(b - a for a, b in _merged(line, lo_ns, hi_ns)) * 1e-9


def _merged(line: Line, lo_ns=None, hi_ns=None) -> list:
    if not len(line):
        return []
    start = line.start
    end = line.start + line.dur
    if lo_ns is not None:
        start, end = np.maximum(start, lo_ns), np.maximum(end, lo_ns)
    if hi_ns is not None:
        start, end = np.minimum(start, hi_ns), np.minimum(end, hi_ns)
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    # an interval opens where a start lies beyond every earlier end
    reach = np.maximum.accumulate(end)
    opens = np.ones(len(start), dtype=bool)
    opens[1:] = start[1:] > reach[:-1]
    first = np.flatnonzero(opens)
    last = np.append(first[1:] - 1, len(start) - 1)
    return [(int(start[a]), int(reach[b])) for a, b in zip(first, last)
            if reach[b] > start[a]]


def busy_s(trace: Trace):
    """Seconds in which an operation ran on the device, averaged over the
    devices in the trace (their programs, where a device has no op line);
    None where the trace holds no device."""
    per_device = []
    for lines in trace.devices.values():
        line = lines.get(OPS_LINE)
        if line is None or not len(line):
            line = lines.get(MODULES_LINE, EMPTY)
        per_device.append(union_s(line))
    return float(np.mean(per_device)) if per_device else None


_HLO = re.compile(r"^(%[^ ]+) = \(?([a-z0-9]+\[[^\]]*\])?.*?[})] ([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.1 f32[8,128] fusion`` of an HLO instruction's full text
    (which is what a TPU's op line names its events by); other names as
    they are, cut to 120 characters."""
    m = _HLO.match(name)
    if m:
        return " ".join(p for p in m.groups() if p)
    return name[:120]


def self_times(line: Line, rename=None) -> dict:
    """Seconds per event name with the time of nested events taken out, so
    that a loop and the operations of its body are not counted twice."""
    if not len(line):
        return {}
    order = np.lexsort((-line.dur, line.start))
    start = line.start[order]
    end = start + line.dur[order]
    own = line.dur[order].astype(np.int64).copy()
    stack = []
    for i in range(len(start)):
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(end[i], end[stack[-1]]) - start[i]
        stack.append(i)
    out = {}
    for i, j in enumerate(order):
        name = line.names[j] if rename is None else rename(line.names[j])
        out[name] = out.get(name, 0) + int(own[i])
    return {k: v * 1e-9 for k, v in out.items()}


def top(times: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(times.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: Trace, span_pattern: str, n: int = 10,
              ordinal: int = 0) -> list:
    """The device's ``n`` longest idle gaps, summed by the innermost host
    range matching ``span_pattern`` that was open at the gap's middle
    (``"(no span)"`` where none was)."""
    merged = _merged(trace.ops(ordinal))
    gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(merged, merged[1:])),
                  key=lambda g: g[0] - g[1])[:max(n * 20, 200)]
    spans = []
    for line in trace.host.values():
        sel = line.matching(span_pattern)
        spans.extend(zip(sel.start.tolist(), (sel.start + sel.dur).tolist(),
                         sel.names))
    out = {}
    for lo, hi in gaps:
        mid = (lo + hi) // 2
        cover = [(e - s, name) for s, e, name in spans if s <= mid < e]
        name = min(cover)[1] if cover else "(no span)"
        out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
    return top(out, n)
