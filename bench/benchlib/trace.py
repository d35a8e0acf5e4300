"""Reduce a profiler trace of the traced window to device times.

The harness wraps the traced steps in a host span ``bench.window`` and each
call into the program in spans of its own (``scheduler.plan``,
``engine.step``, ``engine.drain``).  From the ``.xplane.pb`` this computes, per
device and within that window: the union of the intervals in which an
operation ran (busy), the time of the named kernel's events, the collective
time that no other operation overlaps (exposed), the operations that took the
most time, and the longest idle gaps labelled by the host span that covers
most of each.

Device planes are ``/device:<PLATFORM>:<n>``; their operations are on the line
``XLA Ops``, where a control-flow operation (the layer loop's ``while``) spans
the operations it runs: only the innermost ones count.  An operation's name
there is its HLO instruction (``%packed_attention.4 = bf16[...] ...``); the
reduction keeps the instruction name without the ``%`` and the numeric suffix
(``packed_attention``).  Host spans are on any line of ``/host:CPU``.  Times
are in nanoseconds on the trace's one timeline.
"""
from __future__ import annotations

import dataclasses
import re

WINDOW = "bench.window"
HOST_SPANS = ("scheduler.plan", "engine.step", "engine.drain", "bench.submit")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")


@dataclasses.dataclass
class Device:
    name: str
    busy_ns: float
    kernel_ns: float
    kernel_events: int
    collective_ns: float
    exposed_collective_ns: float
    op_ns: dict              # op name -> summed ns
    gaps: list               # (start_ns, end_ns) idle inside the window


@dataclasses.dataclass
class Reduced:
    window_ns: float
    devices: list
    gaps: list               # (label, ns), longest first, over all devices

    @property
    def busy_s(self) -> float:
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops: dict = {}
        for d in self.devices:
            for k, v in d.op_ns.items():
                ops[k] = ops.get(k, 0.0) + v / len(self.devices)
        best = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in best],
                "idle_gaps": [[k, v / 1e9] for k, v in self.gaps[:top]]}


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def _minus(a: list, b: list) -> float:
    """Length of union(a) not covered by union(b)."""
    left, j = 0.0, 0
    b = _union(b)
    for s, e in _union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                left += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            left += e - cur
    return left


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))


def _leaves(events: list) -> list:
    """(name, start, end) of the events that contain no other event."""
    events = sorted(events, key=lambda x: (x[1], -x[2]))
    return [ev for i, ev in enumerate(events)
            if not (i + 1 < len(events) and events[i + 1][1] < ev[2])]


def _label(gap: tuple, spans: list) -> str:
    best, most = "no host span", 0.0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > most:
            best, most = name, ov
    return best


def reduce(path: str, kernel: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, window = [], None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/device:"):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    w0, w1 = window
    out = []
    for plane in sorted(devices, key=lambda p: p.name):
        ops = _leaves([(op_name(ev.name), max(ev.start_ns, w0),
                        min(ev.end_ns, w1))
                       for line in plane.lines if line.name == OPS_LINE
                       for ev in line.events
                       if ev.end_ns > w0 and ev.start_ns < w1
                       and ev.duration_ns > 0])
        if not ops:
            continue
        busy = _union([(s, e) for _, s, e in ops])
        coll = [(s, e) for n, s, e in ops if COLLECTIVE.search(n)]
        other = [(s, e) for n, s, e in ops if not COLLECTIVE.search(n)]
        kern = [(s, e) for n, s, e in ops if n == kernel]
        op_ns: dict = {}
        for n, s, e in ops:
            op_ns[n] = op_ns.get(n, 0.0) + (e - s)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out.append(Device(plane.name, _length(busy),
                          sum(e - s for s, e in kern), len(kern),
                          _length(_union(coll)), _minus(coll, other), op_ns,
                          gaps))
    if not out:
        raise ValueError(f"no device operations inside the window in {path}")
    gaps = sorted(((_label(g, spans), g[1] - g[0])
                   for d in out for g in d.gaps), key=lambda x: -x[1])
    return Reduced(w1 - w0, out, gaps)
