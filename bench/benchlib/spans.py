"""Reduce the program's own host spans in a profiler trace, beside the device.

The serving path writes host spans into the profiler's trace
(``src/repro/serving/spans.py``): ``sched.plan``, ``sched.pack``,
``engine.operands``, ``engine.dispatch``, ``engine.retire`` and, inside
it, ``engine.fetch`` and ``sched.commit``.  From the same ``.xplane.pb`` that
``trace.reduce`` reads, and within its ``bench.window``, this gives:

- each program span's count and self time (its duration less the part that
  the program spans nested in it cover);
- the iterations, counted as ``engine.dispatch`` spans;
- the device's idle time, each gap of ``trace.reduce``'s ``Reduced`` put down
  whole to the innermost program span that covers most of it, or to
  ``OUTSIDE`` where no program span covers most of it.

A trace of a program that writes none of these spans reduces to zero
iterations, and every reader below then returns ``None``.

Read a kept trace by hand (``bench/run.py --keep-trace <dir>``)::

    PYTHONPATH=bench python -m benchlib.spans <dir>/<file>.xplane.pb
"""
from __future__ import annotations

import dataclasses
import json
import sys
import types

from benchlib import trace as trace_lib

PLAN, PACK, COMMIT = "sched.plan", "sched.pack", "sched.commit"
OPERANDS, DISPATCH = "engine.operands", "engine.dispatch"
RETIRE, FETCH = "engine.retire", "engine.fetch"
PROGRAM = (PLAN, PACK, COMMIT, OPERANDS, DISPATCH, RETIRE, FETCH)
OUTSIDE = "outside the program"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    meta: dict               # the span's integer metadata
    line: int                # host line (thread) it was recorded on


@dataclasses.dataclass
class Spans:
    window_ns: float
    spans: list              # Span, inside the window, by start
    count: dict              # name -> spans
    self_ns: dict            # name -> summed self time
    idle_ns: dict            # label -> device idle ns, averaged over devices

    @property
    def iterations(self) -> int:
        return self.count.get(DISPATCH, 0)


def read(path: str) -> tuple:
    """(window start, window end, program spans wholly inside the window)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, found = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == trace_lib.WINDOW and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in PROGRAM:
                    found.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                      {m: v for m, v in ev.stats}, k))
    if window is None:
        raise ValueError(f"no {trace_lib.WINDOW!r} span in {path}")
    w0, w1 = window
    inside = [s for s in found if s.start_ns >= w0 and s.end_ns <= w1]
    return w0, w1, sorted(inside, key=lambda s: (s.start_ns, -s.end_ns))


def self_pieces(spans: list) -> list:
    """(name, start, end) pieces of each span's own time: its interval less
    the spans nested in it on the same line.  Spans on one line nest."""
    out = []
    for k in sorted({s.line for s in spans}):
        stack: list = []           # [span, children]
        done: list = []

        def close(entry):
            sp, kids = entry
            cur = sp.start_ns
            for c in kids:
                if c.start_ns > cur:
                    done.append((sp.name, cur, c.start_ns))
                cur = max(cur, c.end_ns)
            if cur < sp.end_ns:
                done.append((sp.name, cur, sp.end_ns))

        for sp in (s for s in spans if s.line == k):
            while stack and stack[-1][0].end_ns <= sp.start_ns:
                close(stack.pop())
            if stack:
                stack[-1][1].append(sp)
            stack.append([sp, []])
        while stack:
            close(stack.pop())
        out += done
    return sorted(out, key=lambda p: p[1])


def attribute(gaps: list, pieces: list) -> list:
    """Label each idle gap (start, end), in order of start, by the name of
    the self pieces (``self_pieces``) that cover most of it; the part that
    no piece covers counts for ``OUTSIDE``."""
    labels = []
    j = 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][2] <= g0:
            j += 1
        cover: dict = {}
        k = j
        while k < len(pieces) and pieces[k][1] < g1:
            ov = min(pieces[k][2], g1) - max(pieces[k][1], g0)
            if ov > 0:
                cover[pieces[k][0]] = cover.get(pieces[k][0], 0.0) + ov
            k += 1
        cover[OUTSIDE] = (g1 - g0) - sum(cover.values())
        labels.append(max(cover.items(), key=lambda kv: kv[1])[0])
    return labels


def reduce(path: str, reduced: trace_lib.Reduced) -> Spans:
    """The program's spans of ``path``, and ``reduced``'s idle gaps put down
    to them (``reduced`` is ``trace.reduce`` of the same file)."""
    w0, w1, spans = read(path)
    pieces = self_pieces(spans)
    count: dict = {}
    self_ns: dict = {}
    for s in spans:
        count[s.name] = count.get(s.name, 0) + 1
    for name, s, e in pieces:
        self_ns[name] = self_ns.get(name, 0.0) + (e - s)
    idle: dict = {}
    for d in reduced.devices:
        for (s, e), label in zip(d.gaps, attribute(d.gaps, pieces)):
            idle[label] = idle.get(label, 0.0) + (e - s) / len(reduced.devices)
    return Spans(w1 - w0, spans, count, self_ns, idle)


# ---- readers: ``run.spans`` is this module's ``reduce`` of the run's trace --
def _per_iter_ms(run, names) -> float | None:
    sp = getattr(run, "spans", None)
    if sp is None or not sp.iterations:
        return None
    return sum(sp.self_ns.get(n, 0.0) for n in names) / sp.iterations / 1e6


def sched_ms_per_iter(run):
    """scheduler: self time of plan, pack and commit per iteration."""
    return _per_iter_ms(run, (PLAN, PACK, COMMIT))


def operand_ms_per_iter(run):
    """engine: self time of the operand build per iteration."""
    return _per_iter_ms(run, (OPERANDS,))


def dispatch_ms_per_iter(run):
    """engine: self time of the dispatch (the jitted call and the operand
    copies in its argument list) per iteration."""
    return _per_iter_ms(run, (DISPATCH,))


def host_idle_frac(run):
    """device: idle time put down to a program span other than the fetch
    (so the host, not the device, held the step back), over the window."""
    sp = getattr(run, "spans", None)
    if sp is None or not sp.iterations:
        return None
    held = sum(v for k, v in sp.idle_ns.items() if k in PROGRAM and k != FETCH)
    return 100.0 * held / sp.window_ns


READERS = {"sched_ms_per_iter": sched_ms_per_iter,
           "operand_ms_per_iter": operand_ms_per_iter,
           "dispatch_ms_per_iter": dispatch_ms_per_iter,
           "host_idle_frac": host_idle_frac}


def table(sp: Spans) -> str:
    """Per program span: count, self ms and idle ms; then the idle outside
    the program."""
    rows = [f"{'span':<18}{'count':>7}{'self ms':>12}{'idle ms':>11}"]
    for n in PROGRAM:
        rows.append(f"{n:<18}{sp.count.get(n, 0):>7}"
                    f"{sp.self_ns.get(n, 0.0) / 1e6:>12.3f}"
                    f"{sp.idle_ns.get(n, 0.0) / 1e6:>11.3f}")
    rows.append(f"{OUTSIDE:<37}{sp.idle_ns.get(OUTSIDE, 0.0) / 1e6:>11.3f}")
    rows.append(f"window {sp.window_ns / 1e6:.3f} ms, "
                f"{sp.iterations} iterations")
    return "\n".join(rows)


def main(paths: list) -> None:
    for path in paths:
        sp = reduce(path, trace_lib.reduce(path, kernel=None))
        print(f"{path}\n{table(sp)}", file=sys.stderr)
        run = types.SimpleNamespace(spans=sp)
        print(json.dumps({"trace": path} | {
            k: f(run) for k, f in READERS.items()}))


if __name__ == "__main__":
    main(sys.argv[1:])
