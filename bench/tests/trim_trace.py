"""Cut a kept trace down to a few whole iterations, for a test fixture.

    python3 bench/tests/trim_trace.py <in.xplane.pb> <out.xplane.pb> \
        <kernel> <layers> <iterations>

A ``--trace 1`` run traces 3 s of the window, which on four devices is tens
of megabytes.  This keeps ``iterations`` consecutive whole iterations from
the middle of the traced window: the ``bench.window`` span is moved onto
them, so that every device's ``kernel`` events inside it number exactly
``layers x iterations``, and every event that does not overlap the new
window is dropped.  The window's edges lie in the gap between one
iteration's last kernel event and the next one's first, on every device.

It reads and writes the ``XSpace`` protocol buffer with the message classes
that the installed TensorFlow ships, loaded from their file alone (TensorFlow
itself is not imported).
"""
from __future__ import annotations

import importlib.util
import pathlib
import sys

WINDOW = "bench.window"


def _xplane_pb2():
    tf = importlib.util.find_spec("tensorflow")
    path = pathlib.Path(tf.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(line, ev) -> tuple:
    """(start, end) of an event in ps on the trace's one timeline."""
    s = line.timestamp_ns * 1000 + ev.offset_ps
    return s, s + ev.duration_ps


def _kernel_events(plane, kernel: str) -> list:
    """Sorted (start, end) of the kernel's events on a device plane."""
    out = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            name = plane.event_metadata[ev.metadata_id].name
            op = name.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
            if op == kernel:
                out.append(_span(line, ev))
    return sorted(out)


def trim(src: str, dst: str, kernel: str, layers: int, iterations: int):
    pb = _xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(pathlib.Path(src).read_bytes())
    devices = [p for p in space.planes if p.name.startswith("/device:")
               and _kernel_events(p, kernel)]
    kern = [_kernel_events(p, kernel) for p in devices]
    n = min(len(k) for k in kern) // layers
    if n < iterations + 2:
        raise SystemExit(f"only {n} whole iterations traced")
    first = (n - iterations) // 2          # iteration index of the first kept

    def edge(i: int) -> int:
        """A time between iteration i-1's last kernel event and iteration
        i's first, on every device."""
        before = max(k[i * layers - 1][1] for k in kern)
        after = min(k[i * layers][0] for k in kern)
        if before >= after:
            raise SystemExit(f"iterations {i - 1} and {i} overlap across "
                             f"devices")
        return (before + after) // 2

    w0, w1 = edge(first), edge(first + iterations)
    moved = False
    for plane in space.planes:
        for line in plane.lines:
            keep = []
            for ev in line.events:
                s, e = _span(line, ev)
                name = plane.event_metadata[ev.metadata_id].name
                if name == WINDOW and not moved:
                    ev.offset_ps = w0 - line.timestamp_ns * 1000
                    ev.duration_ps = w1 - w0
                    moved = True
                    keep.append(ev)
                elif e > w0 and s < w1:
                    keep.append(ev)
            kept = [type(ev)() for ev in keep]
            for new, old in zip(kept, keep):
                new.CopyFrom(old)
            del line.events[:]
            line.events.extend(kept)
    if not moved:
        raise SystemExit(f"no {WINDOW!r} span in {src}")
    pathlib.Path(dst).write_bytes(space.SerializeToString())
    return first, (w1 - w0) / 1e12


if __name__ == "__main__":
    src, dst, kernel, layers, iterations = sys.argv[1:6]
    first, seconds = trim(src, dst, kernel, int(layers), int(iterations))
    print(f"kept iterations {first}..{first + int(iterations) - 1} of the "
          f"trace, {seconds:.9f} s, in {dst}")
