"""The serving path's host spans, and the benchmark's reduction of them.

A tiny engine runs a few packed iterations under ``jax.profiler.trace``; the
reduction (``bench/benchlib/spans.py``) reads the spans back from the trace
file.  Its self-time and idle-attribution functions are also checked on
hand-made intervals, and its readings are pinned on a trace recorded on a
v5e chip.
"""
import pathlib
import sys

import jax
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.append(str(BENCH))

from benchlib import spans, trace  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import model  # noqa: E402
from repro.serving import spans as program_spans  # noqa: E402
from repro.serving.config import EngineConfig  # noqa: E402
from repro.serving.engine import ServeEngine  # noqa: E402
from repro.serving.request import Request  # noqa: E402


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(trace file, iterations before, iterations after) of a batch of
    requests served inside a profiler session, after one served outside it
    (which compiles)."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, discrete_sizes=(16, 8), avg_decode_len=4.0))

    def batch(rid0):
        for i in range(3):
            eng.submit(Request(rid=rid0 + i, prompt=list(range(3 + i, 14)),
                               max_new_tokens=4))
        eng.run()

    batch(0)
    it0 = eng.stats.iterations
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            batch(100)
    finally:
        jax.profiler.stop_trace()
    path = str(sorted(out.glob("**/*.xplane.pb"))[-1])
    return path, it0, eng.stats.iterations


def test_one_dispatch_and_one_fetch_per_iteration(traced):
    path, it0, it1 = traced
    _, _, sp = spans.read(path)
    want = list(range(it0 + 1, it1 + 1))
    assert len(want) >= 4
    for name in (spans.DISPATCH, spans.FETCH):
        assert sorted(s.meta["iteration"] for s in sp
                      if s.name == name) == want, name
    for s in sp:
        if s.name == spans.DISPATCH:
            # the launch grid: the decode-only floor (2 slots), then 8, 16
            assert s.meta["tokens"] in (2, 8, 16)
            assert s.meta["kv_bucket"] > 0


def test_dispatch_names_tp_and_nano_groups(traced):
    """Each launch says how many devices its step is split over and its
    nano-batch groups, which add up to the launch length."""
    _, _, sp = spans.read(traced[0])
    seen = set()
    for s in sp:
        if s.name == spans.DISPATCH:
            assert s.meta["tp"] == 1
            groups = [int(g) for g in str(s.meta["nano"]).split("+")]
            assert sum(groups) == s.meta["tokens"]
            seen.add(len(groups))
    # the 16-token launches split in two, the shorter ones do not
    assert seen == {1, 2}
    assert program_spans.groups((128, 120)) == "128+120"


def test_fetch_and_commit_nest_inside_retire(traced):
    _, _, sp = spans.read(traced[0])
    retire = [s for s in sp if s.name == spans.RETIRE]
    inner = [s for s in sp if s.name in (spans.FETCH, spans.COMMIT)]
    assert len(inner) == 2 * len(retire) > 0
    for s in inner:
        assert any(r.line == s.line and r.start_ns <= s.start_ns
                   and s.end_ns <= r.end_ns for r in retire), s


def test_self_times_never_negative(traced):
    # a CPU trace has no device plane: no gaps to put down
    sp = spans.reduce(traced[0], trace.Reduced(1.0, [], []))
    assert set(sp.count) == set(spans.PROGRAM)
    for name in spans.PROGRAM:
        whole = sum(s.end_ns - s.start_ns for s in sp.spans if s.name == name)
        assert 0 <= sp.self_ns[name] <= whole, name
    assert all(e > s for _, s, e in spans.self_pieces(sp.spans))


def test_program_span_names_apart_from_the_benchmarks():
    assert set(program_spans.ALL) == set(spans.PROGRAM)
    assert not set(spans.PROGRAM) & set(trace.HOST_SPANS + (trace.WINDOW,))


def _span(name, s, e, line=0):
    return spans.Span(name, s, e, {}, line)


def test_self_pieces_take_out_nested_spans():
    sp = [_span("engine.retire", 0, 100), _span("engine.fetch", 10, 40),
          _span("sched.commit", 50, 60), _span("sched.plan", 100, 110),
          _span("engine.dispatch", 5, 20, line=1)]
    assert spans.self_pieces(sorted(sp, key=lambda s: s.start_ns)) == [
        ("engine.retire", 0, 10), ("engine.dispatch", 5, 20),
        ("engine.fetch", 10, 40), ("engine.retire", 40, 50),
        ("sched.commit", 50, 60), ("engine.retire", 60, 100),
        ("sched.plan", 100, 110)]


def test_attribute_gaps_to_the_piece_covering_most():
    pieces = [("a", 0, 10), ("b", 10, 30)]
    gaps = [(0, 12), (5, 25), (28, 40), (40, 50)]
    assert spans.attribute(gaps, pieces) == [
        "a", "b", spans.OUTSIDE, spans.OUTSIDE]


def test_readers_read_nothing_without_program_spans():
    path = str(BENCH / "tests" / "data" / "qwen3-4b.offline-conv.xplane.pb")
    sp = spans.reduce(path, trace.reduce(path, kernel=None))
    assert sp.iterations == 0
    run = type("Run", (), {"spans": sp})
    assert all(f(run) is None for f in spans.READERS.values())
    assert all(f(object()) is None for f in spans.READERS.values())


# ``bench/tests/data/qwen3-4b.offline-conv.spans.xplane.pb``: 27 iterations
# of the Qwen3-4B offline cell with the program's spans, traced by
# ``bench/run.py --trace 1 --seed 1414213562`` on one "TPU v5 lite".
@pytest.fixture(scope="module")
def chip():
    path = str(BENCH / "tests" / "data"
               / "qwen3-4b.offline-conv.spans.xplane.pb")
    red = trace.reduce(path, "packed_attention")
    return red, spans.reduce(path, red)


def test_chip_trace_pairs_each_dispatch_with_its_fetch(chip):
    red, sp = chip
    assert sp.iterations == 27
    assert red.devices[0].kernel_events == 36 * 27
    for name in (spans.DISPATCH, spans.FETCH):
        its = [s.meta["iteration"] for s in sp.spans if s.name == name]
        assert len(its) == len(set(its)) == 27, name
    assert {s.meta["iteration"] for s in sp.spans
            if s.name == spans.DISPATCH} == {
        s.meta["iteration"] for s in sp.spans if s.name == spans.FETCH}


def test_chip_trace_readings(chip):
    _, sp = chip
    run = type("Run", (), {"spans": sp})
    got = {k: f(run) for k, f in spans.READERS.items()}
    assert got == pytest.approx({"sched_ms_per_iter": 0.155828926,
                                 "operand_ms_per_iter": 0.495375111,
                                 "dispatch_ms_per_iter": 2.715419037,
                                 "host_idle_frac": 0.106406056}, abs=1e-9)


def test_chip_trace_idle_by_span(chip):
    red, sp = chip
    # the first launch on an empty pipeline, and the drain at the end
    small = {k: v for k, v in sp.idle_ns.items()
             if k not in (spans.DISPATCH, spans.FETCH)}
    assert all(v < 1e4 for v in small.values()), small
    assert sp.idle_ns[spans.DISPATCH] / 1e6 == pytest.approx(3.586, abs=1e-3)
    assert sp.idle_ns[spans.FETCH] / 1e6 == pytest.approx(1.348, abs=1e-3)
    # every idle nanosecond of the device is put down to exactly one label
    assert sum(sp.idle_ns.values()) == pytest.approx(
        red.window_ns - red.busy_s * 1e9, abs=1.0)
