"""The trace reduction, on a trace recorded once on a v5e chip.

``data/qwen3-4b.offline-conv.xplane.pb``: 13 iterations of the Qwen3-4B
offline cell (all at launch length 256 and KV bucket 2048), traced by
``bench/run.py --trace 1`` on one "TPU v5 lite".
"""
import pathlib

import pytest

from benchlib import trace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def chip():
    return trace.reduce(str(DATA / "qwen3-4b.offline-conv.xplane.pb"),
                        "packed_attention")


def test_window_and_busy(chip):
    assert len(chip.devices) == 1
    assert chip.window_s == pytest.approx(3.334072723, abs=1e-9)
    assert chip.busy_s == pytest.approx(3.328599031, abs=1e-9)


def test_kernel_events_one_per_layer_per_iteration(chip):
    d = chip.devices[0]
    assert d.kernel_events == 36 * 13
    assert d.kernel_ns / 1e9 == pytest.approx(2.883104605, abs=1e-9)


def test_no_collectives_on_one_chip(chip):
    assert chip.devices[0].collective_ns == 0
    assert chip.devices[0].exposed_collective_ns == 0


def test_breakdown(chip):
    b = chip.breakdown()
    assert b["device_ops"][0][0] == "packed_attention"
    assert not any(name == "while" for name, _ in b["device_ops"])
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    label, seconds = b["idle_gaps"][0]
    assert label == "engine.step"
    assert seconds == pytest.approx(0.002804587, abs=1e-9)
    # busy plus every gap is the window
    gaps = sum(g[1] - g[0] for g in chip.devices[0].gaps)
    assert (chip.devices[0].busy_ns + gaps) / 1e9 == pytest.approx(
        chip.window_s, abs=1e-9)


def test_op_name():
    assert trace.op_name("%packed_attention.4 = bf16[256,8] custom-call()") \
        == "packed_attention"
    assert trace.op_name("%all-reduce-start.12 = f32[2] all-reduce-start()") \
        == "all-reduce-start"


def test_leaves_drop_enclosing_ops():
    ev = [("while", 0, 100), ("a", 0, 10), ("b", 20, 30), ("c", 40, 100),
          ("d", 120, 130)]
    assert [n for n, *_ in trace._leaves(ev)] == ["a", "b", "c", "d"]


def test_exposed_is_collective_time_not_under_compute():
    coll = [(0, 10), (20, 30)]
    other = [(5, 25)]
    assert trace._minus(coll, other) == 10
    assert trace._minus(coll, []) == 20
    assert trace._minus([], other) == 0
