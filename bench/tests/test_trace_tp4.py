"""The trace reduction on four devices, on a trace recorded once on a
four-chip v5e host.

``data/qwen3-8b-tp4.offline-conv-2k.xplane.pb``: ITERS whole iterations of
the Qwen3-8B tp=4 cell, cut by ``trim_trace.py`` from the 3 s that
``bench/run.py --trace 1 --keep-trace`` recorded.
"""
import pathlib

import pytest

from benchlib import trace

DATA = pathlib.Path(__file__).parent / "data"
ITERS = 4


@pytest.fixture(scope="module")
def tp4():
    return trace.reduce(str(DATA / "qwen3-8b-tp4.offline-conv-2k.xplane.pb"),
                        "packed_attention")


def test_four_devices_are_reduced(tp4):
    assert [d.name for d in tp4.devices] == \
        [f"/device:TPU:{i}" for i in range(4)]
    assert tp4.window_s == pytest.approx(0.193982448, abs=1e-9)
    assert tp4.busy_s == pytest.approx(0.19120964475, abs=1e-9)


def test_each_device_has_exposed_collective_time(tp4):
    """The ring hops' ``collective-permute-start/done`` and the all-gathers
    are on every device; ops on the ``XLA Ops`` line run one at a time, so
    none of that time is overlapped there."""
    for d in tp4.devices:
        assert d.collective_ns > 0, d.name
        assert 0 < d.exposed_collective_ns <= d.collective_ns, d.name
        for op in ("collective-permute-start", "collective-permute-done",
                   "all-gather"):
            assert d.op_ns.get(op, 0) > 0, (d.name, op)


def test_kernel_events_per_layer_per_iteration_on_four_devices(tp4):
    assert [d.kernel_events for d in tp4.devices] == [36 * ITERS] * 4


def test_tp4_readers_read(tp4):
    """The kernel and collective shares read the four-device trace."""
    from benchlib import harness, readers, work
    dims = {"L": 36, "D": 4096, "H": 32, "KV": 8, "hd": 128, "F": 12288,
            "V": 151936, "tied": False}
    # one decode token at position 1023 per iteration: the count gate and
    # the arithmetic, not the traced work
    run = harness.Run(trace=tp4, chips=4, dims=dims, itemsize=2,
                      traced_iterations=[[(0, 1023, True)]] * ITERS,
                      peaks=work.peaks("TPU v5 lite"))
    assert readers.attn_roofline(run) > 0
    assert 0 < readers.collective_exposed_frac(run) < 100
