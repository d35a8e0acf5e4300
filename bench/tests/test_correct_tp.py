"""``correct`` from a whole run of the closed batch, split over four host
devices as the tensor-parallel cell serves it (``test_correct.py`` has the
one-device case and the planted faults)."""
import pytest

from test_correct import _run


@pytest.mark.parametrize("tp", [4])
def test_sound_run_is_correct(tp):
    out = _run(tp=tp)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
