"""``correct`` from a whole run of the harness on the CPU, at a toy size.

A sound run is correct.  Each fault a serving cell can have, planted in the
program underneath the timed path, makes it not correct; so does the
lower-precision control (the reference in int8, W8A8) put in the program's
place.  Only the search for a chip is skipped.
"""
import time

import jax
import jax.numpy as jnp

from benchlib import harness
import tiny

# on eight seeds of this toy cell (CPU) the sound mean gap read
# 0.00008-0.00052 and the control's 0.00089-0.0025; its limit, 0.0008, lies
# between.  This seed read 0.00034 and 0.0025.
SEED = 2**31 + 6977


def _run(kind="closed", tp=1, control=False):
    return harness.run(tiny.cell(kind, tp), SEED, 2.0, False,
                       jax.devices()[:tp], time.perf_counter(),
                       control=control)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_state_left_unchanged_is_caught(monkeypatch):
    """The step computes but never stores the KV it wrote."""
    from repro.models import model
    real = model.forward_packed

    def stale(cfg, params, tokens, cache, *a, **k):
        logits, _ = real(cfg, params, tokens, cache, *a, **k)
        return logits, cache
    monkeypatch.setattr(model, "forward_packed", stale)
    assert not _run()["correct"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    """Attention for the second half of the packed tokens is dropped."""
    from repro.kernels import ops
    real = ops.packed_attention

    def half(q, *a, **k):
        out = real(q, *a, **k)
        keep = jnp.arange(out.shape[0]) < out.shape[0] // 2
        return jnp.where(keep[:, None, None], out, 0)
    monkeypatch.setattr(ops, "packed_attention", half)
    assert not _run()["correct"]


def test_token_altered_where_sampled_is_caught(monkeypatch):
    from repro.serving import sampling
    real = sampling.greedy
    monkeypatch.setattr(sampling, "greedy",
                        lambda logits: (real(logits) + 1) % logits.shape[-1])
    assert not _run()["correct"]


def test_sound_tp4_run_is_correct():
    assert _run("open", tp=4)["correct"]


def test_exchange_between_chips_left_out_is_caught(monkeypatch):
    """Row-parallel projections without their all-reduce."""
    from repro.distributed import tp
    monkeypatch.setattr(tp, "row_parallel",
                        lambda x, w: jnp.einsum("...k,kn->...n", x, w))
    assert not _run("open", tp=4)["correct"]


def test_lower_precision_control_is_not_correct():
    """The reference in int8 (W8A8), judged in the program's place."""
    out = _run(control=True)
    assert not out["correct"], out["checks"]
    assert out["program_mean_logit_gap"] <= \
        out["checks"]["mean_logit_gap"]["limit"]
    assert list(out)[-1] == "checks"
