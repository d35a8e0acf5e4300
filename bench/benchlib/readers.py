"""How each metric is read from a run; ``bench/metrics/<name>.py`` picks one.

A reader returns ``None`` when the run holds nothing for it to read (no
trace, no kernel events, no finished request), and the harness then leaves
the metric out.  Shares are in percent.
"""
from __future__ import annotations

import math

import numpy as np

from benchlib import work


def _p90(xs: list) -> float | None:
    """90th percentile, the nearest rank at or above (never interpolated,
    so an infinitely late request stays infinitely late)."""
    if not xs:
        return None
    xs = sorted(xs)
    return xs[max(math.ceil(0.9 * len(xs)) - 1, 0)]


# ---- end to end (host clock) ----------------------------------------------
def setup_s(run):
    return run.setup_s


def tokens_per_s(run):
    """Prompt tokens prefilled plus output tokens committed in the window,
    over the window."""
    if run.window.get("committed") is None:
        return None
    return run.window["committed"] / run.window["window_s"]


def ttft_p90_ms(run):
    """Over every request due in the window, from when it was due to the
    commit of its first token.  One with no first token by the end of the
    drain counts as infinitely late; if that reaches the percentile, the
    reading is the time from its due time to the end of the drain (a lower
    bound on its lateness), so that the number stays finite."""
    reqs = run.window["window_requests"]
    if not reqs:
        return None
    end = run.window["drain_end"]
    ttft = [(r.first_token_at if r.first_token_at is not None
             else math.inf) - r.arrival for r in reqs]
    v = _p90(ttft)
    if math.isinf(v):
        v = max(end - r.arrival for r in reqs if r.first_token_at is None)
    return v * 1e3


def tpot_p90_ms(run):
    """Over the window's finished requests: (finish - first token) / (output
    tokens - 1)."""
    tpot = [(r.finished_at - r.first_token_at) / (len(r.output) - 1)
            for r in run.window["window_requests"]
            if r.finished_at is not None and len(r.output) > 1]
    v = _p90(tpot)
    return None if v is None else v * 1e3


# ---- per layer -------------------------------------------------------------
def pad_frac(run):
    """scheduler: padding launched over everything launched in the window."""
    s = run.window["stats"]
    launched = s.get("prefill_tokens", 0) + s.get("decode_tokens", 0) \
        + s.get("packed_pad_tokens", 0)
    if not launched:
        return None
    return 100.0 * s["packed_pad_tokens"] / launched


def host_ms_per_iter(run):
    """engine: host time per iteration over the window."""
    s = run.window["stats"]
    if not s.get("iterations"):
        return None
    return 1e3 * s["host_time"] / s["iterations"]


def step_mfu(run):
    """model step: FLOPs the traced iterations need, over the device's busy
    time times chips times the peak."""
    if run.trace is None or not run.traced_iterations or run.peaks is None:
        return None
    need = sum(work.model_flops(run.dims, it) for it in run.traced_iterations)
    return 100.0 * need / (run.trace.busy_s * run.chips
                           * run.peaks["bf16_flops_per_s"])


def attn_roofline(run):
    """kernel: least time for the attention the traced iterations need, over
    the summed device time of the kernel's events.  Each device runs its
    share of the heads; every layer makes one call per iteration.  A trace
    whose kernel events do not number one per layer per iteration per device
    is not read."""
    tr = run.trace
    if tr is None or not run.traced_iterations or run.peaks is None:
        return None
    n_dev, L = len(tr.devices), run.dims["L"]
    events = sum(d.kernel_events for d in tr.devices)
    if events != n_dev * L * len(run.traced_iterations) or not events:
        return None
    least = 0.0
    for it in run.traced_iterations:
        flops, nbytes = work.attention_call(run.dims, it, run.itemsize)
        least += L * n_dev * work.least_time(flops / n_dev, nbytes / n_dev,
                                             run.peaks)
    return 100.0 * least / (sum(d.kernel_ns for d in tr.devices) / 1e9)


def idle_frac(run):
    """device: share of the traced window with no operation on the device."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def collective_exposed_frac(run):
    """tp collectives: collective time no other operation overlaps, over the
    traced window, averaged over the devices."""
    tr = run.trace
    if tr is None or not any(d.collective_ns for d in tr.devices):
        return None
    return 100.0 * float(np.mean([d.exposed_collective_ns
                                  for d in tr.devices])) / tr.window_ns
