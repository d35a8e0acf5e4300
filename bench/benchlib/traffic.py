"""One generator for every traffic mix; a mix is a file of parameters.

``kind`` is ``closed`` (an offline batch: all requests queued at the start) or
``open`` (independent users: Poisson arrivals at ``rate`` requests/s, each
request timed from when it was due).  Prompt and output lengths follow
``prompt`` / ``output`` = ``{"dist": "lognormal", "median", "sigma", "min",
"max"}``.

Every seed gets the same work.  Lengths and inter-arrival gaps are drawn from
``shape_seed`` (a number in the mix's file), in the same order for every
seed, and ``--seed`` draws only the token ids (and the weights, elsewhere).
A window holds a few tens of requests, so a seed that reordered the sizes
would change the work in it, and the spread across seeds would be that of
the work, not of the system (PERF.md, section 6).  ``count`` rounds the
number of requests up to whole ``block``s.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Item:
    prompt: np.ndarray       # int32 token ids
    max_new: int
    due: float | None        # seconds after the origin (open loop)


def _lengths(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if dist["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    elif dist["dist"] == "fixed":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    """Requests a run needs: the batch, or every arrival until the drain's
    end, in whole blocks."""
    if mix["kind"] == "closed":
        n = mix["requests"]
    else:
        n = math.ceil(mix["rate"] * (mix["ramp_s"] + seconds
                                     + mix["drain_s"]))
    return -(-n // mix["block"]) * mix["block"]


def make(mix: dict, seed: int, vocab: int, seconds: float) -> list[Item]:
    n = count(mix, seconds)
    shape_rng = np.random.default_rng(mix["shape_seed"])
    rng = np.random.default_rng(seed)
    prompts = _lengths(mix["prompt"], shape_rng, n)
    outputs = _lengths(mix["output"], shape_rng, n)
    gaps = shape_rng.exponential(1.0 / mix["rate"], n) \
        if mix["kind"] == "open" else None
    due = None if gaps is None else np.cumsum(gaps)
    toks = rng.integers(0, vocab, int(prompts.sum()), dtype=np.int32)
    items, at = [], 0
    for i in range(n):
        items.append(Item(prompt=toks[at:at + prompts[i]],
                          max_new=int(outputs[i]),
                          due=None if due is None else float(due[i])))
        at += prompts[i]
    return items
