"""Whether what the timed path served is correct.

After the window, a sample of the finished requests, drawn from the seed and
always holding the longest, is run through the plain reference once, teacher
forced on each prompt and its served tokens.  A served (greedy) token's gap
is how far its reference logit lies below the reference's best logit at that
position; the number compared is the mean gap over the sample's served tokens
(``mean``).  The widest gap (``widest``) is printed, not compared: on the
chip the widest gaps of sound runs and of the control overlapped within a
factor of two (PERF.md), since both are set by the few near-ties where
rounding flips the greedy pick, while the mean counts how often and how far
the picks go astray.  The control reads the same gaps for the tokens that
the reference computed in the lower precision puts first.
"""
from __future__ import annotations

import numpy as np


def sample(finished: list, seed: int, min_tokens: int,
           max_requests: int) -> list:
    done = [r for r in finished if len(r.output) > 0]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.prompt_len + len(r.output), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 17])
    picked, served = [longest], len(longest.output)
    for j in rng.permutation(len(rest)):
        if served >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[j])
        served += len(rest[j].output)
    return picked


def _gaps(full: list, picks: list) -> np.ndarray:
    """Per token: the reference's best logit minus its logit of the pick."""
    return np.concatenate([lg.max(-1) - lg[np.arange(len(p)), p]
                           for lg, p in zip(full, picks)])


def gaps(ref, conf: dict, seed: int, reqs: list,
         control: bool = False) -> dict:
    """Gaps of the served tokens under the reference (``served``: mean and
    widest) and, when asked, of the lower precision's picks (``control``).
    The reference runs ``batch`` sequences of the engine's ``max_len`` at a
    time, whatever was drawn, so that every run reuses one program and one
    memory peak."""
    size = {"batch": conf["correct"]["batch"],
            "length": conf["engine"]["max_len"]}
    served, low = [], []
    for i in range(0, len(reqs), size["batch"]):
        part = reqs[i:i + size["batch"]]
        seqs = [list(r.prompt) + list(r.output[:-1]) for r in part]
        rows = [list(range(r.prompt_len - 1,
                           r.prompt_len - 1 + len(r.output))) for r in part]
        full = ref.forward_logits(conf, seed, seqs, rows, "f32", **size)
        served.append(_gaps(full, [np.asarray(r.output) for r in part]))
        if control:
            lo = ref.forward_logits(conf, seed, seqs, rows, "int8", **size)
            low.append(_gaps(full, [x.argmax(-1) for x in lo]))
    g = np.concatenate(served)
    out = {"served": {"mean": float(g.mean()), "widest": float(g.max())},
           "tokens": len(g), "requests": len(reqs)}
    if control:
        c = np.concatenate(low)
        out["control"] = {"mean": float(c.mean()), "widest": float(c.max())}
    return out
