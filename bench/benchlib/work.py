"""The work an iteration needs, counted from its shapes, and the chip's peaks.

An iteration is a list of tokens ``(request, position, sampled)``: ``position``
is the token's place in its request (it attends ``position + 1`` rows), and
``sampled`` says the LM head is needed there (a decode token, or the last
prompt token).  These are the counts of the algorithm, not of any kernel: pad
tokens, rows past a token's own context and logits nobody samples count for
nothing, so a kernel that does less waste reads closer to its roofline.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_params(d: dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def model_flops(d: dict, tokens: list) -> float:
    """FLOPs the tokens of one iteration need through the whole model:
    2 per weight per token, attention over each token's own context
    (4·H·hd per row: scores and values), the LM head at sampled tokens."""
    per_tok = 2 * d["L"] * layer_matmul_params(d)
    ctx = sum(pos + 1 for _, pos, _ in tokens)
    heads = sum(1 for *_, sampled in tokens if sampled)
    return (len(tokens) * per_tok + d["L"] * 4 * d["H"] * d["hd"] * ctx
            + heads * 2 * d["D"] * d["V"])


def attention_call(d: dict, tokens: list, itemsize: int) -> tuple:
    """(FLOPs, bytes) one layer's attention needs for one iteration: the
    scores and values over each token's context, each request's K/V rows
    read once (up to the furthest row any of its tokens attends), and q in
    and the output out."""
    H, KV, hd = d["H"], d["KV"], d["hd"]
    flops = sum(4 * H * hd * (pos + 1) for _, pos, _ in tokens)
    rows: dict = {}
    for req, pos, _ in tokens:
        rows[req] = max(rows.get(req, 0), pos + 1)
    kv_bytes = sum(rows.values()) * 2 * KV * hd * itemsize
    qo_bytes = len(tokens) * 2 * H * hd * itemsize
    return flops, kv_bytes + qo_bytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least seconds: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
