"""Needed-work counts against a brute-force count, and the peaks table."""
import pytest

from benchlib import work

D = {"L": 3, "D": 16, "H": 4, "KV": 2, "hd": 8, "F": 24, "V": 50}
# two prefill chunks of one request, a decode token each of two others
TOKENS = ([("a", p, p == 9) for p in range(4, 10)]
          + [("b", 17, True), ("c", 3, True)])


def brute_model_flops(d, tokens):
    """Every multiply-add of the decoder, one token at a time."""
    total = 0
    for _, pos, sampled in tokens:
        for _ in range(d["L"]):
            for k, n in ((d["D"], d["H"] * d["hd"]),      # q
                         (d["D"], d["KV"] * d["hd"]),     # k
                         (d["D"], d["KV"] * d["hd"]),     # v
                         (d["H"] * d["hd"], d["D"]),      # o
                         (d["D"], d["F"]), (d["D"], d["F"]),  # gate, up
                         (d["F"], d["D"])):               # down
                total += 2 * k * n
            for _ in range(pos + 1):                      # each row attended
                for _ in range(d["H"]):
                    total += 2 * d["hd"] + 2 * d["hd"]    # score, value
        if sampled:
            total += 2 * d["D"] * d["V"]
    return total


def test_model_flops_match_brute_force():
    assert work.model_flops(D, TOKENS) == brute_model_flops(D, TOKENS)


def test_attention_call_counts_each_request_once():
    flops, nbytes = work.attention_call(D, TOKENS, 2)
    want_flops = sum(4 * D["H"] * D["hd"] * (p + 1) for _, p, _ in TOKENS)
    rows = {"a": 10, "b": 18, "c": 4}
    want_bytes = sum(rows.values()) * 2 * D["KV"] * D["hd"] * 2 \
        + len(TOKENS) * 2 * D["H"] * D["hd"] * 2
    assert (flops, nbytes) == (want_flops, want_bytes)


def test_least_time_takes_the_slower_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak) == 10.0     # compute-bound
    assert work.least_time(100.0, 50.0, peak) == 5.0       # memory-bound


def test_peaks_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
