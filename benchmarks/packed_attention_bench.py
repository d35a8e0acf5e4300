"""Packed-attention microbenchmark (DESIGN.md §9).

Sweeps the KV-length bucket for a fixed packed stream and reports measured
wall time against an analytic bytes + FLOPs model, for both execution
strategies of ``ops.packed_attention``:

  * ``ref``    — XLA dense-vs-all-slots (scores against every slot's bucket
                 rows, then per-token select): FLOPs carry an extra
                 ``N_slots`` factor but the caches are read once.
  * ``pallas`` / ``interpret`` — block-wise slot gather (each token DMAs
                 only its own slot's rows): minimal FLOPs, bytes carry a
                 per-token factor.

The point of the sweep: both time columns scale with ``kv_bucket``, not
``max_len`` — the §9 claim the engine A/B (offline_throughput) measures
end-to-end.  ``interpret`` runs the Pallas kernel body on CPU and is
orders of magnitude slower than compiled code; it is for correctness
spot-checks, so the default impl here is ``ref``.
"""
from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import emit, time_fn
except ImportError:                      # run directly, not as a module
    from common import emit, time_fn
from repro.kernels import ops
from repro.launch.runtime import enable_compile_cache
from repro.serving.scheduler import default_kv_buckets


def cost_model(t: int, h: int, kv: int, d_qk: int, d_v: int, n_slots: int,
               kv_bucket: int, itemsize: int) -> dict:
    """Analytic FLOPs/bytes for one packed-attention call over S=kv_bucket
    rows per slot.  ``gather``: the Pallas kernel's per-token slot sweep.
    ``dense``: the XLA ref's all-slots einsum."""
    s = kv_bucket
    qk_flops = 2 * t * h * s * d_qk          # scores
    av_flops = 2 * t * h * s * d_v           # context
    cache_row = kv * (d_qk + d_v) * itemsize
    return {
        "gather_flops": qk_flops + av_flops,
        # each token streams its own slot's rows; q + out are T×H vectors
        "gather_bytes": (t * s * cache_row
                         + t * h * (d_qk + d_v) * itemsize),
        "dense_flops": n_slots * (qk_flops + av_flops),
        # caches read once; the (T, N, KV, G, S) score tensor round-trips
        "dense_bytes": (n_slots * s * cache_row
                        + 2 * t * n_slots * h * s * 4
                        + t * h * (d_qk + d_v) * itemsize),
    }


def run(impl: str = "ref", t: int = 64, n_slots: int = 8, max_len: int = 512,
        h: int = 8, kv: int = 2, d_qk: int = 64, d_v: int = 64,
        dtype: str = "bfloat16", iters: int = 5) -> list[dict]:
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(t, h, d_qk)), dt)
    k_cache = jnp.asarray(rng.normal(size=(n_slots, max_len, kv, d_qk)), dt)
    v_cache = jnp.asarray(rng.normal(size=(n_slots, max_len, kv, d_v)), dt)
    slot = jnp.asarray(rng.integers(0, n_slots, size=t), jnp.int32)

    rows = []
    # sweep the same grid the engine actually launches (DESIGN.md §9)
    for b in default_kv_buckets(max_len):
        lengths = jnp.asarray(rng.integers(1, b + 1, size=t), jnp.int32)
        fn = jax.jit(functools.partial(
            ops.packed_attention, logit_scale=d_qk ** -0.5, kv_bucket=b,
            impl=impl))
        sec = time_fn(fn, q, k_cache, v_cache, slot, lengths, iters=iters)
        model = cost_model(t, h, kv, d_qk, d_v, n_slots, b, dt.itemsize)
        kind = "dense" if impl == "ref" else "gather"
        rows.append({
            "bench": "packed_attention",
            "case": f"{impl}/T{t}xN{n_slots}/kv{b}of{max_len}/{dtype}",
            "impl": impl,
            "kv_bucket": b,
            "us_per_call": round(sec * 1e6, 1),
            "model_gflops": round(model[f"{kind}_flops"] / 1e9, 4),
            "model_mbytes": round(model[f"{kind}_bytes"] / 1e6, 3),
            "achieved_gflop_s": round(model[f"{kind}_flops"] / sec / 1e9, 2),
            "achieved_gb_s": round(model[f"{kind}_bytes"] / sec / 1e9, 2),
        })
    # the §9 scaling check, attached to the smallest-bucket row: how much
    # faster the bucketed sweep is than the full-cache sweep
    full, small = rows[-1], rows[0]
    small["speedup_vs_full_sweep"] = round(
        full["us_per_call"] / max(small["us_per_call"], 1e-9), 2)
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="ref",
                    choices=["ref", "pallas", "interpret"])
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--d-qk", type=int, default=64)
    ap.add_argument("--d-v", type=int, default=64,
                    help="set != --d-qk for the absorbed-MLA shape")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    rows = run(impl=args.impl, t=args.tokens, n_slots=args.slots,
               max_len=args.max_len, h=args.heads, kv=args.kv_heads,
               d_qk=args.d_qk, d_v=args.d_v, dtype=args.dtype)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
    for r in rows:
        extra = (f" [{r['speedup_vs_full_sweep']}x faster than the "
                 f"full-cache sweep]" if "speedup_vs_full_sweep" in r else "")
        emit(r["case"], r["us_per_call"],
             f"{r['achieved_gflop_s']} GFLOP/s {r['achieved_gb_s']} GB/s "
             f"(model {r['model_gflops']} GF, {r['model_mbytes']} MB)"
             + extra)


if __name__ == "__main__":
    main()
