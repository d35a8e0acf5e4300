"""Packed-attention microbenchmark (DESIGN.md §9).

Sweeps the KV-length bucket for a fixed packed stream and reports measured
wall time against an analytic bytes + FLOPs model, for both execution
strategies of ``ops.packed_attention``:

  * ``ref``    — XLA dense-vs-all-slots (scores against every slot's bucket
                 rows, then per-token select): FLOPs carry an extra
                 ``N_slots`` factor but the caches are read once.
  * ``pallas`` / ``interpret`` — block-wise slot gather over query tiles
                 of one slot each: minimal FLOPs, bytes are the KV blocks
                 the tile plan fetches (``kv_blocks_fetched``).

The point of the sweep: both time columns scale with ``kv_bucket``, not
``max_len`` — the §9 claim the engine A/B (offline_throughput) measures
end-to-end.  ``interpret`` runs the Pallas kernel body on CPU and is
orders of magnitude slower than compiled code; it is for correctness
spot-checks, so the default impl here is ``ref``.

``--launches`` prints, for the launch shapes of the Qwen3-4B offline cell
(``launches``), the KV block DMAs of the tiled kernel (all kv heads per
DMA) against the per-token grid it replaced (one kv head per DMA), and on
a TPU the device time of one call at Qwen3-4B widths for each
``--block-q``.
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
from repro.kernels import packed_attention as pa
from repro.launch.runtime import enable_compile_cache
from repro.serving.scheduler import default_kv_buckets


def cost_model(t: int, h: int, kv: int, d_qk: int, d_v: int, n_slots: int,
               kv_bucket: int, itemsize: int, token_slot, lengths) -> dict:
    """Analytic FLOPs/bytes for one packed-attention call over S=kv_bucket
    rows per slot.  ``gather``: the Pallas kernel's tiled slot sweep.
    ``dense``: the XLA ref's all-slots einsum."""
    s = kv_bucket
    qk_flops = 2 * t * h * s * d_qk          # scores
    av_flops = 2 * t * h * s * d_v           # context
    cache_row = kv * (d_qk + d_v) * itemsize
    blocks = pa.kv_blocks_fetched(token_slot, lengths, n_slots=n_slots,
                                  group=h // kv, kv_bucket=s)
    block_rows = min(pa.DEFAULT_BLOCK_K, max(8, s))
    return {
        "gather_flops": qk_flops + av_flops,
        # the KV blocks (every kv head's rows) the tile plan fetches; q +
        # out are T×H vectors
        "gather_bytes": (blocks * block_rows * cache_row
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
        model = cost_model(t, h, kv, d_qk, d_v, n_slots, b, dt.itemsize,
                           np.asarray(slot), np.asarray(lengths))
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


# the Qwen3-4B offline cell (bench/configs/qwen3-4b.json): 8 slots x 2048,
# 32 query heads over 8 kv heads of 128, bf16 KV
CELL = dict(n_slots=8, max_len=2048, h=32, kv=8, d=128)


def launches(seed: int = 0) -> list[tuple[str, int, np.ndarray, np.ndarray]]:
    """(name, kv_bucket, token_slot, lengths) of the cell's launch shapes,
    packed as the engine packs them: decodes first, then the prefill chunk,
    then pad tokens in slot 0 with length 1."""
    rng = np.random.default_rng(seed)
    out = []
    for bucket in (2048, 1024):
        ctx = rng.integers(bucket // 2 + 1, bucket + 1, size=7)
        off = bucket - 128 - int(rng.integers(0, bucket // 4))
        slot = np.r_[np.arange(1, 8), np.zeros(128 + 121, int)]
        lens = np.r_[ctx, off + np.arange(1, 129), np.ones(121, int)]
        out.append(("T256-7dec+chunk128+121pad", bucket, slot, lens))
    ctx = rng.integers(1025, 2049, size=8)
    out.append(("T8-8dec", 2048, np.arange(8), ctx))
    return out


def _kernel_seconds(t: int, bucket: int, slot, lens, block_q: int,
                    reps: int = 20) -> float:
    """Seconds of one call at the cell's widths: ``reps`` calls chained in
    one program (each q depends on the last output), over ``reps``.  The
    tile plan and the q/output permutations are inside the chain; the
    kernel reads the K/V caches in place."""
    c = CELL
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(t, c["h"], c["d"])), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(c["n_slots"], c["max_len"], c["kv"],
                                      c["d"])), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=kc.shape), jnp.bfloat16)
    slot = jnp.asarray(slot, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)

    @jax.jit
    def chain(q, kc, vc):
        def body(_, q):
            o = pa.packed_attention(q, kc, vc, slot, lens, kv_bucket=bucket,
                                    block_q=block_q)
            return q + (o * 1e-3).astype(q.dtype)
        return jax.lax.fori_loop(0, reps, body, q)
    return time_fn(chain, q, kc, vc, iters=5) / reps


def launch_rows(block_qs: list[int]) -> list[dict]:
    """KV block DMAs per call of each cell launch, tiled (each DMA every kv
    head's rows of a block) against per-token (each one kv head's); on a
    TPU also the device time per call at each ``block_q``."""
    c = CELL
    on_chip = jax.default_backend() == "tpu"
    rows = []
    for name, bucket, slot, lens in launches():
        t = len(slot)
        per_token = pa.kv_blocks_fetched_per_token(
            t, kv_heads=c["kv"], kv_bucket=bucket)
        for bq in block_qs:
            tiled = pa.kv_blocks_fetched(
                slot, lens, n_slots=c["n_slots"], group=c["h"] // c["kv"],
                kv_bucket=bucket, block_q=bq)
            row = {"bench": "packed_attention_launch",
                   "case": f"{name}/kv{bucket}/Bq{bq}", "block_q": bq,
                   "kv_blocks_tiled": tiled,
                   "kv_blocks_per_token": per_token}
            if on_chip:
                row["us_per_call"] = round(
                    _kernel_seconds(t, bucket, slot, lens, bq) * 1e6, 1)
            rows.append(row)
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
    ap.add_argument("--launches", action="store_true",
                    help="the cell's launch shapes: DMA counts, chip times")
    ap.add_argument("--block-q", default=str(pa.DEFAULT_BLOCK_Q),
                    help="comma-separated tokens per query tile")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.launches:
        rows = launch_rows([int(b) for b in args.block_q.split(",")])
        for r in rows:
            print(json.dumps(r))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(rows, f, indent=2)
        return
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
