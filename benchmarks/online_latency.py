"""Paper Fig. 11/12 + DESIGN.md §14: online latency under Poisson and
bursty arrivals, single replica vs pool, with a chaos smoke.

The *shape* of the latency-vs-rate curve (flat, then hockey-stick at
saturation) and the tight CDF under discrete batching are the paper's
claims; absolute numbers are CPU proxies.  Per workload class this reports
TTFT and TPOT p50/p95/p99 over *finished* requests only — an unfinished
request contributes to the ``finished``/``shed`` counts, never a fabricated
latency (the old ``finished_at or 0`` fallback produced negative
latencies).  The arrival loop lives in ``ReplicaPool.run_online``: it
sleeps only when idle, never busy-waits, and never over-sleeps past the
next arrival.

Modes:
  * default        — pool-vs-single A/B across workload classes
                     (``--json BENCH_8.json`` commits the artifact)
  * --chaos-smoke  — 2 replicas, seeded kill of replica 1 mid-stream;
                     asserts zero lost responses (completed + shed ==
                     submitted) in the JSON row
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.runtime import enable_compile_cache
from repro.models import model
from repro.serving.config import EngineConfig, PoolConfig
from repro.serving.engine import ServeEngine
from repro.serving.faults import FaultPlan
from repro.serving.pool import ReplicaPool
from repro.serving.request import Request


def _fixture():
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_slots=4, max_len=96, discrete_sizes=(32, 16, 8),
                        avg_decode_len=6.0)
    return cfg, params, ecfg


def make_workload(kind: str, n: int, rate: float, vocab: int,
                  seed: int = 0) -> tuple[list[Request], list[float]]:
    """Arrival offsets for one class.

    ``poisson``: exponential inter-arrivals at ``rate`` req/s.
    ``bursty``:  on/off process — bursts of 4 back-to-back arrivals at 4x
    rate separated by idle gaps, same long-run mean rate (the ScaleLLM-style
    workload where p99 separates systems that p50 cannot)."""
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=list(rng.integers(0, vocab,
                                             size=int(rng.integers(4, 16)))),
                    max_new_tokens=int(rng.integers(3, 9)))
            for i in range(n)]
    if kind == "poisson":
        offsets = np.cumsum(rng.exponential(1.0 / rate, size=n))
    elif kind == "bursty":
        offsets, t, burst = [], 0.0, 4
        while len(offsets) < n:
            for _ in range(min(burst, n - len(offsets))):
                t += rng.exponential(1.0 / (4.0 * rate))
                offsets.append(t)
            t += rng.exponential(burst * 0.75 / rate)   # off period
        offsets = np.asarray(offsets[:n])
    else:
        raise ValueError(f"unknown workload class {kind!r}")
    return reqs, list(map(float, offsets))


def _pct(xs: list[float], q: float) -> float:
    return round(float(np.percentile(xs, q)) * 1e3, 2) if xs else None


def run_class(kind: str, replicas: int, rate: float, n: int, seed: int,
              fault_plan: str = "", timeout_s: float = 120.0) -> dict:
    cfg, params, ecfg = _fixture()

    def mk():
        return ServeEngine(cfg, params, ecfg)

    engines = [mk() for _ in range(replicas)]
    for k, eng in enumerate(engines):
        # warm the jit caches before the clock starts: a cold engine's
        # first iterations are compile time, not serving latency, and
        # would swamp the pool-vs-single comparison
        for j in range(4):
            eng.submit(Request(rid=10_000 + 10 * k + j,
                               prompt=list(range(2, 14)), max_new_tokens=6))
        eng.run()
    pool = ReplicaPool(
        engines, PoolConfig(replicas=replicas),
        fault_plan=FaultPlan.parse(fault_plan) if fault_plan else None)
    reqs, offsets = make_workload(kind, n, rate, cfg.vocab_size, seed)
    t0 = time.perf_counter()
    results = pool.run_online(reqs, offsets, duration=timeout_s)
    wall = time.perf_counter() - t0

    done = list(results.values())
    ttft = [r.first_token_at - r.arrival for r in done
            if r.first_token_at is not None]
    tpot = [(r.finished_at - r.first_token_at) / (len(r.output) - 1)
            for r in done
            if r.finished_at is not None and r.first_token_at is not None
            and len(r.output) > 1]
    snap = pool.snapshot()
    return {
        "bench": "online_latency", "class": kind, "replicas": replicas,
        "rate": rate, "submitted": snap["submitted"],
        "finished": len(done), "shed": snap["shed_requests"],
        "lost": snap["submitted"] - len(done) - snap["shed_requests"],
        "ttft_p50_ms": _pct(ttft, 50), "ttft_p95_ms": _pct(ttft, 95),
        "ttft_p99_ms": _pct(ttft, 99),
        "tpot_p50_ms": _pct(tpot, 50), "tpot_p95_ms": _pct(tpot, 95),
        "tpot_p99_ms": _pct(tpot, 99),
        "faults_injected": snap["faults_injected"],
        "redispatched_requests": snap["redispatched_requests"],
        "redispatched_tokens": snap["redispatched_tokens"],
        "retries": snap["retries"],
        "wall_s": round(wall, 2),
    }


def run_ab(n: int, rate: float, seed: int) -> list[dict]:
    """Pool-vs-single A/B per workload class (BENCH_8 artifact rows)."""
    rows = []
    for kind in ("poisson", "bursty"):
        for replicas in (1, 2):
            rows.append(run_class(kind, replicas, rate, n, seed))
    return rows


def run_chaos_smoke(n: int, rate: float, seed: int) -> dict:
    """Seeded kill of replica 1-of-2 mid-stream: the pool must account for
    every submitted request (zero lost responses)."""
    row = run_class("poisson", 2, rate, n, seed, fault_plan="kill@25:r1")
    row["bench"] = "online_latency_chaos"
    assert row["faults_injected"] >= 1, "fault plan never fired"
    assert row["lost"] == 0, f"lost {row['lost']} responses after kill"
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, help="artifact path")
    ap.add_argument("--chaos-smoke", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.chaos_smoke:
        rows = [run_chaos_smoke(args.requests, args.rate, args.seed)]
    else:
        rows = run_ab(args.requests, args.rate, args.seed)
    for r in rows:
        print(f"{r['bench']}/{r.get('class', '')}/r{r['replicas']},"
              f"{r['ttft_p50_ms']},"
              f"ttft p50={r['ttft_p50_ms']}ms p99={r['ttft_p99_ms']}ms "
              f"tpot p99={r['tpot_p99_ms']}ms/tok "
              f"finished={r['finished']}/{r['submitted']} "
              f"shed={r['shed']} lost={r['lost']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)


if __name__ == "__main__":
    main()
