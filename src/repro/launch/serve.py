"""Serving driver: offline batch or poisson-arrival online simulation.

  python -m repro.launch.serve --arch tiny-toy --requests 16
  python -m repro.launch.serve --arch tiny-toy --online --rate 4 --duration 10
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, scale_down
from repro.distributed import tp as tp_lib
from repro.launch.mesh import make_tp_mesh
from repro.launch.runtime import (device_label, enable_compile_cache,
                                  ensure_host_devices)
from repro.models import model as model_lib
from repro.serving.config import EngineConfig, PoolConfig
from repro.serving.engine import ServeEngine
from repro.serving.pool import ReplicaPool
from repro.serving.request import Request


def make_requests(n: int, vocab: int, seed: int = 0, p_mean: int = 24,
                  d_mean: int = 16) -> list[Request]:
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = max(2, int(rng.exponential(p_mean)))
        dlen = max(2, int(rng.exponential(d_mean)))
        out.append(Request(
            rid=i, prompt=list(rng.integers(0, vocab, size=min(plen, 96))),
            max_new_tokens=min(dlen, 64)))
    return out


def queue_wait(done: list[Request]) -> str:
    """Queue wait (first slot minus arrival) at p50 and p90."""
    wait = [r.scheduled_at - r.arrival for r in done
            if r.scheduled_at is not None]
    if not wait:
        return ""
    return (f", queue wait p50 {np.percentile(wait, 50)*1e3:.1f} ms "
            f"p90 {np.percentile(wait, 90)*1e3:.1f} ms")


def serve_pool(args, cfg, params, pcfg, reqs) -> None:
    """Multi-replica path (DESIGN.md §14): N engines behind the router,
    driven by the pool event loop, with optional chaos injection."""
    ecfg = EngineConfig.from_args(args, seed=args.seed)

    def mk_engine():
        return ServeEngine(cfg, params, ecfg)

    pool = ReplicaPool([mk_engine() for _ in range(pcfg.replicas)], pcfg,
                       engine_factory=mk_engine)
    rng = np.random.default_rng(args.seed)
    if args.online:
        offsets = list(np.cumsum(
            rng.exponential(1.0 / args.rate, size=len(reqs))))
    else:
        offsets = [0.0] * len(reqs)
    t0 = time.perf_counter()
    results = pool.run_online(reqs, offsets, duration=args.duration
                              if args.online else None)
    wall = time.perf_counter() - t0

    snap = pool.snapshot()
    engines = [h.engine for h in pool.router.replicas if h.engine is not None]
    n_tok = sum(e.stats.total_tokens for e in engines)
    compiles: dict = {}
    for e in engines:
        for k, n in e.stats.compile_hist.items():
            compiles[k] = compiles.get(k, 0) + n
    print(f"pool[{pcfg.replicas} replicas]: finished {len(results)}"
          f"/{len(reqs)} requests, {snap['shed_requests']} shed, "
          f"{n_tok} tokens in {wall*1e3:.0f} ms "
          f"({n_tok / max(wall, 1e-9):.1f} tok/s on {device_label()})")
    print(f"compiles in dispatch by (launch tokens, kv bucket): "
          f"{dict(sorted(compiles.items()))}")
    print(f"fault tolerance: {snap['faults_injected']} faults injected, "
          f"{snap['redispatched_requests']} requests re-dispatched "
          f"({snap['redispatched_tokens']} committed tokens replayed), "
          f"{snap['retries']} retries, {snap['timeouts']} timeouts, "
          f"{snap['slo_violations']} SLO violations")
    for rep in snap["replicas"]:
        state = "alive" if rep["alive"] else "dead"
        if rep["suspect"]:
            state += "/suspect"
        print(f"  r{rep['replica']} [{state}]: depth {rep['queue_depth']}, "
              f"queued {rep['queued_tokens']} tok, in-flight "
              f"{rep['inflight_tokens']} tok, KV {rep['kv_used_frac']:.0%}")
    done = list(results.values())
    lat = [r.finished_at - r.arrival for r in done
           if r.finished_at is not None]
    if lat and args.online:
        print(f"latency: p50 {np.percentile(lat, 50)*1e3:.1f} ms "
              f"p99 {np.percentile(lat, 99)*1e3:.1f} ms" + queue_wait(done))
    for r in pool.shed[:5]:
        print(f"  shed rid={r.rid}: {r.reject_reason}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-toy")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    # engine knobs are defined ONCE on EngineConfig and shared with
    # benchmarks/offline_throughput.py
    EngineConfig.add_args(ap)
    # pool knobs (DESIGN.md §14) — defined once, shared with the online
    # latency benchmark
    PoolConfig.add_args(ap)
    ap.add_argument("--online", action="store_true")
    ap.add_argument("--rate", type=float, default=4.0, help="req/s (poisson)")
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    ensure_host_devices(args.tp)     # before the first jax operation
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = scale_down(cfg)
    key = jax.random.PRNGKey(args.seed)
    if args.tp > 1:
        # made in the TP layout, so a model larger than one device starts
        tp_lib.validate_tp(cfg, args.tp)
        params = tp_lib.init_params_tp(cfg, key, make_tp_mesh(args.tp))
    else:
        params = model_lib.init(cfg, key)
    pcfg = PoolConfig.from_args(args)
    reqs = make_requests(args.requests, cfg.vocab_size, args.seed)

    if pcfg.replicas > 1 or pcfg.fault_plan:
        serve_pool(args, cfg, params, pcfg, reqs)
        return
    eng = ServeEngine(cfg, params, EngineConfig.from_args(args, seed=args.seed))

    if not args.online:
        for r in reqs:
            eng.submit(r)
        done = eng.run()
    else:
        rng = np.random.default_rng(args.seed)
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=len(reqs)))
        t0, done, i = time.perf_counter(), [], 0
        while time.perf_counter() - t0 < args.duration or eng.scheduler.n_active:
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now:
                # absolute stamp: finished_at (commit time) is absolute
                # perf_counter, so latency = finished_at - arrival works
                reqs[i].arrival = t0 + arrivals[i]
                eng.submit(reqs[i])
                i += 1
            plan = eng.scheduler.plan()
            if plan is None:
                # the oldest in-flight commit may unblock planning (§10) —
                # retire one, not the whole pipeline, and re-plan right away
                # if it made progress
                if eng.in_flight:
                    done += eng.drain(max_retire=1)
                    continue
                if i >= len(reqs) and not eng.scheduler.n_active:
                    break
                time.sleep(0.005)
                continue
            done += eng.step(plan)
        done += eng.drain()
        # run() accumulates wall_time internally; the external plan/step
        # loop must account it itself or throughput/wall prints read 0
        eng.stats.wall_time += time.perf_counter() - t0

    # every figure below comes off the common snapshot() schema shared with
    # the benchmark JSON and the tests (EngineStats / KVStats satellites)
    st = eng.stats.snapshot()
    kv = eng.kv.stats.snapshot()
    print(f"finished {len(done)}/{len(reqs)} requests in "
          f"{st['iterations']} iters")
    print(f"tokens: prefill {st['prefill_tokens']} decode "
          f"{st['decode_tokens']} total {st['total_tokens']}")
    print(f"throughput {st['throughput']:.1f} tok/s on {device_label()} "
          f"(compiles left out)")
    print(f"compiles in dispatch: {st['compiles']} "
          f"({st['compile_time']:.2f} s) by (launch tokens, kv bucket): "
          f"{dict(sorted(st['compile_hist'].items()))}")
    print(f"{st['dispatches_per_iter']:.2f} "
          f"dispatches/iter, {st['syncs_per_iter']:.2f} host syncs/iter, "
          f"{st['packed_pad_tokens']} pad tokens")
    print(f"async depth {eng.async_depth}: "
          f"{st['blocking_syncs']}/{st['host_syncs']} blocking syncs "
          f"({st['blocking_syncs_per_iter']:.2f}/iter), "
          f"blocked {st['blocked_sync_time']*1e3:.0f} ms, "
          f"host {st['host_time']*1e3:.0f} ms, "
          f"dispatch {st['dispatch_time']*1e3:.0f} ms "
          f"(wall {st['wall_time']*1e3:.0f} ms), "
          f"{eng.scheduler.dropped_tokens} overshoot tokens dropped")
    if eng.tp > 1:
        print(f"tp={eng.tp}: "
              f"~{st['tp_collective_bytes_per_iter'] / 1e3:.1f} KB "
              f"modeled collective traffic/iter "
              f"({st['tp_collective_bytes'] / 1e6:.2f} MB total)")
    print("dense batch histogram: "
          f"{dict(sorted(st['dense_batch_hist'].items()))}")
    if st["kv_bucket_hist"]:
        swept = sum(b * n for b, n in st["kv_bucket_hist"].items())
        dense = args.max_len * sum(st["kv_bucket_hist"].values())
        print(f"kv bucket histogram: "
              f"{dict(sorted(st['kv_bucket_hist'].items()))}"
              f" (attention sweep {swept / max(dense, 1):.2f}x of max_len)")
    if eng.spec_k > 0:
        rate = st["spec_acceptance_rate"]
        if rate is None:
            print(f"spec decode k={eng.spec_k}: no verify segments ran")
        else:
            print(f"spec decode k={eng.spec_k} "
                  f"({eng.config.resolved_drafter}): "
                  f"{st['spec_verify_segments']} verify segments, "
                  f"{st['spec_accepted_tokens']}/"
                  f"{st['spec_proposed_tokens']} drafts accepted "
                  f"({rate:.0%} acceptance, "
                  f"{st['spec_accepted_per_verify']:.2f} committed "
                  f"tokens/verify)")
    if eng.kv_dtype == "int8":
        print(f"kv dtype int8: {st['kv_quant_bytes_saved']/1e6:.2f} MB of "
              f"cache writes saved vs {eng.cfg.dtype} storage "
              f"({eng.kv.bytes_per_token} B/token vs "
              f"{eng._kv_bytes_native} B/token)")
    if eng.prefix_caching:
        total_prompt = sum(r.prompt_len for r in done)
        print(f"prefix caching: {kv['prefix_hit_tokens']} prompt tokens "
              f"served from shared blocks "
              f"({kv['prefix_hit_tokens'] / max(total_prompt, 1):.0%} of "
              f"prompt), {kv['cow_copies']} CoW block copies, "
              f"{kv['evicted_blocks']} cached blocks evicted")
    print(f"kv offload: {kv['offload_bytes']/1e6:.2f} MB aggregated in "
          f"{kv['aggregated_copies']} copies")
    lat = [(r.finished_at or 0) - r.arrival for r in done if r.finished_at]
    if lat and args.online:
        norm = [l / max(len(r.output), 1) for l, r in zip(lat, done)]
        print(f"normalized latency: p50 {np.percentile(norm, 50)*1e3:.1f} ms/tok "
              f"p99 {np.percentile(norm, 99)*1e3:.1f} ms/tok"
              + queue_wait(done))


if __name__ == "__main__":
    main()
