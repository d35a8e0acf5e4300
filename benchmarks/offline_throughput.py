"""Paper Fig. 10: offline throughput.

(a) Real engine run (tiny model, CPU ref path) with dataset-like length
    mixes — measures the *system* overheads (scheduling, batching, KV).
(b) Modeled v5e/A100 throughput: NanoFlow schedule vs sequential baseline vs
    Eq. 9 optimal for the paper's model and workloads — the paper's headline
    "% of optimal" numbers.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import costmodel as cm
from repro.core.autosearch import (autosearch, sequential_schedule,
                                   throughput_estimate)
from repro.launch.runtime import (device_label, enable_compile_cache,
                                  ensure_host_devices)
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.request import Request

WORKLOADS = [("const_512_1024", 512, 1024), ("const_1024_512", 1024, 512),
             ("sharegpt", 246, 322), ("lmsys", 102, 222),
             ("splitwise", 1155, 211)]


def modeled(arch: str, hw: cm.Hardware, n_dev: int, bdense: float = 2048
            ) -> list[dict]:
    cfg = get_config(arch)
    ms = cm.model_stats(cfg)
    opt = cm.optimal_throughput(hw, ms, n_dev) / n_dev
    rows = []
    for name, p, d in WORKLOADS:
        w = cm.Workload(p, d)
        nano = autosearch(cfg, w, hw, n_dev, bdense=bdense)
        seq = sequential_schedule(cfg, w, hw, n_dev, bdense=bdense)
        tp_n = throughput_estimate(cfg, nano, w, hw, n_dev, bdense=bdense)
        tp_s = throughput_estimate(cfg, seq, w, hw, n_dev, bdense=bdense)
        rows.append({
            "bench": "offline_throughput_model",
            "case": f"{arch}@{n_dev}x{hw.name}/{name}",
            "nanoflow_tok_s_dev": round(tp_n, 1),
            "sequential_tok_s_dev": round(tp_s, 1),
            "optimal_tok_s_dev": round(opt, 1),
            "pct_optimal": round(100 * tp_n / opt, 1),
            "speedup": round(tp_n / tp_s, 3),
        })
    return rows


def _submit_workload(eng, name: str, p: int, d: int, n_requests: int,
                     vocab: int, rid0: int, p_cap: int = 64,
                     d_cap: int = 32) -> None:
    rng = np.random.default_rng(0)
    for i in range(n_requests):
        plen = max(2, int(rng.exponential(p))) if "like" in name else p
        dlen = max(2, int(rng.exponential(d))) if "like" in name else d
        eng.submit(Request(rid=rid0 + i,
                           prompt=list(rng.integers(0, vocab,
                                                    size=min(plen, p_cap))),
                           max_new_tokens=min(dlen, d_cap)))


# packed-step A/B matrix (DESIGN.md §8-§10): the async pipelined packed
# step (scheduling overlaps device compute, sampled tokens synced one
# iteration late) vs the same step retired eagerly, vs the packed step
# sweeping the full max_len cache (the pre-§9 baseline)
ENGINE_MODES = [
    ("packed-async", {"async_depth": 1}),
    ("packed", {"async_depth": 0}),
    ("packed-dense-kv", {"async_depth": 0, "kv_bucketing": False}),
]


def engine_measured(n_requests: int = 16,
                    base: EngineConfig = EngineConfig()) -> list[dict]:
    """Real engine runs, A/B-ing the asynchronously pipelined packed step
    (DESIGN.md §10, ``async_depth=1`` — iteration i+1 is formed and
    launched before iteration i's sampled tokens are retrieved) against
    the eager kv-bucketed packed step and the same packed step sweeping
    the full ``max_len`` cache every iteration (the DESIGN.md-§8
    baseline, ``kv_bucketing=False``).  Each mode runs the workload twice
    and reports the second (warmed) pass, so XLA compile time — which differs between the modes'
    compile-cache footprints — doesn't pollute the A/B.  Reported per
    mode: tokens/s, dispatches/iteration, host syncs/iteration, prefill
    expansion, the packed step's bucketing-padding fraction, the
    kv-bucket histogram, the attention-sweep fraction (swept rows /
    max_len rows — the FLOPs/bytes saving of §9), and the §10 overlap
    split (blocking syncs/iteration, blocked/host/dispatch seconds,
    speculative overshoot tokens)."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    flops_fwd = 2 * model.active_params(cfg)
    rows = []
    # prompt:decode ratios scaled from the paper's workloads (splitwise
    # 1155:211 ≈ 5:1 prefill-heavy, sharegpt 246:322 decode-leaning); 8
    # slots so iterations carry several concurrent prefill chunks beside
    # the decodes in one packed launch.
    # "longctx-like" provisions a 512-token cache but serves mixed-length
    # contexts — the regime §9's kv bucketing targets: the dense baseline
    # sweeps slots × 512 rows every iteration regardless of actual context
    for name, p, d, max_len, p_cap, d_cap, n_req in [
            ("splitwise-like", 40, 8, 128, 64, 32, n_requests),
            ("sharegpt-like", 12, 16, 128, 64, 32, n_requests),
            ("longctx-like", 104, 12, 512, 160, 16, min(n_requests, 10))]:
        per_mode: dict[str, dict] = {}
        for mode, kwargs in ENGINE_MODES:
            # mode config on top of the CLI base (EngineConfig satellite:
            # one shared validated surface; the A/B matrix pins its own
            # axes, the base supplies attention toggles etc.)
            mode_kw = dict(async_depth=None, kv_bucketing=True,
                           prefix_caching=False, tp=1)
            mode_kw.update(kwargs)
            ecfg = dataclasses.replace(
                base, max_slots=8, max_len=max_len,
                discrete_sizes=(64, 32, 16, 8), avg_decode_len=float(d),
                **mode_kw)
            eng = ServeEngine(cfg, params, ecfg)
            # warmup pass: the *identical* workload -> compiles every
            # (T bucket, kv bucket) program the measured pass will launch
            _submit_workload(eng, name, p, d, n_req, cfg.vocab_size, 0,
                             p_cap=p_cap, d_cap=d_cap)
            eng.run()
            warm = dataclasses.replace(
                eng.stats,
                dense_batch_hist=dict(eng.stats.dense_batch_hist),
                kv_bucket_hist=dict(eng.stats.kv_bucket_hist))
            warm_drop = eng.scheduler.dropped_tokens
            # measured pass
            _submit_workload(eng, name, p, d, n_req, cfg.vocab_size,
                             n_req, p_cap=p_cap, d_cap=d_cap)
            done = eng.run()
            st = dataclasses.replace(
                eng.stats,
                dense_batch_hist=dict(eng.stats.dense_batch_hist),
                kv_bucket_hist=dict(eng.stats.kv_bucket_hist))
            dropped = eng.scheduler.dropped_tokens - warm_drop
            tokens = st.total_tokens - warm.total_tokens
            wall = st.wall_time - warm.wall_time
            # second measured pass, best-of taken: single-core CPU wall
            # times swing 2-3x under scheduler noise — best-of-2 keeps the
            # mode-vs-mode ratios honest without a longer run
            _submit_workload(eng, name, p, d, n_req, cfg.vocab_size,
                             2 * n_req, p_cap=p_cap, d_cap=d_cap)
            eng.run()
            tok2 = eng.stats.total_tokens - st.total_tokens
            wall2 = eng.stats.wall_time - st.wall_time
            if tok2 / max(wall2, 1e-9) > tokens / max(wall, 1e-9):
                tokens, wall = tok2, wall2
            iters = st.iterations - warm.iterations
            prefill_tok = st.prefill_tokens - warm.prefill_tokens
            model_tok = st.prefill_model_tokens - warm.prefill_model_tokens
            expansion = model_tok / max(prefill_tok, 1)
            pad = st.packed_pad_tokens - warm.packed_pad_tokens
            kv_hist = {b: st.kv_bucket_hist.get(b, 0)
                       - warm.kv_bucket_hist.get(b, 0)
                       for b in st.kv_bucket_hist}
            kv_rows = st.packed_attn_kv_rows - warm.packed_attn_kv_rows
            kv_iters = sum(kv_hist.values())
            per_mode[mode] = {
                "bench": "offline_throughput_engine",
                "case": f"tiny-toy/{name}/{mode}",
                "finished": len(done),
                "tokens": tokens,
                "tok_s_cpu": round(tokens / max(wall, 1e-9), 1),
                "_tok_s_raw": tokens / max(wall, 1e-9),
                "iters": iters,
                "dispatches_per_iter": round(
                    (st.model_dispatches - warm.model_dispatches)
                    / max(iters, 1), 3),
                "host_syncs_per_iter": round(
                    (st.host_syncs - warm.host_syncs) / max(iters, 1), 3),
                "prefill_expansion": round(expansion, 3),
                "prefill_flops_per_tok": round(flops_fwd * expansion),
                "pad_fraction": round(pad / max(tokens + pad, 1), 3),
                # DESIGN.md §9 observability: which kv buckets launched, and
                # the attention sweep as a fraction of the dense max_len
                # sweep (attention FLOPs/bytes scale with this)
                "packed_attn_kv_bucket": {str(b): n for b, n
                                          in sorted(kv_hist.items())},
                "attn_kv_sweep_frac": round(
                    kv_rows / max((tokens + pad) * eng.max_len, 1), 3)
                if kv_iters else None,
                # §10 host/device overlap split (measured pass): how often
                # the deferred sync actually stalled the host, and where
                # the wall clock went
                "async_depth": eng.async_depth,
                "blocking_syncs_per_iter": round(
                    (st.blocking_syncs - warm.blocking_syncs)
                    / max(iters, 1), 3),
                "blocked_sync_s": round(
                    st.blocked_sync_time - warm.blocked_sync_time, 3),
                "host_s": round(st.host_time - warm.host_time, 3),
                "dispatch_s": round(st.dispatch_time - warm.dispatch_time, 3),
                "overshoot_tokens": dropped,
            }
        pk = per_mode["packed"]
        pk["speedup_vs_dense_kv"] = round(
            pk["_tok_s_raw"]
            / max(per_mode["packed-dense-kv"]["_tok_s_raw"], 1e-9), 3)
        # §10 async-vs-eager axis: same packed program, same dispatch/sync
        # counts — the delta is the host/device overlap
        per_mode["packed-async"]["speedup_vs_eager"] = round(
            per_mode["packed-async"]["_tok_s_raw"]
            / max(pk["_tok_s_raw"], 1e-9), 3)
        for r in per_mode.values():
            r.pop("_tok_s_raw")
        rows += list(per_mode.values())
    return rows


def engine_tp_ab(tp: int, n_requests: int = 12) -> list[dict]:
    """Tensor-parallel axis (DESIGN.md §11): the async packed step at tp=1
    vs tp=N (shard_map over host-platform devices), warmed, same workload.
    On this CPU container tp>1 adds real ring collectives on one physical
    core — the interesting numbers are the A/B shape (still 1 dispatch + 1
    sync/iter) and the modeled collective bytes/iteration, not a speedup."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    name, p, d, max_len = "splitwise-like", 40, 8, 128
    rows = []
    raw = {}
    for tp_deg in (1, tp):
        eng = ServeEngine(cfg, params, EngineConfig(
            max_slots=8, max_len=max_len, discrete_sizes=(64, 32, 16, 8),
            avg_decode_len=float(d), async_depth=1, tp=tp_deg))
        _submit_workload(eng, name, p, d, n_requests, cfg.vocab_size, 0)
        eng.run()                                  # warmup: compiles all
        warm = dataclasses.replace(eng.stats)
        _submit_workload(eng, name, p, d, n_requests, cfg.vocab_size,
                         n_requests)
        done = eng.run()
        st = eng.stats
        tokens = st.total_tokens - warm.total_tokens
        wall = st.wall_time - warm.wall_time
        iters = st.iterations - warm.iterations
        raw[tp_deg] = tokens / max(wall, 1e-9)
        rows.append({
            "bench": "offline_throughput_engine",
            "case": f"tiny-toy/{name}/packed-tp{tp_deg}",
            "tp": tp_deg,
            "finished": len(done),
            "tokens": tokens,
            "tok_s_cpu": round(raw[tp_deg], 1),
            "iters": iters,
            "dispatches_per_iter": round(
                (st.model_dispatches - warm.model_dispatches)
                / max(iters, 1), 3),
            "host_syncs_per_iter": round(
                (st.host_syncs - warm.host_syncs) / max(iters, 1), 3),
            "prefill_expansion": round(
                (st.prefill_model_tokens - warm.prefill_model_tokens)
                / max(st.prefill_tokens - warm.prefill_tokens, 1), 3),
            "pad_fraction": round(
                (st.packed_pad_tokens - warm.packed_pad_tokens)
                / max(tokens + st.packed_pad_tokens
                      - warm.packed_pad_tokens, 1), 3),
            "tp_collective_bytes_per_iter": round(
                (st.tp_collective_bytes - warm.tp_collective_bytes)
                / max(iters, 1)),
        })
    rows[-1]["speedup_vs_tp1"] = round(raw[tp] / max(raw[1], 1e-9), 3)
    return rows


def engine_prefix_ab(n_requests: int = 12,
                     base: EngineConfig = EngineConfig()) -> list[dict]:
    """Shared-system-prompt workload (DESIGN.md §12): every request carries
    the same system prompt plus a short distinct user suffix — the regime
    cross-request prefix caching targets.  One priming request runs to
    completion first (sharing materializes across *non-concurrent*
    admissions: blocks register at first commit), then the measured wave of
    ``n_requests`` shared-prefix requests runs with ``prefix_caching`` off
    vs on.  Reported per mode: tokens/s, launched prefill FLOPs per prompt
    token (cached tokens are never launched, so this drops ~by the shared
    fraction), mean TTFT, the prefix-hit fraction, and the CoW copy count —
    while dispatches/iteration and host syncs/iteration must stay at the
    packed step's 1 + 1."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    flops_fwd = 2 * model.active_params(cfg)
    rng = np.random.default_rng(0)
    sys_len, sfx_len, d = 48, 8, 8
    system = [int(t) for t in rng.integers(0, cfg.vocab_size, size=sys_len)]
    sfx = rng.integers(0, cfg.vocab_size, size=(n_requests + 1, sfx_len))
    rows, raw = [], {}
    for pc in (False, True):
        ecfg = dataclasses.replace(
            base, max_slots=8, max_len=128, kv_block_size=16,
            discrete_sizes=(64, 32, 16, 8), avg_decode_len=float(d),
            async_depth=1, kv_bucketing=True, kv_buckets=None,
            prefix_caching=pc, tp=1,
            total_pages=None, kv_budget_bytes=None)
        eng = ServeEngine(cfg, params, ecfg)
        # priming pass: completes one shared-prompt request (commits +
        # hash-registers its prefix blocks) and compiles every program the
        # measured wave launches
        eng.submit(Request(rid=0, prompt=system + [int(t) for t in sfx[0]],
                           max_new_tokens=d))
        eng.run()
        warm = eng.stats.snapshot()
        warm_kv = eng.kv.stats.snapshot()
        for i in range(1, n_requests + 1):
            eng.submit(Request(rid=i,
                               prompt=system + [int(t) for t in sfx[i]],
                               max_new_tokens=d,
                               arrival=time.perf_counter()))
        done = eng.run()
        st = eng.stats.snapshot()
        kvs = eng.kv.stats.snapshot()
        tokens = st["total_tokens"] - warm["total_tokens"]
        wall = st["wall_time"] - warm["wall_time"]
        iters = st["iterations"] - warm["iterations"]
        launched = (st["prefill_model_tokens"]
                    - warm["prefill_model_tokens"])
        prompt_tok = sum(r.prompt_len for r in done)
        hits = kvs["prefix_hit_tokens"] - warm_kv["prefix_hit_tokens"]
        ttft = [r.first_token_at - r.arrival for r in done
                if r.first_token_at is not None]
        mode = "prefix" if pc else "no-prefix"
        raw[mode] = {"flops": flops_fwd * launched / max(prompt_tok, 1),
                     "ttft": float(np.mean(ttft)) if ttft else 0.0,
                     "tok_s": tokens / max(wall, 1e-9)}
        rows.append({
            "bench": "offline_throughput_engine",
            "case": f"tiny-toy/shared-sysprompt/{mode}",
            "finished": len(done),
            "tokens": tokens,
            "tok_s_cpu": round(raw[mode]["tok_s"], 1),
            "iters": iters,
            "dispatches_per_iter": round(
                (st["model_dispatches"] - warm["model_dispatches"])
                / max(iters, 1), 3),
            "host_syncs_per_iter": round(
                (st["host_syncs"] - warm["host_syncs"]) / max(iters, 1), 3),
            "prefill_expansion": round(
                launched / max(st["prefill_tokens"]
                               - warm["prefill_tokens"], 1), 3),
            "prefill_flops_per_prompt_tok": round(raw[mode]["flops"]),
            "ttft_mean_ms": round(raw[mode]["ttft"] * 1e3, 1),
            "prefix_hit_frac": round(hits / max(prompt_tok, 1), 3),
            "cow_copies": kvs["cow_copies"] - warm_kv["cow_copies"],
            "evicted_blocks": (kvs["evicted_blocks"]
                               - warm_kv["evicted_blocks"]),
        })
    rows[-1]["prefill_flops_ratio_vs_no_prefix"] = round(
        raw["prefix"]["flops"] / max(raw["no-prefix"]["flops"], 1e-9), 3)
    rows[-1]["ttft_ratio_vs_no_prefix"] = round(
        raw["prefix"]["ttft"] / max(raw["no-prefix"]["ttft"], 1e-9), 3)
    return rows


def engine_spec_ab(n_requests: int = 10, spec_k: int = 4,
                   base: EngineConfig = EngineConfig()) -> list[dict]:
    """Speculative-decoding axis (DESIGN.md §13): the async packed step
    with ``spec_k`` n-gram drafts per decoding slot vs the plain engine,
    on a repetitive-text workload (motif-tiled prompts, long decodes) —
    the prompt-lookup drafter's target regime.  Greedy, so the two modes
    are token-exact by construction and the A/B isolates the schedule:
    committed tokens per model dispatch must exceed 1 for speculation to
    pay (each dispatch still sweeps the same weights — the §13 bet is
    amortizing that sweep over several committed tokens).  Reported:
    tokens/s, verify acceptance rate, accepted tokens per verify segment,
    committed decode tokens per dispatch, and the 1-dispatch /
    1-deferred-sync invariants."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    d = 40
    prompts = []
    for i in range(2 * n_requests):
        motif = [int(t) for t in rng.integers(0, cfg.vocab_size, size=4)]
        prompts.append((motif * 8)[:28 + (i % 4)])
    rows, raw = [], {}
    for k in (0, spec_k):
        ecfg = dataclasses.replace(
            base, max_slots=8, max_len=128, discrete_sizes=(64, 32, 16, 8),
            avg_decode_len=float(d), async_depth=1, kv_bucketing=True,
            prefix_caching=False, tp=1, spec_k=k,
            drafter="ngram" if k else None, temperature=0.0, top_k=None)
        eng = ServeEngine(cfg, params, ecfg)
        # warmup: same prompt shapes -> compiles every (T, kv) program
        for i, p in enumerate(prompts[:n_requests]):
            eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=d))
        eng.run()
        warm = eng.stats.snapshot()
        for i, p in enumerate(prompts[n_requests:]):
            eng.submit(Request(rid=n_requests + i, prompt=list(p),
                               max_new_tokens=d))
        done = eng.run()
        st = eng.stats.snapshot()
        tokens = st["total_tokens"] - warm["total_tokens"]
        wall = st["wall_time"] - warm["wall_time"]
        iters = st["iterations"] - warm["iterations"]
        disp = st["model_dispatches"] - warm["model_dispatches"]
        dec = st["decode_tokens"] - warm["decode_tokens"]
        segs = st["spec_verify_segments"] - warm["spec_verify_segments"]
        prop = st["spec_proposed_tokens"] - warm["spec_proposed_tokens"]
        acc = st["spec_accepted_tokens"] - warm["spec_accepted_tokens"]
        mode = f"spec-k{k}" if k else "no-spec"
        raw[mode] = {"tok_s": tokens / max(wall, 1e-9),
                     "dec_per_disp": dec / max(disp, 1)}
        rows.append({
            "bench": "offline_throughput_engine",
            "case": f"tiny-toy/repetitive/{mode}",
            "spec_k": k,
            "finished": len(done),
            "tokens": tokens,
            "tok_s_cpu": round(raw[mode]["tok_s"], 1),
            "iters": iters,
            "dispatches_per_iter": round(disp / max(iters, 1), 3),
            "host_syncs_per_iter": round(
                (st["host_syncs"] - warm["host_syncs"]) / max(iters, 1), 3),
            "decode_tokens_per_dispatch": round(raw[mode]["dec_per_disp"],
                                                3),
            "spec_verify_segments": segs,
            "spec_acceptance_rate": round(acc / prop, 3) if prop else None,
            "spec_accepted_per_verify": round((acc + segs) / segs, 3)
            if segs else None,
        })
    rows[-1]["speedup_vs_no_spec"] = round(
        raw[f"spec-k{spec_k}"]["tok_s"] / max(raw["no-spec"]["tok_s"], 1e-9),
        3)
    rows[-1]["decode_per_dispatch_vs_no_spec"] = round(
        raw[f"spec-k{spec_k}"]["dec_per_disp"]
        / max(raw["no-spec"]["dec_per_disp"], 1e-9), 3)
    return rows


def _kvdtype_logit_drift(cfg, max_len: int = 48) -> float:
    """Teacher-forced packed forward, native vs int8 cache, f32 weights —
    the max |Δlogit| sample reported in the A/B.  f32 isolates quantization
    drift from bf16 accumulation noise."""
    import jax.numpy as jnp
    fcfg = dataclasses.replace(cfg, dtype="float32")
    params = model.init(fcfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, fcfg.vocab_size, size=24).astype(np.int32)
    t = len(prompt)
    tok = jnp.asarray(prompt)[None]
    pos = jnp.arange(t, dtype=jnp.int32)
    slot = jnp.zeros(t, jnp.int32)
    act = jnp.ones(t, jnp.int32)
    outs = {}
    for kd in (None, "int8"):
        cache = model.init_cache(fcfg, 1, 2, max_len, kd)
        logits, _ = model.forward_packed(fcfg, params, tok, cache, slot,
                                         pos, pos, act, kv_bucket=max_len)
        outs[kd] = np.asarray(logits, np.float32)
    return float(np.abs(outs[None] - outs["int8"]).max())


def engine_kvdtype_ab(n_requests: int = 10,
                      base: EngineConfig = EngineConfig()) -> list[dict]:
    """int8 KV-cache axis (DESIGN.md §15): the async packed step with the
    native bf16 cache vs the quantized int8 cache, at the SAME
    ``kv_budget_bytes`` — the quantized engine's pages budget admits ~2x
    the token rows, which is the whole point (Eq. 5: B_req scales with KV
    capacity).  head_dim 128 (the production shape) so the f32 scale
    overhead is 4/128 per element and the ratio clears 1.9x.  Reported per
    mode: tokens/s, device pages / max concurrent full-length slots at the
    fixed budget, attention HBM bytes/iteration from the cost-model byte
    rate (swept KV rows × eval_shape bytes/token-row), the bytes-saved
    counter, a teacher-forced max-logit-drift sample, and the greedy
    token-match fraction vs the native engine."""
    from repro.serving.engine import kv_bytes_per_token
    cfg = dataclasses.replace(get_config("tiny-toy"), head_dim=128)
    params = model.init(cfg, jax.random.PRNGKey(0))
    name, p, d, max_len = "sharegpt-like", 12, 8, 128
    budget = kv_bytes_per_token(cfg) * 8 * max_len   # 8 native-rate slots
    drift = _kvdtype_logit_drift(cfg)
    rows, raw, outs = [], {}, {}
    for kd in ("bf16", "int8"):
        ecfg = dataclasses.replace(
            base, max_slots=8, max_len=max_len,
            discrete_sizes=(64, 32, 16, 8), avg_decode_len=float(d),
            async_depth=1, kv_bucketing=True, prefix_caching=False, tp=1,
            spec_k=0,
            total_pages=None, kv_budget_bytes=budget, kv_dtype=kd)
        eng = ServeEngine(cfg, params, ecfg)
        # warmup: identical workload -> compiles every (T, kv) program
        _submit_workload(eng, name, p, d, n_requests, cfg.vocab_size, 0)
        eng.run()
        warm = eng.stats.snapshot()
        _submit_workload(eng, name, p, d, n_requests, cfg.vocab_size,
                         n_requests)
        done = eng.run()
        st = eng.stats.snapshot()
        outs[kd] = {r.rid: tuple(r.output) for r in done}
        tokens = st["total_tokens"] - warm["total_tokens"]
        wall = st["wall_time"] - warm["wall_time"]
        iters = st["iterations"] - warm["iterations"]
        kv_rows = st["packed_attn_kv_rows"] - warm["packed_attn_kv_rows"]
        pages = eng.kv.stats.device_pages_total
        raw[kd] = {"tok_s": tokens / max(wall, 1e-9), "pages": pages}
        rows.append({
            "bench": "offline_throughput_engine",
            "case": f"tiny-toy-hd128/{name}/kv-{kd}",
            "kv_dtype": kd,
            "finished": len(done),
            "tokens": tokens,
            "tok_s_cpu": round(raw[kd]["tok_s"], 1),
            "iters": iters,
            "dispatches_per_iter": round(
                (st["model_dispatches"] - warm["model_dispatches"])
                / max(iters, 1), 3),
            "host_syncs_per_iter": round(
                (st["host_syncs"] - warm["host_syncs"]) / max(iters, 1), 3),
            "kv_budget_bytes": budget,
            "kv_bytes_per_token": eng.kv.bytes_per_token,
            "device_pages_total": pages,
            "max_full_len_slots": pages * eng.kv.page_size // max_len,
            "attn_kv_bytes_per_iter": round(
                kv_rows * eng.kv.bytes_per_token / max(iters, 1)),
            "kv_quant_bytes_saved": (st["kv_quant_bytes_saved"]
                                     - warm["kv_quant_bytes_saved"]),
            "max_logit_drift_f32": round(drift, 5) if kd == "int8" else 0.0,
        })
    match = [rid for rid in outs["bf16"]
             if outs["bf16"][rid] == outs["int8"].get(rid)]
    rows[-1]["pages_ratio_vs_bf16"] = round(
        raw["int8"]["pages"] / max(raw["bf16"]["pages"], 1), 3)
    rows[-1]["speedup_vs_bf16"] = round(
        raw["int8"]["tok_s"] / max(raw["bf16"]["tok_s"], 1e-9), 3)
    rows[-1]["greedy_match_frac"] = round(
        len(match) / max(len(outs["bf16"]), 1), 3)
    return rows


def run(engine_only: bool = False, base: EngineConfig = EngineConfig(),
        tp: int = 1, tp_only: bool = False,
        spec_only: bool = False, kvdtype_only: bool = False) -> list[dict]:
    if tp_only:
        return engine_tp_ab(tp)
    if spec_only:
        return engine_spec_ab(base=base)
    if kvdtype_only:
        return engine_kvdtype_ab(base=base)
    out = [] if engine_only else (
        modeled("llama2-70b", cm.A100_80G, 8)
        + modeled("qwen3-8b", cm.TPU_V5E, 16))
    out += engine_measured(base=base)
    out += engine_prefix_ab(base=base)
    out += engine_spec_ab(base=base)
    out += engine_kvdtype_ab(base=base)
    if tp > 1:
        out += engine_tp_ab(tp)
    return out


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--engine-only", action="store_true",
                    help="skip the modeled-hardware rows (CI smoke)")
    ap.add_argument("--json", default=None,
                    help="also write the rows as a JSON artifact")
    ap.add_argument("--tp-only", action="store_true",
                    help="run only the tp=1-vs-tp=N A/B rows (DESIGN.md "
                         "§11; --tp forces N host-platform devices — CI "
                         "runs the tp axis as a separate invocation to keep "
                         "the baseline rows' environment unchanged)")
    ap.add_argument("--spec-only", action="store_true",
                    help="run only the speculative-decoding A/B rows "
                         "(DESIGN.md §13: n-gram drafts vs plain packed "
                         "engine on a repetitive-text workload)")
    ap.add_argument("--kvdtype-only", action="store_true",
                    help="run only the int8-KV A/B rows (DESIGN.md §15: "
                         "bf16 vs int8 cache at the same kv_budget_bytes — "
                         "pages admitted, tok/s, attention bytes/iter, "
                         "logit drift, greedy match)")
    # engine knobs are defined ONCE on EngineConfig (--tp, --attn-fast,
    # --attn-stream, ... — the same surface as launch/serve.py); the mode
    # matrices pin their own A/B axes on top of this base
    EngineConfig.add_args(ap)
    args = ap.parse_args(argv)
    if args.tp_only and args.tp <= 1:
        ap.error("--tp-only needs --tp N with N > 1")
    # before the first jax operation: importing jax does not initialize
    # the backend, so the host-device flag still takes effect here
    ensure_host_devices(args.tp)
    enable_compile_cache()
    dev = device_label()
    rows = run(engine_only=args.engine_only,
               base=EngineConfig.from_args(args), tp=args.tp,
               tp_only=args.tp_only, spec_only=args.spec_only,
               kvdtype_only=args.kvdtype_only)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
    for r in rows:
        if r["bench"] == "offline_throughput_model":
            print(f"fig10/{r['case']},0.0,"
                  f"nano={r['nanoflow_tok_s_dev']} seq={r['sequential_tok_s_dev']} "
                  f"opt={r['optimal_tok_s_dev']} ({r['pct_optimal']}% of optimal, "
                  f"{r['speedup']}x)")
        elif "spec_k" in r:
            extra = ""
            if "speedup_vs_no_spec" in r:
                extra = (f" [{r['speedup_vs_no_spec']}x vs no-spec, "
                         f"{r['decode_per_dispatch_vs_no_spec']}x "
                         f"decode/dispatch]")
            spec = ""
            if r["spec_acceptance_rate"] is not None:
                spec = (f", accept {r['spec_acceptance_rate']}, "
                        f"{r['spec_accepted_per_verify']} tok/verify")
            print(f"fig10/{r['case']},0.0,{r['tok_s_cpu']} tok/s {dev} "
                  f"({r['tokens']} tokens, {r['iters']} iters, "
                  f"{r['dispatches_per_iter']} disp/it, "
                  f"{r['host_syncs_per_iter']} sync/it, "
                  f"{r['decode_tokens_per_dispatch']} decode tok/dispatch"
                  f"{spec}){extra}")
        elif "kv_dtype" in r:
            extra = ""
            if "pages_ratio_vs_bf16" in r:
                extra = (f" [{r['pages_ratio_vs_bf16']}x pages, "
                         f"{r['speedup_vs_bf16']}x tok/s vs bf16, "
                         f"greedy match {r['greedy_match_frac']}, "
                         f"drift {r['max_logit_drift_f32']}]")
            print(f"fig10/{r['case']},0.0,{r['tok_s_cpu']} tok/s {dev} "
                  f"({r['tokens']} tokens, {r['iters']} iters, "
                  f"{r['dispatches_per_iter']} disp/it, "
                  f"{r['host_syncs_per_iter']} sync/it, "
                  f"{r['device_pages_total']} pages / "
                  f"{r['max_full_len_slots']} full-len slots @ fixed "
                  f"budget, {r['attn_kv_bytes_per_iter'] / 1e3:.1f} KB "
                  f"attn/it, saved {r['kv_quant_bytes_saved'] / 1e3:.0f} KB)"
                  f"{extra}")
        elif "prefix_hit_frac" in r:
            extra = ""
            if "prefill_flops_ratio_vs_no_prefix" in r:
                extra = (f" [{r['prefill_flops_ratio_vs_no_prefix']}x "
                         f"prefill FLOPs, {r['ttft_ratio_vs_no_prefix']}x "
                         f"TTFT vs no-prefix]")
            print(f"fig10/{r['case']},0.0,{r['tok_s_cpu']} tok/s {dev} "
                  f"({r['tokens']} tokens, {r['iters']} iters, "
                  f"{r['dispatches_per_iter']} disp/it, "
                  f"{r['host_syncs_per_iter']} sync/it, "
                  f"{r['prefill_flops_per_prompt_tok']} prefill "
                  f"FLOPs/prompt tok, ttft {r['ttft_mean_ms']} ms, "
                  f"prefix hits {r['prefix_hit_frac']}, "
                  f"{r['cow_copies']} CoW){extra}")
        else:
            extra = ""
            if "speedup_vs_dense_kv" in r:
                extra = f" [{r['speedup_vs_dense_kv']}x vs dense-kv]"
            if "speedup_vs_eager" in r:
                extra = (f" [depth {r['async_depth']}: "
                         f"{r['speedup_vs_eager']}x vs eager packed, "
                         f"{r['blocking_syncs_per_iter']} blocking sync/it, "
                         f"blocked {r['blocked_sync_s']}s "
                         f"host {r['host_s']}s, "
                         f"{r['overshoot_tokens']} overshoot]")
            if "tp" in r:
                extra = (f" [tp={r['tp']}: "
                         f"{r['tp_collective_bytes_per_iter'] / 1e3:.1f} KB "
                         f"collective/it"
                         + (f", {r['speedup_vs_tp1']}x vs tp1"
                            if "speedup_vs_tp1" in r else "") + "]")
            sweep = (f", kv sweep {r['attn_kv_sweep_frac']}x"
                     if r.get("attn_kv_sweep_frac") is not None else "")
            print(f"fig10/{r['case']},0.0,{r['tok_s_cpu']} tok/s {dev} "
                  f"({r['tokens']} tokens, {r['iters']} iters, "
                  f"{r['dispatches_per_iter']} disp/it, "
                  f"{r['host_syncs_per_iter']} sync/it, "
                  f"{r['prefill_expansion']}x prefill work, "
                  f"pad {r['pad_fraction']}{sweep}){extra}")


if __name__ == "__main__":
    main()
