"""Tensor-parallel packed serving (DESIGN.md §11).

The TP equivalence suite: ``tp=2`` must be f32 token-exact against ``tp=1``
across GQA, MLA (+MoE) and the recurrent families, on a mixed
prefill+decode workload, at ``async_depth`` 0 and 1 — while keeping the
packed step's 1 model dispatch + 1 host sync per iteration and the
(|T buckets| + 1) × |kv buckets| compile-cache bound.

These tests need ≥ 2 visible devices, so they run in CI's
``tp-host-devices`` job (``XLA_FLAGS=--xla_force_host_platform_device_count
=2``) and skip on the single-device tier-1 run; a subprocess smoke in
``tests/test_distributed.py`` keeps the default pipeline covering the TP
path too.  Equivalence compares in f32 (see DESIGN.md §9: bf16
accumulation-order diffs flip MoE routing) — "token-exact" means identical
sampled tokens, which f32 preserves because the TP all-reduce only reorders
ulp-level partial sums.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.request import Request

needs_devices = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")

# GQA, MLA(+MoE, shared experts, first dense layer), mLSTM+sLSTM,
# Mamba-hybrid (+attention, MoE) — every mixer family's TP layout
FAMILIES = ["tiny-toy", "deepseek-v2-236b", "xlstm-1.3b",
            "jamba-1.5-large-398b"]

SIZES = (16, 8)


def _cfg(name):
    cfg = get_config(name) if name == "tiny-toy" else scale_down(
        get_config(name))
    if cfg.moe is not None:
        # dropless so tp=1 and tp=2 route identically at capacity edges
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg = _cfg(request.param)
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, tp, depth):
    eng = ServeEngine(cfg, params, max_slots=2, max_len=48,
                      discrete_sizes=SIZES, avg_decode_len=4, tp=tp,
                      async_depth=depth)
    rng = np.random.default_rng(7)
    # mixed workload: prompts long enough to chunk across iterations plus
    # short ones that decode while others still prefill, through slot reuse
    for i, n in enumerate([3, 11, 5, 9, 4]):
        eng.submit(Request(
            rid=i, prompt=list(map(int, rng.integers(0, cfg.vocab_size,
                                                     size=n))),
            max_new_tokens=4))
    done = eng.run()
    assert len(done) == 5
    return eng, {r.rid: tuple(r.output) for r in done}


@needs_devices
@pytest.mark.parametrize("depth", [0, 1])
def test_tp2_token_exact_vs_tp1(family, depth):
    cfg, params = family
    e1, out1 = _run(cfg, params, 1, depth)
    e2, out2 = _run(cfg, params, 2, depth)
    assert out1 == out2, (cfg.name, depth, out1, out2)
    # the TP step is still one dispatch + one (deferred) sync per iteration
    assert e2.stats.dispatches_per_iter == 1.0
    assert e2.stats.syncs_per_iter == 1.0
    # compile-cache bound unchanged under TP: (|T buckets| + 1) × |kv b.|
    bound = (len(SIZES) + 1) * len(e2.kv_buckets)
    assert e2._packed_step._cache_size() <= bound
    assert e2._packed_step._cache_size() == e1._packed_step._cache_size()
    # the collective-traffic model reports real traffic only under TP
    assert e2.stats.tp_collective_bytes > 0
    assert e1.stats.tp_collective_bytes == 0


def _run_prefix(cfg, params, tp, prefix):
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=48, kv_block_size=8, discrete_sizes=SIZES,
        avg_decode_len=4.0, tp=tp, prefix_caching=prefix))
    base = list(range(11, 21))
    outs = {}
    # wave 1 completes (and registers its blocks) before wave 2 arrives,
    # so wave 2 can actually hit the shared prefix
    for wave in ([(0, base + [30])],
                 [(i, base + [30 + i]) for i in range(1, 3)]):
        for rid, prompt in wave:
            eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=6))
        for r in eng.run():
            outs[r.rid] = tuple(r.output)
    return eng, outs


@needs_devices
def test_tp2_prefix_caching_token_exact():
    """Prefix caching composes with TP: block ids index the (shard-local
    head/channel, replicated slot·seq) cache layout identically on every
    device, so shared-prefix serving stays f32 token-exact at tp=2 and the
    dispatch/sync/compile-cache invariants hold."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    _, out_np = _run_prefix(cfg, params, 2, False)
    eng, out_pc = _run_prefix(cfg, params, 2, True)
    assert out_np == out_pc
    assert eng.kv.stats.prefix_hit_tokens == 20      # 2 requests x 10 tokens
    assert eng.kv.stats.cow_copies == 2
    # tp=1 with sharing agrees too (same engine, different mesh)
    _, out_t1 = _run_prefix(cfg, params, 1, True)
    assert out_t1 == out_pc
    assert eng.stats.dispatches_per_iter == 1.0
    assert eng.stats.syncs_per_iter == 1.0
    assert eng._packed_step._cache_size() <= (len(SIZES) + 1) * len(
        eng.kv_buckets)


@needs_devices
def test_tp_param_and_cache_are_sharded():
    """The mesh actually shards: a head-sharded param leaf and a KV cache
    leaf must be distributed over both devices, while last_token stays
    replicated (the §10 feedback loop closes without a collective)."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                      discrete_sizes=SIZES, avg_decode_len=4, tp=2)
    wq = eng.params["group0"]["sub0"]["mixer"]["wq"]
    assert not wq.sharding.is_fully_replicated
    k = eng.cache[0]["sub0"]["k"]
    assert not k.sharding.is_fully_replicated
    assert eng.last_token.sharding.is_fully_replicated
    # local shard of the head axis is half the global width
    assert wq.addressable_shards[0].data.shape[2] == wq.shape[2] // 2


def test_tp1_is_default_and_unsharded():
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                      discrete_sizes=SIZES, avg_decode_len=4)
    assert eng.tp == 1 and eng._mesh is None


def test_tp_requires_divisible_widths():
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    # validation precedes mesh construction, so it raises even deviceless
    bad = dataclasses.replace(cfg, n_heads=3, n_kv_heads=3, head_dim=64)
    with pytest.raises(ValueError, match="n_heads"):
        ServeEngine(bad, params, tp=2)


def _run_spec(cfg, params, tp, spec_k, depth=1):
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, discrete_sizes=SIZES, avg_decode_len=4.0,
        tp=tp, spec_k=spec_k, async_depth=depth, async_harvest=False))
    motif = [5, 9, 3, 7]
    for i, p in enumerate([motif * 5, ([2, 4] * 6)[:11], motif * 3]):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=8))
    done = eng.run()
    assert len(done) == 3 and eng.in_flight == 0
    return eng, {r.rid: tuple(r.output) for r in done}


@needs_devices
@pytest.mark.parametrize("depth", [0, 1])
def test_tp2_spec_decode_token_exact(depth):
    """Speculative decoding (DESIGN.md §13) composes with TP: the verify
    segment's acceptance/rollback runs on replicated metadata inside the
    shard_map body, so tp=2 spec serving is f32 token-exact against both
    tp=1 spec and the plain (spec_k=0) engine, with the 1-dispatch /
    1-deferred-sync invariant and the compile-cache bound intact."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    _, base = _run_spec(cfg, params, 1, 0, depth)
    e1, out1 = _run_spec(cfg, params, 1, 3, depth)
    e2, out2 = _run_spec(cfg, params, 2, 3, depth)
    assert out1 == base
    assert out2 == base
    assert e2.stats.dispatches_per_iter == 1.0
    assert e2.stats.syncs_per_iter == 1.0
    assert e2.stats.spec_verify_segments > 0
    assert e2.stats.spec_accepted_tokens == e1.stats.spec_accepted_tokens
    bound = (len(SIZES) + 1) * len(e2.kv_buckets)
    assert e2._packed_step._cache_size() <= bound


def _run_int8(cfg, params, tp, kv_dtype):
    eng = ServeEngine(cfg, params, max_slots=2, max_len=48,
                      discrete_sizes=SIZES, avg_decode_len=4, tp=tp,
                      kv_dtype=kv_dtype)
    rng = np.random.default_rng(1)
    for i, n in enumerate([3, 11, 5, 9, 4]):
        eng.submit(Request(
            rid=i, prompt=list(map(int, rng.integers(0, cfg.vocab_size,
                                                     size=n))),
            max_new_tokens=4))
    done = eng.run()
    assert len(done) == 5
    return eng, {r.rid: tuple(r.output) for r in done}


@needs_devices
@pytest.mark.parametrize("arch", ["tiny-toy", "deepseek-v2-236b"])
def test_tp2_int8_kv_token_exact(arch):
    """int8 KV (DESIGN.md §15) composes with TP: GQA scale leaves shard on
    the kv-head axis next to their values (MLA latent scales replicate), so
    tp=2 quantized serving is f32 token-exact vs tp=1 quantized serving."""
    cfg = _cfg(arch)
    params = model.init(cfg, jax.random.PRNGKey(0))
    e1, out1 = _run_int8(cfg, params, 1, "int8")
    e2, out2 = _run_int8(cfg, params, 2, "int8")
    assert out1 == out2, (cfg.name, out1, out2)
    assert e2.stats.dispatches_per_iter == 1.0
    assert e2.stats.syncs_per_iter == 1.0
    assert e2.stats.kv_quant_bytes_saved > 0
    # quantization adds no retrace keys: the tp=2 compile cache is exactly
    # the native engine's on the same workload
    e2_bf, out2_bf = _run_int8(cfg, params, 2, "bf16")
    assert out2_bf == out2, cfg.name
    assert e2._packed_step._cache_size() == \
        e2_bf._packed_step._cache_size()
    if cfg.mla is None:
        # GQA: int8 value leaf AND its f32 scale leaf shard across devices
        sub = e2.cache[0]["sub0"]
        assert sub["k"].dtype == jnp.int8
        assert not sub["k"].sharding.is_fully_replicated
        assert not sub["k_s"].sharding.is_fully_replicated
    else:
        # absorbed MLA: latent cache + scales replicate (head-dim sharding
        # happens in the absorbed projections, not the cache)
        sub = e2.cache[0]["sub0"]
        assert sub["c_kv"].dtype == jnp.int8
        assert sub["c_kv_s"].sharding.is_fully_replicated
