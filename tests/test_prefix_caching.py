"""Block-table KV with cross-request prefix caching + CoW (DESIGN.md §12).

Covers the tentpole invariants:
  * property-style block-table invariants under a random op soup — a block
    with refcount > 0 is never on the free list or in the evictor, hash
    entries only point at immutable *full* blocks whose content never
    changes after registration, shared blocks are always hash-registered;
  * a shared prefix ending mid-block takes exactly one CoW copy and shares
    the preceding full blocks (the bucket-edge case);
  * f32 token-exactness of the prefix-caching engine vs the no-sharing
    engine across GQA and MLA configs at async depth 0 and 1, with the
    packed step's 1-dispatch/1-deferred-sync invariant and the
    (|T buckets| + 1) × |kv buckets| compile-cache bound unchanged;
  * LRU eviction of cached ref-0 blocks under allocation pressure;
  * the EngineConfig satellite: validation in ``__post_init__``, the shared
    ``add_args``/``from_args`` CLI surface, env pinning via ``from_env``,
    legacy-kwarg deprecation (``page_size`` -> ``kv_block_size``);
  * the stats satellite: ``EngineStats``/``KVStats`` ``snapshot()`` schema.
"""
import argparse
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import BlockAllocator, PagedKVManager
from repro.serving.request import Request

SIZES = (16, 8)


def _mgr(pages=32, bs=4, prefix=True):
    return PagedKVManager(total_pages=pages, page_size=bs, bytes_per_token=1,
                          avg_decode_len=4.0, prefix_caching=prefix)


# ---------------------------------------------------------------------------
# allocator-level invariants
# ---------------------------------------------------------------------------
def test_block_allocator_protocol():
    assert isinstance(_mgr(), BlockAllocator)


def _check_invariants(kv: PagedKVManager, frozen: dict) -> None:
    """The BlockAllocator protocol invariants, checked against internals."""
    table_refs: dict[int, int] = {}
    for t in kv.tables.values():
        for b in t:
            table_refs[b] = table_refs.get(b, 0) + 1
    pin_refs: dict[int, int] = {}
    for s, _ in kv._pending_copies:
        pin_refs[s] = pin_refs.get(s, 0) + 1
    free = set(kv.free_pages)
    # refcounts exactly mirror table membership + copy-source pins, and a
    # referenced block is never free or evictable
    for b, n in kv._ref.items():
        assert n == table_refs.get(b, 0) + pin_refs.get(b, 0), b
        assert n > 0
        assert b not in free
        assert b not in kv.evictor
    for b in set(table_refs) | set(pin_refs):
        assert b in kv._ref
    # a block in two tables (shared) must be hash-registered (immutable)
    for b, n in table_refs.items():
        if n > 1:
            assert b in kv._key, b
    # hash entries: bijective with _key, full blocks only, never free,
    # content frozen forever once registered
    for key, b in kv._hash.items():
        assert kv._key.get(b) == key
        assert len(kv._tokens[b]) == kv.page_size
        assert b not in free
        if key in frozen:
            assert frozen[key] == kv._tokens[b], "registered block mutated"
        else:
            frozen[key] = kv._tokens[b]
    # a registered block is either referenced or cached in the evictor
    for b in kv._key:
        assert b in kv._ref or b in kv.evictor
    # free list disjoint from the evictor
    for b in free:
        assert b not in kv.evictor


def test_block_table_invariants_random_ops():
    """Property-style: a random soup of allocate / (ensure+extend) / free /
    drain over a tiny token alphabet (to force prefix collisions and
    sharing) keeps every block-table invariant at every step."""
    rng = np.random.default_rng(0)
    kv = _mgr(pages=24, bs=4)
    frozen: dict = {}
    live: list[tuple[int, list[int]]] = []
    next_rid = 0
    for _ in range(300):
        op = int(rng.integers(0, 4))
        if op == 0 or not live:
            plen = int(rng.integers(1, 14))
            prompt = [int(t) for t in rng.integers(0, 3, size=plen)]
            if kv.allocate(next_rid, plen, token_ids=prompt):
                live.append((next_rid, prompt))
                next_rid += 1
        elif op == 1:
            i = int(rng.integers(len(live)))
            rid, toks = live[i]
            toks = toks + [int(rng.integers(0, 3))]
            if kv.ensure(rid, len(toks)):
                assert kv.extend(rid, len(toks), token_ids=toks)
                live[i] = (rid, toks)
        elif op == 2:
            i = int(rng.integers(len(live)))
            rid, _ = live.pop(i)
            kv.free(rid)
        else:
            kv.take_pending_copies()
        _check_invariants(kv, frozen)
    assert kv.stats.prefix_hit_tokens > 0, "soup never shared a prefix"
    assert kv.stats.extend_failures == 0


def test_shared_prefix_ends_mid_block():
    """Bucket-edge case: divergence *inside* a cached block shares the full
    blocks before it and takes exactly one CoW copy of the divergent one."""
    kv = _mgr(pages=32, bs=4)
    p0 = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    assert kv.allocate(0, len(p0), token_ids=p0)
    assert kv.cached_tokens(0) == 0
    assert kv.extend(0, len(p0), token_ids=p0)    # commits blocks 0 and 1
    p1 = [1, 2, 3, 4, 5, 6, 99, 98, 97]           # diverges at token 6
    assert kv.allocate(1, len(p1), token_ids=p1)
    # block 0 (tokens 0-3) shared whole; tokens 4-5 of block 1 via CoW
    assert kv.cached_tokens(1) == 6
    assert kv.stats.prefix_hit_tokens == 6
    assert kv.table(1)[0] == kv.table(0)[0]
    assert kv.table(1)[1] != kv.table(0)[1]
    assert kv.take_pending_copies() == [(kv.table(0)[1], kv.table(1)[1])]
    shared = kv.table(0)[0]
    assert kv._ref[shared] == 2 and shared in kv._key


def test_full_block_reuse_no_cow():
    """A prompt that extends a committed prompt block-exactly shares every
    full block with no copy."""
    kv = _mgr(pages=32, bs=4)
    p0 = [1, 2, 3, 4, 5, 6, 7, 8]
    assert kv.allocate(0, 8, token_ids=p0)
    assert kv.extend(0, 8, token_ids=p0)
    p1 = p0 + [9, 10, 11]
    assert kv.allocate(1, len(p1), token_ids=p1)
    assert kv.cached_tokens(1) == 8
    assert kv.table(1)[:2] == kv.table(0)[:2]
    assert kv.take_pending_copies() == []
    assert kv.stats.cow_copies == 0


def test_lru_eviction_reclaims_cached_blocks():
    kv = _mgr(pages=4, bs=4)
    p = [1, 2, 3, 4, 5, 6, 7, 8]
    assert kv.allocate(0, 8, token_ids=p)
    assert kv.extend(0, 8, token_ids=p)
    kv.free(0)
    # registered blocks stay cached (evictor), not on the free list
    assert kv.pages_free == 4 and len(kv.free_pages) == 2
    # an unrelated allocation under pressure reclaims a cached block and
    # drops its hash entry for good
    assert kv.allocate(1, 12, token_ids=[9] * 12)
    assert kv.stats.evicted_blocks == 1
    _check_invariants(kv, {})


def test_no_prefix_mode_degenerates_to_private_pages():
    kv = _mgr(pages=8, bs=4, prefix=False)
    assert kv.allocate(0, 8, token_ids=[1] * 8)
    assert kv.extend(0, 8, token_ids=[1] * 8)
    assert not kv._hash and not len(kv.evictor)
    assert kv.cached_tokens(0) == 0
    kv.free(0)
    assert sorted(kv.free_pages) == list(range(8))
    assert kv.pages_used == 0


# ---------------------------------------------------------------------------
# engine-level: f32 token-exactness vs the no-sharing engine
# ---------------------------------------------------------------------------
ENGINE_FAMILIES = ["tiny-toy", "deepseek-v2-236b"]   # GQA and (absorbed) MLA


@pytest.fixture(scope="module", params=ENGINE_FAMILIES)
def family(request):
    cfg = get_config(request.param) if request.param == "tiny-toy" \
        else scale_down(get_config(request.param))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def toy():
    cfg = dataclasses.replace(get_config("tiny-toy"), dtype="float32")
    return cfg, model.init(cfg, jax.random.PRNGKey(0))


def _serve(cfg, params, prefix, depth, waves):
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=4, max_len=64, kv_block_size=8, discrete_sizes=SIZES,
        async_depth=depth, prefix_caching=prefix, avg_decode_len=4.0))
    outs = {}
    for wave in waves:
        for rid, prompt in wave:
            # 6 new tokens: the committed stream (prompt + output[:-1]) is
            # then 16 tokens, so the *second* block fills and registers —
            # that's what arms the partial-tail CoW path for wave 2
            eng.submit(Request(rid=rid, prompt=list(prompt),
                               max_new_tokens=6))
        for r in eng.run():
            outs[r.rid] = tuple(r.output)
    return eng, outs


@pytest.mark.parametrize("depth", [0, 1])
def test_prefix_engine_token_exact_vs_no_sharing(family, depth):
    """Two waves — the second shares a 10-token prefix with a completed
    request and diverges mid-block (block size 8) — must sample exactly the
    same f32 tokens with and without prefix caching, while actually sharing
    (hits and CoW copies observed) and keeping the packed step's dispatch /
    sync / compile-cache invariants."""
    cfg, params = family
    base = list(range(11, 21))                       # 10 shared tokens
    wave1 = [(0, base + [30])]
    wave2 = [(i, base + [30 + i]) for i in range(1, 4)]
    _, out0 = _serve(cfg, params, False, depth, [wave1, wave2])
    e1, out1 = _serve(cfg, params, True, depth, [wave1, wave2])
    assert out0 == out1, (cfg.name, depth)
    s = e1.kv.stats
    assert s.prefix_hit_tokens == 30                 # 3 requests x 10 tokens
    assert s.cow_copies == 3                         # one mid-block CoW each
    assert e1.stats.dispatches_per_iter == 1.0
    assert e1.stats.syncs_per_iter == 1.0
    bound = (len(SIZES) + 1) * len(e1.kv_buckets)
    assert e1._packed_step._cache_size() <= bound


# ---------------------------------------------------------------------------
# EngineConfig satellite
# ---------------------------------------------------------------------------
def test_engine_config_validation():
    with pytest.raises(AssertionError):
        EngineConfig(prefix_caching=True, max_len=60, kv_block_size=16)
    # defaulting rules stay un-baked: replace() re-resolves
    c = EngineConfig()
    assert c.async_depth is None and c.resolved_async_depth == 1
    assert dataclasses.replace(c, async_depth=0).resolved_async_depth == 0


def test_engine_config_from_args_and_overrides():
    ap = argparse.ArgumentParser()
    EngineConfig.add_args(ap)
    ns = ap.parse_args(["--slots", "4", "--max-len", "64",
                        "--kv-block-size", "8", "--prefix-caching",
                        "--tp", "2", "--no-kv-bucketing"])
    cfg = EngineConfig.from_args(ns)
    assert cfg.max_slots == 4 and cfg.max_len == 64
    assert cfg.kv_block_size == 8 and cfg.prefix_caching and cfg.tp == 2
    assert cfg.resolved_kv_buckets() == (64,)
    # overrides win over flags (benchmark mode matrices rely on this)
    assert EngineConfig.from_args(ns, prefix_caching=False,
                                  tp=1).prefix_caching is False


def test_engine_config_env_pinned_once(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_FAST", "1")
    monkeypatch.delenv("REPRO_ATTN_STREAM", raising=False)
    cfg = EngineConfig.from_env()
    assert cfg.attn_fast is True and cfg.attn_stream is False
    # explicit values win over env
    assert EngineConfig.from_env(attn_fast=False).attn_fast is False
    # from_env pins: a later env flip cannot change the config
    monkeypatch.setenv("REPRO_ATTN_FAST", "0")
    assert cfg.resolved_attn_fast() is True


def test_legacy_kwargs_deprecated_and_mapped(toy):
    cfg, params = toy
    with pytest.warns(DeprecationWarning):
        eng = ServeEngine(cfg, params, max_slots=2, max_len=32, page_size=8)
    assert eng.kv.page_size == 8 and eng.config.kv_block_size == 8
    assert eng.max_slots == 2
    with pytest.raises(TypeError, match="bogus"):
        ServeEngine(cfg, params, bogus=1)
    # config-first call sites stay warning-free, overrides allowed
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = ServeEngine(cfg, params,
                          EngineConfig(max_slots=2, max_len=32), max_len=64)
    assert eng.max_len == 64 and eng.config.max_slots == 2


# ---------------------------------------------------------------------------
# stats satellite: common snapshot() schema
# ---------------------------------------------------------------------------
def test_stats_snapshot_schema(toy):
    cfg, params = toy
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, kv_block_size=8, discrete_sizes=(8,),
        prefix_caching=True, avg_decode_len=2.0))
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=2))
    eng.run()
    snap = eng.stats.snapshot()
    for k in ("iterations", "model_dispatches", "host_syncs", "total_tokens",
              "throughput", "dispatches_per_iter", "syncs_per_iter",
              "dense_batch_hist", "kv_bucket_hist", "wall_time"):
        assert k in snap, k
    # hist entries are copies, not views into live engine state
    snap["dense_batch_hist"][999] = 1
    assert 999 not in eng.stats.dense_batch_hist
    kv = eng.kv.stats.snapshot()
    for k in ("device_pages_total", "offload_bytes", "prefix_hit_tokens",
              "cow_copies", "evicted_blocks", "extend_failures"):
        assert k in kv, k
