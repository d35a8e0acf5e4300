"""Token-packed dense-batch step (DESIGN.md §8).

Covers the tentpole invariants:
  * ``forward_packed`` over a mixed stream (decode token + two prefill
    chunks + padding, all in one call) == per-request ``forward_decode`` /
    ``forward_chunk`` references, across every mixer family;
  * the stacked cache carried through the layer loop: each layer writes
    its own new rows and nothing else, contiguous and block-table;
  * engine packed step == token-by-token ``forward_full`` greedy decoding
    end-to-end (f32 so op-order rounding can't flip MoE routing), through
    slot reuse, across every mixer family;
  * exactly one jitted model dispatch and one device→host transfer per
    engine iteration;
  * the compile cache is bounded by the scheduler's discrete dense sizes
    and the launched shapes come from that set;
  * prefill expansion stays 1.0 and padding is accounted;
  * nano-batch interleave ordering of packed segments;
  * the KV-manager satellite fixes (upload no longer loses blobs on device
    re-allocation failure; LRU evictions count discarded requests).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.configs.base import ATTN
from repro.core.nanobatch import NanoBatchPlan, packed_segment_order
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import PagedKVManager
from repro.serving.request import Request

FAMILIES = ["tiny-toy", "deepseek-v2-236b", "jamba-1.5-large-398b",
            "xlstm-1.3b", "musicgen-medium"]


def _cfg(name, dtype=None):
    cfg = get_config(name) if name == "tiny-toy" else scale_down(
        get_config(name))
    if cfg.moe is not None:
        # dropless so per-request and packed batch shapes route identically
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    return cfg


def _tokens(cfg, key, b, s):
    if cfg.frontend == "audio":
        return jax.random.randint(key, (b, s, cfg.num_codebooks), 0,
                                  cfg.vocab_size)
    return jax.random.randint(key, (b, s), 0, cfg.vocab_size)


def _gather_slot(cache, i):
    return jax.tree.map(lambda a: a[:, i:i + 1], cache)


# f32 end-to-end: the packed step must be *semantically* exact against the
# per-request paths — in f32 the recurrent families agree to the last ulp,
# so any real masking/offset bug shows as a gross error instead of hiding
# under a bf16 accumulation-order tolerance (bf16 coverage comes from the
# tiny-toy naive-greedy engine tests, which run the packed step by default)
@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg = _cfg(request.param, dtype="float32")
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mixed_stream(cfg, max_len):
    """The packed stream: slot0 decode @4, slot1 chunk [4,9), slot2 chunk
    [4,6), 2 padding tokens -> T = 10 (any further slot gets no token)."""
    dec = _tokens(cfg, jax.random.PRNGKey(2), 1, 1)
    ch1 = _tokens(cfg, jax.random.PRNGKey(3), 1, 5)
    ch2 = _tokens(cfg, jax.random.PRNGKey(4), 1, 2)
    pad = jnp.zeros_like(_tokens(cfg, jax.random.PRNGKey(5), 1, 2))
    stream = jnp.concatenate([dec, ch1, ch2, pad], axis=1)
    slot = jnp.asarray([0] + [1] * 5 + [2] * 2 + [0] * 2, jnp.int32)
    pos = jnp.asarray([4, 4, 5, 6, 7, 8, 4, 5, 0, 0], jnp.int32)
    active = jnp.asarray([True] * 8 + [False] * 2)
    wpos = jnp.where(active, pos, max_len)
    return (dec, ch1, ch2), stream, slot, pos, wpos, active


def _per_request_reference(cfg, params, cache, segments, pre):
    """Per-request decode/chunk references on gathered one-slot caches for
    ``_mixed_stream``'s three segments, each after ``pre`` cached tokens.
    Returns (logits of the 8 real tokens, [new one-slot cache per slot])."""
    dec, ch1, ch2 = segments
    clen = jnp.full((1,), pre, jnp.int32)
    ref_dec, ref_dec_cache = model.forward_decode(
        cfg, params, dec, _gather_slot(cache, 0), clen)
    ref1, ref1_cache = model.forward_chunk(
        cfg, params, ch1, _gather_slot(cache, 1), clen)
    ref2, ref2_cache = model.forward_chunk(
        cfg, params, ch2, _gather_slot(cache, 2), clen)
    ref = jnp.concatenate([ref_dec[:, None], ref1, ref2], axis=1)
    return ref, [ref_dec_cache, ref1_cache, ref2_cache]


def _assert_logits_match(cfg, got, ref):
    err = float(jnp.abs(got.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    scale = float(jnp.abs(ref.astype(jnp.float32)).max()) + 1e-6
    # f32: exact up to einsum-order rounding (the recurrent families are
    # bit-identical; attention differs in reduction order, and the MoE
    # router amplifies those ulps into slightly different expert weights) —
    # a real masking/offset bug would be O(scale)
    assert err <= max(1e-3 * scale, 1e-4), (cfg.name, err, scale)


def _assert_close(got, ref, what):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    tol = max(1e-3 * (np.abs(ref).max() + 1e-6), 1e-4)
    assert np.abs(got - ref).max() <= tol, what


def test_forward_packed_matches_per_request_reference(family):
    """One packed call carrying a decode token (slot 0), a deep prefill
    chunk (slot 1), a short chunk (slot 2), and padding == the per-request
    decode/chunk reference paths."""
    cfg, params = family
    max_len, pre = 16, 4
    cache = model.init_cache(cfg, 1, 3, max_len)

    # common 4-token prefix in every slot (per-row chunk path)
    prefix = _tokens(cfg, jax.random.PRNGKey(1), 3, pre)
    _, cache = model.forward_chunk(cfg, params, prefix, cache,
                                   jnp.zeros((3,), jnp.int32))

    segments, stream, slot, pos, wpos, active = _mixed_stream(cfg, max_len)
    logits, new_cache = model.forward_packed(cfg, params, stream, cache,
                                             slot, pos, wpos, active)
    ref, ref_caches = _per_request_reference(cfg, params, cache, segments,
                                             pre)
    _assert_logits_match(cfg, logits[:, :8], ref)

    # committed state: each slot's recurrent carry matches its reference;
    # padding committed nothing (slot 0's state untouched by the pad tokens)
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        for i, spec in enumerate(pattern):
            got_sub = new_cache[gi][f"sub{i}"]
            for si, ref_cache in enumerate(ref_caches):
                ref_sub = ref_cache[gi][f"sub{i}"]
                for name, leaf in got_sub.items():
                    _assert_close(leaf[:, si], ref_sub[name][:, 0],
                                  (cfg.name, gi, i, name, si))


BLOCK = 4


@pytest.mark.parametrize("mode", ["contiguous", "block-table"])
def test_forward_packed_writes_each_layers_new_rows_only(family, mode):
    """The stacked cache is carried through the layer loop and every layer
    writes only its own T new rows.  From a cache whose attention rows are
    all random (past each slot's context too): every layer's rows at
    (slot, wpos) hold that layer's new K/V (latents) of the per-request
    reference, every other row of every layer is bit-for-bit the input, a
    slot with no tokens keeps its recurrent state bit-for-bit, and the
    logits match the reference.  In block-table mode the slots' blocks sit
    at permuted physical blocks and the checks run on the logical view."""
    cfg, params = family
    n, max_len, pre, kv_bucket = 4, 16, 4, 12
    # recurrent state from a random 4-token prefix in each slot; attention
    # leaves random in every row of every layer
    cache = model.init_cache(cfg, 1, n, max_len)
    prefix = _tokens(cfg, jax.random.PRNGKey(1), n, pre)
    _, cache = model.forward_chunk(cfg, params, prefix, cache,
                                   jnp.zeros((n,), jnp.int32))
    attn_subs = [{f"sub{i}" for i, spec in enumerate(pattern)
                  if spec.mixer == ATTN}
                 for pattern, _ in cfg.layer_groups()]
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    cache = [{sub: ({name: jax.random.normal(next(keys), a.shape, a.dtype)
                     for name, a in c.items()}
                    if sub in attn_subs[gi] else c)
              for sub, c in group.items()}
             for gi, group in enumerate(cache)]

    segments, stream, slot, pos, wpos, active = _mixed_stream(cfg, max_len)
    kw = {}
    if mode == "block-table":
        nb = max_len // BLOCK
        tables = jnp.asarray(np.random.default_rng(0).permutation(n * nb)
                             .reshape(n, nb), jnp.int32)
        kw = dict(block_tables=tables, token_dst=jnp.where(
            active, tables[slot, pos // BLOCK] * BLOCK + pos % BLOCK,
            n * max_len))

    def logical(tree):
        """Attention leaves as per-slot rows (L, N, S, ...)."""
        if mode == "contiguous":
            return tree

        def view(a):
            blocks = a.reshape((a.shape[0], n * nb, BLOCK) + a.shape[3:])
            return blocks[:, kw["block_tables"]].reshape(a.shape)
        return [{sub: (jax.tree.map(view, c) if sub in attn_subs[gi] else c)
                 for sub, c in group.items()}
                for gi, group in enumerate(tree)]

    logits, new_cache = model.forward_packed(
        cfg, params, stream, cache, slot, pos, wpos, active,
        kv_bucket=kv_bucket, **kw)
    before, after = logical(cache), logical(new_cache)
    ref, ref_caches = _per_request_reference(cfg, params, before, segments,
                                             pre)
    _assert_logits_match(cfg, logits[:, :8], ref)

    written = np.zeros((n, max_len), bool)
    rows = [(int(s), int(p)) for s, p, a in zip(slot, pos, active) if a]
    for s, p in rows:
        written[s, p] = True
    for gi, (pattern, _) in enumerate(cfg.layer_groups()):
        for i, spec in enumerate(pattern):
            sub = f"sub{i}"
            for name, leaf in after[gi][sub].items():
                got = np.asarray(leaf)
                inp = np.asarray(before[gi][sub][name])
                what = (cfg.name, mode, gi, sub, name)
                if spec.mixer == ATTN:
                    np.testing.assert_array_equal(
                        got[:, ~written], inp[:, ~written], err_msg=str(what))
                    for s, p in rows:
                        _assert_close(got[:, s, p],
                                      ref_caches[s][gi][sub][name][:, 0, p],
                                      what + (s, p))
                else:
                    np.testing.assert_array_equal(got[:, 3], inp[:, 3],
                                                  err_msg=str(what))
                    for s, ref_cache in enumerate(ref_caches):
                        _assert_close(got[:, s], ref_cache[gi][sub][name][:, 0],
                                      what + (s,))


def _naive_greedy(cfg, params, prompt, n):
    """Token-by-token greedy decoding by full recomputation, fed and read by
    the engine's rule for multi-codebook frontends: each token repeated
    over the codebooks on input, codebook 0 kept on output."""
    toks, out = list(prompt), []
    for _ in range(n):
        x = jnp.asarray(toks, jnp.int32)[None]
        if cfg.frontend == "audio":
            x = jnp.repeat(x[..., None], cfg.num_codebooks, axis=-1)
        logits, _ = model.forward_full(cfg, params, x)
        t = int(np.asarray(jnp.argmax(logits[0, -1], axis=-1)).flat[0])
        out.append(t)
        toks.append(t)
    return out


def test_engine_packed_matches_naive_greedy(family):
    """End-to-end: the packed single-dispatch step produces the same tokens
    as token-by-token ``forward_full`` greedy decoding, with slot reuse
    (5 requests through 2 slots)."""
    cfg, params = family
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size,
                                 size=int(rng.integers(3, 12))))
               for _ in range(5)]
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=48, discrete_sizes=(16, 8), avg_decode_len=4))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=3))
    done = eng.run()
    assert len(done) == len(prompts)
    for r in done:
        want = _naive_greedy(cfg, params, prompts[r.rid], r.max_new_tokens)
        assert r.output == want, (cfg.name, r.rid, r.output, want)


def test_packed_one_dispatch_one_sync_per_iteration():
    """Acceptance criterion: a packed iteration issues exactly one jitted
    model dispatch and one device→host transfer."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=4, max_len=64, discrete_sizes=(32, 16, 8),
        avg_decode_len=4))
    for i in range(6):
        eng.submit(Request(
            rid=i, prompt=list(rng.integers(0, cfg.vocab_size, size=20)),
            max_new_tokens=4))
    eng.run()
    assert eng.stats.iterations > 0
    assert eng.stats.model_dispatches == eng.stats.iterations
    assert eng.stats.host_syncs == eng.stats.iterations


def test_packed_compile_cache_bounded_and_shapes_discrete():
    """The packed program is keyed only by the bucketed launch length T, so
    the XLA compile cache is bounded by the discrete dense sizes — and every
    launched shape comes from that set."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    sizes = (32, 16, 8)
    eng = ServeEngine(cfg, params, max_slots=4, max_len=64,
                      discrete_sizes=sizes, avg_decode_len=4)
    rng = np.random.default_rng(2)
    for i in range(8):
        eng.submit(Request(
            rid=i,
            prompt=list(rng.integers(0, cfg.vocab_size,
                                     size=int(rng.integers(3, 40)))),
            max_new_tokens=3))
    eng.run()
    # len(sizes) buckets + the max_active floor bucket (decode-only launches)
    assert eng._packed_step._cache_size() <= len(sizes) + 1
    assert set(eng.stats.dense_batch_hist) <= set(sizes)
    assert eng.stats.prefill_expansion == 1.0
    # padding accounted on both sides of the scheduler/engine boundary
    assert eng.stats.packed_pad_tokens == eng.scheduler.padding_tokens
    assert eng.scheduler.launched_tokens >= eng.stats.total_tokens


# ---------------------------------------------------------------------------
# nano-batch interleave ordering
# ---------------------------------------------------------------------------
def test_packed_segment_order_interleave():
    kinds = ["prefill", "decode", "prefill", "decode", "prefill"]
    lengths = [8, 1, 32, 1, 16]
    order = packed_segment_order(kinds, lengths)
    assert [kinds[i] for i in order[:2]] == ["decode", "decode"]
    assert [lengths[i] for i in order[2:]] == [32, 16, 8]


def test_nano_plan_assigns_segments():
    plan = NanoBatchPlan((8, 8))
    assert plan.assign_segments([1, 1, 6, 8]) == (0, 0, 0, 1)


def test_scheduler_pack_accounts_padding():
    from repro.serving.scheduler import GlobalBatchScheduler
    kv = PagedKVManager(total_pages=1024, page_size=16, bytes_per_token=64,
                        avg_decode_len=8)
    sched = GlobalBatchScheduler(kv, discrete_sizes=(16, 8), max_active=8)
    sched.submit(Request(rid=0, prompt=list(range(11)), max_new_tokens=1))
    plan = sched.plan()
    packed = sched.pack(plan)
    assert packed.launch_tokens in (16, 8)
    assert packed.tokens == plan.dense_tokens
    assert packed.padding == packed.launch_tokens - packed.tokens
    assert sched.padding_tokens == packed.padding
    assert sum(packed.nano.sizes) == packed.launch_tokens
    assert len(packed.segment_nano) == len(packed.segments)


# ---------------------------------------------------------------------------
# KV-manager satellites
# ---------------------------------------------------------------------------
def test_upload_failure_keeps_host_blob():
    """Device re-allocation failure must not lose the host KV blob (it used
    to be popped first and silently discarded)."""
    kv = PagedKVManager(total_pages=4, page_size=8, bytes_per_token=64,
                        avg_decode_len=8)
    kv.allocate(1, 32)                        # all 4 pages
    data = np.arange(32, dtype=np.float32)
    kv.offload(1, data)                       # frees pages, blob on host
    kv.allocate(2, 32)                        # device full again
    assert kv.upload(1, np.float32, (32,)) is None
    assert 1 in kv.host_pool                  # blob retained, retryable
    assert kv.stats.discarded_requests == 0
    kv.free(2)
    back = kv.upload(1, np.float32, (32,))
    np.testing.assert_array_equal(back, data)


def test_lru_eviction_counts_discarded_requests():
    kv = PagedKVManager(total_pages=64, page_size=8, bytes_per_token=64,
                        avg_decode_len=8, host_capacity_bytes=1000)
    for rid in range(5):
        kv.allocate(rid, 8)
        kv.offload(rid, np.zeros(100, np.float32))    # 400 B each
    assert kv.stats.discarded_requests > 0
    assert kv.stats.discarded_requests == 5 - len(kv.host_pool)
