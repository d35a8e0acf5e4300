"""Incremental chunked prefill (DESIGN.md §7).

Covers the tentpole invariants:
  * chunked == unchunked prefill across every mixer family (GQA attention,
    MLA, Mamba SSM, mLSTM/sLSTM, audio frontend) for chunk sizes below and
    above the conv kernel;
  * the ``forward_full(initial_states=...)`` carry path matches too;
  * linear work: a p-token prompt prefilled in k chunks executes exactly p
    model token-positions;
  * the engine's packed step: greedy-exact vs naive decoding on the
    attention toy, through slot reuse, eager and pipelined, with and without
    prefix caching;
  * the scheduler only emits bucketed chunk lengths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.configs.base import ATTN
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import PagedKVManager
from repro.serving.request import Request
from repro.serving.scheduler import GlobalBatchScheduler

# one arch per mixer family (smoke-scaled): GQA, MLA+MoE, Mamba-hybrid+MoE,
# mLSTM/sLSTM, audio frontend
FAMILIES = ["tiny-toy", "deepseek-v2-236b", "jamba-1.5-large-398b",
            "xlstm-1.3b", "musicgen-medium"]


def _cfg(name):
    cfg = get_config(name) if name == "tiny-toy" else scale_down(
        get_config(name))
    if cfg.moe is not None:
        # dropless so prefill/decode paths route identically (capacity drops
        # legitimately differ between batched shapes)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    return cfg


def _tokens(cfg, key, b, s):
    if cfg.frontend == "audio":
        return jax.random.randint(key, (b, s, cfg.num_codebooks), 0,
                                  cfg.vocab_size)
    return jax.random.randint(key, (b, s), 0, cfg.vocab_size)


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg = _cfg(request.param)
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


# chunk 1 exercises chunks shorter than the conv kernel (d_conv - 1 == 3
# history rows); 5 a ragged split; 12 the unchunked degenerate case
@pytest.mark.parametrize("chunk", [1, 5, 12])
def test_forward_chunk_matches_full(family, chunk):
    cfg, params = family
    b, s = 2, 12
    toks = _tokens(cfg, jax.random.PRNGKey(2), b, s)
    full, _ = model.forward_full(cfg, params, toks)

    cache = model.init_cache(cfg, 1, b, s + 2)
    clen = jnp.zeros((b,), jnp.int32)
    outs, off = [], 0
    while off < s:
        length = min(chunk, s - off)
        lg, cache = model.forward_chunk(cfg, params, toks[:, off:off + length],
                                        cache, clen)
        outs.append(lg)
        off += length
        clen = jnp.full((b,), off, jnp.int32)
    got = jnp.concatenate(outs, axis=1)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - full.astype(jnp.float32)).max())
    scale = float(jnp.abs(full.astype(jnp.float32)).max()) + 1e-6
    assert err <= max(0.02 * scale, 1e-4), (cfg.name, chunk, err, scale)


def test_forward_chunk_then_decode_matches_prefill(family):
    """Decode from a chunk-built cache == decode from the one-shot prefill
    cache (the engine's handoff invariant)."""
    cfg, params = family
    b, s = 2, 10
    toks = _tokens(cfg, jax.random.PRNGKey(3), b, s)

    cache = model.init_cache(cfg, 1, b, s)
    clen = jnp.zeros((b,), jnp.int32)
    off = 0
    while off < s - 1:
        length = min(4, s - 1 - off)
        _, cache = model.forward_chunk(cfg, params, toks[:, off:off + length],
                                       cache, clen)
        off += length
        clen = jnp.full((b,), off, jnp.int32)
    dec_c, _ = model.forward_decode(cfg, params, toks[:, s - 1: s], cache,
                                    clen)

    _, cache_p, clen_p = model.prefill(cfg, params, toks[:, : s - 1],
                                       max_len=s)
    dec_p, _ = model.forward_decode(cfg, params, toks[:, s - 1: s], cache_p,
                                    clen_p)
    err = float(jnp.abs(dec_c.astype(jnp.float32)
                        - dec_p.astype(jnp.float32)).max())
    scale = float(jnp.abs(dec_p.astype(jnp.float32)).max()) + 1e-6
    assert err <= max(0.02 * scale, 1e-4), (cfg.name, err, scale)


def test_forward_full_initial_states_carry(family):
    """The reference (non-bucketed) carry path: chain forward_full chunks
    via initial_states/q_offset, accumulating attention prefixes."""
    cfg, params = family
    b, s, ch = 2, 12, 5
    toks = _tokens(cfg, jax.random.PRNGKey(4), b, s)
    full, _ = model.forward_full(cfg, params, toks)

    outs, states, off = [], None, 0
    while off < s:
        length = min(ch, s - off)
        lg, _aux, new_states = model.forward_full(
            cfg, params, toks[:, off:off + length], q_offset=off,
            initial_states=states, return_states=True)
        outs.append(lg)
        if states is None:
            states = new_states
        else:
            merged = []
            for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
                g = {}
                for i, spec in enumerate(pattern):
                    old = states[gi][f"sub{i}"]
                    new = new_states[gi][f"sub{i}"]
                    if spec.mixer == ATTN:   # prefix KV accumulates
                        g[f"sub{i}"] = {"kv": tuple(
                            jnp.concatenate([o, n], axis=2)
                            for o, n in zip(old["kv"], new["kv"]))}
                    else:                    # recurrent state replaces
                        g[f"sub{i}"] = new
                merged.append(g)
            states = merged
        off += length
    got = jnp.concatenate(outs, axis=1)
    err = float(jnp.abs(got.astype(jnp.float32)
                        - full.astype(jnp.float32)).max())
    scale = float(jnp.abs(full.astype(jnp.float32)).max()) + 1e-6
    assert err <= max(0.02 * scale, 1e-4), (cfg.name, err, scale)


# ---------------------------------------------------------------------------
# engine: linear work + correctness through the jitted bucketed path
# ---------------------------------------------------------------------------
def test_engine_prefill_work_is_linear():
    """Acceptance criterion: a 512-token prompt prefilled in 64-token chunks
    executes exactly 512 model token-positions — the same count as one
    unchunked prefill."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = list(rng.integers(0, cfg.vocab_size, size=512))
    eng = ServeEngine(cfg, params, max_slots=2, max_len=520,
                      discrete_sizes=(64,), avg_decode_len=2)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
    done = eng.run()
    assert len(done) == 1
    st = eng.stats
    assert st.prefill_tokens == 512
    assert st.prefill_model_tokens == 512          # == one unchunked prefill
    assert st.prefill_expansion == 1.0
    # and it really was chunked: 512/64 prefill iterations at least
    assert st.iterations >= 8


@pytest.mark.parametrize("prefix_caching", [False, True],
                         ids=["no-prefix", "prefix"])
@pytest.mark.parametrize("async_depth", [0, 1])
def test_engine_incremental_matches_naive_greedy(async_depth, prefix_caching):
    """End-to-end: jitted bucketed chunked prefill + decode == token-by-token
    full recomputation (attention toy; exact argmax equality), eager and
    pipelined.  With prefix caching the prompts share a 20-token prefix, so
    later requests attend a 16-token block an earlier request wrote."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size,
                                 size=int(rng.integers(3, 20))))
               for _ in range(5)]
    if prefix_caching:
        shared = list(rng.integers(0, cfg.vocab_size, size=20))
        prompts = [shared + p for p in prompts]
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, discrete_sizes=(8,), avg_decode_len=4,
        async_depth=async_depth, prefix_caching=prefix_caching))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=4))
    done = eng.run()
    assert len(done) == len(prompts)
    assert eng.stats.prefill_expansion == 1.0
    if prefix_caching:
        assert eng.kv.stats.prefix_hit_tokens > 0
    for r in done:
        toks = list(prompts[r.rid])
        want = []
        for _ in range(r.max_new_tokens):
            logits, _ = model.forward_full(
                cfg, params, jnp.asarray(toks, jnp.int32)[None])
            t = int(np.argmax(np.asarray(logits[0, -1])))
            want.append(t)
            toks.append(t)
        assert r.output == want, (r.rid, r.output, want)


@pytest.mark.parametrize("variant", ["flash_attention_ref",
                                     "flash_attention_fast",
                                     "flash_attention_stream"])
def test_ref_attention_per_row_q_offset(variant):
    """The ref kernels accept per-row (B,) q_offsets (different slots sit at
    different prefix depths) — equal to row-by-row scalar offsets."""
    from repro.kernels import ref
    fn = getattr(ref, variant)
    rng = np.random.default_rng(0)
    b, sq, skv, h, kv, d = 3, 4, 16, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, skv, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, skv, kv, d)), jnp.float32)
    offs = jnp.asarray([0, 3, 7], jnp.int32)
    batched = fn(q, k, v, causal=True, q_offset=offs)
    rows = [fn(q[i:i + 1], k[i:i + 1], v[i:i + 1], causal=True,
               q_offset=int(offs[i])) for i in range(b)]
    np.testing.assert_allclose(np.asarray(batched),
                               np.asarray(jnp.concatenate(rows)), atol=1e-6)


# the second size set has its smallest discrete size above the default
# prefill_chunk_min — the scheduler must still keep every non-terminal chunk
# bucketed (chunk_min is floored at the smallest size)
@pytest.mark.parametrize("sizes", [(64, 32, 16, 8), (64, 32, 16)])
def test_scheduler_quantizes_chunk_lengths(sizes):
    """Chunk lengths come from the discrete set (plus exact sub-minimum
    terminal remainders), bounding the jit compile cache."""
    kv = PagedKVManager(total_pages=1024, page_size=16, bytes_per_token=64,
                        avg_decode_len=8)
    sched = GlobalBatchScheduler(kv, discrete_sizes=sizes, max_active=8)
    rng = np.random.default_rng(2)
    for i in range(6):
        sched.submit(Request(rid=i,
                             prompt=list(range(int(rng.integers(3, 150)))),
                             max_new_tokens=1))
    seen = set()
    for _ in range(100):
        plan = sched.plan()
        if plan is None:
            break
        assert plan.dense_tokens <= plan.dense_batch
        sampled = {}
        for c in plan.prefill:
            seen.add(c.length)
            # bucketed, or a terminal remainder below the smallest size
            assert c.length in sizes or (
                c.length < min(sizes)
                and c.offset + c.length == c.req.prompt_len), c.length
            if c.offset + c.length == c.req.prompt_len:
                sampled[c.req.rid] = 0
        for r in plan.decode:
            sampled[r.rid] = 0
        sched.commit(plan, sampled, 0.0)
    assert seen, "no prefill chunks emitted"
