"""int8 KV-cache serving, engine end-to-end (DESIGN.md §15).

The quantized engine must be a drop-in: same packed step (1 dispatch +
1 sync per iteration, same compile-cache bound), same scheduler, same
block-table/prefix/spec-decode machinery — only the attention cache leaves
change (int8 values + f32 per-(row, kv-head) scales).  Covered here:

  * the int8 engine serves the native engine's short-horizon mixed
    workload to the same output lengths, and along the sequences the
    native engine served its teacher-forced logits stay within the drift
    bound (f32 configs; exact tokens are not compared — int8 rounding can
    flip a near-tie of random-init weights), GQA and absorbed-MLA
    families, async depth 0 and 1;
  * teacher-forced logit drift vs the native cache stays under the
    per-family bound;
  * a fixed ``kv_budget_bytes`` admits ~2x the pages (>= 1.9x at
    head_dim 128 — the acceptance criterion);
  * composition with prefix caching and speculative decoding;
  * the ``kv_quant_bytes_saved`` counter and config validation.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine, kv_bytes_per_token
from repro.serving.request import Request

SIZES = (16, 8)
FAMILIES = ["tiny-toy", "deepseek-v2-236b"]      # GQA / absorbed MLA (+MoE)
# max teacher-forced logit drift vs the native cache (f32 toy weights;
# symmetric int8 rounds each K/V row to ~0.4% of its max)
DRIFT_BOUND = {"tiny-toy": 0.08, "deepseek-v2-236b": 0.08}


def _cfg(name):
    cfg = get_config(name) if name == "tiny-toy" else scale_down(
        get_config(name))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg = _cfg(request.param)
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg):
    rng = np.random.default_rng(1)
    return [list(map(int, rng.integers(0, cfg.vocab_size, size=n)))
            for n in [3, 11, 5, 9, 4]]


def _run(cfg, params, kv_dtype, depth, **kw):
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=48, kv_block_size=8, discrete_sizes=SIZES,
        avg_decode_len=4.0, async_depth=depth, kv_dtype=kv_dtype, **kw))
    for i, prompt in enumerate(_prompts(cfg)):
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=4))
    done = eng.run()
    assert len(done) == 5
    return eng, {r.rid: tuple(r.output) for r in done}


def _drift(cfg, params, tokens) -> float:
    """Max |logit| gap of a teacher-forced packed forward over ``tokens``,
    native vs int8 cache: same tokens, same positions — the only
    difference is cache quantization."""
    t = len(tokens)
    tok = jnp.asarray(tokens, jnp.int32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)
    slot = jnp.zeros(t, jnp.int32)
    act = jnp.ones(t, jnp.int32)
    outs = {}
    for kd in (None, "int8"):
        cache = model.init_cache(cfg, 1, 2, 48, kd)
        logits, _ = model.forward_packed(cfg, params, tok, cache,
                                         slot, pos, pos, act, kv_bucket=48)
        outs[kd] = np.asarray(logits, np.float32)[0]
    return float(np.abs(outs[None] - outs["int8"]).max())


@pytest.mark.parametrize("depth", [0, 1])
def test_int8_greedy_token_match(family, depth):
    cfg, params = family
    e_bf, out_bf = _run(cfg, params, "bf16", depth)
    e_i8, out_i8 = _run(cfg, params, "int8", depth)
    # same requests served to the same lengths
    assert {k: len(v) for k, v in out_bf.items()} == \
        {k: len(v) for k, v in out_i8.items()}, (cfg.name, depth)
    # along every sequence the native engine served, the int8 cache moves
    # the logits by no more than the quantization bound
    bound = DRIFT_BOUND[cfg.name.replace("-smoke", "")]
    for rid, prompt in enumerate(_prompts(cfg)):
        drift = _drift(cfg, params, prompt + list(out_bf[rid]))
        assert drift < bound, (cfg.name, depth, rid, drift)
    # still the single-dispatch packed step with a bounded compile cache
    assert e_i8.stats.dispatches_per_iter == 1.0
    assert e_i8.stats.syncs_per_iter == 1.0
    bound = (len(SIZES) + 1) * len(e_i8.kv_buckets)
    assert e_i8._packed_step._cache_size() <= bound
    # counters: quantized run banked real bytes, native run none
    assert e_i8.stats.kv_quant_bytes_saved > 0
    assert e_bf.stats.kv_quant_bytes_saved == 0
    assert e_i8.stats.snapshot()["kv_quant_bytes_saved"] > 0


def test_int8_logit_drift_bound(family):
    """Teacher-forced packed forward, native vs int8 cache, on a fixed
    16-token prompt."""
    cfg, params = family
    prompt = [int(x) for x in np.arange(1, 17) % cfg.vocab_size]
    drift = _drift(cfg, params, prompt)
    assert drift < DRIFT_BOUND[cfg.name.replace("-smoke", "")], \
        (cfg.name, drift)


def test_int8_doubles_admitted_pages_at_fixed_budget():
    """Acceptance criterion: at the same ``kv_budget_bytes`` the int8
    engine admits >= 1.9x the pages (head_dim 128: the f32 scale adds
    4/128 B per element to the 1 B int8 value)."""
    cfg = dataclasses.replace(get_config("tiny-toy"), head_dim=128)
    assert cfg.dtype == "bfloat16"
    params = model.init(cfg, jax.random.PRNGKey(0))
    budget = kv_bytes_per_token(cfg) * 8 * 16    # 16 native pages of 8 rows
    engines = {}
    for kd in ("bf16", "int8"):
        engines[kd] = ServeEngine(cfg, params, EngineConfig(
            max_slots=4, max_len=64, kv_block_size=8, discrete_sizes=SIZES,
            avg_decode_len=4.0, kv_budget_bytes=budget, kv_dtype=kd))
    n_bf = engines["bf16"].kv.stats.device_pages_total
    n_i8 = engines["int8"].kv.stats.device_pages_total
    assert n_i8 >= 1.9 * n_bf, (n_bf, n_i8)
    # the rate the pool charges per token is the quantized one
    assert engines["int8"].kv.bytes_per_token == kv_bytes_per_token(
        cfg, "int8")
    assert engines["int8"].kv.bytes_per_token < \
        0.52 * engines["bf16"].kv.bytes_per_token


@pytest.mark.parametrize("depth", [0, 1])
def test_int8_composes_with_prefix_caching(depth):
    """Prefix caching shares int8 blocks byte-identically (hashes stay over
    token ids; CoW copies move (values, scales) pairs), so shared-prefix
    serving is token-exact vs the unshared int8 engine."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    base = list(range(11, 21))

    def serve(prefix):
        eng = ServeEngine(cfg, params, EngineConfig(
            max_slots=4, max_len=64, kv_block_size=8, discrete_sizes=SIZES,
            avg_decode_len=4.0, async_depth=depth, prefix_caching=prefix,
            kv_dtype="int8"))
        outs = {}
        for wave in ([(0, base + [30])],
                     [(i, base + [30 + i]) for i in range(1, 4)]):
            for rid, prompt in wave:
                eng.submit(Request(rid=rid, prompt=list(prompt),
                                   max_new_tokens=6))
            for r in eng.run():
                outs[r.rid] = tuple(r.output)
        return eng, outs

    _, out_np = serve(False)
    eng, out_pc = serve(True)
    assert out_np == out_pc
    assert eng.kv.stats.prefix_hit_tokens == 30  # 3 requests x 10 tokens
    assert eng.kv.stats.cow_copies == 3
    assert eng.stats.dispatches_per_iter == 1.0


def test_int8_composes_with_spec_decode():
    """Speculative decoding's accept/rollback chain operates on positions,
    not bytes — rejected int8 rows (values + scales) just stay unattended —
    so spec_k > 0 keeps greedy exactness vs the plain int8 engine."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))

    def serve(spec_k):
        eng = ServeEngine(cfg, params, EngineConfig(
            max_slots=2, max_len=64, kv_block_size=8, discrete_sizes=(24, 8),
            avg_decode_len=6.0, spec_k=spec_k, kv_dtype="int8"))
        rng = np.random.default_rng(1)
        for i in range(3):
            eng.submit(Request(
                rid=i, prompt=list(map(int, rng.integers(
                    0, cfg.vocab_size, size=int(rng.integers(3, 10))))),
                max_new_tokens=6))
        done = eng.run()
        return eng, {r.rid: tuple(r.output) for r in done}

    _, out0 = serve(0)
    eng, out2 = serve(2)
    assert out0 == out2
    assert eng.stats.spec_verify_segments > 0
    assert eng.stats.dispatches_per_iter == 1.0


def test_int8_kv_dtype_validation():
    with pytest.raises(AssertionError):
        EngineConfig(kv_dtype="fp8")             # unknown dtype tag
    assert EngineConfig(kv_dtype="int8").kv_dtype == "int8"
