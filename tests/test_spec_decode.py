"""Packed speculative decoding (DESIGN.md §13).

Covers the tentpole invariants:
  * greedy spec-decode is f32 **token-exact** vs the plain packed engine
    across GQA and MLA configs, at async depth 0 and 1 and several
    ``spec_k`` — the verify/rollback path may only change *when* tokens
    are produced, never *which*;
  * 1 model dispatch + 1 (deferred) host sync per iteration regardless of
    ``spec_k`` — acceptance, rejection sampling and the cache_len rollback
    all happen inside the single packed program;
  * the compile cache keeps the (|T buckets| + 1) × |kv buckets| bound:
    ``spec_k`` only swaps the decode-only floor bucket for
    ``max_active × (spec_k + 1)``;
  * acceptance accounting (``spec_proposed_tokens`` /
    ``spec_accepted_tokens`` / ``spec_verify_segments``): a drafter that
    replays the known continuation gets near-perfect acceptance and
    finishes in correspondingly fewer iterations;
  * speculation requires attention-only models (recurrent state cannot
    roll back) and composes with cross-request prefix caching (§12);
  * the sampling satellite: ``temperature`` / ``top_k`` serving is
    deterministic and async-depth invariant (per-(slot, pos) PRNG keys),
    and the config surface validates its invariants.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.draft import Drafter, NgramDrafter, make_drafter
from repro.serving.engine import ServeEngine
from repro.serving.request import Request

SIZES = (16, 8)
# GQA (tiny-toy) and MLA (+MoE) — the two attention cache layouts the
# verify segment's scatter/rollback must cover
FAMILIES = ["tiny-toy", "deepseek-v2-236b"]


def _cfg(name):
    cfg = get_config(name) if name == "tiny-toy" else scale_down(
        get_config(name))
    if cfg.moe is not None:
        # dropless so spec and plain runs route identically
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    cfg = _cfg(request.param)
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg):
    """Repetitive motifs (the n-gram drafter's home turf) plus one random
    prompt (acceptance may be ~0 there — correctness must not care)."""
    rng = np.random.default_rng(3)
    motif = [5, 9, 3, 7]
    return [motif * 5, ([2, 4] * 8)[:13],
            list(map(int, rng.integers(0, cfg.vocab_size, size=7)))]


def _run(cfg, params, spec_k, depth, *, max_new=12, drafter=None, slots=2,
         **kw):
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=slots, max_len=96, discrete_sizes=SIZES,
        avg_decode_len=4.0, spec_k=spec_k, async_depth=depth,
        async_harvest=False, **kw))
    if drafter is not None:
        eng.drafter = eng.scheduler.drafter = drafter
    for i, p in enumerate(_prompts(cfg)):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=max_new))
    done = eng.run()
    assert len(done) == 3
    assert eng.in_flight == 0
    return eng, {r.rid: tuple(r.output) for r in done}


# ---------------------------------------------------------------------------
# greedy token-exactness + single-dispatch invariants
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [0, 1])
@pytest.mark.parametrize("spec_k", [2, 4])
def test_greedy_spec_decode_token_exact(family, spec_k, depth):
    cfg, params = family
    _, base = _run(cfg, params, 0, depth)
    eng, out = _run(cfg, params, spec_k, depth)
    assert out == base, (cfg.name, spec_k, depth)
    # still ONE dispatch and ONE (deferred) sync per iteration: the whole
    # verify/accept/rollback path lives inside the packed program
    assert eng.stats.dispatches_per_iter == 1.0
    assert eng.stats.syncs_per_iter == 1.0
    st = eng.stats
    assert st.spec_verify_segments > 0
    assert st.spec_proposed_tokens == st.spec_verify_segments * spec_k
    assert 0 <= st.spec_accepted_tokens <= st.spec_proposed_tokens
    assert st.spec_accepted_per_verify >= 1.0


def test_spec_compile_cache_bound(family):
    """spec_k swaps the decode-only floor bucket (max_active × (spec_k+1))
    into the T grid — still (|T buckets| + 1) × |kv buckets| programs."""
    cfg, params = family
    eng, _ = _run(cfg, params, 3, 1)
    bound = (len(SIZES) + 1) * len(eng.kv_buckets)
    assert eng._packed_step._cache_size() <= bound, \
        (eng._packed_step._cache_size(), bound)


# ---------------------------------------------------------------------------
# acceptance accounting with a known-good drafter
# ---------------------------------------------------------------------------
class _ReplayDrafter:
    """Proposes the continuation of a known target sequence — near-perfect
    acceptance at async depth 0 (the drafter sees fully-committed history),
    so the engine must finish in ~max_new / (spec_k + 1) verify segments."""

    def __init__(self, targets: dict[int, list[int]]):
        self.targets = targets

    def propose(self, req, k):
        tgt = self.targets.get(req.rid, [])
        return tgt[len(req.output):len(req.output) + k]


def test_replay_drafter_acceptance_and_iteration_count(family):
    cfg, params = family
    k, max_new = 3, 12
    e0, base = _run(cfg, params, 0, 0, max_new=max_new)
    replay = _ReplayDrafter({rid: list(out) for rid, out in base.items()})
    assert isinstance(replay, Drafter)   # runtime-checkable protocol
    eng, out = _run(cfg, params, k, 0, max_new=max_new, drafter=replay)
    assert out == base
    st = eng.stats
    # every draft that fit under max_new_tokens was accepted: the only
    # rejections are final-segment tails truncated by the cap
    assert st.spec_acceptance_rate > 0.6, st.spec_acceptance_rate
    assert st.spec_accepted_per_verify > 2.0, st.spec_accepted_per_verify
    # the whole point: far fewer verify segments than plain decode steps
    plain_decode_iters = e0.stats.decode_tokens  # 1 committed token each
    assert st.spec_verify_segments < plain_decode_iters / 2


# ---------------------------------------------------------------------------
# composition + guardrails
# ---------------------------------------------------------------------------
def test_spec_requires_attention_only():
    cfg = dataclasses.replace(scale_down(get_config("xlstm-1.3b")),
                              dtype="float32")
    params = model.init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(AssertionError, match="attention-only"):
        ServeEngine(cfg, params, EngineConfig(max_slots=2, max_len=64,
                                              spec_k=2))


def test_spec_composes_with_prefix_caching():
    """Verify-segment write targets route through the block table on
    device (the host leaves them OOB), so §13 stays token-exact under the
    §12 block-table KV with shared prefixes."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    kw = dict(prefix_caching=True, kv_block_size=8)
    _, base = _run(cfg, params, 0, 1, **kw)
    eng, out = _run(cfg, params, 2, 1, **kw)
    assert out == base
    assert eng.stats.dispatches_per_iter == 1.0
    assert eng.stats.spec_verify_segments > 0


# ---------------------------------------------------------------------------
# stochastic sampling satellite (temperature / top_k)
# ---------------------------------------------------------------------------
def test_stochastic_sampling_deterministic_and_depth_invariant():
    """PRNG keys fold (request id, position) only — never the launch
    index, physical slot, or sampled values — so a temperature/top_k run
    is exactly reproducible and identical at any async depth, even when
    slot-reuse timing differs between depths."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    kw = dict(temperature=0.8, top_k=8)
    _, a = _run(cfg, params, 0, 0, **kw)
    _, b = _run(cfg, params, 0, 0, **kw)
    _, c = _run(cfg, params, 0, 1, **kw)
    assert a == b        # deterministic replay
    assert a == c        # lag-invariant draws (slot reuse shifts, rid wins)
    _, greedy = _run(cfg, params, 0, 0)
    assert a != greedy   # the sampler is actually in the graph


def test_stochastic_spec_decode_token_exact():
    """Sample-and-compare rejection with (rid, pos)-keyed draws: a
    re-verify of a rejected position repeats the same sample, so
    point-mass-drafter speculation commits exactly the plain stochastic
    trajectory (common random numbers) — token-exact beyond greedy."""
    cfg = _cfg("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    kw = dict(temperature=0.8)
    _, base = _run(cfg, params, 0, 1, **kw)
    eng, out = _run(cfg, params, 2, 1, **kw)
    assert out == base
    st = eng.stats
    assert st.spec_proposed_tokens == st.spec_verify_segments * 2
    assert st.spec_accepted_tokens <= st.spec_proposed_tokens


# ---------------------------------------------------------------------------
# config + drafter registry surface
# ---------------------------------------------------------------------------
def test_config_validation():
    with pytest.raises(AssertionError):
        EngineConfig(spec_k=-1)
    with pytest.raises(AssertionError):
        EngineConfig(max_len=8, spec_k=8)
    with pytest.raises(AssertionError):
        EngineConfig(drafter="nope")
    with pytest.raises(AssertionError, match="top_k"):
        EngineConfig(top_k=5)                 # needs temperature > 0
    with pytest.raises(AssertionError):
        EngineConfig(temperature=-0.1)
    assert EngineConfig(spec_k=2).resolved_drafter == "ngram"
    assert EngineConfig().resolved_drafter is None
    assert EngineConfig(drafter="ngram").resolved_drafter is None  # spec off


def test_ngram_drafter_lookup():
    d = make_drafter("ngram")
    assert isinstance(d, NgramDrafter)
    with pytest.raises(ValueError):
        make_drafter("nope")
    # trailing 2-gram (3, 7) recurs -> proposes its continuation
    r = Request(rid=0, prompt=[5, 9, 3, 7, 1, 2, 3, 7], max_new_tokens=4)
    assert d.propose(r, 3) == [1, 2, 3]
    # drafts follow committed *output* too (self-history lookup)
    r2 = Request(rid=1, prompt=[4, 4], max_new_tokens=4)
    r2.output = [8, 6, 4, 4]
    assert d.propose(r2, 2) == [8, 6]
    # no recurrence -> no proposal (scheduler pads; padding gets rejected)
    r3 = Request(rid=2, prompt=[1, 2, 3, 4], max_new_tokens=4)
    assert d.propose(r3, 3) == []
