"""Serving-engine tests: end-to-end correctness vs naive decoding, scheduler
invariants (hypothesis), KV manager accounting, async EOS semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.configs import get_config
from repro.models import model
from repro.serving.config import EngineConfig
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import PagedKVManager
from repro.serving.request import Request
from repro.serving.scheduler import GlobalBatchScheduler


@pytest.fixture(scope="module")
def toy():
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_matches_naive_greedy(toy):
    cfg, params = toy
    eng = ServeEngine(cfg, params, max_slots=4, max_len=64,
                      discrete_sizes=(32, 16, 8), avg_decode_len=6)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=list(rng.integers(0, cfg.vocab_size,
                                             size=int(rng.integers(3, 14)))),
                    max_new_tokens=5) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(reqs)
    for r in done[:3]:
        toks = list(r.prompt)
        want = []
        for _ in range(r.max_new_tokens):
            logits, _ = model.forward_full(
                cfg, params, jnp.asarray(toks, jnp.int32)[None])
            t = int(np.argmax(np.asarray(logits[0, -1])))
            want.append(t)
            toks.append(t)
        assert r.output == want, (r.rid, r.output, want)


def test_async_eos_one_extra_iteration(toy):
    """EOS acts one iteration late (§5.3) and the post-EOS token is
    stripped from the final output."""
    cfg, params = toy
    # find what token the model emits first for some prompt, use it as EOS
    prompt = [5, 9, 11]
    logits, _ = model.forward_full(cfg, params,
                                   jnp.asarray(prompt, jnp.int32)[None])
    eos = int(np.argmax(np.asarray(logits[0, -1])))
    eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                      discrete_sizes=(16, 8), avg_decode_len=4)
    r = Request(rid=0, prompt=prompt, max_new_tokens=6, eos_id=eos)
    eng.submit(r)
    done = eng.run()
    assert len(done) == 1
    assert done[0].output[-1] == eos          # stripped to the EOS token
    assert len(done[0].output) <= 2           # EOS first or second token
    # decode_tokens counts the extra post-EOS token (paper's <1% overhead)
    assert eng.stats.decode_tokens >= len(done[0].output)


def test_discrete_batching_only_emits_configured_sizes(toy):
    cfg, params = toy
    sizes = (16, 8)
    eng = ServeEngine(cfg, params, max_slots=4, max_len=64,
                      discrete_sizes=sizes, avg_decode_len=4)
    rng = np.random.default_rng(1)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, 64, size=11)),
                           max_new_tokens=4))
    eng.run()
    assert set(eng.stats.dense_batch_hist) <= set(sizes)


# ---------------------------------------------------------------------------
# KV manager properties
# ---------------------------------------------------------------------------
@given(tokens=st.lists(st.integers(1, 300), min_size=1, max_size=20))
@settings(max_examples=50, deadline=None)
def test_kv_allocation_never_exceeds_pool(tokens):
    kv = PagedKVManager(total_pages=64, page_size=16, bytes_per_token=128,
                        avg_decode_len=32)
    live = []
    for i, t in enumerate(tokens):
        if kv.allocate(i, t):
            live.append(i)
        assert kv.pages_used <= 64
        assert kv.pages_used + kv.pages_free == 64
    for i in live:
        kv.free(i)
    assert kv.pages_used == 0 and kv.pages_free == 64


@given(st.data())
@settings(max_examples=30, deadline=None)
def test_peak_estimator_is_admission_safe(data):
    """If the estimator admits, actually growing every request to its
    predicted end never exceeds the pool (no-eviction invariant, §4.4)."""
    kv = PagedKVManager(total_pages=48, page_size=8, bytes_per_token=64,
                        avg_decode_len=16)
    reqs = []
    for i in range(data.draw(st.integers(1, 8))):
        p = data.draw(st.integers(1, 60))
        m = data.draw(st.integers(1, 40))
        r = Request(rid=i, prompt=list(range(p)), max_new_tokens=m)
        if kv.can_admit(r, reqs) and kv.allocate(i, p):
            reqs.append(r)
    # simulate worst-case growth to predicted lengths
    grown = [r.predicted_final_len(kv.avg_decode_len) for r in reqs]
    finish = sorted(range(len(reqs)), key=lambda j: grown[j] - reqs[j].prompt_len)
    alive = set(range(len(reqs)))
    for t in sorted(set(grown[j] - reqs[j].prompt_len for j in finish)) or [0]:
        demand = sum(kv.pages_for(min(reqs[j].prompt_len + t, grown[j]))
                     for j in alive)
        assert demand <= kv.stats.device_pages_total
        for j in list(alive):
            if grown[j] - reqs[j].prompt_len <= t:
                alive.discard(j)


def test_offload_upload_roundtrip():
    kv = PagedKVManager(total_pages=32, page_size=8, bytes_per_token=64,
                        avg_decode_len=8, host_capacity_bytes=1 << 20)
    kv.allocate(1, 40)
    data = np.arange(40 * 16, dtype=np.float32).reshape(40, 16)
    kv.offload(1, data)
    assert kv.pages_used == 0
    assert kv.stats.offload_bytes == data.nbytes
    back = kv.upload(1, np.float32, (40, 16))
    np.testing.assert_array_equal(back, data)
    assert kv.stats.upload_bytes == data.nbytes
    assert kv.pages_used == kv.pages_for(40)


def test_host_pool_lru_eviction():
    kv = PagedKVManager(total_pages=64, page_size=8, bytes_per_token=64,
                        avg_decode_len=8, host_capacity_bytes=1000)
    for rid in range(5):
        kv.allocate(rid, 8)
        kv.offload(rid, np.zeros(100, np.float32))   # 400 B each
    assert kv.stats.host_bytes <= 1000
    assert kv.upload(0, np.float32, (100,)) is None  # LRU-evicted
    assert kv.upload(4, np.float32, (100,)) is not None


def test_scheduler_admission_respects_capacity():
    kv = PagedKVManager(total_pages=8, page_size=8, bytes_per_token=64,
                        avg_decode_len=64)
    sched = GlobalBatchScheduler(kv, discrete_sizes=(16, 8), max_active=16)
    for i in range(10):
        sched.submit(Request(rid=i, prompt=list(range(16)),
                             max_new_tokens=48))
    plan = sched.plan()
    assert plan is not None
    assert sched.n_active < 10           # capacity-bounded admission
    assert kv.pages_used <= kv.stats.device_pages_total


def test_compile_hist_counts_each_launch_key_once(toy):
    """A packed launch at a new (launch tokens, KV bucket) compiles once and
    is charged to that key; a second launch at the same key adds nothing,
    and throughput leaves the compile's seconds out of the wall time."""
    cfg, params = toy
    eng = ServeEngine(cfg, params, EngineConfig(
        max_slots=2, max_len=32, discrete_sizes=(8,), avg_decode_len=4.0))
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=1))
    eng.run()
    s = eng.stats
    assert s.iterations == 1 and s.compiles == 1
    (key, n), = s.compile_hist.items()
    assert n == 1 and key[1] in s.kv_bucket_hist and s.compile_time > 0
    assert s.throughput == pytest.approx(
        s.total_tokens / (s.wall_time - s.compile_time))
    eng.submit(Request(rid=1, prompt=[4, 5, 6], max_new_tokens=1))
    eng.run()
    assert s.iterations == 2 and s.compiles == 1
    assert s.compile_hist == {key: 1}
