"""Packed-attention kernel + KV-length bucketing (DESIGN.md §9).

Covers the tentpole invariants:
  * Pallas (interpret=True) packed attention == the XLA ref across GQA and
    absorbed-MLA shapes (incl. ``d_v != d_qk``), f32 and bf16, on random
    packings and on engine-shaped launches that exercise the query-tile
    plan (``LAUNCHES``);
  * ``kv_blocks_fetched`` counts the KV block DMAs the clamped index maps
    issue;
  * ``ops.packed_attention`` dispatches ``impl`` for real — the MLA
    ``d_v != d_qk`` case runs the Pallas kernel, no silent ref downgrade;
  * kv-bucket slicing is exact at and around bucket boundaries, in the ref,
    the kernel, and the scheduler's quantizer;
  * engine end-to-end: kv-bucketed packed step == dense max_len sweep
    (f32 per the known bf16-nondeterminism constraint), with a request
    crossing a bucket edge mid-decode;
  * the packed compile cache is bounded by |T buckets| × |kv buckets| and
    the kv-bucket histogram records what launched;
  * the §Perf-HC3 env toggles are now explicit engine arguments (env is
    only the construction-time fallback — no trace-time env reads).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scale_down
from repro.kernels import ops, ref
from repro.kernels import packed_attention as pa
from repro.models import model
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import PagedKVManager
from repro.serving.request import Request
from repro.serving.scheduler import GlobalBatchScheduler, default_kv_buckets

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=2e-5)


# engine-shaped launches, new cases of the parity tests below where ``t``
# names one: name -> (packing, tokens per query tile, block-table block size)
LAUNCHES = {
    # decodes in slots 1.., a prefill chunk in slot 0 whose lengths rise
    # with position, then a pad tail in slot 0 with length 1
    "engine-bq8": ("engine", 8, None),
    "shuffled-bq8": ("shuffled", 8, None),      # the same, packed at random
    "engine-bq64": ("engine", 64, None),        # T < Bq
    "runs-bq4": ("runs", 4, None),              # worst case: T one-token runs
    "engine-bq8-bt8": ("engine", 8, 8),         # block-table mode
    "shuffled-bq8-bt8": ("shuffled", 8, 8),
}


def _launch(layout, n, s):
    """(token_slot, lengths) packed as the engine packs an iteration."""
    if layout == "runs":
        return np.arange(n), RNG.integers(1, s + 1, size=n)
    c = s // 4
    off = int(RNG.integers(0, s - c + 1))
    slot = np.r_[np.arange(1, n), np.zeros(c + 8, int)]
    lens = np.r_[RNG.integers(s // 2, s + 1, size=n - 1),
                 off + np.arange(1, c + 1), np.ones(8, int)]
    if layout == "shuffled":
        order = RNG.permutation(slot.size)
        slot, lens = slot[order], lens[order]
    return slot, lens


def _case(t, n, s, h, kv, d_qk, d_v, dtype):
    if isinstance(t, str):
        slot, lens = _launch(LAUNCHES[t][0], n, s)
        t = slot.size
    else:
        slot = RNG.integers(0, n, size=t)
        lens = RNG.integers(1, s + 1, size=t)
    q = jnp.asarray(RNG.normal(size=(t, h, d_qk)), dtype)
    k = jnp.asarray(RNG.normal(size=(n, s, kv, d_qk)), dtype)
    v = jnp.asarray(RNG.normal(size=(n, s, kv, d_v)), dtype)
    return (q, k, v, jnp.asarray(slot, jnp.int32),
            jnp.asarray(lens, jnp.int32))


def _tiling(t):
    """Kernel keywords of launch ``t``: tokens per tile; for a block-table
    launch, the table (applied to the caches by ``_to_blocks``)."""
    if not isinstance(t, str):
        return {}, None
    _, bq, bs = LAUNCHES[t]
    return {"block_q": bq}, bs


def _to_blocks(n, s, bs, *caches):
    """Scatter each slot's logical blocks of ``bs`` rows into a permuted
    physical row space; returns the table and the physical caches."""
    nb = s // bs
    perm = RNG.permutation(n * nb)

    def phys(x):
        flat = x.reshape(n * nb, bs, *x.shape[2:])
        return jnp.zeros_like(flat).at[perm].set(flat).reshape(x.shape)
    return (jnp.asarray(perm.reshape(n, nb), jnp.int32),
            *[phys(x) for x in caches])


# ---------------------------------------------------------------------------
# kernel parity: pallas-interpret vs XLA ref
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,n,s,h,kv,d_qk,d_v", [
    (10, 3, 64, 4, 2, 32, 32),       # GQA
    (7, 2, 48, 8, 8, 16, 16),        # MHA
    (5, 4, 40, 4, 1, 16, 16),        # MQA, ragged S
    ("engine-bq8", 3, 64, 4, 2, 32, 32),
    ("shuffled-bq8", 3, 64, 4, 2, 32, 32),
    ("engine-bq64", 3, 64, 4, 2, 32, 32),
    ("runs-bq4", 6, 40, 4, 2, 16, 16),          # ragged S
    ("engine-bq8", 3, 40, 4, 1, 24, 16),        # absorbed MLA, ragged S
    ("engine-bq8-bt8", 3, 64, 4, 2, 32, 32),
    ("shuffled-bq8-bt8", 4, 48, 4, 1, 24, 16),  # MLA in block-table mode
])
def test_packed_attention_parity_gqa(t, n, s, h, kv, d_qk, d_v, dtype):
    q, k, v, slot, lens = _case(t, n, s, h, kv, d_qk, d_v, dtype)
    kw, bs = _tiling(t)
    want = ref.packed_attention_ref(q, k, v, slot, lens)
    if bs is not None:
        kw["block_tables"], k, v = _to_blocks(n, s, bs, k, v)
    out = pa.packed_attention(q, k, v, slot, lens, block_k=16, interpret=True,
                              **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_attention_parity_mla_dv_neq_dqk(dtype):
    """Absorbed MLA attends with d_qk = rank + rope but d_v = rank — the
    kernel must handle the mismatch (it used to silently fall back)."""
    t, n, s, h, d_qk, d_v = 6, 3, 48, 4, 24, 16
    q, k, v, slot, lens = _case(t, n, s, h, 1, d_qk, d_v, dtype)
    scale = d_qk ** -0.5
    out = pa.packed_attention(q, k, v, slot, lens, logit_scale=scale,
                              block_k=16, interpret=True)
    want = ref.packed_attention_ref(q, k, v, slot, lens, logit_scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_ops_dispatch_is_real(monkeypatch):
    """``ops.packed_attention(impl=...)`` routes to the Pallas kernel —
    including the ``d_v != d_qk`` case — instead of discarding ``impl``."""
    calls = []
    real = pa.packed_attention

    def spy(*args, **kwargs):
        calls.append(kwargs.get("interpret"))
        return real(*args, **kwargs)

    monkeypatch.setattr(pa, "packed_attention", spy)
    q, k, v, slot, lens = _case(5, 2, 32, 4, 1, 24, 16, jnp.float32)
    scale = 24 ** -0.5
    got = ops.packed_attention(q, k, v, slot, lens, logit_scale=scale,
                               impl="interpret")
    assert calls == [True]
    want = ops.packed_attention(q, k, v, slot, lens, logit_scale=scale,
                                impl="ref")
    assert calls == [True]                      # ref path never touches pa
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# int8 KV: in-register dequant parity (DESIGN.md §15)
# ---------------------------------------------------------------------------
def _quantize(k, v):
    from repro.serving import kvquant
    kq, ks = kvquant.quantize_kv(k)
    vq, vs = kvquant.quantize_kv(v)
    return kq, ks, vq, vs


@pytest.mark.parametrize("t,n,s,h,kv,d_qk,d_v", [
    (10, 3, 64, 4, 2, 32, 32),       # GQA
    (6, 3, 48, 4, 1, 24, 16),        # absorbed MLA: d_v != d_qk
    ("engine-bq8", 3, 64, 4, 2, 32, 32),
    ("shuffled-bq8-bt8", 3, 64, 4, 2, 32, 32),  # int8 + block table
    ("runs-bq4", 6, 48, 4, 1, 24, 16),          # absorbed MLA
    (10, 3, 64, 8, 4, 32, 32),       # 4 kv heads: one int8 of each word
    ("engine-bq8", 3, 64, 16, 8, 32, 32),       # 8: every other word
])
def test_packed_attention_int8_parity(t, n, s, h, kv, d_qk, d_v):
    """Pallas kernel with int8 k/v + f32 scale tiles == ref dequant path
    (tight tol: both dequantize the same stored values), and both stay
    within the quantization-noise band of the unquantized oracle."""
    q, k, v, slot, lens = _case(t, n, s, h, kv, d_qk, d_v, jnp.float32)
    kq, ks, vq, vs = _quantize(k, v)
    scale = d_qk ** -0.5
    want = ref.packed_attention_ref(q, kq, vq, slot, lens, k_scale=ks,
                                    v_scale=vs, logit_scale=scale)
    kw, bs = _tiling(t)
    caches = (kq, vq, ks, vs)
    if bs is not None:
        kw["block_tables"], *caches = _to_blocks(n, s, bs, *caches)
    kq_p, vq_p, ks_p, vs_p = caches
    out = pa.packed_attention(q, kq_p, vq_p, slot, lens, k_scale=ks_p,
                              v_scale=vs_p, logit_scale=scale, block_k=16,
                              interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=2e-5)
    exact = ref.packed_attention_ref(q, k, v, slot, lens, logit_scale=scale)
    err = float(jnp.abs(out - exact).max())
    assert err < 0.05 * float(jnp.abs(exact).max()) + 1e-6, err


def test_packed_attention_int8_kv_bucket():
    """Scale tiles ride the same kv_bucket slice as the values."""
    t, n, s, h, kv, d = 8, 3, 64, 4, 2, 16
    q, k, v, slot, _ = _case(t, n, s, h, kv, d, d, jnp.float32)
    lens = jnp.asarray(RNG.integers(1, 33, size=t), jnp.int32)
    kq, ks, vq, vs = _quantize(k, v)
    full = ref.packed_attention_ref(q, kq, vq, slot, lens,
                                    k_scale=ks, v_scale=vs)
    for impl_kw in (dict(), dict(kv_bucket=32)):
        got = pa.packed_attention(q, kq, vq, slot, lens, k_scale=ks,
                                  v_scale=vs, block_k=16, interpret=True,
                                  **impl_kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=2e-5)


def test_packed_attention_int8_block_tables():
    """Block-table mode: the scale tiles dereference the same physical
    block ids as the int8 value tiles (non-identity permutation)."""
    t, n, s, h, kv, d, bs = 6, 3, 32, 4, 2, 16, 8
    nb = s // bs
    q, k, v, slot, lens = _case(t, n, s, h, kv, d, d, jnp.float32)
    kq, ks, vq, vs = _quantize(k, v)
    # scatter logical blocks into a permuted physical row space
    perm = RNG.permutation(n * nb)
    tables = jnp.asarray(perm.reshape(n, nb), jnp.int32)
    flat = lambda x: x.reshape(n * nb, bs, *x.shape[2:])
    phys = lambda x: jnp.zeros_like(flat(x)).at[perm].set(flat(x)) \
        .reshape(x.shape)
    kq_p, vq_p, ks_p, vs_p = phys(kq), phys(vq), phys(ks), phys(vs)
    want = ref.packed_attention_ref(q, kq, vq, slot, lens,
                                    k_scale=ks, v_scale=vs)
    got_ref = ref.packed_attention_ref(q, kq_p, vq_p, slot, lens,
                                       block_tables=tables,
                                       k_scale=ks_p, v_scale=vs_p)
    got_pal = pa.packed_attention(q, kq_p, vq_p, slot, lens,
                                  block_tables=tables, k_scale=ks_p,
                                  v_scale=vs_p, interpret=True)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_pal), np.asarray(want),
                               rtol=1e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the model step's stacked cache, read in place (DESIGN.md §8-§9)
# ---------------------------------------------------------------------------
# name -> (int8 KV, block-table block size, kv_bucket); block_k is 16
STACKED = {
    "contiguous": (False, None, 64),
    "contiguous-ragged-bucket": (False, None, 40),   # not a multiple of 16
    "int8": (True, None, 64),
    "block-table": (False, 8, 48),
    "int8-block-table": (True, 8, 64),
}


def _stack(leaf, poison):
    """(3, ...) stack with ``leaf`` as layer 1 and ``poison`` elsewhere."""
    other = jnp.full_like(leaf, poison)
    return jnp.stack([other, leaf, other])


@pytest.mark.parametrize("variant", list(STACKED))
def test_packed_attention_reads_one_layer_of_the_stack(variant):
    """An (L=3, N, S, KV, D) stack read at layer 1 (traced) equals the ref
    on layer 1's 4-D slice.  Layers 0 and 2 are NaN (int8 leaves: NaN
    scales), and so are layer 1's rows past a contiguous bucket: a read of
    any of them would reach the output."""
    int8, bs, bucket = STACKED[variant]
    t, n, s, h, kv, d = 12, 3, 64, 4, 2, 16
    q, k, v, slot, _ = _case(t, n, s, h, kv, d, d, jnp.float32)
    lens = jnp.asarray(RNG.integers(1, bucket + 1, size=t), jnp.int32)
    leaves = list(_quantize(k, v)) if int8 else [k, v]   # k, [k_s,] v[, v_s]
    kw = {}
    if bs is not None:
        kw["block_tables"], *leaves = _to_blocks(n, s, bs, *leaves)
    if int8:
        kq, ks, vq, vs = leaves
        want = ref.packed_attention_ref(q, kq, vq, slot, lens, k_scale=ks,
                                        v_scale=vs, kv_bucket=bucket, **kw)
        stacks = [_stack(kq, 0), _stack(ks, np.nan), _stack(vq, 0),
                  _stack(vs, np.nan)]
    else:
        want = ref.packed_attention_ref(q, *leaves, slot, lens,
                                        kv_bucket=bucket, **kw)
        stacks = [_stack(x, np.nan) for x in leaves]
    if bs is None:                       # poison layer 1 past the bucket
        stacks = [x.at[1, :, bucket:].set(np.nan)
                  if x.dtype == jnp.float32 else x for x in stacks]
    if int8:
        k_st, ks_st, v_st, vs_st = stacks
        kw.update(k_scale=ks_st, v_scale=vs_st)
    else:
        k_st, v_st = stacks
    layer = jnp.int32(1)
    got = pa.packed_attention(q, k_st, v_st, slot, lens, layer=layer,
                              kv_bucket=bucket, block_k=16, interpret=True,
                              **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2e-5)
    got_ref = ops.packed_attention(q, k_st, v_st, slot, lens, layer=layer,
                                   kv_bucket=bucket, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# pad-free ragged last block (DESIGN.md §15): s % block_k != 0 masks the
# final tile in-kernel instead of jnp.pad-ing a copy of the whole cache
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,block_k", [(40, 16), (33, 16), (24, 16), (7, 8)])
def test_packed_attention_ragged_last_block(s, block_k):
    t, n, h, kv, d = 8, 3, 4, 2, 16
    q, k, v, slot, lens = _case(t, n, s, h, kv, d, d, jnp.float32)
    out = pa.packed_attention(q, k, v, slot, lens, block_k=block_k,
                              interpret=True)
    want = ref.packed_attention_ref(q, k, v, slot, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=2e-5)


def test_packed_attention_no_cache_pad(monkeypatch):
    """The hot path never materializes a padded copy of the K/V caches.
    (Pallas *interpret mode* pads partial blocks internally — that's the
    simulator, not the lowered program — so only pads issued from our
    kernel module count.)"""
    import traceback
    calls = []
    real = jnp.pad

    def spy(*args, **kwargs):
        if any(pa.__file__ == f.filename
               for f in traceback.extract_stack()):
            calls.append(args[0].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(jnp, "pad", spy)
    # fresh shape -> jit re-traces with the spy active; any pad of the
    # cache would fire at trace time
    q, k, v, slot, lens = _case(9, 3, 41, 4, 2, 16, 16, jnp.float32)
    pa.packed_attention(q, k, v, slot, lens, block_k=16, interpret=True)
    assert calls == []


# ---------------------------------------------------------------------------
# the tile plan's KV block DMAs: the numpy count == a host replay of the
# kernel's own tile plan and clamped index map
# ---------------------------------------------------------------------------
def _replayed_kv_dmas(slot, lens, *, n, group, bucket, block_q):
    """Consecutive distinct K block indices over the grid, in grid order,
    from ``_tile_plan`` and ``_kv_index`` as the kernel runs them."""
    t = len(slot)
    block_k = min(pa.DEFAULT_BLOCK_K, max(8, bucket))
    bq = pa.tile_tokens(t, group, block_q)
    n_tiles = pa.num_tiles(t, bq, n)
    *_, tile_slot, tile_len, tile_hi = pa._tile_plan(
        jnp.asarray(slot, jnp.int32), jnp.asarray(lens, jnp.int32),
        n_slots=n, bq=bq, n_tiles=n_tiles, block_k=block_k)
    tables = [np.asarray(x) for x in (tile_slot, tile_len, tile_hi)]
    prev, dmas = None, 0
    for i in range(n_tiles):
        for sb in range(-(-bucket // block_k)):
            idx = tuple(int(x) for x in pa._kv_index(i, sb, *tables))
            dmas += idx != prev
            prev = idx
    return dmas


def _cell_launch():
    """The Qwen3-4B offline cell's typical launch: 7 decodes, a 128-token
    chunk and 121 pads in slot 0, KV bucket 2048."""
    slot = np.r_[np.arange(1, 8), np.zeros(128 + 121, int)]
    lens = np.r_[RNG.integers(1025, 2049, size=7),
                 1500 + np.arange(1, 129), np.ones(121, int)]
    return slot, lens


# ``kv`` heads ride every block DMA together, so they do not enter the count
@pytest.mark.parametrize("launch,n,s,kv,group,block_q", [
    ("engine", 3, 64, 2, 4, 8),
    ("shuffled", 5, 96, 2, 2, 4),
    ("runs", 6, 64, 1, 8, 4),        # one-token runs, one tile per slot
    ("engine", 4, 64, 2, 4, 64),     # T < Bq
    ("cell", 8, 2048, 8, 4, pa.DEFAULT_BLOCK_Q),
])
def test_kv_blocks_fetched_matches_index_map_replay(launch, n, s, kv, group,
                                                    block_q):
    slot, lens = _cell_launch() if launch == "cell" else _launch(launch, n, s)
    want = _replayed_kv_dmas(slot, lens, n=n, group=group, bucket=s,
                             block_q=block_q)
    got = pa.kv_blocks_fetched(slot, lens, n_slots=n, group=group,
                               kv_bucket=s, block_q=block_q)
    assert got == want


def test_kv_blocks_fetched_cell_launch_10x_below_per_token():
    """In one kv head's blocks: each tiled DMA carries all 8 heads, each
    per-token DMA one."""
    slot, lens = _cell_launch()
    tiled = pa.kv_blocks_fetched(slot, lens, n_slots=8, group=4,
                                 kv_bucket=2048)
    per_token = pa.kv_blocks_fetched_per_token(256, kv_heads=8,
                                               kv_bucket=2048)
    assert per_token == 256 * 8 * 8
    assert 10 * 8 * tiled <= per_token, (tiled, per_token)


# ---------------------------------------------------------------------------
# kv-bucket correctness at bucket boundaries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bucket,max_lens", [
    (32, 32),        # every length exactly at the bucket edge
    (32, 31),        # strictly inside
    (64, 33),        # one past the previous bucket edge -> needs the next
])
def test_kv_bucket_slicing_exact(bucket, max_lens):
    t, n, s, h, kv, d = 8, 3, 64, 4, 2, 16
    q, k, v, slot, _ = _case(t, n, s, h, kv, d, d, jnp.float32)
    lens = jnp.asarray(RNG.integers(1, max_lens + 1, size=t)
                       .clip(max=max_lens), jnp.int32)
    lens = lens.at[0].set(max_lens)             # hit the boundary for sure
    full = ref.packed_attention_ref(q, k, v, slot, lens)
    sliced = ref.packed_attention_ref(q, k, v, slot, lens, kv_bucket=bucket)
    np.testing.assert_allclose(np.asarray(sliced), np.asarray(full),
                               rtol=1e-6, atol=1e-6)
    kern = pa.packed_attention(q, k, v, slot, lens, kv_bucket=bucket,
                               block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(full),
                               rtol=1e-5, atol=2e-5)


def test_scheduler_bucket_kv_boundaries():
    kvm = PagedKVManager(total_pages=64, page_size=8, bytes_per_token=64,
                         avg_decode_len=8)
    sched = GlobalBatchScheduler(kvm, discrete_sizes=(16, 8), max_active=8,
                                 kv_buckets=(32, 64, 128))
    assert sched.bucket_kv(1) == 32
    assert sched.bucket_kv(32) == 32             # exactly at the edge
    assert sched.bucket_kv(33) == 64             # one past the edge
    assert sched.bucket_kv(64) == 64
    assert sched.bucket_kv(65) == 128
    assert sched.bucket_kv(10_000) == 128        # saturates at max_len
    # no grid -> pack() reports kv_bucket=None (engine sweeps max_len)
    plain = GlobalBatchScheduler(kvm, discrete_sizes=(16, 8), max_active=8)
    plain.submit(Request(rid=0, prompt=list(range(11)), max_new_tokens=1))
    packed = plain.pack(plain.plan())
    assert packed.kv_bucket is None
    # first plan chunks the first 8 prompt tokens -> KV extent 8
    assert packed.kv_needed == 8


def test_default_kv_buckets_grid():
    assert default_kv_buckets(512) == (64, 128, 256, 512)
    assert default_kv_buckets(520) == (64, 128, 256, 512, 520)
    assert default_kv_buckets(128) == (64, 128)
    assert default_kv_buckets(48) == (48,)


# ---------------------------------------------------------------------------
# engine end-to-end: bucketed == dense (f32: bf16 accumulation-order diffs
# + MoE routing would flip argmax between execution paths)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["tiny-toy", "deepseek-v2-236b"])
def test_engine_kv_bucketing_matches_dense(arch):
    cfg = get_config(arch) if arch == "tiny-toy" else scale_down(
        get_config(arch))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = model.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    # prompt 30 + 4 decode tokens crosses the 32-bucket edge mid-decode
    # (context 31..34); prompt 12 stays inside the smallest bucket
    prompts = [list(rng.integers(0, cfg.vocab_size, size=n))
               for n in (30, 12, 7)]
    outs = {}
    for name, kw in [("bucketed", dict(kv_buckets=(32, 64))),
                     ("dense", dict(kv_bucketing=False))]:
        eng = ServeEngine(cfg, params, max_slots=2, max_len=64,
                          discrete_sizes=(16, 8), avg_decode_len=4, **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=4))
        done = eng.run()
        assert len(done) == len(prompts)
        outs[name] = {r.rid: r.output for r in done}
        if name == "bucketed":
            # both edge-straddling buckets really launched
            assert set(eng.stats.kv_bucket_hist) == {32, 64}
    assert outs["bucketed"] == outs["dense"]


def test_packed_compile_cache_bounded_by_t_times_kv_buckets():
    """Acceptance criterion: the packed program is keyed by (T bucket,
    kv bucket) only, so the compile cache is ≤ |T buckets| × |kv buckets| —
    and attention work tracked the buckets, not max_len."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    sizes = (32, 16, 8)
    kv_grid = (32, 64, 128)
    eng = ServeEngine(cfg, params, max_slots=4, max_len=128,
                      discrete_sizes=sizes, avg_decode_len=4,
                      kv_buckets=kv_grid)
    rng = np.random.default_rng(5)
    for i in range(10):
        eng.submit(Request(
            rid=i,
            prompt=list(rng.integers(0, cfg.vocab_size,
                                     size=int(rng.integers(3, 60)))),
            max_new_tokens=3))
    eng.run()
    assert eng.kv_buckets == kv_grid
    # len(sizes) + the max_active floor bucket, × the kv grid
    assert eng._packed_step._cache_size() <= (len(sizes) + 1) * len(kv_grid)
    assert set(eng.stats.kv_bucket_hist) <= set(kv_grid)
    # short contexts actually used the small buckets: the launched
    # attention sweep is strictly less than a max_len sweep every iteration
    launched = sum(eng.stats.kv_bucket_hist.values())
    assert launched == eng.stats.iterations
    assert min(eng.stats.kv_bucket_hist) < eng.max_len
    assert eng.stats.packed_attn_kv_rows < \
        eng.scheduler.launched_tokens * eng.max_len


# ---------------------------------------------------------------------------
# §Perf HC3 toggle promotion: explicit args, env only as fallback default
# ---------------------------------------------------------------------------
def test_attn_toggles_resolved_at_engine_construction(monkeypatch):
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    monkeypatch.delenv("REPRO_ATTN_FAST", raising=False)
    monkeypatch.delenv("REPRO_ATTN_STREAM", raising=False)
    assert ServeEngine(cfg, params).attn_fast is False
    # explicit argument wins over env...
    monkeypatch.setenv("REPRO_ATTN_FAST", "1")
    eng = ServeEngine(cfg, params, attn_fast=False, attn_stream=True)
    assert eng.attn_fast is False and eng.attn_stream is True
    # ...env is the fallback, captured once at construction
    eng2 = ServeEngine(cfg, params)
    assert eng2.attn_fast is True
    monkeypatch.setenv("REPRO_ATTN_FAST", "0")
    assert eng2.attn_fast is True                # no trace-time env re-read


def test_attn_config_context_pins_and_restores():
    assert ops.attn_fast_default() in (False, True)
    before = (ops.attn_fast_default(), ops.attn_stream_default())
    with ops.attn_config(fast=True, stream=True):
        assert ops.attn_fast_default() is True
        assert ops.attn_stream_default() is True
    assert (ops.attn_fast_default(), ops.attn_stream_default()) == before


def test_ops_fast_kwarg_selects_variant(monkeypatch):
    """The explicit ``fast`` kwarg picks the ref variant regardless of env."""
    monkeypatch.setenv("REPRO_ATTN_FAST", "1")
    called = []
    monkeypatch.setattr(ref, "packed_attention_ref",
                        lambda *a, **k: called.append("ref"))
    monkeypatch.setattr(ref, "packed_attention_fast",
                        lambda *a, **k: called.append("fast"))
    q, k, v, slot, lens = _case(2, 2, 8, 2, 1, 8, 8, jnp.float32)
    ops.packed_attention(q, k, v, slot, lens, impl="ref", fast=False)
    ops.packed_attention(q, k, v, slot, lens, impl="ref", fast=True)
    ops.packed_attention(q, k, v, slot, lens, impl="ref")   # env fallback
    assert called == ["ref", "fast", "fast"]
