"""Pure-jnp reference oracles for every Pallas kernel.

These are the *semantics* of each kernel: differentiable, shardable under
GSPMD, and used (a) as the model's XLA execution path on CPU / in the dry-run
and (b) as the ground truth for kernel `interpret=True` allclose sweeps.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def causal_qmask(sq: int, skv: int, q_offset: int | jax.Array) -> jax.Array:
    """(B|1, 1, 1, Sq, Skv) causal mask for chunked-prefill attention.

    ``q_offset`` — absolute position of q[0] relative to k[0] — may be a
    scalar (shared by all batch rows) or a per-row ``(B,)`` array (the
    engine's incremental prefill runs different slots at different prefix
    depths).  Broadcasts against ``(B, KV, G, Sq, Skv)`` scores.
    """
    qo = jnp.asarray(q_offset, jnp.int32).reshape(-1, 1)          # (B|1, 1)
    qpos = qo + jnp.arange(sq, dtype=jnp.int32)[None, :]          # (B|1, Sq)
    kpos = jnp.arange(skv, dtype=jnp.int32)
    return (qpos[:, :, None] >= kpos[None, None, :])[:, None, None]


# ---------------------------------------------------------------------------
# attention (prefill / train): causal GQA
# ---------------------------------------------------------------------------
def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        logit_scale: Optional[float] = None,
                        q_offset: int | jax.Array = 0) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H = KV * group.

    ``q_offset``: absolute position of q[0] relative to k[0] (chunked prefill
    attends to earlier cache positions non-causally); scalar or per-row (B,).
    """
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    dv = v.shape[-1]                 # may differ from d (e.g. MLA)
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5

    qg = q.reshape(b, sq, kv, group, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) * scale  # (B,KV,G,Sq,Skv)
    if causal:
        scores = jnp.where(causal_qmask(sq, skv, q_offset), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention (decode): one new token vs a length-masked KV cache
# ---------------------------------------------------------------------------
def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len: jax.Array, *,
                         logit_scale: Optional[float] = None) -> jax.Array:
    """q: (B, H, D); k_cache/v_cache: (B, S, KV, D); cache_len: (B,) int32 —
    number of valid positions (the new token's KV must already be written, so
    positions [0, cache_len) are attended)."""
    b, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5

    qg = q.reshape(b, kv, group, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32)) * scale
    valid = jnp.arange(s)[None, :] < cache_len[:, None]          # (B,S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhv->bhgv", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, dv).astype(q.dtype)


def _kv_bucket_view(k_cache: jax.Array, v_cache: jax.Array,
                    kv_bucket: Optional[int]):
    """Static slice of the slot caches to the iteration's KV-length bucket
    (DESIGN.md §9).  The caller guarantees ``max(lengths) <= kv_bucket``;
    rows at or beyond the bucket are never attended, so slicing them off is
    exact — and the einsums below then read/compute O(kv_bucket) per slot
    instead of O(max_len)."""
    if kv_bucket is not None and kv_bucket < k_cache.shape[1]:
        k_cache = jax.lax.slice_in_dim(k_cache, 0, kv_bucket, axis=1)
        v_cache = jax.lax.slice_in_dim(v_cache, 0, kv_bucket, axis=1)
    return k_cache, v_cache


def _block_gather_view(cache: jax.Array, block_tables: jax.Array,
                       kv_bucket: Optional[int]) -> jax.Array:
    """Per-slot contiguous view of a block-table cache (DESIGN.md §12).

    ``cache``: (N_slots, S, ...) physical storage whose *flat* row space
    (N·S rows) is carved into fixed-size blocks; ``block_tables``:
    (N_slots, S // block_size) int32 — physical block id backing each
    slot's logical block.  Gathers the first ``kv_bucket`` logical rows of
    every slot back into (N_slots, kv_bucket, ...), after which the dense
    packed-attention math is unchanged (the Pallas kernel instead gathers
    block-wise at the index-map level and never materializes this view)."""
    n, s = cache.shape[0], cache.shape[1]
    nb_cols = block_tables.shape[1]
    bs = s // nb_cols
    sweep = s if kv_bucket is None or kv_bucket > s else kv_bucket
    nbk = sweep // bs
    flat = cache.reshape((n * nb_cols, bs) + cache.shape[2:])
    view = flat[block_tables[:, :nbk]]              # (N, nbk, bs, ...)
    return view.reshape((n, nbk * bs) + cache.shape[2:])


def _layer_view(stack: jax.Array, layer, rows: int) -> jax.Array:
    """Layer ``layer``'s first ``rows`` rows of every slot of a stacked
    cache leaf (L, N_slots, S, ...), as (N_slots, rows, ...)."""
    return jax.lax.dynamic_slice(
        stack, (layer,) + (0,) * (stack.ndim - 1),
        (1, stack.shape[1], rows) + stack.shape[3:])[0]


def _dequant_views(k_cache, v_cache, k_scale, v_scale, block_tables,
                   kv_bucket, layer):
    """Cut layer ``layer`` out of stacked caches (5-D k/v), apply the
    bucket / block-table view to the caches (and scale leaves, when
    quantized), then dequantize to f32 for the dense sweep."""
    if k_cache.ndim == 5:
        s = k_cache.shape[2]
        rows = s if block_tables is not None or kv_bucket is None \
            else min(kv_bucket, s)
        k_cache, v_cache = (_layer_view(x, layer, rows)
                            for x in (k_cache, v_cache))
        if k_scale is not None:
            k_scale, v_scale = (_layer_view(x, layer, rows)
                                for x in (k_scale, v_scale))
    if block_tables is not None:
        k_cache = _block_gather_view(k_cache, block_tables, kv_bucket)
        v_cache = _block_gather_view(v_cache, block_tables, kv_bucket)
        if k_scale is not None:
            k_scale = _block_gather_view(k_scale, block_tables, kv_bucket)
            v_scale = _block_gather_view(v_scale, block_tables, kv_bucket)
    else:
        k_cache, v_cache = _kv_bucket_view(k_cache, v_cache, kv_bucket)
        if k_scale is not None:
            k_scale, v_scale = _kv_bucket_view(k_scale, v_scale, kv_bucket)
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32) * k_scale[..., None]
        v_cache = v_cache.astype(jnp.float32) * v_scale[..., None]
    return k_cache, v_cache


def packed_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         token_slot: jax.Array, lengths: jax.Array, *,
                         logit_scale: Optional[float] = None,
                         kv_bucket: Optional[int] = None,
                         block_tables: Optional[jax.Array] = None,
                         k_scale: Optional[jax.Array] = None,
                         v_scale: Optional[jax.Array] = None,
                         layer=0
                         ) -> jax.Array:
    """Segment-masked attention for the token-packed dense-batch step
    (DESIGN.md §8): every token of a packed ``(T,)`` stream attends its own
    slot's cache rows ``[0, lengths[t])`` and nothing else.

    q: (T, H, D) packed queries; k_cache/v_cache: (N_slots, S, KV, D/Dv)
    slot caches, or the stacked (L, N_slots, S, KV, D/Dv) caches of which
    layer ``layer`` (traced) is read (the packed step scatters each
    token's K/V at its ``(slot, position)`` before calling this);
    token_slot: (T,) int32 slot per token; lengths: (T,) int32 =
    position + 1 per token; kv_bucket:
    static bound on ``max(lengths)`` — only that many cache rows are read
    (KV-length bucketing, DESIGN.md §9), ``None`` means the full cache.

    Segments never attend across each other: slot selection restricts each
    query to its own request's cache, and the length mask is exactly the
    causal mask because a segment's K/V occupies positions ``[0, pos]``.

    Shape strategy: scores/contexts are computed dense against *all* slots
    (over the kv_bucket rows) and selected per token, rather than gathering
    each token's ``(S, ...)`` cache — the caches are then read once per
    einsum instead of once per token (T-fold less traffic; N_slots is
    small, so the extra FLOPs are noise next to the dense GEMMs).  The
    Pallas kernel (kernels/packed_attention.py) gathers block-wise instead,
    through the same call sites.

    ``block_tables`` (optional, DESIGN.md §12): block-table mode — the
    caches are physical block storage and each slot's logical rows are
    gathered through its table before the dense sweep.

    ``k_scale``/``v_scale`` (optional, (N_slots, S, KV) f32, or stacked
    like the caches, DESIGN.md §15):
    int8 caches — the same views apply to the scale leaves and the dense
    sweep dequantizes (``row * scale`` in f32) before the einsums; this is
    the XLA analogue of the Pallas kernel's in-register dequant.
    """
    k_cache, v_cache = _dequant_views(
        k_cache, v_cache, k_scale, v_scale, block_tables, kv_bucket, layer)
    t, h, d = q.shape
    n, s, kv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5

    qg = q.reshape(t, kv, group, d).astype(jnp.float32)
    scores_all = jnp.einsum("tkgd,nskd->tnkgs", qg,
                            k_cache.astype(jnp.float32)) * scale
    idx = token_slot.reshape(t, 1, 1, 1, 1)
    scores = jnp.take_along_axis(scores_all, idx, axis=1)[:, 0]  # (T,KV,G,S)
    valid = jnp.arange(s)[None, :] < lengths[:, None]            # (T,S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_all = jnp.einsum("tkgs,nskv->tnkgv", probs,
                         v_cache.astype(jnp.float32))
    out = jnp.take_along_axis(ctx_all, idx, axis=1)[:, 0]        # (T,KV,G,Dv)
    return out.reshape(t, h, dv).astype(q.dtype)


def packed_attention_fast(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                          token_slot: jax.Array, lengths: jax.Array, *,
                          logit_scale: Optional[float] = None,
                          kv_bucket: Optional[int] = None,
                          block_tables: Optional[jax.Array] = None,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None,
                          layer=0
                          ) -> jax.Array:
    """No-upcast variant of ``packed_attention_ref`` (§Perf HC3): same
    math, bf16 einsum operands with f32 in-register accumulation (int8
    caches dequantize to f32 first — the scale multiply *is* the upcast)."""
    k_cache, v_cache = _dequant_views(
        k_cache, v_cache, k_scale, v_scale, block_tables, kv_bucket, layer)
    t, h, d = q.shape
    n, s, kv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5

    qg = q.reshape(t, kv, group, d)
    scores_all = jnp.einsum("tkgd,nskd->tnkgs", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
    idx = token_slot.reshape(t, 1, 1, 1, 1)
    scores = jnp.take_along_axis(scores_all, idx, axis=1)[:, 0]
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    ctx_all = jnp.einsum("tkgs,nskv->tnkgv", probs, v_cache,
                         preferred_element_type=jnp.float32)
    out = jnp.take_along_axis(ctx_all, idx, axis=1)[:, 0]
    return out.reshape(t, h, dv).astype(q.dtype)


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, page_table: jax.Array,
                               cache_len: jax.Array, *,
                               logit_scale: Optional[float] = None) -> jax.Array:
    """Paged KV: k_pages/v_pages (NP, PS, KV, D) global page pool;
    page_table (B, MAXP) int32 page ids (-1 = unused); cache_len (B,)."""
    b = q.shape[0]
    np_, ps, kvh, d = k_pages.shape
    maxp = page_table.shape[1]
    safe = jnp.maximum(page_table, 0)
    k = k_pages[safe]                              # (B, MAXP, PS, KV, D)
    v = v_pages[safe]
    k = k.reshape(b, maxp * ps, kvh, d)
    v = v.reshape(b, maxp * ps, kvh, d)
    return decode_attention_ref(q, k, v, cache_len, logit_scale=logit_scale)


# ---------------------------------------------------------------------------
# "fast" attention variants (§Perf HC3): identical math, but the big K/V
# tensors are NOT pre-upcast with .astype(f32) — the einsums take bf16
# operands with preferred_element_type=f32 (MXU-style in-register
# accumulation), so XLA never materializes an f32 copy of the KV cache /
# activations.  Enabled via env REPRO_ATTN_FAST=1 (kernels/ops.py).
# ---------------------------------------------------------------------------
def flash_attention_fast(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True,
                         logit_scale: Optional[float] = None,
                         q_offset: int | jax.Array = 0) -> jax.Array:
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    dv = v.shape[-1]
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5
    qg = q.reshape(b, sq, kv, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        scores = jnp.where(causal_qmask(sq, skv, q_offset), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhv->bqhgv", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def decode_attention_fast(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, cache_len: jax.Array, *,
                          logit_scale: Optional[float] = None) -> jax.Array:
    b, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5
    qg = q.reshape(b, kv, group, d)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(s)[None, :] < cache_len[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgk,bkhv->bhgv", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# streaming (flash-style) attention in pure XLA: lax.scan over KV blocks
# with running (m, l, acc).  Never materializes the (Sq, Skv) score matrix —
# peak intermediate is (Sq, block).  Differentiable (bwd recomputes per
# block).  This is the XLA-path analogue of the Pallas flash kernel, used
# for long-sequence prefill/train cells (REPRO_ATTN_STREAM=1).
# ---------------------------------------------------------------------------
def flash_attention_stream(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           logit_scale: Optional[float] = None,
                           q_offset: int | jax.Array = 0,
                           block: int = 1024) -> jax.Array:
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    dv = v.shape[-1]
    group = h // kv
    scale = logit_scale if logit_scale is not None else d ** -0.5

    blk = min(block, skv)
    pad = (-skv) % blk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (skv + pad) // blk

    qg = (q.reshape(b, sq, kv, group, d).astype(jnp.float32) * scale)
    kb = k.reshape(b, nb, blk, kv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, blk, kv, dv).transpose(1, 0, 2, 3, 4)
    qo = jnp.asarray(q_offset, jnp.int32).reshape(-1, 1)          # (B|1, 1)
    qpos = qo + jnp.arange(sq, dtype=jnp.int32)[None, :]          # (B|1, Sq)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, start = inp                              # (B,blk,KV,*), scalar
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc.astype(jnp.float32))
        kpos = start + jnp.arange(blk)[None, :]          # (1, blk)
        mask = jnp.broadcast_to(kpos < skv, qpos.shape[:1] + (sq, blk))
        if causal:
            mask = mask & (qpos[:, :, None] >= kpos[None])
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + \
            jnp.einsum("bhgqk,bkhv->bhgqv", p, vc.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, kv, group, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, kv, group, sq, dv), jnp.float32)
    starts = jnp.arange(nb) * blk
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# fused overlap: dense GEMM co-scheduled with decode attention (NanoFlow's
# signature op pair).  Reference = the pair computed independently.
# ---------------------------------------------------------------------------
def fused_overlap_ref(x: jax.Array, w: jax.Array, q: jax.Array,
                      k_cache: jax.Array, v_cache: jax.Array,
                      cache_len: jax.Array) -> tuple[jax.Array, jax.Array]:
    gemm_out = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    attn_out = decode_attention_ref(q, k_cache, v_cache, cache_len)
    return gemm_out, attn_out


# ---------------------------------------------------------------------------
# Mamba-1 selective scan
# ---------------------------------------------------------------------------
def ssm_scan_ref(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                 c: jax.Array, d: jax.Array,
                 h0: Optional[jax.Array] = None) -> tuple[jax.Array, jax.Array]:
    """Selective state-space scan.

    x: (B, S, C) inner activations; dt: (B, S, C) positive step sizes;
    a: (C, N) negative-real state matrix; b, c: (B, S, N) input/output
    projections; d: (C,) skip.  Returns (y (B,S,C), h_final (B,C,N)).
    Discretization: h_t = exp(dt*a) h_{t-1} + dt * b_t * x_t ; y = (c_t·h) + d*x.
    """
    bsz, s, ch = x.shape
    n = a.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, ch, n), jnp.float32)

    x32, dt32 = x.astype(jnp.float32), dt.astype(jnp.float32)
    b32, c32 = b.astype(jnp.float32), c.astype(jnp.float32)
    a32 = a.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp                     # (B,C) (B,C) (B,N) (B,N)
        da = jnp.exp(dtt[..., None] * a32)        # (B,C,N)
        h = da * h + (dtt * xt)[..., None] * bt[:, None, :]
        y = jnp.einsum("bcn,bn->bc", h, ct)
        return h, y

    xs = (jnp.moveaxis(x32, 1, 0), jnp.moveaxis(dt32, 1, 0),
          jnp.moveaxis(b32, 1, 0), jnp.moveaxis(c32, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x32 * d
    return y.astype(x.dtype), h_final


def ssm_step_ref(x_t: jax.Array, dt_t: jax.Array, a: jax.Array, b_t: jax.Array,
                 c_t: jax.Array, d: jax.Array,
                 h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Single decode step.  x_t, dt_t: (B, C); b_t, c_t: (B, N); h: (B, C, N)."""
    da = jnp.exp(dt_t.astype(jnp.float32)[..., None] * a.astype(jnp.float32))
    h = da * h + (dt_t * x_t).astype(jnp.float32)[..., None] * b_t.astype(jnp.float32)[:, None, :]
    y = jnp.einsum("bcn,bn->bc", h, c_t.astype(jnp.float32)) + x_t.astype(jnp.float32) * d
    return y.astype(x_t.dtype), h


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix memory) — chunkwise-parallel reference
# ---------------------------------------------------------------------------
def mlstm_chunk_ref(q: jax.Array, k: jax.Array, v: jax.Array, i_gate: jax.Array,
                    f_gate: jax.Array, *, chunk: int = 64,
                    initial: Optional[tuple] = None
                    ) -> tuple[jax.Array, tuple]:
    """Stabilized mLSTM over (B, S, H, D) q/k/v with (B, S, H) log-space gates.

    i_gate = log-input-gate (pre-exp), f_gate = log-sigmoid(forget preact).
    Sequential reference over time (the chunked Pallas kernel must match).
    Returns (y (B,S,H,Dv), (C, n, m) final state).
    """
    bsz, s, h, dqk = q.shape
    dv = v.shape[-1]
    scale = dqk ** -0.5
    if initial is None:
        c0 = jnp.zeros((bsz, h, dqk, dv), jnp.float32)
        n0 = jnp.zeros((bsz, h, dqk), jnp.float32)
        m0 = jnp.full((bsz, h), -jnp.inf, jnp.float32)
    else:
        c0, n0, m0 = initial

    q32 = q.astype(jnp.float32) * scale
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    ig, fg = i_gate.astype(jnp.float32), f_gate.astype(jnp.float32)

    def step(state, inp):
        c, n, m = state
        qt, kt, vt, it, ft = inp
        m_new = jnp.maximum(ft + m, it)                     # (B,H)
        f_sc = jnp.exp(ft + m - m_new)[..., None]
        i_sc = jnp.exp(it - m_new)[..., None]
        c = f_sc[..., None] * c + (i_sc * kt)[..., None] * vt[:, :, None, :]
        n = f_sc * n + i_sc * kt
        num = jnp.einsum("bhd,bhdv->bhv", qt, c)
        den = jnp.abs(jnp.einsum("bhd,bhd->bh", qt, n))
        den = jnp.maximum(den, jnp.exp(-m_new))[..., None]
        y = num / den
        return (c, n, m_new), y

    xs = (jnp.moveaxis(q32, 1, 0), jnp.moveaxis(k32, 1, 0),
          jnp.moveaxis(v32, 1, 0), jnp.moveaxis(ig, 1, 0),
          jnp.moveaxis(fg, 1, 0))
    state, ys = jax.lax.scan(step, (c0, n0, m0), xs)
    y = jnp.moveaxis(ys, 0, 1).astype(q.dtype)              # (B,S,H,Dv)
    return y, state


def mlstm_step_ref(q_t, k_t, v_t, i_t, f_t, state):
    """One decode step; shapes (B,H,D*) / (B,H); state = (C, n, m)."""
    c, n, m = state
    scale = q_t.shape[-1] ** -0.5
    qt = q_t.astype(jnp.float32) * scale
    kt, vt = k_t.astype(jnp.float32), v_t.astype(jnp.float32)
    it, ft = i_t.astype(jnp.float32), f_t.astype(jnp.float32)
    m_new = jnp.maximum(ft + m, it)
    f_sc = jnp.exp(ft + m - m_new)[..., None]
    i_sc = jnp.exp(it - m_new)[..., None]
    c = f_sc[..., None] * c + (i_sc * kt)[..., None] * vt[:, :, None, :]
    n = f_sc * n + i_sc * kt
    num = jnp.einsum("bhd,bhdv->bhv", qt, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt, n)),
                      jnp.exp(-m_new))[..., None]
    return (num / den).astype(q_t.dtype), (c, n, m_new)


# ---------------------------------------------------------------------------
# plain GEMM (oracle for block-tiled matmul kernel)
# ---------------------------------------------------------------------------
def gemm_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
