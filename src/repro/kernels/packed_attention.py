"""Pallas TPU packed attention (the token-packed serve step's hot op).

Grid ``(query tile, KV block)``, KV minor so a tile's running-softmax
scratch (m, l, acc) persists across the cache sweep.

**The carried stack, read in place**: the caches are the model step's
stacked ``(L, N_slots, S, KV, D)`` leaves (DESIGN.md §8) and ``layer``
(traced, one more scalar-prefetch operand) picks the layer.  Each grid
step DMAs one ``(Bk, KV, D)`` block of one slot in its stored row-major
order: every kv head at once, so the block's last two dims are whole and
layout-legal, and no per-layer bucket copy or head-major transpose of the
cache exists outside the kernel.  The step then runs the per-row online
softmax for each kv head in turn, reading the head's rows with
sublane-strided 32-bit loads (``_head_rows``): as many loads per block as
a head-major block took.  A 4-D cache ``(N_slots, S, KV, D)`` is the
one-layer stack; one kv head (absorbed MLA) drops the head axis.

**Tile plan** (``_tile_plan``, on the device, inside the wrapper): every
packed token goes to its rank within its slot's run — a stable sort by
``token_slot``, whatever order the caller packed — and each slot's run is
cut into query tiles of ``Bq`` tokens.  A tile therefore holds tokens of
one slot only, and one grid step applies one KV block of that slot to all
``Bq·G`` query rows of each kv head of the tile (G = query heads per kv
head): a prefill chunk reads its slot's KV once per tile, not once per
token.  ``q`` is gathered into tile order and the output scattered back
through the same map.  Small scalar-prefetch tables carry each tile's
slot, its length (the largest ``lengths`` in it) and the last block it
needs; per-row lengths ride a VMEM operand, 0 for rows past the end of the
run.  At most ``min(T, ceil(T / Bq) + N_slots)`` tiles are needed, a
static bound, so the compile cache keeps its |T buckets| × |KV buckets|
bound.  ``Bq = min(T, block_q, MAX_TILE_ROWS // G)``: the row cap keeps a
wide GQA group (absorbed MLA's 128 heads over one latent) inside VMEM.

**Clamped index map** (``_kv_index``): the KV block of step ``(i, sb)`` is
``min(sb, hi[i])`` with ``hi[i] = max(ceil(tile_len / Bk) - 1, 0)``.  A
block past the tile's context repeats the previous block index, so the
pipeline issues no DMA for it, and ``pl.when`` skips its math.  Tiles past
the last real one (``tile_len == 0``) point every step at the last real
tile's final block: they fetch no KV at all.  ``kv_blocks_fetched`` counts
the block DMAs a plan issues, in numpy.

**Per-row arithmetic** is what it was with one token per step: f32 online
softmax, a per-row length mask (the causal/segment mask is a pure length
mask, DESIGN.md §8-§9), so every row sees the same blocks in the same
order.

``d_v`` may differ from ``d_qk`` (absorbed MLA attends with
d_qk = rank + rope but d_v = rank).

``kv_bucket`` statically bounds the swept cache extent (KV-length
bucketing, DESIGN.md §9): the sweep stops at block ``ceil(kv_bucket /
Bk)``, and no row past it is fetched.  A bucket that is not a multiple of
``block_k`` gets a *masked partial last block*: its rows past the bucket
are discarded by the length mask (scores) plus an explicit zero-mask on
the value rows — no O(cache) ``jnp.pad`` on the hot path (DESIGN.md §15).

int8 KV (DESIGN.md §15): with ``k_scale``/``v_scale`` the k/v blocks DMA
*as stored* (int8) with a small ``(Bk, KV)`` f32 scale tile of every kv
head (same slot/block index); the kernel picks each head's column and
dequantizes in-register (``k_i8 * scale`` in f32) before the flash math.
The scales alone are cut per layer first: XLA stores a stacked f32
``(L, N, S, KV)`` leaf S-minor, which no layout-legal ``(Bk, KV)`` tile
reads in place, and handed the stack it copied the whole padded stack in
every layer.

Block tables (DESIGN.md §12): the caches are physical block storage, seen
as ``(L, N·S / bs, bs, KV, D)`` (a free reshape), and the index map
dereferences ``bt[tile_slot[i] * nb_cols + min(sb, hi[i])]``; ``block_k``
is the block size.  All four variants (contiguous, int8, block table,
int8 + block table) share the tile plan, the index maps and the one kernel
body.

VMEM per step (bf16, Bk=256, KV=8, D=128, rows R = Bq·G ≤ 512 per kv
head): k and v (Bk, KV, D) double-buffered 2 MB, q and out (KV, R, D)
0.5 MB each at R=128, per-row lengths (R, 1) int32, scratch acc f32
(KV, R, Dv) + m, l (KV, R, 1), and one head's f32 temporaries (R, Bk) and
(Bk, D) at a time: ~5 MB for Qwen3-4B; ~7 MB for absorbed MLA (KV 1,
Dqk 576, Dv 512, R = 4·128).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256
DEFAULT_BLOCK_Q = 32
MAX_TILE_ROWS = 512


def tile_tokens(t: int, group: int, block_q: int = DEFAULT_BLOCK_Q) -> int:
    """Bq, the tokens per query tile, for a launch of ``t`` tokens."""
    return max(1, min(t, block_q, MAX_TILE_ROWS // group))


def num_tiles(t: int, bq: int, n_slots: int) -> int:
    """Static bound on the tiles a launch needs: each slot's run wastes at
    most one partial tile, and no tile is empty of tokens but the tail."""
    return min(t, -(-t // bq) + n_slots)


def _tile_plan(token_slot, lengths, *, n_slots: int, bq: int, n_tiles: int,
               block_k: int):
    """Device tile plan.  Returns ``dest`` (T,), each token's row in tile
    order (tile ``dest // bq``, row ``dest % bq``); ``src`` (n_tiles·bq,),
    the token at each row, ``T`` past the end of a run; ``row_len``, that
    token's length, 0 past the end of a run; and the per-tile
    ``tile_slot``, ``tile_len`` (0 for an empty tile) and ``tile_hi`` (last
    block to fetch).  Empty tiles, all at the end, carry the last real
    tile's slot and last block, so their index maps repeat its final DMA."""
    t = token_slot.shape[0]
    onehot = (token_slot[:, None] ==
              jnp.arange(n_slots, dtype=token_slot.dtype)[None, :])
    run = jnp.cumsum(onehot.astype(jnp.int32), axis=0)         # (T, N)
    rank = jnp.take_along_axis(run, token_slot[:, None], axis=1)[:, 0] - 1
    tiles = -(-run[-1] // bq)                                  # per slot
    first = jnp.cumsum(tiles) - tiles
    dest = first[token_slot] * bq + rank
    src = jnp.full((n_tiles * bq,), t, jnp.int32).at[dest].set(
        jnp.arange(t, dtype=jnp.int32))
    row_len = jnp.take(lengths.astype(jnp.int32), src, mode="fill",
                       fill_value=0)
    tile_len = row_len.reshape(n_tiles, bq).max(axis=1)
    tile_slot = jnp.zeros((n_tiles,), jnp.int32).at[dest // bq].set(
        token_slot.astype(jnp.int32))
    tile_hi = jnp.maximum(-(-tile_len // block_k) - 1, 0)
    last = jnp.maximum(jnp.sum(tiles) - 1, 0)
    pin = jnp.minimum(jnp.arange(n_tiles), last)
    return dest, src, row_len, tile_slot[pin], tile_len, tile_hi[pin]


def _kv_index(i, sb, slot_ref, len_ref, hi_ref):
    """The (slot, block) a grid step reads, clamped to the tile's context;
    empty tiles stay on the last real tile's final block."""
    blk = jnp.where(len_ref[i] > 0, jnp.minimum(sb, hi_ref[i]), hi_ref[i])
    return slot_ref[i], blk


def _head_rows(ref, head: int):
    """Kv head ``head``'s ``(Bk, D)`` rows of a ``(1, 1, Bk, KV, D)`` block
    (a ``(1, 1, Bk, D)`` block holds one kv head), in f32.  The block keeps
    the stored order, so a head's rows lie KV rows apart; Mosaic loads such
    rows one at a time, where one sublane-strided load of 32-bit words
    reads eight.  A narrower dtype packs ``4 // itemsize`` heads of a row
    into each word, low bits first: shifting the head's bits out gives its
    exact f32 (bf16) or integer (int8) value."""
    if ref.ndim == 4:
        return ref[0, 0].astype(jnp.float32)
    _, _, bk, kvh, d = ref.shape
    pack = 4 // ref.dtype.itemsize
    if kvh % pack:                       # int8 with 2 kv heads: row by row
        return ref[0, 0, :, head, :].astype(jnp.float32)
    words = ref.bitcast(jnp.int32) if pack > 1 else ref
    w = words.reshape(bk * kvh // pack, d)[
        pl.ds(head // pack, bk, stride=kvh // pack), :]
    sub = head % pack
    if pack == 1:
        return w.astype(jnp.float32)
    if pack == 2:                        # bf16: the high half of an f32
        return pltpu.bitcast(w << 16 if sub == 0 else w & -65536,
                             jnp.float32)
    return ((w << 8 * (3 - sub)) >> 24).astype(jnp.float32)    # int8


def _head_scale(s_ref, head: int):
    """Kv head ``head``'s column of a ``(1, 1, Bk, KV)`` scale tile, as a
    ``(Bk, 1)`` row scale.  The tile carries every head because a block's
    last two dims must be (8, 128)-aligned or whole; a one-hot lane
    reduction picks the head without a lane slice."""
    sc = s_ref[0, 0]                                              # (Bk, KV)
    lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    return jnp.sum(jnp.where(lane == head, sc, 0.0), axis=1,
                   keepdims=True)


def _kernel(*refs, n_prefetch: int, kv_heads: int, quant: bool,
            scale: float, block_k: int, s_valid: Optional[int]):
    """Online-softmax body of one (tile, KV block) step, each kv head of
    the block in turn.  ``s_valid`` (static) is the swept KV extent when
    the last block is partial — its rows >= s_valid lie past the bucket
    (or past the array, uninitialized DMA tail) and are zeroed out of the
    value accumulation (their *scores* are already masked: kpos >=
    s_valid >= every row's length)."""
    tlen_ref = refs[2]
    q_ref, k_ref, v_ref, *rest = refs[n_prefetch:]
    ks_ref, vs_ref = rest[:2] if quant else (None, None)
    rl_ref, o_ref, m_ref, l_ref, acc_ref = rest[2:] if quant else rest
    i = pl.program_id(0)
    sb = pl.program_id(1)
    nsb = pl.num_programs(1)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks past every row of the tile contribute nothing: skip the math
    # (the clamped index map issued no DMA for them either)
    @pl.when(sb * block_k < tlen_ref[i])
    def _compute():
        for head in range(kv_heads):
            q = q_ref[0, head].astype(jnp.float32) * scale        # (R, Dqk)
            k = _head_rows(k_ref, head)                           # (Bk, Dqk)
            v = _head_rows(v_ref, head)                           # (Bk, Dv)
            if quant:
                k = k * _head_scale(ks_ref, head)
                v = v * _head_scale(vs_ref, head)
            if s_valid is not None:
                vrow = sb * block_k + \
                    jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                v = jnp.where(vrow < s_valid, v, 0.0)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (R, Bk)

            kpos = sb * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < rl_ref[0], s, NEG_INF)           # (R, 1) lens

            m_prev = m_ref[head]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[head] = l_ref[head] * alpha + \
                jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[head] = acc_ref[head] * alpha + \
                jnp.dot(p, v, preferred_element_type=jnp.float32)
            m_ref[head] = m_new

    @pl.when(sb == nsb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("logit_scale", "kv_bucket",
                                             "block_k", "block_q",
                                             "interpret"))
def packed_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     token_slot: jax.Array, lengths: jax.Array, *,
                     layer=0,
                     logit_scale: Optional[float] = None,
                     kv_bucket: Optional[int] = None,
                     block_tables: Optional[jax.Array] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_k: int = DEFAULT_BLOCK_K,
                     block_q: int = DEFAULT_BLOCK_Q,
                     interpret: bool = False) -> jax.Array:
    """q: (T, H, Dqk) packed queries; k_cache: (L, N_slots, S, KV, Dqk)
    stacked caches, or one layer's (N_slots, S, KV, Dqk); v_cache: the
    same with Dv; token_slot: (T,) int32 slot per token; lengths: (T,)
    int32 — token t attends rows [0, lengths[t]) of its slot in layer
    ``layer`` (traced int; 0 for a 4-D cache).

    ``kv_bucket`` (static): the caller guarantees ``max(lengths) <=
    kv_bucket``; only the first ``kv_bucket`` cache rows are swept.
    Returns (T, H, Dv).

    ``block_tables`` (optional, (N_slots, S // block_size) int32,
    DESIGN.md §12): block-table mode — the caches are *physical block
    storage* (each layer's flat row space N·S carved into fixed-size
    blocks) and grid step ``(i, sb)`` DMAs physical block
    ``bt[tile_slot[i], sb]`` instead of slot row-block ``sb``.  The table
    is a traced operand of static shape, so the compile-cache bound is
    unchanged.

    ``k_scale``/``v_scale`` (optional, (L, N_slots, S, KV) or
    (N_slots, S, KV) f32, DESIGN.md §15): int8 caches — k/v blocks
    dequantize in-register (``row * scale``) after the int8 HBM read; the
    scale tiles ride the same index maps.

    ``block_q`` (static) is the most tokens per query tile (module
    docstring); ``block_k`` the rows per KV block of a contiguous cache.
    """
    quant = k_scale is not None
    if k_cache.ndim == 4:                # one layer: the L = 1 stack
        k_cache, v_cache = k_cache[None], v_cache[None]
        if quant:
            k_scale, v_scale = k_scale[None], v_scale[None]
    t, h, d = q.shape
    n_layers, n, s, kvh, _ = k_cache.shape
    dv = v_cache.shape[-1]
    group = h // kvh
    scale = logit_scale if logit_scale is not None else d ** -0.5
    sweep = s if kv_bucket is None else min(kv_bucket, s)

    scales = []
    if quant:
        # the layer's swept scale rows (1/64 of its int8 K/V bytes; module
        # docstring), read as layer 0 of the cut
        rows = s if block_tables is not None else sweep
        scales = [jax.lax.dynamic_slice(
            x, (layer,) + (0,) * 3, (1, n, rows, kvh))
            for x in (k_scale, v_scale)]

    if block_tables is None:
        # masked partial last block instead of an O(cache) pad (DESIGN.md
        # §15): rows of the final block past ``sweep`` are length-masked
        # in the scores and zero-masked in the values
        block_k = min(block_k, max(8, sweep))
        nsb = -(-sweep // block_k)
        s_valid = sweep if sweep % block_k else None
        tables = []

        def block(slot, blk, *_):
            return slot, blk
    else:
        # physical block storage: (L, N·S/bs, bs, KV, D), a free reshape;
        # a DMA can't span two physical blocks, so block_k is the block size
        nb_cols = block_tables.shape[1]
        block_k = s // nb_cols
        assert sweep % block_k == 0, (sweep, block_k)
        nsb = sweep // block_k
        s_valid = None
        k_cache = k_cache.reshape(n_layers, n * nb_cols, block_k, kvh, d)
        v_cache = v_cache.reshape(n_layers, n * nb_cols, block_k, kvh, dv)
        scales = [x.reshape(1, n * nb_cols, block_k, kvh) for x in scales]
        tables = [block_tables.reshape(-1).astype(jnp.int32)]

        def block(slot, blk, bt_ref):
            return bt_ref[slot * nb_cols + blk], 0

    bq = tile_tokens(t, group, block_q)
    n_tiles = num_tiles(t, bq, n)
    rows = bq * group
    dest, src, row_len, tile_slot, tile_len, tile_hi = _tile_plan(
        token_slot, lengths, n_slots=n, bq=bq, n_tiles=n_tiles,
        block_k=block_k)
    # q in tile order: (n_tiles, KV, Bq·G, Dqk), row b·G + g of a tile is
    # token b's query head g of the kv head; rows past a run are zeros
    qt = jnp.take(q, src, axis=0, mode="fill", fill_value=0)
    qt = qt.reshape(n_tiles, bq, kvh, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(n_tiles, kvh, rows, d)
    rl = jnp.repeat(row_len.reshape(n_tiles, bq), group, axis=1)[..., None]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # one kv head (absorbed MLA): drop the head axis, so a block is (Bk, D)
    heads = (kvh,) if kvh > 1 else ()
    k_cache = k_cache.reshape(k_cache.shape[:3] + heads + (d,))
    v_cache = v_cache.reshape(v_cache.shape[:3] + heads + (dv,))

    def kv_map(i, sb, layer_ref, *pf):
        a, b = block(*_kv_index(i, sb, *pf[:3]), *pf[3:])
        return (layer_ref[0], a, b, 0) + (0,) * len(heads)

    def scale_map(i, sb, layer_ref, *pf):
        return (0,) + kv_map(i, sb, layer_ref, *pf)[1:4]

    def tile_map(i, sb, *_):
        return i, 0, 0, 0

    in_specs = [pl.BlockSpec((1, kvh, rows, d), tile_map),
                pl.BlockSpec((1, 1, block_k) + heads + (d,), kv_map),
                pl.BlockSpec((1, 1, block_k) + heads + (dv,), kv_map)]
    in_specs += [pl.BlockSpec((1, 1, block_k, kvh), scale_map)] * len(scales)
    in_specs.append(pl.BlockSpec((1, rows, 1), lambda i, sb, *_: (i, 0, 0)))
    # layer, tile_slot, tile_len, tile_hi[, table]
    n_prefetch = 4 + len(tables)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(n_tiles, nsb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, rows, dv), tile_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, rows, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((kvh, rows, 1), jnp.float32),    # l (running denom)
            pltpu.VMEM((kvh, rows, dv), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_prefetch=n_prefetch, kv_heads=kvh,
                          quant=quant, scale=scale, block_k=block_k,
                          s_valid=s_valid),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, kvh, rows, dv), q.dtype),
        interpret=interpret,
        name="packed_attention",
    )(layer, tile_slot, tile_len, tile_hi, *tables, qt, k_cache, v_cache,
      *scales, rl)
    out = out.reshape(n_tiles, kvh, bq, group, dv).transpose(0, 2, 1, 3, 4) \
        .reshape(n_tiles * bq, h, dv)
    return jnp.take(out, dest, axis=0)


# ---------------------------------------------------------------------------
# host mirror: the KV block DMAs a launch issues (no JAX, not traced)
# ---------------------------------------------------------------------------
def kv_blocks_fetched(token_slot, lengths, *, n_slots: int, group: int,
                      kv_bucket: int, block_k: int = DEFAULT_BLOCK_K,
                      block_q: int = DEFAULT_BLOCK_Q) -> int:
    """K block DMAs (each with its V block, every kv head in one) that one
    call of the tiled kernel issues on a contiguous cache: the grid steps,
    in order, whose clamped block index differs from the step before.
    Mirrors ``_tile_plan`` and ``_kv_index`` in numpy."""
    slot = np.asarray(token_slot, np.int64)
    lens = np.asarray(lengths, np.int64)
    t = slot.shape[0]
    block_k = min(block_k, max(8, kv_bucket))
    nsb = -(-kv_bucket // block_k)
    bq = tile_tokens(t, group, block_q)
    n_tiles = num_tiles(t, bq, n_slots)
    tile_slot, tile_len = [], []
    for sl in range(n_slots):                    # a stable sort by slot
        run = lens[slot == sl]
        for j in range(0, run.size, bq):
            tile_slot.append(sl)
            tile_len.append(int(run[j:j + bq].max()))
    real = len(tile_slot)
    hi = [max(-(-ln // block_k) - 1, 0) for ln in tile_len]
    idx = []
    for i in range(n_tiles):
        r = min(i, max(real - 1, 0))
        live = i < real and tile_len[i] > 0
        for sb in range(nsb):
            idx.append((tile_slot[r] if real else 0,
                        min(sb, hi[r]) if live else (hi[r] if real else 0)))
    return sum(1 for j, b in enumerate(idx) if j == 0 or b != idx[j - 1])


def kv_blocks_fetched_per_token(t: int, *, kv_heads: int, kv_bucket: int,
                                block_k: int = DEFAULT_BLOCK_K) -> int:
    """The same count for the per-token grid this kernel replaced, one
    step per (token, kv head, KV block), every step a DMA of one kv head's
    block: ``T × KV × nsb``."""
    block_k = min(block_k, max(8, kv_bucket))
    return t * kv_heads * -(-kv_bucket // block_k)
