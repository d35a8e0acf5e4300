"""Pallas TPU packed attention (the token-packed serve step's hot op).

One program instance per ``(token, kv head, KV block)``: the BlockSpec index
map dereferences a scalar-prefetch ``token_slot`` table, so each packed
token's KV blocks are DMA'd from *its own slot's* cache rows — the slot
gather happens at the index-map level (exactly like the paged kernel's page
table) and the dense-vs-all-slots score matrix of the XLA ref is never
formed.  KV is minor in the grid so the per-(token, kv head) running-softmax
scratch persists across the cache sweep (flash-style online softmax), and
blocks entirely beyond the token's ``lengths`` are skipped with ``pl.when``
(the causal/segment mask is a pure length mask, DESIGN.md §8-§9).

``d_v`` may differ from ``d_qk`` (absorbed MLA attends with
d_qk = rank + rope but d_v = rank), so the MLA packed path runs this kernel
instead of silently falling back to the ref.

``kv_bucket`` statically bounds the swept cache extent (KV-length bucketing,
DESIGN.md §9): the kernel only touches ``kv_bucket`` rows per slot, so FLOPs
and HBM traffic scale with the iteration's actual context, not ``max_len``.

A bucket that is not a multiple of ``block_k`` gets a *masked partial last
block* rather than a padded cache copy: the grid's KV dimension is
``ceil(s / block_k)`` and the out-of-bounds tail of the final tile is
discarded by the existing length mask (scores) plus an explicit zero-mask on
the value rows — no O(cache) ``jnp.pad`` on the hot path (DESIGN.md §15).

int8 KV (DESIGN.md §15): when ``k_scale``/``v_scale`` are passed the k/v
tiles DMA *as stored* (int8) together with a small ``(Bk, KV)`` f32 scale
tile of every kv head (same slot/block index, ~KV/head_dim the bytes), and
the kernel picks its head's column and dequantizes in-register —
``k_i8 * scale`` in f32 — before the flash math.  Attention
HBM traffic drops ~2× while the grid, the scalar-prefetch index maps and
the online-softmax scratch are unchanged.

VMEM per step (bf16, Bk=256, D=128, G≤16):
  k (Bk, Dqk) + v (Bk, Dv) + q (G, Dqk) + acc f32 (G, Dv) ≈ 0.2 MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256


def _flash_step(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref,
                l_ref, acc_ref, *, scale: float, block_k: int,
                s_valid: Optional[int]):
    """Shared online-softmax body.  ``ks_ref``/``vs_ref`` (optional) hold the
    per-row dequant scales; ``s_valid`` (static) is the true KV extent when
    the last block is partial — rows >= s_valid are uninitialized DMA tail
    and must be zeroed out of the value accumulation (their *scores* are
    already masked: kpos >= s_valid >= lengths[t])."""
    t = pl.program_id(0)
    head = pl.program_id(1)
    sb = pl.program_id(2)
    nsb = pl.num_programs(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks entirely past the token's length contribute nothing — skip the
    # MXU work (the DMA was issued by the index map regardless)
    @pl.when(sb * block_k < len_ref[t])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale               # (G, Dqk)
        k = k_ref[0, 0].astype(jnp.float32)                       # (Bk, Dqk)
        v = v_ref[0, 0].astype(jnp.float32)                       # (Bk, Dv)
        if ks_ref is not None:
            k = k * _head_scale(ks_ref, head)
            v = v * _head_scale(vs_ref, head)
        if s_valid is not None:
            vrow = sb * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(vrow < s_valid, v, 0.0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (G, Bk)

        kpos = sb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < len_ref[t], s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(sb == nsb - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _head_scale(s_ref, head):
    """This grid step's kv-head column of a ``(1, Bk, KV)`` scale tile, as a
    ``(Bk, 1)`` row scale.  The tile carries every head because a block's
    last two dims must be (8, 128)-aligned or whole; a one-hot lane
    reduction picks the head without a dynamic lane slice."""
    sc = s_ref[0]                                                 # (Bk, KV)
    lane = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    return jnp.sum(jnp.where(lane == head, sc, 0.0), axis=1,
                   keepdims=True)


def _kernel(slot_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, scale: float, block_k: int,
            s_valid: Optional[int] = None):
    _flash_step(len_ref, q_ref, k_ref, v_ref, None, None, o_ref, m_ref,
                l_ref, acc_ref, scale=scale, block_k=block_k, s_valid=s_valid)


def _kernel_quant(slot_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_ref, l_ref, acc_ref, *, scale: float, block_k: int,
                  s_valid: Optional[int] = None):
    _flash_step(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref,
                l_ref, acc_ref, scale=scale, block_k=block_k, s_valid=s_valid)


def _kernel_block(bt_ref, slot_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, block_k: int):
    # block-table mode: the physical-block dereference happened in the index
    # map (bt[slot[t] * nb_cols + sb]); the flash math is identical
    _flash_step(len_ref, q_ref, k_ref, v_ref, None, None, o_ref, m_ref,
                l_ref, acc_ref, scale=scale, block_k=block_k, s_valid=None)


def _kernel_block_quant(bt_ref, slot_ref, len_ref, q_ref, k_ref, v_ref,
                        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        scale: float, block_k: int):
    _flash_step(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref,
                l_ref, acc_ref, scale=scale, block_k=block_k, s_valid=None)


@functools.partial(jax.jit, static_argnames=("logit_scale", "kv_bucket",
                                             "block_k", "interpret"))
def packed_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     token_slot: jax.Array, lengths: jax.Array, *,
                     logit_scale: Optional[float] = None,
                     kv_bucket: Optional[int] = None,
                     block_tables: Optional[jax.Array] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_k: int = DEFAULT_BLOCK_K,
                     interpret: bool = False) -> jax.Array:
    """q: (T, H, Dqk) packed queries; k_cache: (N_slots, S, KV, Dqk);
    v_cache: (N_slots, S, KV, Dv); token_slot: (T,) int32 slot per token;
    lengths: (T,) int32 — token t attends rows [0, lengths[t]) of its slot.

    ``kv_bucket`` (static): the caller guarantees ``max(lengths) <=
    kv_bucket``; only the first ``kv_bucket`` cache rows are swept.
    Returns (T, H, Dv).

    ``block_tables`` (optional, (N_slots, S // block_size) int32,
    DESIGN.md §12): block-table mode — the caches are *physical block
    storage* (flat row space N·S carved into fixed-size blocks) and the
    scalar-prefetch gather goes through the table: grid step
    ``(t, kv, sb)`` DMAs physical block ``bt[slot[t], sb]`` instead of slot
    row-block ``sb``.  One extra prefetched operand, same grid, same flash
    math — the compile-cache bound (|T buckets| × |kv buckets|) is
    unchanged because the table is a traced operand of static shape.

    ``k_scale``/``v_scale`` (optional, (N_slots, S, KV) f32, DESIGN.md §15):
    int8 caches — k/v tiles dequantize in-register (``row * scale``) after
    the int8 HBM read; the scale tiles ride the same index maps.
    """
    t, h, d = q.shape
    n, s, kvh, _ = k_cache.shape
    dv = v_cache.shape[-1]
    if block_tables is not None:
        return _packed_attention_block(q, k_cache, v_cache, token_slot,
                                       lengths, block_tables,
                                       k_scale=k_scale, v_scale=v_scale,
                                       logit_scale=logit_scale,
                                       kv_bucket=kv_bucket,
                                       interpret=interpret)
    if kv_bucket is not None and kv_bucket < s:
        k_cache = jax.lax.slice_in_dim(k_cache, 0, kv_bucket, axis=1)
        v_cache = jax.lax.slice_in_dim(v_cache, 0, kv_bucket, axis=1)
        if k_scale is not None:
            k_scale = jax.lax.slice_in_dim(k_scale, 0, kv_bucket, axis=1)
            v_scale = jax.lax.slice_in_dim(v_scale, 0, kv_bucket, axis=1)
        s = kv_bucket
    group = h // kvh
    scale = logit_scale if logit_scale is not None else d ** -0.5

    # masked partial last block instead of an O(cache) pad (DESIGN.md §15):
    # the final tile's DMA tail past ``s`` is uninitialized — scores there
    # are length-masked and the value rows zero-masked in-kernel
    block_k = min(block_k, max(8, s))
    nsb = -(-s // block_k)
    s_valid = s if s % block_k else None

    qf = q.reshape(t, kvh, group, d)
    kf = k_cache.transpose(0, 2, 1, 3)        # (N, KV, S, Dqk)
    vf = v_cache.transpose(0, 2, 1, 3)        # (N, KV, S, Dv)

    grid = (t, kvh, nsb)
    in_specs = [
        pl.BlockSpec((1, 1, group, d),
                     lambda ti, kv, sb, slot, ln: (ti, kv, 0, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ti, kv, sb, slot, ln: (slot[ti], kv, sb, 0)),
        pl.BlockSpec((1, 1, block_k, dv),
                     lambda ti, kv, sb, slot, ln: (slot[ti], kv, sb, 0)),
    ]
    operands = [qf, kf, vf]
    kernel = _kernel
    if k_scale is not None:
        # scales stay (N, S, KV): a (1, Bk, KV) tile is layout-legal where a
        # (1, 1, Bk) tile of the head-major transpose is not
        in_specs += [
            pl.BlockSpec((1, block_k, kvh),
                         lambda ti, kv, sb, slot, ln: (slot[ti], sb, 0)),
            pl.BlockSpec((1, block_k, kvh),
                         lambda ti, kv, sb, slot, ln: (slot[ti], sb, 0)),
        ]
        operands += [k_scale, v_scale]
        kernel = _kernel_quant
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # token_slot, lengths
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, dv),
                               lambda ti, kv, sb, slot, ln: (ti, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),      # m (running max)
            pltpu.VMEM((group,), jnp.float32),      # l (running denom)
            pltpu.VMEM((group, dv), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_k=block_k,
                          s_valid=s_valid),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, group, dv), q.dtype),
        interpret=interpret,
        name="packed_attention",
    )(token_slot, lengths, *operands)
    return out.reshape(t, h, dv)


def _packed_attention_block(q, k_cache, v_cache, token_slot, lengths,
                            block_tables, *, k_scale, v_scale, logit_scale,
                            kv_bucket, interpret):
    """Block-table gather mode (DESIGN.md §12).  The KV grid dimension
    sweeps *logical* blocks 0..kv_bucket/bs; the index map dereferences the
    flattened table so each step's DMA lands on the request's physical
    block.  ``block_k`` is pinned to the block size — a DMA can't span two
    physical blocks that are not adjacent in memory."""
    t, h, d = q.shape
    n, s, kvh, _ = k_cache.shape
    dv = v_cache.shape[-1]
    nb_cols = block_tables.shape[1]
    bs = s // nb_cols
    sweep = s if kv_bucket is None or kv_bucket > s else kv_bucket
    assert sweep % bs == 0, (sweep, bs)
    group = h // kvh
    scale = logit_scale if logit_scale is not None else d ** -0.5

    qf = q.reshape(t, kvh, group, d)
    # physical block storage, KV-heads major so one (block, head) tile DMAs
    # contiguously: (N*S/bs, bs, KV, D) -> (NB, KV, bs, D)
    kf = k_cache.reshape(n * nb_cols, bs, kvh, d).transpose(0, 2, 1, 3)
    vf = v_cache.reshape(n * nb_cols, bs, kvh, dv).transpose(0, 2, 1, 3)
    bt = block_tables.reshape(-1).astype(jnp.int32)

    grid = (t, kvh, sweep // bs)
    in_specs = [
        pl.BlockSpec((1, 1, group, d),
                     lambda ti, kv, sb, bt, slot, ln: (ti, kv, 0, 0)),
        pl.BlockSpec((1, 1, bs, d),
                     lambda ti, kv, sb, bt, slot, ln:
                     (bt[slot[ti] * nb_cols + sb], kv, 0, 0)),
        pl.BlockSpec((1, 1, bs, dv),
                     lambda ti, kv, sb, bt, slot, ln:
                     (bt[slot[ti] * nb_cols + sb], kv, 0, 0)),
    ]
    operands = [qf, kf, vf]
    kernel = _kernel_block
    if k_scale is not None:
        ksf = k_scale.reshape(n * nb_cols, bs, kvh)      # (NB, bs, KV)
        vsf = v_scale.reshape(n * nb_cols, bs, kvh)
        in_specs += [
            pl.BlockSpec((1, bs, kvh),
                         lambda ti, kv, sb, bt, slot, ln:
                         (bt[slot[ti] * nb_cols + sb], 0, 0)),
            pl.BlockSpec((1, bs, kvh),
                         lambda ti, kv, sb, bt, slot, ln:
                         (bt[slot[ti] * nb_cols + sb], 0, 0)),
        ]
        operands += [ksf, vsf]
        kernel = _kernel_block_quant
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # block_tables, token_slot, lengths
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, group, dv),
                               lambda ti, kv, sb, bt, slot, ln: (ti, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),      # m (running max)
            pltpu.VMEM((group,), jnp.float32),      # l (running denom)
            pltpu.VMEM((group, dv), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_k=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, group, dv), q.dtype),
        interpret=interpret,
        name="packed_attention",
    )(bt, token_slot, lengths, *operands)
    return out.reshape(t, h, dv)
