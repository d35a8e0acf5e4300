"""Attention blocks: GQA (with qk-norm) and DeepSeek-V2 MLA.

TP head layout
--------------
When tensor-parallel degree ``tp`` does not divide the head counts we use an
*effective layout* (see DESIGN.md §4):

  * MHA (group==1): pad q and kv heads together to the next multiple of tp.
  * GQA: replicate each kv head r = tp/gcd(kv, tp) times; distribute its g
    q-heads across the replicas in groups of g_eff = ceil(g/r), zero-padding
    the ragged remainder.  Heads are stored kv-major so each shard's q heads
    find their kv head locally.

Padding is numerically exact for inference (padded O-projection rows are
zero-init).  kv replication is exact for inference; for *training* with
tp ∤ kv the replicas are free parameters (slightly larger model) — documented
in DESIGN.md.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import tp
from repro.kernels import ops
from repro.models.layers import apply_rope, apply_rope_nohead, rmsnorm, shard
from repro.models.param import ParamDef
from repro.serving import kvquant


@dataclasses.dataclass(frozen=True)
class HeadLayout:
    n_heads: int          # original q heads
    n_kv: int             # original kv heads
    nh_eff: int
    kv_eff: int
    g_eff: int            # q heads per effective kv head
    replication: int      # kv replication factor r


def head_layout(n_heads: int, n_kv: int, tp: int) -> HeadLayout:
    assert n_heads % n_kv == 0, (n_heads, n_kv)
    g = n_heads // n_kv
    if g == 1:
        nh_eff = kv_eff = math.ceil(n_heads / tp) * tp
        return HeadLayout(n_heads, n_kv, nh_eff, kv_eff, 1, 1)
    r = tp // math.gcd(n_kv, tp)
    kv_eff = n_kv * r
    g_eff = math.ceil(g / r)
    return HeadLayout(n_heads, n_kv, kv_eff * g_eff, kv_eff, g_eff, r)


def qhead_permutation(hl: HeadLayout) -> tuple[list[int], list[int]]:
    """Map original q-head index -> effective slot (kv-major layout).

    Returns (slots, pad_slots): slots[i] = eff index of original q head i;
    pad_slots = eff indices that hold zero-padded heads.
    """
    g = hl.n_heads // hl.n_kv
    slots, used = [], set()
    for h in range(hl.n_heads):
        kv = h // g
        j = h % g                        # index within the kv group
        rep, within = divmod(j, hl.g_eff)
        eff_kv = kv * hl.replication + rep
        slot = eff_kv * hl.g_eff + within
        slots.append(slot)
        used.add(slot)
    pad = [s for s in range(hl.nh_eff) if s not in used]
    return slots, pad


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def gqa_defs(cfg: ModelConfig, tp: int) -> dict:
    hl = head_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dt = cfg.dtype
    defs = {
        "wq": ParamDef((d, hl.nh_eff, hd), ("w_embed", "heads", "head_dim"), dtype=dt),
        "wk": ParamDef((d, hl.kv_eff, hd), ("w_embed", "kv_heads", "head_dim"), dtype=dt),
        "wv": ParamDef((d, hl.kv_eff, hd), ("w_embed", "kv_heads", "head_dim"), dtype=dt),
        "wo": ParamDef((hl.nh_eff, hd, d), ("heads", "head_dim", "w_embed"),
                       dtype=dt, fan_in_axes=(0, 1)),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="ones", dtype=dt)
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="ones", dtype=dt)
    return defs


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """Shared projection path.  x: (B, S, D) -> q (B,S,He,hd), k/v (B,S,KVe,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = shard(q, "batch", "act_seq", "act_heads", None)
    k = shard(k, "batch", "act_seq", "act_kv_heads", None)
    v = shard(v, "batch", "act_seq", "act_kv_heads", None)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_full(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
             *, q_offset=0, kv_prefix: Optional[tuple] = None,
             return_kv: bool = False):
    """Train / prefill attention over the whole chunk.

    kv_prefix: optional (k, v, prefix_len) earlier-cache tensors for chunked
    prefill — prepended to this chunk's K/V before the causal attention.
    """
    q, k, v = _qkv(cfg, p, x, positions)
    k_all, v_all = k, v
    if kv_prefix is not None:
        pk, pv, _plen = kv_prefix
        k_all = jnp.concatenate([pk, k], axis=1)
        v_all = jnp.concatenate([pv, v], axis=1)
    out = ops.flash_attention(q, k_all, v_all, causal=True, q_offset=q_offset)
    out = shard(out, "batch", "act_seq", "act_heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = shard(y, "batch", "act_seq", "embed")
    if return_kv:
        return y, (k, v)
    return y


def gqa_decode(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
               cache: dict, cache_len: jax.Array):
    """Single-token decode.  x: (B, 1, D); cache{k,v}: (B, S, KVe, hd);
    cache_len: (B,) valid positions *before* this token.  Writes the new
    token's KV at cache_len, then attends over cache_len+1 positions."""
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    k_cache = _write_at(cache["k"], k_new[:, 0], cache_len)
    v_cache = _write_at(cache["v"], v_new[:, 0], cache_len)
    k_cache = shard(k_cache, "batch", "kv_seq", "act_kv_heads", None)
    v_cache = shard(v_cache, "batch", "kv_seq", "act_kv_heads", None)
    out = ops.decode_attention(q[:, 0], k_cache, v_cache, cache_len + 1)
    out = shard(out, "batch", "act_heads", None)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None, :]
    y = shard(y, "batch", "act_seq", "embed")
    return y, {"k": k_cache, "v": v_cache}


def gqa_prefill_chunk(cfg: ModelConfig, p: dict, x: jax.Array,
                      positions: jax.Array, cache: dict, cache_len: jax.Array):
    """Incremental chunked prefill (DESIGN.md §7).  x: (B, S_chunk, D);
    cache_len: (B,) prefix tokens already in the cache.  Writes this chunk's
    K/V at the prefix offset, then attends the chunk's queries over the full
    cache with a ``q_offset`` causal mask — positions beyond
    cache_len + S_chunk are never written yet, so the mask excludes them.
    Each prompt token is projected exactly once across chunks (O(p) FLOPs
    instead of the O(p²/chunk) of re-running the prefix per chunk)."""
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    k_cache = _write_seq_at(cache["k"], k_new, cache_len)
    v_cache = _write_seq_at(cache["v"], v_new, cache_len)
    k_cache = shard(k_cache, "batch", "kv_seq", "act_kv_heads", None)
    v_cache = shard(v_cache, "batch", "kv_seq", "act_kv_heads", None)
    out = ops.flash_attention(q, k_cache, v_cache, causal=True,
                              q_offset=cache_len)
    out = shard(out, "batch", "act_seq", "act_heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = shard(y, "batch", "act_seq", "embed")
    return y, {"k": k_cache, "v": v_cache}


def _write_rows(cache: dict, layer: jax.Array, new_rows: dict,
                token_slot: jax.Array, token_wpos: jax.Array, token_dst,
                block_tables) -> dict:
    """Scatter one layer's T new rows per leaf into the group's stacked
    cache (L, N_slots, S, ...), carried through the layer loop (DESIGN.md
    §8): at ``(layer, slot, wpos)``, or in block-table mode (DESIGN.md §12)
    at ``(layer, token_dst)`` of the (L, N·S, ...) view.  Padding rows
    carry an out-of-bounds ``wpos``/``dst`` and are dropped; only the T
    rows move, the stack is updated in place.  Returns the new stack; GQA
    hands it to the kernel as it is, MLA cuts its layer's view from it
    (``_layer_views``)."""
    new_cache = {}
    for name, val in new_rows.items():
        stack = cache[name]
        val = val.astype(stack.dtype)
        if block_tables is None:
            stack = stack.at[layer, token_slot, token_wpos].set(
                val, mode="drop")
        else:
            n_layers, n, s = stack.shape[:3]
            flat = stack.reshape((n_layers, n * s) + stack.shape[3:])
            stack = flat.at[layer, token_dst].set(
                val, mode="drop").reshape(stack.shape)
        new_cache[name] = stack
    return new_cache


def _layer_views(cache: dict, layer: jax.Array, kv_bucket: Optional[int],
                 block_tables) -> dict:
    """The rows of layer ``layer`` that MLA's absorbed concat reads, one
    dynamic slice of each stacked leaf: the first ``kv_bucket`` rows of
    each slot, or in block-table mode the layer's whole storage (any
    block may be in a slot's table).  Indexing the layer first and cutting
    its rows afterwards makes XLA transpose all S rows."""
    views = {}
    for name, stack in cache.items():
        n, s = stack.shape[1:3]
        rows = s if kv_bucket is None or block_tables is not None \
            else min(kv_bucket, s)
        views[name] = jax.lax.dynamic_slice(
            stack, (layer,) + (0,) * (stack.ndim - 1),
            (1, n, rows) + stack.shape[3:])[0]
    return views


def _block_view(cache: jax.Array, block_tables: jax.Array,
                kv_bucket: Optional[int]) -> jax.Array:
    """Gather a block-table cache back into per-slot contiguous logical
    rows, (N, kv_bucket, ...) — the dense-read analogue of the Pallas
    kernel's index-map dereference (used by the MLA latent path, where the
    absorbed concat needs a materialized view anyway)."""
    n, s = cache.shape[:2]
    nb_cols = block_tables.shape[1]
    bs = s // nb_cols
    sweep = s if kv_bucket is None or kv_bucket > s else kv_bucket
    flat = cache.reshape((n * nb_cols, bs) + cache.shape[2:])
    view = flat[block_tables[:, :sweep // bs]]
    return view.reshape((n, sweep) + cache.shape[2:])


def gqa_packed(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
               cache: dict, layer: jax.Array, token_slot: jax.Array,
               token_wpos: jax.Array, kv_bucket: Optional[int] = None,
               token_dst=None, block_tables=None):
    """Token-packed dense-batch step (DESIGN.md §8).  x: (1, T, D) — the
    iteration's decode tokens and *all* prefill-chunk tokens packed into one
    stream; positions: (1, T) absolute position of each token in its own
    request; cache{k,v}: (L, N_slots, S, KVe, hd) — the group's *stacked*
    slot cache, carried through the layer loop, of which this is layer
    ``layer`` (traced).  Scatters each token's K/V at ``(layer, slot,
    wpos)`` (``wpos == S`` for padding tokens → dropped), then runs
    segment-masked attention: token t attends rows [0, positions[t]] of its
    own slot only, which covers the carried prefix *and* same-segment
    tokens written by this very dispatch.  Returns the updated stack: only
    the T new rows are written.

    ``kv_bucket`` (static, DESIGN.md §9): the engine's bound on this
    iteration's ``max(positions) + 1`` — attention reads only that many
    cache rows per slot, so its FLOPs/bytes scale with actual context, not
    ``max_len``.  The updated stack goes to the kernel whole, with
    ``layer``: it reads that layer's rows in place, with no per-layer copy
    or transpose of the cache in between (DESIGN.md §9).

    Under tensor parallelism (DESIGN.md §11) the projections and the slot
    cache are sharded along (kv-)heads, attention is per-head local, and
    only the output projection reduces across shards
    (``tp.out_project`` — a nano-batch-chunked ring all-reduce).

    Block-table mode (DESIGN.md §12, ``token_dst``/``block_tables`` set):
    the same leaves are treated as physical block storage — K/V scatter at
    flat row ``token_dst[t]`` of the layer and attention gathers through
    the per-slot table, so requests can share immutable prefix blocks.
    TP-safe: the reshapes fold the unsharded (slot, seq) axes only.

    int8 KV (DESIGN.md §15, scale leaves ``k_s``/``v_s`` present): each
    token's post-rope K/V row quantizes *in-program* at scatter time
    (symmetric per-(token, kv-head), f32 scale rides the same scatter), and
    attention dequantizes in-register after the int8 HBM read — the kernel
    receives the int8 leaves plus the scale tiles, never a dense f32 copy."""
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    quantized = "k_s" in cache
    if quantized:
        k_val, k_s_new = kvquant.quantize_kv(k_new[0])
        v_val, v_s_new = kvquant.quantize_kv(v_new[0])
        new_rows = {"k": k_val, "v": v_val, "k_s": k_s_new, "v_s": v_s_new}
    else:
        new_rows = {"k": k_new[0], "v": v_new[0]}
    new_cache = _write_rows(cache, layer, new_rows, token_slot, token_wpos,
                            token_dst, block_tables)
    scales = {}
    if quantized:
        scales = {"k_scale": new_cache["k_s"], "v_scale": new_cache["v_s"]}
    out = ops.packed_attention(q[0], new_cache["k"], new_cache["v"],
                               token_slot, positions[0] + 1, layer=layer,
                               kv_bucket=kv_bucket,
                               block_tables=block_tables, **scales)
    y = tp.out_project(out, p["wo"])[None]
    y = shard(y, "batch", "act_seq", "embed")
    return y, new_cache


def _write_at(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """cache: (B, S, ...); new: (B, ...); idx: (B,) — per-row dynamic write."""
    def one(c, n, i):
        return jax.lax.dynamic_update_slice(c, n[None], (i,) + (0,) * (c.ndim - 1))
    return jax.vmap(one)(cache, new, idx)


def _write_seq_at(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """cache: (B, S, ...); new: (B, s, ...); idx: (B,) — write the s rows of
    each batch row at its own offset (partial-prefix write, chunked prefill)."""
    def one(c, n, i):
        return jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (i,) + (0,) * (c.ndim - 1))
    return jax.vmap(one)(cache, new, idx)


def gqa_init_cache(cfg: ModelConfig, tp: int, batch: int, max_len: int,
                   kv_dtype: Optional[str] = None) -> dict:
    hl = head_layout(cfg.n_heads, cfg.n_kv_heads, tp)
    hd = cfg.resolved_head_dim
    shape = (batch, max_len, hl.kv_eff, hd)
    if kv_dtype == "int8":
        # int8 value leaves + f32 per-(token, kv-head) scale leaves
        # (DESIGN.md §15) — same (batch, seq, kv-head) leading layout, so
        # CoW / block-table / TP paths treat them like any other leaf
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(shape[:-1], jnp.float32),
                "v_s": jnp.zeros(shape[:-1], jnp.float32)}
    return {"k": jnp.zeros(shape, jnp.dtype(cfg.dtype)),
            "v": jnp.zeros(shape, jnp.dtype(cfg.dtype))}


def gqa_cache_axes(kv_dtype: Optional[str] = None) -> dict:
    axes = {"k": ("batch", "kv_seq", "act_kv_heads", None),
            "v": ("batch", "kv_seq", "act_kv_heads", None)}
    if kv_dtype == "int8":
        axes["k_s"] = ("batch", "kv_seq", "act_kv_heads")
        axes["v_s"] = ("batch", "kv_seq", "act_kv_heads")
    return axes


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank latent KV; the cache holds only (c_kv, k_rope)
# per token.  *All* paths (train/prefill, chunked prefill, decode) run the
# absorbed form: W_uk is folded into the query and W_uv applied after the
# softmax, so attention runs entirely in the (rank + rope) latent — a GQA
# with a single shared kv "head".  One association order everywhere means
# prefill and decode agree to kernel precision; the earlier split (naive
# per-head prefill vs absorbed decode) rounded differently in bf16, and MoE
# routing amplified those ulps into expert flips
# (test_prefill_decode_consistency[deepseek-v2-236b]).
# ---------------------------------------------------------------------------
def mla_defs(cfg: ModelConfig, tp: int) -> dict:
    m = cfg.mla
    assert m is not None
    d, dt = cfg.d_model, cfg.dtype
    nh = math.ceil(cfg.n_heads / tp) * tp          # pad heads to tp multiple
    qk = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wdq": ParamDef((d, m.q_lora_rank), ("w_embed", "lora"), dtype=dt),
        "q_ln": ParamDef((m.q_lora_rank,), ("lora",), init="ones", dtype=dt),
        "wuq": ParamDef((m.q_lora_rank, nh, qk), ("lora", "heads", "head_dim"), dtype=dt),
        "wdkv": ParamDef((d, m.kv_lora_rank), ("w_embed", "lora"), dtype=dt),
        "kv_ln": ParamDef((m.kv_lora_rank,), ("lora",), init="ones", dtype=dt),
        "wkr": ParamDef((d, m.qk_rope_dim), ("w_embed", "head_dim"), dtype=dt),
        "wuk": ParamDef((m.kv_lora_rank, nh, m.qk_nope_dim),
                        ("lora", "heads", "head_dim"), dtype=dt),
        "wuv": ParamDef((m.kv_lora_rank, nh, m.v_head_dim),
                        ("lora", "heads", "head_dim"), dtype=dt),
        "wo": ParamDef((nh, m.v_head_dim, d), ("heads", "head_dim", "w_embed"),
                       dtype=dt, fan_in_axes=(0, 1)),
    }


def _mla_q(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    cq = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wdq"]), p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"])
    q = shard(q, "batch", "act_seq", "act_heads", None)
    q_nope = q[..., : m.qk_nope_dim]
    q_rope = apply_rope(q[..., m.qk_nope_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    c_kv = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wdkv"]), p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope_nohead(jnp.einsum("bsd,dr->bsr", x, p["wkr"]),
                               positions, cfg.rope_theta)
    return c_kv, k_rope          # (B,S,rank), (B,S,rope_dim)


def _mla_q_absorbed(cfg: ModelConfig, p: dict, x: jax.Array,
                    positions: jax.Array) -> jax.Array:
    """Absorbed query: q_nope · W_uk folded into the latent.
    Returns (B, S, H, rank + rope_dim)."""
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def _mla_unabsorb(p: dict, out_lat: jax.Array, dtype) -> jax.Array:
    """probs·c_kv latent context -> per-head values via W_uv.
    out_lat: (B[, S], H, rank) -> (B[, S], H, v_head_dim)."""
    return jnp.einsum("...hr,rhk->...hk", out_lat.astype(dtype), p["wuv"])


def mla_full(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
             *, q_offset=0, kv_prefix: Optional[tuple] = None,
             return_kv: bool = False):
    """Absorbed MLA for train/prefill: attention over the latent KV with a
    single shared kv head (group = n_heads)."""
    m = cfg.mla
    q_abs = _mla_q_absorbed(cfg, p, x, positions)        # (B,S,H,rank+rope)
    c_kv, k_rope = _mla_latent(cfg, p, x, positions)
    if kv_prefix is not None:
        pc, pr, _plen = kv_prefix
        c_kv_all = jnp.concatenate([pc, c_kv], axis=1)
        k_rope_all = jnp.concatenate([pr, k_rope], axis=1)
    else:
        c_kv_all, k_rope_all = c_kv, k_rope
    k_abs = jnp.concatenate([c_kv_all, k_rope_all], axis=-1)[:, :, None, :]
    v_lat = c_kv_all[:, :, None, :]                      # (B,S,1,rank)
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out_lat = ops.flash_attention(q_abs, k_abs, v_lat, causal=True,
                                  logit_scale=scale, q_offset=q_offset)
    out = _mla_unabsorb(p, out_lat, x.dtype)
    out = shard(out, "batch", "act_seq", "act_heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = shard(y, "batch", "act_seq", "embed")
    if return_kv:
        return y, (c_kv, k_rope)
    return y


def mla_decode(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
               cache: dict, cache_len: jax.Array):
    """Absorbed decode: scores and context computed in the latent, through
    the same decode_attention kernel the GQA path uses (KV head = 1)."""
    m = cfg.mla
    q_abs = _mla_q_absorbed(cfg, p, x, positions)        # (B,1,H,rank+rope)
    c_new, r_new = _mla_latent(cfg, p, x, positions)     # (B,1,rank/rope)
    ckv = _write_at(cache["c_kv"], c_new[:, 0], cache_len)
    krp = _write_at(cache["k_rope"], r_new[:, 0], cache_len)
    ckv = shard(ckv, "batch", "kv_seq", None)
    k_abs = jnp.concatenate([ckv, krp], axis=-1)[:, :, None, :]
    v_lat = ckv[:, :, None, :]
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out_lat = ops.decode_attention(q_abs[:, 0], k_abs, v_lat, cache_len + 1,
                                   logit_scale=scale)
    out = _mla_unabsorb(p, out_lat, x.dtype)
    out = shard(out, "batch", "act_heads", None)
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None, :]
    y = shard(y, "batch", "act_seq", "embed")
    return y, {"c_kv": ckv, "k_rope": krp}


def mla_prefill_chunk(cfg: ModelConfig, p: dict, x: jax.Array,
                      positions: jax.Array, cache: dict, cache_len: jax.Array):
    """Incremental chunked prefill for MLA (DESIGN.md §7): write the chunk's
    latents at the prefix offset, attend absorbed queries over the latent
    cache.  No per-head K/V is ever materialized — the prefix cost per chunk
    is O(S_cache · (rank + rope)), not O(S_cache · heads · head_dim)."""
    m = cfg.mla
    q_abs = _mla_q_absorbed(cfg, p, x, positions)        # (B,s,H,rank+rope)
    c_new, r_new = _mla_latent(cfg, p, x, positions)
    ckv = _write_seq_at(cache["c_kv"], c_new, cache_len)
    krp = _write_seq_at(cache["k_rope"], r_new, cache_len)
    ckv = shard(ckv, "batch", "kv_seq", None)
    k_abs = jnp.concatenate([ckv, krp], axis=-1)[:, :, None, :]
    v_lat = ckv[:, :, None, :]
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out_lat = ops.flash_attention(q_abs, k_abs, v_lat, causal=True,
                                  logit_scale=scale, q_offset=cache_len)
    out = _mla_unabsorb(p, out_lat, x.dtype)
    out = shard(out, "batch", "act_seq", "act_heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = shard(y, "batch", "act_seq", "embed")
    return y, {"c_kv": ckv, "k_rope": krp}


def mla_packed(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
               cache: dict, layer: jax.Array, token_slot: jax.Array,
               token_wpos: jax.Array, kv_bucket: Optional[int] = None,
               token_dst=None, block_tables=None):
    """Token-packed step for MLA (DESIGN.md §8): scatter each token's
    latents at ``(layer, slot, wpos)`` of the stacked cache (as
    ``gqa_packed``), attend absorbed queries over the slot's latent cache
    of that layer with the segment/length mask.  Same absorbed association
    order as every other MLA path.  ``d_v != d_qk`` (latent rank vs
    rank + rope) is handled natively by the packed-attention kernel.

    ``kv_bucket`` (static, DESIGN.md §9) cuts the latent views from the
    stack *before* the absorbed-key concat, so the materialized (N, S, rank + rope) key
    tensor also scales with the bucket, not ``max_len``.

    Under tensor parallelism (DESIGN.md §11) the latent path — ``c_kv`` /
    ``k_rope`` and their cache — is replicated (it is one shared kv
    "head"); the absorbed per-head projections are sharded along heads and
    the output projection reduces across shards (``tp.out_project``).

    Block-table mode (DESIGN.md §12): latents scatter at their flat
    physical rows and the bucket view is a per-slot *gather* through the
    block table instead of a slice — the absorbed concat then proceeds on
    the logical view, so the dense latent attention (one shared kv "head")
    needs no kernel-side table.

    int8 KV (DESIGN.md §15, ``c_kv_s``/``k_rope_s`` present): only the
    latent/rope leaves quantize (the cache stores nothing else) —
    per-(token,) symmetric scales scatter alongside, and the bucketed
    *views* dequantize right before the absorbed concat, so the int8 HBM
    read feeds the same flash kernel unchanged."""
    m = cfg.mla
    q_abs = _mla_q_absorbed(cfg, p, x, positions)        # (1,T,H,rank+rope)
    c_new, r_new = _mla_latent(cfg, p, x, positions)
    quantized = "c_kv_s" in cache
    if quantized:
        c_val, c_s_new = kvquant.quantize_kv(c_new[0])
        r_val, r_s_new = kvquant.quantize_kv(r_new[0])
        new_rows = {"c_kv": c_val, "k_rope": r_val,
                    "c_kv_s": c_s_new, "k_rope_s": r_s_new}
    else:
        new_rows = {"c_kv": c_new[0], "k_rope": r_new[0]}
    new_cache = _write_rows(cache, layer, new_rows, token_slot, token_wpos,
                            token_dst, block_tables)
    view = _layer_views(new_cache, layer, kv_bucket, block_tables)
    view["c_kv"] = shard(view["c_kv"], "batch", "kv_seq", None)
    if block_tables is not None:
        view = {name: _block_view(a, block_tables, kv_bucket)
                for name, a in view.items()}
    ckv_v, krp_v = view["c_kv"], view["k_rope"]
    if quantized:
        ckv_v = kvquant.dequantize_kv(ckv_v, view["c_kv_s"], x.dtype)
        krp_v = kvquant.dequantize_kv(krp_v, view["k_rope_s"], x.dtype)
    k_abs = jnp.concatenate([ckv_v, krp_v], axis=-1)[:, :, None, :]
    v_lat = ckv_v[:, :, None, :]
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    out_lat = ops.packed_attention(q_abs[0], k_abs, v_lat, token_slot,
                                   positions[0] + 1, logit_scale=scale)
    out = _mla_unabsorb(p, out_lat, x.dtype)             # (T,H,v_head)
    out = shard(out[None], "batch", "act_seq", "act_heads", None)[0]
    y = tp.out_project(out, p["wo"])[None]
    y = shard(y, "batch", "act_seq", "embed")
    return y, new_cache


def mla_init_cache(cfg: ModelConfig, tp: int, batch: int, max_len: int,
                   kv_dtype: Optional[str] = None) -> dict:
    m = cfg.mla
    if kv_dtype == "int8":
        return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), jnp.int8),
                "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), jnp.int8),
                "c_kv_s": jnp.zeros((batch, max_len), jnp.float32),
                "k_rope_s": jnp.zeros((batch, max_len), jnp.float32)}
    dt = jnp.dtype(cfg.dtype)
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dt)}


def mla_cache_axes(kv_dtype: Optional[str] = None) -> dict:
    axes = {"c_kv": ("batch", "kv_seq", None),
            "k_rope": ("batch", "kv_seq", None)}
    if kv_dtype == "int8":
        axes["c_kv_s"] = ("batch", "kv_seq")
        axes["k_rope_s"] = ("batch", "kv_seq")
    return axes
