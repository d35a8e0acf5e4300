"""Per-layer block dispatch: LayerSpec -> param defs / forward / cache."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, FFN_DENSE, FFN_MOE, FFN_MOE_DENSE,
                                FFN_NONE, MAMBA, MLSTM, SLSTM, LayerSpec,
                                ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import rmsnorm
from repro.models.param import ParamDef


def block_defs(cfg: ModelConfig, spec: LayerSpec, tp: int) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    defs: dict = {"norm1": ParamDef((d,), ("w_embed",), init="ones", dtype=dt)}
    if spec.mixer == ATTN:
        defs["mixer"] = (attn.mla_defs(cfg, tp) if cfg.mla is not None
                         else attn.gqa_defs(cfg, tp))
    elif spec.mixer == MAMBA:
        defs["mixer"] = ssm_mod.mamba_defs(cfg, tp)
    elif spec.mixer == MLSTM:
        defs["mixer"] = xlstm_mod.mlstm_defs(cfg, tp)
    elif spec.mixer == SLSTM:
        defs["mixer"] = xlstm_mod.slstm_defs(cfg, tp)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != FFN_NONE:
        defs["norm2"] = ParamDef((d,), ("w_embed",), init="ones", dtype=dt)
        if spec.ffn == FFN_DENSE:
            defs["ffn"] = moe_mod._ffn_defs(d, cfg.d_ff, dt, cfg.ffn_gated)
        elif spec.ffn == FFN_MOE:
            defs["ffn"] = moe_mod.moe_defs(cfg)
        elif spec.ffn == FFN_MOE_DENSE:
            defs["ffn"] = moe_mod.moe_defs(cfg, dense_residual=True)
        else:
            raise ValueError(spec.ffn)
    return defs


def _ffn_apply(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jax.Array):
    if spec.ffn == FFN_NONE:
        return x, jnp.zeros((), jnp.float32)
    h = rmsnorm(x, p["norm2"], cfg.norm_eps)
    if spec.ffn == FFN_DENSE:
        return x + moe_mod.dense_ffn(p["ffn"], h), jnp.zeros((), jnp.float32)
    y, aux = moe_mod.moe_ffn(cfg, p["ffn"], h,
                             dense_residual=(spec.ffn == FFN_MOE_DENSE))
    return x + y, aux


def block_full(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jax.Array,
               positions: jax.Array, *, q_offset=0,
               initial: Optional[dict] = None, return_state: bool = False):
    """Train/prefill.  Returns (x, aux_loss[, state])."""
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    state = None
    if spec.mixer == ATTN:
        kv_prefix = initial.get("kv_prefix") if initial else None
        fn = attn.mla_full if cfg.mla is not None else attn.gqa_full
        if return_state:
            y, kv = fn(cfg, p["mixer"], h, positions, q_offset=q_offset,
                       kv_prefix=kv_prefix, return_kv=True)
            state = {"kv": kv}
        else:
            y = fn(cfg, p["mixer"], h, positions, q_offset=q_offset,
                   kv_prefix=kv_prefix)
    elif spec.mixer == MAMBA:
        r = ssm_mod.mamba_full(cfg, p["mixer"], h, initial=initial,
                               return_state=return_state)
        y, state = r if return_state else (r, None)
    elif spec.mixer == MLSTM:
        r = xlstm_mod.mlstm_full(cfg, p["mixer"], h, initial=initial,
                                 return_state=return_state)
        y, state = r if return_state else (r, None)
    elif spec.mixer == SLSTM:
        r = xlstm_mod.slstm_full(cfg, p["mixer"], h, initial=initial,
                                 return_state=return_state)
        y, state = r if return_state else (r, None)
    else:
        raise ValueError(spec.mixer)
    x = x + y
    x, aux = _ffn_apply(cfg, spec, p, x)
    if return_state:
        return x, aux, state
    return x, aux


def block_decode(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jax.Array,
                 positions: jax.Array, cache: dict, cache_len: jax.Array):
    """Single-token decode.  Returns (x, new_cache)."""
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == ATTN:
        fn = attn.mla_decode if cfg.mla is not None else attn.gqa_decode
        y, new_cache = fn(cfg, p["mixer"], h, positions, cache, cache_len)
    elif spec.mixer == MAMBA:
        y, new_cache = ssm_mod.mamba_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == MLSTM:
        y, new_cache = xlstm_mod.mlstm_decode(cfg, p["mixer"], h, cache)
    elif spec.mixer == SLSTM:
        y, new_cache = xlstm_mod.slstm_decode(cfg, p["mixer"], h, cache)
    else:
        raise ValueError(spec.mixer)
    x = x + y
    x, _aux = _ffn_apply(cfg, spec, p, x)
    return x, new_cache


def block_chunk(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jax.Array,
                positions: jax.Array, cache: dict, cache_len: jax.Array):
    """Incremental chunked prefill (DESIGN.md §7): run a multi-token chunk
    against the carried cache.  Attention writes the chunk's K/V (latents)
    at the ``cache_len`` offset and attends over the prefix; recurrent
    mixers resume from the cached state (cache format == state format).
    Returns (x, new_cache) — same contract as ``block_decode``."""
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == ATTN:
        fn = attn.mla_prefill_chunk if cfg.mla is not None \
            else attn.gqa_prefill_chunk
        y, new_cache = fn(cfg, p["mixer"], h, positions, cache, cache_len)
    elif spec.mixer == MAMBA:
        y, new_cache = ssm_mod.mamba_full(cfg, p["mixer"], h, initial=cache,
                                          return_state=True)
    elif spec.mixer == MLSTM:
        y, new_cache = xlstm_mod.mlstm_full(cfg, p["mixer"], h, initial=cache,
                                            return_state=True)
    elif spec.mixer == SLSTM:
        y, new_cache = xlstm_mod.slstm_full(cfg, p["mixer"], h, initial=cache,
                                            return_state=True)
    else:
        raise ValueError(spec.mixer)
    x = x + y
    x, _aux = _ffn_apply(cfg, spec, p, x)
    return x, new_cache


def block_packed(cfg: ModelConfig, spec: LayerSpec, p: dict, x: jax.Array,
                 positions: jax.Array, cache: dict, layer: jax.Array,
                 token_slot: jax.Array, token_wpos: jax.Array,
                 token_active: jax.Array, kv_bucket: Optional[int] = None,
                 token_dst=None, block_tables=None):
    """Token-packed dense-batch step (DESIGN.md §8): one (1, T) stream
    holding the iteration's decode tokens and all prefill-chunk tokens with
    per-token (slot, position) metadata, run against the *whole* slot cache.
    ``cache`` is the group's stacked cache (leading layer axis), carried
    through the layer loop; ``layer`` (traced) picks this block's layer.
    Attention scatters only its T new K/V rows into the stack at (layer,
    slot, wpos), applies the segment mask, and reads only ``kv_bucket``
    cache rows per slot (KV-length bucketing, DESIGN.md §9; ``None`` = full
    ``max_len``); recurrent mixers read their layer's per-slot state,
    advance it with active-masking, and write the layer back.
    ``token_dst``/``block_tables`` switch attention to block-table mode
    (DESIGN.md §12; attention-only — the engine rejects prefix caching for
    models with recurrent mixers).
    Returns (x, new stacked cache)."""
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == ATTN:
        fn = attn.mla_packed if cfg.mla is not None else attn.gqa_packed
        y, new_cache = fn(cfg, p["mixer"], h, positions, cache, layer,
                          token_slot, token_wpos, kv_bucket=kv_bucket,
                          token_dst=token_dst, block_tables=block_tables)
    else:
        if spec.mixer == MAMBA:
            fn = ssm_mod.mamba_packed
        elif spec.mixer == MLSTM:
            fn = xlstm_mod.mlstm_packed
        elif spec.mixer == SLSTM:
            fn = xlstm_mod.slstm_packed
        else:
            raise ValueError(spec.mixer)
        state = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            cache)
        y, state = fn(cfg, p["mixer"], h, state, token_slot, token_active)
        new_cache = jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, layer, 0),
            cache, state)
    x = x + y
    x, _aux = _ffn_apply(cfg, spec, p, x)
    return x, new_cache


def block_init_cache(cfg: ModelConfig, spec: LayerSpec, tp: int, batch: int,
                     max_len: int, kv_dtype: str | None = None) -> dict:
    if spec.mixer == ATTN:
        return (attn.mla_init_cache(cfg, tp, batch, max_len, kv_dtype)
                if cfg.mla is not None
                else attn.gqa_init_cache(cfg, tp, batch, max_len, kv_dtype))
    if spec.mixer == MAMBA:
        return ssm_mod.mamba_init_cache(cfg, tp, batch)
    if spec.mixer == MLSTM:
        return xlstm_mod.mlstm_init_cache(cfg, tp, batch)
    if spec.mixer == SLSTM:
        return xlstm_mod.slstm_init_cache(cfg, tp, batch)
    raise ValueError(spec.mixer)


def block_cache_axes(cfg: ModelConfig, spec: LayerSpec,
                     kv_dtype: str | None = None) -> dict:
    if spec.mixer == ATTN:
        return (attn.mla_cache_axes(kv_dtype) if cfg.mla is not None
                else attn.gqa_cache_axes(kv_dtype))
    if spec.mixer == MAMBA:
        return ssm_mod.mamba_cache_axes()
    if spec.mixer == MLSTM:
        return xlstm_mod.mlstm_cache_axes()
    if spec.mixer == SLSTM:
        return xlstm_mod.slstm_cache_axes()
    raise ValueError(spec.mixer)
