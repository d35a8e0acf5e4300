"""Unified LM: embedding/frontend -> scanned decoder groups -> head.

Three execution paths (all pure functions of (cfg, params, ...)):
  * ``forward_full``  — train / prefill over a whole token chunk
  * ``forward_decode``— one-token decode against a carried cache
  * ``loss_fn``       — token-level xent (+ MoE aux) on top of forward_full

Layers are grouped by ``cfg.layer_groups()`` and executed with
``jax.lax.scan`` over stacked parameter pytrees, so HLO size (and compile
time on this 1-core container) stays flat in depth.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import blocks
from repro.models.layers import cross_entropy, rmsnorm, shard
from repro.models.param import (ParamDef, count_params, init_params, map_defs,
                                param_shapes, stack_defs)

# ---------------------------------------------------------------------------
# definitions
# ---------------------------------------------------------------------------


def model_defs(cfg: ModelConfig, tp: int = 1) -> dict:
    d, v, dt = cfg.d_model, cfg.vocab_size, cfg.dtype
    defs: dict = {}
    if cfg.frontend == "audio":
        k = cfg.num_codebooks
        defs["embed"] = ParamDef((k, v, d), (None, "vocab", "w_embed"),
                                 dtype=dt, fan_in_axes=(1,))
        defs["head"] = ParamDef((d, k, v), ("w_embed", None, "vocab"), dtype=dt)
    else:
        defs["embed"] = ParamDef((v, d), ("vocab", "w_embed"), dtype=dt)
        if not cfg.tie_embeddings:
            defs["head"] = ParamDef((d, v), ("w_embed", "vocab"), dtype=dt)
    if cfg.frontend == "vision":
        defs["patch_proj"] = ParamDef((d, d), ("w_embed", None), dtype=dt)
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        group = {f"sub{i}": blocks.block_defs(cfg, spec, tp)
                 for i, spec in enumerate(pattern)}
        defs[f"group{gi}"] = stack_defs(group, reps)
    defs["final_norm"] = ParamDef((d,), ("w_embed",), init="ones", dtype=dt)
    return defs


def init(cfg: ModelConfig, key: jax.Array, tp: int = 1) -> dict:
    return init_params(model_defs(cfg, tp), key)


def shapes(cfg: ModelConfig, tp: int = 1, mesh=None, rules=None) -> dict:
    return param_shapes(model_defs(cfg, tp), mesh, rules)


def num_params(cfg: ModelConfig) -> int:
    return count_params(model_defs(cfg, tp=1))


def active_params(cfg: ModelConfig) -> int:
    """6·N_active·D — total minus the inactive routed-expert fraction."""
    tree = model_defs(cfg, tp=1)
    total = count_params(tree)
    if cfg.moe is None:
        return total
    expert_total = 0

    def leaf(path, d: ParamDef):
        nonlocal expert_total
        if "experts" in d.axes and path[-1] in ("w_gate", "w_up", "w_down"):
            import numpy as np
            expert_total += int(np.prod(d.shape))
        return None

    map_defs(leaf, tree)
    frac = cfg.moe.top_k / cfg.moe.num_experts
    return total - expert_total + int(expert_total * frac)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------
def _embed(cfg: ModelConfig, params: dict, tokens: jax.Array,
           patches: Optional[jax.Array] = None) -> jax.Array:
    if cfg.frontend == "audio":
        # tokens: (B, S, K) — sum codebook embeddings
        k = cfg.num_codebooks
        parts = [jnp.take(params["embed"][i], tokens[..., i], axis=0)
                 for i in range(k)]
        x = functools.reduce(jnp.add, parts)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "vision" and patches is not None:
        pe = jnp.einsum("bsd,dk->bsk", patches.astype(x.dtype),
                        params["patch_proj"])
        x = jnp.concatenate([pe, x], axis=1)
    return shard(x, "batch", "act_seq", "embed")


def _head(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.frontend == "audio":
        logits = jnp.einsum("bsd,dkv->bskv", x, params["head"])
        return shard(logits, "batch", "act_seq", None, "act_vocab")
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return shard(logits, "batch", "act_seq", "act_vocab")


# ---------------------------------------------------------------------------
# forward: full chunk (train / prefill)
# ---------------------------------------------------------------------------
def forward_full(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
                 patches: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None,
                 q_offset: int | jax.Array = 0,
                 initial_states: Optional[list] = None,
                 return_states: bool = False,
                 remat: str = "none"):
    """Returns (logits, aux_loss[, states]).

    ``states``: per-group stacked mixer states (KV for attention, recurrent
    state for SSM/LSTM) for handing off to the decode path.

    ``initial_states``: carry state from an earlier chunk, in the exact
    structure this function returns via ``return_states`` — threading it
    (plus ``q_offset`` / ``positions`` set to the prefix length) continues a
    chunked prefill without recomputing the prefix (DESIGN.md §7).  For
    attention the state holds the prefix KV (latents), which is concatenated
    before the causal attention; recurrent mixers resume exactly.
    """
    x = _embed(cfg, params, tokens, patches)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        # q_offset may be a scalar or per-row (B,) — reshape to a column so
        # it broadcasts over the sequence axis (matching causal_qmask)
        qo = jnp.asarray(q_offset, jnp.int32).reshape(-1, 1)
        positions = jnp.arange(s, dtype=jnp.int32)[None, :] + jnp.zeros(
            (b, 1), jnp.int32) + qo
    aux = jnp.zeros((), jnp.float32)
    states: list[Any] = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        stacked = params[f"group{gi}"]
        init_g = initial_states[gi] if initial_states is not None else None

        def body(carry, xs, _pattern=pattern, _has_init=init_g is not None):
            x, aux = carry
            layer_p, layer_init = xs if _has_init else (xs, None)
            sts = {}
            for i, spec in enumerate(_pattern):
                init_i = None
                if layer_init is not None:
                    init_i = _state_to_initial(spec, layer_init[f"sub{i}"])
                if return_states:
                    x, a, st = blocks.block_full(
                        cfg, spec, layer_p[f"sub{i}"], x, positions,
                        q_offset=q_offset, initial=init_i, return_state=True)
                    sts[f"sub{i}"] = st
                else:
                    x, a = blocks.block_full(cfg, spec, layer_p[f"sub{i}"], x,
                                             positions, q_offset=q_offset,
                                             initial=init_i)
                aux = aux + a
            return (x, aux), (sts if return_states else None)

        if remat != "none":
            body = _remat(body, remat)
        xs = (stacked, init_g) if init_g is not None else stacked
        (x, aux), sts = jax.lax.scan(body, (x, aux), xs)
        states.append(sts)
    logits = _head(cfg, params, x)
    if return_states:
        return logits, aux, states
    return logits, aux


def _state_to_initial(spec, state: dict) -> dict:
    """Returned-state structure -> ``block_full(initial=...)`` structure.
    Attention states hold the prefix KV pair under "kv"; block_full expects
    it as ``kv_prefix`` (the prefix length is implied by the array shape).
    Recurrent states pass through unchanged (state format == cache format)."""
    from repro.configs.base import ATTN
    if spec.mixer == ATTN:
        pk, pv = state["kv"]
        return {"kv_prefix": (pk, pv, pk.shape[1])}
    return state


def _remat(body, policy: str):
    policies = {
        "full": None,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    return jax.checkpoint(body, policy=policies[policy], prevent_cse=False)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict, *,
            remat: str = "none", aux_weight: float = 0.01):
    """batch: {tokens, labels[, patches]} — labels ignore index < 0."""
    logits, aux = forward_full(cfg, params, batch["tokens"],
                               patches=batch.get("patches"), remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vision" and batch.get("patches") is not None:
        # loss only on text positions (after the patch prefix)
        n_patch = batch["patches"].shape[1]
        logits = logits[:, n_patch:]
    mask = (labels >= 0).astype(jnp.float32)
    xent = cross_entropy(logits, jnp.maximum(labels, 0), mask)
    return xent + aux_weight * aux, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# cache: per-group stacked block caches
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, tp: int, batch: int, max_len: int,
               kv_dtype: str | None = None) -> list:
    out = []
    for pattern, reps in cfg.layer_groups():
        group = {}
        for i, spec in enumerate(pattern):
            one = blocks.block_init_cache(cfg, spec, tp, batch, max_len,
                                          kv_dtype)
            group[f"sub{i}"] = jax.tree.map(
                lambda a: jnp.tile(a[None], (reps,) + (1,) * a.ndim), one)
        out.append(group)
    return out


def cache_shapes(cfg: ModelConfig, tp: int, batch: int, max_len: int,
                 mesh=None, rules=None) -> list:
    """ShapeDtypeStructs for the cache (dry-run; no allocation)."""
    cache = jax.eval_shape(lambda: init_cache(cfg, tp, batch, max_len))
    if mesh is None:
        return cache
    axes = cache_axes(cfg)
    from repro.distributed.sharding import logical_to_pspec
    from jax.sharding import NamedSharding

    def attach(sds, ax):
        spec = logical_to_pspec((None,) + tuple(ax), mesh, rules)
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(attach, cache, axes,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def cache_axes(cfg: ModelConfig, kv_dtype: str | None = None) -> list:
    """Logical axes per cache leaf (without the leading layer-stack dim)."""
    out = []
    for pattern, reps in cfg.layer_groups():
        group = {f"sub{i}": blocks.block_cache_axes(cfg, spec, kv_dtype)
                 for i, spec in enumerate(pattern)}
        out.append(group)
    return out


def cache_pspecs(cfg: ModelConfig, mesh, rules=None) -> list:
    from repro.distributed.sharding import logical_to_pspec
    axes = cache_axes(cfg)
    return jax.tree.map(
        lambda ax: logical_to_pspec((None,) + tuple(ax), mesh, rules),
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


# ---------------------------------------------------------------------------
# forward: decode step
# ---------------------------------------------------------------------------
def forward_decode(cfg: ModelConfig, params: dict, tokens: jax.Array,
                   cache: list, cache_len: jax.Array):
    """tokens: (B, 1[, K]); cache_len: (B,) valid positions before this token.

    Returns (logits (B, vocab[, K]), new_cache).
    """
    x = _embed(cfg, params, tokens)
    positions = cache_len[:, None]
    new_cache: list = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        stacked_p = params[f"group{gi}"]
        stacked_c = cache[gi]

        def body(x, pc, _pattern=pattern):
            layer_p, layer_c = pc
            new_c = {}
            for i, spec in enumerate(_pattern):
                x, c = blocks.block_decode(cfg, spec, layer_p[f"sub{i}"], x,
                                           positions, layer_c[f"sub{i}"],
                                           cache_len)
                new_c[f"sub{i}"] = c
            return x, new_c

        x, nc = jax.lax.scan(body, x, (stacked_p, stacked_c))
        new_cache.append(nc)
    logits = _head(cfg, params, x)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# forward: incremental prefill chunk against a carried cache
# ---------------------------------------------------------------------------
def forward_chunk(cfg: ModelConfig, params: dict, tokens: jax.Array,
                  cache: list, cache_len: jax.Array):
    """Incremental chunked prefill (DESIGN.md §7): run ``tokens``
    (B, S_chunk[, K]) as the next S_chunk prompt positions after the
    ``cache_len`` (B,) tokens already in ``cache``.

    The multi-token generalization of ``forward_decode``: attention writes
    the chunk's K/V (latents) into the cache at the prefix offset and
    attends causally over prefix + chunk; recurrent mixers resume from the
    cached state.  Each prompt token passes through the model exactly once
    across chunks — O(p) model FLOPs for a p-token prompt, vs O(p²/chunk)
    for prefix recomputation.  All shapes are static given the chunk length,
    so ``jax.jit`` compiles one program per (bucketed) chunk size.

    Per-row ``cache_len`` offsets are supported on the XLA/ref kernel path;
    the engine calls this one slot at a time (B = 1).

    Returns (logits (B, S_chunk, vocab[, K]), new_cache).
    """
    x = _embed(cfg, params, tokens)
    b, s = x.shape[0], x.shape[1]
    positions = cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    new_cache: list = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        stacked_p = params[f"group{gi}"]
        stacked_c = cache[gi]

        def body(x, pc, _pattern=pattern):
            layer_p, layer_c = pc
            new_c = {}
            for i, spec in enumerate(_pattern):
                x, c = blocks.block_chunk(cfg, spec, layer_p[f"sub{i}"], x,
                                          positions, layer_c[f"sub{i}"],
                                          cache_len)
                new_c[f"sub{i}"] = c
            return x, new_c

        x, nc = jax.lax.scan(body, x, (stacked_p, stacked_c))
        new_cache.append(nc)
    logits = _head(cfg, params, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# forward: token-packed dense-batch step (decode + all prefill chunks fused)
# ---------------------------------------------------------------------------
def forward_packed(cfg: ModelConfig, params: dict, tokens: jax.Array,
                   cache: list, token_slot: jax.Array, token_pos: jax.Array,
                   token_wpos: jax.Array, token_active: jax.Array,
                   kv_bucket: Optional[int] = None, token_dst=None,
                   block_tables=None):
    """One iteration's *entire* model work as a single program (DESIGN.md
    §8): the decode tokens (one per decoding slot) and every scheduled
    prefill chunk are packed into one ``(1, T)`` token stream with per-token
    metadata, generalizing ``forward_chunk`` from one contiguous segment to
    many.

    tokens: (1, T[, K]) packed stream; token_slot: (T,) slot id per token;
    token_pos: (T,) absolute position of the token within its request;
    token_wpos: (T,) cache write position — ``token_pos`` for real tokens,
    ``max_len`` (out of bounds → scatter-dropped) for padding; token_active:
    (T,) False for padding tokens, which then neither write K/V nor commit
    recurrent state.  Under the engine's async pipeline (DESIGN.md §10) the
    stream's decode positions arrive as *device-substituted* values: the
    host writes placeholders and ``sampling.substitute_last`` gathers the
    real tokens from the device-resident ``last_token`` buffer before this
    function runs — the semantics here are unchanged, the values just never
    round-tripped through the host.

    Attention writes each token's K/V (MLA latents) at ``(layer, slot,
    pos)`` of the group's stacked cache, which the layer loop carries, and
    applies a segment-aware mask — a token attends rows ``[0, pos]`` of its
    own slot only, so segments never attend across each other; recurrent
    mixers advance per-slot state through a token scan with active-masking.
    ``kv_bucket`` (static, DESIGN.md §9): upper bound on this iteration's
    ``max(token_pos) + 1`` — attention reads only that many cache rows per
    slot, so its cost scales with actual context.  ``T`` and ``kv_bucket``
    are the only shape parameters, so the engine's jit compile cache is
    bounded by |discrete dense sizes| × |kv buckets|.

    ``token_dst`` ((T,) int32 flat physical rows) and ``block_tables``
    ((N_slots, max_len/block_size) int32) switch attention layers to
    block-table mode (DESIGN.md §12): K/V scatter by physical row, gather
    through per-slot tables — requests then share immutable prefix blocks.
    Both are traced operands of static shape, so the compile-cache bound
    above is unchanged.

    Speculative verify segments (DESIGN.md §13) need no support here at
    all: a slot's k+1 verify positions are just a k+1-token segment, and
    ``token_pos`` / ``token_wpos`` / ``token_dst`` are already traced
    operands — the engine rewrites them on device (true positions from the
    rolled-back ``cache_len`` chain) before calling this function, and the
    segment-causal mask above *is* the draft/verify factorization.

    Returns (logits (1, T, vocab[, K]), new_cache).
    """
    x = _embed(cfg, params, tokens)
    positions = token_pos[None]
    new_cache: list = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        stacked_p = params[f"group{gi}"]

        def body(carry, xs, _pattern=pattern):
            x, group_c = carry
            layer_p, layer = xs
            group_c = dict(group_c)
            for i, spec in enumerate(_pattern):
                x, group_c[f"sub{i}"] = blocks.block_packed(
                    cfg, spec, layer_p[f"sub{i}"], x, positions,
                    group_c[f"sub{i}"], layer, token_slot, token_wpos,
                    token_active, kv_bucket=kv_bucket, token_dst=token_dst,
                    block_tables=block_tables)
            return (x, group_c), None

        # the stacked cache is carried, not scanned: as a scanned input and
        # output XLA copies the whole stack and every layer slices out and
        # writes back its whole cache; carried, each layer writes its T new
        # rows in place (DESIGN.md §8)
        (x, nc), _ = jax.lax.scan(
            body, (x, cache[gi]), (stacked_p, jnp.arange(reps)))
        new_cache.append(nc)
    logits = _head(cfg, params, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# prefill -> cache handoff (dry-run prefill step & engine prefill)
# ---------------------------------------------------------------------------
def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            patches: Optional[jax.Array] = None, tp: int = 1,
            max_len: Optional[int] = None):
    """Run the full-chunk path, then scatter per-layer states into a decode
    cache of capacity ``max_len`` (defaults to the prompt length).

    Returns (last_logits (B, ...), cache, cache_len (B,)).
    """
    logits, _aux, states = forward_full(cfg, params, tokens, patches=patches,
                                        return_states=True)
    b = tokens.shape[0]
    s_total = logits.shape[1]
    cap = max_len or s_total
    cache = init_cache(cfg, tp, b, cap)
    new_cache = []
    for gi, (pattern, reps) in enumerate(cfg.layer_groups()):
        group_c = cache[gi]
        group_s = states[gi]
        out_group = {}
        for i, spec in enumerate(pattern):
            out_group[f"sub{i}"] = _state_to_cache(
                cfg, spec, group_c[f"sub{i}"], group_s[f"sub{i}"])
        new_cache.append(out_group)
    cache_len = jnp.full((b,), s_total, jnp.int32)
    return logits[:, -1], new_cache, cache_len


def _state_to_cache(cfg, spec, cache_z, state):
    from repro.configs.base import ATTN
    if spec.mixer == ATTN:
        if cfg.mla is not None:
            c_kv, k_rope = state["kv"]           # (L, B, S, rank/rope)
            ck = _place(cache_z["c_kv"], c_kv)
            kr = _place(cache_z["k_rope"], k_rope)
            return {"c_kv": ck, "k_rope": kr}
        k, v = state["kv"]                        # (L, B, S, KV, hd)
        return {"k": _place(cache_z["k"], k), "v": _place(cache_z["v"], v)}
    return state                                  # recurrent: state IS cache


def _place(zeros: jax.Array, filled: jax.Array) -> jax.Array:
    """Write prompt-length tensors into the zero cache prefix (seq offset 0)."""
    return jax.lax.dynamic_update_slice(
        zeros, filled.astype(zeros.dtype), (0,) * zeros.ndim)


# ---------------------------------------------------------------------------
# input specs (dry-run stand-ins; ShapeDtypeStruct only)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig, *, mesh=None, rules=None,
                tp: int = 1) -> dict:
    """ShapeDtypeStructs for every model input of the given shape cell."""
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import logical_to_pspec

    b, s = shape.global_batch, shape.seq_len

    def sds(shp, axes, dtype=jnp.int32):
        sharding = None
        if mesh is not None:
            sharding = NamedSharding(mesh, logical_to_pspec(axes, mesh, rules))
        return jax.ShapeDtypeStruct(shp, dtype, sharding=sharding)

    if shape.step in ("train", "prefill"):
        if cfg.frontend == "vision":
            n_patch = min(cfg.num_patch_tokens, s // 4)
            s_text = s - n_patch
            specs = {
                "tokens": sds((b, s_text), ("batch", "act_seq")),
                "patches": sds((b, n_patch, cfg.d_model),
                               ("batch", "act_seq", "embed"),
                               jnp.dtype(cfg.dtype)),
            }
            if shape.step == "train":
                specs["labels"] = sds((b, s_text), ("batch", "act_seq"))
            return specs
        if cfg.frontend == "audio":
            specs = {"tokens": sds((b, s, cfg.num_codebooks),
                                   ("batch", "act_seq", None))}
            if shape.step == "train":
                specs["labels"] = sds((b, s, cfg.num_codebooks),
                                      ("batch", "act_seq", None))
            return specs
        specs = {"tokens": sds((b, s), ("batch", "act_seq"))}
        if shape.step == "train":
            specs["labels"] = sds((b, s), ("batch", "act_seq"))
        return specs

    # decode: one new token against a seq_len cache
    tok_shape = (b, 1, cfg.num_codebooks) if cfg.frontend == "audio" else (b, 1)
    tok_axes = ("batch", "act_seq", None) if cfg.frontend == "audio" \
        else ("batch", "act_seq")
    return {
        "tokens": sds(tok_shape, tok_axes),
        "cache": cache_shapes(cfg, tp, b, s, mesh, rules),
        "cache_len": sds((b,), ("batch",)),
    }
