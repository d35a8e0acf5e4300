"""Tensor-parallel runtime for the packed serving step (DESIGN.md §11).

The engine wraps its jitted packed iteration in ``shard_map`` over a 1-D
``("model",)`` mesh.  Inside the body every array is a *local shard* and the
model code must say where cross-shard reductions happen.  Rather than fork a
second copy of every mixer family, the packed-path code calls the helpers
here at its (few) reduction points; outside a TP context every helper
degrades to the exact single-device computation, so ``tp=1`` remains the
unsharded code path.

Layout (one mesh axis, ``"model"``; table in DESIGN.md §11):

  * GQA      — q/k/v/o projections and the K/V slot cache sharded along
               (kv-)heads; attention is per-head local; the output
               projection is row-parallel (all-reduce).
  * MLA      — the latent path (``c_kv``/``k_rope`` cache and their
               projections) is *replicated*; the absorbed per-head
               projections (``wuq``/``wuk``/``wuv``/``wo``) are sharded
               along heads; output projection row-parallel.
  * Mamba    — the expanded inner dim ``d_in`` is sharded (contiguous
               channel blocks); dt/B/C come from a row-parallel projection
               (psum inside the token scan); ``w_out`` row-parallel.
  * mLSTM    — sharded along *heads* (= contiguous ``d_in`` channel
               blocks); the (C, n, m) matrix memory is head-sharded; the
               i/f gates are row-parallel (psum) then sliced to the local
               heads; the out-norm reduces over the full width via psum;
               ``w_down`` row-parallel.
  * sLSTM    — the tiny scalar recurrence runs replicated (DESIGN.md §4);
               only the post-recurrence GLU FFN is column/row-parallel.
  * MoE      — experts sharded over the mesh axis; routing computed
               replicated, each shard combines its local experts' outputs
               and the combine is psum'd.  Shared/dense-residual FFNs are
               column/row-parallel.
  * embed / head / norms / ``last_token`` / sampled tokens — replicated:
    greedy sampling needs the full vocab row, and a replicated
    ``last_token`` buffer means the §10 feedback loop closes with no
    collective.

Fused projections whose columns are later ``split`` in half (mamba/mLSTM
``x‖z`` up-projections, the sLSTM GLU ``u‖g``) are **re-interleaved** at
placement time (``shard_params_tp``) so each shard's contiguous column
block holds the *matching* halves — the math is unchanged, only the
storage layout of the fused axis moves.

Row-parallel matmuls route through the ring-decomposed collective matmul
(``distributed/collective_matmul.matmul_allreduce``), launched **per
nano-batch group** of the packed stream: group i's all-reduce has no data
dependence on group i+1's GEMM, so the paper's §4.3 network/compute
overlap is expressed as real dependency freedom in the launched program —
the ``NanoBatchPlan`` split governs launched collectives, not just the
cost model.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ATTN, FFN_DENSE, FFN_MOE, FFN_MOE_DENSE,
                                MAMBA, MLSTM, SLSTM, ModelConfig)
from repro.core.nanobatch import nano_batch_sizes_for
from repro.distributed.collective_matmul import matmul_allreduce

# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TPContext:
    axis: str                       # mesh axis name ("model")
    size: int                       # shard count
    nano: tuple[int, ...] = ()      # nano-batch split of the packed T axis


class _State(threading.local):
    ctx: Optional[TPContext] = None


_STATE = _State()


@contextlib.contextmanager
def tp_ctx(axis: str, size: int, nano: tuple[int, ...] = ()):
    """Activate the TP helpers for a shard_map trace body.  ``size <= 1``
    deactivates (helpers become the single-device computation)."""
    prev = _STATE.ctx
    _STATE.ctx = TPContext(axis, int(size), tuple(nano)) if size > 1 else None
    try:
        yield
    finally:
        _STATE.ctx = prev


def active() -> Optional[TPContext]:
    return _STATE.ctx


def shard_map_packed(body, mesh, nano: int, in_specs, out_specs):
    """``shard_map(body)`` over the 1-D TP mesh: the one TP wrapping of a
    packed forward (the engine's step and ``chip_smoke.py``'s teacher-forced
    check both go through it).  ``body`` is traced under a ``tp_ctx`` whose
    nano-batch split (``nano`` groups) comes from the static length of its
    third operand, the ``(1, T)`` token stream — so the compile cache keys
    only on the launch shape, and the nano-batch layout governs how the
    row-parallel all-reduces are chunked.  The replication check is off:
    the body mixes manually replicated values with psum'd partials, which
    the static tracker cannot prove."""
    size = int(mesh.shape["model"])

    def traced(*args):
        sizes = nano_batch_sizes_for(args[2].shape[1], nano).sizes
        with tp_ctx("model", size, sizes):
            return body(*args)
    return jax.shard_map(traced, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def world() -> int:
    return _STATE.ctx.size if _STATE.ctx is not None else 1


# ---------------------------------------------------------------------------
# collective helpers (identity outside a TP context)
# ---------------------------------------------------------------------------
def psum(x: jax.Array) -> jax.Array:
    ctx = _STATE.ctx
    return jax.lax.psum(x, ctx.axis) if ctx is not None else x


def shard_block(x: jax.Array, axis: int = -1) -> jax.Array:
    """Slice this shard's contiguous block of a replicated full tensor
    (e.g. the psum'd mLSTM gates back down to the local heads)."""
    ctx = _STATE.ctx
    if ctx is None:
        return x
    blk = x.shape[axis] // ctx.size
    start = jax.lax.axis_index(ctx.axis) * blk
    return jax.lax.dynamic_slice_in_dim(x, start, blk, axis=axis)


def row_parallel(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x (..., k_local) @ w (k_local, n)`` summed over the TP axis.

    Under TP the sum is launched through the ring-decomposed collective
    matmul once **per nano-batch group** of the leading (token) axis, so
    group i's collective is dependency-free of group i+1's GEMM (paper
    §4.3 / DESIGN.md §11).  Outside a TP context this is the plain einsum.
    """
    ctx = _STATE.ctx
    if ctx is None:
        return jnp.einsum("...k,kn->...n", x, w)
    if w.shape[-1] % ctx.size:
        # ring reduce-scatter needs n % p == 0; fall back to a plain psum
        return jax.lax.psum(jnp.einsum("...k,kn->...n", x, w), ctx.axis)
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    m = xf.shape[0]
    sizes = ctx.nano if (len(ctx.nano) > 1 and sum(ctx.nano) == m) else (m,)
    outs, start = [], 0
    for s in sizes:
        outs.append(matmul_allreduce(
            jax.lax.slice_in_dim(xf, start, start + s, axis=0), w, ctx.axis))
        start += s
    y = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return y.reshape(lead + (w.shape[-1],))


def out_project(out: jax.Array, wo: jax.Array) -> jax.Array:
    """Attention output projection ``(t,h,k),(h,k,d)->(t,d)`` — row-parallel
    over the (head-sharded) contraction under TP."""
    if _STATE.ctx is None:
        return jnp.einsum("thk,hkd->td", out, wo)
    return row_parallel(out.reshape(out.shape[0], -1),
                        wo.reshape(-1, wo.shape[-1]))


def rmsnorm_sharded(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over a last axis that is TP-sharded: the mean-square reduces
    over the *full* width via psum; ``weight`` is the local shard.  Outside
    a TP context this is exactly ``models.layers.rmsnorm``."""
    ctx = _STATE.ctx
    x32 = x.astype(jnp.float32)
    ss = jnp.sum(jnp.square(x32), axis=-1, keepdims=True)
    width = x.shape[-1] * (ctx.size if ctx is not None else 1)
    if ctx is not None:
        ss = jax.lax.psum(ss, ctx.axis)
    var = ss / width
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


# ---------------------------------------------------------------------------
# layout: logical param axes -> mesh axes for the manual (shard_map) layout
# ---------------------------------------------------------------------------
# Only these logical axes map to the mesh; vocab/embed/lora/head_dim/state
# stay replicated (greedy sampling wants full-vocab logits; the MLA latent
# is replicated by design).  At most one axis of a leaf is sharded.
_MANUAL_AXES = {"heads": "model", "kv_heads": "model", "ff": "model",
                "inner": "model", "experts": "model"}


def _param_spec(path: tuple[str, ...], d) -> P:
    name = path[-1]
    if name == "router":
        return P()                   # routing is computed replicated
    # mLSTM overrides, scoped to the mixer subtree so an unrelated leaf
    # that happens to share a name can never inherit the head layout:
    if "mixer" in path and name in ("w_q", "w_k", "w_v"):
        # per-head block-diagonal projections: shard the head axis (axis 1
        # after layer stacking) — the logical tags say replicated/dv but
        # the manual layout shards whole heads (DESIGN.md §11)
        return P(None, "model")
    if "mixer" in path and name in ("w_i", "w_f"):
        # gate inputs (d_in, h): rows local, full-h output psum'd
        return P(None, "model")
    spec, used = [], False
    for ax in d.axes:
        m = _MANUAL_AXES.get(ax) if ax is not None else None
        if m is not None and not used:
            spec.append(m)
            used = True
        else:
            spec.append(None)
    return P(*spec)


def _needs_half_interleave(path: tuple[str, ...]) -> bool:
    """Fused projections whose output columns are later split in half:
    mamba ``w_in`` / mLSTM ``w_up`` (x‖z) and sLSTM ``w_ffn_up`` (u‖g).
    A contiguous column shard of the fused axis would put *all* of x on
    shard 0 and all of z on shard 1; re-interleaving gives every shard the
    matching halves.  (The FFN ``w_up`` is not fused — no interleave.)"""
    return (path[-1] == "w_ffn_up"
            or (path[-1] in ("w_in", "w_up") and "mixer" in path))


def _interleave_halves(w, p: int):
    """Columns ``[a | b]`` -> ``[a_0 b_0 | a_1 b_1 | ...]`` (p blocks each);
    numpy or jax arrays alike."""
    lead, n = w.shape[:-1], w.shape[-1]
    return w.reshape(lead + (2, p, n // (2 * p))).swapaxes(-3, -2) \
        .reshape(w.shape)


def _interleave_fused(params: dict, p: int, path: tuple = ()) -> dict:
    """Apply the fused-column re-interleave to the leaves that need it."""
    out = {}
    for k, v in params.items():
        sub = path + (k,)
        if isinstance(v, dict):
            out[k] = _interleave_fused(v, p, sub)
        else:
            out[k] = _interleave_halves(v, p) \
                if _needs_half_interleave(sub) else v
    return out


def param_pspecs_tp(cfg: ModelConfig) -> dict:
    """PartitionSpec tree matching ``model.init``'s param tree under the
    manual TP layout (shard_map in_specs / NamedSharding placement)."""
    from repro.models.model import model_defs
    from repro.models.param import map_defs
    return map_defs(_param_spec, model_defs(cfg, tp=1))


def _on_mesh(specs, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _param_shardings(cfg: ModelConfig, mesh) -> dict:
    return _on_mesh(param_pspecs_tp(cfg), mesh)


def init_params_tp(cfg: ModelConfig, key: jax.Array, mesh) -> dict:
    """``model.init(cfg, key)`` made directly in the TP layout: each device
    materializes only its own shards (random bits are partitionable), so
    no device ever holds the whole model — the way a model that does not
    fit one chip gets started.  Same values as ``shard_params_tp`` of the
    unsharded init."""
    from repro.models.model import init
    p = int(mesh.shape["model"])
    return jax.jit(lambda k: _interleave_fused(init(cfg, k), p),
                   out_shardings=_param_shardings(cfg, mesh))(key)


def shard_params_tp(cfg: ModelConfig, params: dict, mesh) -> dict:
    """Place a param tree that came from the host (or one device) on the TP
    mesh, applying the fused-column re-interleave where the layout
    requires it."""
    p = int(mesh.shape["model"])
    return jax.device_put(_interleave_fused(params, p),
                          _param_shardings(cfg, mesh))


def place_params_tp(cfg: ModelConfig, params: dict, mesh) -> dict:
    """The engine's entry: params already laid out on ``mesh`` (from
    ``init_params_tp``) are used as they are; anything else goes through
    ``shard_params_tp``."""
    want = _param_shardings(cfg, mesh)
    placed = jax.tree.map(
        lambda a, s: isinstance(a, jax.Array)
        and a.sharding.is_equivalent_to(s, a.ndim), params, want)
    if all(jax.tree.leaves(placed)):
        return params
    return shard_params_tp(cfg, params, mesh)


def _block_cache_specs(cfg: ModelConfig, spec,
                       kv_dtype: str | None = None) -> dict:
    """PartitionSpecs per cache leaf (leading layer-stack dim included).
    ``kv_dtype="int8"`` (DESIGN.md §15) adds the scale leaves: GQA scales
    (L,N,S,KV) shard on the same kv-head axis as the values; MLA latent
    scales are replicated like the latent itself."""
    if spec.mixer == ATTN:
        if cfg.mla is not None:
            out = {"c_kv": P(), "k_rope": P()}       # latent replicated
            if kv_dtype == "int8":
                out["c_kv_s"] = P()
                out["k_rope_s"] = P()
            return out
        out = {"k": P(None, None, None, "model"),    # (L,N,S,KV,hd): kv heads
               "v": P(None, None, None, "model")}
        if kv_dtype == "int8":
            out["k_s"] = P(None, None, None, "model")   # (L,N,S,KV)
            out["v_s"] = P(None, None, None, "model")
        return out
    if spec.mixer == MAMBA:
        return {"conv": P(None, None, None, "model"),   # (L,N,K-1,d_in)
                "ssm": P(None, None, "model")}          # (L,N,d_in,n)
    if spec.mixer == MLSTM:
        return {"conv": P(None, None, None, "model"),   # (L,N,K-1,d_in)
                "c": P(None, None, "model"),            # (L,N,h,dqk,dv)
                "n": P(None, None, "model"),            # (L,N,h,dqk)
                "m": P(None, None, "model")}            # (L,N,h)
    if spec.mixer == SLSTM:
        return {k: P() for k in ("conv", "c", "n", "h", "m")}  # replicated
    raise ValueError(spec.mixer)


def cache_pspecs_tp(cfg: ModelConfig, kv_dtype: str | None = None) -> list:
    """PartitionSpec tree matching ``model.init_cache``'s structure (pass
    the engine's kv_dtype so the int8 scale leaves get their specs — the
    tree is used as shard_map in_specs and must match the cache exactly)."""
    out = []
    for pattern, reps in cfg.layer_groups():
        out.append({f"sub{i}": _block_cache_specs(cfg, spec, kv_dtype)
                    for i, spec in enumerate(pattern)})
    return out


def init_cache_tp(cfg: ModelConfig, batch: int, max_len: int, mesh,
                  kv_dtype: str | None = None) -> list:
    """``model.init_cache`` made directly in the TP layout (no device holds
    the whole cache first)."""
    from repro.models.model import init_cache
    return jax.jit(lambda: init_cache(cfg, 1, batch, max_len, kv_dtype),
                   out_shardings=_on_mesh(cache_pspecs_tp(cfg, kv_dtype),
                                          mesh))()


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------
def validate_tp(cfg: ModelConfig, tp: int) -> None:
    """The manual layout shards whole heads / channel blocks / experts —
    every sharded width must divide by ``tp`` (the dry-run effective-layout
    machinery of DESIGN.md §4 pads/replicates instead; the real engine
    keeps the exact math and demands divisibility)."""
    errs = []

    def div(n, v, what):
        if v % n:
            errs.append(f"{what}={v} not divisible by tp={n}")

    div(tp, cfg.d_model, "d_model")
    for spec in set(cfg.layer_specs()):
        if spec.mixer == ATTN:
            div(tp, cfg.n_heads, "n_heads")
            if cfg.mla is None:
                div(tp, cfg.n_kv_heads, "n_kv_heads")
        elif spec.mixer == MAMBA:
            from repro.models.ssm import _dims
            d_in, _, _ = _dims(cfg)
            div(tp, d_in, "mamba d_in")
        elif spec.mixer == MLSTM:
            from repro.models.xlstm import _mlstm_dims
            d_in, h, _ = _mlstm_dims(cfg)
            div(tp, h, "mlstm heads")
            div(tp, d_in, "mlstm d_in")
        elif spec.mixer == SLSTM:
            div(tp, cfg.d_model, "slstm d_model")
        if spec.ffn == FFN_DENSE:
            div(tp, cfg.d_ff, "d_ff")
        elif spec.ffn in (FFN_MOE, FFN_MOE_DENSE):
            m = cfg.moe
            div(tp, m.num_experts, "num_experts")
            if m.num_shared_experts:
                div(tp, m.shared_d_ff, "shared_d_ff")
            if spec.ffn == FFN_MOE_DENSE:
                div(tp, cfg.d_ff, "d_ff (dense residual)")
    if errs:
        raise ValueError(f"config {cfg.name!r} cannot shard at tp={tp}: "
                         + "; ".join(errs))


# ---------------------------------------------------------------------------
# collective-traffic model (benchmark observability)
# ---------------------------------------------------------------------------
def collective_bytes_per_iter(cfg: ModelConfig, t: int, tp: int,
                              itemsize: int) -> int:
    """Wire-byte model of one packed iteration's TP collectives: the bytes
    each shard receives.  A row-parallel reduction (``row_parallel``, the
    per-layer output projections) is ``matmul_allreduce``: a ring
    reduce-scatter of float32 partial sums, then a ring all-gather in the
    activation dtype, so ``(p-1)/p × payload × (4 + itemsize)``.  A plain
    ``psum`` (the MoE combine, mamba dt/B/C, the mLSTM gates and norm inside
    the token scans) is a ring all-reduce, ``2(p-1)/p × payload`` of its
    dtype.  A model, not a measurement — reported per iteration by the
    benchmarks."""
    if tp <= 1:
        return 0
    d = cfg.d_model
    rows = psums = 0
    for spec in cfg.layer_specs():
        if spec.mixer == ATTN:
            rows += t * d                          # wo
        elif spec.mixer == MAMBA:
            from repro.models.ssm import _dims
            _, dt_rank, n = _dims(cfg)
            rows += t * d                          # w_out
            psums += t * (dt_rank + 2 * n) * itemsize      # dt/B/C
        elif spec.mixer == MLSTM:
            rows += t * d                          # w_down
            psums += t * (2 * cfg.n_heads + 1) * 4         # f32 gates+norm
        elif spec.mixer == SLSTM:
            rows += t * d                          # w_ffn_down
        if spec.ffn == FFN_DENSE:
            rows += t * d
        elif spec.ffn in (FFN_MOE, FFN_MOE_DENSE):
            psums += t * d * itemsize              # combine psum
            if cfg.moe.num_shared_experts:
                rows += t * d
            if spec.ffn == FFN_MOE_DENSE:
                rows += t * d
    return int((tp - 1) / tp * (rows * (4 + itemsize) + 2 * psums))
