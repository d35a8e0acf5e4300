"""Jitted kernel entry points with implementation dispatch.

``impl`` selects the execution path:
  * ``"ref"``       — pure-jnp oracle (differentiable; the XLA/GSPMD path used
                      on CPU and inside the dry-run lowering)
  * ``"pallas"``    — TPU Pallas kernel (compiled; requires TPU backend)
  * ``"interpret"`` — Pallas kernel body interpreted on CPU (kernel tests)

Default comes from ``set_default_impl`` / env REPRO_KERNEL_IMPL, falling back
to "ref" on non-TPU backends and "pallas" on TPU.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax

from repro.kernels import ref as _ref

_DEFAULT: Optional[str] = None


def set_default_impl(impl: Optional[str]) -> None:
    global _DEFAULT
    _DEFAULT = impl


def default_impl() -> str:
    if _DEFAULT is not None:
        return _DEFAULT
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: Optional[str]) -> str:
    return impl if impl is not None else default_impl()


# ---------------------------------------------------------------------------
# attention ref variants (§Perf HC3): explicit arguments with env fallback.
#
# These used to be read straight from the environment *at trace time inside
# jitted code* — a retrace footgun: flipping the env var between calls
# silently changes what an already-cached program means on the next compile.
# They are now explicit arguments (per-call kwarg > pinned module default >
# env).  ``ServeEngine`` resolves them ONCE at construction and pins them
# around its jitted bodies with ``attn_config``, so every retrace of an
# engine's programs sees the same values regardless of later env mutation.
# ---------------------------------------------------------------------------
_ATTN_FAST: Optional[bool] = None
_ATTN_STREAM: Optional[bool] = None


def attn_fast_default() -> bool:
    """No-upcast attention refs (see kernels/ref.py)."""
    if _ATTN_FAST is not None:
        return _ATTN_FAST
    return os.environ.get("REPRO_ATTN_FAST", "0") == "1"


def attn_stream_default() -> bool:
    """Streamed long-sequence flash ref (see kernels/ref.py)."""
    if _ATTN_STREAM is not None:
        return _ATTN_STREAM
    return os.environ.get("REPRO_ATTN_STREAM", "0") == "1"


@contextlib.contextmanager
def attn_config(*, fast: Optional[bool] = None, stream: Optional[bool] = None):
    """Pin the fast/stream defaults for the duration (engine trace bodies)."""
    global _ATTN_FAST, _ATTN_STREAM
    prev = (_ATTN_FAST, _ATTN_STREAM)
    if fast is not None:
        _ATTN_FAST = fast
    if stream is not None:
        _ATTN_STREAM = stream
    try:
        yield
    finally:
        _ATTN_FAST, _ATTN_STREAM = prev


def _attn_fast(explicit: Optional[bool] = None) -> bool:
    return explicit if explicit is not None else attn_fast_default()


def _attn_stream(explicit: Optional[bool] = None) -> bool:
    return explicit if explicit is not None else attn_stream_default()


# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal=True, logit_scale=None, q_offset=0,
                    impl: Optional[str] = None, fast: Optional[bool] = None,
                    stream: Optional[bool] = None):
    impl = _resolve(impl)
    # The Pallas kernel takes q_offset as a *static* int (chunked prefill
    # passes a traced per-row offset so one compiled program serves every
    # prefix depth) and assumes v's head dim equals q/k's (absorbed MLA has
    # d_qk = rank + rope but d_v = rank) — route both cases to the XLA ref
    # path, which runs on every backend including TPU.
    if impl != "ref" and (not isinstance(q_offset, int)
                          or v.shape[-1] != q.shape[-1]):
        impl = "ref"
    if impl == "ref":
        if _attn_stream(stream) and q.shape[1] > 512:
            return _ref.flash_attention_stream(
                q, k, v, causal=causal, logit_scale=logit_scale,
                q_offset=q_offset)
        fn = _ref.flash_attention_fast if _attn_fast(fast) \
            else _ref.flash_attention_ref
        return fn(q, k, v, causal=causal, logit_scale=logit_scale,
                  q_offset=q_offset)
    from repro.kernels import flash_attention as _fa
    return _fa.flash_attention(q, k, v, causal=causal, logit_scale=logit_scale,
                               q_offset=q_offset, interpret=(impl == "interpret"))


def decode_attention(q, k_cache, v_cache, cache_len, *, logit_scale=None,
                     impl: Optional[str] = None, fast: Optional[bool] = None):
    impl = _resolve(impl)
    # The Pallas kernel assumes v's head dim equals q/k's; absorbed MLA
    # attends with d_qk = rank + rope but d_v = rank — route the mismatched
    # case to the XLA ref path (correct on every backend).
    if impl != "ref" and v_cache.shape[-1] != q.shape[-1]:
        impl = "ref"
    if impl == "ref":
        fn = _ref.decode_attention_fast if _attn_fast(fast) \
            else _ref.decode_attention_ref
        return fn(q, k_cache, v_cache, cache_len, logit_scale=logit_scale)
    from repro.kernels import decode_attention as _da
    return _da.decode_attention(q, k_cache, v_cache, cache_len,
                                logit_scale=logit_scale,
                                interpret=(impl == "interpret"))


def packed_attention(q, k_cache, v_cache, token_slot, lengths, *,
                     layer=0, logit_scale=None,
                     kv_bucket: Optional[int] = None,
                     block_tables=None, k_scale=None, v_scale=None,
                     impl: Optional[str] = None, fast: Optional[bool] = None):
    """Segment-masked attention over a token-packed stream (DESIGN.md §8):
    token t attends rows [0, lengths[t]) of slot ``token_slot[t]``'s cache.

    The caches are either one layer's ``(N_slots, S, KV, D)`` or the
    model step's stacked ``(L, N_slots, S, KV, D)``, of which layer
    ``layer`` (traced) is read: the Pallas kernel reads it in place, the
    refs cut the layer's view first.

    ``kv_bucket`` (static) bounds the swept cache extent — the engine passes
    the iteration's KV-length bucket so work scales with actual context, not
    ``max_len`` (DESIGN.md §9).  The Pallas kernel gathers each token's slot
    rows block-wise via scalar-prefetch indexing and handles the absorbed-MLA
    ``d_v != d_qk`` case natively, so no silent ref downgrade here.

    ``block_tables`` (optional, DESIGN.md §12): block-table mode — the
    caches are physical block storage and every gather is routed through
    the per-slot table (index-map dereference in the Pallas kernel, dense
    per-slot gather in the refs).

    ``k_scale``/``v_scale`` (optional, f32, the caches' shape without the
    head dim, DESIGN.md §15): int8 caches — every impl dequantizes after
    the int8 read (in-register in the Pallas kernel, dense in the refs)."""
    impl = _resolve(impl)
    if impl == "ref":
        fn = _ref.packed_attention_fast if _attn_fast(fast) \
            else _ref.packed_attention_ref
        return fn(q, k_cache, v_cache, token_slot, lengths,
                  logit_scale=logit_scale, kv_bucket=kv_bucket,
                  block_tables=block_tables, k_scale=k_scale,
                  v_scale=v_scale, layer=layer)
    from repro.kernels import packed_attention as _pa
    return _pa.packed_attention(q, k_cache, v_cache, token_slot, lengths,
                                layer=layer, logit_scale=logit_scale,
                                kv_bucket=kv_bucket,
                                block_tables=block_tables, k_scale=k_scale,
                                v_scale=v_scale,
                                interpret=(impl == "interpret"))


def paged_decode_attention(q, k_pages, v_pages, page_table, cache_len, *,
                           logit_scale=None, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                               cache_len, logit_scale=logit_scale)
    from repro.kernels import decode_attention as _da
    return _da.paged_decode_attention(q, k_pages, v_pages, page_table,
                                      cache_len, logit_scale=logit_scale,
                                      interpret=(impl == "interpret"))


def fused_overlap(x, w, q, k_cache, v_cache, cache_len, *,
                  gemm_fraction: float = 0.5, impl: Optional[str] = None):
    """NanoFlow signature op: GEMM co-scheduled with decode attention.

    ``gemm_fraction`` — fraction of grid steps assigned to GEMM tiles (the
    TPU analogue of the paper's SM-partition ratio; autosearch sets it)."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.fused_overlap_ref(x, w, q, k_cache, v_cache, cache_len)
    from repro.kernels import fused_overlap as _fo
    return _fo.fused_overlap(x, w, q, k_cache, v_cache, cache_len,
                             gemm_fraction=gemm_fraction,
                             interpret=(impl == "interpret"))


def ssm_scan(x, dt, a, b, c, d, h0=None, *, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.ssm_scan_ref(x, dt, a, b, c, d, h0)
    from repro.kernels import ssm_scan as _ss
    return _ss.ssm_scan(x, dt, a, b, c, d, h0, interpret=(impl == "interpret"))
