"""Engine configuration (`EngineConfig`): one frozen dataclass instead of
``ServeEngine.__init__``'s ~17 loose keyword arguments.

Design rules:

  * **Fields store what the caller said** — ``async_depth=None`` stays
    ``None``; the ``resolved_*`` accessors apply the defaulting rules
    (async depth 1, the n-gram drafter under speculation, env fallbacks
    for the attention toggles).  This keeps
    ``dataclasses.replace`` composable: overriding one field never bakes a
    stale resolution of another into the copy.
  * **Validation lives in ``__post_init__``** — every invariant the engine
    used to assert at construction (value ranges, block-size divisibility
    for prefix caching, spec_k below max_len) fails fast here, before any
    device work.
  * **Env is read at construction, never at trace time** — the
    ``REPRO_ATTN_FAST`` / ``REPRO_ATTN_STREAM`` fallbacks are captured by
    ``resolved_attn_fast()`` / ``resolved_attn_stream()``, which the engine
    calls exactly once in ``__init__`` (and ``from_env`` calls once to
    pin them into explicit field values).  No jitted body ever consults
    ``os.environ``.
  * **Flags are defined once** — ``add_args(parser)`` registers the CLI
    surface shared by ``launch/serve.py`` and
    ``benchmarks/offline_throughput.py``; ``from_args(ns, **overrides)``
    turns the parsed namespace back into a config.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Optional


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "0") == "1"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-engine knobs (model-independent).  ``None`` fields mean
    "apply the documented default" — see the ``resolved_*`` accessors."""
    # ---- capacity -----------------------------------------------------------
    max_slots: int = 8
    max_len: int = 512
    # KV block size: the unit of the block-table allocator (and of the
    # legacy page accounting — ``page_size`` is accepted as an alias)
    kv_block_size: int = 16
    total_pages: Optional[int] = None
    kv_budget_bytes: Optional[int] = None
    avg_decode_len: float = 64.0
    # ---- batching -----------------------------------------------------------
    discrete_sizes: tuple[int, ...] = (256, 128, 64, 32, 16, 8)
    nano: int = 2
    # ---- step / pipeline ----------------------------------------------------
    async_depth: Optional[int] = None        # None -> 1
    async_harvest: bool = True
    tp: int = 1
    # ---- KV-length bucketing (DESIGN.md §9) ---------------------------------
    kv_buckets: Optional[tuple[int, ...]] = None
    kv_bucketing: bool = True
    # ---- cross-request prefix caching (DESIGN.md §12) -----------------------
    prefix_caching: bool = False
    # ---- KV storage dtype (DESIGN.md §15) -----------------------------------
    # "bf16" = the model's native dtype (pre-§15 behavior); "int8" stores
    # int8 value leaves + per-(token, kv-head) f32 scale leaves and the
    # packed step quantizes at scatter / dequantizes in-register on load
    kv_dtype: str = "bf16"
    # ---- speculative decoding (DESIGN.md §13) -------------------------------
    # draft tokens verified per decoding slot per iteration; 0 disables
    # (each decode segment is then the plain single token of §8/§10)
    spec_k: int = 0
    drafter: Optional[str] = None            # None -> "ngram" when spec_k > 0
    # ---- sampling (packed step; greedy when temperature == 0) ---------------
    temperature: float = 0.0
    top_k: Optional[int] = None
    # ---- attention toggles (§Perf HC3; None -> env fallback) ----------------
    attn_fast: Optional[bool] = None
    attn_stream: Optional[bool] = None
    seed: int = 0

    # ------------------------------------------------------------------------
    def __post_init__(self):
        assert self.tp >= 1, self.tp
        assert self.resolved_async_depth >= 0, self.async_depth
        assert self.kv_block_size >= 1, self.kv_block_size
        assert self.max_slots >= 1 and self.max_len >= 1
        if self.prefix_caching:
            assert self.max_len % self.kv_block_size == 0, \
                (self.max_len, self.kv_block_size)
        assert self.kv_dtype in ("bf16", "int8"), self.kv_dtype
        assert self.spec_k >= 0, self.spec_k
        if self.spec_k > 0:
            assert self.spec_k < self.max_len, (self.spec_k, self.max_len)
        if self.drafter is not None:
            from repro.serving.draft import drafter_names
            assert self.drafter in drafter_names(), \
                (self.drafter, drafter_names())
        assert self.temperature >= 0.0, self.temperature
        if self.top_k is not None:
            assert self.top_k >= 1, self.top_k
            assert self.temperature > 0, \
                "top_k sampling needs temperature > 0 (temperature == 0 " \
                "is greedy and ignores top_k)"

    # ---- defaulting rules (never baked into the stored fields) --------------
    @property
    def resolved_async_depth(self) -> int:
        # the pipeline is the default serving mode (§5.3 / DESIGN.md §10)
        return int(self.async_depth) if self.async_depth is not None else 1

    @property
    def resolved_drafter(self) -> Optional[str]:
        """The drafter name to instantiate: explicit value, else the n-gram
        reference drafter whenever speculation is on."""
        if self.spec_k <= 0:
            return None
        return self.drafter if self.drafter is not None else "ngram"

    def resolved_attn_fast(self) -> bool:
        """Explicit value, else one env read — call once at construction."""
        return _env_flag("REPRO_ATTN_FAST") if self.attn_fast is None \
            else bool(self.attn_fast)

    def resolved_attn_stream(self) -> bool:
        return _env_flag("REPRO_ATTN_STREAM") if self.attn_stream is None \
            else bool(self.attn_stream)

    def resolved_kv_buckets(self) -> tuple[int, ...]:
        """The KV-length bucket grid (DESIGN.md §9), ascending, topped by
        ``max_len``; ``kv_bucketing=False`` pins the single max_len bucket."""
        from repro.serving.scheduler import default_kv_buckets
        if not self.kv_bucketing:
            return (self.max_len,)
        if self.kv_buckets is None:
            return default_kv_buckets(self.max_len)
        grid = tuple(sorted({min(b, self.max_len) for b in self.kv_buckets}))
        return grid if grid[-1] == self.max_len else grid + (self.max_len,)

    # ---- construction helpers -----------------------------------------------
    @classmethod
    def from_env(cls, **overrides) -> "EngineConfig":
        """A config with the attention-toggle env fallbacks pinned into
        explicit field values (the single env read of the process's
        configuration path)."""
        base = cls(**overrides)
        return dataclasses.replace(
            base,
            attn_fast=base.resolved_attn_fast(),
            attn_stream=base.resolved_attn_stream())

    @classmethod
    def add_args(cls, ap: argparse.ArgumentParser) -> None:
        """Register the shared engine CLI surface (defined once, consumed by
        ``launch/serve.py`` and ``benchmarks/offline_throughput.py``)."""
        ap.add_argument("--slots", type=int, default=cls.max_slots,
                        help="slot count (concurrent active requests)")
        ap.add_argument("--max-len", type=int, default=256,
                        help="per-slot cache capacity (tokens)")
        ap.add_argument("--async-depth", type=int, default=None,
                        help="iterations kept in flight before syncing their "
                             "sampled tokens (DESIGN.md §10); 0 = eager "
                             "lock-step; default: 1")
        ap.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel degree (DESIGN.md §11): the "
                             "packed step runs as one shard_map program over "
                             "a 1-D model mesh of chips; with "
                             "JAX_PLATFORMS=cpu, of host-platform devices")
        ap.add_argument("--no-kv-bucketing", action="store_true",
                        help="sweep max_len every iteration instead of the "
                             "KV-length bucket (DESIGN.md §9; A/B baseline)")
        ap.add_argument("--prefix-caching",
                        action=argparse.BooleanOptionalAction, default=False,
                        help="cross-request prefix caching over the "
                             "block-table KV (DESIGN.md §12): identical "
                             "prompt prefixes are prefilled once and shared "
                             "(copy-on-write on divergence)")
        ap.add_argument("--kv-block-size", type=int, default=cls.kv_block_size,
                        help="KV block size (tokens per block-table block; "
                             "must divide --max-len when --prefix-caching)")
        ap.add_argument("--kv-dtype", default=cls.kv_dtype,
                        choices=["bf16", "int8"],
                        help="KV-cache storage dtype (DESIGN.md §15): int8 "
                             "stores quantized values + per-(token, kv-head) "
                             "f32 scales — ~2x the admitted requests at a "
                             "fixed --kv-budget, dequant-on-load in the "
                             "packed-attention kernel")
        ap.add_argument("--spec-k", type=int, default=cls.spec_k,
                        help="speculative decoding (DESIGN.md §13): draft "
                             "tokens verified per decoding slot per packed "
                             "iteration; 0 = off")
        ap.add_argument("--drafter", default=None,
                        choices=["ngram"],
                        help="draft proposer for --spec-k > 0 (default: "
                             "ngram prompt-lookup/self-history matching)")
        ap.add_argument("--temperature", type=float, default=cls.temperature,
                        help="sampling temperature (0 = greedy, the default "
                             "and the spec-decode exactness baseline)")
        ap.add_argument("--top-k", type=int, default=None,
                        help="top-k sampling cutoff (needs --temperature > 0)")
        ap.add_argument("--attn-fast", action=argparse.BooleanOptionalAction,
                        default=None,
                        help="no-upcast attention refs (§Perf HC3); default: "
                             "REPRO_ATTN_FAST env")
        ap.add_argument("--attn-stream", action=argparse.BooleanOptionalAction,
                        default=None,
                        help="streamed long-seq flash ref; default: "
                             "REPRO_ATTN_STREAM env")

    @classmethod
    def from_args(cls, ns: argparse.Namespace, **overrides) -> "EngineConfig":
        """Build a config from an ``add_args`` namespace; ``overrides`` win
        over flags (benchmark mode matrices pass their per-mode kwargs)."""
        kw = dict(
            max_slots=ns.slots,
            max_len=ns.max_len,
            async_depth=ns.async_depth,
            tp=ns.tp,
            kv_bucketing=not ns.no_kv_bucketing,
            prefix_caching=ns.prefix_caching,
            kv_block_size=ns.kv_block_size,
            kv_dtype=ns.kv_dtype,
            spec_k=ns.spec_k,
            drafter=ns.drafter,
            temperature=ns.temperature,
            top_k=ns.top_k,
            attn_fast=ns.attn_fast,
            attn_stream=ns.attn_stream,
        )
        kw.update(overrides)
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Multi-replica pool + SLO admission knobs (DESIGN.md §14).

    Same rules as ``EngineConfig``: fields store what the caller said,
    validation in ``__post_init__``, flags defined once in ``add_args``
    and rebuilt by ``from_args``.  All SLO fields are optional — ``None``
    disables that admission/violation check, so a bare single-replica pool
    behaves exactly like the engine it wraps."""

    replicas: int = 1
    # TTFT admission SLO: predicted time-to-first-token (cheapest replica's
    # backlog / measured service rate) above this -> shed with reason
    slo_ttft_ms: Optional[float] = None
    # TPOT SLO: per-output-token latency; checked at completion (a
    # violation is recorded, not retroactively shed)
    slo_tpot_ms: Optional[float] = None
    # hard backlog cap per replica in tokens: the deterministic shed
    # trigger (virtual-clock tests can't rely on wall-time predictions)
    shed_backlog_tokens: Optional[int] = None
    # admission headroom: predicted TTFT is compared against
    # slo_ttft_ms * slo_safety (under-admit rather than violate)
    slo_safety: float = 1.0
    # queue-timeout for a request stuck WAITING on one replica; after
    # ``retry_limit`` re-routes it is shed with reason "retry_limit"
    request_timeout_s: Optional[float] = None
    retry_limit: int = 3
    backoff_base_s: float = 0.01
    # chaos: FaultPlan spec string ("kill@40:r1,...") or None
    fault_plan: Optional[str] = None
    # session affinity (multi-turn requests pinned to their prefix cache)
    affinity: bool = True

    def __post_init__(self):
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if self.retry_limit < 0:
            raise ValueError("retry_limit must be >= 0")
        for f in ("slo_ttft_ms", "slo_tpot_ms", "request_timeout_s"):
            v = getattr(self, f)
            if v is not None and v <= 0:
                raise ValueError(f"{f} must be positive when set")

    @classmethod
    def add_args(cls, ap: argparse.ArgumentParser) -> None:
        """Pool CLI surface shared by launch/serve.py and the online
        latency benchmark."""
        ap.add_argument("--replicas", type=int, default=cls.replicas,
                        help="engine replicas behind the router")
        ap.add_argument("--slo-ttft-ms", type=float, default=None,
                        help="TTFT SLO; admission sheds requests whose "
                             "predicted TTFT exceeds it")
        ap.add_argument("--slo-tpot-ms", type=float, default=None,
                        help="per-output-token SLO; violations counted "
                             "at completion")
        ap.add_argument("--shed-backlog-tokens", type=int, default=None,
                        help="hard per-replica backlog cap (tokens) "
                             "before shedding")
        ap.add_argument("--request-timeout-s", type=float, default=None,
                        help="queue timeout before retry-with-backoff")
        ap.add_argument("--retry-limit", type=int, default=cls.retry_limit,
                        help="re-dispatch attempts before a request is "
                             "shed")
        ap.add_argument("--fault-plan", default=None,
                        help="chaos spec, e.g. 'kill@40:r1,stall@10:r0:20'"
                             " (tick-indexed, deterministic)")

    @classmethod
    def from_args(cls, ns: argparse.Namespace, **overrides) -> "PoolConfig":
        kw = dict(
            replicas=ns.replicas,
            slo_ttft_ms=ns.slo_ttft_ms,
            slo_tpot_ms=ns.slo_tpot_ms,
            shed_backlog_tokens=ns.shed_backlog_tokens,
            request_timeout_s=ns.request_timeout_s,
            retry_limit=ns.retry_limit,
            fault_plan=ns.fault_plan,
        )
        kw.update(overrides)
        return cls(**kw)
