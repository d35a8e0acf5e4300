"""End-to-end serving engine: scheduler + paged KV + model execution.

Slot-based execution: model state lives in fixed-capacity slot caches
(static shapes — bounded compiled programs; the paper's discrete-batching
insight applied to the XLA compilation cache).  Prefill runs in chunks
(chunked prefill, §4.2) whose KV states are written into the request's slot.

**Packed step (DESIGN.md §8).**  One iteration = one jitted
program: the decode tokens (one per decoding slot) and *all* scheduled
prefill chunks are packed into a single ``(1, T)`` token stream with
per-token ``(slot, position)`` metadata and run through
``model.forward_packed`` — K/V (MLA latents) scattered at each segment's
offset, a segment-aware mask so segments never attend across each other,
recurrent state advanced per-slot with active-masking, greedy sampling
on-device.  Exactly one model dispatch and one device→host transfer per
iteration (``EngineStats.model_dispatches`` / ``host_syncs``).  ``T``
is bucketed to the scheduler's discrete dense sizes, so
``BatchPlan.dense_batch`` is the *actual launched shape*; the iteration's
max KV extent is quantized to a KV-length bucket grid (DESIGN.md §9) and
passed statically into the step, so attention sweeps ``kv_bucket`` cache
rows per slot instead of ``max_len`` and the compile cache is bounded by
``(len(discrete_sizes) + 1) × len(kv_buckets)`` (the ``max_active`` floor
bucket for decode-only iterations, DESIGN.md §8).  Segment order inside
the stream follows the nano-batch interleave
(``core/nanobatch.packed_segment_order``), so the interleave governs the
real token layout of the launched program, not just the cost model.

**Asynchronous iteration pipeline (``async_depth``, DESIGN.md §10).**  The
packed step's one sync per iteration is *deferrable*: the program samples
on device and scatters each slot's token into a device-resident
``last_token`` buffer, and the next iteration's decode inputs are gathered
from that buffer *in-program* — so iteration i+1's entire input stream is
computable from scheduler state alone, before iteration i's results ever
reach the host.  With ``async_depth=k`` the engine keeps up to ``k``
iterations in flight (a ring of sampled-token handles), planning
speculatively (``scheduler.mark_launched``) and reconciling on commit
(lag-(1+k) EOS, late speculative tokens dropped).  The packed step
defaults to ``async_depth=1``; ``async_depth=0`` retires each iteration
immediately and is bit-identical to the pre-§10 lock-step engine (the
A/B baseline).  ``EngineStats`` splits the wall clock into host work /
dispatch / blocked-sync time so the overlap is measurable.

**Tensor-parallel serving (``tp=N``, DESIGN.md §11).**  The same packed
step runs as **one ``shard_map`` program** over a 1-D ``("model",)`` mesh:
params and the slot KV caches are sharded along heads/channels per mixer
family (GQA kv heads; MLA keeps the latent replicated and shards the
absorbed per-head projections; SSM/xLSTM shard the state's head/channel
axis; sLSTM's tiny recurrence stays replicated), attention and FFN output
projections ride the ring-decomposed collective matmuls of
``distributed/collective_matmul`` launched *per nano-batch group* — so
segment group i's all-reduce is dependency-free of group i+1's GEMMs, the
paper's §4.3 network/compute overlap as real launched collectives.  The
``last_token`` buffer, sampled tokens and ``cache_len`` stay replicated
(sampling reads full-vocab logits on every shard), so the iteration is
still exactly one dispatch + one (deferred) sync and ``async_depth``
composes unchanged; the compile cache keeps the
(|T buckets| + 1) × |kv buckets| bound per mesh.  ``tp=1`` is exactly the
single-device path.  On a TPU host the mesh is the first N chips; a CPU
run (``JAX_PLATFORMS=cpu``) gets N host devices from
``XLA_FLAGS=--xla_force_host_platform_device_count=N``, and there the
*intra*-device overlap is still modeled by core/autosearch.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ATTN, ModelConfig
from repro.distributed import tp as tp_lib
from repro.kernels import ops
from repro.launch.mesh import make_tp_mesh
from repro.models import blocks
from repro.models import model as model_lib
from repro.serving import draft as draft_lib
from repro.serving import sampling
from repro.serving import spans
from repro.serving.config import EngineConfig
from repro.serving.kvcache import PagedKVManager
from repro.serving.request import Request, State
from repro.serving.scheduler import BatchPlan, GlobalBatchScheduler


def kv_bytes_per_token(cfg: ModelConfig,
                       kv_dtype: Optional[str] = None) -> int:
    """Per-token KV-cache bytes, derived from the *actual* attention cache
    leaves (``jax.eval_shape`` — no allocation): for each attention layer,
    the bytes of one sequence row of every leaf.  GQA: ``2·kv·hd·itemsize``
    per layer; MLA caches only the latent ``c_kv + k_rope`` (the absorbed
    path never materializes per-head K/V — charging the GQA formula made
    deepseek-style admission ~an order of magnitude too conservative);
    attention-free SSM/xLSTM models carry O(1) recurrent state and no
    per-token pages at all, so this is 0 for them (the old
    ``max(n_attn, 1)`` floor charged them per-token paging).

    ``kv_dtype="int8"`` (DESIGN.md §15) rates the quantized layout — int8
    value leaves plus the f32 scale leaves — so a fixed ``kv_budget_bytes``
    admits ~2× the tokens of the native-dtype cache."""
    per_spec: dict = {}
    total = 0
    for spec in cfg.layer_specs():
        if spec.mixer != ATTN:
            continue
        if spec not in per_spec:
            leaves = jax.eval_shape(
                lambda s=spec: blocks.block_init_cache(cfg, s, 1, 1, 2,
                                                       kv_dtype))
            per_spec[spec] = sum(
                int(np.prod(leaf.shape[2:])) * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(leaves))
        total += per_spec[spec]
    return total


@dataclasses.dataclass
class EngineStats:
    iterations: int = 0
    prefill_tokens: int = 0          # prompt tokens admitted to the cache
    prefill_model_tokens: int = 0    # model token-positions run during
    #                                  prefill; must equal prefill_tokens
    #                                  (linear prefill, O(p) per prompt)
    decode_tokens: int = 0
    wall_time: float = 0.0
    # host/device overlap split (DESIGN.md §10):
    #   host_time        — scheduling, packing, metadata build, commit,
    #                      finalize (pure host work)
    #   dispatch_time    — time inside jitted calls (enqueue overhead on an
    #                      async backend, ≈ device compute on a sync one)
    #   blocked_sync_time— time spent *waiting* on device→host transfers
    #   blocking_syncs   — retrievals whose result was not already ready,
    #                      i.e. the syncs that actually stalled the host
    host_time: float = 0.0
    dispatch_time: float = 0.0
    blocked_sync_time: float = 0.0
    blocking_syncs: int = 0
    model_dispatches: int = 0        # hot-path model program launches
    host_syncs: int = 0              # device→host result transfers
    packed_pad_tokens: int = 0       # bucketing padding launched (packed step)
    dense_batch_hist: dict[int, int] = dataclasses.field(default_factory=dict)
    # iterations per launched KV-length bucket (DESIGN.md §9; packed step)
    kv_bucket_hist: dict[int, int] = dataclasses.field(default_factory=dict)
    # Σ launch_tokens × kv_bucket — the packed-attention score-work actually
    # launched; compare against launch_tokens × max_len to see the bucketing
    # saving (attention FLOPs/bytes scale with this, not with max_len)
    packed_attn_kv_rows: int = 0
    # modeled TP collective traffic (DESIGN.md §11; ring all-reduce wire
    # bytes per tp_lib.collective_bytes_per_iter) — 0 at tp=1
    tp_collective_bytes: int = 0
    # speculative decoding (DESIGN.md §13): drafts launched into verify
    # segments, drafts the target model accepted, and verify segments
    # retired — acceptance is counted at retire time (device truth), so
    # decode_tokens stays the committed-token trajectory
    spec_proposed_tokens: int = 0
    spec_accepted_tokens: int = 0
    spec_verify_segments: int = 0
    # fault-tolerant re-dispatch (DESIGN.md §14): requests checkpointed and
    # handed back by ``evacuate`` (replica failure or graceful leave), and
    # the committed tokens they fold into their replay prefix — the
    # re-prefill work another replica will absorb
    evacuated_requests: int = 0
    evacuated_tokens: int = 0
    # int8 KV quantization (DESIGN.md §15): cache bytes saved vs the
    # native-dtype layout for every token row written (counted at launch —
    # tokens × (native rate − quantized rate))
    kv_quant_bytes_saved: int = 0
    # programs compiled (or loaded from the persistent compile cache) while
    # a packed step was dispatched, their seconds, and how many fell on each
    # (launch tokens, kv bucket) key
    compiles: int = 0
    compile_time: float = 0.0
    compile_hist: dict[tuple[int, int], int] = dataclasses.field(
        default_factory=dict)

    @property
    def total_tokens(self) -> int:
        return self.prefill_tokens + self.decode_tokens

    @property
    def throughput(self) -> float:
        """Tokens over the wall time spent serving, compiles left out."""
        serving = self.wall_time - self.compile_time
        return self.total_tokens / serving if serving > 0 else 0.0

    @property
    def prefill_expansion(self) -> float:
        """Model-token-positions per prompt token (1.0 == linear prefill)."""
        return (self.prefill_model_tokens / self.prefill_tokens
                if self.prefill_tokens else 0.0)

    @property
    def dispatches_per_iter(self) -> float:
        return self.model_dispatches / self.iterations if self.iterations else 0.0

    @property
    def syncs_per_iter(self) -> float:
        return self.host_syncs / self.iterations if self.iterations else 0.0

    @property
    def blocking_syncs_per_iter(self) -> float:
        """Steady-state pipeline health (§10): < 1 means some iterations'
        results were already on host when the engine asked for them — the
        host/device overlap absorbed the sync."""
        return self.blocking_syncs / self.iterations if self.iterations \
            else 0.0

    @property
    def tp_collective_bytes_per_iter(self) -> float:
        return self.tp_collective_bytes / self.iterations \
            if self.iterations else 0.0

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of launched draft tokens the target model accepted."""
        return self.spec_accepted_tokens / self.spec_proposed_tokens \
            if self.spec_proposed_tokens else 0.0

    @property
    def spec_accepted_per_verify(self) -> float:
        """Committed tokens per verify segment (the base sample plus
        accepted drafts): > 1 means speculation beats one-token decode."""
        return (self.spec_accepted_tokens + self.spec_verify_segments) \
            / self.spec_verify_segments if self.spec_verify_segments else 0.0

    _DERIVED = ("total_tokens", "throughput", "prefill_expansion",
                "dispatches_per_iter", "syncs_per_iter",
                "blocking_syncs_per_iter", "tp_collective_bytes_per_iter",
                "spec_acceptance_rate", "spec_accepted_per_verify")

    def snapshot(self) -> dict:
        """Common stats schema (same contract as ``KVStats.snapshot``):
        every counter field plus the derived ratios, consumed by serve.py
        prints, benchmark JSON artifacts, and tests."""
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self)}
        out["dense_batch_hist"] = dict(self.dense_batch_hist)
        out["kv_bucket_hist"] = dict(self.kv_bucket_hist)
        out["compile_hist"] = dict(self.compile_hist)
        for name in self._DERIVED:
            out[name] = getattr(self, name)
        return out


# A compile fires synchronously inside the jitted call that needs it, so the
# one listener charges it to whichever packed dispatch this thread is in.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_dispatching = threading.local()


def _on_compile(event: str, duration: float, **_) -> None:
    at = getattr(_dispatching, "at", None)
    if event == _COMPILE_EVENT and at is not None:
        stats, key = at
        stats.compiles += 1
        stats.compile_time += duration
        stats.compile_hist[key] = stats.compile_hist.get(key, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_compile)


@dataclasses.dataclass
class _InFlight:
    """One launched-but-unretired packed iteration (DESIGN.md §10): the
    deferred device→host sync is the ``tokens`` handle — the per-slot
    payload ``(max_slots, W + 1)`` of token ring ‖ accept_len (§13), one
    transfer per iteration regardless of speculation width."""
    plan: BatchPlan
    sample_at: list              # (rid, slot, kind) triples; kind is
    #                              "decode" | "verify" | "prefill"
    tokens: jax.Array            # payload handle, not yet transferred
    iteration: int               # stats.iterations at launch


class ServeEngine:
    #: legacy keyword -> EngineConfig field (one release of back-compat;
    #: ``page_size`` is the old name for the block-table block size)
    _LEGACY_KWARGS = {
        "max_slots": "max_slots", "max_len": "max_len",
        "page_size": "kv_block_size", "kv_block_size": "kv_block_size",
        "total_pages": "total_pages", "kv_budget_bytes": "kv_budget_bytes",
        "avg_decode_len": "avg_decode_len",
        "discrete_sizes": "discrete_sizes", "async_depth": "async_depth",
        "async_harvest": "async_harvest", "nano": "nano", "tp": "tp",
        "kv_buckets": "kv_buckets", "kv_bucketing": "kv_bucketing",
        "prefix_caching": "prefix_caching", "attn_fast": "attn_fast",
        "attn_stream": "attn_stream", "seed": "seed",
        "spec_k": "spec_k", "drafter": "drafter",
        "temperature": "temperature", "top_k": "top_k",
        "kv_dtype": "kv_dtype",
    }

    def __init__(self, cfg: ModelConfig, params,
                 config: Optional[EngineConfig] = None, **kwargs):
        """``ServeEngine(cfg, params, EngineConfig(...))`` is the
        configuration surface; every engine knob lives on the frozen
        ``EngineConfig`` (serving/config.py), validated in its
        ``__post_init__``.  ``**kwargs`` are accepted as overrides on top of
        ``config`` — and, with no ``config``, as the legacy keyword style
        (deprecated for one release; ``page_size`` maps to
        ``kv_block_size``)."""
        unknown = set(kwargs) - set(self._LEGACY_KWARGS)
        if unknown:
            raise TypeError(f"unknown engine kwargs: {sorted(unknown)}")
        mapped = {self._LEGACY_KWARGS[k]: v for k, v in kwargs.items()}
        if config is None:
            if mapped:
                warnings.warn(
                    "ServeEngine(cfg, params, max_slots=..., ...) keyword "
                    "configuration is deprecated; pass "
                    "ServeEngine(cfg, params, EngineConfig(...))",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig(**mapped)
        elif mapped:
            config = dataclasses.replace(config, **mapped)
        self.config = config
        self.cfg = cfg
        self.params = params
        self.max_slots = config.max_slots
        self.max_len = config.max_len
        # async pipeline (DESIGN.md §10): up to async_depth iterations stay
        # in flight; async_harvest additionally retires any already-finished
        # iteration without blocking, shrinking the speculation window
        # (tests pin it False to exercise worst-case lag deterministically)
        self.async_depth = config.resolved_async_depth
        self.async_harvest = bool(config.async_harvest)
        self._ring: deque[_InFlight] = deque()
        # commit/arrival timestamp source: the replica pool (DESIGN.md §14)
        # injects a virtual clock for deterministic SLO tests; duration
        # accounting (host/dispatch/blocked splits) always uses perf_counter
        self._clock = time.perf_counter
        self.nano = config.nano
        self.key = jax.random.PRNGKey(config.seed)
        # §Perf HC3 toggles (single source of truth: EngineConfig): resolved
        # ONCE here — an explicit config value wins, else the process
        # default (an active ops.attn_config pin, else one env read) — and
        # pinned around every jitted trace body, so a later env flip can
        # never silently change a retrace (EngineConfig.from_env pins env
        # into explicit field values for callers who want that eagerly)
        self.attn_fast = bool(config.attn_fast) \
            if config.attn_fast is not None else ops.attn_fast_default()
        self.attn_stream = bool(config.attn_stream) \
            if config.attn_stream is not None else ops.attn_stream_default()
        # KV-length bucket grid (DESIGN.md §9): the packed step sweeps only
        # the iteration's bucket, not max_len; kv_bucketing=False pins the
        # single max_len bucket (the pre-§9 dense-vs-full-cache behaviour,
        # kept for A/B)
        self.kv_buckets = config.resolved_kv_buckets()

        # KV storage dtype (DESIGN.md §15): "bf16" keeps the model's native
        # dtype; "int8" swaps the attention cache leaves for int8 values +
        # f32 per-(token, kv-head) scales — quantize-at-scatter in the
        # packed program, dequant-on-load in the attention kernel
        self.kv_dtype = config.kv_dtype
        self._cache_kv_dtype = "int8" if self.kv_dtype == "int8" else None
        # per-token KV bytes from the actual cache leaves — NOT the GQA
        # formula: MLA caches only the latent (c_kv + k_rope) and
        # attention-free recurrent models cache nothing per token
        page_size = config.kv_block_size
        kv_bytes = kv_bytes_per_token(cfg, self._cache_kv_dtype)
        # native-dtype rate, kept for the bytes-saved counter (== kv_bytes
        # when not quantizing, so the saving reads 0)
        self._kv_bytes_native = kv_bytes if self._cache_kv_dtype is None \
            else kv_bytes_per_token(cfg)
        if config.total_pages is not None:
            pages = config.total_pages
        elif config.kv_budget_bytes is not None and kv_bytes > 0:
            # device KV budget in bytes -> pages the budget actually buys
            # (what the wrong bytes-per-token used to corrupt: deepseek-style
            # MLA got ~an order of magnitude fewer pages than its latent
            # cache needs)
            pages = max(int(config.kv_budget_bytes)
                        // (kv_bytes * page_size), 1)
        else:
            pages = config.max_slots * config.max_len // page_size
        # cross-request prefix caching (DESIGN.md §12): block-table mode —
        # block ids ARE physical storage (flat slot-cache rows / block
        # size), so the pool is capped at what the leaves can hold, and the
        # model must be attention-only (recurrent mixers carry per-slot
        # state that cannot be block-shared)
        self.prefix_caching = bool(config.prefix_caching)
        if self.prefix_caching:
            assert all(s.mixer == ATTN for s in cfg.layer_specs()), \
                "prefix caching (DESIGN.md §12) needs attention-only models"
            for b in self.kv_buckets:
                assert b % page_size == 0, \
                    (f"kv bucket {b} not divisible by kv_block_size "
                     f"{page_size}")
            pages = min(pages, config.max_slots * config.max_len // page_size)
        self._nb_cols = config.max_len // page_size
        self.kv = PagedKVManager(total_pages=pages, page_size=page_size,
                                 bytes_per_token=kv_bytes,
                                 avg_decode_len=config.avg_decode_len,
                                 prefix_caching=self.prefix_caching)
        # speculative decoding (DESIGN.md §13): each decoding slot launches
        # a spec_k+1-token verify segment; acceptance/rollback happen
        # on-device, so the mode needs attention-only models — rejected
        # positions just stay unattended cache rows, whereas a recurrent
        # mixer's per-slot state would already have advanced through them
        self.spec_k = int(config.spec_k)
        if self.spec_k:
            assert all(s.mixer == ATTN for s in cfg.layer_specs()), \
                "speculative decoding (DESIGN.md §13) needs attention-only " \
                "models (recurrent state cannot roll back rejected positions)"
        self.drafter = (draft_lib.make_drafter(config.resolved_drafter)
                        if self.spec_k else None)
        # packed-step sampling (greedy when temperature == 0 — the default
        # and the spec-decode exactness baseline)
        self.temperature = float(config.temperature)
        self.top_k = config.top_k
        self.scheduler = GlobalBatchScheduler(
            self.kv, discrete_sizes=config.discrete_sizes,
            max_active=config.max_slots, kv_buckets=self.kv_buckets,
            max_request_len=self.max_len, spec_k=self.spec_k,
            drafter=self.drafter)

        # tensor parallelism (DESIGN.md §11): 1-D ("model",) mesh, params
        # and slot caches placed with the manual shard_map layout (fused
        # x‖z / u‖g projection columns re-interleaved so each shard holds
        # matching halves; caches made sharded, never whole on one device);
        # the last_token / cache_len buffers stay replicated so the §10
        # feedback loop closes without a collective
        self.tp = int(config.tp)
        self._mesh = None
        if self.tp > 1:
            tp_lib.validate_tp(cfg, self.tp)
            self._mesh = make_tp_mesh(self.tp)
            self.params = tp_lib.place_params_tp(cfg, self.params, self._mesh)

        # slot caches: model cache trees with leading batch = max_slots
        self.cache = self._new_cache(self.max_slots)
        self.cache_len = jnp.zeros((self.max_slots,), jnp.int32)
        # device-resident sampled-token feedback (DESIGN.md §10), generalized
        # to the per-slot token ring (§13): row = the W = spec_k+1 samples of
        # the slot's last verify segment, of which the first accept_len were
        # accepted.  The packed program scatters each sample point's tokens
        # here and gathers the next iteration's decode inputs from
        # ring[slot, accept_len-1] *in-program*, so accepted tokens never
        # touch the host to form the next input stream (multi-codebook
        # frontends keep codebook 0, matching the host feedback path).
        # W = 1 collapses exactly to the §10 single-token buffer.
        self.last_token = jnp.zeros((self.max_slots, self.spec_k + 1),
                                    jnp.int32)
        self.accept_len = jnp.ones((self.max_slots,), jnp.int32)
        self.slot_free = list(range(self.max_slots))
        self.stats = EngineStats()
        # host mirror of each slot's context length (packed step builds its
        # per-token positions from this without any device read).  With
        # speculation this is the *upper bound* — every verify launch
        # advances it by W; retire resyncs it to the committed truth
        # (total_tokens - 1 + inflight), so it never drifts past what the
        # scheduler's worst-case KV accounting already covers
        self._pos = np.zeros((self.max_slots,), np.int64)

        # fresh one-slot cache, selected into a slot's recurrent state on
        # (re)assignment (``_reset_recurrent``) so a reused slot never leaks
        # the previous request's state
        self._slot_init = self._new_cache(1)

        # modeled collective wire bytes per launched token (linear in T):
        # resolved once here so the per-iteration stats update off the §10
        # host hot path is a single multiply
        self._tp_iter_bytes = tp_lib.collective_bytes_per_iter(
            cfg, 1, self.tp, jnp.dtype(cfg.dtype).itemsize)
        if self.tp > 1:
            rep = NamedSharding(self._mesh, P())
            self.cache_len = jax.device_put(self.cache_len, rep)
            self.last_token = jax.device_put(self.last_token, rep)
            self.accept_len = jax.device_put(self.accept_len, rep)

        # one compiled program per (bucketed launch length T, kv bucket) —
        # the compile cache is bounded by |discrete dense sizes| × |kv
        # buckets| (kv_bucket is static: it sets the swept cache extent;
        # the last_token buffer is a traced operand, NOT a trace axis).
        # tp=1 jits the body directly (the exact single-device path);
        # tp>1 wraps the same body in shard_map over the mesh — same
        # trace axes, so the compile-cache bound is preserved per mesh
        if self.tp == 1:
            self._packed_step = jax.jit(self._packed_impl,
                                        donate_argnums=(1, 8, 9),
                                        static_argnums=(16,))
        else:
            self._packed_step = self._build_packed_tp_step()
        # block-table operands (DESIGN.md §12) are traced arrays of static
        # shape, so they add no compile-cache axis; outside prefix mode the
        # step gets these (1,) dummies, which the python-constant
        # ``prefix_caching`` branch in ``_packed_core`` dead-code-eliminates
        self._dummy_dst = jnp.zeros((1,), jnp.int32)
        self._dummy_blk = jnp.zeros((1,), jnp.int32)
        # whole-block device copy for copy-on-write divergence: (src, dst)
        # are traced scalars, so ALL CoW traffic shares one compiled
        # program; the donated cache makes each copy a data dependency of
        # the following packed dispatch (device-order safety without a sync)
        self._cow_step = jax.jit(self._cow_impl, donate_argnums=(0,))

    def _new_cache(self, slots: int) -> list:
        if self._mesh is None:
            return model_lib.init_cache(self.cfg, 1, slots, self.max_len,
                                        self._cache_kv_dtype)
        return tp_lib.init_cache_tp(self.cfg, slots, self.max_len,
                                    self._mesh, self._cache_kv_dtype)

    # ---- jitted token-packed step (one dispatch per iteration) --------------
    def _packed_impl(self, params, cache, tokens, token_slot, token_pos,
                     token_active, cache_len, reset, last_token, accept_len,
                     from_last, sample_slot, verify_idx, token_rid, token_dst,
                     block_tables, kv_bucket):
        """tp=1 entry: the packed body with the fresh-slot cache closed over
        (the TP entry passes it as a shard_map operand instead)."""
        return self._packed_core(params, cache, tokens, token_slot, token_pos,
                                 token_active, cache_len, reset, last_token,
                                 accept_len, from_last, sample_slot,
                                 verify_idx, token_rid, token_dst,
                                 block_tables, self._slot_init, kv_bucket)

    def _packed_core(self, params, cache, tokens, token_slot, token_pos,
                     token_active, cache_len, reset, last_token, accept_len,
                     from_last, sample_slot, verify_idx, token_rid, token_dst,
                     block_tables, slot_init, kv_bucket):
        """The whole iteration as one program (DESIGN.md §8): reset reused
        slots' recurrent state, substitute the stream's decode placeholders
        with the device-resident token ring (§10/§13 — the previous
        iteration's samples never round-trip through the host), run the
        packed multi-segment forward, sample on-device (greedy by default),
        scatter the samples back into the ring at the stream's sample
        points, and advance ``cache_len`` from the per-token metadata — so
        the only device→host transfer is the per-slot payload (ring ‖
        accept_len), and even that one is deferrable (``async_depth``).

        With speculation (``spec_k > 0``, DESIGN.md §13) each decoding
        slot's row of ``verify_idx`` names its W = spec_k+1 stream
        positions.  Their true positions are computed HERE from the donated
        ``cache_len`` chain (``base + 0..k``), overwriting the host's
        worst-case values — that is what lets the host launch iteration
        i+1 before it knows how many of iteration i's drafts were
        accepted.  Acceptance is exact prefix matching (greedy) /
        sample-and-compare rejection sampling (stochastic, point-mass
        drafter): draft j is accepted iff it equals the target sample at
        position j-1; the committed run is the base sample plus the
        accepted prefix, ``accept_len = accepted + 1``, and ``cache_len``
        advances by exactly that (the on-device rollback — rejected
        positions' KV rows sit above the new length and are overwritten by
        the next verify segment before anything attends them).

        ``kv_bucket`` is static (DESIGN.md §9): attention sweeps only that
        many cache rows per slot, so the program's attention cost tracks
        the iteration's actual context, not ``max_len``.  Under TP this
        exact body runs inside ``shard_map`` (DESIGN.md §11) with a
        ``tp_ctx`` active, so the mixer families' reduction points become
        real collectives."""
        cache = self._reset_recurrent(cache, reset, slot_init)
        W = self.spec_k + 1
        T = token_slot.shape[0]
        pos = token_pos
        if self.spec_k:
            # device-true verify positions: segment j writes cache_len + j
            vpos = cache_len[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
            pos = pos.at[verify_idx.reshape(-1)].set(
                vpos.reshape(-1).astype(pos.dtype), mode="drop")
            is_verify = jnp.zeros((T,), bool).at[
                verify_idx.reshape(-1)].set(True, mode="drop")
            verify_on = verify_idx[:, 0] < T            # (max_slots,)
        token_wpos = jnp.where(token_active, pos, self.max_len) \
            .astype(jnp.int32)
        toks = sampling.substitute_last(tokens, last_token, token_slot,
                                        from_last, accept_len=accept_len)
        if self.prefix_caching and self.spec_k:
            # verify write targets follow the device-true positions through
            # the slot's block table (the host left them OOB)
            bs = self.kv.page_size
            blk = block_tables[token_slot,
                               jnp.minimum(pos // bs, self._nb_cols - 1)]
            vdst = blk.astype(token_dst.dtype) * bs + pos % bs
            token_dst = jnp.where(is_verify & token_active, vdst, token_dst)
        with ops.attn_config(fast=self.attn_fast, stream=self.attn_stream):
            # self.prefix_caching is a python constant per engine, so the
            # non-prefix trace never sees the (dummy) block operands at all
            logits, new_cache = model_lib.forward_packed(
                self.cfg, params, toks, cache, token_slot, pos,
                token_wpos, token_active, kv_bucket=kv_bucket,
                token_dst=token_dst if self.prefix_caching else None,
                block_tables=block_tables if self.prefix_caching else None)
        if self.temperature > 0:
            # keys fold (rid, pos) ONLY — launch-index and slot independent
            # (sampling.packed_keys), so stochastic serving replays exactly
            # and §13 re-verifies of a rejected position repeat the same
            # draw (point-mass speculation stays token-exact)
            keys = sampling.packed_keys(self.key, token_rid, pos,
                                        self.max_len + 1)
            next_tok = sampling.sample_tokens(logits[0], keys,
                                              self.temperature, self.top_k)
        else:
            next_tok = sampling.greedy(logits[0])
        # multi-codebook frontends keep codebook 0 (the one rule, §10)
        tok0 = next_tok if next_tok.ndim == 1 else next_tok[:, 0]
        new_len = jnp.where(reset, 0, cache_len)
        if self.spec_k:
            in0 = toks[0] if toks.ndim == 2 else toks[0, :, 0]
            # per verify slot: the W inputs and W target samples of its
            # segment (fill values never match each other on OOB rows)
            seg_in = jnp.take(in0.astype(jnp.int32), verify_idx, axis=0,
                              mode="fill", fill_value=-1)
            seg_out = jnp.take(tok0, verify_idx, axis=0, mode="fill",
                               fill_value=-2)
            # draft j (input j) is accepted iff it equals target sample
            # j-1; the accepted run is the longest matching prefix
            match = (seg_in[:, 1:] == seg_out[:, :-1]).astype(jnp.int32)
            acc = jnp.cumprod(match, axis=1).sum(axis=1)
            n_acc = jnp.where(verify_on, acc + 1, accept_len)
            new_ring = jnp.where(verify_on[:, None], seg_out, last_token)
            nv = token_active & ~is_verify
            new_len = new_len.at[token_slot].max(jnp.where(nv, pos + 1, 0))
            # the §13 rollback: verify slots advance by the accepted count
            # only; rejected rows sit above new_len, overwritten next launch
            new_len = jnp.where(verify_on, cache_len + acc + 1, new_len)
        else:
            n_acc = accept_len
            new_ring = last_token
            new_len = new_len.at[token_slot].max(
                jnp.where(token_active, pos + 1, 0))
        # single-sample points (prefill-final; every decode at spec_k=0)
        # write ring column 0 with accept_len 1
        new_ring = new_ring.at[sample_slot, 0].set(
            tok0.astype(new_ring.dtype), mode="drop")
        n_acc = n_acc.at[sample_slot].set(1, mode="drop")
        payload = jnp.concatenate(
            [new_ring, n_acc[:, None].astype(new_ring.dtype)], axis=1)
        return payload, new_cache, new_len, new_ring, n_acc

    def _build_packed_tp_step(self):
        """jit(shard_map(packed body)) over the 1-D TP mesh (DESIGN.md
        §11).  The body is ``_packed_core`` unchanged, wrapped by
        ``tp.shard_map_packed`` (a ``tp_ctx`` whose nano split comes from
        the static launch length) — so the compile cache still keys only
        on (T bucket, kv bucket).  Returns a callable with the tp=1 step's
        signature (the fresh-slot cache is injected as a shard_map operand
        here; carries ``_cache_size`` for the compile-cache-bound
        assertions)."""
        param_specs = tp_lib.param_pspecs_tp(self.cfg)
        cache_specs = tp_lib.cache_pspecs_tp(self.cfg, self._cache_kv_dtype)
        rep = P()
        # token_dst / block_tables / verify_idx ride as replicated
        # operands: the cache leaves shard on head/channel axes only, so
        # block ids (flat (slot, seq) rows / block size) and stream indices
        # are shard-local and identical on every shard (DESIGN.md §12/§13)
        in_specs = (param_specs, cache_specs) + (rep,) * 14 + (cache_specs,)
        out_specs = (rep, cache_specs, rep, rep, rep)

        def entry(*args):
            # args: the 17 operands of _packed_core, then the static bucket
            *operands, kv_bucket = args
            return tp_lib.shard_map_packed(
                lambda *a: self._packed_core(*a, kv_bucket), self._mesh,
                self.nano, in_specs, out_specs)(*operands)

        jitted = jax.jit(entry, donate_argnums=(1, 8, 9),
                         static_argnums=(17,))

        def step(params, cache, tokens, token_slot, token_pos, token_active,
                 cache_len, reset, last_token, accept_len, from_last,
                 sample_slot, verify_idx, token_rid, token_dst, block_tables,
                 kv_bucket):
            return jitted(params, cache, tokens, token_slot, token_pos,
                          token_active, cache_len, reset, last_token,
                          accept_len, from_last, sample_slot, verify_idx,
                          token_rid, token_dst, block_tables,
                          self._slot_init, kv_bucket)

        step._cache_size = jitted._cache_size
        return step

    def _reset_recurrent(self, cache, reset, slot_init):
        """Select fresh recurrent state for slots in ``reset`` (reused slots
        must not leak the previous request's SSM/LSTM state).  Attention
        leaves need no reset — rows at or beyond the new request's written
        extent are never attended — and skipping them keeps the masked
        select off the big (slots, max_len, ...) tensors."""
        out = []
        for gi, (pattern, reps) in enumerate(self.cfg.layer_groups()):
            g = {}
            for i, spec in enumerate(pattern):
                sub = cache[gi][f"sub{i}"]
                if spec.mixer == ATTN:
                    g[f"sub{i}"] = sub
                else:
                    g[f"sub{i}"] = jax.tree.map(
                        lambda c, z: jnp.where(
                            reset.reshape((1, -1) + (1,) * (c.ndim - 2)),
                            z.astype(c.dtype), c),
                        sub, slot_init[gi][f"sub{i}"])
            out.append(g)
        return out

    # ---- copy-on-write block copy (DESIGN.md §12) ---------------------------
    def _cow_impl(self, cache, src, dst):
        """Copy physical block ``src`` -> ``dst`` in every attention cache
        leaf (prefix caching implies an attention-only model).  ``src`` and
        ``dst`` are *traced* int32 scalars, so all CoW traffic shares ONE
        compiled program; the cache is donated, making each queued copy a
        data dependency of the next packed dispatch — device ordering
        without a host sync, and no extra ``model_dispatches``."""
        bs = self.kv.page_size

        def copy(c):
            # leaves are (L, slots, max_len, ...); blocks live in the flat
            # (slots*max_len) row space, sharded (if at all) on trailing
            # head/channel axes only — shard-local reshape is safe
            flat = c.reshape((c.shape[0], c.shape[1] * c.shape[2])
                             + c.shape[3:])
            blk = jax.lax.dynamic_slice_in_dim(flat, src * bs, bs, axis=1)
            flat = jax.lax.dynamic_update_slice_in_dim(flat, blk, dst * bs,
                                                       axis=1)
            return flat.reshape(c.shape)

        return jax.tree.map(copy, cache)

    # ---- public API ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        # a slot holds max_len positions; without this clamp a request with
        # prompt_len + max_new_tokens > max_len decodes past the cache and
        # trips the kv-bucket bound mid-run (admission only checks pool
        # capacity, not per-slot extent).  Speculation reserves spec_k
        # extra rows of slack: a verify segment launched at the cap still
        # writes its (possibly rejected) draft positions (§13)
        req.max_new_tokens = min(
            req.max_new_tokens,
            max(self.max_len - req.prompt_len - self.spec_k, 0))
        self.scheduler.submit(req)

    @property
    def in_flight(self) -> int:
        """Launched-but-unretired iterations (§10); 0 once drained."""
        return len(self._ring)

    def run(self, max_iters: int = 10_000) -> list[Request]:
        done: list[Request] = []
        t0 = time.perf_counter()
        for _ in range(max_iters):
            tp = time.perf_counter()
            plan = self.scheduler.plan()
            self.stats.host_time += time.perf_counter() - tp
            if plan is None:
                if self._ring:
                    # nothing plannable until in-flight results land (e.g.
                    # every request sits in its post-EOS window): retire the
                    # oldest iteration and re-plan with its commits applied
                    done += self._retire_oldest()
                    continue
                break
            done += self.step(plan)
        done += self.drain()
        self.stats.wall_time += time.perf_counter() - t0
        return done

    def drain(self, max_retire: Optional[int] = None) -> list[Request]:
        """Retire in-flight iterations, oldest first (§10).  With no bound
        this is the exit barrier — ``run()`` drains before returning, and
        external plan/step drivers (online serving loops) must drain after
        their arrival loop so no sampled tokens are left on device.
        ``max_retire=1`` is the mid-loop idle step: retire just the oldest
        iteration (its commits may unblock planning) without flushing the
        whole pipeline and re-serializing host and device."""
        done: list[Request] = []
        retired = 0
        while self._ring and (max_retire is None or retired < max_retire):
            done += self._retire_oldest()
            retired += 1
        return done

    def evacuate(self, *, drain: bool = True) \
            -> tuple[list[Request], list[Request]]:
        """Checkpoint every unfinished request for re-dispatch on another
        replica (DESIGN.md §14) and release all engine-local state for them
        (slot, cache_len, KV blocks).  Returns ``(finished, moved)``.

        ``drain=True`` is the graceful drain-and-evacuate (replica leave):
        in-flight iterations retire first, so their sampled tokens commit
        and the replay prefix is as long as possible.  ``drain=False`` is
        the failure path: a dead replica's in-flight results are *lost* —
        the ring is abandoned unfetched and only committed tokens survive
        into the checkpoint (which is exactly what keeps the resumed
        generation token-exact: nothing uncommitted is ever replayed).

        Requests whose committed output already holds EOS (or whose budget
        is spent) finish here instead of moving — they have nothing left to
        generate, and re-running them would append past EOS."""
        finished: list[Request] = []
        if drain:
            finished += self.drain()
        else:
            self._ring.clear()
        moved: list[Request] = []
        sched = self.scheduler
        for r in list(sched.active) + list(sched.waiting):
            if r.state in (State.FINISHED, State.DISCARDED, State.REJECTED):
                continue
            if r.slot >= 0:
                self.slot_free.append(r.slot)
                self._pos[r.slot] = 0
                if drain:
                    # a live device: clear the slot length for reuse.  On
                    # the failure path the device is gone — skip the op.
                    self.cache_len = self.cache_len.at[r.slot].set(0)
                r.slot = -1
            self.kv.free(r.rid)
            folded = r.checkpoint_redispatch()
            if r.state == State.FINISHED:
                # EOS/budget already committed: finished at the checkpoint
                r.finished_at = self._clock()
                finished.append(r)
                continue
            self.stats.evacuated_requests += 1
            self.stats.evacuated_tokens += folded
            moved.append(r)
        sched.active = []
        sched.waiting.clear()
        return finished, moved

    def step(self, plan: BatchPlan) -> list[Request]:
        self.stats.iterations += 1
        self.stats.dense_batch_hist[plan.dense_batch] = \
            self.stats.dense_batch_hist.get(plan.dense_batch, 0) + 1
        # launch now, sync up to async_depth iterations later
        self._ring.append(self._launch_packed(plan))
        finished: list[Request] = []
        if self.async_harvest:
            # free retirement: anything whose result already landed commits
            # now, keeping the speculation window as small as possible
            while self._ring and self._ring[0].tokens.is_ready():
                finished += self._retire_oldest()
        while len(self._ring) > self.async_depth:
            finished += self._retire_oldest()
        return finished

    # ---- packed iteration: one dispatch, one (deferred) host sync -----------
    @spans.spanned(spans.RETIRE)
    def _retire_oldest(self) -> list[Request]:
        """Transfer the oldest in-flight iteration's payload (the deferred
        sync — blocking only if the device hasn't caught up), commit its
        tokens to the scheduler, and finalize whatever finished.  The
        payload row for a verify slot is its token ring ‖ accept_len: the
        first ``accept_len`` ring entries are the committed run (§13);
        other sample points read ring column 0 (their accept_len is 1)."""
        inf = self._ring.popleft()
        with spans.span(spans.FETCH, iteration=inf.iteration):
            payload = self._fetch(inf.tokens)    # (max_slots, W + 1)
        t1 = time.perf_counter()
        W = self.spec_k + 1
        sampled: dict[int, object] = {}
        for rid, s, kind in inf.sample_at:
            if kind == "verify":
                n_acc = int(min(max(payload[s, W], 1), W))
                sampled[rid] = [int(x) for x in payload[s, :n_acc]]
                self.stats.spec_verify_segments += 1
                self.stats.spec_proposed_tokens += self.spec_k
                self.stats.spec_accepted_tokens += n_acc - 1
                # launch counted the guaranteed base sample; add the rest
                self.stats.decode_tokens += n_acc - 1
            else:
                sampled[rid] = int(payload[s, 0])
        finished = self.scheduler.commit(inf.plan, sampled, self._clock())
        for r in finished:
            self._finalize(r)
        if self.spec_k:
            # resync the host position upper bound to the committed truth:
            # each launch advanced _pos by the worst case W while the
            # device advanced by the accepted count — without this the
            # bound would drift one rejected-draft's worth per commit.
            # (total_tokens - 1) is the device cache_len after this commit
            # with nothing in flight; each still-in-flight launch adds at
            # most its worst case, which `inflight` counts exactly.
            for r in inf.plan.decode:
                if r.slot >= 0 and r.state not in (State.FINISHED,
                                                   State.DISCARDED):
                    self._pos[r.slot] = r.total_tokens - 1 + r.inflight
        self.stats.host_time += time.perf_counter() - t1
        return finished

    def _fetch(self, handle: jax.Array) -> np.ndarray:
        """Device→host retrieval with overlap accounting: counts the sync,
        the time spent waiting, and whether it actually blocked (the result
        was not yet ready — §10's pipeline-health signal)."""
        t0 = time.perf_counter()
        ready = handle.is_ready()
        out = np.asarray(handle)
        self.stats.blocked_sync_time += time.perf_counter() - t0
        self.stats.host_syncs += 1
        if not ready:
            self.stats.blocking_syncs += 1
        return out

    def _launch_packed(self, plan: BatchPlan) -> _InFlight:
        t_host = time.perf_counter()
        packed = self.scheduler.pack(plan, nano=self.nano)
        with spans.span(spans.OPERANDS):
            W = self.spec_k + 1
            reset = np.zeros((self.max_slots,), bool)
            for seg in packed.segments:
                r = seg.req
                if r.slot < 0:
                    assert self.slot_free, \
                        "scheduler admitted beyond slot capacity"
                    r.slot = self.slot_free.pop()
                    reset[r.slot] = True
                    self._pos[r.slot] = 0
                    if r.scheduled_at is None:
                        r.scheduled_at = self._clock()

            bs = self.kv.page_size
            oob = self.max_slots * self.max_len
            if self.prefix_caching:
                # decode writes land at pos = _pos[slot] .. _pos[slot]+W-1 (not
                # yet advanced; the worst case under speculation): grow each
                # decoding request's block table NOW, launch-side, so the write
                # targets exist before the (possibly deferred-commit)
                # ``extend`` ever runs (DESIGN.md §12)
                for seg in packed.segments:
                    if seg.is_decode:
                        self.kv.ensure(seg.req.rid,
                                       int(self._pos[seg.req.slot]) + W)

            t_total = packed.launch_tokens
            tokens = np.zeros((t_total,), np.int32)
            slot = np.zeros((t_total,), np.int32)
            pos = np.zeros((t_total,), np.int32)
            active = np.zeros((t_total,), bool)
            # decode positions take the ring's newest accepted token on device
            # (§10/§13): the host writes a placeholder and never needs the
            # sampled value
            from_last = np.zeros((t_total,), bool)
            # block-table operands (prefix mode): per-token flat scatter target
            # (OOB = dropped write, covers padding) and per-slot block tables
            token_dst = np.full((t_total,), oob, np.int64)
            tables_arr = np.zeros((self.max_slots, self._nb_cols), np.int32)
            # per-slot verify stream positions (§13); OOB rows (== t_total)
            # mark slots with no verify segment this iteration
            verify_idx = np.full((self.max_slots, W), t_total, np.int32)
            # per-token request id: the stochastic sampler's PRNG identity
            # (sampling.packed_keys folds (rid, pos) — slot- and
            # launch-independent); dead under greedy
            rid_arr = np.zeros((t_total,), np.int32)
            sample_at: list[tuple[int, int, str]] = []   # (rid, slot, kind)
            t = 0
            for seg in packed.segments:
                r = seg.req
                tbl = None
                if self.prefix_caching:
                    tbl = np.asarray(self.kv.table(r.rid), np.int64)
                    # the allocator sizes tables by *predicted* length
                    # (prompt + avg_decode), which may exceed max_len —
                    # blocks past max_len // bs hold no writable positions,
                    # so the gather table only needs the addressable prefix
                    nb = min(len(tbl), self._nb_cols)
                    tables_arr[r.slot, :nb] = tbl[:nb]
                rid_arr[t:t + seg.length] = r.rid & 0x7fffffff
                if seg.is_decode:
                    from_last[t] = True
                    slot[t:t + W] = r.slot
                    p = int(self._pos[r.slot])
                    # host positions are the worst-case bound; with spec_k > 0
                    # the program recomputes the true ones from cache_len
                    pos[t:t + W] = p + np.arange(W)
                    active[t:t + W] = True
                    if self.spec_k:
                        tokens[t + 1:t + W] = seg.draft
                        verify_idx[r.slot] = np.arange(t, t + W)
                        sample_at.append((r.rid, r.slot, "verify"))
                    else:
                        if tbl is not None and p // bs < len(tbl):
                            token_dst[t] = tbl[p // bs] * bs + p % bs
                        sample_at.append((r.rid, r.slot, "decode"))
                    t += W
                else:
                    ln = seg.length
                    tokens[t:t + ln] = r.prompt[seg.offset:seg.offset + ln]
                    slot[t:t + ln] = r.slot
                    qs = np.arange(seg.offset, seg.offset + ln)
                    pos[t:t + ln] = qs
                    active[t:t + ln] = True
                    if tbl is not None and len(tbl):
                        cov = qs // bs < len(tbl)
                        token_dst[t:t + ln] = np.where(
                            cov, tbl[np.minimum(qs // bs, len(tbl) - 1)] * bs
                            + qs % bs, oob)
                    if seg.offset + ln == r.prompt_len:
                        sample_at.append((r.rid, r.slot, "prefill"))
                    t += ln
            assert t == packed.tokens, (t, packed.tokens)
            # single-sample points scatter into ring column 0; non-sample
            # positions write out of bounds -> dropped.  Verify segments are
            # NOT sample points: their whole row lands by acceptance
            sample_slot = np.full((t_total,), self.max_slots, np.int32)
            t = 0
            for seg in packed.segments:
                if seg.is_decode:
                    if not self.spec_k:
                        sample_slot[t] = seg.req.slot
                    t += W
                else:
                    if seg.offset + seg.length == seg.req.prompt_len:
                        sample_slot[t + seg.length - 1] = seg.req.slot
                    t += seg.length

            # iteration's KV-length bucket (DESIGN.md §9): every attended row
            # must sit below it — the scheduler quantized the max extent up
            # (host pos is the §13 worst case, so the check stays sufficient)
            kv_bucket = packed.kv_bucket if packed.kv_bucket is not None \
                else self.max_len
            assert not active.any() or int(pos[active].max()) < kv_bucket, \
                (int(pos[active].max()), kv_bucket)
            self.stats.kv_bucket_hist[kv_bucket] = \
                self.stats.kv_bucket_hist.get(kv_bucket, 0) + 1
            self.stats.packed_attn_kv_rows += packed.launch_tokens * kv_bucket
            if self.tp > 1:
                self.stats.tp_collective_bytes += \
                    packed.launch_tokens * self._tp_iter_bytes

            tok_in = jnp.asarray(tokens[None])
            if self.cfg.frontend == "audio":
                tok_in = jnp.repeat(tok_in[..., None], self.cfg.num_codebooks,
                                    axis=-1)
            # launch-side bookkeeping BEFORE dispatch: the scheduler's next
            # plan may be formed while this iteration is still on device (§10)
            self.scheduler.mark_launched(plan)
            n_decode = 0
            for seg in packed.segments:
                if seg.is_decode:
                    self._pos[seg.req.slot] += W
                    n_decode += 1
                else:
                    self._pos[seg.req.slot] = seg.offset + seg.length
            # count the guaranteed base sample per decode/verify segment here;
            # accepted drafts are added at retire time (device truth)
            self.stats.decode_tokens += n_decode
            self.stats.prefill_tokens += packed.tokens - n_decode * W
            self.stats.prefill_model_tokens += packed.tokens - n_decode * W
            self.stats.packed_pad_tokens += packed.padding
            if self._cache_kv_dtype is not None:
                # cache bytes this launch did NOT write vs the native-dtype
                # layout: every real token scatters one quantized row per
                # attention layer (DESIGN.md §15)
                self.stats.kv_quant_bytes_saved += packed.tokens * \
                    (self._kv_bytes_native - self.kv.bytes_per_token)
            if self.prefix_caching:
                dst_op = jnp.asarray(token_dst.astype(np.int32))
                tbl_op = jnp.asarray(tables_arr)
                # drain queued copy-on-write block copies BEFORE the dispatch:
                # cache donation chains each copy in front of the forward pass
                # on device, with no host sync and no extra model dispatch
                for c_src, c_dst in self.kv.take_pending_copies():
                    self.cache = self._cow_step(self.cache, jnp.int32(c_src),
                                                jnp.int32(c_dst))
            else:
                dst_op, tbl_op = self._dummy_dst, self._dummy_blk
        t_disp = time.perf_counter()
        self.stats.host_time += t_disp - t_host
        it = self.stats.iterations
        _dispatching.at = (self.stats, (t_total, kv_bucket))
        try:
            with spans.span(spans.DISPATCH, iteration=it, tokens=t_total,
                            kv_bucket=kv_bucket, tp=self.tp,
                            nano=spans.groups(packed.nano.sizes)):
                payload, self.cache, self.cache_len, self.last_token, \
                    self.accept_len = self._packed_step(
                        self.params, self.cache, tok_in, jnp.asarray(slot),
                        jnp.asarray(pos), jnp.asarray(active),
                        self.cache_len, jnp.asarray(reset), self.last_token,
                        self.accept_len, jnp.asarray(from_last),
                        jnp.asarray(sample_slot), jnp.asarray(verify_idx),
                        jnp.asarray(rid_arr), dst_op, tbl_op, kv_bucket)
        finally:
            _dispatching.at = None
        self.stats.dispatch_time += time.perf_counter() - t_disp
        self.stats.model_dispatches += 1
        return _InFlight(plan=plan, sample_at=sample_at, tokens=payload,
                         iteration=it)

    def _finalize(self, r: Request) -> None:
        if r.slot >= 0:
            self.slot_free.append(r.slot)
            self.cache_len = self.cache_len.at[r.slot].set(0)
            self._pos[r.slot] = 0
            r.slot = -1
        # strip the post-EOS overshoot (async EOS, §5.3; under the §10
        # pipeline, later speculative tokens were already dropped at commit)
        if r.pending_eos and r.eos_id is not None and r.eos_id in r.output:
            r.output = r.output[: r.output.index(r.eos_id) + 1]
        # offload KV for multi-round reuse — size-only accounting: no
        # per-finished-request garbage blob is materialized (kvcache.py)
        self.kv.offload(r.rid,
                        nbytes=max(r.total_tokens * self.kv.bytes_per_token,
                                   1))

