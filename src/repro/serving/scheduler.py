"""Global batch scheduler (paper §4.2): continuous batching + chunked
prefill + discrete batching, with asynchronous control-flow scheduling
(§5.3 / DESIGN.md §10).

Every iteration the scheduler emits a ``BatchPlan``:
  * all active decode requests contribute one token each;
  * head-of-line prefill requests contribute chunks sized to top the dense
    batch up to the chosen *discrete* size (paper: GEMM efficiency cliffs —
    launch 2048, never 2049);
  * new requests are admitted eagerly while the KV peak-memory estimate fits.

Plans are formed **speculatively** from launch-side state
(``prefill_launched`` / ``inflight``), not committed results: every
in-flight decode is assumed to continue, so the engine can form and launch
iteration i+1 before iteration i's sampled tokens ever reach the host (the
§5.3 mechanism generalized from lag-1 EOS to a lag-(1+depth) pipeline).
``commit`` reconciles late — it applies sampled tokens as they arrive,
flags EOS (acted on at the next planning opportunity, paper's <1%
overhead), finishes requests, and *drops* speculative tokens that raced
past a finish (``dropped_tokens``).  With an eager engine
(``async_depth=0``) launch state never leads committed state and the
schedule is bit-identical to the pre-§10 lock-step one.

With speculative decoding (``spec_k > 0``, DESIGN.md §13) each decoding
request contributes a ``spec_k + 1``-token *verify segment* instead of a
single decode token: the device-fed last accepted token plus ``spec_k``
drafter proposals.  All launch-side accounting (``inflight``, KV extents,
token budgets) uses the worst case — every verify launch is charged the
full ``spec_k + 1`` samples — and ``commit`` reconciles with the actual
accepted prefix, so admission/planning stay conservative while the device
rolls ``cache_len`` back for rejected positions on its own.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

from repro.core.nanobatch import (NanoBatchPlan, nano_batch_sizes_for,
                                  packed_segment_order)
from repro.serving import spans
from repro.serving.kvcache import PagedKVManager
from repro.serving.request import Request, State


@dataclasses.dataclass
class PrefillChunk:
    req: Request
    offset: int          # token offset within the prompt
    length: int


@dataclasses.dataclass
class BatchPlan:
    decode: list[Request]
    prefill: list[PrefillChunk]
    dense_batch: int     # the discrete dense size this plan fills
    # tokens per decode entry: 1, or spec_k + 1 when each decoding slot
    # launches a verify segment (DESIGN.md §13)
    decode_width: int = 1

    @property
    def dense_tokens(self) -> int:
        return (len(self.decode) * self.decode_width
                + sum(c.length for c in self.prefill))


@dataclasses.dataclass
class PackedSegment:
    """One contiguous token run of the packed stream (DESIGN.md §8):
    a single decode token, one prefill chunk, or — with speculative
    decoding (§13) — one ``spec_k + 1``-token verify segment whose first
    token is device-fed and whose tail holds the drafter's proposals."""
    req: Request
    offset: int          # position of the segment's first token (prefill);
    #                      decode positions come from the engine's slot state
    length: int
    is_decode: bool
    draft: tuple[int, ...] = ()   # spec_k proposals (verify segments only)


@dataclasses.dataclass
class PackedPlan:
    """Token-packed launch layout for one iteration: segments in nano-batch
    interleave order, plus the bucketed launch length (the *actual* compiled
    shape — the paper's discrete-batching insight applied end-to-end) and
    the iteration's KV-length bucket (DESIGN.md §9)."""
    segments: list[PackedSegment]
    tokens: int                     # real tokens (== BatchPlan.dense_tokens)
    launch_tokens: int              # bucketed T the program is compiled for
    dense_batch: int                # the discrete size the plan targeted
    nano: NanoBatchPlan             # nano-batch split of the launched stream
    segment_nano: tuple[int, ...]   # nano-batch id per segment
    kv_bucket: Optional[int] = None  # quantized max KV extent this iteration
    kv_needed: int = 0              # exact max KV extent (diagnostics)

    @property
    def padding(self) -> int:
        return self.launch_tokens - self.tokens


def default_kv_buckets(max_len: int, floor: int = 64) -> tuple[int, ...]:
    """Power-of-two KV-length grid up to ``max_len`` (DESIGN.md §9):
    ``(64, 128, 256, ..., max_len)``.  Coarse enough that the packed-step
    compile cache stays small (|T buckets| × |kv buckets| programs), fine
    enough that short-context iterations never sweep the whole cache."""
    b = min(floor, max_len)
    out = []
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class GlobalBatchScheduler:
    def __init__(self, kv: PagedKVManager, *,
                 discrete_sizes: tuple[int, ...] = (2048, 1024, 512, 256, 128,
                                                    64, 32, 16, 8),
                 max_active: int = 256,
                 prefill_chunk_min: int = 8,
                 kv_buckets: Optional[tuple[int, ...]] = None,
                 max_request_len: Optional[int] = None,
                 spec_k: int = 0, drafter=None):
        self.kv = kv
        self.sizes = tuple(sorted(discrete_sizes, reverse=True))
        self.max_active = max_active
        # speculative decoding (DESIGN.md §13): every decode entry plans,
        # launches, and is charged ``spec_k + 1`` tokens (worst case); the
        # drafter fills the segment's proposal tail at pack() time
        self.spec_k = int(spec_k)
        self.drafter = drafter
        # per-slot position extent (the engine's max_len): a prompt longer
        # than a slot can hold is never admitted — it stays in the waiting
        # queue (long-standing documented behavior), instead of prefilling
        # past the cache and tripping the kv-bucket bound mid-run
        self.max_request_len = max_request_len
        # KV-length grid (DESIGN.md §9), ascending; None disables bucketing
        # (PackedPlan.kv_bucket stays None -> the engine sweeps max_len)
        self.kv_buckets = (tuple(sorted(set(kv_buckets)))
                          if kv_buckets else None)
        # chunk lengths are quantized to the discrete sizes; raising the
        # floor to the smallest size means the only unbucketed lengths are
        # terminal remainders < chunk_min, keeping the engine's jit compile
        # cache bounded by len(sizes) + chunk_min - 1 programs
        self.chunk_min = max(prefill_chunk_min, self.sizes[-1])
        self.waiting: deque[Request] = deque()
        self.active: list[Request] = []
        # padding accounting for the packed step (tokens launched but unused)
        self.padding_tokens = 0
        self.launched_tokens = 0
        # speculative decode tokens launched for requests that finished
        # before their commit arrived (async pipeline overshoot, §10)
        self.dropped_tokens = 0
        # prompt tokens served from shared blocks at admission (§12)
        self.prefix_hit_tokens = 0

    # ---- admission ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.waiting.append(req)

    def _admit(self) -> None:
        """Eager admission under the peak-memory estimate (§4.4).  With a
        prefix-caching allocator (DESIGN.md §12) the prompt's token ids are
        handed to ``allocate`` for content-hash matching, and prefill starts
        at the cached boundary: the matched prefix's KV already sits in
        shared blocks, so only the uncached suffix is ever planned."""
        prefix = getattr(self.kv, "prefix_caching", False)
        while self.waiting and len(self.active) < self.max_active:
            cand = self.waiting[0]
            if (self.max_request_len is not None
                    and cand.prompt_len > self.max_request_len):
                break
            if not self.kv.can_admit(cand, self.active):
                break
            if not self.kv.allocate(cand.rid, max(cand.prompt_len, 1),
                                    token_ids=cand.prompt if prefix else None):
                break
            self.waiting.popleft()
            cand.state = State.PREFILL
            if prefix:
                cached = self.kv.cached_tokens(cand.rid)
                cand.prefill_done = cand.prefill_launched = cached
                self.prefix_hit_tokens += cached
            self.active.append(cand)

    # ---- discrete batching (§4.2) -------------------------------------------
    def _pick_dense(self, available: int) -> int:
        for s in self.sizes:
            if s <= available:
                return s
        return self.sizes[-1]

    def _quantize_chunk(self, want: int) -> int:
        """Round a prefill chunk length down to a discrete size.

        The engine's jitted prefill step compiles one program per chunk
        length; quantizing to the discrete set bounds the XLA compile cache
        (the paper's discrete-batching insight applied to prefill).  The
        only lengths that fall through are terminal remainders below the
        smallest discrete size (``chunk_min`` is floored at that size in
        ``__init__``), so the cache stays bounded by
        ``len(sizes) + chunk_min - 1`` entries."""
        for s in self.sizes:
            if s <= want:
                return s
        return want

    # ---- per-iteration plan --------------------------------------------------
    def _decodable(self, r: Request) -> bool:
        """Speculative decode eligibility (§10): plan from *launched* state.

        A request decodes once its whole prompt has been launched (the
        first decode token is the prefill-final sample, which may still be
        in flight — the engine's device-resident ``last_token`` buffer
        feeds it forward without a host round-trip).  Generation is capped
        by launched samples (``len(output) + inflight``), so speculation
        never runs past ``max_new_tokens``; once an EOS has been *committed*
        (``pending_eos``) the request stops planning as soon as one
        post-EOS token is in flight — the §5.3 single extra token,
        regardless of pipeline depth."""
        return (r.state != State.FINISHED
                and r.prefill_launched >= r.prompt_len
                and len(r.output) + r.inflight < r.max_new_tokens
                and not (r.pending_eos and r.inflight > 0))

    @spans.spanned(spans.PLAN)
    def plan(self) -> Optional[BatchPlan]:
        self._admit()
        decode = [r for r in self.active if self._decodable(r)]
        prefilling = [r for r in self.active if r.prefill_unlaunched > 0]

        width = self.spec_k + 1
        available = len(decode) * width + sum(r.prefill_unlaunched
                                              for r in prefilling)
        if available == 0:
            return None
        dense = self._pick_dense(available)

        budget = max(dense - len(decode) * width, 0)
        chunks: list[PrefillChunk] = []
        for r in prefilling:
            if budget < min(self.chunk_min, r.prefill_unlaunched):
                break
            take = self._quantize_chunk(min(budget, r.prefill_unlaunched))
            chunks.append(PrefillChunk(req=r, offset=r.prefill_launched,
                                       length=take))
            budget -= take
        return BatchPlan(decode=decode, prefill=chunks, dense_batch=dense,
                         decode_width=width)

    def mark_launched(self, plan: BatchPlan) -> None:
        """Advance launch-side state when the engine dispatches ``plan``
        (after ``pack()`` — packing reads the pre-launch in-flight counts).
        Each decode entry puts ``decode_width`` sampled tokens in flight
        (the worst case of a verify segment, §13 — ``commit`` reconciles
        with the accepted count) and each prefill-*final* chunk puts one;
        ``commit`` retires them as results arrive."""
        for r in plan.decode:
            r.inflight += plan.decode_width
        for c in plan.prefill:
            c.req.prefill_launched += c.length
            if c.req.prefill_launched >= c.req.prompt_len:
                c.req.inflight += 1

    # ---- packed launch layout (single-dispatch step, DESIGN.md §8) ----------
    def bucket_tokens(self, tokens: int) -> int:
        """Launch length for ``tokens`` packed tokens: the smallest discrete
        dense size that fits (compile-cache bounded by ``len(sizes)``), or —
        defensively, if an iteration ever exceeds the largest size — the
        next multiple of it.  When ``max_active`` sits below the smallest
        discrete size, it joins the grid as a floor bucket: a decode-only
        iteration can never exceed ``max_active`` tokens, and padding it up
        to a size no real batch reaches would be pure waste (one extra
        compiled program, used by every decode-only iteration).  With
        speculative decoding a decode-only iteration reaches
        ``max_active × (spec_k + 1)`` tokens, so that floor joins the grid
        instead — still exactly one extra bucket (the "static spec_k grid"
        of DESIGN.md §13's compile-cache accounting)."""
        grid = tuple(reversed(self.sizes))   # ascending
        floor = self.max_active * (self.spec_k + 1)
        if floor < grid[0]:
            grid = (floor,) + grid
        for s in grid:
            if tokens <= s:
                return s
        return -(-tokens // self.sizes[0]) * self.sizes[0]

    def bucket_kv(self, needed: int) -> int:
        """Quantize an iteration's max KV extent up to the kv-bucket grid
        (DESIGN.md §9) — the smallest bucket that covers it, saturating at
        the top of the grid (== the engine's ``max_len``)."""
        assert self.kv_buckets, "scheduler constructed without kv_buckets"
        for s in self.kv_buckets:
            if needed <= s:
                return s
        return self.kv_buckets[-1]

    def _kv_needed(self, segs: list[PackedSegment]) -> int:
        """Exact max KV extent this iteration's attention touches: a decode
        segment's first token writes at position ``total_tokens + inflight
        - 1`` (prompt + committed outputs + launched-but-uncommitted
        samples, which all occupy cache rows below it) and its last draft
        position sits ``spec_k`` rows further (§13 verify segments; the
        worst case — the device may roll back to less); each position
        attends one more row than its index.  A prefill chunk attends
        ``offset + length`` rows.  With an eager non-speculative engine
        ``inflight`` and ``spec_k`` are zero at pack time and this reduces
        to the pre-§10 ``total_tokens``."""
        needed = 1
        for s in segs:
            needed = max(needed,
                         s.req.total_tokens + s.req.inflight + self.spec_k
                         if s.is_decode else s.offset + s.length)
        return needed

    def _draft(self, r: Request) -> tuple[int, ...]:
        """Exactly ``spec_k`` draft tokens for a verify segment (§13).  The
        drafter sees the *committed* history only (under the async pipeline
        that lags the device by up to ``async_depth`` verifies — stale
        drafts lower acceptance, never correctness); short or empty
        proposals are padded with the last history token so every verify
        segment has the uniform static width the accounting assumes."""
        if self.spec_k == 0:
            return ()
        prop = list(self.drafter.propose(r, self.spec_k))[:self.spec_k] \
            if self.drafter is not None else []
        if len(prop) < self.spec_k:
            hist = r.prompt + r.output
            pad = prop[-1] if prop else (hist[-1] if hist else 0)
            prop += [pad] * (self.spec_k - len(prop))
        return tuple(int(t) for t in prop)

    @spans.spanned(spans.PACK)
    def pack(self, plan: BatchPlan, *, nano: int = 2) -> PackedPlan:
        """Lay one iteration's decode tokens + prefill chunks out as a
        token-packed stream: segments ordered by the nano-batch interleave
        (core/nanobatch.packed_segment_order — memory-bound decode first,
        compute-bound chunks in descending length), launch length bucketed
        to the discrete dense sizes, the max KV extent quantized to the
        kv-bucket grid, padding accounted."""
        width = plan.decode_width
        segs = [PackedSegment(req=r, offset=-1, length=width, is_decode=True,
                              draft=self._draft(r))
                for r in plan.decode]
        segs += [PackedSegment(req=c.req, offset=c.offset, length=c.length,
                               is_decode=False) for c in plan.prefill]
        order = packed_segment_order(
            [("verify" if s.length > 1 else "decode") if s.is_decode
             else "prefill" for s in segs],
            [s.length for s in segs])
        segs = [segs[i] for i in order]
        tokens = plan.dense_tokens
        launch = self.bucket_tokens(tokens)
        nano_plan = nano_batch_sizes_for(launch, nano)
        self.padding_tokens += launch - tokens
        self.launched_tokens += launch
        kv_needed = self._kv_needed(segs)
        return PackedPlan(segments=segs, tokens=tokens, launch_tokens=launch,
                          dense_batch=plan.dense_batch, nano=nano_plan,
                          segment_nano=nano_plan.assign_segments(
                              [s.length for s in segs]),
                          kv_bucket=(self.bucket_kv(kv_needed)
                                     if self.kv_buckets else None),
                          kv_needed=kv_needed)

    # ---- post-iteration bookkeeping -------------------------------------------
    @spans.spanned(spans.COMMIT)
    def commit(self, plan: BatchPlan, sampled, now: float) -> list[Request]:
        """Apply iteration results.  ``sampled``: rid -> next token id, or
        — for a §13 verify segment — the *accepted* token list (1 to
        ``decode_width`` tokens: the target-model sample at the segment
        base plus every accepted draft continuation).

        EOS is *not* acted on this iteration (async top-level scheduling,
        §5.3): the request is flagged and removed at the next planning
        opportunity, generating one extra token (one extra *verify
        segment* under speculation — everything after the post-EOS token
        is dropped here) — paper's <1% overhead.  Under a pipelined engine
        (§10) commits arrive up to ``async_depth`` iterations after their
        plan was formed; tokens sampled for a request that has since
        FINISHED (its later iterations were launched before the
        EOS-bearing commit landed) are *dropped* here — the request was
        already finalized and returned, so a late append would mutate a
        result the caller holds.  ``max_new_tokens`` truncation works the
        same way: accepted tokens past the cap are dropped, so speculation
        never overshoots the request's contract."""
        finished = []
        prefix = getattr(self.kv, "prefix_caching", False)
        for c in plan.prefill:
            c.req.prefill_done += c.length
            # lock-step drivers call plan()/commit() without the engine's
            # mark_launched(): keep launch state from falling *behind*
            # committed state, so the next plan's chunks still advance
            # (under a pipelined engine launched already leads done and
            # this is a no-op)
            c.req.prefill_launched = max(c.req.prefill_launched,
                                         c.req.prefill_done)
            # committed-and-written rows are exactly the prefilled prompt
            # prefix: full blocks below it promote into the hash table (§12)
            self.kv.extend(c.req.rid, max(c.req.total_tokens, 1),
                           token_ids=(c.req.prompt[:c.req.prefill_done]
                                      if prefix else None))
            if c.req.prefill_remaining == 0:
                c.req.state = State.DECODE
        decode_rids = {r.rid for r in plan.decode}
        for r in list(plan.decode) + [c.req for c in plan.prefill
                                      if c.req.state == State.DECODE]:
            tok = sampled.get(r.rid)
            if tok is None:
                continue
            toks = list(tok) if isinstance(tok, (list, tuple)) else [tok]
            # retire the *launched* worst case (decode_width per verify
            # segment, 1 per prefill-final), not the accepted count —
            # launch-side accounting charged the worst case too
            launched = plan.decode_width if r.rid in decode_rids else 1
            r.inflight = max(r.inflight - launched, 0)
            if r.state in (State.FINISHED, State.DISCARDED):
                self.dropped_tokens += len(toks)  # late speculative (§10)
                continue
            if r.first_token_at is None:
                r.first_token_at = now
            for t in toks:
                if r.state == State.FINISHED:
                    self.dropped_tokens += 1   # accepted past finish (§13)
                    continue
                r.output.append(t)
                # extend may fail only if the §4.4 peak estimate
                # under-predicted (requests decoding far past
                # avg_decode_len) — the launch-aware sweep
                # (kvcache.peak_pages) removes the pipeline-lag cause, the
                # rest is inherent to the heuristic; failures are counted
                # (KVStats.extend_failures), the paper's answer is rare
                # reclaim (State.DISCARDED), not a hard error on the
                # serving loop.  Committed-and-written rows at this point
                # are the prompt plus every output but the newest (its KV
                # lands next launch): only blocks fully below that promote
                # into the hash table (§12)
                self.kv.extend(r.rid, r.total_tokens + 1,
                               token_ids=(r.prompt + r.output[:-1]
                                          if prefix else None))
                hit_eos = (r.eos_id is not None and t == r.eos_id)
                if r.pending_eos or len(r.output) >= r.max_new_tokens:
                    r.state = State.FINISHED
                    r.finished_at = now
                    finished.append(r)
                elif hit_eos:
                    r.pending_eos = True   # detected next iteration
        self.active = [r for r in self.active if r.state != State.FINISHED]
        return finished

    @property
    def n_active(self) -> int:
        return len(self.active)

    @property
    def n_waiting(self) -> int:
        return len(self.waiting)
