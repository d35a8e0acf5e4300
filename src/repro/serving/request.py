"""Request lifecycle for the serving engine."""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class State(enum.Enum):
    WAITING = "waiting"        # admitted to queue, no KV yet
    PREFILL = "prefill"        # chunked prefill in progress
    DECODE = "decode"          # generating
    FINISHED = "finished"
    DISCARDED = "discarded"    # OOM victim (paper §4.4: rare reclaim)
    SWAPPED = "swapped"        # KV offloaded to host (multi-round)
    REJECTED = "rejected"      # shed by SLO admission control (DESIGN.md §14)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    arrival: float = 0.0
    eos_id: Optional[int] = None
    # multi-turn session key (DESIGN.md §14): the router pins a session to
    # one replica so follow-up turns land on their prefix-cached KV
    session: Optional[int] = None

    state: State = State.WAITING
    prefill_done: int = 0              # tokens prefilled so far (chunked)
    output: list[int] = dataclasses.field(default_factory=list)
    slot: int = -1                     # engine cache slot while active
    # engine clock when the request first got a slot, so its first chunk
    # launched: queue wait is ``scheduled_at - arrival``
    scheduled_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # async EOS (paper §5.3): EOS seen at iter i is acted on at iter i+1
    pending_eos: bool = False
    # ---- speculative launch state (async pipeline, DESIGN.md §10) ----------
    # prompt tokens *launched* into the model; runs ahead of ``prefill_done``
    # (which tracks committed results) by the in-flight iterations
    prefill_launched: int = 0
    # sampled tokens launched but not yet committed: in-flight decode tokens
    # plus the prefill-final token.  Planning bounds generation with
    # ``len(output) + inflight`` so speculation never launches past
    # ``max_new_tokens``, and caps post-EOS overshoot at one in-flight token.
    # Under §13 spec decoding this counts *worst-case* tokens — each verify
    # segment adds its full width ``spec_k + 1`` at launch and commit
    # reconciles down to the actual accept_len, so the bound stays safe
    inflight: int = 0
    # ---- fault-tolerant re-dispatch (DESIGN.md §14) ------------------------
    # prompt length as the user submitted it; set on the first checkpoint
    # (``checkpoint_redispatch``) when committed output is folded into the
    # prompt as a forced replay prefix.  None == never re-dispatched.
    orig_prompt_len: Optional[int] = None
    # pool-level retry count (timeout / failure re-dispatch) and the shed
    # reason when admission control rejects the request outright
    retries: int = 0
    reject_reason: Optional[str] = None
    # replica the request last ran on (pool bookkeeping / session affinity)
    replica: Optional[int] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def context_len(self) -> int:
        return self.prefill_done + len(self.output)

    @property
    def prefill_remaining(self) -> int:
        return self.prompt_len - self.prefill_done

    @property
    def prefill_unlaunched(self) -> int:
        """Prompt tokens not yet launched — what the *next* plan can chunk
        (``prefill_remaining`` counts committed progress and lags this by
        the in-flight iterations when the engine pipelines, §10)."""
        return self.prompt_len - self.prefill_launched

    @property
    def total_tokens(self) -> int:
        return self.prompt_len + len(self.output)

    def predicted_final_len(self, avg_decode: float) -> int:
        """Peak-memory estimator input (§4.4): assume avg decode length."""
        want = max(int(avg_decode), 1)
        return self.prompt_len + min(self.max_new_tokens, max(want, 1))

    @property
    def generated(self) -> list[int]:
        """All tokens this request generated, including any that were
        committed before a failure and replayed as a forced prefix
        (DESIGN.md §14).  For a never-re-dispatched request this is exactly
        ``output``; the chaos-exactness tests compare this stream."""
        if self.orig_prompt_len is None:
            return list(self.output)
        return list(self.prompt[self.orig_prompt_len:]) + list(self.output)

    def checkpoint_redispatch(self) -> int:
        """Reset to a re-dispatchable checkpoint: fold every *committed*
        output token into the prompt as a forced replay prefix and clear all
        engine-local state (slot, launch counters, in-flight samples — those
        died with the replica).  Replaying the committed tokens as prompt
        makes the resumed generation token-exact: under greedy decoding the
        next sample depends only on the prefix, and the stochastic sampler's
        keys fold (rid, position) only (§13), both of which the replay
        preserves.  Returns the number of tokens folded (the re-prefill cost
        the pool accounts as ``redispatched_tokens``).

        A request whose committed output already contains EOS — or whose
        token budget is exhausted — has nothing left to generate: it is
        finished here (output stripped to EOS exactly like the engine's
        finalize path) and the caller must not re-dispatch it."""
        if self.orig_prompt_len is None:
            self.orig_prompt_len = len(self.prompt)
        out = list(self.output)
        if self.eos_id is not None and self.eos_id in out:
            out = out[: out.index(self.eos_id) + 1]
            self.prompt = list(self.prompt) + out
            self.output = []
            self.state = State.FINISHED
            self.pending_eos = False
            self.inflight = 0
            return 0
        folded = len(out)
        self.prompt = list(self.prompt) + out
        self.max_new_tokens -= folded
        self.output = []
        self.prefill_done = 0
        self.prefill_launched = 0
        self.inflight = 0
        self.slot = -1
        self.pending_eos = False
        if self.max_new_tokens <= 0:
            self.state = State.FINISHED
            return 0
        self.state = State.WAITING
        return folded
