"""Engine-side request state for continuous batching."""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field
from typing import Any

from smg_tpu.protocols.sampling import SamplingParams


class QueueFullError(RuntimeError):
    """Admission backpressure: the bounded waiting queue rejected a submit.

    Retryable by design — the RPC layer maps it to RESOURCE_EXHAUSTED and the
    gateway router to retry-another-worker / HTTP 429 (never a breaker
    failure: a full queue is load, not fault)."""


class RequestStatus(enum.Enum):
    WAITING = "waiting"
    # admitted to a slot, prompt KV partially computed (resumable chunked
    # prefill: ``prefill_pos`` is the cursor); not yet a decode lane
    PREFILLING = "prefilling"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED = "finished"
    ABORTED = "aborted"


@dataclass
class FinishInfo:
    reason: str  # "stop" | "length" | "abort" | "error" | "timeout"
    matched_stop: str | int | None = None
    message: str | None = None


@dataclass
class EngineRequest:
    rid: str
    prompt_ids: list[int]
    sampling: SamplingParams
    arrival_time: float = field(default_factory=time.monotonic)
    priority: int = 0
    # absolute time.monotonic() deadline (None = no deadline).  The scheduler
    # expires WAITING requests before admission and aborts RUNNING lanes past
    # it, both with finish reason "timeout" (engine failure-isolation layer).
    deadline: float | None = None

    # runtime
    status: RequestStatus = RequestStatus.WAITING
    output_ids: list[int] = field(default_factory=list)
    logprobs: list[float] = field(default_factory=list)
    seq_len: int = 0  # tokens whose KV is currently cached
    # resumable-prefill cursor: prompt tokens whose KV is computed so far
    # (== seq_len while PREFILLING; chunked prefill advances it at most one
    # per-step budget's worth per scheduler step)
    prefill_pos: int = 0
    cached_tokens: int = 0  # tokens served from the radix prefix cache
    owned_pages: list[int] = field(default_factory=list)  # pages this request owns
    shared_pages: list[int] = field(default_factory=list)  # radix-cache pages (pinned)
    radix_node: Any = None  # locked RadixNode for the shared prefix
    slot: int | None = None  # decode slot index
    # recurrent models: the state slot this sequence holds beside its pages
    # (kv_cache.StateSlotPool), from admission to release or preemption
    state_slot: int | None = None
    finish: FinishInfo | None = None
    # filled by the engine layer (detokenize/stop strings)
    detok: Any = None
    stop_checker: Any = None
    # constrained decoding (grammar vocab mask) — engine-installed TokenFilter
    token_filter: Any = None
    # runner-side penalty slot state is current for this request's slot
    penalty_synced: bool = False
    # LoRA adapter bank slot applied to this request (0 = base model)
    lora_idx: int = 0
    # Multimodal embeddings spliced into the prompt at placeholder positions:
    # (embeds [M, E] float32, positions [M] int32).  Reference: the EPD
    # encode leg ships vision-tower output to prefill (``stages/encode.rs``).
    mm_embeds: tuple | None = None
    # radix-key salt cache: (n_tokens_covered, per-page salts) —
    # scheduler-computed (see Scheduler._mm_extra_keys)
    mm_extra_keys: "tuple | None" = None
    # M-RoPE (Qwen2-VL): per-token [3, prompt_len] position ids + the decode
    # position delta (engine/mrope.py); None = standard rope
    mrope_pos: Any = None
    mrope_delta: int = 0
    # speculative decoding: consecutive zero-acceptance verifies (back-off)
    # + the request's incremental n-gram index (engine/speculative.py)
    spec_cold: int = 0
    spec_index: Any = None
    # draft-model proposer: committed tokens mirrored into the draft KV
    # cache so far (engine/draft.py; reset on preemption)
    draft_len: int = 0
    # scheduler admission serial: unique per request lifetime, used to key
    # decode-state reuse in the overlap pipeline (rids are client-supplied
    # and reusable; object ids recycle after GC)
    sched_serial: int = -1
    # gateway OTel trace id (32 hex chars) propagated over the worker hop;
    # recorded into the flight-recorder timeline so a postmortem dump links
    # back to the request's distributed trace.  None = no trace context.
    trace_id: str | None = None
    # time.monotonic() when Engine.submit started waiting for the engine
    # lock; the flight recorder's ``queued_t`` minus this is the lock wait
    submit_t: float | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt_ids)

    @property
    def total_len(self) -> int:
        return len(self.prompt_ids) + len(self.output_ids)

    @property
    def all_token_ids(self) -> list[int]:
        return self.prompt_ids + self.output_ids

    @property
    def is_finished(self) -> bool:
        return self.status in (RequestStatus.FINISHED, RequestStatus.ABORTED)


@dataclass
class StepOutput:
    """One request's increment from a scheduler step."""

    request: EngineRequest
    new_token_ids: list[int]
    finished: bool
    finish: FinishInfo | None = None
    # per-token logprobs captured at ACCEPT time — slicing request.logprobs
    # later mis-attributes them once a step carries both a prefill and a
    # decode increment for the same request
    logprobs: list[float] = field(default_factory=list)
