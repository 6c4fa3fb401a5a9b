"""Continuous-batching scheduler: token-budget prefill/decode interleaving,
radix prefix reuse, page accounting with evict-then-preempt back-pressure.

This is the in-tree replacement for the scheduler the reference delegates to
SGLang behind ZMQ (``grpc_servicer/.../request_manager.py:48-65``, SURVEY.md
§3.3) — redesigned for XLA: every device step is a fixed-shape bucketed call
into ``ModelRunner``; all bookkeeping (pages, slots, stops) lives host-side.

Step shape: one prefill phase, then one decode step for every running lane
— EVERY step.  Under the default ``prefill_mix_policy="stall-free"``,
``max_prefill_tokens`` is a true PER-STEP budget (Sarathi-Serve): the phase
resumes in-progress (``PREFILLING``) prefills from their cursors and admits
waiting prompts into the leftover, so a long prompt advances one chunk per
step while decode inter-token latency stays flat.  Non-final chunks write
KV without sampling (no key fold); the final chunk samples the first token
and promotes the request to a decode lane.  ``"throughput"`` restores the
legacy drain-the-queue admission (all chunks in one step).

Overlapped pipeline (``SchedulerConfig.overlap_schedule``, default on): the
decode launch of step N is dispatched BEFORE step N-1's outputs are
consumed, exploiting JAX async dispatch — ``decode_multi_async`` returns
unmaterialized arrays, and the host runs detokenization / stop scanning /
admission bookkeeping while the device computes the next step (SGLang's
overlap scheduler / vLLM async scheduling, TPU-shaped).  An
``InFlightFrame`` records the launch; a speculative lookahead launch chains
the frame's own device-resident last-token column as the next input.  Any
divergence from the schedule the synchronous path would have run (finish,
stop-string rollback, abort) discards the frame and rewinds the
sampling-key counter, which keeps token streams byte-identical to
``overlap_schedule off``.  The prefill phase runs every step BEFORE launch
decisions with a fixed key-fold ordering rule — prefill folds before the
step's decode fold — so the lookahead SURVIVES admissions that stay
fold-free (resumable non-final chunks, requests parked ``PREFILLING``,
waiting-over-budget, back-pressure) and is only suppressed for the one
step in which a prefill actually samples.  Grammar-masked batches force a
sync boundary (their next device call depends on last step's host
results).  Speculative decoding runs its own pipelined variant
(``_step_spec``): eligible lanes draft host-side (n-gram index or draft
model) and verify as ONE batched fused device block
(``runner.decode_spec_async``) whose frame stays in flight across steps —
the host-side drafting, detokenize, and stream callbacks overlap the
device's verify pass exactly as the lookahead overlaps decode.
``DecodeState`` keeps steady-state decode inputs
(sampling params, penalty scalars, LoRA indices, page tables)
device-resident, refreshed only on batch-composition or page-table change.
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from smg_tpu.engine.config import EngineConfig
from smg_tpu.engine.flight_recorder import (
    HORIZON_REASONS,
    PREFILL_SYNC_REASONS,
    SLOW_STEP_S,
)
from smg_tpu.engine.kv_cache import PagePool, StateSlotPool
from smg_tpu.engine.radix_cache import RadixCache
from smg_tpu.engine.request import (
    EngineRequest,
    FinishInfo,
    QueueFullError,
    RequestStatus,
    StepOutput,
)
from smg_tpu.engine.runner import DecodeState, ModelRunner
from smg_tpu.engine.spans import StepAccount, spanned
from smg_tpu.faults import FAULTS
from smg_tpu.utils import get_logger

logger = get_logger("engine.scheduler")


@dataclass
class InFlightFrame:
    """One dispatched decode horizon whose results are not yet consumed.

    ``lanes`` pins each batch row to (slot, request, expected_seq_len): the
    request's ``seq_len`` must still equal the recorded value when the frame
    is consumed, else the lane went stale while in flight (stop-string
    rollback, abort, external finish) and its tokens are dropped — their KV
    landed past the request's final ``seq_len``, which never enters the
    radix cache (the same overshoot convention the decode horizon uses).

    ``toks``/``lps`` are unmaterialized ``jax.Array``s: JAX async dispatch
    returns them before the device finishes, and ``np.asarray`` at consume
    time is the deferred fetch.  ``rng_mark`` is set on every frame: the
    megastep consumes ``folds`` (= horizon) sampling-key counter values at
    launch (one in-loop fold per column), so a discarded frame rewinds all
    of them and a horizon trimmed at a finish rewinds the unused tail."""

    lanes: list  # [(slot, EngineRequest, expected_seq_len)]
    toks: "object"  # jax.Array [B, max_steps] (columns >= steps_run unset)
    lps: "object"  # jax.Array [B, max_steps]
    horizon: int  # requested K this launch (<= compiled max_steps)
    B: int  # padded batch bucket
    B_real: int
    mp_b: int
    positions: "object" = None  # np [B] launch positions (lookahead chaining)
    lane_sig: tuple = ()  # DecodeState signature the launch was built under
    use_pen: bool = False
    use_lora: bool = False
    use_mrope: bool = False
    rng_mark: int | None = None
    lookahead: bool = False
    folds: int = 1  # sampling-key counter values consumed by the launch
    steps_run: "object" = None  # jax.Array scalar: columns the device loop ran
    # speculative verify frames (``_launch_spec_frame``): ``toks`` holds the
    # emitted rows [B, W] (accepted drafts + bonus/correction), ``n_emit``
    # the per-lane emit counts, and the host-side draft metadata feeds the
    # acceptance telemetry at consume.  ``horizon`` is the compiled block
    # width W and ``folds`` is 1 (one launch fold; ``_discard_frame``'s
    # rewind machinery applies unchanged).
    spec: bool = False
    n_emit: "object" = None  # jax.Array [B] (spec frames only)
    draft_ns: "list | None" = None  # per-lane drafted-token counts
    tiers: "list | None" = None  # per-lane drafting tier ("ngram"/"draft")
    # recurrent models: ``clean`` is the device's word that this frame met no
    # finish (a frame chained on it then ran; on one that met a finish it ran
    # no column).  ``ran`` is what the host knows of THIS frame: False once
    # the frame it was chained on is known to have met a finish, so that
    # discarding it has nothing to take out of the state again.
    clean: "object" = None  # jax.Array scalar bool
    ran: bool = True
    # routed experts: the frame's counts (``LatentModelRunner.frame_counts``)
    routed: "object" = None  # jax.Array int32, one count a name of ``module.ROUTED_COUNTS``
    # a self-drafting runner's verify frame (``SelfDraftingRunner.frame_tail``):
    # ``(emitted [B, N], last [B], positions [B], [drafted, accepted])``, device
    # arrays; ``toks``/``lps`` are then ``[B, N, 2]`` and a lane's tokens of
    # column j are the first ``emitted[b, j]`` of them
    tail: "object" = None
    # the launch's serial in the step account: fetching this frame proves
    # every launch up to it done (``spans.StepAccount``)
    serial: int = 0


def _launch_attrs(frame: "InFlightFrame") -> dict:
    """Attributes of an ``smg.step.launch`` span."""
    return {"K": frame.horizon, "lanes": frame.B_real,
            "lookahead": int(frame.lookahead)}


class Scheduler:
    def __init__(
        self,
        runner: ModelRunner,
        config: EngineConfig,
        event_sink: Callable | None = None,
        metrics: "object | None" = None,
    ):
        self.runner = runner
        self.config = config
        # EngineMetrics (engine/metrics.py) — optional so bare schedulers in
        # tests stay dependency-free; every hook is None-guarded
        self.metrics = metrics
        self.sched = config.scheduler
        self.ps = runner.spec.page_size
        self.mp = runner.max_pages_per_seq
        if runner.widest_table_only:
            # the decode kernel reads each lane's own pages, so the runner has
            # one decode program a batch bucket, at the widest table
            self._mp_bucket = lambda pages_needed: self.mp
        self.pool = PagePool(runner.spec.num_pages)
        self.radix = (
            RadixCache(self.ps, event_sink) if self.sched.enable_prefix_cache else None
        )
        self.waiting: deque[EngineRequest] = deque()
        # draft-model speculative proposer (engine/draft.py); the engine
        # installs one when config.draft_model is set
        self.draft = None
        self.slots: list[EngineRequest | None] = [None] * self.sched.max_batch_size
        # a model with recurrent layers: every resident sequence holds a
        # state slot beside its pages, and a prefix hit without the state at
        # that prefix is no hit (no snapshot is kept yet, so none is)
        self.state_pool = (
            StateSlotPool(runner.state_spec.num_slots)
            if getattr(runner, "state_spec", None) is not None else None
        )
        # a model with window layers: the slot holds rings of the last tokens,
        # which a decode frame overwrites only where no later query looks, so
        # a frame thrown away or trimmed costs nothing; recurrent state moves
        # on with every column and cannot be taken back
        self._frames_advance_state = (
            self.state_pool is not None and runner.frames_advance_state)
        self._window_slots = self.state_pool is not None and not runner.frames_advance_state
        # the model's own next-token module drafts inside the decode frame:
        # a column emits one or two tokens a lane, the host drafts nothing and
        # the plain pipeline (lookahead and all) carries the verify frames
        self._self_draft = getattr(runner, "self_drafting", False)
        self._tpc = getattr(runner, "tokens_per_column", 1)  # tokens a lane a column, at most
        self.mtp_counts = {"drafted": 0, "accepted": 0, "columns": 0, "tokens": 0}
        self.num_state_prefix_hits_declined = 0
        self.num_state_recomputed_tokens = 0
        self._step_state_lanes = 0
        self.page_tables = np.zeros((self.sched.max_batch_size, self.mp), np.int32)
        self.requests: dict[str, EngineRequest] = {}
        # counters for GetLoads / metrics
        self.num_prefill_tokens = 0
        self.num_decode_tokens = 0
        # speculative decoding acceptance telemetry (engine/speculative.py)
        self.num_spec_drafted = 0
        self.num_spec_accepted = 0
        self.num_preemptions = 0
        # radix hit-rate accounting, counted once per admission (NOT per
        # match_prefix probe — back-pressured requests re-probe every step).
        # cached vs computed prompt tokens is the single source of truth the
        # gateway's smg_cached_prompt_tokens_total and the cache-aware
        # policy both key off.
        self.num_cached_prompt_tokens = 0
        self.num_computed_prompt_tokens = 0
        self.num_radix_hit_pages = 0
        self.num_radix_miss_pages = 0
        # overlapped decode pipeline (engine/engine.py drives step_overlap):
        # the frame whose device work is in flight, the persistent
        # device-resident decode inputs, and lookahead outcome counters
        self.inflight: InFlightFrame | None = None
        self._dstate = DecodeState()
        self._pages_dirty = True  # page-table rows changed since last upload
        self._serial = 0  # admission serial for decode-state signatures
        self.num_lookahead_kept = 0
        self.num_lookahead_discarded = 0
        # a step that prefills: ``_chaining`` is set while the overlap
        # pipeline's prefill phase may leave a grouped prefill's first tokens
        # unfetched, ``_pending_group`` is that group, (members, the
        # runner's unfetched parts), until the step's decode launch is out
        self._chaining = False
        self._pending_group: tuple | None = None
        # the engine's: hands its lock to the submissions that wait for it
        # (``Engine._let_submitters_in``); None where nothing drives the
        # scheduler through an engine
        self.let_submitters_in = None
        self.num_prefill_chained = 0
        self.num_prefill_sync = dict.fromkeys(PREFILL_SYNC_REASONS, 0)
        # megastep decode (device-fused K-step horizon) accounting + the
        # adaptive horizon controller's observed-finish-rate state:
        # wasted tokens = columns computed on device but never accepted
        # (trimmed horizons — normally 0 thanks to the early exit — plus
        # discarded lookahead frames, counted at their full width as an
        # upper bound since their results are never fetched)
        self.num_wasted_decode_tokens = 0
        self.num_megastep_early_exits = 0
        # EMA of decode columns between finishes (the controller sizes K so
        # most horizons complete without a trim); 0 = no observation yet
        self._finish_gap_ema = 0.0
        self._cols_since_finish = 0
        # step-scoped megastep telemetry for the flight-recorder ring
        self._step_horizon = 0
        # why _pick_horizon chose its last K; the launch that follows copies
        # it into the step record and counts it (HORIZON_REASONS)
        self._picked_reason = ""
        self._step_horizon_reason = ""
        self.num_decode_launches = dict.fromkeys(HORIZON_REASONS, 0)
        self._step_columns_run = 0
        # routed experts, over the decode frames consumed: the module's
        # ``ROUTED_COUNTS`` by name (token-expert pairs, those on held experts,
        # held experts hit summed over layers and columns, the most rows one
        # layer and column computed; for a model with identity experts the
        # pairs on those)
        self.moe_counts = (dict.fromkeys(runner.module.ROUTED_COUNTS, 0)
                           if hasattr(runner, "moe_info") else None)
        self._step_moe = None
        # step-scoped speculative-decoding telemetry (flight-recorder ring
        # spec fields) + the acceptance-length EMA the adaptive depth
        # controller reads (_pick_spec_depth)
        self._step_spec_drafted = 0
        self._step_spec_accepted = 0
        self._spec_accept_ema = 0.0
        # failure isolation (poison-step quarantine / deadlines / drain)
        self.num_quarantined = 0
        self.num_step_failures = 0
        self.consec_step_failures = 0  # reset by any clean step
        self._step_had_failure = False  # set within a step by _count_step_failure
        self.num_queue_rejections = 0
        self.num_deadline_waiting = 0
        self.num_deadline_running = 0
        # drain mode (engine.stop(drain=True)): admission stops — in-progress
        # PREFILLING continuations and RUNNING lanes still finish
        self.draining = False
        # flight recorder (engine/flight_recorder.py): step-level black box —
        # per-step ring + per-request timelines, auto-dumped on quarantine /
        # health flip (plus watchdog/drain at the engine layer).  Host-side
        # metadata only; every hook below is None-guarded so the recorder can
        # be disabled for A/B overhead benches.
        self.flight = None
        if getattr(config, "flight_recorder", True):
            from smg_tpu.engine.flight_recorder import FlightRecorder

            self.flight = FlightRecorder(
                ring_size=getattr(config, "flight_ring_size", 256),
                timeline_keep=getattr(config, "flight_timeline_keep", 64),
                dump_dir=getattr(config, "flight_dump_dir", None),
                dump_min_interval_secs=getattr(
                    config, "flight_dump_min_interval_secs", 5.0
                ),
            )
            self.flight.metrics = metrics
        # the step account (engine/spans.py): where the step thread's
        # seconds go, by the spans that mark them, and when the chip had
        # nothing queued.  The runner's prefill spans feed the same one
        self.account = runner.account = StepAccount()
        # mesh device count riding every flight-ring record (1 = single
        # -device): postmortems from a mixed fleet self-describe their
        # topology; runner.mesh_devices is the single source
        self._mesh_devices = runner.mesh_devices
        # step-scoped recorder state (reset at the top of every step)
        self._step_fault_phases: list[str] = []
        self._step_admissions = 0
        self._step_outcome: str | None = None
        # dump reasons raised mid-step (quarantine, health flip): fired AFTER
        # the step's own ring record lands, so the dump contains the failing
        # step rather than ending one short of it
        self._pending_dumps: list[str] = []

    # ---- public API ----

    def add_request(self, req: EngineRequest) -> None:
        if req.rid in self.requests:
            raise ValueError(f"duplicate request id {req.rid}")
        if self.draining:
            # a submit racing stop(drain=True) lands after the drain sweep:
            # accepting it would queue a request no admission loop will ever
            # touch (silent client hang).  QueueFullError is the right shape
            # — retryable on another worker, 429 at the front door.
            raise QueueFullError("engine draining; retry on another worker")
        self._check_queue_capacity(req)
        self._serial += 1
        req.sched_serial = self._serial
        self.requests[req.rid] = req
        self.waiting.append(req)
        if self.flight is not None:
            self.flight.on_queued(
                req.rid, prompt_tokens=len(req.prompt_ids),
                trace_id=req.trace_id, meta=self._flight_meta(req),
                deadline_t=req.deadline, submit_t=req.submit_t,
            )

    def _flight_meta(self, req: EngineRequest) -> dict:
        """Sampling/route metadata recorded into the request's timeline (the
        postmortem needs to show HOW a request was running, not just when)."""
        sp = req.sampling
        meta = {
            "temperature": sp.temperature, "top_p": sp.top_p,
            "top_k": sp.top_k, "max_new_tokens": sp.max_new_tokens,
            "priority": req.priority,
        }
        if sp.lora_adapter:
            meta["lora"] = sp.lora_adapter
        if req.token_filter is not None:
            meta["constrained"] = True
        return meta

    def _check_queue_capacity(self, req: EngineRequest) -> None:
        """Bounded-queue backpressure at submit time.  Only NEW submissions
        are bounded — preemption victims re-enter ``waiting`` directly (they
        already hold an admission, rejecting them would lose work)."""
        sched = self.sched
        full = bool(
            sched.max_queued_requests
            and len(self.waiting) >= sched.max_queued_requests
        )
        if not full and sched.max_queued_tokens:
            # O(len(waiting)) under the engine lock, but self-limiting: the
            # cap itself bounds the queue this sum walks (every waiting
            # request holds >= 1 token), so the walk never exceeds
            # max_queued_tokens entries
            queued = sum(len(r.all_token_ids) for r in self.waiting)
            full = queued + len(req.prompt_ids) > sched.max_queued_tokens
        if full:
            self.num_queue_rejections += 1
            if self.metrics is not None:
                self.metrics.queue_rejections.inc()
            raise QueueFullError(
                f"engine waiting queue full ({len(self.waiting)} queued); "
                "retry on another worker or later"
            )

    def abort_request(self, rid: str) -> bool:
        req = self.requests.get(rid)
        if req is None or req.is_finished:
            return False
        if req.status == RequestStatus.WAITING or req.status == RequestStatus.PREEMPTED:
            try:
                self.waiting.remove(req)
            except ValueError:
                pass
            req.status = RequestStatus.ABORTED
            req.finish = FinishInfo(reason="abort")
            self._count_finish(req, "abort")
            self.requests.pop(rid, None)
            return True
        self._release(req, FinishInfo(reason="abort"), aborted=True)
        return True

    def has_work(self) -> bool:
        return (
            bool(self.waiting)
            or any(s is not None for s in self.slots)
            or self.inflight is not None
        )

    def prefill_inflight_tokens(self) -> int:
        """Un-prefilled prompt tokens of admitted, in-progress (resumable)
        prefills — the slot-holding half of the prefill backlog."""
        return sum(
            len(r.all_token_ids) - r.prefill_pos
            for r in self.slots
            if r is not None and r.status is RequestStatus.PREFILLING
        )

    def loads(self) -> dict:
        running = sum(1 for s in self.slots if s is not None)
        # token-load estimate for dp-aware routing: un-prefilled prompt tokens
        # plus the remaining generation budget of every admitted request
        queued = sum(
            len(r.prompt_ids) + r.sampling.max_new_tokens for r in self.waiting
        )
        prefill_inflight = self.prefill_inflight_tokens()
        num_prefilling = 0
        for s in self.slots:
            if s is not None:
                queued += max(s.sampling.max_new_tokens - len(s.output_ids), 0)
                if s.status is RequestStatus.PREFILLING:
                    # un-prefilled prompt tokens are still queued work too
                    queued += len(s.all_token_ids) - s.prefill_pos
                    num_prefilling += 1
        # prefill PRESSURE for load-aware routing: work the per-step budget
        # still has to chew through before new admissions decode (waiting
        # prompts re-counted here by their full un-cached prompt length)
        waiting_prompt_tokens = sum(len(r.all_token_ids) for r in self.waiting)
        total_prompt = self.num_cached_prompt_tokens + self.num_computed_prompt_tokens
        out = {
            "num_waiting": len(self.waiting),
            "num_running": running,
            # chunked-prefill backlog (per-step budget scheduling): slots
            # mid-prefill, their remaining tokens, and the whole backlog the
            # router should see as prefill pressure (not just slot occupancy)
            "num_prefilling": num_prefilling,
            "prefill_inflight_tokens": prefill_inflight,
            "prefill_backlog_tokens": prefill_inflight + waiting_prompt_tokens,
            "spec_drafted": self.num_spec_drafted,
            "spec_accepted": self.num_spec_accepted,
            "free_pages": self.pool.free_count,
            "cached_pages": self.radix.num_cached_pages if self.radix else 0,
            "total_pages": self.runner.spec.num_pages,
            "queued_tokens": queued,
            # radix hit-rate accounting (admission-time, see __init__ note):
            # the gateway's cache-aware policy and smg_cached_prompt_tokens
            # read the same numbers
            "cached_prompt_tokens": self.num_cached_prompt_tokens,
            "computed_prompt_tokens": self.num_computed_prompt_tokens,
            "cache_hit_rate": (
                self.num_cached_prompt_tokens / total_prompt if total_prompt else 0.0
            ),
            "preemptions": self.num_preemptions,
            "radix_hit_pages": self.num_radix_hit_pages,
            "radix_miss_pages": self.num_radix_miss_pages,
            "radix_evicted_pages": self.radix.evicted_pages if self.radix else 0,
            # overlap pipeline: lookahead launches that stood vs. were
            # discarded after a schedule change (stop/abort/rollback)
            "lookahead_kept": self.num_lookahead_kept,
            "lookahead_discarded": self.num_lookahead_discarded,
            # sampling prefills of the overlap pipeline: those whose step
            # launched its decode frame behind them, before their first
            # tokens were fetched, and those that fetched first, by why
            # (PREFILL_SYNC_REASONS)
            "prefill_chained_launches": self.num_prefill_chained,
            "prefill_sync_launches": dict(self.num_prefill_sync),
            # megastep decode: device-computed-but-never-emitted columns and
            # device-side early exits (a finish ended a horizon early)
            "wasted_decode_tokens": self.num_wasted_decode_tokens,
            "megastep_early_exits": self.num_megastep_early_exits,
            # decode launches by why their K was chosen (HORIZON_REASONS)
            "decode_launches": dict(self.num_decode_launches),
            # failure isolation: quarantine/deadline/backpressure counters
            # the gateway's health + routing decisions key off
            "quarantined_requests": self.num_quarantined,
            "step_failures": self.num_step_failures,
            "consecutive_step_failures": self.consec_step_failures,
            "queue_rejections": self.num_queue_rejections,
            "deadline_expirations_waiting": self.num_deadline_waiting,
            "deadline_expirations_running": self.num_deadline_running,
            "draining": self.draining,
            # sharded runner mode: mesh topology (devices / per-axis shape /
            # platform / donation verdict), so operators can see a TP
            # worker's sharding from /scheduler without reaching into the runner
            "mesh": self.runner.mesh_info(),
            # which attention implementation the dispatch rule resolved to,
            # and how many launches each one has had
            "attention": self.runner.attention_info(),
            # grouped prefill launches and the host arrays their dispatches
            # uploaded: one a launch (the packed inputs) under plain sampling
            "prefill_uploads": dict(self.runner.prefill_uploads),
            # what the grouped prefills computed (``padded_tokens``: rows x
            # tokens of every launch) for the ``real_tokens`` of their rows,
            # the launches by padded shape, and the groups sent up in parts
            "prefill_padding": {**self.runner.prefill_padding,
                                "launches": dict(self.runner.prefill_padding["launches"])},
            # the step account's sums: seconds by phase (with ``gap`` and
            # the whole ``step``) and the seconds the chip had nothing queued
            "step_phases": self.account.sums(),
            # the last steps that took over a second with the gap before
            # them, whole: where a stalled window is read
            "slow_steps": (self.flight.slow_steps() if self.flight is not None
                           else {"threshold_s": SLOW_STEP_S, "count": 0, "steps": []}),
        }
        if self._window_slots:
            spec, info = self.runner.spec, self.runner.window_info()
            out.update({
                # the two kinds of cache: pages of the full-attention layers,
                # and the window layers' slots, whose size is the window's
                "kv_groups": {
                    "global": {"layers": spec.num_layers,
                               "bytes_per_token": spec.bytes_per_page // spec.page_size,
                               "pages_total": self.pool.num_pages - 1,
                               "pages_in_use": self.pool.num_pages - 1 - self.pool.free_count},
                    "window": {**info, "slots_in_use": self.state_pool.in_use},
                },
                # radix matches turned down for want of the window entries at
                # their end, and tokens prefilled again because a sequence
                # lost its slot to a preemption
                "window_prefix_hits_declined": self.num_state_prefix_hits_declined,
                "window_recomputed_tokens": self.num_state_recomputed_tokens,
            })
            if self._self_draft:
                # drafts the verify columns tried and took, the lane-columns
                # run and the tokens they gave
                out["mtp"] = dict(self.mtp_counts)
        elif self.state_pool is not None:
            info = dict(self.runner.state_info())
            out.update({
                "state_slots_total": info.pop("slots_total"),
                "state_slots_in_use": self.state_pool.in_use,
                "state_slot_bytes": info.pop("slot_bytes"),
                # radix matches turned down for want of a state snapshot, and
                # tokens prefilled again because a sequence lost its state
                # (preemption, or a discarded frame that had advanced it)
                "state_prefix_hits_declined": self.num_state_prefix_hits_declined,
                "state_recomputed_tokens": self.num_state_recomputed_tokens,
                # which decode step the recurrent layers run, under the
                # module's name for it (``linattn_decode``, ``ssm_decode``)
                **info,
            })
        if self.moe_counts is not None:
            out["moe"] = {**self.runner.moe_info(), **self.moe_counts}
        if hasattr(self.runner, "latent_info"):
            out["latent_cache"] = self.runner.latent_info()
        dsa = getattr(self.runner, "dsa_info", lambda: None)()
        if dsa is not None:
            # a learned selector over the cache: rows that attended and those
            # behind more than ``index_topk`` tokens, cached tokens scored;
            # prefill launches by the host's count, decode frames by the device's
            decode = {k[4:]: v for k, v in self.moe_counts.items() if k.startswith("dsa_")}
            out["dsa"] = {**dsa, **{f"decode_{k}": v for k, v in decode.items()}}
        if self.metrics is not None:
            # rolling-window live signal (p50/p95 step time, tokens/s) for
            # the /scheduler endpoint, dp-aware routing, and benchmarks
            out["stats"] = self.metrics.window.snapshot()
        return out

    def audit(self) -> dict:
        """Zero-leak resource audit over the page pool, radix cache, slots,
        and the overlap frame (the ``loads()["audit"]`` payload).

        Invariants it makes assertable:

        - ``leaked_pages == 0`` ALWAYS: every allocatable page is free,
          radix-cached, or owned by a slot-resident request (waiting and
          preempted requests hold no pages; PD export/import hold them only
          within a single engine-locked call, which this — also
          engine-locked — can never observe mid-flight);
        - at quiescence (no slots, no queue, no in-flight frame) the radix
          lock refcounts are zero and no output callbacks linger (checked at
          the engine layer) — a nonzero here is a leaked ``lock``/callback
          from some release path.

        O(slots + tree nodes): ops-plane cost, never paid by the step loop.
        """
        live = [r for r in self.slots if r is not None]
        held_pages = sum(len(r.owned_pages) for r in live)
        pinned_shared = sum(len(r.shared_pages) for r in live)
        cached = self.radix.num_cached_pages if self.radix else 0
        allocatable = self.pool.num_pages - 1  # page 0 = reserved garbage
        free = self.pool.free_count
        locks = (
            self.radix.lock_stats() if self.radix is not None
            else {"locked_nodes": 0, "lock_refcounts": 0}
        )
        quiescent = (
            not live and not self.waiting and self.inflight is None
        )
        leaked = allocatable - free - cached - held_pages
        # state slots: every slot in use belongs to a resident sequence
        leaked_slots = 0
        if self.state_pool is not None:
            held = {r.state_slot for r in live if r.state_slot is not None}
            leaked_slots = self.state_pool.in_use - len(held)
        return {
            **({("leaked_window_slots" if self._window_slots
                 else "leaked_state_slots"): leaked_slots}
               if self.state_pool is not None else {}),
            "live_slots": len(live),
            "waiting_requests": len(self.waiting),
            "inflight_frames": 0 if self.inflight is None else 1,
            "held_pages": held_pages,
            "pinned_shared_pages": pinned_shared,
            "free_pages": free,
            "radix_cached_pages": cached,
            "allocatable_pages": allocatable,
            "leaked_pages": leaked,
            "radix_locked_nodes": locks["locked_nodes"],
            "radix_lock_refcounts": locks["lock_refcounts"],
            "quiescent": quiescent,
            # the one-bit verdict the harness asserts: no page unaccounted
            # for now, and no stray pins once nothing is running
            "clean": leaked == 0 and leaked_slots == 0 and (
                not quiescent
                or (locks["locked_nodes"] == 0 and locks["lock_refcounts"] == 0)
            ),
        }

    def flush_cache(self) -> bool:
        """Drop the prefix cache (only when idle, like the reference engines)."""
        if any(s is not None for s in self.slots) or self.waiting:
            return False
        # an idle scheduler can still hold a stale in-flight frame (all its
        # lanes finished since launch); resolve it before swapping buffers
        self.drop_inflight()
        if self.radix:
            self.pool.free(self.radix.clear())
        self.runner.flush_cache_buffers()
        return True

    # ---- the step ----

    def step(self) -> list[StepOutput]:
        """One scheduler iteration with failure isolation: prefill failures
        are quarantined per-request inside the admission phase (see
        ``_admit_*``); anything that still escapes is a decode-phase failure
        handled by blame-and-retry (``_recover_decode_failure``) so one
        poisoned batch never livelocks the engine."""
        outputs: list[StepOutput] = []
        self._step_had_failure = False
        fl = self.flight
        self._step_fault_phases = []
        self._step_admissions = 0
        self._step_outcome = None
        self._step_horizon = 0
        self._step_horizon_reason = ""
        self._step_spec_drafted = 0
        self._step_spec_accepted = 0
        self._step_state_lanes = 0
        self._step_columns_run = 0
        self._step_moe = None
        pf0, dc0 = self.num_prefill_tokens, self.num_decode_tokens
        we0, ee0 = self.num_wasted_decode_tokens, self.num_megastep_early_exits
        self.account.begin_step()
        escaped = True  # exception past recovery -> engine loop (phase=loop)
        try:
            try:
                self._step_inner(outputs)
            except Exception as e:  # noqa: BLE001 — isolation boundary
                self._recover_decode_failure(outputs, e)
            else:
                if not self._step_had_failure:
                    # only a step with NO recorded failure resets the streak —
                    # a step that quarantined a prefill failure completed, but
                    # counting it as clean would make the unhealthy threshold
                    # unreachable for a worker failing every prefill
                    self.consec_step_failures = 0
            escaped = False
        finally:
            phases = self.account.end_step(self.has_work())
            if self.metrics is not None:
                self.metrics.observe_phases(self.account)
            if fl is not None:
                # the ring record lands even for a step whose exception is
                # escaping to the engine loop — a postmortem that omits the
                # failing step is useless
                fl.record_step(
                    step_s=phases.pop("step_s"),
                    prefill_tokens=self.num_prefill_tokens - pf0,
                    decode_tokens=self.num_decode_tokens - dc0,
                    running=sum(1 for s in self.slots if s is not None),
                    waiting=len(self.waiting),
                    max_batch=self.sched.max_batch_size,
                    prefill_inflight_tokens=self.prefill_inflight_tokens(),
                    free_pages=self.pool.free_count,
                    admissions=self._step_admissions,
                    finishes=sum(1 for o in outputs if o.finished),
                    overlap=self._step_outcome,
                    fetch_wait_s=phases.pop("fetch_wait_s"),
                    faults=self._step_fault_phases + (["loop"] if escaped else []),
                    horizon=self._step_horizon,
                    early_exits=self.num_megastep_early_exits - ee0,
                    wasted_decode_tokens=self.num_wasted_decode_tokens - we0,
                    spec_drafted=self._step_spec_drafted,
                    spec_accepted=self._step_spec_accepted,
                    mesh=self._mesh_devices,
                    horizon_reason=self._step_horizon_reason,
                    state_lanes=self._step_state_lanes,
                    columns_run=self._step_columns_run,
                    moe=self._step_moe,
                    phases=phases,
                )
                self.flush_pending_dumps()
        return outputs

    def flush_pending_dumps(self) -> None:
        """Fire dump reasons raised mid-step (quarantine, health flip) now
        that the triggering step's ring record is in place.  Also called by
        the engine loop's last-resort handler for escaped exceptions."""
        if self.flight is None or not self._pending_dumps:
            return
        pending, self._pending_dumps = self._pending_dumps, []
        for reason in pending:
            self.flight.auto_dump(reason)

    def _step_inner(self, outputs: list[StepOutput]) -> None:
        m = self.metrics
        self._expire_deadlines(outputs)
        pf0, dc0 = self.num_prefill_tokens, self.num_decode_tokens
        t0 = time.perf_counter() if m else 0.0
        # speculative mode runs its own pipelined schedule: drafting needs
        # last step's accepted tokens host-side, so the chained LOOKAHEAD is
        # impossible — but the batched verify frame itself stays in flight
        # across steps (launched at the end of step N, consumed at the top
        # of step N+1), overlapping drafting/detokenize/callbacks with the
        # device's verify pass
        spec_mode = self._host_drafts()
        overlap = self.sched.overlap_schedule and not spec_mode
        if overlap:
            outcome = self._step_outcome = self._step_overlap(outputs)
        elif spec_mode and self.sched.overlap_schedule:
            outcome = self._step_outcome = self._step_spec(outputs)
        else:
            self.drop_inflight()  # mode flip mid-run: never strand a frame
            self._admit(outputs)
            self._decode(outputs)
            outcome = None
        if m is not None:
            # the three schedules alike, from the step account: the fetch of
            # the frame in flight is the decode side's, wherever it fell
            spent = self.account.step
            m.observe_step(
                step_s=time.perf_counter() - t0,
                prefill_s=spent["admit"],
                decode_s=spent["consume"] + spent["launch"],
                prefill_tokens=self.num_prefill_tokens - pf0,
                decode_tokens=self.num_decode_tokens - dc0,
                running=sum(1 for s in self.slots if s is not None),
                waiting=len(self.waiting),
                prefill_inflight_tokens=self.prefill_inflight_tokens(),
                max_batch=self.sched.max_batch_size,
                free_pages=self.pool.free_count,
                total_pages=self.runner.spec.num_pages,
                cached_pages=self.radix.num_cached_pages if self.radix else 0,
                cumulative={
                    "preemptions": self.num_preemptions,
                    "radix_hit_pages": self.num_radix_hit_pages,
                    "radix_miss_pages": self.num_radix_miss_pages,
                    "radix_evicted_pages": self.radix.evicted_pages if self.radix else 0,
                    "cached_prompt_tokens": self.num_cached_prompt_tokens,
                    "wasted_decode_tokens": self.num_wasted_decode_tokens,
                    "megastep_early_exits": self.num_megastep_early_exits,
                },
                decode_horizon=self._step_horizon,
            )
            if self._window_slots:
                m.window_slots_total.set(self.state_pool.num_slots - 1)
                m.window_slots_in_use.set(self.state_pool.in_use)
            if outcome is not None:
                m.observe_overlap(outcome=outcome,
                                  fetch_wait_s=spent["consume_fetch"])

    # ---- failure isolation (poison-step quarantine) ----

    def _fail_request(
        self, req: EngineRequest, message: str, outputs: list[StepOutput]
    ) -> None:
        """Quarantine one request: fail it with a terminal ``error`` output,
        releasing its slot, pages, radix locks, and (via ``_release``'s
        error path) keeping its possibly-poisoned KV OUT of the radix cache.
        Surviving lanes are untouched."""
        if req.is_finished:
            return
        logger.error("quarantining request %s: %s", req.rid, message)
        self.num_quarantined += 1
        if self.metrics is not None:
            self.metrics.quarantined_requests.inc()
        if self.flight is not None:
            # the quarantine event lands BEFORE the terminal finish moves the
            # timeline to the finished ring, so the dump identifies the
            # blamed request; the dump itself is deferred until this step's
            # ring record is in place (flush_pending_dumps)
            self.flight.event(req.rid, "quarantine", message=message[:200])
            self._pending_dumps.append("quarantine")
        if req.status in (RequestStatus.WAITING, RequestStatus.PREEMPTED):
            try:
                self.waiting.remove(req)
            except ValueError:
                pass
        finish = FinishInfo(reason="error", message=message)
        if req.slot is not None:
            self._release(req, finish)
        else:
            req.finish = finish
            req.status = RequestStatus.FINISHED
            self._count_finish(req, "error", message)
            self.requests.pop(req.rid, None)
        outputs.append(StepOutput(req, [], True, finish))

    def _count_step_failure(self, phase: str) -> None:
        self.num_step_failures += 1
        self.consec_step_failures += 1
        self._step_had_failure = True
        if self.metrics is not None:
            self.metrics.step_failures.labels(phase=phase).inc()
        self._step_fault_phases.append(phase)
        if (
            self.flight is not None
            and self.consec_step_failures
            == self.config.max_consecutive_step_failures
        ):
            # the streak just crossed the unhealthy threshold: Engine.healthy
            # flips false after this step — capture the run-up
            self._pending_dumps.append("health_flip")

    def _recover_decode_failure(
        self, outputs: list[StepOutput], exc: Exception
    ) -> None:
        """Blame attribution for a decode-phase step failure.

        A decode batch gives no per-row error signal, so blame falls on the
        MOST-RECENTLY-ADMITTED lane (the newest schedule change is the most
        likely poison) — it is quarantined, then the surviving lanes get ONE
        synchronous retry this step.  A second failure condemns the whole
        batch: every remaining lane is quarantined rather than livelocking
        the engine on a poison batch.  Any in-flight frame was stashed back
        on ``self.inflight`` by the raising path, so ``drop_inflight``
        rewinds its sampling-key fold before the retry refolds."""
        self.drop_inflight()
        active = self._decode_active()
        if not active:
            # nothing to blame (failure outside the decode batch — e.g. an
            # admission-bookkeeping bug): surface it WITHOUT counting here;
            # the engine loop's last-resort handler counts it once as
            # phase="loop" (counting both would double-step the streak)
            raise exc
        self._count_step_failure("decode")
        logger.exception("decode step failed; attributing blame")
        newest = max(active, key=lambda t: t[1].sched_serial)[1]
        self._fail_request(newest, f"decode step failed: {exc}", outputs)
        if not self._decode_active():
            return
        try:
            self._decode(outputs)
        except Exception as e2:  # noqa: BLE001 — second strike: condemn batch
            self._count_step_failure("decode")
            self.drop_inflight()
            logger.exception("decode retry failed; quarantining the batch")
            for _slot, req in self._decode_active():
                self._fail_request(req, f"decode step failed after retry: {e2}",
                                   outputs)

    # ---- per-request deadlines ----

    def _expire_deadlines(self, outputs: list[StepOutput]) -> None:
        """Finish requests past their deadline with reason ``timeout``:
        WAITING/PREEMPTED requests expire in queue (cheap sweep — they never
        touched the device), RUNNING/PREFILLING lanes are released exactly
        like an abort (the overlap pipeline sees the lane vanish and
        discards its in-flight frame via the staleness check).  No-op when
        no request carries a deadline, so the fault-free hot path is
        untouched."""
        now = time.monotonic()
        expired_waiting = [
            r for r in self.waiting
            if r.deadline is not None and now > r.deadline
        ]
        for req in expired_waiting:
            self.waiting.remove(req)
            req.status = RequestStatus.FINISHED
            req.finish = FinishInfo(reason="timeout")
            if self.flight is not None:
                self.flight.event(req.rid, "deadline", state="waiting")
            self._count_finish(req, "timeout")
            self.requests.pop(req.rid, None)
            self.num_deadline_waiting += 1
            if self.metrics is not None:
                self.metrics.deadline_expirations.labels(state="waiting").inc()
            outputs.append(StepOutput(req, [], True, req.finish))
        for req in list(self.slots):
            if (
                req is not None
                and req.deadline is not None
                and now > req.deadline
                and not req.is_finished
            ):
                if self.flight is not None:
                    self.flight.event(req.rid, "deadline", state="running")
                self._release(req, FinishInfo(reason="timeout"))
                self.num_deadline_running += 1
                if self.metrics is not None:
                    self.metrics.deadline_expirations.labels(state="running").inc()
                outputs.append(StepOutput(req, [], True, req.finish))

    # ---- graceful drain ----

    def drain_waiting(self, outputs: list[StepOutput]) -> None:
        """Terminate every queued (not yet admitted) request with a terminal
        ``abort`` output — drain mode finishes admitted work and refuses the
        rest, and clients must see a terminal chunk, not a hang."""
        while self.waiting:
            req = self.waiting.popleft()
            req.status = RequestStatus.ABORTED
            req.finish = FinishInfo(reason="abort", message="engine draining")
            self._count_finish(req, "abort", "engine draining")
            self.requests.pop(req.rid, None)
            outputs.append(StepOutput(req, [], True, req.finish))

    # ---- overlapped pipeline (one-step lookahead) ----
    #
    # Invariant: token streams are byte-identical to the synchronous path.
    # The sequence of device calls (prefill/decode, with their folded
    # sampling keys and batch compositions) must therefore be EXACTLY the
    # sequence the sync scheduler would have issued; a lookahead launch that
    # turns out to mismatch it (a finish, a stop-string rollback, an abort)
    # is discarded and the sampling-key counter rewound before relaunching.
    # The prefill phase runs every step ahead of launch decisions, so
    # admissions no longer discard — they either fold (suppressing that
    # step's lookahead launch) or stay fold-free (lookahead survives).
    # A step whose phase folded launches its decode frame after the phase,
    # as the sync step does, and where the phase was a grouped prefill it
    # launches BEHIND it: dispatched before the prefill's first tokens are
    # fetched, so the launch work hides behind the prefill as a lookahead's
    # hides behind a frame (``_launch_behind_prefill``).

    def _step_overlap(self, outputs: list[StepOutput]) -> str:
        """One pipeline iteration; returns its outcome (OVERLAP_OUTCOMES)."""
        frame = self.inflight
        self.inflight = None
        outcome = "sync"
        if frame is not None and self._frame_stale(frame):
            # the schedule changed while the frame was in flight (stop-string
            # rollback, abort, external finish, PD adoption): its tokens
            # never existed in the sync schedule.  Their KV overshoot past
            # each request's final seq_len never enters the radix cache, so
            # dropping them is safe.  This runs BEFORE the prefill phase so
            # the sampling-key rewind happens while the frame's fold is
            # still the newest.
            self._discard_frame(frame)
            # only a LOOKAHEAD discard counts toward the kept/discarded
            # metric ratio — a stale cold frame dropped on stop/abort is not
            # a lookahead outcome (same rule _discard_frame applies to
            # loads()' counters; the two surfaces must agree)
            outcome = "discarded" if frame.lookahead else "sync"
            frame = None
        look = None
        if frame is not None:
            # Key-fold ordering rule: the synchronous step is [prefill
            # phase][decode launch], and the chained lookahead IS this
            # step's decode fold, dispatched early (before the frame's
            # results are fetched — the whole point: the deferred fetch +
            # host bookkeeping below overlap the device computing the
            # lookahead step).  The early launch is therefore only legal
            # when this step's prefill phase is provably FOLD-FREE —
            # ``_prefill_phase_fold_free`` predicts that conservatively.
            # That is how the pipeline SURVIVES admissions: a resumable
            # chunk that eats the whole budget, or an empty queue, keeps
            # the lookahead; any possible sampling prefill downgrades one
            # step to the sync path.
            try:
                if self._prefill_phase_fold_free():
                    look = self._launch_lookahead(frame)
                used = self._consume_frame(frame, outputs)
                if look is not None and self._frames_advance_state:
                    look.ran = bool(frame.clean)
                if look is not None and self._self_draft:
                    # how far a verify frame took each lane is known only now
                    look.lanes = [(s, r, r.seq_len) for s, r, _e in look.lanes]
            except Exception:
                # quarantine path: rewind the NEWEST folds first (the chained
                # lookahead launched off this frame), then stash the frame on
                # ``inflight`` so the step-level handler's drop_inflight
                # rewinds its folds too before the blame/retry refolds
                if look is not None:
                    self._discard_frame(look)
                self.inflight = frame
                raise
            if used < frame.horizon:
                # a finish trimmed the horizon mid-frame: the chained
                # lookahead no longer matches the sync schedule (the lane
                # set changes at the finish), and the frame's UNUSED in-loop
                # key folds must rewind BEFORE the prefill phase can fold —
                # sync's next fold after the finish is mark+used+1
                if look is not None:
                    self._discard_frame(look)
                    look = None
                    outcome = "discarded"
                self._rewind_unused_folds(frame, used)
        # The prefill phase runs AFTER the consume so admission sees every
        # slot and page freed by finishes inside the frame — exactly the
        # capacity the sync schedule's admission would see this step.  (Its
        # folds stay correctly ordered: when a lookahead was launched the
        # phase is fold-free by the predictor's guarantee; otherwise this
        # step's decode fold happens at the tail cold launch, after the
        # phase.)
        # with no lookahead in flight this step's decode launch comes after
        # the phase, and a grouped prefill may leave its first tokens on the
        # device for that launch to chain on (``_prefill_group``)
        self._chaining = look is None
        try:
            disturbed = self._admit(outputs)
        except Exception:
            # whoever handles this reads every lane's last token
            self._settle_pending(outputs)
            raise
        finally:
            self._chaining = False
        pend, self._pending_group = self._pending_group, None
        if look is not None:
            if disturbed or self._frame_stale(look):
                # ``disturbed`` here means the fold-free predictor lied —
                # a key folded after the lookahead's; keeping the launch
                # would desync streams, so discarding is the safe response.
                # Otherwise: consuming finished/trimmed a lane, and the
                # sync schedule would repack the batch (and refold the key).
                self._discard_frame(look)
                outcome = "discarded"
            else:
                self.inflight = look
                outcome = "kept"
        if pend is not None:
            self.inflight = self._launch_behind_prefill(pend, outputs)
            if self.inflight is not None:
                outcome = "chained"
        if self.inflight is None:
            active = self._decode_active()
            if active:
                self.inflight = self._launch_frame(active)
        return outcome

    def _launch_behind_prefill(
        self, pend: tuple, outputs: list[StepOutput]
    ) -> InFlightFrame | None:
        """Dispatch this step's decode frame BEHIND the grouped prefill
        ``pend`` (``_prefill_group`` left its first tokens on the device),
        then fetch and accept those tokens: the host's launch work runs
        while the device prefills instead of after it.  The frame is the one
        the synchronous order would launch (same lanes, positions, tables,
        keys; the promoted lanes' input tokens come from the prefill's own
        output), so the streams do not move.  Returns the frame, or None
        where the caller's cold launch has to follow the fetch after all:

        - the horizon's pages cannot be had from the headroom (a preemption
          could pick a lane whose first token is not accepted yet);
        - the prefill failed on the device (surfacing at the fetch): the
          frame's folds are rewound before the members retry solo, so the
          quarantine path refolds as it always did;
        - a first token ended its request: the frame holds a lane the
          synchronous launch would not, and goes before anything else folds.
        """
        active = self._decode_active()
        horizon, _ = self._pick_horizon(active, owed=pend[0])
        fits = self._pages_needed(active, horizon) <= self._headroom_pages()
        frame = None
        try:
            if fits:
                frame = self._launch_frame(active, first=pend)
        finally:
            # also where the launch raised: the recovery that follows reads
            # every lane's last token
            accepted = self._accept_first_tokens_guarded(pend, outputs, frame)
        self._count_prefill_launch(None if frame is not None else "page_headroom")
        if not accepted:
            return None
        if frame is not None and self._frame_stale(frame):
            self._discard_frame(frame)
            return None
        return frame

    def _mp_bucket(self, pages_needed: int) -> int:
        """Power-of-two page-table width bucket (>= 8, capped at the full
        table) — bounds the jit variant count while trimming decode
        attention to live pages.  Every launch path (sync, lookahead, spec
        verify) must share this so their compiled shapes and the
        overlap/sync page tables agree."""
        mp_b = 8
        while mp_b < pages_needed:
            mp_b *= 2
        return min(mp_b, self.mp)

    def _decode_active(self) -> list:
        """Decode-eligible lanes: resident AND past prefill.  A
        ``PREFILLING`` slot-holder has no sampled token to feed back yet, so
        it is invisible to decode (and to frame lane signatures) until its
        final chunk promotes it.

        Ordered by ADMISSION SERIAL, not physical slot: a lane that
        finishes inside an in-flight frame frees its slot only at consume
        time, so the same admission can land in different slot numbers
        under the overlap and sync schedules.  Per-row sampling keys follow
        row order — serial order is schedule-invariant, slot order is not,
        and byte-identical streams require the former."""
        act = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and r.status is RequestStatus.RUNNING
        ]
        act.sort(key=lambda t: t[1].sched_serial)
        return act

    def _prefill_phase_fold_free(self) -> bool:
        """Conservatively predict, BEFORE the in-flight frame is consumed,
        that this step's prefill phase cannot fold a sampling key (no final
        chunk, no admission).  The chained lookahead — this step's decode
        fold — is dispatched ahead of the phase, and sync folds prefill
        before decode, so the early launch is only legal under this
        guarantee.

        Conservative means: may return False and cost one lookahead (that
        step runs the sync path), never wrongly True.  Admission capacity
        (slots/pages freed by finishes INSIDE the frame) is unknowable
        pre-consume, so any POSSIBLE admission predicts False — the phase
        itself then runs post-consume and sees exactly the capacity the
        sync schedule would.  What remains predictable: the oldest
        ``PREFILLING`` continuation's next chunk is final iff its remainder
        fits the budget (fold), and a non-final chunk eats the entire
        budget, making every admission impossible regardless of capacity —
        the waiting-over-budget case where the lookahead survives."""
        if self.sched.prefill_mix_policy == "throughput":
            # legacy drain: any waiting request may admit (and fold)
            return not self.waiting
        budget = self.sched.max_prefill_tokens
        cont = [
            r for r in self.slots
            if r is not None and r.status is RequestStatus.PREFILLING
        ]
        if cont:
            first = min(cont, key=lambda r: r.sched_serial)
            if len(first.all_token_ids) - first.prefill_pos <= budget:
                return False  # final chunk will sample this step
            budget = 0  # the non-final chunk consumes the whole budget
        return budget == 0 or not self.waiting

    def _frame_stale(self, frame: InFlightFrame) -> bool:
        """True when the frame no longer matches the schedule the sync path
        would run: any lane released/rolled back, or the decode lane set
        changed.  A waiting queue no longer stales a lookahead by itself:
        the prefill phase runs every step BEFORE launch decisions, so an
        admission either folds a key there (which suppresses the next
        lookahead) or parks the request ``PREFILLING`` outside the lane set
        — either way the frame in flight still matches the sync schedule."""
        if frame.spec:
            # a spec frame reaching the non-spec pipeline is a mode mix-up
            # (runtime config flip): never consume it here
            return True
        active = self._decode_active()
        if len(active) != len(frame.lanes):
            return True
        for (slot, req, expected), (i, r) in zip(frame.lanes, active):
            if (
                slot != i
                or req is not r
                or req.is_finished
                or req.seq_len != expected
            ):
                return True
        return False

    def _discard_frame(self, frame: InFlightFrame) -> None:
        """Drop an in-flight frame's results.  Rewinds the sampling-key
        counter (so the replacement launch folds the key the sync schedule
        would have) unless something else folded a key since the launch
        (e.g. a PD prefill_only interleave — parity is already off there).
        Device-side penalty counts advanced by the discarded horizon are
        marked for host-side re-derivation."""
        if frame.lookahead:
            # loads()' kept/discarded pair describes LOOKAHEAD launches only
            # (a stale cold frame dropped on stop/abort is not a lookahead
            # outcome and would inflate the ratio)
            self.num_lookahead_discarded += 1
        if (
            frame.rng_mark is not None
            and self.runner._step == frame.rng_mark + frame.folds
        ):
            # rewind EVERY in-loop fold the launch consumed (a megastep
            # consumes horizon folds, one per column)
            self.runner.rng_restore(frame.rng_mark)
        # the discarded horizon's device-computed columns are pure waste; the
        # results are never fetched, so count the full requested width (an
        # upper bound — the device may have early-exited sooner)
        self.num_wasted_decode_tokens += frame.B_real * frame.horizon
        if self._frames_advance_state and frame.ran:
            self._state_lost([r for _s, r, _e in frame.lanes], "discarded frame")
        if frame.use_pen:
            for _slot, req, _expected in frame.lanes:
                if req.sampling.has_penalties and not req.is_finished:
                    req.penalty_synced = False

    def _count_routed(self, routed: list) -> None:
        """The expert layers' counts of a consumed decode frame."""
        new = dict(zip(self.runner.module.ROUTED_COUNTS, routed))
        c = self.moe_counts
        for name, n in new.items():
            c[name] = max(c[name], n) if name == "rows_max" else c[name] + n
        self._step_moe = new
        if self.metrics is not None:
            held, zero = new["picks_held"], new.get("picks_zero", 0)
            self.metrics.moe_picks.labels(held="true").inc(held)
            self.metrics.moe_picks.labels(held="false").inc(new["picks"] - held - zero)
            if "picks_zero" in new:
                self.metrics.moe_picks.labels(held="identity").inc(zero)

    def _state_lost(self, reqs: list, why: str) -> None:
        """A frame advanced these sequences' recurrent state by columns that
        are not accepted (a discarded frame that ran, or a host trim short of
        what the device ran).  The state cannot be taken back and no snapshot
        is kept, so each sequence still alive gives up its slot and pages
        and prefills again from its first token (``state_recomputed_tokens``).
        A lookahead chained on a frame that met a finish never gets here: it
        ran no column (``InFlightFrame.ran``)."""
        for req in reqs:
            if (req.is_finished or req.slot is None
                    or req.status is not RequestStatus.RUNNING):
                continue
            logger.warning("request %s lost its recurrent state (%s); prefilling again",
                           req.rid, why)
            self._preempt(req)

    def _rewind_unused_folds(self, frame: InFlightFrame, used: int) -> None:
        """A finish trimmed a consumed megastep at column ``used-1``: the
        launch consumed ``frame.folds`` key-counter values but the sync
        schedule only folded ``used`` of them before recomposing the batch.
        Rewind the tail so the relaunch (and any prefill fold before it)
        lands on exactly the counter value the K=1 schedule would use.  The
        guard mirrors ``_discard_frame``'s: rewind only while this frame's
        folds are still the newest (any chained lookahead was discarded
        first — LIFO rewinds)."""
        if (
            frame.rng_mark is not None
            and self.runner._step == frame.rng_mark + frame.folds
        ):
            self.runner.rng_restore(frame.rng_mark + used)

    def drop_inflight(self) -> None:
        """Discard any pending frame (engine stop/drain, cache flush, or a
        runtime overlap-mode flip)."""
        if self.inflight is not None:
            self._discard_frame(self.inflight)
            self.inflight = None

    def _token_finish(
        self, sp, tok: int, out_len: int, total_len: int
    ) -> FinishInfo | None:
        """THE token-level finish rule, for one accepted decode token with
        the post-acceptance counters (``out_len`` output tokens so far,
        ``total_len`` prompt+output).  Single source of truth shared by
        ``_accept_tokens`` (acceptance) and ``_host_finish_col`` (megastep
        trim) — and mirrored on DEVICE by the done mask built in
        ``_refresh_decode_state`` (stop_ids/limits); a rule added here must
        be added there too, or the device loop will overrun the trim point
        (wasted columns, never wrong streams — the host trim stays
        authoritative)."""
        if not sp.ignore_eos and tok in self.config.model.eos_token_ids:
            return FinishInfo(reason="stop", matched_stop=tok)
        if tok in sp.stop_token_ids:
            return FinishInfo(reason="stop", matched_stop=tok)
        if out_len >= sp.max_new_tokens:
            return FinishInfo(reason="length")
        if total_len >= self.sched.max_seq_len:
            return FinishInfo(reason="length")
        return None

    def _host_finish_col(self, req: EngineRequest, row, horizon: int):
        """First column of ``row`` (one lane's megastep tokens) that triggers
        a finish under ``_token_finish``, or None — the host-side mirror of
        the device done mask: the trim column it yields must match the
        device's early-exit column, and the K-sweep parity tests pin the two
        rule sets together.  A column of a verify frame is the one or two
        tokens the lane emitted in it."""
        sp = req.sampling
        out_len = len(req.output_ids)
        total = req.total_len
        for j in range(horizon):
            # smglint: disable-next=HOTSYNC row was device_get-fetched in _consume_frame
            for tok in (row[j] if self._self_draft else (int(row[j]),)):
                out_len += 1
                total += 1
                if self._token_finish(sp, int(tok), out_len, total) is not None:
                    return j
        return None

    def _count_drafts(self, frame: InFlightFrame, tail, used: int, ran: int) -> None:
        """A consumed verify frame's drafts: tried and taken in the columns
        accepted (``loads()["mtp"]``, the step record, the ``mtp`` tier of the
        speculation metrics); a draft taken in a column the host trimmed
        counts as tried."""
        emitted, _last, _pos, (drafted, _accepted) = tail
        tokens = int(emitted[:frame.B_real, :used].sum())
        columns = frame.B_real * used
        accepted = tokens - columns
        drafted = int(drafted) * used // ran if ran else 0
        c = self.mtp_counts
        c["drafted"] += drafted
        c["accepted"] += accepted
        c["columns"] += columns
        c["tokens"] += tokens
        self.num_spec_drafted += drafted
        self.num_spec_accepted += accepted
        self._step_spec_drafted += drafted
        self._step_spec_accepted += accepted
        self.num_wasted_decode_tokens += drafted - accepted
        if self.metrics is not None and drafted:
            self.metrics.observe_spec("mtp", drafted, accepted)

    @spanned("smg.step.consume")
    def _consume_frame(
        self, frame: InFlightFrame, outputs: list[StepOutput]
    ) -> int:
        """Deferred fetch + host-side acceptance; returns the columns
        accepted.  ``jax.device_get`` is the EXPLICIT
        materialization of the async results — the one intended device→host
        sync per steady-state step, and the form the transfer guard permits.

        K=1 equivalence rule: acceptance stops at the EARLIEST finish column
        across the batch.  Columns up to and including it were sampled with
        the exact keys and batch composition the single-step schedule would
        have used; everything past it belongs to a recomposed batch, so it
        is discarded for every lane and the unused key folds are rewound by
        the caller.  The device's done-mask early exit means those discarded
        columns were (normally) never computed."""
        FAULTS.fire(
            "engine.device_fetch",
            rids=",".join(r.rid for _s, r, _e in frame.lanes),
        )
        with self.account.span("smg.step.consume.fetch", proves=frame.serial):
            toks, lps, steps_run, clean, routed, tail = jax.device_get(
                (frame.toks, frame.lps, frame.steps_run, frame.clean, frame.routed,
                 frame.tail)
            )
        if tail is not None:
            # a verify frame: a lane's tokens column by column
            by_column = lambda rows: [[row[j][:n] for j, n in enumerate(ns)]
                                      for row, ns in zip(rows, tail[0])]
            toks, lps = by_column(toks), by_column(lps)
        # recurrent models: what a frame chained on this one did (ran, or ran
        # no column because this one met a finish)
        frame.clean = clean
        if frame.lookahead:
            self.num_lookahead_kept += 1
        sr = int(steps_run) if steps_run is not None else frame.horizon
        # host-side trim: earliest finish column across all lanes (scanning
        # only device-computed columns — later ones hold unset zeros)
        used = min(frame.horizon, sr) if sr > 0 else frame.horizon
        if self._frames_advance_state:
            used = min(frame.horizon, sr)  # a frame that ran no column gives no token
        finished_any = False
        for idx, (_slot, req, _expected) in enumerate(frame.lanes):
            col = self._host_finish_col(req, toks[idx], used)
            if col is not None:
                finished_any = True
                if col + 1 < used:
                    used = col + 1
        self._step_horizon = frame.horizon
        self._step_columns_run = min(frame.horizon, sr)
        if routed is not None:
            self._count_routed([int(x) for x in routed])
        if sr < frame.horizon:
            self.num_megastep_early_exits += 1
        if sr > used:
            # device computed past the accepted trim point (possible only if
            # the device done rules lag the host's) — pure waste, normally 0
            self.num_wasted_decode_tokens += (sr - used) * frame.B_real
        self._step_state_lanes = sum(
            1 for _s, r, _e in frame.lanes if r.state_slot is not None)
        if tail is None:
            self.num_decode_tokens += frame.B_real * used
            flat = lambda row: row[:used]
        else:
            flat = lambda columns: [x for column in columns[:used] for x in column]
        for idx, (_slot, req, _expected) in enumerate(frame.lanes):
            before = len(req.output_ids)
            self._accept_tokens(
                req,
                [int(t) for t in flat(toks[idx])],
                [float(x) for x in flat(lps[idx])],
                outputs,
                advance_seq=True,
            )
            if tail is not None:
                self.num_decode_tokens += len(req.output_ids) - before
        if tail is not None:
            self._count_drafts(frame, tail, used, sr)
        if self._frames_advance_state and sr > used:
            # the device ran past what the host accepts: the state holds
            # tokens that were never emitted
            self._state_lost([r for _s, r, _e in frame.lanes], "host trim short of device")
        # adaptive-horizon controller signal: EMA of decode columns between
        # finishes — the expected uninterrupted run length K should track
        self._cols_since_finish += used
        if finished_any:
            gap = float(self._cols_since_finish)
            self._finish_gap_ema = (
                gap if self._finish_gap_ema == 0.0
                else 0.7 * self._finish_gap_ema + 0.3 * gap
            )
            self._cols_since_finish = 0
        return used

    @spanned("smg.step.launch", _launch_attrs)
    def _launch_lookahead(self, frame: InFlightFrame) -> InFlightFrame | None:
        """Chained launch for the step AFTER ``frame``, dispatched before
        ``frame`` is consumed.  Input tokens are the frame's last sampled
        column (device-resident — no host round trip); positions advance by
        the horizon.  The caller only launches after a fold-free prefill
        phase (see ``_step_overlap``) — a waiting queue that is over budget
        or back-pressured does NOT suppress the launch.  Returns None when
        the next step is not predictable:

        - any lane is grammar-constrained (the vocab mask is host-derived
          from last step's token — the structured-output forced-sync case);
        - any lane will deterministically finish inside the frame being
          consumed (max_new_tokens / max_seq_len) — the launch would be
          discarded for certain;
        - page capacity for the extended horizon isn't available from the
          free pool (eviction/preemption here would diverge from the sync
          schedule's, which runs AFTER finishes release pages).
        """
        FAULTS.fire(
            "engine.decode_step",
            rids=",".join(r.rid for _s, r, _e in frame.lanes),
        )
        H = frame.horizon
        # the chained frame re-evaluates the horizon controller (admission
        # pressure / finish-rate/page headroom may have moved since the cold
        # launch); forced-K=1 lane sets stay forced, so max_steps (and with
        # it the compiled trace and stop-state signature) cannot flip
        H2, max_steps = self._pick_horizon(
            [(s, r) for s, r, _ in frame.lanes]
        )
        if self._frames_advance_state and (max_steps == 1 or frame.clean is None):
            # without the device's stop state a frame cannot tell the one
            # chained on it that it met a finish, and a lookahead that ran and
            # is then discarded costs its lanes their state
            return None
        ps = self.ps
        max_seq = self.sched.max_seq_len
        need = 0
        reach = [self._reach(req, expected, H + H2) for _slot, req, expected in frame.lanes]
        for (_slot, req, expected), limit in zip(frame.lanes, reach):
            sp = req.sampling
            if req.token_filter is not None:
                return None
            if len(req.output_ids) + H >= sp.max_new_tokens:
                return None
            if req.total_len + H >= max_seq:
                return None
            have = len(req.shared_pages) + len(req.owned_pages)
            need += max(0, math.ceil(limit / ps) - have)
        if need > self._headroom_pages():
            return None
        for (_slot, req, expected), limit in zip(frame.lanes, reach):
            # precheck guarantees allocation without preemption (it may evict
            # cached pages that no request holds)
            if not self._ensure_seq_capacity(req, limit - expected):
                return None  # defensive; unreachable after the precheck
        mp_b = self._mp_bucket(max(math.ceil(limit / ps) for limit in reach))
        if frame.tail is None:
            positions = frame.positions + np.int32(H)
            positions[frame.B_real:] = mp_b * ps  # padded rows -> garbage page
        ds = self._refresh_decode_state(
            [(s, r) for s, r, _ in frame.lanes], frame.B, mp_b,
            frame.use_pen, frame.use_lora, frame.use_mrope, frame.lane_sig,
        )
        mark = self.runner.rng_mark()
        with self.account.span("smg.step.launch.dispatch"):
            # the chained input column comes off the in-flight frame with a
            # STATIC lax slice: `frame.toks[:, -1]` would route the index
            # through eager dispatch as a scalar operand — an implicit
            # host→device transfer every launch, which the steady-state guard
            # forbids
            if frame.tail is None:
                last_col = lax.index_in_dim(frame.toks, frame.horizon - 1, axis=1,
                                            keepdims=False)
            else:
                # a verify frame says itself where it left each lane (a padded
                # lane holds no slot and writes nowhere, wherever it stands)
                _emitted, last_col, positions, _spec = frame.tail
            toks, lps, steps_run = self.runner.decode_multi_async(
                last_col, positions, ds.page_tables,
                ds.temps, ds.topks, ds.topps, ds.minps, H2,
                max_steps=max_steps,
                stop_state=(ds.stop_ids, ds.limits, ds.live)
                if max_steps > 1 else None,
                pen=(ds.slot_idx, ds.freqs, ds.pres, ds.reps)
                if frame.use_pen else None,
                lora_idx=ds.lora_idx if frame.use_lora else None,
                rope_delta=ds.rope_delta if frame.use_mrope else None,
                **self._state_kw(ds, chain=frame.clean),
            )
        self._count_decode_launch()
        return InFlightFrame(
            serial=self.account.launched,
            clean=self.runner.frame_clean,
            routed=self.runner.frame_counts,
            tail=self.runner.frame_tail,
            lanes=[(s, r, e + H) for s, r, e in frame.lanes],
            toks=toks, lps=lps, horizon=H2, B=frame.B, B_real=frame.B_real,
            mp_b=mp_b, positions=positions, lane_sig=frame.lane_sig,
            use_pen=frame.use_pen, use_lora=frame.use_lora,
            use_mrope=frame.use_mrope, rng_mark=mark, lookahead=True,
            folds=H2, steps_run=steps_run,
        )

    # ---- admission / prefill (the per-step prefill phase) ----

    @spanned("smg.step.admit")
    def _admit(self, outputs: list[StepOutput]) -> bool:
        """Run this step's prefill phase under the configured mix policy.

        Returns True when any SAMPLING prefill ran — i.e. a key was folded
        and/or the decode lane set grew.  The overlap pipeline keys the
        lookahead-launch decision off this: a fold-free phase (non-final
        resumable chunks, back-pressure, over-budget waiting) leaves the
        global key-fold order untouched, so a chained decode launch stays
        byte-identical to the synchronous schedule."""
        if self._chaining and self.let_submitters_in is not None:
            # the overlap pipeline with no lookahead out: the frame in flight
            # is fetched, the step's decode launch follows this phase, and a
            # request that came while the fetch blocked is still outside the
            # lock.  Let in now, it is prefilled in this step; let in after
            # it, it waits out the frame this step is about to launch
            self.let_submitters_in()
        if self.sched.prefill_mix_policy == "throughput":
            return self._admit_legacy(outputs)
        return self._admit_budgeted(outputs)

    def _admit_budgeted(self, outputs: list[StepOutput]) -> bool:
        """Stall-free chunked-prefill scheduling (Sarathi-style): spend at
        most ONE ``max_prefill_tokens`` budget per step, split between

        1. resuming ``PREFILLING`` slot-holders from their cursors (oldest
           admission first), then
        2. admitting waiting prompts into leftover budget — whole short
           prompts batch through the grouped prefill; a prompt bigger than
           the leftover packs its first ``budget``-sized chunk and parks in
           its slot as ``PREFILLING`` (slivers under one page wait instead).

        Non-final chunks write KV only (no sampling, no key fold —
        ``runner.prefill_extend``); the FINAL chunk samples the request's
        first token and promotes it to a decode lane.  ``_decode`` runs
        every step regardless, so running lanes never observe more than
        ~one chunk of added latency while a long prompt streams in."""
        sched = self.sched
        budget = sched.max_prefill_tokens
        disturbed = False
        cont = sorted(
            (r for r in self.slots
             if r is not None and r.status is RequestStatus.PREFILLING),
            key=lambda r: r.sched_serial,
        )
        for req in cont:
            if budget <= 0:
                break
            remaining = len(req.all_token_ids) - req.prefill_pos
            if remaining <= budget:
                budget -= remaining
                try:
                    self._prefill_final(req, outputs)
                except Exception as e:  # noqa: BLE001 — quarantine boundary
                    self._count_step_failure("prefill")
                    self._fail_request(req, f"prefill failed: {e}", outputs)
                # disturbed either way: on failure we cannot know whether the
                # key folded before the raise, and a wrongly-kept lookahead
                # would desync streams — discarding one is the safe cost
                disturbed = True
            else:
                if budget < min(self.ps, sched.max_prefill_tokens):
                    # sub-page leftover from an earlier final: a bucketed
                    # dispatch for a sliver isn't worth it — same rule
                    # admission applies.  (A FULL budget always runs, even
                    # one configured below page_size, so progress is
                    # guaranteed.)
                    break
                try:
                    self._prefill_chunk(req, budget)
                except Exception as e:  # noqa: BLE001 — quarantine boundary
                    self._count_step_failure("prefill")
                    self._fail_request(req, f"prefill failed: {e}", outputs)
                budget = 0
        group: list[EngineRequest] = []
        while budget > 0 and not self.draining and self.waiting:
            got = self._try_admit_head(outputs, budget_left=budget)
            if got is None:
                break  # no slot, page back-pressure, or sliver-sized leftover
            if got == "consumed":
                continue  # head finished without admission (error / 0-budget)
            req = got
            remaining = len(req.all_token_ids) - req.prefill_pos
            if remaining <= budget:
                budget -= remaining
                group.append(req)
                if len(group) >= sched.max_prefill_group:
                    self._prefill_group_guarded(group, outputs)
                    disturbed = True
                    group = []
            else:
                # over budget: pack the leftover as the first resumable chunk
                try:
                    self._prefill_chunk(req, budget)
                except Exception as e:  # noqa: BLE001 — quarantine boundary
                    self._count_step_failure("prefill")
                    self._fail_request(req, f"prefill failed: {e}", outputs)
                budget = 0
        if group:
            self._prefill_group_guarded(group, outputs)
            disturbed = True
        return disturbed

    def _prefill_group_guarded(
        self, group: list[EngineRequest], outputs: list[StepOutput]
    ) -> None:
        """Grouped prefill with per-request blame attribution: when the
        batched call fails, fall back to solo prefills so only the culprit
        is quarantined and innocent group members still promote this step."""
        try:
            self._prefill_group(group, outputs)
        except Exception:  # noqa: BLE001 — quarantine boundary
            self._retry_group_solo(group, outputs)

    def _retry_group_solo(
        self, group: list[EngineRequest], outputs: list[StepOutput]
    ) -> None:
        """The grouped prefill of ``group`` failed (call from the handler)."""
        self._count_step_failure("prefill")
        logger.exception(
            "grouped prefill failed; retrying %d members solo", len(group)
        )
        for req in group:
            if req.is_finished:
                continue
            try:
                self._prefill_final(req, outputs)
            except Exception as e:  # noqa: BLE001 — the culprit
                self._fail_request(req, f"prefill failed: {e}", outputs)

    def _first_tokens_needed(self, group: list[EngineRequest]) -> str | None:
        """Why the host needs the first tokens of ``group`` before it can
        launch the step's decode frame (a PREFILL_SYNC_REASONS word), or None
        where the frame may be dispatched behind the prefill.  Read off what
        the requests carry: penalty counts, a grammar's mask and a stop
        string are derived from the token on the host; a first token that
        ends its request by its length changes the lane set for certain.  A
        stop id may end it too: a frame that held the lane is then thrown
        away (``_launch_behind_prefill``), which costs a model with
        recurrent state every lane's state, so there it waits."""
        eos = self.config.model.eos_token_ids
        for req in group:
            sp = req.sampling
            if sp.has_penalties:
                return "penalties"
            if req.token_filter is not None:
                return "token_filter"
            if sp.stop:
                return "stop_strings"
            if (len(req.output_ids) + 1 >= sp.max_new_tokens
                    or req.total_len + 1 >= self.sched.max_seq_len):
                return "first_token_ends"
            if self._frames_advance_state and (
                    sp.stop_token_ids or (eos and not sp.ignore_eos)):
                return "recurrent_stop_ids"
        return None

    def _count_prefill_launch(self, sync_reason: str | None) -> None:
        """One sampling prefill of the overlap pipeline: the step's decode
        frame went out behind it (None), or its tokens were fetched first."""
        if sync_reason is None:
            self.num_prefill_chained += 1
        else:
            self.num_prefill_sync[sync_reason] += 1

    def _settle_pending(self, outputs: list[StepOutput]) -> None:
        """Fetch and accept a grouped prefill's first tokens now, where
        something other than the step's decode launch comes next (as a
        rule the phase's next sampling prefill)."""
        pend, self._pending_group = self._pending_group, None
        if pend is not None:
            self._count_prefill_launch("earlier_group")
            self._accept_first_tokens_guarded(pend, outputs)

    def _accept_first_tokens_guarded(
        self, pend: tuple, outputs: list[StepOutput],
        frame: InFlightFrame | None = None,
    ) -> bool:
        """``_accept_first_tokens`` outside ``_prefill_group_guarded``: a
        prefill that failed on the device has its members retried solo, after
        ``frame`` (dispatched behind it) has given its folds back."""
        try:
            self._accept_first_tokens(pend, outputs)
            return True
        except Exception:  # noqa: BLE001 — quarantine boundary
            if frame is not None:
                self._discard_frame(frame)
            self._retry_group_solo(pend[0], outputs)
            return False

    def _accept_first_tokens(self, pend: tuple, outputs: list[StepOutput]) -> None:
        """The blocking fetch of a grouped prefill's first tokens and their
        acceptance.  A launch that failed on the device surfaces here: the
        members go back to where admission left them and the caller retries
        them solo (counted again there, never double)."""
        group, parts, serial = pend
        try:
            toks, lps = self.runner.fetch_first_tokens(parts, len(group))
            self.account.fetched(serial)
        except Exception:
            for req in group:
                self.num_prefill_tokens -= req.seq_len - req.cached_tokens
                req.seq_len = req.prefill_pos = req.cached_tokens
                req.status = RequestStatus.PREFILLING
            raise
        for i, req in enumerate(group):
            self._accept_tokens(
                # smglint: disable-next=HOTSYNC toks/lps fetched in fetch_first_tokens
                req, [int(toks[i])], [float(lps[i])], outputs, advance_seq=False
            )

    def _admit_legacy(self, outputs: list[StepOutput]) -> bool:
        """Drain-the-queue admission (``prefill_mix_policy="throughput"``):
        every admissible request prefills THIS step, long prompts looping
        all their chunks back-to-back — maximal prefill throughput, at the
        cost of stalling decode for the whole drain."""
        disturbed = False
        while not self.draining and self.waiting:
            # collect a group of admissible single-chunk prompts; long prompts
            # run solo through the chunk loop
            group: list[EngineRequest] = []
            admitted_any = False
            while self.waiting and len(group) < self.sched.max_prefill_group:
                got = self._try_admit_head(outputs)
                if got is None:
                    break
                if got == "consumed":
                    continue
                req = got
                admitted_any = True
                disturbed = True
                prompt = req.all_token_ids
                remaining = len(prompt) - req.cached_tokens
                if remaining > self.sched.max_prefill_tokens:
                    # long prompts chunk through the solo loop; short ones
                    # batch — including under serving pp and M-RoPE (the
                    # grouped forward takes pp_mesh + per-row rope ids)
                    try:
                        self._prefill_solo(req, prompt, req.cached_tokens, outputs)
                    except Exception as e:  # noqa: BLE001 — quarantine boundary
                        self._count_step_failure("prefill")
                        self._fail_request(req, f"prefill failed: {e}", outputs)
                else:
                    # mm requests batch like text: the group path splices
                    # per-row embeddings (r3 forced them solo — weak #6)
                    group.append(req)
            if group:
                self._prefill_group_guarded(group, outputs)
            if not admitted_any:
                return disturbed
        return disturbed

    def _try_admit_head(
        self, outputs: list[StepOutput], budget_left: int | None = None
    ):
        """Admit the head of the waiting queue into a free slot: radix-match
        its prefix, allocate pages for the WHOLE prompt (back-pressure
        applies here, not mid-prefill), and park it as ``PREFILLING`` with
        the cursor at the matched prefix — the caller decides how much of it
        prefills this step.  Returns the request on admission, ``None`` when
        blocked (no slot / pages / the leftover ``budget_left`` is a
        sub-page sliver not worth a chunk), or ``"consumed"`` when the head
        finished without admission (error / zero-token budget)."""
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        if not free_slots:
            return None
        req = self.waiting[0]
        prompt = req.all_token_ids  # includes prior output after preemption
        if len(prompt) + 1 > self.sched.max_seq_len:
            self.waiting.popleft()
            req.status = RequestStatus.FINISHED
            req.finish = FinishInfo(
                reason="error",
                message=f"prompt length {len(prompt)} exceeds max_seq_len {self.sched.max_seq_len}",
            )
            self._count_finish(req, "error", req.finish.message)
            outputs.append(StepOutput(req, [], True, req.finish))
            return "consumed"
        if req.sampling.max_new_tokens == 0:
            self.waiting.popleft()
            req.status = RequestStatus.FINISHED
            req.finish = FinishInfo(reason="length")
            self._count_finish(req, "length")
            outputs.append(StepOutput(req, [], True, req.finish))
            return "consumed"

        # radix prefix match (never match the full prompt: at least
        # one token must be computed to produce logits).
        # mm requests participate via per-page content-hash extra
        # keys (reference approach): identical placeholder token
        # runs with different pixels hash to different chains, so
        # repeated image prompts DO share KV instead of re-encoding
        shared_pages: list[int] = []
        node = None
        if self.radix is not None:
            shared_pages, node = self.radix.match_prefix(
                prompt[:-1],
                extra_keys=self._mm_extra_keys(req, len(prompt)),
            )
        if self.state_pool is not None:
            if self.state_pool.free_count == 0:
                return None  # every state slot is held: wait for a release
            if shared_pages:
                # the pages of the prefix are here and the recurrent state at
                # its end is not: the prompt prefills from its first token
                self.num_state_prefix_hits_declined += 1
                shared_pages, node = [], None
        matched_tokens = len(shared_pages) * self.ps
        remaining = len(prompt) - matched_tokens
        if (
            budget_left is not None
            and remaining > budget_left
            and budget_left < min(self.ps, self.sched.max_prefill_tokens)
        ):
            return None  # sliver: cheaper to wait for next step's full budget
        prompt_pages_total = math.ceil(len(prompt) / self.ps)
        need = prompt_pages_total - len(shared_pages)

        # pin the matched chain BEFORE the free-page check: the check may
        # EVICT from the radix cache, and an unpinned matched prefix is fair
        # game — ``shared_pages`` would then reference freed (re-allocatable)
        # pages.  Routinely hit since mid-prefill preemption banks partial
        # prefixes that readmission immediately matches under page pressure.
        if node is not None:
            self.radix.lock(node)
        if not self._ensure_free_pages(need + self.sched.watermark_pages):
            if node is not None:
                self.radix.unlock(node)
            return None  # back-pressure: wait for pages

        self.waiting.popleft()
        # admission-time hit-rate accounting (once per admission; a
        # preempted request re-admits and recounts — its re-prefill
        # really does re-read/re-compute those tokens)
        self.num_cached_prompt_tokens += matched_tokens
        self.num_computed_prompt_tokens += remaining
        self.num_radix_hit_pages += len(shared_pages)
        self.num_radix_miss_pages += need
        req.radix_node = node
        req.shared_pages = shared_pages
        req.cached_tokens = matched_tokens
        req.owned_pages = self.pool.alloc(need)
        if self.state_pool is not None:
            # a chunk at position 0 starts from zero state whatever the slot
            # held before (models/olmo_hybrid.py), so binding is all it takes
            req.state_slot = self.state_pool.alloc()
            if req.status is RequestStatus.PREEMPTED:
                # it had a state and lost it: everything it held is computed again
                self.num_state_recomputed_tokens += len(prompt) - 1
        req.status = RequestStatus.PREFILLING
        req.prefill_pos = matched_tokens
        req.seq_len = matched_tokens

        slot = free_slots[0]
        req.slot = slot
        row = self.page_tables[slot]
        row[:] = 0
        all_pages = shared_pages + req.owned_pages
        row[: len(all_pages)] = all_pages
        self.slots[slot] = req
        self._pages_dirty = True
        self._step_admissions += 1
        if self.flight is not None:
            self.flight.event(
                req.rid, "admitted", slot=slot, cached_tokens=matched_tokens
            )
        return req

    def _prefill_chunk(self, req: EngineRequest, take: int) -> None:
        """Advance a resumable prefill by one NON-final chunk: KV writes
        only, nothing sampled, no key fold (see ``runner.prefill_extend``) —
        which is what lets a lookahead decode frame stay in flight across
        this step."""
        FAULTS.fire("engine.prefill", rid=req.rid)
        start = req.prefill_pos
        with self.account.span("smg.step.admit.pack"):
            chunk = req.all_token_ids[start : start + take]
            mm = self._mm_chunk(req, start, len(chunk))
            rope_pos = self._mrope_chunk(req, start, len(chunk))
        self.runner.prefill_extend(
            chunk,
            prefix_len=start,
            page_table=self.page_tables[req.slot],
            lora_idx=req.lora_idx,
            mm=mm,
            rope_pos=rope_pos,
            **self._slot_kw(req, start + len(chunk)),
        )
        self.num_prefill_tokens += len(chunk)
        req.prefill_pos += len(chunk)
        req.seq_len = req.prefill_pos
        if self.flight is not None:
            self.flight.event(
                req.rid, "prefill_chunk", start=start, n=len(chunk), final=False
            )

    def _prefill_final(
        self, req: EngineRequest, outputs: list[StepOutput]
    ) -> None:
        """Run the FINAL chunk of a resumable prefill: write the remaining
        prompt KV, sample the request's first token (this is the prefill key
        fold the overlap pipeline orders lookahead launches after), and
        promote the request to a decode lane."""
        self._settle_pending(outputs)
        if self._chaining:
            self._count_prefill_launch("solo")
        FAULTS.fire("engine.prefill", rid=req.rid)
        sp = req.sampling
        with self.account.span("smg.step.admit.pack"):
            prompt = req.all_token_ids
            start = req.prefill_pos
            chunk = prompt[start:]
            pen, mask = self._solo_pen_and_mask(req)
            mm = self._mm_chunk(req, start, len(chunk))
            rope_pos = self._mrope_chunk(req, start, len(chunk))
        tok, lp = self.runner.prefill(
            chunk,
            prefix_len=start,
            page_table=self.page_tables[req.slot],
            temperature=sp.temperature,
            top_k=sp.top_k,
            top_p=sp.top_p,
            min_p=sp.min_p,
            pen=pen,
            mask=mask,
            lora_idx=req.lora_idx,
            mm=mm,
            rope_pos=rope_pos,
            **self._slot_kw(req),
        )
        self.num_prefill_tokens += len(chunk)
        req.prefill_pos = len(prompt)
        req.seq_len = len(prompt)
        req.status = RequestStatus.RUNNING
        if self.flight is not None:
            self.flight.event(
                req.rid, "prefill_chunk", start=start, n=len(chunk), final=True
            )
        self._accept_tokens(req, [tok], [lp], outputs, advance_seq=False)

    def _slot_kw(self, req: EngineRequest, end: int | None = None) -> dict:
        """A recurrent model's keywords of a solo prefill launch.  ``end``:
        where the chunk ends in ``all_token_ids``; a self-drafting runner is
        told the token behind a chunk that is not the last (its next-token
        module's last position takes it, and not the token sampled)."""
        if self.state_pool is None:
            return {}
        kw = {"state_slot": req.state_slot}
        if self._self_draft and end is not None and end < len(req.all_token_ids):
            kw["next_token"] = req.all_token_ids[end]
        return kw

    def _mask_for(self, req: EngineRequest) -> np.ndarray:
        """Constrained-decoding vocab mask for the request's next token.
        Fail-safe: a vocabulary with no valid continuation (tokenizer can't
        spell the grammar) degrades to EOS-only so generation terminates
        instead of sampling uniformly over NEG_INF logits."""
        f = req.token_filter
        m = f.allowed_mask(f.text_of(req.output_ids))
        if not m.any():
            m = m.copy()
            m[list(self.config.model.eos_token_ids)] = True
        return m

    def _req_pen_state(self, req: EngineRequest) -> tuple:
        """Host-side (counts [V], pmask [V]) snapshot for a prefill call."""
        return self.runner.penalty_state(req.prompt_ids, req.output_ids)

    def _solo_pen_and_mask(self, req: EngineRequest) -> tuple:
        """A solo sampling prefill's ``pen`` and ``mask`` (None where the
        request has no penalties, no grammar)."""
        sp = req.sampling
        pen = None
        if sp.has_penalties:
            counts, pmask = self._req_pen_state(req)
            pen = (counts, pmask, sp.frequency_penalty, sp.presence_penalty,
                   sp.repetition_penalty)
        mask = self._mask_for(req) if req.token_filter is not None else None
        return pen, mask

    def _prefill_solo(
        self, req: EngineRequest, prompt: list[int], matched_tokens: int,
        outputs: list[StepOutput],
    ) -> None:
        """Long prompts: loop chunks under the prefill token budget."""
        self._settle_pending(outputs)
        if self._chaining:
            self._count_prefill_launch("solo")
        FAULTS.fire("engine.prefill", rid=req.rid)
        row = self.page_tables[req.slot]
        start = matched_tokens
        sp = req.sampling
        with self.account.span("smg.step.admit.pack"):
            pen, mask = self._solo_pen_and_mask(req)
        tok = lp = None
        while start < len(prompt):
            with self.account.span("smg.step.admit.pack"):
                chunk = prompt[start : start + self.sched.max_prefill_tokens]
                mm = self._mm_chunk(req, start, len(chunk))
                rope_pos = self._mrope_chunk(req, start, len(chunk))
            tok, lp = self.runner.prefill(
                chunk,
                prefix_len=start,
                page_table=row,
                temperature=sp.temperature,
                top_k=sp.top_k,
                top_p=sp.top_p,
                min_p=sp.min_p,
                pen=pen,
                mask=mask,
                lora_idx=req.lora_idx,
                mm=mm,
                rope_pos=rope_pos,
                **self._slot_kw(req, start + len(chunk)),
            )
            self.num_prefill_tokens += len(chunk)
            start += len(chunk)
            req.prefill_pos = start
            if self.flight is not None:
                self.flight.event(
                    req.rid, "prefill_chunk", start=start - len(chunk),
                    n=len(chunk), final=start >= len(prompt),
                )
        req.seq_len = len(prompt)
        req.status = RequestStatus.RUNNING
        self._accept_tokens(req, [tok], [lp], outputs, advance_seq=False)

    def _mrope_chunk(self, req: EngineRequest, start: int, n: int):
        """[3, n] M-RoPE ids for one prefill chunk.  Positions past the
        prompt (re-prefill after preemption re-runs generated tokens) are
        text: all three axes = sequence position + delta."""
        if req.mrope_pos is None:
            return None
        idx = np.arange(start, start + n)
        out = np.broadcast_to(
            (idx + req.mrope_delta)[None, :], (3, n)
        ).astype(np.int32).copy()
        pl = req.mrope_pos.shape[1]
        within = idx < pl
        if within.any():
            out[:, within] = req.mrope_pos[:, idx[within]]
        return out

    def _mm_extra_keys(
        self, req: EngineRequest, n_tokens: int | None = None
    ) -> "list[int] | None":
        """Per-page mm content salts for radix keying (reference: extra keys
        mixed into block hashes).  Page p's salt digests the embedding rows
        and in-page offsets of every placeholder position the page covers;
        0 = page has no mm content.

        ``n_tokens`` extends coverage past the prompt — insert at finish
        covers generated-token pages, whose rope positions under M-RoPE are
        shifted by the delta and therefore must not alias plain-rope chains
        with the same token ids (nor insert unsalted pages a later M-RoPE
        turn can't re-match)."""
        if req.mm_embeds is None:
            return None
        if n_tokens is None:
            n_tokens = len(req.prompt_ids)
        cached = req.mm_extra_keys
        if cached is not None and cached[0] == n_tokens:
            return cached[1]
        import hashlib

        embeds, positions = req.mm_embeds
        n_pages = math.ceil(n_tokens / self.ps)
        keys = [0] * n_pages
        order = np.argsort(positions)
        for p in range(n_pages):
            lo, hi = p * self.ps, (p + 1) * self.ps
            sel = order[(positions[order] >= lo) & (positions[order] < hi)]
            # KV also depends on rope position ids: under M-RoPE every page
            # whose ids deviate from the sequential arange (the image pages
            # and everything after them — generated positions carry the
            # delta) must salt its hash
            mr = None
            if req.mrope_pos is not None:
                mslice = self._mrope_chunk(req, lo, min(hi, n_tokens) - lo)
                seq = np.arange(lo, lo + mslice.shape[1], dtype=mslice.dtype)
                if not (mslice == seq[None, :]).all():
                    mr = mslice
            if sel.size == 0 and mr is None:
                continue
            h = hashlib.blake2b(digest_size=8)
            # smglint: disable-next=HOTSYNC mm positions/embeds are host numpy
            h.update(np.ascontiguousarray(positions[sel] - lo).tobytes())
            h.update(np.ascontiguousarray(embeds[sel], np.float32).tobytes())
            if mr is not None:
                h.update(b"mrope")
                # smglint: disable-next=HOTSYNC mrope ids are host numpy
                h.update(np.ascontiguousarray(mr).tobytes())
            keys[p] = int.from_bytes(h.digest(), "little") or 1
        req.mm_extra_keys = (n_tokens, keys)
        return keys

    def _mm_chunk(self, req: EngineRequest, start: int, chunk_len: int):
        """Slice the request's mm embeddings for one prefill chunk: a dense
        [chunk_len, E] buffer + bool mask selecting placeholder rows."""
        if req.mm_embeds is None:
            return None
        embeds, positions = req.mm_embeds
        sel = (positions >= start) & (positions < start + chunk_len)
        out = np.zeros((chunk_len, embeds.shape[1]), np.float32)
        m = np.zeros(chunk_len, bool)
        idx = positions[sel] - start
        out[idx] = embeds[sel]
        m[idx] = True
        return out, m

    def _prefill_group(
        self, group: list[EngineRequest], outputs: list[StepOutput]
    ) -> None:
        """Batched prefill for a group of single-chunk prompts.  What needs
        no token's value is done at dispatch; the first tokens are fetched
        and accepted here, or, in a step of the overlap pipeline whose
        requests let it (``_first_tokens_needed``), after that step's decode
        launch has been dispatched behind the prefill
        (``_launch_behind_prefill``)."""
        self._settle_pending(outputs)
        for req in group:
            # per-member seam BEFORE any bookkeeping mutates, so the guarded
            # caller's solo fallback sees a clean state for every member
            FAULTS.fire("engine.prefill", rid=req.rid)
        with self.account.span("smg.step.admit.pack"):
            chunks = []
            g = len(group)
            V = self.runner.model_cfg.vocab_size
            temps = np.zeros(g, np.float32)
            topks = np.full(g, -1, np.int32)
            topps = np.ones(g, np.float32)
            minps = np.zeros(g, np.float32)
            use_pen = any(r.sampling.has_penalties for r in group)
            use_mask = any(r.token_filter is not None for r in group)
            counts = np.zeros((g, V), np.int32) if use_pen else None
            pmask = np.zeros((g, V), bool) if use_pen else None
            freqs = np.zeros(g, np.float32)
            pres = np.zeros(g, np.float32)
            reps = np.ones(g, np.float32)
            mask_arr = np.ones((g, V), bool) if use_mask else None
            use_lora = any(r.lora_idx for r in group)
            lora_idx = np.array([r.lora_idx for r in group], np.int32) if use_lora else None
            mm_rows: list = []
            rope_rows: list = []
            for i, req in enumerate(group):
                prompt = req.all_token_ids
                chunk = prompt[req.cached_tokens :]
                chunks.append((chunk, req.cached_tokens, self.page_tables[req.slot]))
                mm_rows.append(self._mm_chunk(req, req.cached_tokens, len(chunk)))
                rope_rows.append(self._mrope_chunk(req, req.cached_tokens, len(chunk)))
                sp = req.sampling
                temps[i] = sp.temperature
                topks[i] = sp.top_k
                topps[i] = sp.top_p
                minps[i] = sp.min_p
                if use_pen and sp.has_penalties:
                    counts[i], pmask[i] = self._req_pen_state(req)
                    freqs[i] = sp.frequency_penalty
                    pres[i] = sp.presence_penalty
                    reps[i] = sp.repetition_penalty
                if use_mask and req.token_filter is not None:
                    mask_arr[i] = self._mask_for(req)
        parts = self.runner.prefill_batched_async(
            chunks, temps, topks, topps, minps,
            pen=(counts, pmask, freqs, pres, reps) if use_pen else None,
            mask=mask_arr,
            lora_idx=lora_idx,
            mm=mm_rows if any(m is not None for m in mm_rows) else None,
            rope=rope_rows if any(r is not None for r in rope_rows) else None,
            **({"state_slots": [r.state_slot for r in group]}
               if self.state_pool is not None else {}),
        )
        for i, req in enumerate(group):
            # counted only after the batched call was dispatched (a failed
            # group re-counts through the solo fallback, never double)
            self.num_prefill_tokens += len(chunks[i][0])
            req.seq_len = req.total_len
            req.prefill_pos = req.seq_len
            req.status = RequestStatus.RUNNING
            if self.flight is not None:
                self.flight.event(
                    req.rid, "prefill_chunk", start=chunks[i][1],
                    n=len(chunks[i][0]), final=True, grouped=True,
                )
        pend = (group, parts, self.account.launched)
        if self._chaining:
            why = self._first_tokens_needed(group)
            if why is None:
                self._pending_group = pend
                return
            self._count_prefill_launch(why)
        self._accept_first_tokens(pend, outputs)

    def _ensure_free_pages(self, n: int) -> bool:
        if self.pool.free_count >= n:
            return True
        if self.radix is not None:
            freed = self.radix.evict(n - self.pool.free_count)
            if freed:
                self.pool.free(freed)
        return self.pool.free_count >= n

    # ---- decode ----

    def _decode(self, outputs: list[StepOutput]) -> None:
        """Synchronous decode: plan + launch + immediate consume (the overlap
        pipeline calls the same launch/consume halves with a frame between).
        Runs EVERY step — a request mid-resumable-prefill holds its slot but
        never blocks the running lanes from decoding.  Speculative mode runs
        the same phase ordering as the pipelined ``_step_spec`` (rest
        megastep, then the batched verify block) with the frame consumed
        in-step — which is exactly what keeps overlap-on and overlap-off
        spec streams byte-identical."""
        active = self._decode_active()
        if not active:
            return
        if self._host_drafts():
            self._spec_phase(outputs, pipelined=False)
            return
        self._decode_batch(active, outputs)

    def _host_drafts(self) -> bool:
        """Speculation whose drafts the host makes (n-gram, draft model) and a
        verify block of its own checks; not the model's own module, whose
        verify columns are the decode frame's."""
        return (self.sched.speculative and not self._self_draft) or self.draft is not None

    def _decode_batch(self, active: list, outputs: list[StepOutput]) -> None:
        """Launch one megastep for ``active`` and consume it in-step."""
        frame = self._launch_frame(active)
        if frame is not None:
            try:
                used = self._consume_frame(frame, outputs)
            except Exception:
                # stash so the quarantine handler's drop_inflight rewinds
                # this frame's sampling-key folds before any retry refolds
                self.inflight = frame
                raise
            if used < frame.horizon:
                # a finish trimmed the horizon: rewind the unused in-loop
                # folds so the next launch continues the K=1 key sequence
                self._rewind_unused_folds(frame, used)

    def _refresh_decode_state(
        self, active: list, B: int, mp_b: int,
        use_pen: bool, use_lora: bool, use_mrope: bool, sig: tuple,
        stop_e: int = 0,
    ) -> DecodeState:
        """Bring the persistent device-resident decode inputs up to date.

        Sampling params / penalty scalars / LoRA indices / megastep stop
        state (``stop_e`` > 0: per-lane stop-token id sets, absolute length
        limits, live-lane mask) change only on batch-composition change
        (``sig`` mismatch); page tables re-upload only on composition
        change, mp_b bucket change, or after any host-side row mutation
        (``_pages_dirty``).  Steady-state decode therefore re-uses resident
        ``jax.Array``s — ``jnp.asarray`` in the runner is a no-op — instead
        of ~10 host->device uploads per step."""
        ds = self._dstate
        S = self.sched.max_batch_size  # runner's garbage penalty-state row
        # placement-aware upload: mesh-replicated commit under tp>1 (the
        # sharded jits' in_shardings match exactly — no per-launch reshard),
        # plain jnp.asarray on single-device engines
        up = self.runner.upload
        if ds.lane_sig != sig:
            temps = np.zeros(B, np.float32)
            topks = np.full(B, -1, np.int32)
            topps = np.ones(B, np.float32)
            minps = np.zeros(B, np.float32)
            slot_idx = np.full(B, S, np.int32)
            freqs = np.zeros(B, np.float32)
            pres = np.zeros(B, np.float32)
            reps = np.ones(B, np.float32)
            lora_idx = np.zeros(B, np.int32) if use_lora else None
            rope_delta = np.zeros(B, np.int32) if use_mrope else None
            for idx, (slot, req) in enumerate(active):
                sp = req.sampling
                temps[idx] = sp.temperature
                topks[idx] = sp.top_k
                topps[idx] = sp.top_p
                minps[idx] = sp.min_p
                if use_pen:
                    slot_idx[idx] = slot
                    if sp.has_penalties:
                        freqs[idx] = sp.frequency_penalty
                        pres[idx] = sp.presence_penalty
                        reps[idx] = sp.repetition_penalty
                if use_mrope:
                    rope_delta[idx] = req.mrope_delta
                if use_lora:
                    lora_idx[idx] = req.lora_idx
            ds.temps = up(temps)
            ds.topks = up(topks)
            ds.topps = up(topps)
            ds.minps = up(minps)
            if use_pen:
                ds.slot_idx = up(slot_idx)
                ds.freqs = up(freqs)
                ds.pres = up(pres)
                ds.reps = up(reps)
            ds.lora_idx = up(lora_idx) if use_lora else None
            ds.rope_delta = up(rope_delta) if use_mrope else None
            if self.state_pool is not None:
                state_slots = np.zeros(B, np.int32)
                for idx, (_slot, req) in enumerate(active):
                    state_slots[idx] = req.state_slot
                ds.state_slots = up(state_slots)
            if stop_e > 0:
                # megastep device stop state: one upload per composition.
                # stop_ids [B, E] (-1 padded; tokens are always >= 0 so the
                # pad never matches), limits [B] = absolute total-length cap,
                # live [B] marks real lanes (padded rows start "done")
                eos_ids = tuple(self.config.model.eos_token_ids)
                stop_ids = np.full((B, stop_e), -1, np.int32)
                limits = np.full(B, 1, np.int32)
                live = np.zeros(B, bool)
                for idx, (_slot, req) in enumerate(active):
                    sp = req.sampling
                    ids = list(sp.stop_token_ids)
                    if not sp.ignore_eos:
                        ids.extend(eos_ids)
                    stop_ids[idx, : len(ids)] = ids
                    limits[idx] = min(
                        req.prompt_len + sp.max_new_tokens,
                        self.sched.max_seq_len,
                    )
                    live[idx] = True
                ds.stop_ids = up(stop_ids)
                ds.limits = up(limits)
                ds.live = up(live)
            else:
                ds.stop_ids = ds.limits = ds.live = None
            ds.lane_sig = sig
            ds.pt_sig = None
        if use_pen:
            # runner-side counts rows re-derive lazily (admission, preemption
            # readmit, discarded-lookahead rollback) regardless of sig reuse
            for slot, req in active:
                if req.sampling.has_penalties and not req.penalty_synced:
                    self.runner.sync_slot_penalty_state(
                        slot, req.prompt_ids, req.output_ids
                    )
                    req.penalty_synced = True
        pt_sig = (sig, mp_b)
        if ds.pt_sig != pt_sig or self._pages_dirty:
            page_tables = np.zeros((B, mp_b), np.int32)
            for idx, (slot, _req) in enumerate(active):
                page_tables[idx] = self.page_tables[slot][:mp_b]
            ds.page_tables = up(page_tables)
            ds.pt_sig = pt_sig
            self._pages_dirty = False
        return ds

    def _headroom_pages(self) -> int:
        """Pages a decode launch may count on without preempting anyone: the
        free pool and the radix cache's pages no live request holds.  Such a
        page is freed by the next column that needs one whatever the horizon,
        and never by a preemption, so counting it moves no stream; with the
        free pool alone a frame runs one or two columns as soon as finished
        prompts have filled the pool, and every frame's end costs the whole
        batch (``PERF.md``, Findings: PR 34 read 3,340 tokens/s before the
        pool filled and 1,200 after in ``reason``; PR 49, which took the
        Llama path's exception away, a third of ``eval``'s launches cut
        short and 2.3 % of its tokens).  (A model with recurrent layers pins
        no cached page at all: no match is honoured there without the state
        at its end.)"""
        free = self.pool.free_count
        if self.radix is not None:
            free += self.radix.num_unpinned_pages
        return free

    def _state_kw(self, ds: DecodeState, chain=None) -> dict:
        """A recurrent model's keywords of a decode launch: every lane's
        state slot and, for a lookahead, the ``clean`` of the frame it is
        chained on."""
        if self.state_pool is None:
            return {}
        if self._window_slots:
            return {"state_slots": ds.state_slots}
        return {"state_slots": ds.state_slots, "chain": chain}

    def _reach(self, req: EngineRequest, seq_len: int, k: int) -> int:
        """Tokens ``req`` may hold after ``k`` more columns from ``seq_len``:
        a column a token, capped by the table.  A verify column gives up to
        ``_tpc``, but never past the request's own limit (the device holds a
        lane to it), so a frame asks for no page, and no wider table, than
        the one-row schedule asks for by the request's end."""
        most = self.sched.max_seq_len
        if self._tpc == 1:
            return min(seq_len + k, most)
        limit = req.prompt_len + req.sampling.max_new_tokens
        return min(seq_len + k * self._tpc, max(limit, seq_len + k), most)

    def _pages_needed(self, active: list, k: int) -> int:
        """Pages the lanes of ``active`` lack for ``k`` more tokens each."""
        need = 0
        for _, r in active:
            limit = self._reach(r, r.seq_len, k)
            have = len(r.shared_pages) + len(r.owned_pages)
            need += max(0, math.ceil(limit / self.ps) - have)
        return need

    def _pick_horizon(self, active: list, owed: list = ()) -> tuple[int, int]:
        """Choose this launch's decode horizon K and the compiled loop width
        ``max_steps``; returns ``(K, max_steps)`` with ``K <= max_steps``.
        ``owed`` names the requests whose first token is sampled and not
        yet accepted (``_launch_behind_prefill``): they count as holding it.

        Forced K=1 (``max_steps`` 1 too — these batches compile their own
        lean trace, mirroring the overlap pipeline's sync-forcing paths):

        - grammar-constrained lanes: the vocab mask is host-derived per
          token, so the next device call depends on last step's host result;
        - stop-string lanes: matches are found at the ENGINE layer after
          detokenization — the device done mask cannot see them, and a
          mid-horizon match would roll back emitted text.  Conservative by
          design: any lane with stop strings forces K=1 (the "near-window"
          refinement would need per-token detokenization to bound).

        (Under speculative mode this governs the NO-DRAFT steps and the
        rest batch: when nothing proposes, the whole batch rides the full
        horizon here — speculation itself budgets its depth in
        ``_pick_spec_depth``, the other half of the same budget.)

        Pending admission work — a non-empty waiting queue or a resumable
        ``PREFILLING`` slot — ALSO forces K=1, for byte-parity rather than
        merely cadence: the K=1 schedule runs a prefill phase between every
        two decode steps, so an admission (or a final resumable chunk) can
        fold a key and join the decode batch between any two columns.  A
        horizon spanning that point would compute its later columns with
        yesterday's batch composition — tokens the single-step schedule
        never produces.  (This is the megastep analogue of PR 4's "prefill
        budget runs every step" rule; it is what lets the K-sweep parity
        harness hold through chunked-prefill admissions mid-stream.)  These
        batches keep the wide compiled trace (K=1 rides the dynamic loop
        bound), so admission bursts don't retrace.  The rule samples the
        queue at LAUNCH time, so a request submitted while a K-column frame
        is already in flight waits up to K decode columns before its first
        prefill chunk can run, and no longer: the step that fetches that
        frame lets the submission in ahead of its prefill phase
        (``_admit``), where it used to find the next frame launched as well.
        Shorter frames while a slot stands free were measured and lose: a
        frame's end costs the whole batch about 2 ms on the device and, where
        no lookahead is out, the host's 2-3 ms beside it, more than the one
        lane's wait they save (``PERF.md``, Findings, PR 49: ``eval`` -6 % at
        two columns a frame, -2 % at four).

        Otherwise the static path uses ``decode_horizon`` as-is, and the
        adaptive controller (``adaptive_horizon``) starts from the cap and
        halves K down by observed pressure: the finish-gap EMA (size K so
        most horizons complete without a trim), page headroom (growing
        every lane K tokens must fit free pages — never force an eviction
        cascade just to run a bigger horizon), and the smallest remaining
        per-lane token budget (a length finish is imminent; the early exit
        makes overshoot free, but a tight K keeps the chained lookahead
        launchable)."""
        sched = self.sched
        cap = sched.horizon_cap
        forced = any(
            r.token_filter is not None or r.sampling.stop
            for _, r in active
        )
        if forced or cap <= 1:
            self._picked_reason = "forced_lane" if forced else "cap"
            return 1, 1
        if self.waiting or any(
            r is not None and r.status is RequestStatus.PREFILLING
            for r in self.slots
        ):
            self._picked_reason = "pending_admission"
            return 1, cap
        if sched.adaptive_horizon:
            k = cap
            ema = self._finish_gap_ema
            while k > 1 and ema > 0.0 and k > ema:
                k //= 2
            late = {id(r) for r in owed}
            rem = min(
                min(
                    r.sampling.max_new_tokens - len(r.output_ids),
                    self.sched.max_seq_len - r.total_len,
                ) - (id(r) in late)
                for _, r in active
            )
            k = max(1, min(k, rem))
            self._picked_reason = "adaptive" if k < cap else "full"
        else:
            k = min(max(sched.decode_horizon, 1), cap)
            self._picked_reason = "full"
        # page-headroom clamp applies to the STATIC path too (parity, not
        # just politeness): growing every lane K tokens must fit the free
        # pool, else _ensure_seq_capacity would evict/preempt for a horizon
        # the K=1 schedule never asks for — and a preemption refolds the
        # victim's keys, diverging its stream at temperature > 0
        while k > 1 and self._pages_needed(active, k) > self._headroom_pages():
            k //= 2
            self._picked_reason = "page_headroom"
        return k, cap

    def _count_decode_launch(self) -> None:
        """A decode megastep was enqueued at the K ``_pick_horizon`` just
        chose: its reason goes on the step record, into
        ``loads()["decode_launches"]`` and
        ``smg_engine_decode_launches_total{horizon_reason}``."""
        reason = self._step_horizon_reason = self._picked_reason
        self.num_decode_launches[reason] += 1
        if self.metrics is not None:
            self.metrics.decode_launches.labels(horizon_reason=reason).inc()

    def _stop_id_width(self, active: list) -> int:
        """Power-of-two width (>= 1) of the device stop-token id set: EOS
        ids (unless ignore_eos) + per-request stop_token_ids, maxed over the
        batch.  Part of the lane signature — a composition whose width
        changes re-uploads the [B, E] id table (and compiles that E once)."""
        eos = len(self.config.model.eos_token_ids)
        n = 1
        for _, r in active:
            sp = r.sampling
            ids = (0 if sp.ignore_eos else eos) + len(sp.stop_token_ids)
            n = max(n, ids)
        e = 1
        while e < n:
            e *= 2
        return e

    @spanned("smg.step.launch", _launch_attrs)
    def _launch_frame(
        self, active: list, first: tuple | None = None
    ) -> InFlightFrame | None:
        """Plan + dispatch one decode megastep for ``active`` slots; returns
        the in-flight frame (results unmaterialized) or None when capacity
        pressure evicted every candidate.  ``first`` is a grouped prefill
        whose first tokens are still on the device
        (``_launch_behind_prefill``, which has seen to it that nobody is
        preempted here): its members' input tokens are taken from there."""
        owed = first[0] if first is not None else ()
        FAULTS.fire(
            "engine.decode_step", rids=",".join(r.rid for _i, r in active)
        )
        use_mask = any(r.token_filter is not None for _, r in active)
        use_pen = any(r.sampling.has_penalties for _, r in active)
        use_lora = any(r.lora_idx for _, r in active)
        use_mrope = any(r.mrope_delta for _, r in active)
        horizon, max_steps = self._pick_horizon(active, owed=owed)
        # ensure pages exist for the whole horizon's KV writes; may preempt.
        # _ensure_seq_capacity refuses requests already evicted as a PEER's
        # preemption victim earlier in this pass (incl. by the spec leg).
        survivors = []
        for i, req in active:
            if self._ensure_seq_capacity(req, self._reach(req, req.seq_len, horizon) - req.seq_len):
                survivors.append((i, req))
        active = [(i, r) for i, r in survivors if self.slots[i] is r]
        if not active:
            return None

        B_real = len(active)
        B = self.sched.decode_bucket(B_real)
        V = self.runner.model_cfg.vocab_size
        # Trim the page table to the pages LIVE this horizon (bucketed so jit
        # variants stay bounded): the XLA decode attention gathers
        # B*mp*page_size tokens of KV per layer, so rows sized to max_seq_len
        # make every decode pay for the worst-case context.  A batch at mean
        # context 256 of max 8192 reads 32x less with trimmed rows.
        mp_b = self._mp_bucket(max(
            math.ceil(self._reach(r, r.seq_len, horizon) / self.ps) for _, r in active
        ))
        E = self._stop_id_width(active) if max_steps > 1 else 0
        sig = (
            B, use_pen, use_lora, use_mrope, max_steps, E,
            tuple((i, r.sched_serial) for i, r in active),
        )
        ds = self._refresh_decode_state(
            active, B, mp_b, use_pen, use_lora, use_mrope, sig, stop_e=E
        )
        tokens = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        mask_arr = np.ones((B, V), bool) if use_mask else None
        member = {id(r): i for i, r in enumerate(owed)}
        owner = np.full(B, -1, np.int32)  # the lane's place in ``first``'s group
        for idx, (slot, req) in enumerate(active):
            if id(req) in member:
                owner[idx] = member[id(req)]
            else:
                tokens[idx] = req.output_ids[-1]
            positions[idx] = req.seq_len
            if use_mask and req.token_filter is not None:
                mask_arr[idx] = self._mask_for(req)
        # padded rows: positions land beyond mp_b*ps so writes hit the garbage page
        for idx in range(B_real, B):
            positions[idx] = mp_b * self.ps

        mark = self.runner.rng_mark()
        with self.account.span("smg.step.launch.dispatch"):
            if first is not None:
                tokens = self.runner.chain_first_tokens(tokens, owner, first[1])
            toks, lps, steps_run = self.runner.decode_multi_async(
                tokens, positions, ds.page_tables,
                ds.temps, ds.topks, ds.topps, ds.minps, horizon,
                max_steps=max_steps,
                stop_state=(ds.stop_ids, ds.limits, ds.live)
                if max_steps > 1 else None,
                pen=(ds.slot_idx, ds.freqs, ds.pres, ds.reps) if use_pen else None,
                mask=mask_arr,
                lora_idx=ds.lora_idx if use_lora else None,
                rope_delta=ds.rope_delta if use_mrope else None,
                **self._state_kw(ds),
            )
        self._count_decode_launch()
        return InFlightFrame(
            serial=self.account.launched,
            clean=self.runner.frame_clean,
            routed=self.runner.frame_counts,
            tail=self.runner.frame_tail,
            lanes=[(i, r, r.seq_len) for i, r in active],
            toks=toks, lps=lps, horizon=horizon, B=B, B_real=B_real,
            mp_b=mp_b, positions=positions, lane_sig=sig,
            use_pen=use_pen, use_lora=use_lora, use_mrope=use_mrope,
            rng_mark=mark, lookahead=False,
            folds=horizon, steps_run=steps_run,
        )

    # ---- speculative decoding (two-tier drafting + fused batched verify) ----
    #
    # The production spec path: eligible lanes draft host-side — the default
    # zero-cost tier matches the request's own recent tokens against its
    # per-lane incremental n-gram index ("prompt lookup decoding"); an
    # optional small draft MODEL (engine/draft.py) replaces it when
    # configured — and ALL eligible lanes verify in ONE fused device block
    # (``runner.decode_spec_async``): K drafted positions scored in a single
    # forward, acceptance on device (greedy chain at temp 0, rejection
    # sampling at temp > 0), rejected columns' KV masked to the garbage
    # page.  With overlap on, the verify frame stays IN FLIGHT across steps
    # (launched at the end of step N, consumed at the top of step N+1), so
    # drafting/detokenize/callbacks hide behind the device pass; the frame
    # rides the InFlightFrame staleness/rewind machinery, so stop-string
    # rollback, abort, deadline expiry, and quarantine discard it and rewind
    # its sampling-key fold exactly like a discarded lookahead.  Steps where
    # nothing drafts run the plain megastep at the controller's FULL horizon
    # — speculation no longer forces sync + K=1.

    def _partition_spec(self, active: list) -> tuple[list, list]:
        """Split decode-eligible lanes into (spec-eligible, rest).

        Eligible = unconstrained, penalty-free, no logprobs, no LoRA (the
        verify scores BASE-model distributions only), and no stop STRINGS
        (engine-layer matches would roll back mid-block emissions — stop
        string lanes keep the K=1 megastep path, same rule as the horizon
        matrix).  M-RoPE lanes are eligible (text rope ids + delta).
        Membership is static per request, which keeps the in-flight spec
        frame's staleness check meaningful.  pp engines fall back entirely
        (the fused block doesn't compose with the layer-sharded scan)."""
        if self.runner.use_pp or not hasattr(
            self.runner.module, "forward_verify_block"
        ):
            return [], active
        eligible, rest = [], []
        for slot, req in active:
            sp = req.sampling
            ok = (
                req.token_filter is None
                and not sp.has_penalties
                and not sp.logprobs
                and not req.lora_idx
                and not sp.stop
                and bool(req.output_ids)
            )
            (eligible if ok else rest).append((slot, req))
        return eligible, rest

    def _spec_tier(self) -> str:
        """Resolve the drafting tier: the draft model serves when installed
        (unless the config pins "ngram"); prompt-lookup n-grams otherwise."""
        tier = getattr(self.sched, "speculative_tier", "auto")
        if self.draft is not None and tier in ("auto", "draft"):
            return "draft"
        return "ngram"

    def _pick_spec_depth(self, eligible: list) -> int:
        """Budget this launch's draft depth — the speculation half of the
        horizon controller's budget (``_pick_horizon`` still owns the
        multi-step-decode half for no-draft steps and the rest batch):

        - cap at ``spec_max_draft`` (the compiled block width);
        - adaptive mode tracks the acceptance-length EMA and drafts one past
          it (deep drafts on a cold context waste verify columns);
        - page headroom clamps exactly like the megastep's K clamp: growing
          every eligible lane depth+1 tokens must fit the free pool, never
          force an eviction cascade for speculation."""
        sched = self.sched
        d = max(1, sched.spec_max_draft)
        if sched.adaptive_horizon and self._spec_accept_ema > 0.0:
            d = min(d, int(self._spec_accept_ema) + 2)
        ps = self.ps
        while d > 1:
            need = 0
            for _, r in eligible:
                limit = min(r.seq_len + d + 1, sched.max_seq_len)
                have = len(r.shared_pages) + len(r.owned_pages)
                need += max(0, math.ceil(limit / ps) - have)
            if need <= self.pool.free_count:
                break
            d //= 2
        return d

    def _collect_drafts(self, eligible: list) -> dict:
        """Per-lane draft proposals: {slot: (proposals, tier)}.  The draft
        -model tier ensures KV capacity BEFORE proposing (draft KV writes
        ride the same page tables); the n-gram tier is pure host lookup.
        Lanes in acceptance back-off (``spec_cold``) or out of room propose
        nothing — ``_spec_phase`` routes them to the rest megastep at the
        controller's full horizon (the back-off's whole point: a lane whose
        drafts keep missing must not lose the multi-token decode path)."""
        from smg_tpu.engine.speculative import SpecConfig, propose_ngram

        cfg = SpecConfig(
            enabled=True,
            max_draft=self.sched.spec_max_draft,
            ngram_max=self.sched.spec_ngram_max,
            ngram_min=self.sched.spec_ngram_min,
        )
        depth = self._pick_spec_depth(eligible)
        tier = self._spec_tier()
        out: dict = {}
        for slot, req in eligible:
            if self.slots[slot] is not req:
                continue  # evicted as a peer's preemption victim
            room = min(self.sched.max_seq_len, self.mp * self.ps)
            k = min(depth, max(0, room - req.seq_len - 1))
            if k <= 0 or req.spec_cold >= 3:
                out[slot] = ([], None)
                continue
            if tier == "draft":
                # capacity FIRST: the draft writes KV through the same page
                # table, so pages must exist before ensure_context/propose
                if not self._ensure_seq_capacity(req, k + 1):
                    continue  # preempted
                if self.slots[slot] is not req:
                    continue
                pt_full = self.page_tables[slot]
                self.draft.ensure_context(req, pt_full)
                proposals = self.draft.propose(
                    req.output_ids[-1], req.seq_len, pt_full, k
                )
            else:
                proposals = propose_ngram(
                    req.all_token_ids, cfg,
                    index=req.spec_index
                    if req.spec_index is not None
                    else self._new_spec_index(req, cfg),
                )[:k]
            out[slot] = (proposals, tier if proposals else None)
        return out

    def _spec_phase(self, outputs: list[StepOutput], pipelined: bool) -> None:
        """The decode phase under speculative mode, SAME ordering in both
        schedules (this is what keeps overlap-on/off spec streams
        byte-identical): draft (capacity ensures may preempt), rest-lane
        megastep, then the batched verify launch — left in flight when
        ``pipelined``, consumed in-step otherwise.  When no lane drafted
        anything, the whole batch takes the plain megastep at the
        controller's full horizon instead."""
        active = self._decode_active()
        if not active:
            return
        eligible, rest = self._partition_spec(active)
        drafts = self._collect_drafts(eligible) if eligible else {}
        # only lanes that actually PROPOSED ride the verify block; everyone
        # else — ineligible lanes, acceptance back-off (spec_cold), nothing
        # to propose, out of room — takes the rest megastep at the
        # controller's FULL horizon (a draft_n=0 spec row would cap them at
        # 1 token/step, inverting the back-off's purpose)
        drafting = [
            (i, r) for i, r in eligible if drafts.get(i, ([], None))[0]
        ]
        rest += [
            (i, r) for i, r in eligible if not drafts.get(i, ([], None))[0]
        ]
        # admission-serial order: lane order drives per-row sampling keys,
        # and serial order is the schedule-invariant one (see _decode_active)
        rest.sort(key=lambda t: t[1].sched_serial)
        rest = [
            (i, r) for i, r in rest
            if self.slots[i] is r and r.status is RequestStatus.RUNNING
        ]
        if rest:
            self._decode_batch(rest, outputs)
        drafting = [
            (i, r) for i, r in drafting
            if self.slots[i] is r and r.status is RequestStatus.RUNNING
            and not r.is_finished
        ]
        if not drafting:
            return
        frame = self._launch_spec_frame(drafting, drafts, pipelined)
        if frame is None:
            return
        if pipelined:
            self.inflight = frame
        else:
            try:
                self._consume_spec_frame(frame, outputs)
            except Exception:
                # stash: the quarantine handler's drop_inflight rewinds the
                # launch fold before any retry refolds
                self.inflight = frame
                raise

    @spanned("smg.step.launch", _launch_attrs)
    def _launch_spec_frame(
        self, drafting: list, drafts: dict, pipelined: bool
    ) -> InFlightFrame | None:
        """Dispatch ONE fused verify block for the lanes that proposed.  The
        trace is keyed only on (B bucket, mp bucket, W): per-lane draft
        counts ride device scalars and padded rows are inert, so the
        compiled program stays stable while per-lane drafting comes and
        goes."""
        FAULTS.fire(
            "engine.decode_step", rids=",".join(r.rid for _s, r in drafting)
        )
        # ensure pages for every lane's drafts + bonus FIRST, then re-filter:
        # a later lane's ensure may preempt an earlier one already vetted
        # (same two-phase rule as _launch_frame — a preempted lane must
        # never ride the block, its page-table row is already reassigned)
        survivors = []
        for slot, req in drafting:
            props, tier = drafts.get(slot, ([], None))
            if self._ensure_seq_capacity(req, len(props) + 1):
                survivors.append((slot, req, props, tier))
        lanes, props_rows, tier_rows = [], [], []
        for slot, req, props, tier in survivors:
            if self.slots[slot] is not req or req.status is not RequestStatus.RUNNING:
                continue  # evicted as a peer's preemption victim
            lanes.append((slot, req))
            props_rows.append(props)
            tier_rows.append(tier)
        if not lanes:
            return None
        B_real = len(lanes)
        B = self.sched.decode_bucket(B_real)
        W = max(2, self.sched.spec_max_draft + 1)  # compiled block width
        mp_b = self._mp_bucket(max(
            math.ceil(
                min(r.seq_len + len(p) + 1, self.sched.max_seq_len) / self.ps
            )
            for (_s, r), p in zip(lanes, props_rows)
        ))
        tokens = np.zeros((B, W), np.int32)
        draft_n = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        temps = np.zeros(B, np.float32)
        topks = np.full(B, -1, np.int32)
        topps = np.ones(B, np.float32)
        minps = np.zeros(B, np.float32)
        page_tables = np.zeros((B, mp_b), np.int32)
        use_mrope = any(r.mrope_delta for _s, r in lanes)
        rope_delta = np.zeros(B, np.int32) if use_mrope else None
        for idx, ((slot, req), props) in enumerate(zip(lanes, props_rows)):
            sp = req.sampling
            tokens[idx, 0] = req.output_ids[-1]
            if props:
                tokens[idx, 1:1 + len(props)] = props
            draft_n[idx] = len(props)
            positions[idx] = req.seq_len
            temps[idx] = sp.temperature
            topks[idx] = sp.top_k
            topps[idx] = sp.top_p
            minps[idx] = sp.min_p
            page_tables[idx] = self.page_tables[slot][:mp_b]
            if use_mrope:
                rope_delta[idx] = req.mrope_delta
        for idx in range(B_real, B):
            # padded rows: positions beyond the table send every KV write to
            # the garbage page, and the all-zero page-table row is inert
            positions[idx] = mp_b * self.ps
        mark = self.runner.rng_mark()
        with self.account.span("smg.step.launch.dispatch"):
            emitted, n_emit, lps = self.runner.decode_spec_async(
                tokens, draft_n, positions, page_tables,
                temps, topks, topps, minps,
                rope_delta=rope_delta,
            )
        return InFlightFrame(
            serial=self.account.launched,
            lanes=[(s, r, r.seq_len) for s, r in lanes],
            toks=emitted, lps=lps, horizon=W, B=B, B_real=B_real,
            mp_b=mp_b, rng_mark=mark, lookahead=pipelined, folds=1,
            spec=True, n_emit=n_emit,
            draft_ns=[len(p) for p in props_rows], tiers=tier_rows,
        )

    def _spec_frame_stale(self, frame: InFlightFrame) -> bool:
        """Staleness for an in-flight SPEC frame: PER-LANE checks only.
        Unlike the megastep lookahead, membership cannot GROW between launch
        and consume — ``_step_spec`` consumes the frame BEFORE the step's
        admissions/promotions and before the next round of drafting — so the
        hazards are lanes that vanished or moved: deadline expiry, abort,
        quarantine, preemption, stop-string rollback.  Any such lane
        discards the frame (and rewinds its fold) exactly like a discarded
        lookahead; rest-batch lanes never invalidate the verify block."""
        if not frame.spec:
            return True
        for slot, req, expected in frame.lanes:
            if (
                self.slots[slot] is not req
                or req.status is not RequestStatus.RUNNING
                or req.is_finished
                or req.seq_len != expected
            ):
                return True
        return False

    @spanned("smg.step.consume")
    def _consume_spec_frame(
        self, frame: InFlightFrame, outputs: list[StepOutput]
    ) -> None:
        """Deferred fetch + acceptance bookkeeping for one verify block.
        Unlike the megastep's batch-wide trim, acceptance is PER LANE: each
        lane's emitted run is its own accepted drafts + bonus/correction,
        and ``_accept_tokens`` truncates at that lane's own finish (EOS /
        stop token / length inside an accepted run) — a finish in lane A
        never discards lane B's accepted tokens, because no cross-lane
        recomposition happens inside a block."""
        FAULTS.fire(
            "engine.device_fetch",
            rids=",".join(r.rid for _s, r, _e in frame.lanes),
        )
        with self.account.span("smg.step.consume.fetch", proves=frame.serial):
            toks, lps, n_emit = jax.device_get(
                (frame.toks, frame.lps, frame.n_emit)
            )
        if frame.lookahead:
            self.num_lookahead_kept += 1
        m = self.metrics
        for idx, (_slot, req, _expected) in enumerate(frame.lanes):
            # smglint: disable-next=HOTSYNC n_emit was device_get-fetched above
            n = int(n_emit[idx])
            drafted = frame.draft_ns[idx]
            accepted = max(0, min(n - 1, drafted))
            if drafted:
                self.num_spec_drafted += drafted
                self.num_spec_accepted += accepted
                self._step_spec_drafted += drafted
                self._step_spec_accepted += accepted
                # rejected verify columns were computed but never emitted
                self.num_wasted_decode_tokens += drafted - accepted
                # acceptance back-off + the depth controller's EMA
                req.spec_cold = 0 if accepted else req.spec_cold + 1
                self._spec_accept_ema = (
                    float(accepted) if self._spec_accept_ema == 0.0
                    else 0.8 * self._spec_accept_ema + 0.2 * accepted
                )
                if m is not None:
                    m.observe_spec(frame.tiers[idx] or "ngram",
                                   drafted, accepted)
            before_out = len(req.output_ids)
            self._accept_tokens(
                req, [int(t) for t in toks[idx][:n]],
                [float(x) for x in lps[idx][:n]], outputs,
                advance_seq=True,
            )
            kept = len(req.output_ids) - before_out
            self.num_decode_tokens += kept
            # columns emitted by the block but truncated at a finish inside
            # the accepted run were computed-and-dropped: waste, not output
            self.num_wasted_decode_tokens += n - kept
            if drafted and self.draft is not None and not req.is_finished:
                # draft KV coverage: the tier fed [y0, drafts...] at the
                # entry positions, so coverage extends over y0 plus the
                # accepted drafts — capped at the fed range and the
                # post-accept seq_len (a finish inside the run truncates).
                # Wrong coverage only costs acceptance rate, never
                # correctness (the target verify gates every token).
                req.draft_len = min(
                    _expected + 1 + accepted, _expected + drafted, req.seq_len
                )

    def _step_spec(
        self, outputs: list[StepOutput]
    ) -> str | None:
        """One pipelined speculative iteration; returns its outcome.  Mirrors ``_step_overlap``'s shape: consume the in-flight
        verify frame first (admission must see slots/pages its finishes
        freed), run the prefill phase, then the spec decode phase leaves the
        next verify block in flight.  Fold order — prefill, rest-megastep,
        spec launch — is identical to the synchronous schedule's, so streams
        are byte-identical to ``overlap_schedule off``."""
        frame = self.inflight
        self.inflight = None
        outcome = None
        if frame is not None:
            if self._spec_frame_stale(frame):
                self._discard_frame(frame)
                outcome = "discarded" if frame.lookahead else None
            else:
                try:
                    self._consume_spec_frame(frame, outputs)
                except Exception:
                    # stash so the step-level handler's drop_inflight rewinds
                    # the launch fold before the blame/retry refolds
                    self.inflight = frame
                    raise
                outcome = "kept"
        self._admit(outputs)
        self._spec_phase(outputs, pipelined=True)
        return outcome

    def _new_spec_index(self, req: EngineRequest, cfg) -> "object":
        from smg_tpu.engine.speculative import NgramIndex

        req.spec_index = NgramIndex(cfg.ngram_min, cfg.ngram_max)
        return req.spec_index

    def _ensure_seq_capacity(self, req: EngineRequest, n_tokens: int = 1) -> bool:
        """Make sure pages exist for positions seq_len..seq_len+n_tokens-1.
        Returns False if the request had to be preempted."""
        if req.slot is None or req.status is RequestStatus.PREEMPTED:
            # already evicted (e.g. as a peer's preemption victim this pass):
            # page_tables[None] would numpy-broadcast over EVERY slot's row,
            # corrupting all resident requests' page tables
            return False
        limit = min(req.seq_len + n_tokens, self.sched.max_seq_len)
        needed = math.ceil(limit / self.ps)
        have = len(req.shared_pages) + len(req.owned_pages)
        while needed > have:
            if not self._ensure_free_pages(1):
                victim = self._pick_preemption_victim(req)
                if victim is None:
                    # nothing else to preempt: preempt this request itself
                    self._preempt(req)
                    return False
                self._preempt(victim)
                if not self._ensure_free_pages(1):
                    self._preempt(req)
                    return False
            page = self.pool.alloc(1)[0]
            req.owned_pages.append(page)
            self.page_tables[req.slot][have] = page
            self._pages_dirty = True
            have += 1
        return True

    def _pick_preemption_victim(self, requester: EngineRequest) -> EngineRequest | None:
        candidates = [
            r for r in self.slots if r is not None and r is not requester
        ]
        if not candidates:
            return None
        # youngest first (FCFS fairness: latest arrival pays)
        return max(candidates, key=lambda r: r.arrival_time)

    def _preempt(self, req: EngineRequest) -> None:
        logger.warning("preempting request %s (out of KV pages)", req.rid)
        self.num_preemptions += 1
        if self.flight is not None:
            self.flight.event(
                req.rid, "preempt", at_tokens=req.seq_len,
                status=req.status.value,
            )
        slot = req.slot
        self.slots[slot] = None
        self.page_tables[slot][:] = 0
        self._pages_dirty = True
        req.slot = None
        self._free_state_slot(req)
        if (
            req.status is RequestStatus.PREFILLING
            and self.radix is not None
            and req.prefill_pos >= self.ps
        ):
            # Mid-prefill victim: bank the chunks computed so far in the
            # radix cache instead of discarding them, so readmission RESUMES
            # from the cursor via a prefix hit rather than recomputing the
            # whole prompt.  Best-effort by design — the banked pages are
            # evictable like any cached prefix, so a pool starved enough to
            # reclaim them degrades to a restart, never to a deadlock.
            tokens = req.all_token_ids[: req.prefill_pos]
            full_pages = len(tokens) // self.ps
            all_pages = req.shared_pages + req.owned_pages
            n_shared = len(req.shared_pages)
            to_free: list[int] = []
            dupes = self.radix.insert(
                tokens, all_pages[:full_pages],
                extra_keys=self._mm_extra_keys(req, len(tokens)),
            )
            for idx, page in dupes:
                if idx >= n_shared:
                    to_free.append(page)
            to_free.extend(all_pages[full_pages:])
            if to_free:
                self.pool.free(to_free)
        else:
            self.pool.free(req.owned_pages)
        req.owned_pages = []
        req.shared_pages = []
        if req.radix_node is not None:
            self.radix.unlock(req.radix_node)
            req.radix_node = None
        req.seq_len = 0
        req.prefill_pos = 0
        req.cached_tokens = 0
        req.penalty_synced = False  # re-derive counts on readmission
        req.draft_len = 0  # draft cache rows are gone with the pages
        req.status = RequestStatus.PREEMPTED
        self.waiting.appendleft(req)

    # ---- finish bookkeeping ----

    def _accept_tokens(
        self,
        req: EngineRequest,
        toks: list[int],
        lps: list[float],
        outputs: list[StepOutput],
        advance_seq: bool,
    ) -> None:
        """Accept sampled tokens in order until a stop condition; overshoot
        beyond the stop (decode horizon) is discarded — its KV writes landed
        in owned pages past seq_len, which never enter the radix cache."""
        sp = req.sampling
        had_output = bool(req.output_ids)
        accepted: list[int] = []
        accepted_lps: list[float] = []
        finish: FinishInfo | None = None
        for tok, lp in zip(toks, lps):
            if advance_seq:
                req.seq_len += 1
            req.output_ids.append(tok)
            req.logprobs.append(lp)
            accepted.append(tok)
            accepted_lps.append(lp)
            finish = self._token_finish(
                sp, tok, len(req.output_ids), req.total_len
            )
            if finish is not None:
                break
        if self.flight is not None and accepted:
            # TTFT/ITL sampling rides acceptance (host timestamps only); the
            # call precedes _release so token ordering beats the finish event
            self.flight.on_tokens(req.rid, len(accepted), first=not had_output)
        if finish is not None:
            self._release(req, finish)
        outputs.append(
            StepOutput(req, accepted, finish is not None, finish,
                       logprobs=accepted_lps)
        )

    # ---- PD disaggregation (SURVEY.md §2.5: PrefillDecode routing mode) ----

    def prefill_only(
        self, prompt_ids: list[int], sampling, token_filter=None
    ) -> tuple[int, list[int], int]:
        """Prefill a prompt and keep its pages allocated (no decode slot).
        Returns (first_token, pages, seq_len).  Caller must ``release_pages``.
        Used by the prefill leg of PD disaggregation; ``token_filter`` and
        penalties apply to the first sampled token exactly as in the
        co-located prefill paths."""
        if self.state_pool is not None:
            raise ValueError(self.runner.module.SERVING_LIMITS["kv_transfer"])
        n_pages = math.ceil(len(prompt_ids) / self.ps)
        if not self._ensure_free_pages(n_pages):
            raise RuntimeError("out of KV pages for prefill-only request")
        pages = self.pool.alloc(n_pages)
        row = np.zeros(self.mp, np.int32)
        row[: len(pages)] = pages
        pen = None
        if sampling.has_penalties:
            counts, pmask = self.runner.penalty_state(prompt_ids, [])
            pen = (counts, pmask, sampling.frequency_penalty,
                   sampling.presence_penalty, sampling.repetition_penalty)
        mask = None
        if token_filter is not None:
            mask = token_filter.allowed_mask("")
            if not mask.any():
                mask = mask.copy()
                mask[list(self.config.model.eos_token_ids)] = True
        start = 0
        tok = None
        while start < len(prompt_ids):
            chunk = prompt_ids[start : start + self.sched.max_prefill_tokens]
            tok, _ = self.runner.prefill(
                chunk, prefix_len=start, page_table=row,
                temperature=sampling.temperature, top_k=sampling.top_k,
                top_p=sampling.top_p, min_p=sampling.min_p,
                pen=pen, mask=mask,
            )
            self.num_prefill_tokens += len(chunk)
            start += len(chunk)
        return tok, pages, len(prompt_ids)

    def release_pages(self, pages: list[int]) -> None:
        self.pool.free(pages)

    def adopt_prefilled(
        self, req: EngineRequest, pages: list[int], first_token: int
    ) -> bool:
        """Adopt a request whose prompt KV was imported (decode leg of PD).
        Pages become owned by the request; returns False when no slot free."""
        if self.state_pool is not None:
            raise ValueError(self.runner.module.SERVING_LIMITS["kv_transfer"])
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        if not free_slots:
            return False
        if req.rid in self.requests:
            raise ValueError(f"duplicate request id {req.rid}")
        self._serial += 1
        req.sched_serial = self._serial  # DecodeState lane signatures key
        # off this; a stale -1 here would alias successive adoptees' params
        self.requests[req.rid] = req
        req.owned_pages = list(pages)
        req.seq_len = req.prompt_len
        req.prefill_pos = req.prompt_len  # prompt KV imported, cursor done
        req.status = RequestStatus.RUNNING
        slot = free_slots[0]
        req.slot = slot
        row = self.page_tables[slot]
        row[:] = 0
        row[: len(pages)] = pages
        self.slots[slot] = req
        self._pages_dirty = True
        if self.flight is not None:
            # PD adoptee: queued+admitted collapse into one adoption instant
            # (its prefill ran on the other leg's worker)
            self.flight.on_queued(
                req.rid, prompt_tokens=req.prompt_len, trace_id=req.trace_id,
                meta=self._flight_meta(req), submit_t=req.submit_t,
            )
            self.flight.event(req.rid, "adopted", slot=slot)
        # first_token is accepted by the caller (stop checks + client emission)
        del first_token
        return True

    def alloc_import_pages(self, n_tokens: int) -> list[int]:
        n_pages = math.ceil(n_tokens / self.ps)
        if not self._ensure_free_pages(n_pages):
            raise RuntimeError("out of KV pages for import")
        return self.pool.alloc(n_pages)

    def finish_request(self, rid: str, reason: str, matched_stop=None) -> None:
        """External finish (e.g. the engine found a stop string)."""
        req = self.requests.get(rid)
        if req is None or req.is_finished or req.slot is None:
            return
        self._release(req, FinishInfo(reason=reason, matched_stop=matched_stop))

    def _free_state_slot(self, req: EngineRequest) -> None:
        """A recurrent model's state slot goes with the sequence's pages."""
        if req.state_slot is not None:
            self.state_pool.free(req.state_slot)
            req.state_slot = None

    def _count_finish(
        self, req: EngineRequest, reason: str, message: str | None = None
    ) -> None:
        if self.metrics is not None:
            self.metrics.on_finish(reason)
        if self.flight is not None:
            # terminal timeline event: moves the request to the finished ring
            self.flight.on_finish(req.rid, reason, message)

    def _release(
        self, req: EngineRequest, finish: FinishInfo, aborted: bool = False
    ) -> None:
        req.finish = finish
        req.status = RequestStatus.ABORTED if aborted else RequestStatus.FINISHED
        self._count_finish(req, finish.reason, finish.message)
        if req.slot is not None:
            self.page_tables[req.slot][:] = 0
            self._pages_dirty = True
            self.slots[req.slot] = None
            req.slot = None
        self._free_state_slot(req)

        # Only tokens whose KV is actually written may enter the radix cache:
        # the final sampled token is never fed back, so its position has no KV
        # (inserting it would poison shared prefixes with a garbage slot).
        tokens = req.all_token_ids[: req.seq_len]
        full_pages = len(tokens) // self.ps
        n_shared = len(req.shared_pages)
        to_free: list[int] = []
        if self.radix is not None and finish.reason != "error":
            all_pages = req.shared_pages + req.owned_pages
            # mm pages insert with their content salts (pages past the
            # prompt get 0 via the key helper's bounds guard)
            dupes = self.radix.insert(
                tokens, all_pages[:full_pages],
                extra_keys=self._mm_extra_keys(req, len(tokens)),
            )
            for idx, page in dupes:
                if idx >= n_shared:
                    to_free.append(page)
            # partial tail page(s) stay ours -> free
            to_free.extend(all_pages[full_pages:])
        else:
            to_free.extend(req.owned_pages)
        if to_free:
            self.pool.free(to_free)
        req.owned_pages = []
        req.shared_pages = []
        if req.radix_node is not None:
            self.radix.unlock(req.radix_node)
            req.radix_node = None
        self.requests.pop(req.rid, None)
