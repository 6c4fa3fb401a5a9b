"""Engine facade: scheduler + detokenization + stop strings + streaming.

The worker-side entry point — what the reference reaches through
``SGLangSchedulerServicer`` → ZMQ → external scheduler (SURVEY.md §3.3) is a
direct in-process call here.  Token-level stops live in the scheduler; string
stops need the tokenizer, so they live at this layer (matching the split in
the reference, where the gateway's StreamingProcessor scans stop strings).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import uuid
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from smg_tpu.analysis.runtime_guards import make_lock
from smg_tpu.engine.config import EngineConfig
from smg_tpu.engine.detokenize import IncrementalDecoder, StopStringChecker
from smg_tpu.engine.events import KvEventPublisher
from smg_tpu.engine.request import EngineRequest, RequestStatus, StepOutput
from smg_tpu.engine.runner import ModelRunner
from smg_tpu.engine.scheduler import Scheduler
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.utils import get_logger

logger = get_logger("engine")

#: the longest a step waits for the submissions it lets in
#: (``Engine._let_submitters_in``): a thread that was blocked on the lock
#: needs a fraction of a millisecond to take it and queue its request; past
#: this the step goes on and the submission is admitted a frame later
SUBMIT_YIELD_S = 0.002


@dataclass
class RequestOutput:
    """One streamed increment for a request (engine-level, post-detok)."""

    rid: str
    new_token_ids: list[int] = field(default_factory=list)
    text_delta: str = ""
    finished: bool = False
    finish_reason: str | None = None
    matched_stop: str | int | None = None
    prompt_tokens: int = 0
    output_tokens: int = 0
    cached_tokens: int = 0
    logprobs: list[float] = field(default_factory=list)


@dataclass
class GenerationResult:
    rid: str
    token_ids: list[int]
    text: str
    finish_reason: str
    matched_stop: str | int | None
    prompt_tokens: int
    output_tokens: int
    cached_tokens: int
    logprobs: list[float]


class Engine:
    def __init__(
        self, config: EngineConfig, tokenizer=None, params=None, devices=None,
        vision_params=None,
    ):
        from smg_tpu.config import validate_engine_config
        from smg_tpu.config.validation import raise_on_errors

        raise_on_errors(validate_engine_config(config), logger=logger)
        self.config = config
        self.tokenizer = tokenizer
        self.events = KvEventPublisher()
        runner_cls = ModelRunner
        if getattr(config.model, "recurrent", False):
            from smg_tpu.engine.recurrent_runner import RecurrentModelRunner

            runner_cls = RecurrentModelRunner
        elif getattr(config.model, "latent_cache", False):
            from smg_tpu.engine.latent_runner import LatentModelRunner

            runner_cls = LatentModelRunner
        elif getattr(config.model, "window_cache", False):
            from smg_tpu.engine.window_runner import SelfDraftingRunner, WindowModelRunner

            # the model's own next-token module drafts inside the decode frame
            runner_cls = (SelfDraftingRunner if config.scheduler.self_drafting(config.model)
                          else WindowModelRunner)
        self.runner = runner_cls(config, params=params, devices=devices)
        # engine-deep metric set (own registry; the gateway additionally
        # registers it into its CollectorRegistry so /metrics is one scrape)
        from smg_tpu.engine.metrics import EngineMetrics

        self.metrics = EngineMetrics(
            window_secs=config.metrics_window_secs,
            device_sample_interval_secs=config.device_metrics_interval_secs,
        )
        self.metrics.set_mesh_devices(self.runner.mesh_devices)
        self._metric_devices: list | None = None  # built lazily, once
        self.scheduler = Scheduler(
            self.runner, config, event_sink=self.events.publish,
            metrics=self.metrics,
        )
        if config.draft_model is not None and self.runner.mesh is None:
            from smg_tpu.engine.draft import DraftRunner

            self.scheduler.draft = DraftRunner(
                config.draft_model,
                num_pages=self.runner.spec.num_pages,
                page_size=self.runner.spec.page_size,
                prefill_bucket=config.scheduler.coarse_prefill_bucket,
                dtype=config.cache.dtype,  # draft cache follows the KV dtype
                seed=config.draft_seed,
                device=self.runner._device,
                max_prefill_tokens=min(
                    config.scheduler.max_prefill_tokens,
                    max(config.scheduler.prefill_token_buckets),
                ),
            )
        # vision tower (VLM): jitted per grid shape, params device-resident.
        # ``vision_params`` comes from the checkpoint loader
        # (models.weights.load_vision_params); random-init is the test path.
        self._vision_params = None
        self._vision_fns: dict[tuple, object] = {}
        if config.model.vision is not None:
            if vision_params is not None:
                import jax

                self._vision_params = jax.device_put(vision_params)
            else:
                import jax

                from smg_tpu.models.vit import init_vision_params

                vkey = jax.random.PRNGKey(config.seed ^ 0x71510)
                # smglint: disable-next=RETRACE one-shot vision-tower init
                self._vision_params = jax.jit(
                    lambda k: init_vision_params(config.model.vision, k)
                )(vkey)
        self._callbacks: dict[str, object] = {}
        self._json_filter = None  # shared TokenFilter (piece table + mask cache)
        self._grammar_filters: dict = {}  # (kind, pattern) -> TokenFilter
        self._lock = make_lock("engine", reentrant=True)
        self._wakeup = threading.Condition(self._lock)
        self._thread: threading.Thread | None = None
        self._stopping = False
        self.start_time = time.monotonic()
        # profiler state under a lock of its own: jax.profiler writes the
        # trace inside stop_trace(), seconds on a chip, and that must never
        # happen under the engine lock (every step and submit would wait)
        self._profile_lock = make_lock("engine.profiler")
        self._profiling = False  # a trace runs, or is being written
        self._profile_stopping = False  # stop_trace() is writing it
        self._profile_steps_left: int | None = None
        self._profile_launches: dict = {}  # program launches when the trace began
        self._profile_dir = ""  # where the running trace goes
        # submit-side lock wait (smg_engine_submit_lock_wait_seconds_total):
        # written under the engine lock, read by loads()
        self.num_submits = 0
        self.submit_lock_wait_s_total = 0.0
        # submissions that wait for the engine lock right now, counted under
        # a lock of its own (they do not hold the engine's yet): the step lets
        # them in before its prefill phase (``_let_submitters_in``)
        self._waiters_lock = make_lock("engine.submit_waiters")
        self._submit_waiters = 0
        self.scheduler.let_submitters_in = self._let_submitters_in
        # failure isolation: step-watchdog state.  ``_last_progress`` is a
        # bare float written by the step thread and read by the watchdog
        # WITHOUT the engine lock — the watchdog must never block on a lock
        # the wedged step thread is holding.
        self._watchdog: threading.Thread | None = None
        self._last_progress = time.monotonic()
        self._stalled = False
        self.num_watchdog_stalls = 0

    # ---- submission ----

    def submit(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        rid: str | None = None,
        on_output=None,
        priority: int = 0,
        mm_embeds: tuple | None = None,  # (embeds [M, E] f32, positions [M])
        timeout_secs: float | None = None,
        trace_id: str | None = None,
    ) -> str:
        """Queue a request.  ``timeout_secs`` is the remaining client budget:
        the scheduler expires it in queue or aborts it mid-generation with a
        terminal ``timeout`` finish once the budget runs out.  Raises
        ``QueueFullError`` (retryable) under admission backpressure.
        ``trace_id`` links the flight-recorder timeline to the gateway's
        OTel trace (propagated over the worker hop as gRPC metadata)."""
        rid = rid or f"req-{uuid.uuid4().hex[:16]}"
        req = EngineRequest(
            rid=rid, prompt_ids=list(prompt_ids), sampling=sampling, priority=priority
        )
        req.trace_id = trace_id
        if timeout_secs is not None:
            # an exhausted budget (<= 0) still submits: the first sweep
            # returns the terminal "timeout" through the normal output path
            req.deadline = time.monotonic() + max(timeout_secs, 0.0)
        if mm_embeds is not None:
            import numpy as np

            embeds, positions, *rest = mm_embeds
            grids = rest[0] if rest else None  # per-image merged (gh, gw)
            embeds = np.asarray(embeds, np.float32)
            positions = np.asarray(positions, np.int64)
            if positions.size and (positions.min() < 0
                                   or positions.max() >= len(prompt_ids)):
                raise ValueError("mm_embeds positions out of prompt range")
            if embeds.shape[0] != positions.shape[0]:
                raise ValueError("mm_embeds embeds/positions length mismatch")
            req.mm_embeds = (embeds, positions)
            if grids and self.config.model.mrope_section is not None:
                # Qwen2-VL M-RoPE: 3-axis position ids per token + the
                # decode delta (engine/mrope.py)
                if self.config.parallel.sp > 1:
                    # reject HERE — deep in the step loop the error would
                    # wedge an admitted request in its slot forever (the
                    # runner refuses M-RoPE under ring/sp prefill; pp
                    # composes since r5 — rope ids ride the pp consts)
                    raise ValueError(
                        "M-RoPE image requests are not supported with sp yet"
                    )
                from smg_tpu.engine.mrope import (
                    image_runs_from_positions,
                    mrope_positions,
                )

                runs = image_runs_from_positions(positions, grids)
                req.mrope_pos, req.mrope_delta = mrope_positions(
                    len(prompt_ids), runs
                )
        if self.tokenizer is not None:
            req.detok = IncrementalDecoder(
                self.tokenizer, skip_special_tokens=sampling.skip_special_tokens
            )
            if sampling.stop:
                req.stop_checker = StopStringChecker(sampling.stop)
        req.token_filter = self._build_token_filter(sampling)
        if sampling.lora_adapter:
            req.lora_idx = self.runner.lora_index(sampling.lora_adapter)
        with self._locked_for_submit(req), TraceAnnotation("smg.submit"):
            self.scheduler.add_request(req)  # may raise QueueFullError
            # fresh work resets the watchdog clock: stall time is measured
            # from "work existed and no step completed", not from engine idle
            self._last_progress = time.monotonic()
            if on_output is not None:
                self._callbacks[rid] = on_output
            self._wakeup.notify_all()
        return rid

    @contextlib.contextmanager
    def _locked_for_submit(self, req: EngineRequest):
        """The engine lock for a submission, with the wait for it stamped
        (``req.submit_t`` -> the timeline's ``submit_t``), counted and
        spanned: ``step()`` holds the lock across the blocking fetch of the
        frame in flight, so this wait is part of a caller's time to first
        token that ``queued_t`` (stamped after the lock is won) cannot see."""
        req.submit_t = time.monotonic()
        with self._waiters_lock:
            self._submit_waiters += 1
        try:
            with TraceAnnotation("smg.submit.lock_wait"):
                self._wakeup.acquire()
        finally:
            with self._waiters_lock:
                self._submit_waiters -= 1
        try:
            wait = time.monotonic() - req.submit_t
            self.num_submits += 1
            self.submit_lock_wait_s_total += wait
            self.metrics.observe_submit_lock_wait(wait)
            yield
        finally:
            self._wakeup.release()

    def _let_submitters_in(self) -> None:
        """Called by the step, which holds the engine lock, where nothing of
        its own is on the device yet (``Scheduler._admit``): hand the lock to
        the submissions that wait for it, so that this step's prefill phase
        admits them.  ``step()`` holds the lock across the blocking fetch of
        the frame in flight, so a caller's next request, sent when it heard
        of a finish, waits out the rest of that frame on the lock; let in
        only after the step, it would find the next frame launched and wait
        that one out in the queue too, with its lane computed empty all the
        while.  The wait on the condition lets go of the lock; a submission
        notifies it once queued; ``SUBMIT_YIELD_S`` bounds it, because the
        chip has nothing to do meanwhile."""
        if self._submitters_waiting():
            self._wakeup.wait_for(lambda: not self._submitters_waiting(),
                                  timeout=SUBMIT_YIELD_S)

    def _submitters_waiting(self) -> bool:
        with self._waiters_lock:
            return self._submit_waiters > 0

    def _build_token_filter(self, sampling: SamplingParams):
        """Install the grammar vocab-mask filter for structured output.

        ``json_schema`` constrains generation to syntactically valid JSON
        (``{}`` = any document; schema *shape* is not yet enforced on-device,
        matching a grammar-backend-less engine).  The filter is shared across
        requests: the piece table and text->mask cache are tokenizer-global.
        Reference behavior: xgrammar-backed structured output in the engines
        behind ``sglang_scheduler.proto`` SamplingParams."""
        if sampling.json_schema is None and not sampling.regex and not sampling.ebnf:
            return None
        if self.tokenizer is None:
            logger.warning("grammar constraint ignored: engine has no tokenizer")
            return None
        if sampling.regex or sampling.ebnf:
            # pattern/grammar-specific acceptors share one filter per
            # pattern (piece table + mask cache are pattern-keyed)
            from smg_tpu.constrained import TokenFilter

            key = ("ebnf", sampling.ebnf) if sampling.ebnf else ("regex", sampling.regex)
            cached = self._grammar_filters.get(key)
            if cached is not None:
                return cached
            if sampling.ebnf:
                from smg_tpu.constrained.ebnf import EbnfMachine

                machine = EbnfMachine(sampling.ebnf)
            else:
                from smg_tpu.constrained.regex_fsm import RegexMachine

                machine = RegexMachine(sampling.regex)
            filt = TokenFilter(
                self.tokenizer, machine, self.config.model.vocab_size,
                eos_token_ids=self.config.model.eos_token_ids,
            )
            if len(self._grammar_filters) >= 16:  # bound pattern-keyed mask caches
                self._grammar_filters.pop(next(iter(self._grammar_filters)))
            self._grammar_filters[key] = filt
            return filt
        if self._json_filter is None:
            from smg_tpu.constrained import JsonMachine, TokenFilter

            self._json_filter = TokenFilter(
                self.tokenizer,
                JsonMachine(),
                self.config.model.vocab_size,
                eos_token_ids=self.config.model.eos_token_ids,
            )
        return self._json_filter

    def abort(self, rid: str) -> bool:
        with self._lock:
            ok = self.scheduler.abort_request(rid)
            self._callbacks.pop(rid, None)
            return ok

    @property
    def healthy(self) -> bool:
        """Engine-level health: false while the step watchdog sees a stall,
        or after N consecutive failed steps (``max_consecutive_step_failures``).
        Surfaced through ``loads()`` and the RPC ``health()`` so the
        gateway's HealthMonitor + circuit breakers route around a poisoned
        or wedged worker instead of queueing onto it."""
        return (
            not self._stalled
            and self.scheduler.consec_step_failures
            < self.config.max_consecutive_step_failures
        )

    def loads(self, include_audit: bool = True) -> dict:
        """Engine load/stat snapshot.  ``include_audit=False`` is for hot
        per-dispatch callers (the DP-replica pick) that only want the cheap
        counters — the audit's radix-tree lock walk is ops-plane cost."""
        with self._lock:
            out = self.scheduler.loads()
            if include_audit:
                # zero-leak quiescence audit: operators (and the loadgen
                # harness) assert steady-state cleanliness from loads() /
                # /scheduler without reaching into scheduler internals
                out["audit"] = self._audit_locked()
                # compiled-program inventory (launch/recompile counters per
                # cached jit family) — cheap snapshot, no lowering; the full
                # verification pass is program_audit()
                out["programs"] = self.runner._programs.snapshot()
        out["healthy"] = self.healthy
        out["watchdog_stalls"] = self.num_watchdog_stalls
        out["submits"] = self.num_submits
        out["submit_lock_wait_seconds"] = self.submit_lock_wait_s_total
        return out

    def program_audit(self, *, check_donation: bool = True) -> dict:
        """Compiled-program audit (analysis/runtime_guards.ProgramAuditor):
        arm ``self.runner._programs`` after warmup, run steady-state
        traffic, then call this.  Verifies from the lowered/compiled
        representation that every captured input matched its mesh
        commitment, every intended donation actually aliased an output, and
        reports provenance for any recompile observed while armed."""
        return self.runner.program_audit(check_donation=check_donation)

    def _audit_locked(self) -> dict:
        """``Scheduler.audit`` + the one leak class only the engine sees
        (output callbacks).  Caller holds the engine lock."""
        out = self.scheduler.audit()
        pending = len(self._callbacks)
        out["pending_callbacks"] = pending
        out["clean"] = out["clean"] and (not out["quiescent"] or pending == 0)
        return out

    def audit(self) -> dict:
        """Zero-leak quiescence audit (``Scheduler.audit`` + engine-level
        callback accounting).  The contract the loadgen harness asserts at
        steady state: ``clean`` is True, meaning every KV page is free,
        radix-cached, or held by a live lane; radix lock refcounts and
        output callbacks are all owned by live requests; and no in-flight
        overlap frame is stranded.  Also rides ``loads()["audit"]`` (and
        thus ``/scheduler``) so operators get the same verdict remotely."""
        with self._lock:
            return self._audit_locked()

    def dump_flight(self, reason: str = "manual") -> dict:
        """Flight-recorder snapshot: the per-step ring, per-request
        timelines, and the index of auto-dumps (postmortem black box;
        ``DumpFlight`` RPC / ``GET /debug/flight/{worker}`` land here).

        Deliberately does NOT take the engine lock: a wedged step thread
        (the very situation a postmortem is for) holds that lock, and the
        recorder is internally consistent under its own small lock."""
        fl = self.scheduler.flight
        if fl is None:
            from smg_tpu.engine.flight_recorder import SCHEMA_VERSION

            return {
                "schema_version": SCHEMA_VERSION,
                "error": "flight recorder disabled",
            }
        snap = fl.snapshot(reason)
        snap["engine"] = {
            "model_id": self.config.model_id,
            "healthy": self.healthy,
            "uptime_secs": time.monotonic() - self.start_time,
            "watchdog_stalls": self.num_watchdog_stalls,
            "consecutive_step_failures": self.scheduler.consec_step_failures,
            "step_failures": self.scheduler.num_step_failures,
            "quarantined_requests": self.scheduler.num_quarantined,
            "draining": self.scheduler.draining,
            # the device this box flew on (a remote worker's loads() proto
            # carries counters only, so this is where a gateway can read it)
            "mesh": self.runner.mesh_info(),
            "attention": self.runner.attention_info(),
        }
        if fl.dumps:
            # the newest auto-dump rides along in full so one fetch answers
            # "what did the black box capture when it tripped"
            snap["last_auto_dump"] = fl.dumps[-1]
        return snap

    def flush_cache(self) -> bool:
        with self._lock:
            return self.scheduler.flush_cache()

    def embed(self, batches: "list[list[int]]"):
        """Sequence embeddings (blocks the step loop briefly)."""
        with self._lock:
            return self.runner.embed(batches)

    @property
    def supports_vision(self) -> bool:
        return self._vision_params is not None

    #: max distinct (gh, gw) grids kept compiled; beyond this the least
    #: recently used entry is dropped (its XLA executable is GC'd).  Arbitrary
    #: image sizes otherwise grow the compile cache without bound.
    VISION_COMPILE_CACHE = 32

    def encode_image(self, pixel_values, grid: tuple) -> "object":
        """Vision-tower encode: pre-patchified pixels [N, patch_dim] ->
        language-space embeddings [N/merge^2, hidden] (np.float32).  The EPD
        encode leg (reference: encoder servicer + ``stages/encode.rs``); also
        serves colocated inline encode."""
        import functools

        import jax
        import numpy as np

        if self._vision_params is None:
            raise ValueError("model has no vision tower")
        vcfg = self.config.model.vision
        key = (int(grid[0]), int(grid[1]))
        with self._lock:
            fn = self._vision_fns.get(key)
            if fn is None:
                from smg_tpu.models.vit import forward_vision

                fn = jax.jit(functools.partial(forward_vision, cfg=vcfg, grid=key))
                while len(self._vision_fns) >= self.VISION_COMPILE_CACHE:
                    self._vision_fns.pop(next(iter(self._vision_fns)))
            # move-to-end: dict insertion order doubles as LRU order
            self._vision_fns.pop(key, None)
            self._vision_fns[key] = fn
            out = fn(self._vision_params, pixel_values=jax.numpy.asarray(
                pixel_values, jax.numpy.float32))
        return np.asarray(out, np.float32)

    # ---- LoRA adapters (reference: Load/Unload/ListLoRAAdapter RPCs) ----

    def load_lora_adapter(
        self, name: str, path: str | None = None, data: bytes | None = None
    ) -> int:
        """Install an adapter from a PEFT dir / .npz path / inline npz bytes.
        Returns the bank slot.  In-place bank write — no recompile, and
        in-flight requests are unaffected (their gates don't touch the slot
        until a new request names the adapter)."""
        import os

        from smg_tpu.models import lora as lora_mod

        if data is not None:
            weights = lora_mod.load_npz(data)
        elif path is None:
            raise ValueError("need path or data")
        elif os.path.isdir(path):
            weights = lora_mod.load_peft_dir(path, self.config.model)
        else:
            weights = lora_mod.load_npz(path)
        with self._lock:
            if name in self.runner._lora_names and self._lora_slot_busy(
                self.runner._lora_names[name]
            ):
                raise ValueError(
                    f"adapter {name!r} has in-flight requests; drain before replacing"
                )
            return self.runner.load_lora(name, weights)

    def _lora_slot_busy(self, slot: int) -> bool:
        """True when any live request is pinned to the bank slot (the bank is
        re-read every decode step, so swapping a busy slot would change an
        in-flight request's weights mid-generation)."""
        return any(
            r.lora_idx == slot and not r.is_finished
            for r in self.scheduler.requests.values()
        )

    def unload_lora_adapter(self, name: str) -> bool:
        with self._lock:
            idx = self.runner._lora_names.get(name)
            if idx is not None and self._lora_slot_busy(idx):
                raise ValueError(
                    f"adapter {name!r} has in-flight requests; drain before unloading"
                )
            return self.runner.unload_lora(name)

    def list_lora_adapters(self) -> list[str]:
        with self._lock:
            return self.runner.list_loras()

    # ---- profiling (reference: /start_profile proxy -> engine profiler;
    # TPU-native backend is jax.profiler's XLA/XProf trace) ----

    def start_profile(
        self,
        output_dir: str,
        host_tracer: bool = True,
        python_tracer: bool = False,
        num_steps: int = 0,
    ) -> str:
        """Begin a jax.profiler trace; returns the resolved trace dir.
        ``num_steps > 0`` auto-stops the trace after that many engine steps
        (reference StartProfileRequest.num_steps semantics).  Neither this
        nor ``stop_profile`` takes the engine lock: steps and submits go on
        while the profiler starts and while it writes the trace."""
        import jax

        with self._profile_lock:
            if self._profiling:
                raise RuntimeError("profiler already running")
            self._profiling = True  # claimed; released below if the start fails
        try:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 2 if host_tracer else 0
            opts.python_tracer_level = 1 if python_tracer else 0
            jax.profiler.start_trace(output_dir, profiler_options=opts)
        except BaseException:
            with self._profile_lock:
                self._profiling = False
            raise
        with self._profile_lock:
            self._profile_steps_left = num_steps if num_steps > 0 else None
            self._profile_launches = self.runner._programs.launch_counts()
            self._profile_dir = output_dir
        logger.info("profiler started -> %s", output_dir)
        return output_dir

    def stop_profile(self) -> None:
        import jax

        with self._profile_lock:
            if not self._profiling or self._profile_stopping:
                raise RuntimeError("profiler not running")
            self._profile_stopping = True
            self._profile_steps_left = None
            before, beside = self._profile_launches, self._profile_dir
        programs = self.runner._programs
        t0 = time.monotonic()
        try:
            jax.profiler.stop_trace()  # writes the trace: seconds, no engine lock
            written = time.monotonic() - t0
            # the trace names a fusion ``fusion.516``: which named scope each
            # instruction of the programs it holds belongs to, for its readers
            # (``loads()["programs"]["scopes"]``).  Here and nowhere else: the
            # trace is closed, this is no step's thread, no lock is held
            maps = programs.publish_scopes(
                [k for k, n in programs.launch_counts().items() if n > before.get(k, 0)])
            if maps and beside:
                # whoever holds the trace holds its map: ``<dir>.scopes.json``
                # stays when the trace's directory is cut down and deleted
                try:
                    with open(beside.rstrip("/") + ".scopes.json", "w") as f:
                        json.dump(maps, f)
                except OSError:
                    logger.exception("no scope map beside %s", beside)
        finally:
            # trace serialization can fail (unwritable dir); never wedge
            # the profiler state on it
            with self._profile_lock:
                self._profiling = False
                self._profile_stopping = False
        logger.info("profiler stopped (trace written in %.1f s; scope maps so far: %d "
                    "programs, %d stale, %.2f s)", written, programs.scope_lowerings,
                    programs.scope_stale, programs.scope_seconds)

    def _profile_step_done(self) -> None:
        """Count one step against a ``num_steps`` trace.  At zero the stop
        runs on a thread of its own: the caller is ``step()``, and writing
        the trace on the step thread would stall serving just as writing it
        under the engine lock did."""
        with self._profile_lock:
            if self._profile_steps_left is None:
                return
            self._profile_steps_left -= 1
            if self._profile_steps_left > 0:
                return
            self._profile_steps_left = None
        threading.Thread(
            target=self._stop_profile_after_steps, name="smg-profiler-stop",
            daemon=True,
        ).start()

    def _stop_profile_after_steps(self) -> None:
        try:
            self.stop_profile()
        except RuntimeError:
            pass  # an explicit stop_profile got there first
        except Exception:
            logger.exception("step-bounded profiler stop failed")

    # ---- PD disaggregation legs ----

    def prefill_export(
        self, prompt_ids: list[int], sampling: SamplingParams,
        connector: str = "host",
    ) -> dict:
        """Prefill leg: compute the prompt's KV, export pages via the chosen
        connector, free them.  Returns {first_token, k, v, seq_len, connector}
        (k/v: [L, n, ps, KD] — numpy for ``host``, on-device jax.Arrays for
        ``device``)."""
        from smg_tpu.engine.kv_connector import get_connector

        conn = get_connector(connector)
        with self._lock:
            tok, pages, seq_len = self.scheduler.prefill_only(
                prompt_ids, sampling, token_filter=self._build_token_filter(sampling)
            )
            k, v = conn.export(self.runner, pages)
            self.scheduler.release_pages(pages)
        return {
            "first_token": tok, "k": k, "v": v, "seq_len": seq_len,
            "connector": conn.name,
        }

    def submit_prefilled(
        self,
        prompt_ids: list[int],
        first_token: int,
        k,  # np [L, n_pages, ps, KD]
        v,
        sampling: SamplingParams,
        rid: str | None = None,
        on_output=None,
        trace_id: str | None = None,
    ) -> str:
        """Decode leg: import prompt KV, adopt the request, continue decoding.
        Falls back to a normal (re-prefilling) submission when no slot/pages
        are available."""
        rid = rid or f"req-{uuid.uuid4().hex[:16]}"
        req = EngineRequest(rid=rid, prompt_ids=list(prompt_ids), sampling=sampling)
        req.trace_id = trace_id
        if self.tokenizer is not None:
            req.detok = IncrementalDecoder(
                self.tokenizer, skip_special_tokens=sampling.skip_special_tokens
            )
            if sampling.stop:
                req.stop_checker = StopStringChecker(sampling.stop)
        req.token_filter = self._build_token_filter(sampling)
        if sampling.lora_adapter:
            req.lora_idx = self.runner.lora_index(sampling.lora_adapter)
        with self._locked_for_submit(req), TraceAnnotation("smg.submit"):
            pages = None
            try:
                from smg_tpu.engine.kv_connector import resolve_for_payload

                pages = self.scheduler.alloc_import_pages(len(prompt_ids))
                resolve_for_payload(k).import_(self.runner, pages, k, v)
                adopted = self.scheduler.adopt_prefilled(req, pages, first_token)
            except Exception:
                logger.exception("KV import failed for %s", rid)
                adopted = False
            if not adopted and pages is not None:
                self.scheduler.release_pages(pages)
            if on_output is not None:
                self._callbacks[rid] = on_output
            if adopted:
                step_outs: list = []
                self.scheduler._accept_tokens(
                    req, [int(first_token)], [0.0], step_outs, advance_seq=False
                )
                outputs = [self._postprocess(so) for so in step_outs]
            else:
                # degraded path: re-prefill locally (keeps the request alive
                # under slot/page pressure)
                logger.warning("PD adopt failed for %s; falling back to local prefill", rid)
                self.scheduler.add_request(req)
                outputs = []
            self._wakeup.notify_all()
        for out in outputs:
            cb = self._callbacks.get(out.rid)
            if cb is not None:
                cb(out)
                if out.finished:
                    self._callbacks.pop(out.rid, None)
        return rid

    # ---- stepping ----

    def step(self) -> list[RequestOutput]:
        """One scheduler iteration; returns per-request increments."""
        with self._lock, TraceAnnotation("smg.step"):
            step_outs = self.scheduler.step()
            with TraceAnnotation("smg.step.postprocess"):
                outputs = [self._postprocess(so) for so in step_outs]
            self.events.flush()
            if self.config.device_metrics_interval_secs > 0:
                # cadence-gated HBM gauges (no-op between samples; CPU
                # devices report no memory_stats and are skipped).  The
                # device set is fixed for the engine's lifetime — build the
                # list once, not on every step of the hot loop.
                if self._metric_devices is None:
                    self._metric_devices = self.runner.local_devices()
                self.metrics.maybe_sample_devices(self._metric_devices)
        # smglint: disable-next=GUARDED lock-free gate; the count re-reads under the profiler's lock
        if self._profile_steps_left is not None:
            self._profile_step_done()
        # watchdog progress mark + stall recovery (a step completed end to
        # end, so a previously-flagged wedge has cleared)
        self._last_progress = time.monotonic()
        if self._stalled:
            self._stalled = False
            logger.warning("engine step progress resumed; stall cleared")
        with TraceAnnotation("smg.step.callbacks"):
            for out in outputs:
                cb = self._callbacks.get(out.rid)
                if cb is not None:
                    try:
                        cb(out)
                    except Exception:
                        logger.exception("output callback failed for %s", out.rid)
                    if out.finished:
                        self._callbacks.pop(out.rid, None)
        return outputs

    def _postprocess(self, so: StepOutput) -> RequestOutput:
        req = so.request
        out = RequestOutput(
            rid=req.rid,
            new_token_ids=list(so.new_token_ids),
            finished=so.finished,
            finish_reason=so.finish.reason if so.finish else None,
            matched_stop=so.finish.matched_stop if so.finish else None,
            prompt_tokens=req.prompt_len,
            output_tokens=len(req.output_ids),
            cached_tokens=req.cached_tokens,
            logprobs=list(so.logprobs),
        )
        if req.detok is None:
            return out
        if req.stop_checker is not None:
            # feed token-by-token so a mid-chunk stop (decode horizon) trims
            # both the text AND the trailing tokens after the stop
            emitted_parts: list[str] = []
            consumed = 0
            stopped = False
            for tok in so.new_token_ids:
                piece, stopped = req.stop_checker.feed(req.detok.put([tok]))
                consumed += 1
                emitted_parts.append(piece)
                if stopped:
                    break
            if stopped and consumed < len(so.new_token_ids):
                # roll back the overshoot tokens (their KV past seq_len never
                # enters the radix cache)
                cut = len(so.new_token_ids) - consumed
                out.new_token_ids = out.new_token_ids[:consumed]
                out.logprobs = out.logprobs[:consumed]
                req.output_ids = req.output_ids[: len(req.output_ids) - cut]
                req.logprobs = req.logprobs[: len(req.logprobs) - cut]
                req.seq_len -= cut
                out.output_tokens = len(req.output_ids)
            if stopped:
                matched = req.stop_checker.matched
                if not so.finished:
                    self.scheduler.finish_request(req.rid, "stop", matched_stop=matched)
                out.finished = True
                out.finish_reason = "stop"
                out.matched_stop = matched
            elif so.finished:
                piece, stopped_late = req.stop_checker.feed(req.detok.flush())
                emitted_parts.append(piece)
                if stopped_late:
                    out.finish_reason = "stop"
                    out.matched_stop = req.stop_checker.matched
                else:
                    emitted_parts.append(req.stop_checker.flush())
            out.text_delta = "".join(emitted_parts)
        else:
            text = req.detok.put(so.new_token_ids) if so.new_token_ids else ""
            if so.finished:
                text += req.detok.flush()
            out.text_delta = text
        return out

    # ---- background loop ----

    def warmup(self) -> list[tuple[str, float]]:
        """Compile and run the largest prefill and decode programs before
        serving (``ModelRunner.warmup``): the serving commands call this
        before they bind a port, so a server that cannot run its own
        defaults never reports healthy."""
        with self._lock:
            return self.runner.warmup()

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stopping = False
        self._last_progress = time.monotonic()
        self._thread = threading.Thread(target=self._loop, name="smg-engine", daemon=True)
        self._thread.start()
        if self.config.step_watchdog_secs > 0 and self._watchdog is None:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="smg-engine-watchdog", daemon=True
            )
            self._watchdog.start()

    def stop(self, drain: bool = False, timeout: float = 30.0) -> None:
        """Stop the engine.  ``drain=True`` first stops admission, fails
        every still-queued request with a terminal ``abort`` output (clients
        see an end, not a hang), and waits up to ``timeout`` seconds for the
        admitted lanes (RUNNING and mid-prefill) to finish streaming before
        the loop is torn down."""
        if drain:
            fl = self.scheduler.flight
            if fl is not None:
                # capture the pre-drain state (the black box's "engine shut
                # down on purpose" record) before the sweep mutates it
                fl.auto_dump("drain")
            with self._wakeup:
                self.scheduler.draining = True
                step_outs: list = []
                self.scheduler.drain_waiting(step_outs)
                outputs = [self._postprocess(so) for so in step_outs]
                self._wakeup.notify_all()
            for out in outputs:
                cb = self._callbacks.pop(out.rid, None)
                if cb is not None:
                    try:
                        cb(out)
                    except Exception:
                        logger.exception("drain callback failed for %s", out.rid)
            deadline = time.monotonic() + max(timeout, 0.0)
            # only wait when a loop is actually running the work down
            while self._thread is not None and time.monotonic() < deadline:
                with self._lock:
                    if not self.scheduler.has_work():
                        break
                time.sleep(0.01)
            else:
                if self._thread is not None:
                    logger.warning(
                        "drain timeout (%.1fs): stopping with work in flight",
                        timeout,
                    )
        with self._wakeup:
            self._stopping = True
            self._wakeup.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._watchdog is not None:
            self._watchdog.join(timeout=5)
            self._watchdog = None

    def _watchdog_loop(self) -> None:
        """Step watchdog: flags the engine unhealthy when no step completes
        for ``step_watchdog_secs`` while work is pending (a wedged device
        fetch, a runaway compile).  Runs LOCK-FREE — the wedged step thread
        is usually holding the engine lock, so the watchdog only reads
        scheduler state (racy but monotonic enough for a threshold check)
        and takes the lock opportunistically to abort the in-flight frame."""
        T = self.config.step_watchdog_secs
        poll = max(min(T / 4.0, 1.0), 0.01)
        logger.info("engine step watchdog started (threshold %.1fs)", T)
        while not self._stopping:
            time.sleep(poll)
            if self._stopping:
                break
            try:
                has_work = self.scheduler.has_work()  # unlocked read, see above
            except Exception:
                continue
            stalled_for = time.monotonic() - self._last_progress
            if not has_work or stalled_for <= T:
                continue
            if not self._stalled:
                self._stalled = True
                self.num_watchdog_stalls += 1
                self.metrics.watchdog_stalls.inc()
                logger.error(
                    "engine wedged: no step progress for %.1fs with work "
                    "pending; marking unhealthy", stalled_for,
                )
                fl = self.scheduler.flight
                if fl is not None:
                    # lock-free by design: auto_dump takes only the
                    # recorder's own lock, never the engine lock the wedged
                    # step thread is holding
                    fl.auto_dump("watchdog_stall")
                # best-effort in-flight-frame abort: only possible when the
                # step thread is NOT holding the lock (e.g. wedged outside
                # the step body); a blocked acquire here would deadlock the
                # watchdog behind the very stall it is reporting
                if self._lock.acquire(blocking=False):
                    try:
                        self.scheduler.drop_inflight()
                    finally:
                        self._lock.release()
        logger.info("engine step watchdog stopped")

    def _loop(self) -> None:
        """Drives the step loop — and, with ``overlap_schedule`` on, the
        two-stage decode pipeline: each ``step()`` consumes the previously
        launched device work and leaves the next launch in flight, so host
        postprocessing here (detokenize, stop strings, callbacks) overlaps
        device compute.  ``has_work`` includes the in-flight frame, so the
        pipeline drains naturally after the last request finishes or aborts;
        an explicit stop() discards whatever is still in flight."""
        logger.info("engine loop started")
        while True:
            with self._wakeup:
                if self._stopping:
                    break
                if not self.scheduler.has_work():
                    self._wakeup.wait(timeout=0.05)
                    continue
            try:
                self.step()
            except Exception:
                # last-resort containment: the scheduler's quarantine layer
                # handles prefill/decode failures in-band, so anything
                # arriving here escaped blame attribution.  Count it toward
                # the consecutive-failure health threshold (loads()/health()
                # go false at N) and keep the loop alive — the gateway
                # routes around an unhealthy worker while it retries.
                self.scheduler._count_step_failure("loop")
                # a health-flip crossing counted here (outside a step) would
                # otherwise wait for the next step to dump
                self.scheduler.flush_pending_dumps()
                logger.exception(
                    "engine step failed (%d consecutive)",
                    self.scheduler.consec_step_failures,
                )
                time.sleep(0.1)
        with self._lock:
            # stop() mid-generation: the frame's results will never be
            # consumed (clients are gone); drop it so the sampling-key
            # counter and penalty state stay coherent for a restart
            self.scheduler.drop_inflight()
        logger.info("engine loop stopped")

    # ---- sync convenience ----

    def generate(
        self,
        prompt_ids: list[int] | None = None,
        text: str | None = None,
        sampling: SamplingParams | None = None,
        rid: str | None = None,
        timeout_secs: float = 300.0,
    ) -> GenerationResult:
        """Blocking generate.  Drives the loop inline when no background
        thread is running (tests), otherwise waits on the stream.

        ``timeout_secs`` rides the per-request deadline plumbing: an expired
        generation comes back as a normal result with
        ``finish_reason="timeout"`` (pages/lane released by the scheduler's
        sweep), not a raised ``TimeoutError`` with an orphaned abort.  The
        raise remains only as a backstop for a wedged engine that stops
        producing outputs at all."""
        sampling = sampling or SamplingParams()
        if prompt_ids is None:
            if text is None or self.tokenizer is None:
                raise ValueError("need prompt_ids, or text with a tokenizer")
            prompt_ids = self.tokenizer.encode(text)

        done = threading.Event()
        chunks: list[RequestOutput] = []

        def on_output(out: RequestOutput) -> None:
            chunks.append(out)
            if out.finished:
                done.set()

        rid = self.submit(prompt_ids, sampling, rid=rid, on_output=on_output,
                          timeout_secs=timeout_secs)
        # backstop margin past the deadline: the sweep itself needs a step
        # to run, and a truly wedged engine never steps again
        backstop = timeout_secs + 30.0
        if self._thread is None:
            deadline = time.monotonic() + backstop
            while not done.is_set():
                self.step()
                if time.monotonic() > deadline:
                    self.abort(rid)
                    raise TimeoutError(f"generation {rid} timed out")
        else:
            if not done.wait(timeout=backstop):
                self.abort(rid)
                raise TimeoutError(f"generation {rid} timed out")

        token_ids: list[int] = []
        logprobs: list[float] = []
        text_out = []
        last = chunks[-1]
        for c in chunks:
            token_ids.extend(c.new_token_ids)
            logprobs.extend(c.logprobs)
            text_out.append(c.text_delta)
        return GenerationResult(
            rid=rid,
            token_ids=token_ids,
            text="".join(text_out),
            finish_reason=last.finish_reason or "stop",
            matched_stop=last.matched_stop,
            prompt_tokens=last.prompt_tokens,
            output_tokens=last.output_tokens,
            cached_tokens=chunks[0].cached_tokens if chunks else 0,
            logprobs=logprobs,
        )
