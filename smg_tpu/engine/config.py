"""Engine configuration.

Reference analogue: engine-launch knobs forwarded by ``bindings/python/src/smg/serve.py:32-196``
(tp size, memory fraction, ports) plus SGLang's own scheduler config.  Here the
engine is in-tree so the config is first-class and validated.

TPU-first design notes:
- XLA compiles one program per distinct shape, so batch/seq sizes are drawn
  from explicit bucket ladders (``prefill_token_buckets``, ``decode_batch_buckets``).
- The KV cache is paged: ``page_size`` tokens per page, pages shared across
  sequences via the radix prefix cache at page granularity.
- Parallelism is declared as a mesh shape over named axes; shardings are
  derived in ``smg_tpu.parallel.sharding``.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh shape over named axes.

    ``dp``: data parallel (replicated params, independent batches)
    ``tp``: tensor parallel (heads / ffn sharded; collectives ride ICI)
    ``sp``: sequence parallel for long-context prefill (ring attention)
    ``ep``: expert parallel (MoE)
    ``pp``: pipeline parallel (inter-slice / DCN)
    """

    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    @property
    def world_size(self) -> int:
        return self.dp * self.tp * self.sp * self.ep * self.pp

    def axis_sizes(self) -> dict[str, int]:
        return {"dp": self.dp, "tp": self.tp, "sp": self.sp, "ep": self.ep, "pp": self.pp}

    @classmethod
    def from_spec(cls, spec: str, base: "ParallelConfig | None" = None) -> "ParallelConfig":
        """Parse a ``--mesh-shape`` string ("tp=4" / "dp=2,tp=4") over
        ``base`` (axes not named keep the base's value).  Raises ValueError
        on unknown axes, malformed entries, or sizes < 1 — the CLI
        validation layer turns these into startup errors."""
        sizes = (base or cls()).axis_sizes()
        seen: set[str] = set()
        for part in (p.strip() for p in spec.split(",") if p.strip()):
            axis, sep, val = part.partition("=")
            axis = axis.strip()
            if not sep or axis not in sizes:
                raise ValueError(
                    f"mesh-shape entry {part!r}: expected axis=N with axis "
                    f"in {sorted(sizes)}"
                )
            if axis in seen:
                # a repeated axis is a typo, not an override — last-wins
                # would silently boot the wrong topology
                raise ValueError(f"mesh-shape names {axis!r} twice")
            seen.add(axis)
            try:
                n = int(val)
            except ValueError:
                raise ValueError(f"mesh-shape entry {part!r}: size must be an int") from None
            if n < 1:
                raise ValueError(f"mesh-shape entry {part!r}: size must be >= 1")
            sizes[axis] = n
        return cls(**sizes)


@dataclass(frozen=True)
class CacheConfig:
    """Paged KV cache layout.

    ``page_size`` is in tokens.  TPU lane width is 128 and bf16 sublane packing
    is 16, so head_dim stays a multiple of 128 and page_size a multiple of 8.
    """

    page_size: int = 16
    num_pages: int = 2048  # overridden by hbm-based sizing when auto=True
    auto_size: bool = True
    hbm_utilization: float = 0.9  # fraction of free HBM given to KV after weights
    dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        if self.page_size % 8 != 0:
            raise ValueError("page_size must be a multiple of 8 for TPU tiling")


@dataclass(frozen=True)
class SchedulerConfig:
    """Continuous-batching scheduler knobs (token-budget interleaving of
    prefill and decode — the reference relies on SGLang's scheduler for this;
    ours is in-tree, SURVEY.md §7 step 2)."""

    max_batch_size: int = 64  # decode slots
    max_seq_len: int = 8192
    # per-STEP prefill token budget (Sarathi-style stall-free chunked
    # prefill): each step() spends at most this many prompt tokens on
    # prefill — split across a group of short prompts or one chunk of a long
    # one — and decode runs every step, so running lanes never observe a
    # multi-chunk stall while a long prompt streams in
    max_prefill_tokens: int = 4096
    # padded lengths a prefill program is compiled for.  The octaves down
    # from the top (``coarse_prefill_buckets``) serve every launch; a rung
    # between two of them (1,536, 3,072) serves a launch of one cold row
    # alone, where the padding is that row's own and a rung is one program
    # (PERF.md, Findings, PR 42: a 3,072-token row of olmo-hybrid-7b takes
    # two thirds of a 4,096-token one's time)
    prefill_token_buckets: tuple[int, ...] = (64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096)
    decode_batch_buckets: tuple[int, ...] = (8, 16, 32, 64)
    schedule_policy: str = "fcfs"  # fcfs | priority
    enable_prefix_cache: bool = True
    watermark_pages: int = 8  # keep this many pages free before admitting prefill
    # decode steps fused per device call (the megastep: a lax.while_loop with
    # in-loop sampling-key folds and device-side stop detection); sampled
    # tokens feed back on-device and the host syncs once per horizon.  Token
    # streams are byte-identical to decode_horizon=1 at ANY temperature: each
    # in-loop column folds the exact key the single-step path would have, a
    # per-lane done mask (EOS/stop-token ids + max-token budget) early-exits
    # the loop at the first finish, and the host trims acceptance at that
    # column and rewinds the unused key folds before relaunching.  >1
    # amortizes the per-step host round trip ~K-fold.  Every cell of the
    # benchmark passes 8; at that width the device's idle share read 2.8 %
    # (ledger, before PR 27).
    decode_horizon: int = 1
    # adaptive horizon controller: pick K per step from page headroom and
    # observed finish rates (EMA of columns-until-finish), capped at
    # horizon_cap.  Pending admission work (waiting queue / resumable
    # prefills) forces K=1 in EVERY mode — a K=1 schedule can admit between
    # any two decode steps, so a horizon spanning an admission point would
    # break byte-parity; grammar masks / stop strings / speculative decoding
    # force K=1 exactly like the static path.
    adaptive_horizon: bool = False
    # compiled horizon bound: the megastep jit is traced ONCE per batch
    # bucket with this as the loop's static output width, and the per-launch
    # K rides a device scalar — so neither the static decode_horizon sweep
    # nor the adaptive controller costs a retrace.  0 = follow decode_horizon
    # (the default keeps the K=1 trace as lean as today's).
    decode_horizon_max: int = 0
    # single-chunk prompts admitted together in one batched prefill call
    # (fills the MXU and amortizes dispatch; long prompts still chunk solo)
    max_prefill_group: int = 8
    # prefill scheduling policy:
    #   "stall-free"  — max_prefill_tokens is a true per-step budget:
    #                   admission is capped per step, long prompts advance
    #                   one resumable chunk per step (PREFILLING cursor),
    #                   leftover budget packs partial chunks of the next
    #                   waiting prompt, and decode runs EVERY step;
    #   "throughput"  — legacy drain-the-queue admission: all chunks of a
    #                   long prompt run back-to-back inside one step and the
    #                   waiting queue drains before decode (maximizes prefill
    #                   throughput, stalls decode ITL under long prompts).
    prefill_mix_policy: str = "stall-free"
    # admission backpressure: bound the WAITING queue so an overloaded engine
    # rejects at submit (retryable QueueFullError -> RESOURCE_EXHAUSTED ->
    # router retry-another-worker / 429) instead of growing host memory and
    # queue latency without limit.  0 = unbounded (legacy behavior).
    max_queued_requests: int = 0
    # token-denominated variant of the same bound: waiting prompt tokens plus
    # the incoming prompt must fit.  0 = unbounded.
    max_queued_tokens: int = 0
    # overlapped decode pipeline (one-step lookahead): the step loop launches
    # the next decode before last step's outputs are consumed, so host-side
    # work (detokenize, stop strings, admission bookkeeping) hides behind
    # device compute.  Token streams stay byte-identical to the synchronous
    # path; speculative decoding and grammar-masked batches force a sync
    # boundary (their next device step depends on last step's host results).
    overlap_schedule: bool = True
    # speculative decoding (engine/speculative.py + the fused verify block
    # in engine/runner.py): eligible lanes draft up to spec_max_draft tokens
    # host-side and verify them in ONE batched device forward with on-device
    # acceptance — greedy chains at temperature 0 (token-identical to plain
    # greedy decode), distribution-preserving rejection sampling above it.
    # The verify frame pipelines across steps under overlap_schedule, and
    # no-draft steps fall back to the full megastep horizon (speculation no
    # longer forces sync + K=1).
    speculative: bool = False
    spec_max_draft: int = 8
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # drafting tier: "auto" uses the draft MODEL when one is configured
    # (EngineConfig.draft_model) and prompt-lookup n-grams otherwise;
    # "ngram" pins the zero-cost tier even with a draft model installed;
    # "draft" requires a configured draft model.  "mtp": the model's own
    # next-token module drafts inside the decode frame (a model that has one,
    # ``ModelConfig.mtp_layers``; "auto" picks it there).
    speculative_tier: str = "auto"

    def __post_init__(self) -> None:
        if self.max_batch_size > max(self.decode_batch_buckets):
            raise ValueError("max_batch_size must be <= largest decode batch bucket")
        if self.max_prefill_tokens > max(self.prefill_token_buckets):
            raise ValueError("max_prefill_tokens must be <= largest prefill bucket")
        if self.prefill_mix_policy not in ("stall-free", "throughput"):
            raise ValueError(
                "prefill_mix_policy must be 'stall-free' or 'throughput', "
                f"got {self.prefill_mix_policy!r}"
            )
        if self.decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        if self.speculative_tier not in ("auto", "ngram", "draft", "mtp"):
            raise ValueError(
                "speculative_tier must be 'auto', 'ngram', 'draft' or 'mtp', "
                f"got {self.speculative_tier!r}"
            )
        if self.spec_max_draft < 1:
            raise ValueError("spec_max_draft must be >= 1")
        if self.decode_horizon_max and self.decode_horizon_max < self.decode_horizon:
            raise ValueError(
                "decode_horizon_max must be 0 or >= decode_horizon"
            )

    def self_drafting(self, model) -> bool:
        """Speculation is on and the tier is the model's own next-token
        module: the decode frames are verify frames
        (``engine/window_runner.SelfDraftingRunner``) and the host drafts
        nothing."""
        return (self.speculative and self.speculative_tier in ("auto", "mtp")
                and getattr(model, "mtp_layers", 0) > 0)

    @property
    def horizon_cap(self) -> int:
        """Compiled megastep width: the static bound every decode trace is
        built with (per-launch K <= this rides a device scalar, so varying K
        never retraces)."""
        return max(self.decode_horizon_max, self.decode_horizon, 1)

    def prefill_bucket(self, n_tokens: int) -> int:
        """The finest rung that holds ``n_tokens``: what a launch of one
        cold row pads to."""
        return _rung(sorted(self.prefill_token_buckets), n_tokens)

    @functools.cached_property
    def coarse_prefill_buckets(self) -> tuple[int, ...]:
        """The ladder's octaves, ascending: its top rung, and going down
        every rung at most half the one kept last.  A launch of several
        rows, a solo chunk and an embedding batch pad to these: their
        padding is the longest row's or they are rare, and each rung there
        is a program for every group size."""
        kept: list[int] = []
        for b in sorted(self.prefill_token_buckets, reverse=True):
            if not kept or 2 * b <= kept[-1]:
                kept.append(b)
        return tuple(reversed(kept))

    def coarse_prefill_bucket(self, n_tokens: int) -> int:
        return _rung(self.coarse_prefill_buckets, n_tokens)

    def decode_bucket(self, batch: int) -> int:
        for b in self.decode_batch_buckets:
            if batch <= b:
                return b
        return max(self.decode_batch_buckets)


def _rung(ladder, n: int) -> int:
    """The first rung of the ascending ``ladder`` that holds ``n``, else its
    top."""
    return next((b for b in ladder if n <= b), ladder[-1])


@dataclass
class EngineConfig:
    model: "object" = None  # smg_tpu.models.config.ModelConfig (untyped to avoid cycle)
    model_path: str | None = None  # HF-format dir (config.json + safetensors)
    tokenizer_path: str | None = None
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    cache: CacheConfig = field(default_factory=CacheConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    dtype: str = "bfloat16"
    seed: int = 0
    # attention kernel: "auto" picks pallas on TPU devices, XLA elsewhere
    attention_impl: str = "auto"
    # serving identity
    model_id: str = "smg-tpu-model"
    # profiling hook (reference: /start_profile proxying, common.proto:75-87)
    profile_dir: str | None = None
    # LoRA adapter bank size (slots beyond the implicit "no adapter" slot 0;
    # reference: Load/Unload/ListLoRAAdapter, sglang_scheduler.proto:48-62)
    max_loras: int = 4
    # speculative draft model (engine/draft.py): a smaller ModelConfig whose
    # greedy proposals replace n-gram lookup; None = prompt-lookup drafting
    draft_model: "object" = None
    draft_seed: int = 1
    # engine-deep observability (engine/metrics.py): rolling-stats horizon
    # surfaced via loads()/the /scheduler endpoint, and the cadence for
    # device.memory_stats() HBM gauges (0 disables device sampling)
    metrics_window_secs: float = 30.0
    device_metrics_interval_secs: float = 10.0
    # ---- failure isolation ----
    # step watchdog: a separate thread that flags the engine unhealthy when
    # no step completes for this many seconds while work is pending (a
    # wedged device fetch / runaway compile).  0 disables (the default:
    # legitimate XLA first-compiles can take minutes on loaded CPU CI;
    # enable in production once the engine is warm).
    step_watchdog_secs: float = 0.0
    # N consecutive failed steps flip the engine unhealthy: loads()["healthy"]
    # and the RPC health() go false so HealthMonitor + circuit breakers route
    # around the worker while it keeps retrying.
    max_consecutive_step_failures: int = 3
    # ---- flight recorder (engine/flight_recorder.py) ----
    # always-on step-level black box: a bounded ring of per-step records plus
    # per-request timelines, auto-dumped as JSON on quarantine / watchdog
    # stall / health flip / drain and fetchable via Engine.dump_flight() ->
    # DumpFlight RPC -> GET /debug/flight/{worker}.  Host-side metadata only
    # (never forces a device sync); disable only for A/B overhead benches.
    flight_recorder: bool = True
    flight_ring_size: int = 256
    flight_timeline_keep: int = 64
    # dump destination: None keeps the last dumps in memory (fetchable over
    # RPC); a directory additionally writes reason-tagged JSON files
    flight_dump_dir: str | None = None
    # per-reason dump rate limit (a quarantine storm produces one dump per
    # interval, not one per poisoned request)
    flight_dump_min_interval_secs: float = 5.0

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)
