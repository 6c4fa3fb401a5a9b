"""ModelRunner: owns params, KV buffers, and the bucketed jit step cache.

TPU execution model (SURVEY.md §7 hard part a): XLA compiles one program per
shape, so prefill lengths and decode batch sizes are drawn from fixed bucket
ladders; the runner pads to the bucket, compiles on first use, and donates the
KV buffers every step so updates alias in place.

Parallelism: params/caches carry NamedShardings derived from the model's
logical axes (``smg_tpu/parallel/sharding.py``); GSPMD partitions the step
functions and inserts ICI collectives.  Single-device runs skip sharding.
"""

from __future__ import annotations

import math
import time
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from smg_tpu.analysis.runtime_guards import ProgramAuditor
from smg_tpu.engine import prefill_pack
from smg_tpu.engine.config import EngineConfig
from smg_tpu.engine.donation import kv_donation_policy
from smg_tpu.engine.kv_cache import KvCacheSpec, create_kv_buffers, plan_cache
from smg_tpu.engine.sampling import apply_penalties
from smg_tpu.engine.sampling import sample_tokens as _sample_fast
from smg_tpu.engine.sampling import sample_tokens_exact as _sample_exact
from smg_tpu.engine.spans import StepAccount
from smg_tpu.models.registry import get_model
from smg_tpu.ops.attention import land_side_buffers
from smg_tpu.ops.rope import rope_frequencies
from smg_tpu.parallel.mesh import build_mesh
from smg_tpu.parallel.sharding import (
    ShardingRules,
    logical_to_sharding,
    shard_hint,
    tree_shardings,
)
from smg_tpu.utils import get_logger

logger = get_logger("engine.runner")

# Largest chunk the paged prefill kernel is chosen for.  The kernel keeps the
# whole chunk's K/V lane slice in VMEM (T x 128 lanes, twice, double-buffered);
# 4096 is the top of the default bucket ladder and the largest T that
# tests/test_tpu_compile.py compiles for a v5e.
PREFILL_KERNEL_MAX_T = 4096

# Float32 scores of a cold grouped prefill, ``G x T x H x T x 4`` bytes, up
# to which the XLA form stays the faster one.  Timed alone on a v5e
# (``scripts/time_prefill_attention.py``; the table is ``PERF.md``, Findings,
# PR 38) XLA keeps scores of 96 MiB and less on the chip and beats the
# online-softmax kernel by 1.1 to 3 times there (0.08 against 0.10 ms a layer
# at 1 x 1,024 tokens of 16 heads); from 120 MiB on they go through HBM and
# the kernel wins by 1.8 to 12 times (0.26 against 1.15 ms at 1 x 2,048).
# Nothing between the two sizes was timed, and no program of a model with 16
# or 30 heads falls there.  ``LatentModelRunner`` reads the same constant for
# the expanded latent attention (128 and 64 heads): there the two forms are
# within 2.3 % of each other in the launch whole at 64 MiB, XLA's wins under
# it and the kernel from 128 MiB on (``PERF.md``, Findings, PR 44).
FLASH_PREFILL_MIN_SCORE_BYTES = 96 * 2**20


def _dev(x, dtype, sharding=None) -> jax.Array:
    """Explicit upload for decode hot-path inputs: resident ``jax.Array``s
    pass through untouched (the DecodeState steady-state case — zero
    transfers), host values go up via ``jax.device_put`` so the steady-state
    transfer guard (``jax.transfer_guard("disallow")``) can tell intended
    uploads from accidental ones.

    ``sharding`` (the runner's replicated NamedSharding on a mesh) commits
    host uploads straight to every mesh device: without it an upload lands
    uncommitted on the default device and every sharded jit launch pays an
    IMPLICIT device-to-device reshard — ~10 per step, and the first thing
    the steady-state transfer guard trips on under tp>1."""
    if isinstance(x, jax.Array):
        # a dtype mismatch here means a scheduler path built the wrong
        # buffer; the eager convert below would be an implicit transfer the
        # guard rightly rejects, so keep it visible rather than masked
        return x if x.dtype == dtype else jnp.asarray(x, dtype)
    if sharding is not None:
        return jax.device_put(np.asarray(x, dtype), sharding)
    # smglint: disable-next=SHARDDISC single-device path: mesh is None, there is no commitment target
    return jax.device_put(np.asarray(x, dtype))


def _attn_label(family: str, impl: str) -> str:
    """Key of ``ModelRunner.attn_launches`` for a program of ``family``
    ("prefill" | "decode") traced with attention ``impl``."""
    return f"pallas_{family}" if impl.startswith("pallas") else "xla"


def _pad_rows(a: np.ndarray, G: int, fill=0) -> np.ndarray:
    """Pad a [g, V] array to [G, V] rows filled with ``fill``."""
    a = np.asarray(a)  # smglint: disable=HOTSYNC host-side padding of host rows
    if a.shape[0] == G:
        return a
    out = np.full((G, a.shape[1]), fill, a.dtype)
    out[: a.shape[0]] = a
    return out


def _pad_vec(v: np.ndarray, G: int, fill) -> np.ndarray:
    v = np.asarray(v)  # smglint: disable=HOTSYNC host-side padding of host rows
    if v.shape[0] == G:
        return v
    out = np.full(G, fill, v.dtype)
    out[: v.shape[0]] = v
    return out


def _pick_sampler():
    """SMG_EXACT_SAMPLING=1 selects the full-sort exact sampler (no top-k cap)."""
    import os

    return _sample_exact if os.environ.get("SMG_EXACT_SAMPLING") == "1" else _sample_fast


def one_token_column(step):
    """A decode frame's column (``ModelRunner._decode_frame_fn``) that emits
    one token a lane, from ``step(cur, j, side) -> (logits, side, counts)``:
    the token the loop samples from the logits is the one written and the
    lane's last, and the lane stands one further."""
    def column(cur, j, side, sample):
        logits, side, counts = step(cur, j, side)
        new, lps = sample(logits)
        return new, lps, new, None, side, counts

    return column


class DecodeState:
    """Device-resident steady-state decode inputs.

    The overlapped pipeline re-dispatches decode for an unchanged batch
    composition every step; without this object the scheduler pays ~10
    ``jnp.asarray`` host->device uploads per step for arrays that only change
    on admit/finish/preempt (sampling params, LoRA indices, penalty scalars)
    or on page growth (page tables).  The scheduler keys reuse off
    ``lane_sig`` (lane composition + bucket + feature flags) and ``pt_sig``
    plus its ``_pages_dirty`` flag (page tables); the next step's input
    TOKENS chain device-side from the in-flight frame's last sampled column
    (``InFlightFrame.toks[:, -1]``), so a steady-state lookahead launch
    uploads nothing but a [B] positions vector."""

    __slots__ = (
        "lane_sig", "temps", "topks", "topps", "minps",
        "slot_idx", "freqs", "pres", "reps", "lora_idx", "rope_delta",
        "pt_sig", "page_tables",
        "stop_ids", "limits", "live", "state_slots",
    )

    def __init__(self):
        self.lane_sig = None
        self.temps = self.topks = self.topps = self.minps = None
        self.slot_idx = self.freqs = self.pres = self.reps = None
        self.lora_idx = None
        self.rope_delta = None
        self.pt_sig = None
        self.page_tables = None
        # megastep device-side stop state, uploaded once per composition
        # change: per-lane stop-token id set ([B, E], -1 padded; EOS ids
        # included unless ignore_eos), absolute total-length limits ([B]:
        # min(prompt_len + max_new_tokens, max_seq_len)), and the real-lane
        # mask ([B] bool — padded rows start "done" so they never gate the
        # early exit)
        self.stop_ids = None
        self.limits = None
        self.live = None
        # a recurrent model's state slot of every lane ([B]; 0 for padding)
        self.state_slots = None


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        params=None,
        devices: list | None = None,
    ):
        self.config = config
        self.model_cfg = config.model
        self.module = get_model(self.model_cfg.arch)
        # the prefill entry points mark ``smg.step.admit.pack`` and
        # ``smg.step.admit.dispatch`` on this account; a scheduler puts its
        # own here, a runner driven alone keeps one nobody reads
        self.account = StepAccount()
        # grouped prefill launches, and the host arrays they uploaded
        self.prefill_uploads = {"launches": 0, "arrays": 0}
        # what the grouped prefills computed against what they were asked
        # for (``_group_shape``): tokens of the rows, ``G x T`` of the
        # launches, launches by shape, and the groups that went up in parts
        self.prefill_padding = {"real_tokens": 0, "padded_tokens": 0, "launches": {},
                                "groups_in_parts": 0}
        # serving pp: the layer axis of the param stack AND the KV cache
        # shard over "pp" (parallel/pp_serving.py); each stage holds L/S
        # layers — the capacity path for models that don't fit TP-only
        self.use_pp = config.parallel.pp > 1
        if self.use_pp:
            base = ShardingRules()
            self.rules = ShardingRules(
                rules={**base.rules, "layers": "pp"}
            )
        else:
            self.rules = ShardingRules()

        world = config.parallel.world_size
        self.mesh = build_mesh(config.parallel, devices=devices) if world > 1 else None
        # single-device engines honor an explicit device pin (PD pairs on one
        # host, tests on virtual CPU devices): committing params + KV buffers
        # to the device makes every jit follow them there
        self._device = devices[0] if (devices and world == 1) else None
        # the replicated NamedSharding every non-sharded step input commits
        # to under a mesh: host uploads born mesh-resident cost one explicit
        # h2d broadcast instead of an implicit per-launch reshard
        self._replicated = (
            logical_to_sharding((), self.mesh, self.rules)
            if self.mesh is not None else None
        )

        self.inv_freq = jnp.asarray(
            rope_frequencies(
                # rope_theta 0: the model applies no rotary embedding and
                # never reads these
                self.model_cfg.rope_dim, self.model_cfg.rope_theta or 10000.0,
                self.model_cfg.rope_scaling
            )
        )
        if self._replicated is not None:
            self.inv_freq = jax.device_put(self.inv_freq, self._replicated)

        key = jax.random.PRNGKey(config.seed)
        self.param_shardings = None
        if self.mesh is not None:
            # shape-aware: logical axes whose mesh axis doesn't divide the
            # actual dim (a 2-kv-head model on a tp=4 mesh) replicate that
            # dim instead of failing at trace time
            if params is not None:
                shapes = params
            else:
                shapes = jax.eval_shape(
                    partial(self.module.init_params, self.model_cfg), key
                )
            self.param_shardings = tree_shardings(
                self.module.logical_axes(self.model_cfg), self.mesh, self.rules,
                shapes=shapes,
            )
        if params is not None:
            self.params = params
            if self.mesh is not None:
                # loaded checkpoints arrive as host/default-device arrays;
                # commit them to their shardings ONCE here or every sharded
                # jit call re-scatters the full weights
                self.params = jax.device_put(self.params, self.param_shardings)
            elif self._device is not None:
                self.params = jax.device_put(self.params, self._device)
        elif self.mesh is not None:
            # smglint: disable-next=RETRACE one-shot weight init at construction
            self.params = jax.jit(
                partial(self.module.init_params, self.model_cfg),
                out_shardings=self.param_shardings,
            )(key)
        else:
            # smglint: disable-next=RETRACE one-shot weight init at construction
            self.params = jax.jit(partial(self.module.init_params, self.model_cfg))(key)
            if self._device is not None:
                self.params = jax.device_put(self.params, self._device)

        # the platform the params and cache live on: the attention dispatch,
        # the donation policy and the cache sizing all choose from it
        self.platform = self.local_devices()[0].platform

        # KV cache sizing + buffers.  Sizing inputs are per-device: the
        # tightest device's free HBM and its local parameter shard bytes
        # (GSPMD shards most weights over tp/ep, so global nbytes would
        # over-subtract and under-size the cache).
        param_bytes = self._local_param_bytes()
        self.spec: KvCacheSpec = self._plan_cache(param_bytes)
        kv_sharding = None
        if self.mesh is not None:
            from smg_tpu.models.llama import kv_cache_logical_axes

            kv_sharding = logical_to_sharding(
                kv_cache_logical_axes(), self.mesh, self.rules,
                shape=self.spec.shape,
            )
        elif self._device is not None:
            kv_sharding = jax.sharding.SingleDeviceSharding(self._device)
        self.kv_sharding = kv_sharding
        # XLA decode and verify attention adapt to where the cache's lane
        # axis lies: whole on the device, its products run on the fused
        # lanes; split over a mesh axis, per head (ops.attention
        # ._attend_cache_and_side says why)
        lane_axis = kv_sharding.spec[3] if self.mesh is not None else None
        self.kv_lanes_sharded = (
            lane_axis is not None and self.mesh.shape[lane_axis] > 1)
        self.k_cache, self.v_cache = create_kv_buffers(self.spec, kv_sharding)
        self._create_state_buffers()
        logger.info(
            "kv cache: %d pages x %d tokens (%.1f MiB)",
            self.spec.num_pages,
            self.spec.page_size,
            self.spec.num_pages * self.spec.bytes_per_page / 2**20,
        )

        self.max_pages_per_seq = math.ceil(
            config.scheduler.max_seq_len / config.cache.page_size
        )
        self.attn_impl = self._resolve_attn_impl()
        # launches by attention implementation ("xla", "pallas_prefill",
        # "pallas_decode"): loads()/"/scheduler" report them, so a run can
        # show that each side of the dispatch rule really executed
        self.attn_launches = {"xla": 0, "pallas_prefill": 0, "pallas_decode": 0}
        # per-backend / per-mode KV donation policy (engine/donation.py) —
        # resolved once against where the cache actually lives, replacing
        # PR 2's runner-internal CPU-overlap heuristic
        self.donation = kv_donation_policy(
            self.platform,
            overlap_active=config.scheduler.overlap_schedule,
            sharded=self.mesh is not None,
        )
        logger.info("%s", self.donation.describe())
        # mesh topology is fixed at construction: resolve the device count
        # (the single source the metrics gauge, flight ring, and loads()
        # all read) and the loads()/"/scheduler" snapshot ONCE — loads()
        # rides hot per-dispatch paths (DP replica pick) that must not
        # re-probe devices
        self.mesh_devices = (
            config.parallel.world_size if self.mesh is not None else 1
        )
        self._mesh_info = {
            "devices": self.mesh_devices,
            "shape": config.parallel.axis_sizes(),
            "platform": self.platform,
            "device_kind": self.local_devices()[0].device_kind,
            "donate_kv": self.donation.donate_kv,
            # what each device must hold at rest: its parameter shard and its
            # share of the KV buffers (HBM gauges are read against these)
            "param_bytes_per_device": param_bytes,
            "kv_bytes_per_device": sum(
                x.addressable_shards[0].data.nbytes
                for x in (self.k_cache, self.v_cache)
            ),
        }
        self._rng_key = jax.random.PRNGKey(config.seed ^ 0x5EED)
        if self._replicated is not None:
            self._rng_key = jax.device_put(self._rng_key, self._replicated)
        self._fold_in = None  # jitted fold_in, built on first key (see _next_key)
        self._merge = None  # jitted merge of first tokens (see chain_first_tokens)
        self._step = 0
        self._compiled: dict = {}
        # compiled-program auditor: every jit family below registers through
        # wrap() with its intended donation positions and (mesh mode) the
        # committed in_shardings, so program_audit() can verify commitment /
        # donation-aliasing / recompile provenance from captured launches
        self._programs = ProgramAuditor()
        # Penalty state lives on-device so the decode horizon can update it
        # inside the scan (output counts feed back without host round trips).
        # Lazy: most workloads never set a penalty, and the buffers are
        # [max_batch+1, vocab] (row S is the garbage row for padded slots).
        self._counts_buf = None  # [S+1, V] int32: per-slot output token counts
        self._pmask_buf = None  # [S+1, V] bool: token appeared in the prompt
        # LoRA adapter bank: stacked [L, N, ...] arrays, slot 0 all-zeros
        # ("no adapter"); loading writes a slot in place — no recompile
        self._lora_bank = None
        self._lora_names: dict[str, int] = {}
        self._lora_rank = 0
        # what the decode frame launched last left besides its tokens, on the
        # device (``decode_multi_async`` sets all three; None: the frame has
        # none): ``frame_clean``, whether it met no finish, which a frame
        # launched ahead of it chains on (``RecurrentModelRunner``);
        # ``frame_counts``, the expert layers' int32 counts, one a name of
        # the module's ``ROUTED_COUNTS``; ``frame_tail``, a verify frame's
        # ``(emitted, last, positions, [drafted, accepted])``
        # (``SelfDraftingRunner``)
        self.frame_clean = self.frame_counts = self.frame_tail = None

    def _plan_cache(self, param_bytes: int) -> KvCacheSpec:
        """Size the paged cache from what the tightest device has free."""
        return plan_cache(
            self.model_cfg, self.config.cache, self._detect_hbm(), param_bytes,
            tp=self.config.parallel.tp,
        )

    def _create_state_buffers(self) -> None:
        """Per-sequence state beside the pages: none for a model whose layers
        are all attention (``engine/recurrent_runner.py`` has the other kind)."""
        self.state_spec = None

    def _resolve_attn_impl(self) -> str:
        """What the configured mode can mean on this engine: "xla",
        "pallas", or "auto" (kernels usable; ``_attn_impl_for`` and
        ``_prefill_impl_for`` choose per program from its shapes).  Together
        with those two this is the whole dispatch rule: it reads the config,
        the platform, the mesh and the shapes, and nothing else."""
        mode = self.config.attention_impl
        why = None
        if mode != "auto":
            why = "configured"
            if mode == "pallas" and self.mesh is not None:
                raise ValueError(
                    "attention_impl='pallas' under a mesh: the kernels are "
                    "not wrapped in shard_map and GSPMD cannot partition them"
                )
        elif self.platform != "tpu":
            mode, why = "xla", f"platform is {self.platform}"
        elif self.mesh is not None:
            # Mosaic kernels are not partitioned by GSPMD and the two
            # pallas_calls are not wrapped in shard_map
            mode, why = "xla", "the kernels are not partitioned over a mesh"
        elif self.spec.lanes % 128:
            mode, why = "xla", "the cache's lanes are not a multiple of 128"
        logger.info("attention impl: %s%s", mode, f" ({why})" if why else "")
        return mode

    def invalidate_compiled(self, kind: str | None = None) -> None:
        """Drop compiled step functions (all, or those whose cache key starts
        with ``kind``, e.g. "decode_multi")."""
        if kind is None:
            dropped = list(self._compiled)
            self._compiled.clear()
        else:
            dropped = [k for k in self._compiled if k[0] == kind]
            for k in dropped:
                del self._compiled[k]
        self._programs.forget(dropped)

    def _register(self, k, fn, *, donate, in_shardings, attn: str,
                  products: str | None = None):
        """Cache jitted program ``fn`` under key ``k`` behind the auditor's
        launch wrapper, and count its launches under the attention
        implementation it was traced with (``products``: the form of its
        XLA decode products, where it has them)."""
        launch = self._programs.wrap(k, fn, donate=donate,
                                     in_shardings=in_shardings)
        logger.info("program %s: attention %s%s", k, attn,
                    f" ({products} products)" if products else "")

        def counted(*args):
            self.attn_launches[attn] += 1
            return launch(*args)

        self._compiled[k] = counted
        return counted

    def program_audit(self, *, check_donation: bool = True) -> dict:
        """Audit every cached compiled program from its compiled
        representation (see analysis/runtime_guards.ProgramAuditor): arm
        ``self._programs`` after warmup, run steady-state traffic, then call
        this — ``report["clean"]`` asserts zero uncommitted/mismatched
        inputs and every intended donation verified-aliased."""
        return self._programs.audit(check_donation=check_donation)

    def _attn_impl_for(self, B: int, mp: int) -> str:
        """Decode attention for one (batch bucket, table width) program.
        The XLA path gathers ``B*mp*ps`` tokens of KV per layer, whatever
        the lanes hold, and multiplies them where the gather left them (on
        the fused lanes; per head under a mesh that splits them, which
        copies the gather once more); the kernel streams, for each lane, the
        pages that lane holds.  Where the kernel can run (one TPU chip, no
        mesh, no pp, cache lanes a multiple of 128: ``_resolve_attn_impl``)
        it is the answer at every shape: timed alone on a v5e
        (``scripts/time_decode_attention.py``; the tables are ``PERF.md``
        7.11, PR 30 and PR 48) it moves held bytes at about 755 GB/s with
        0.01-0.04 ms a layer of fixed cost at 1 to 64 lanes, at every page
        width from 256 lanes (2 KV heads of 128) to 3,840 since PR 48 built
        the copies' loops out of a block (before it 215 GB/s at 256 lanes
        and 475 at ``mimo-v2-flash``'s 768 + 512), where XLA reads the
        whole table at about 400 GB/s and four times slower once the
        gather's result passes 64 MiB; at half-full tables it ties at 1
        lane x 8 pages (0.01 ms either way), wins by 2.5 x at 8 x 64 and by
        6.6 x at 16 x 256, and wins at full tables too.  ``B`` and ``mp`` stay in
        the signature because they are what a program is compiled for and
        what the next sweep may have to split on."""
        if self.use_pp:
            return "xla"  # pallas kernels don't run inside the pp shard_map
        if self.attn_impl != "auto":
            return self.attn_impl
        return "pallas"

    def _prefill_impl_for(self, T: int, mp: int) -> str:
        """Solo-prefill attention for one (chunk bucket, table width)
        program.  The XLA path scores every chunk token against all
        ``mp*ps`` table slots, whatever the live prefix is; the kernel
        streams only the prefix pages that hold tokens.  The kernel is
        chosen only at shapes it is known to compile at: 128-lane-sliceable
        heads and ``T <= PREFILL_KERNEL_MAX_T``.  The 2048-slot crossover
        has no measurement on record (ROADMAP D4, S7(1))."""
        if self.use_pp or self.attn_impl == "xla":
            return "xla"
        d = self.model_cfg.head_dim
        c = max(1, 128 // d)
        if self.model_cfg.num_kv_heads % c or (c * d) % 128:
            return "xla"  # lanes not 128-sliceable for the kernel
        if T > PREFILL_KERNEL_MAX_T:
            return "xla"
        if self.attn_impl == "pallas":
            return "pallas"
        return "pallas" if mp * self.spec.page_size > 2048 else "xla"

    def _grouped_prefill_impl_for(self, G: int, T: int, no_ctx: bool) -> str:
        """Attention of one grouped-prefill program of ``G`` rows in the
        ``T``-token bucket.  Behind a cached prefix (``no_ctx`` false) it is
        XLA's over the rows' gathered pages.  When every row is cold the keys
        are the chunk's own and there are two forms: XLA's, which scores the
        whole ``[G, T, H, T]`` square in float32, and the online-softmax
        kernel (``ops/pallas/flash_prefill.py``), which keeps a block of
        scores in VMEM and stops at the diagonal and at a row's length.
        Where kernels can run at all (``_resolve_attn_impl``), the heads are
        whole 128-lane tiles and the model has neither a softcap nor a
        window (the kernel takes none), the kernel is chosen from the size
        on at which XLA's scores no longer stay on the chip
        (``FLASH_PREFILL_MIN_SCORE_BYTES``); ``attention_impl='pallas'``
        forces it at every size."""
        cfg = self.model_cfg
        if (not no_ctx or self.use_pp or self.attn_impl == "xla"
                or cfg.head_dim % 128 or cfg.attn_logit_softcap or cfg.sliding_window):
            return "xla"
        if self.attn_impl != "auto":
            return self.attn_impl
        scores = G * T * cfg.num_heads * T * 4
        return "pallas" if scores > FLASH_PREFILL_MIN_SCORE_BYTES else "xla"

    def _local_param_bytes(self) -> int:
        """Bytes of parameters resident on ONE device (the sizing unit)."""
        leaves = jax.tree.leaves(self.params)
        if self.mesh is not None:
            try:
                return sum(x.addressable_shards[0].data.nbytes for x in leaves)
            except Exception:
                return sum(x.nbytes for x in leaves) // self.config.parallel.world_size
        return sum(x.nbytes for x in leaves)

    def local_devices(self) -> list:
        """Devices this engine occupies (mesh devices, the committed single
        device, or the default device) — the unit HBM gauges sample over."""
        return list(self.mesh.devices.flat) if self.mesh is not None else (
            [self._device] if self._device is not None else jax.devices()[:1]
        )

    def mesh_info(self) -> dict:
        """Mesh topology snapshot for ``loads()`` / ``/scheduler`` and the
        launch banner: device count, per-axis shape (all five named axes),
        the backend platform, and the donation verdict.  Resolved once at
        construction (topology is immutable); the copy keeps callers from
        mutating the cached snapshot."""
        return dict(self._mesh_info)

    @property
    def xla_decode_products(self) -> str:
        """The form XLA decode and verify programs are traced with."""
        return "per_head" if self.kv_lanes_sharded else "fused_lanes"

    def attention_info(self) -> dict:
        """The attention dispatch as ``loads()`` / ``/scheduler`` report it:
        the resolved mode, the form of the XLA decode products, and launches
        so far per implementation."""
        return {"mode": self.attn_impl,
                "xla_decode_products": self.xla_decode_products,
                "launches": dict(self.attn_launches)}

    def _detect_hbm(self) -> int | None:
        """Free HBM on the tightest device this engine will occupy.

        Non-addressable devices (other hosts' chips on a multi-host mesh)
        are skipped.  None means the backend keeps no memory statistics
        (the CPU client), and the cache then has the configured
        ``num_pages``; on a TPU that is a start-up error, because a cache
        sized by a default would serve without a word."""
        free = None
        for d in self.local_devices():
            if d.process_index != jax.process_index():
                continue
            stats = d.memory_stats()
            if not stats or "bytes_limit" not in stats:
                continue
            f = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            free = f if free is None else min(free, f)
        if free is None:
            if self.platform == "tpu":
                raise RuntimeError(
                    "no TPU device of this engine reports memory_stats(); "
                    "cannot size the KV cache"
                )
            if self.config.cache.auto_size:
                logger.info(
                    "platform %s reports no memory statistics: kv cache keeps "
                    "the configured %d pages", self.platform,
                    self.config.cache.num_pages,
                )
        return free

    # ---- penalty slot state ----

    def _ensure_penalty_buffers(self) -> None:
        if self._counts_buf is None:
            S = self.config.scheduler.max_batch_size
            V = self.model_cfg.vocab_size
            if self._replicated is not None:
                # born mesh-resident: the buffers thread through every
                # sharded megastep as replicated in_shardings
                # smglint: disable-next=RETRACE one-shot lazy buffer creation
                zeros = jax.jit(
                    lambda d: jnp.zeros((S + 1, V), d),
                    static_argnums=0, out_shardings=self._replicated,
                )
                self._counts_buf = zeros(jnp.int32)
                self._pmask_buf = zeros(jnp.bool_)
            else:
                self._counts_buf = jnp.zeros((S + 1, V), jnp.int32)
                self._pmask_buf = jnp.zeros((S + 1, V), jnp.bool_)

    def penalty_state(
        self, prompt_ids: list[int], output_ids: list[int]
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host-side (counts [V] int32, prompt_mask [V] bool) for a request."""
        V = self.model_cfg.vocab_size
        ids = np.asarray([t for t in output_ids if 0 <= t < V], np.int64)
        counts = np.bincount(ids, minlength=V).astype(np.int32)
        pmask = np.zeros(V, bool)
        pmask[[t for t in prompt_ids if 0 <= t < V]] = True
        return counts, pmask

    def sync_slot_penalty_state(
        self, slot: int, prompt_ids: list[int], output_ids: list[int]
    ) -> None:
        """(Re)initialize a decode slot's penalty state after admission —
        output counts re-derived host-side so preemption/readmission stays
        exact; thereafter counts update on-device inside the decode scan."""
        self._ensure_penalty_buffers()
        counts, pmask = self.penalty_state(prompt_ids, output_ids)
        self._counts_buf = self._counts_buf.at[slot].set(jnp.asarray(counts))
        self._pmask_buf = self._pmask_buf.at[slot].set(jnp.asarray(pmask))

    # ---- LoRA bank (multi-adapter serving; see models/lora.py) ----

    @property
    def lora_slots(self) -> int:
        return self.config.max_loras + 1  # slot 0 = no adapter

    def lora_index(self, name: str) -> int:
        try:
            return self._lora_names[name]
        except KeyError:
            raise ValueError(f"unknown LoRA adapter {name!r}") from None

    def list_loras(self) -> list[str]:
        return sorted(self._lora_names)

    def load_lora(self, name: str, weights: dict) -> int:
        """Install (or replace) an adapter in the bank; returns its slot."""
        from smg_tpu.models.lora import canonical_keys, validate_adapter

        rank = validate_adapter(self.model_cfg, weights)
        N = self.lora_slots
        if self._lora_bank is None:
            self._lora_rank = rank
            L = self.model_cfg.num_layers
            bank = {}
            for key in canonical_keys():
                shape = (L, N) + weights[key].shape[1:]
                zeros = jnp.zeros(shape, jnp.float32)
                if self._replicated is not None:
                    # mesh-resident bank: the sharded step functions take it
                    # as a replicated in_sharding every launch
                    zeros = jax.device_put(zeros, self._replicated)
                bank[key] = zeros
            self._lora_bank = bank
        if rank > self._lora_rank:
            raise ValueError(
                f"adapter rank {rank} exceeds bank rank {self._lora_rank} "
                f"(first-loaded adapter fixes the bank rank)"
            )
        idx = self._lora_names.get(name)
        if idx is None:
            used = set(self._lora_names.values())
            free = [i for i in range(1, N) if i not in used]
            if not free:
                raise ValueError(f"LoRA bank full ({N - 1} slots)")
            idx = free[0]
        for key in self._lora_bank:  # canonical keys only; ignore npz extras
            w = np.asarray(weights[key], np.float32)
            if rank < self._lora_rank:  # zero-pad smaller ranks into the bank
                pad = self._lora_rank - rank
                axis = 2 if key.endswith("_a") else 1
                pads = [(0, 0)] * w.ndim
                pads[axis] = (0, pad)
                w = np.pad(w, pads)
            self._lora_bank[key] = self._lora_bank[key].at[:, idx].set(
                jnp.asarray(w)
            )
        self._lora_names[name] = idx
        logger.info("lora adapter %r -> slot %d (rank %d)", name, idx, rank)
        return idx

    def unload_lora(self, name: str) -> bool:
        idx = self._lora_names.pop(name, None)
        if idx is None:
            return False
        for key in self._lora_bank:
            self._lora_bank[key] = self._lora_bank[key].at[:, idx].set(0.0)
        return True

    # ---- step function construction ----

    def _next_key(self):
        # the fold runs through a jitted wrapper with the step counter
        # uploaded explicitly: eager fold_in(key, python_int) is an IMPLICIT
        # scalar host->device transfer every launch, which the steady-state
        # transfer guard (analysis/runtime_guards.py) forbids
        self._step += 1
        if self._fold_in is None:
            self._fold_in = jax.jit(jax.random.fold_in)
        return self._fold_in(
            self._rng_key, self._scalar_up(np.uint32(self._step))
        )

    def _scalar_up(self, x) -> jax.Array:
        """Explicit scalar upload, mesh-committed when sharded (an
        uncommitted scalar would be implicitly re-broadcast at every sharded
        jit boundary — the transfer the steady-state guard forbids)."""
        if self._replicated is not None:
            return jax.device_put(x, self._replicated)
        # smglint: disable-next=SHARDDISC single-device path: mesh is None, there is no commitment target
        return jax.device_put(x)

    def upload(self, x, dtype=None) -> jax.Array:
        """Host array -> device-resident decode input, with the engine's
        placement: replicated across the mesh under tp>1 (so the persistent
        ``DecodeState`` buffers match the sharded step functions'
        in_shardings exactly — zero per-launch resharding), the plain
        default-device ``jnp.asarray`` otherwise (byte-identical to the
        pre-sharded path)."""
        if self._replicated is not None:
            # smglint: disable-next=HOTSYNC host-side packing of a host array
            return jax.device_put(np.asarray(x, dtype), self._replicated)
        return jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)

    def _pack_prefill(self, chunks, temps, topks, topps, minps, G: int, T: int,
                      state_slots=None) -> np.ndarray:
        """A grouped prefill's host inputs as the one array its program
        takes apart (``prefill_pack``), with the next key counter in it: the
        value ``_next_key`` would upload, which the program folds itself."""
        self._step += 1
        return prefill_pack.pack(chunks, temps, topks, topps, minps, self._step, G, T,
                                 state_slots=state_slots)

    def _prefill_upload(self, x, dtype=None) -> jax.Array:
        """``upload`` for a grouped prefill's dispatch, counted
        (``loads()["prefill_uploads"]``: one array a launch under plain
        sampling, the packed inputs; the optional arms' arrays beside it)."""
        self.prefill_uploads["arrays"] += 1
        return self.upload(x, dtype)

    def rng_mark(self) -> int:
        """Snapshot the sampling-key counter before a speculative (lookahead)
        dispatch; ``rng_restore`` rewinds it if the dispatch is discarded so
        the replacement call folds the SAME key the synchronous path would
        have used — the invariant behind overlap/sync stream parity."""
        return self._step

    def rng_restore(self, mark: int) -> None:
        self._step = mark

    def _consume_folds(self, n: int) -> int:
        """Advance the sampling-key counter for ``n`` IN-LOOP folds (one per
        megastep column: column j folds counter value mark+1+j on device,
        exactly the key the K=1 path's ``_next_key`` would produce at that
        global step).  Returns the pre-advance mark; the scheduler rewinds to
        ``mark + used`` when a finish trims the horizon so the relaunch
        refolds the same keys the single-step schedule would have."""
        mark = self._step
        self._step += n
        return mark

    def _prefill_fn(self, T: int, mp: int, use_pen: bool = False,
                    use_mask: bool = False, use_lora: bool = False,
                    use_ring: bool = False, use_embeds: bool = False,
                    use_mrope: bool = False):
        impl = "xla" if use_ring else self._prefill_impl_for(T, mp)
        k = ("prefill", T, mp, impl, use_pen, use_mask, use_lora, use_ring,
             use_embeds, use_mrope)
        if k in self._compiled:
            return self._compiled[k]
        cfg = self.model_cfg
        module = self.module
        n_slots = self.lora_slots
        sp_mesh = self.mesh if use_ring else None
        pp_mesh = self.mesh if self.use_pp else None

        def step(params, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table,
                 key, temp, topk, topp, minp, *extra):
            i = 0
            if use_pen:
                counts, pmask, freq, pres, rep = extra[:5]
                i = 5
            mask = None
            if use_mask:
                mask = extra[i]
                i += 1
            lora_bank = lora_gates = None
            if use_lora:
                lora_bank, lora_idx = extra[i], extra[i + 1]
                lora_gates = jax.nn.one_hot(lora_idx, n_slots, dtype=jnp.float32)
                i += 2
            input_embeds = embeds_mask = None
            if use_embeds:
                input_embeds, embeds_mask = extra[i], extra[i + 1]
                i += 2
            rope_pos = None
            if use_mrope:
                rope_pos = extra[i]
            logits, kc, vc = module.forward_prefill(
                params, cfg, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table,
                lora=lora_bank, lora_gates=lora_gates, sp_mesh=sp_mesh,
                attn_impl=impl,
                input_embeds=input_embeds, embeds_mask=embeds_mask,
                pp_mesh=pp_mesh,
                rope_pos=rope_pos,
            )
            logits = logits[None]
            if use_pen:
                with jax.named_scope("smg.sample"):
                    logits = apply_penalties(logits, counts, pmask, freq, pres, rep)
            toks, lps = _pick_sampler()(logits, key, temp, topk, topp, minp, mask=mask)
            return toks[0], lps[0], kc, vc

        n_extra = ((5 if use_pen else 0) + (1 if use_mask else 0)
                   + (2 if use_lora else 0) + (2 if use_embeds else 0)
                   + (1 if use_mrope else 0))
        if self.mesh is not None:
            r = self._replicated
            in_sh = (self.param_shardings, r, r, r, r,
                     self.kv_sharding, self.kv_sharding, r, r, r, r, r, r)
            in_sh = in_sh + (r,) * n_extra
            fn = jax.jit(
                step,
                in_shardings=in_sh,
                out_shardings=(r, r, self.kv_sharding, self.kv_sharding),
                donate_argnums=(5, 6),
            )
        else:
            in_sh = None
            fn = jax.jit(step, donate_argnums=(5, 6))
        return self._register(k, fn, donate=(5, 6), in_shardings=in_sh,
                              attn=_attn_label("prefill", impl))

    def _prefill_extend_fn(self, T: int, mp: int, use_lora: bool = False,
                           use_ring: bool = False, use_embeds: bool = False,
                           use_mrope: bool = False):
        """KV-write-only prefill chunk: a NON-final chunk of a resumable
        (budgeted) prefill writes prompt KV but samples nothing — the lm head
        and sampler are absent from the program (XLA DCEs them), no sampling
        key is folded, and nothing is fetched.  That fold-neutrality is what
        lets the overlap pipeline keep a lookahead decode frame in flight
        while a ``PREFILLING`` request advances: the global key-fold order
        stays exactly the budgeted-sync order (prefill folds only on FINAL
        chunks, which suppress the lookahead for that step)."""
        impl = "xla" if use_ring else self._prefill_impl_for(T, mp)
        k = ("prefill_extend", T, mp, impl, use_lora, use_ring, use_embeds,
             use_mrope)
        if k in self._compiled:
            return self._compiled[k]
        cfg = self.model_cfg
        module = self.module
        n_slots = self.lora_slots
        sp_mesh = self.mesh if use_ring else None
        pp_mesh = self.mesh if self.use_pp else None

        def step(params, inv_freq, tokens, prefix_len, t_real, kc, vc,
                 page_table, *extra):
            i = 0
            lora_bank = lora_gates = None
            if use_lora:
                lora_bank, lora_idx = extra[i], extra[i + 1]
                lora_gates = jax.nn.one_hot(lora_idx, n_slots, dtype=jnp.float32)
                i += 2
            input_embeds = embeds_mask = None
            if use_embeds:
                input_embeds, embeds_mask = extra[i], extra[i + 1]
                i += 2
            rope_pos = extra[i] if use_mrope else None
            _logits, kc, vc = module.forward_prefill(
                params, cfg, inv_freq, tokens, prefix_len, t_real, kc, vc,
                page_table,
                lora=lora_bank, lora_gates=lora_gates, sp_mesh=sp_mesh,
                attn_impl=impl,
                input_embeds=input_embeds, embeds_mask=embeds_mask,
                pp_mesh=pp_mesh,
                rope_pos=rope_pos,
            )
            return kc, vc

        n_extra = ((2 if use_lora else 0) + (2 if use_embeds else 0)
                   + (1 if use_mrope else 0))
        # same CPU-PJRT caveat as decode_multi: a donated input makes CPU
        # dispatch synchronous, and this call exists precisely to stay async
        # under an in-flight decode frame — the donation policy
        # (engine/donation.py) skips donation there
        donate = (5, 6) if self.donation.donate_kv else ()
        if self.mesh is not None:
            r = self._replicated
            in_sh = (self.param_shardings, r, r, r, r,
                     self.kv_sharding, self.kv_sharding, r)
            in_sh = in_sh + (r,) * n_extra
            fn = jax.jit(
                step,
                in_shardings=in_sh,
                out_shardings=(self.kv_sharding, self.kv_sharding),
                donate_argnums=donate,
            )
        else:
            in_sh = None
            fn = jax.jit(step, donate_argnums=donate)
        return self._register(k, fn, donate=donate, in_shardings=in_sh,
                              attn=_attn_label("prefill", impl))

    def _prefill_batched_fn(self, G: int, T: int, mp: int, no_ctx: bool = False,
                            use_pen: bool = False, use_mask: bool = False,
                            use_lora: bool = False, use_embeds: bool = False,
                            use_mrope: bool = False):
        k = ("prefill_batched", G, T, mp, no_ctx, use_pen, use_mask, use_lora,
             use_embeds, use_mrope)
        if k in self._compiled:
            return self._compiled[k]
        cfg = self.model_cfg
        module = self.module
        n_slots = self.lora_slots
        pp_mesh = self.mesh if self.use_pp else None
        impl = self._grouped_prefill_impl_for(G, T, no_ctx)

        def step(params, inv_freq, packed, kc, vc, rng_key, *extra):
            with jax.named_scope("smg.prefill.unpack"):
                (tokens, page_tables, prefix_lens, t_reals, topks, temps, topps, minps,
                 counter, _slots) = prefill_pack.unpack(packed, G, T, mp, slots=False)
                key = jax.random.fold_in(rng_key, counter)
            i = 0
            if use_pen:
                counts, pmask, freqs, pres, reps = extra[:5]
                i = 5
            mask = None
            if use_mask:
                mask = extra[i]
                i += 1
            lora_bank = lora_gates = None
            if use_lora:
                lora_bank, lora_idx = extra[i], extra[i + 1]
                lora_gates = jax.nn.one_hot(lora_idx, n_slots, dtype=jnp.float32)
                i += 2
            input_embeds = embeds_mask = None
            if use_embeds:
                input_embeds, embeds_mask = extra[i], extra[i + 1]
                i += 2
            rope_pos = extra[i] if use_mrope else None
            logits, kc, vc = module.forward_prefill_batched(
                params, cfg, inv_freq, tokens, prefix_lens, t_reals, kc, vc, page_tables,
                no_ctx=no_ctx, lora=lora_bank, lora_gates=lora_gates,
                input_embeds=input_embeds, embeds_mask=embeds_mask,
                rope_pos=rope_pos, pp_mesh=pp_mesh, attn_impl=impl,
            )
            if use_pen:
                with jax.named_scope("smg.sample"):
                    logits = apply_penalties(logits, counts, pmask, freqs, pres, reps)
            toks, lps = _pick_sampler()(logits, key, temps, topks, topps, minps,
                                        mask=mask)
            return toks, lps, kc, vc

        n_extra = ((5 if use_pen else 0) + (1 if use_mask else 0)
                   + (2 if use_lora else 0) + (2 if use_embeds else 0)
                   + (1 if use_mrope else 0))
        if self.mesh is not None:
            r = self._replicated
            in_sh = (self.param_shardings, r, r, self.kv_sharding, self.kv_sharding, r)
            in_sh = in_sh + (r,) * n_extra
            fn = jax.jit(
                step,
                in_shardings=in_sh,
                out_shardings=(r, r, self.kv_sharding, self.kv_sharding),
                donate_argnums=(3, 4),
            )
        else:
            in_sh = None
            fn = jax.jit(step, donate_argnums=(3, 4))
        return self._register(k, fn, donate=(3, 4), in_shardings=in_sh,
                              attn=_attn_label("prefill", impl))

    def prefill_batched(self, chunks, *args, **kw) -> tuple[np.ndarray, np.ndarray]:
        """Prefill several single-chunk sequences in one call:
        ``prefill_batched_async`` and the fetch of what it sampled.
        Returns (tokens [G_real], logprobs [G_real])."""
        return self.fetch_first_tokens(
            self.prefill_batched_async(chunks, *args, **kw), len(chunks))

    @staticmethod
    def fetch_first_tokens(parts: list, g_real: int) -> tuple[np.ndarray, np.ndarray]:
        """Materialise what ``prefill_batched_async`` dispatched, in the
        group's own row order."""
        got = jax.device_get([(t, l) for _rows, t, l in parts])  # intended blocking fetch
        toks, lps = np.zeros(g_real, np.int32), np.zeros(g_real, np.float32)
        for (rows, _t, _l), (t, l) in zip(parts, got):
            toks[rows], lps[rows] = t[: len(rows)], l[: len(rows)]
        return toks, lps

    def _prefill_rung(self, chunks) -> int:
        """The padded length of one grouped launch.  A single cold row takes
        the ladder's finest rung: the padding is all its own, and the rung
        is one program.  Every other launch takes the octave of its longest
        row (``SchedulerConfig.coarse_prefill_buckets``), where a rung is a
        program for every group size and for the rows behind a prefix."""
        sched = self.config.scheduler
        longest = max(len(c[0]) for c in chunks)
        if len(chunks) == 1 and chunks[0][1] == 0:
            return sched.prefill_bucket(longest)
        return sched.coarse_prefill_bucket(longest)

    def _group_shape(self, chunks) -> tuple[int, int]:
        """``(G, T)`` of the program that one launch of ``chunks`` runs, the
        rows rounded up to a power of two; counted in ``prefill_padding``."""
        G = 1 << (len(chunks) - 1).bit_length()
        T = self._prefill_rung(chunks)
        pad, shape = self.prefill_padding, f"{G}x{T}"
        pad["real_tokens"] += sum(len(c[0]) for c in chunks)
        pad["padded_tokens"] += G * T
        pad["launches"][shape] = pad["launches"].get(shape, 0) + 1
        return G, T

    def _split_group(self, lengths: "list[int]") -> "list[list[int]]":
        """The rows of a group as the launches that run them.  A group is
        padded to ``G x T``, both rounded up, so one long prompt among short
        ones makes a program of up to four times the step's token budget:
        more than ``_plan_cache`` keeps room for, mostly padding, and a
        program for every such shape.  A group whose padded size passes the
        budget goes up in parts of as many rows as fit it at the longest
        row's octave, in the group's order."""
        most = self._rows_inside_budget(
            self.config.scheduler.coarse_prefill_bucket(max(lengths)))
        rows = list(range(len(lengths)))
        return [rows[k:k + most] for k in range(0, len(rows), most)]

    def _rows_inside_budget(self, T: int) -> int:
        """The most rows of ``T`` padded tokens, a power of two as a program's
        rows are, that stay inside the step's budget (one row at least)."""
        most = 1
        while 2 * most * T <= self.config.scheduler.max_prefill_tokens:
            most *= 2
        return most

    def prefill_batched_async(
        self,
        chunks: "list[tuple[list[int], int, np.ndarray]]",  # (token_ids, prefix_len, page_table_row)
        temps: np.ndarray,  # [G_real]
        topks: np.ndarray,
        topps: np.ndarray,
        minps: np.ndarray,
        pen: tuple | None = None,  # (counts [G_real,V], pmask [G_real,V], freqs, pres, reps)
        mask: np.ndarray | None = None,  # [G_real, V] bool
        lora_idx: np.ndarray | None = None,  # [G_real] adapter slot per row
        mm: "list[tuple | None] | None" = None,  # per-row (dense [t,E], bool [t])
        rope: "list[np.ndarray | None] | None" = None,  # per-row [3, t] M-RoPE ids
        **per_row: "np.ndarray | None",  # [G_real] each: what a runner's launch takes besides
    ) -> "list[tuple[np.ndarray, jax.Array, jax.Array]]":
        """Dispatch the grouped prefill and return its first tokens
        UNMATERIALISED, as the launches that computed them: a list of
        ``(rows, tokens [G], logprobs [G])`` where ``rows`` names the
        members of ``chunks`` that the launch's first ``len(rows)`` rows
        hold.  One launch (``_launch_group``) for each part of the group
        (``_split_group``), each folding the next sampling key.
        ``fetch_first_tokens`` brings them to the host;
        ``chain_first_tokens`` hands them to a decode launch on the device."""
        split = self._split_group([len(c[0]) for c in chunks])
        self.prefill_padding["groups_in_parts"] += len(split) > 1
        parts = []
        for rows in split:
            rows = np.array(rows, np.int64)
            take = lambda a: None if a is None else np.take(a, rows, axis=0)
            some = lambda v: None if v is None else [v[i] for i in rows]
            toks, lps = self._launch_group(
                some(chunks), take(temps), take(topks), take(topps), take(minps),
                None if pen is None else tuple(take(a) for a in pen),
                take(mask), take(lora_idx), some(mm), some(rope),
                **{k: take(v) for k, v in per_row.items()})
            parts.append((rows, toks, lps))
        return parts

    def _launch_group(self, chunks, temps, topks, topps, minps, pen, mask, lora_idx, mm,
                      rope) -> "tuple[jax.Array, jax.Array]":
        """One part of ``prefill_batched_async`` as one launch: its tokens
        and logprobs ``[G]``, still on the device."""
        with self.account.span("smg.step.admit.pack"):
            G, T = self._group_shape(chunks)
            mp = len(chunks[0][2])
            no_ctx = all(c[1] == 0 for c in chunks)
            use_lora = lora_idx is not None and self._lora_bank is not None
            use_embeds = mm is not None and any(m is not None for m in mm)
            use_mrope = rope is not None and any(r is not None for r in rope)
            fn = self._prefill_batched_fn(G, T, mp, no_ctx,
                                          use_pen=pen is not None,
                                          use_mask=mask is not None,
                                          use_lora=use_lora,
                                          use_embeds=use_embeds,
                                          use_mrope=use_mrope)
            packed = self._pack_prefill(chunks, temps, topks, topps, minps, G, T)
        with self.account.span("smg.step.admit.dispatch"):
            up = self._prefill_upload
            self.prefill_uploads["launches"] += 1
            args = [self.params, self.inv_freq, up(packed), self.k_cache, self.v_cache,
                    self._rng_key]
            if pen is not None:
                counts, pmask, freqs, pres, reps = pen
                args += [
                    up(_pad_rows(counts, G).astype(np.int32)),
                    up(_pad_rows(pmask, G)),
                    up(_pad_vec(freqs, G, 0.0), jnp.float32),
                    up(_pad_vec(pres, G, 0.0), jnp.float32),
                    up(_pad_vec(reps, G, 1.0), jnp.float32),
                ]
            if mask is not None:
                args.append(up(_pad_rows(mask, G, fill=True)))
            if use_lora:
                args += [
                    self._lora_bank,
                    up(_pad_vec(np.asarray(lora_idx, np.int32), G, 0)),
                ]
            if use_embeds:
                E = next(m[0].shape[1] for m in mm if m is not None)
                dense = np.zeros((G, T, E), np.float32)
                emask = np.zeros((G, T), bool)
                for i, m in enumerate(mm):
                    if m is not None:
                        d, bm = m
                        dense[i, : d.shape[0]] = d
                        emask[i, : bm.shape[0]] = bm
                args += [up(dense), up(emask)]
            if use_mrope:
                # default rows: all three axes = sequential position, which makes
                # apply_mrope EXACTLY apply_rope for the text rows in the group
                prefix_lens = _pad_vec(np.array([c[1] for c in chunks], np.int32), G, 0)
                rp = np.broadcast_to(
                    (prefix_lens[:, None] + np.arange(T))[:, None, :], (G, 3, T)
                ).astype(np.int32).copy()
                for i, r in enumerate(rope):
                    if r is not None:
                        rp[i, :, : r.shape[1]] = r
                args.append(up(rp))
            toks, lps, self.k_cache, self.v_cache = fn(*args)
        return toks, lps

    def chain_first_tokens(self, tokens: np.ndarray, owner: np.ndarray,
                           parts: list) -> jax.Array:
        """The ``tokens`` [B] input of a decode launch dispatched behind a
        grouped prefill whose first tokens are still on the device:
        ``tokens`` as the host knows it, with each lane whose ``owner`` [B]
        is not -1 taking that member's token from ``parts``
        (``prefill_batched_async``).  One small program a part, keyed by
        (B, the part's padded rows); every upload is explicit, so the
        steady-state transfer guard stays green."""
        up = self._replicated
        col = _dev(tokens, jnp.int32, up)
        for rows, toks, _lps in parts:
            src = np.full(len(tokens), -1, np.int32)
            for j, i in enumerate(rows):
                src[owner == i] = j
            if (src >= 0).any():
                col = self._merge_fn()(col, _dev(src, jnp.int32, up), toks)
        return col

    def _merge_fn(self):
        """``where(src >= 0, first[src], col)``: jitted once, compiled for
        each (B, G) it meets (``warmup`` meets them all)."""
        if self._merge is None:
            def merge(col, src, first):
                picked = first.astype(jnp.int32)[jnp.maximum(src, 0)]
                return jnp.where(src >= 0, picked, col)
            r = self._replicated
            placed = {} if r is None else {"in_shardings": (r, r, r), "out_shardings": r}
            self._merge = jax.jit(merge, **placed)
        return self._merge

    def _decode_multi_fn(self, B: int, mp: int, N: int, E: int = 0,
                         use_pen: bool = False, use_mask: bool = False,
                         use_lora: bool = False, use_mrope: bool = False):
        """The Llama family's decode frame, for ``_decode_frame_fn``'s loop:
        K and V side buffers ``[L, B, N, KD]`` that land in the pages at the
        frame's end.  ``use_lora`` adds the adapter bank and the rows' adapter
        indices, ``use_mrope`` a [B] rope position delta (M-RoPE decode: the
        text axes are equal, so the offset rides the standard rope path)."""
        cfg, module = self.model_cfg, self.module
        KD = cfg.num_kv_heads * cfg.head_dim
        L = cfg.num_layers
        mesh, rules = self.mesh, self.rules
        pp_mesh = self.mesh if self.use_pp else None
        kv_lanes_sharded = self.kv_lanes_sharded

        def frame(params, inv_freq, entry_pos, kc, vc, page_tables, *, attn_impl, arms):
            hk0 = jnp.zeros((L, B, N, KD), kc.dtype)
            hv0 = jnp.zeros((L, B, N, KD), kc.dtype)
            # align the horizon KV carry with the cache's lane sharding so
            # the final scatter is shard-local — without the hint the SPMD
            # partitioner is free to replicate the carry and all-gather at
            # the scatter (layers/kv_lanes mirror kv_cache_logical_axes)
            hk0 = shard_hint(hk0, ("layers", None, None, "kv_lanes"), mesh, rules)
            hv0 = shard_hint(hv0, ("layers", None, None, "kv_lanes"), mesh, rules)

            def column(cur, j, side):
                logits, hk, hv = module.forward_decode_horizon(
                    params, cfg, inv_freq, cur, entry_pos + j, entry_pos, j,
                    kc, vc, page_tables, *side, attn_impl=attn_impl,
                    lora=arms.lora, lora_gates=arms.lora_gates, pp_mesh=pp_mesh,
                    rope_delta=arms.rope_delta, kv_lanes_sharded=kv_lanes_sharded)
                return logits, (hk, hv), None

            def land(side, ran, _last):
                # one scatter into the donated cache; columns that did not run
                # and positions past the table go to the reserved garbage page
                return land_side_buffers(kc, vc, *side, page_tables, entry_pos, ran), None

            return (hk0, hv0), one_token_column(column), land

        return self._decode_frame_fn(B, mp, N, E, use_pen, use_mask, frame,
                                     arms=(use_lora, use_mrope))

    def _bind_moe_impl(self, impl: str) -> None:
        """Bind the implementation of the expert layers' grouped products to
        the module's forwards, so that every program family calls them as it
        calls a model without experts (``LatentModelRunner``,
        ``WindowModelRunner``)."""
        self.moe_impl, module = impl, self.module
        self.module = SimpleNamespace(**{
            **vars(module),
            **{f: partial(getattr(module, f), moe_impl=impl)
               for f in ("forward_prefill", "forward_prefill_batched",
                         "forward_decode_horizon", "forward_verify_column",
                         "forward_mtp_prefill", "forward_mtp_column", "forward_mtp_draft")
               if hasattr(module, f)}})

    def _decode_frame_fn(self, B: int, mp: int, N: int, E: int, use_pen: bool,
                         use_mask: bool, frame, *, variant: tuple = (),
                         arms: "tuple[bool, bool] | None" = None, n_held: int = 0,
                         donate_held: tuple = (), chained: bool = False, W: int = 1):
        """The decode MEGASTEP, every runner's: up to N columns fused into
        one jitted ``lax.while_loop`` with in-loop sampling-key folds and
        device-side stop detection.  Sampled tokens feed back on the device,
        so a frame costs the host one dispatch and one fetch whatever its
        width, and the loop bound ``n_steps`` rides a device scalar, so ONE
        trace per batch bucket serves every K <= N (compile time does not
        scale with the horizon).

        **The frame** is the runner's: ``frame(params, inv_freq, entry_pos,
        kc, vc, page_tables, *held, attn_impl=, arms=) -> (side0, column,
        land)``.  What a sequence holds besides its pages is ``n_held``
        arguments behind ``page_tables`` (those at ``donate_held`` donated).
        ``side0`` is whatever the frame carries from column to column (its
        empty side buffers, and anything else of its own: the loop never
        looks inside).  ``column(cur, j, side, sample)`` runs column ``j``
        behind the lanes' last tokens ``cur`` and returns ``(tokens, logprobs,
        last, reach, side, counts)``: the up to ``W`` tokens a lane it wrote
        (``[B]``, or ``[B, W]``), each lane's last token, where each lane
        then stands (None for a column of one token a lane: ``entry_pos + j
        + 1``), and the expert layers' counts (None without
        ``module.ROUTED_COUNTS``, which the loop then does not carry).
        ``sample(logits)`` is the loop's, once a column: penalties, the
        column's key, the sampler, the count.  ``one_token_column`` makes a
        column from ``(cur, j, side) -> (logits, side, counts)``.
        ``land(side, ran, last) -> (caches, tail)`` gives the caches as the
        program returns them (``ran`` [1, N]: the columns run) and what the
        frame hands the host besides (None: nothing).  ``arms`` is what the
        frame may read of the loop's arguments: ``lora``, ``lora_gates``,
        ``rope_delta`` (None where off), ``temps``, and the stop state as
        ``ends(tokens)`` and ``limits`` (None where ``E`` is 0).

        **The key**: ``("decode_multi", B, mp, N, E, attn_impl, *variant,
        use_pen, use_mask)`` and, for the frame that has them (``arms``
        given), ``use_lora, use_mrope``.

        Byte-parity with the single-step path at any temperature: column j
        folds ``fold_in(base_key, step0 + 1 + j)`` — exactly the key
        ``_next_key`` would have produced at that global step — so a megastep
        is indistinguishable from K consecutive single-step launches.

        Device-side stop detection (``E > 0``): a per-lane done mask tracks
        stop-token hits ([B, E] id set: EOS + stop_token_ids) and the
        absolute length limit ([B]); the loop EXITS at the first column where
        any real lane finishes (padded lanes start done and never gate it).
        Because the host trims acceptance at the earliest finish anyway (the
        K=1-equivalence rule), exiting at the FIRST done lane strictly
        subsumes per-lane freezing: no token beyond the exit column is ever
        computed, so a finish inside a large horizon wastes nothing.

        ``use_pen`` threads the per-slot [S+1, V] output-count/prompt-mask
        buffers through the loop (counts update on-device as tokens are
        sampled, so penalties stay exact across the horizon — and exact
        under a trim, since every computed column is an accepted column).
        ``use_mask`` adds a [B, V] constrained-decoding vocab mask; the
        scheduler forces N=1 for masked batches since the mask is
        host-derived per token.

        ``chained``: the last held argument is ``chain``, a device bool
        (true for a frame that chains on none).  A frame launched ahead of
        one that met a finish then runs no column at all, and the program
        returns ``clean``: whether a frame chained on this one may run.

        The program returns ``(tokens [B, N(, W)], logprobs, steps_run,
        *caches, extras)``; ``extras`` holds ``counts_buf``, ``clean``,
        ``routed`` and ``tail`` where the frame has them
        (``decode_multi_async`` is the one caller)."""
        use_stop = E > 0
        use_lora, use_mrope = arms or (False, False)
        attn_impl = self._attn_impl_for(B, mp)
        k = ("decode_multi", B, mp, N, E, attn_impl, *variant, use_pen, use_mask,
             *(arms or ()))
        if k in self._compiled:
            return self._compiled[k]
        module = self.module
        n_slots = self.lora_slots
        routed_names = getattr(module, "ROUTED_COUNTS", ())
        wide = () if W == 1 else (W,)

        def multi(params, inv_freq, tokens, entry_pos, kc, vc, page_tables, *rest):
            held = rest[:n_held]
            base_key, step0, n_steps, temps, topks, topps, minps, *extra = rest[n_held:]
            i = 0
            if use_pen:
                counts_buf, pmask_buf, slot_idx, freqs, pres, reps = extra[:6]
                i = 6
            mask = None
            if use_mask:
                mask = extra[i]
                i += 1
            lora_bank = lora_gates = None
            if use_lora:
                lora_bank, lora_idx = extra[i], extra[i + 1]
                with jax.named_scope("smg.frame.begin"):
                    lora_gates = jax.nn.one_hot(lora_idx, n_slots, dtype=jnp.float32)
                i += 2
            rope_delta = None
            if use_mrope:
                rope_delta = extra[i]
                i += 1
            ends = limits = None
            if use_stop:
                stop_ids, limits, live = extra[i], extra[i + 1], extra[i + 2]
                ends = lambda t: jnp.any(t[:, None] == stop_ids, axis=1)
            if chained:
                chain = held[-1]
                with jax.named_scope("smg.frame.begin"):
                    n_steps = jnp.where(chain, n_steps, 0)
            # the frame's own parts under ``smg.frame.*``: a trace's readers
            # find an operation's scope in the program's scope map
            # (``ProgramAuditor.scope_map``), and what the frame does besides
            # the model's columns is then ``runner.decode_frame_time_share``
            with jax.named_scope("smg.frame.begin"):
                side0, column, land = frame(
                    params, inv_freq, entry_pos, kc, vc, page_tables, *held,
                    attn_impl=attn_impl,
                    arms=SimpleNamespace(lora=lora_bank, lora_gates=lora_gates,
                                         rope_delta=rope_delta, temps=temps, ends=ends,
                                         limits=limits))
                counts0 = counts_buf[slot_idx] if use_pen else jnp.zeros((B, 0))
                pmask = pmask_buf[slot_idx] if use_pen else None
                # padded lanes start done so the any-real-lane-done exit ignores
                # them; without stop detection nothing is ever done
                done0 = (~live) if use_stop else jnp.zeros((B,), jnp.bool_)
                routed0 = (jnp.zeros((len(routed_names),), jnp.int32)
                           if routed_names else None)
                init = (jnp.int32(0), tokens, jnp.zeros((B, N, *wide), jnp.int32),
                        jnp.zeros((B, N, *wide), jnp.float32), side0, counts0, done0,
                        routed0)
            sampler = _pick_sampler()

            @jax.named_scope("smg.frame.emit")
            def cond(carry):
                j, done = carry[0], carry[6]
                ok = j < n_steps
                if use_stop:
                    # first finish ends the horizon: the host accepts nothing
                    # past it (K=1 equivalence), so further columns are waste
                    ok = jnp.logical_and(ok, ~jnp.any(done & live))
                return ok

            def body(carry):
                j, cur, toks_out, lps_out, side, counts, done, routed = carry

                def sample(logits):
                    nonlocal counts
                    if use_pen:
                        with jax.named_scope("smg.frame.penalties"):
                            logits = apply_penalties(logits, counts, pmask, freqs, pres,
                                                     reps)
                    # the IN-LOOP fold: column j's key is the key the K=1 path
                    # folds at global step step0+1+j (then split(.., 1)[0], the
                    # same per-launch split the single-step scan applied)
                    with jax.named_scope("smg.sample"):
                        kj = jax.random.split(jax.random.fold_in(
                            base_key, step0 + j.astype(jnp.uint32) + jnp.uint32(1)), 1)[0]
                    new, lps = sampler(logits, kj, temps, topks, topps, minps, mask=mask)
                    if use_pen:
                        with jax.named_scope("smg.frame.penalties"):
                            counts = counts.at[jnp.arange(B), new].add(1)
                    return new, lps

                toks, lps, last, reach, side, c = column(cur, j, side, sample)
                with jax.named_scope("smg.frame.emit"):
                    if routed_names:
                        routed = module.merge_counts(routed, c)
                    at = (0, j) + (0,) * len(wide)
                    toks_out = lax.dynamic_update_slice(
                        toks_out, toks[:, None].astype(jnp.int32), at)
                    lps_out = lax.dynamic_update_slice(
                        lps_out, lps[:, None].astype(jnp.float32), at)
                    if use_stop:
                        # length finish: a lane that stands at ``reach`` holds
                        # reach + 1 tokens in all (decode steady state: total =
                        # seq + 1), so it is done once reach >= limit - 1; a
                        # one-token column's reach is entry_pos + j + 1
                        over = ((entry_pos + j) >= (limits - 2) if reach is None
                                else reach >= (limits - 1))
                        done = done | ends(last) | over
                    return (j + 1, last, toks_out, lps_out, side, counts, done, routed)

            steps_run, last, outs, lps, side, counts, done, routed = \
                lax.while_loop(cond, body, init)
            with jax.named_scope("smg.frame.land"):
                caches, tail = land(side, jnp.arange(N)[None, :] < steps_run, last)
                extras = {}
                if use_pen:
                    extras["counts_buf"] = counts_buf.at[slot_idx].set(counts)
                if chained:
                    extras["clean"] = chain & ~jnp.any(done & live) if use_stop else chain
                if routed_names:
                    extras["routed"] = routed
                if tail is not None:
                    extras["tail"] = tail
            return (outs, lps, steps_run, *caches, extras)

        n_extra = ((6 if use_pen else 0) + (1 if use_mask else 0)
                   + (2 if use_lora else 0) + (1 if use_mrope else 0)
                   + (3 if use_stop else 0))
        # KV donation aliases the cache update in place — essential on TPU
        # (cache is a large fraction of HBM), and under GSPMD each device
        # aliases its local cache shard.  The per-backend/per-mode rules
        # (CPU-PJRT blocks dispatch on donated inputs, which would serialize
        # the overlapped pipeline) live in engine/donation.py.
        donate = (4, 5, *(7 + i for i in donate_held)) + ((14 + n_held,) if use_pen else ())
        if not self.donation.donate_kv:
            donate = ()
        in_sh = None
        placed = {}
        if self.mesh is not None:
            # a mesh is the Llama frame's alone: nothing held, K and V out
            r = self._replicated
            in_sh = (self.param_shardings, r, r, r, self.kv_sharding, self.kv_sharding,
                     r) + (r,) * (n_held + 7 + n_extra)
            placed = {"in_shardings": in_sh, "out_shardings": (
                r, r, r, self.kv_sharding, self.kv_sharding,
                {"counts_buf": r} if use_pen else {})}
        return self._register(k, jax.jit(multi, donate_argnums=donate, **placed),
                              donate=donate, in_shardings=in_sh,
                              attn=_attn_label("decode", attn_impl),
                              products=(self.xla_decode_products
                                        if attn_impl == "xla" else None))

    def decode_multi_async(
        self,
        tokens,  # [B] int32 (np OR device array — device chaining is free)
        positions,  # [B] int32
        page_tables,  # [B, mp] int32
        temps,
        topks,
        topps,
        minps,
        num_steps: int,
        max_steps: int | None = None,
        stop_state: tuple | None = None,  # (stop_ids [B,E], limits [B], live [B])
        pen: tuple | None = None,  # (slot_idx [B], freqs [B], pres [B], reps [B])
        mask: np.ndarray | None = None,  # [B, V] bool
        lora_idx=None,  # [B] adapter slot per row (0 = none)
        rope_delta=None,  # [B] M-RoPE decode offsets
        state_slots=None,  # [B] each lane's state slot (0: the lane does not run)
        chain=None,  # ``frame_clean`` of the frame this one is launched ahead of
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Dispatch a decode megastep and return UNMATERIALIZED result arrays
        (tokens [B, N], logprobs [B, N], steps_run scalar) where
        N = ``max_steps or num_steps`` is the COMPILED width — the trace is
        keyed on N, and the per-launch ``num_steps`` (<= N) rides a device
        scalar, so an adaptive horizon never retraces.  JAX async dispatch
        means this returns as soon as the computation is enqueued — the
        overlapped scheduler consumes last step's tokens while this one runs.
        Every input accepts either numpy (uploaded once) or a resident
        ``jax.Array`` (``jnp.asarray`` is a no-op), which is how the
        ``DecodeState`` buffers avoid per-step uploads.

        ``stop_state`` (required when N > 1) arms device-side stop detection;
        the loop early-exits at the first finishing lane.  The launch
        consumes ``num_steps`` sampling-key folds (one per column, in-loop);
        the caller rewinds the unused tail via ``rng_restore(mark + used)``
        when a finish trims the horizon.

        ``state_slots`` and ``chain`` are for the runners whose sequences
        hold a slot beside their pages (``_frame_state_args``); ``chain``
        None is a frame that follows a consumed one.  After the call
        ``frame_clean``, ``frame_counts`` and ``frame_tail`` are this
        frame's."""
        B, mp = page_tables.shape
        N = max_steps or num_steps
        use_pen = pen is not None
        use_mask = mask is not None
        use_lora = lora_idx is not None and self._lora_bank is not None
        use_mrope = rope_delta is not None
        E = 0
        if N > 1:
            if stop_state is None:
                raise ValueError(
                    "decode megastep with N > 1 requires stop_state — the "
                    "device-side done mask is what keeps a multi-step "
                    "horizon byte-identical to K=1"
                )
            E = stop_state[0].shape[1]
        fn = self._decode_multi_fn(B, mp, N, E, use_pen, use_mask, use_lora,
                                   use_mrope)
        # the megastep folds its own keys in-loop: consume num_steps counter
        # values and upload the pre-advance mark; column j folds mark+1+j,
        # exactly _next_key's value at that global step
        mark = self._consume_folds(num_steps)
        if state_slots is None:
            # a caller that knows nothing of slots: every lane on the garbage slot
            state_slots = np.zeros(B, np.int32)
        # _dev: resident DecodeState buffers pass through (zero transfers in
        # steady state); host inputs upload EXPLICITLY — committed to the
        # mesh when sharded — so the transfer guard can police this launch
        # path
        up = self._replicated
        args = [
            self.params,
            self.inv_freq,
            _dev(tokens, jnp.int32, up),
            _dev(positions, jnp.int32, up),
            self.k_cache,
            self.v_cache,
            _dev(page_tables, jnp.int32, up),
            *self._frame_state_args(state_slots, chain),
            self._rng_key,
            self._scalar_up(np.uint32(mark)),
            self._scalar_up(np.int32(num_steps)),
            _dev(temps, jnp.float32, up),
            _dev(topks, jnp.int32, up),
            _dev(topps, jnp.float32, up),
            _dev(minps, jnp.float32, up),
        ]
        if use_pen:
            self._ensure_penalty_buffers()
            slot_idx, freqs, pres, reps = pen
            args += [
                self._counts_buf,
                self._pmask_buf,
                _dev(slot_idx, jnp.int32, up),
                _dev(freqs, jnp.float32, up),
                _dev(pres, jnp.float32, up),
                _dev(reps, jnp.float32, up),
            ]
        if use_mask:
            args.append(_dev(mask, jnp.bool_, up))
        if use_lora:
            args += [self._lora_bank, _dev(lora_idx, jnp.int32, up)]
        if use_mrope:
            args.append(_dev(rope_delta, jnp.int32, up))
        if E:
            stop_ids, limits, live = stop_state
            args += [
                _dev(stop_ids, jnp.int32, up),
                _dev(limits, jnp.int32, up),
                _dev(live, jnp.bool_, up),
            ]
        toks, lps, steps_run, self.k_cache, self.v_cache, *state, extras = fn(*args)
        self._take_frame_state(state)
        if use_pen:
            self._counts_buf = extras["counts_buf"]
        self.frame_clean = extras.get("clean")
        self.frame_counts = extras.get("routed")
        self.frame_tail = extras.get("tail")
        return toks, lps, steps_run

    def _frame_state_args(self, state_slots, chain) -> list:
        """What a decode program takes between the page tables and the key:
        nothing, for a model whose sequences hold pages alone."""
        return []

    def _take_frame_state(self, state: list) -> None:
        """Rebind what a decode program returns behind the pages."""
        assert not state

    def decode_multi(
        self,
        tokens: np.ndarray,
        positions: np.ndarray,
        page_tables: np.ndarray,  # [B, mp]
        temps: np.ndarray,
        topks: np.ndarray,
        topps: np.ndarray,
        minps: np.ndarray,
        num_steps: int,
        max_steps: int | None = None,
        stop_state: tuple | None = None,
        pen: tuple | None = None,
        mask: np.ndarray | None = None,
        lora_idx: np.ndarray | None = None,
        rope_delta: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Synchronous decode horizon: dispatch + blocking fetch.
        Returns (tokens [B, n], logprobs [B, n]) where n is the number of
        columns the device loop actually ran — num_steps unless a caller
        -provided ``stop_state`` early-exited the loop (columns past the
        exit are never computed and are not returned).

        Runner-level callers (benches, tests) have no scheduler stop state;
        a multi-step call without one gets a neutral never-done mask so the
        loop runs the full horizon (n == num_steps) — overshoot semantics
        identical to the pre-megastep scan."""
        if stop_state is None and (max_steps or num_steps) > 1:
            B = page_tables.shape[0]
            stop_state = (
                np.full((B, 1), -1, np.int32),  # no stop ids
                np.full(B, np.int32(2**30)),  # unreachable length limit
                np.ones(B, bool),
            )
        toks, lps, steps = self.decode_multi_async(
            tokens, positions, page_tables, temps, topks, topps, minps,
            num_steps, max_steps=max_steps, stop_state=stop_state,
            pen=pen, mask=mask, lora_idx=lora_idx, rope_delta=rope_delta,
        )
        # intended blocking fetch
        toks, lps, steps = jax.device_get((toks, lps, steps))
        n = int(steps)
        return toks[:, :n], lps[:, :n]

    # ---- host-facing API ----

    def _chunk_bucket(self, n_tokens: int) -> int:
        """Padded length of a solo or continuing prefill chunk: an octave."""
        return self.config.scheduler.coarse_prefill_bucket(n_tokens)

    def _prefill_chunk_prep(
        self, token_ids, prefix_len, page_table, lora_idx, mm, rope_pos
    ):
        """Shared host-side packing/validation for one prefill chunk — the
        invariants the sampling (``prefill``) and KV-only
        (``prefill_extend``) entry points must never diverge on — inside
        the span ``smg.step.admit.pack``.  Nothing is uploaded here:
        ``_chunk_args`` does that, inside the caller's
        ``smg.step.admit.dispatch``.

        - Bucket padding: chunk padded to the prefill token bucket.
        - Scheduler invariant the Pallas prefill kernel relies on: every
          chunk token's position must fit the page table (the kernel attends
          tokens past capacity where the XLA path drops them — divergence
          documented at ops/pallas/prefill_attention.py).  Fail loudly here
          instead of producing path-dependent attention.
        - Sequence-parallel prefill: cold chunks (the long-context case — a
          huge first chunk is exactly what sp exists for) ring-attend with
          the token dim sharded over sp; warm chunks need the cache gather.
        Returns (T, mp, use_lora, use_ring, host) where ``host`` is what
        ``_chunk_args`` uploads."""
        with self.account.span("smg.step.admit.pack"):
            t = len(token_ids)
            T = self._chunk_bucket(t)
            tokens = np.zeros(T, np.int32)
            tokens[:t] = token_ids
            mp = len(page_table)
            ps = self.config.cache.page_size
            if prefix_len + t > mp * ps:
                raise ValueError(
                    f"prefill chunk overruns page table: prefix {prefix_len} + "
                    f"chunk {t} > {mp} pages * {ps}"
                )
            use_lora = lora_idx > 0 and self._lora_bank is not None
            sp = self.config.parallel.sp
            use_ring = (
                self.mesh is not None and sp > 1 and prefix_len == 0 and T % sp == 0
                and not self.use_pp  # ring + pp composition is future work
            )
            if rope_pos is not None and use_ring:
                raise ValueError("M-RoPE does not compose with ring prefill yet")
            # a COPY of the row: the caller hands a view of the scheduler's
            # table, the CPU client may alias host memory instead of copying
            # it, and ``prefill_extend`` returns before the program has run;
            # a preemption that zeroes the row in the same step would send
            # the chunk's KV to the garbage page
            host = {"tokens": tokens, "prefix_len": prefix_len, "t": t,
                    "page_table": np.array(page_table, np.int32),
                    "lora_idx": lora_idx if use_lora else None}
            if mm is not None:
                embeds, emask = mm
                pe = np.zeros((T, embeds.shape[1]), np.float32)
                pe[:t] = embeds
                pm = np.zeros(T, bool)
                pm[:t] = emask
                host["mm"] = (pe, pm)
            if rope_pos is not None:
                rp = np.zeros((3, T), np.int32)
                rp[:, :t] = rope_pos
                host["rope"] = rp
        return T, mp, use_lora, use_ring, host

    def _chunk_args(self, host: dict) -> tuple[list, list]:
        """Upload what ``_prefill_chunk_prep`` packed: ``(base_args,
        tail_args)``, the common [params..page_table] prefix of a chunk
        program's arguments and the lora/mm/rope suffix in extra-arg order."""
        up = self.upload  # mesh-replicated commit under tp>1; jnp.asarray else
        base_args = [
            self.params,
            self.inv_freq,
            up(host["tokens"]),
            up(host["prefix_len"], jnp.int32),
            up(host["t"], jnp.int32),
            self.k_cache,
            self.v_cache,
            up(host["page_table"]),
        ]
        tail_args = []
        if host["lora_idx"] is not None:
            tail_args += [self._lora_bank, up(host["lora_idx"], jnp.int32)]
        if "mm" in host:
            tail_args += [up(x) for x in host["mm"]]
        if "rope" in host:
            tail_args.append(up(host["rope"]))
        return base_args, tail_args

    def prefill(
        self,
        token_ids: list[int],
        prefix_len: int,
        page_table: np.ndarray,  # [<= max_pages_per_seq] int32
        temperature: float,
        top_k: int,
        top_p: float,
        min_p: float,
        pen: tuple | None = None,  # (counts [V], pmask [V], freq, pres, rep) scalars
        mask: np.ndarray | None = None,  # [V] bool
        lora_idx: int = 0,  # adapter slot (0 = none)
        mm: tuple | None = None,  # (embeds [t, E] f32, emask [t] bool) mm splice
        rope_pos: "np.ndarray | None" = None,  # [3, t] M-RoPE position ids
    ) -> tuple[int, float]:
        """Run one prefill chunk; returns (sampled_token, logprob)."""
        T, mp, use_lora, use_ring, host = self._prefill_chunk_prep(
            token_ids, prefix_len, page_table, lora_idx, mm, rope_pos
        )
        fn = self._prefill_fn(T, mp, use_pen=pen is not None,
                              use_mask=mask is not None, use_lora=use_lora,
                              use_ring=use_ring, use_embeds=mm is not None,
                              use_mrope=rope_pos is not None)
        with self.account.span("smg.step.admit.dispatch"):
            base_args, tail_args = self._chunk_args(host)
            args = base_args + self._solo_sampling_args(
                temperature, top_k, top_p, min_p, pen, mask) + tail_args
            tok, lp, self.k_cache, self.v_cache = fn(*args)
        return self._fetch_solo(tok, lp)

    def _solo_sampling_args(self, temperature, top_k, top_p, min_p, pen, mask) -> list:
        """A solo sampling prefill's arguments behind the page table: the
        folded key, the four sampling scalars, then ``pen`` and ``mask``."""
        up = self.upload
        args = [
            self._next_key(),
            up([temperature], jnp.float32),
            up([top_k], jnp.int32),
            up([top_p], jnp.float32),
            up([min_p], jnp.float32),
        ]
        if pen is not None:
            counts, pmask, freq, pres, rep = pen
            args += [
                up(counts, jnp.int32)[None],
                up(pmask)[None],
                up([freq], jnp.float32),
                up([pres], jnp.float32),
                up([rep], jnp.float32),
            ]
        if mask is not None:
            args.append(up(mask)[None])
        return args

    def _fetch_solo(self, tok, lp) -> tuple[int, float]:
        """The blocking fetch of a solo prefill's token: it proves the
        newest launch done (``spans.StepAccount``)."""
        out = int(tok), float(lp)
        self.account.fetched(self.account.launched)
        return out

    def prefill_extend(
        self,
        token_ids: list[int],
        prefix_len: int,
        page_table: np.ndarray,  # [<= max_pages_per_seq] int32
        lora_idx: int = 0,
        mm: tuple | None = None,  # (embeds [t, E] f32, emask [t] bool)
        rope_pos: "np.ndarray | None" = None,  # [3, t] M-RoPE position ids
    ) -> None:
        """Write one NON-final prefill chunk's KV and return immediately
        (async dispatch; nothing sampled, no key fold, nothing fetched).
        The budgeted scheduler advances a ``PREFILLING`` request's cursor
        with this between steps; the FINAL chunk goes through ``prefill``,
        which samples the first token."""
        T, mp, use_lora, use_ring, host = self._prefill_chunk_prep(
            token_ids, prefix_len, page_table, lora_idx, mm, rope_pos
        )
        fn = self._prefill_extend_fn(T, mp, use_lora=use_lora,
                                     use_ring=use_ring,
                                     use_embeds=mm is not None,
                                     use_mrope=rope_pos is not None)
        with self.account.span("smg.step.admit.dispatch"):
            base_args, tail_args = self._chunk_args(host)
            self.k_cache, self.v_cache = fn(*(base_args + tail_args))

    def warmup(self) -> list[tuple[str, float]]:
        """Compile and run, once each, the largest solo-prefill,
        grouped-prefill and decode programs the scheduler can launch for a
        request with default features, so that a program which cannot
        compile, or does not fit beside the cache, stops the process at
        start-up instead of failing every long request later.  (Optional
        features — penalties, masks, LoRA, embeddings, speculation — still
        compile on first use.)  Last, every shape of the merge that a decode
        launch behind a grouped prefill runs (``chain_first_tokens``).

        Page tables are all zero and decode positions sit past the table, so
        every KV write lands on the garbage page; the sampling-key counter
        is restored.  Returns ``(program, seconds)`` pairs: set-up time on
        the host clock, compilation included."""
        sched = self.config.scheduler
        mp = self.max_pages_per_seq
        slots = mp * self.config.cache.page_size
        t = min(sched.max_prefill_tokens, sched.max_seq_len - 1)
        ids = [0] * t
        table = np.zeros(mp, np.int32)
        # a full group splitting the step's budget evenly, each member behind
        # one cached token so that the program gathers context.  (A skewed
        # group pads to more rows x tokens than this; its attention is
        # bounded by ops.attention.SCORE_BLOCK_BYTES all the same.)
        G = min(sched.max_prefill_group, t)
        group = [(ids[: t // G], 1, table)] * G
        B = sched.decode_bucket(sched.max_batch_size)
        N = sched.horizon_cap
        zeros, ones = np.zeros(B, np.float32), np.ones(B, np.float32)

        def merges():
            # tiny, and one for every (decode bucket, padded group or part of
            # one) a launch behind a grouped prefill can meet: none of them
            # may compile inside traffic
            g, sizes = 1, []
            while g < 2 * sched.max_prefill_group:
                sizes.append(g)
                g *= 2
            for b in (b for b in sched.decode_batch_buckets if b <= B):
                for g in sizes:
                    first = self.upload(np.zeros(g, np.int32))
                    self.chain_first_tokens(
                        np.zeros(b, np.int32), np.zeros(b, np.int32),
                        [(np.arange(1), first, None)])

        steps = {
            "prefill_extend": lambda: self.prefill_extend(ids, 0, table),
            "prefill": lambda: self.prefill(ids, 0, table, 0.0, -1, 1.0, 0.0),
            "prefill_batched": lambda: self.prefill_batched(
                group, zeros[:G], np.full(G, -1, np.int32), ones[:G], zeros[:G]
            ),
            "decode_multi": lambda: self.decode_multi(
                np.zeros(B, np.int32), np.full(B, slots, np.int32),
                np.zeros((B, mp), np.int32), zeros, np.full(B, -1, np.int32),
                ones, zeros, num_steps=N, max_steps=N,
            ),
            "chain_first_tokens": merges,
        }
        mark = self.rng_mark()
        took = []
        for name, run in steps.items():
            t0 = time.perf_counter()
            run()
            jax.block_until_ready((self.k_cache, self.v_cache))
            took.append((name, time.perf_counter() - t0))
            logger.info("warmup: largest %s program ran (%.1fs with "
                        "compilation)", name, took[-1][1])
        self.rng_restore(mark)
        return took

    def _decode_spec_fn(self, B: int, mp: int, W: int, use_mrope: bool = False):
        """The fused speculative VERIFY megastep: score a W-token draft block
        for every lane in ONE forward, accept on device, and scatter only the
        accepted columns' KV into the cache (rejected columns go to the
        garbage page).  The spec analogue of ``_decode_multi_fn``: where the
        decode megastep runs K serial in-loop forwards for K tokens, this
        program yields up to W tokens per lane for ONE weight pass — the
        classic draft-verify win on a bandwidth-bound decode — while sharing
        the megastep's conventions: the launch consumes a sampling-key
        counter fold (column-0's ``fold_in(base, mark+1)``, exactly the key a
        K=1 launch would fold at that global step; ``InFlightFrame.folds``
        rewinds it when the frame is discarded), positions past the page
        table scatter to the garbage page, and padded batch rows are inert.

        Acceptance per lane (per-lane ``draft_n`` rides a device scalar, so
        variable drafting never retraces):

        - temperature == 0: greedy chain — accept drafted column c+1 while it
          equals the argmax after column c; the first mismatch's argmax is
          the correction token.  Token-identical to plain greedy decode.
        - temperature > 0: ``sampling.spec_accept_sample`` vmapped over lanes
          (per-lane split keys) — distribution-preserving rejection sampling
          specialized to the deterministic draft.

        Returns (emitted [B, W] int32, n_emit [B] int32, caches): lane b's
        tokens are ``emitted[b, :n_emit[b]]`` (accepted drafts + the
        bonus/correction sample); columns past ``n_emit`` are unset."""
        k = ("decode_spec", B, mp, W, use_mrope)
        if k in self._compiled:
            return self._compiled[k]
        cfg = self.model_cfg
        module = self.module

        def spec(params, inv_freq, tokens, draft_n, entry_pos, kc, vc,
                 page_tables, base_key, step0, temps, topks, topps, minps,
                 *extra):
            from smg_tpu.engine.sampling import spec_accept_sample

            rope_delta = extra[0] if use_mrope else None
            logits, bk, bv = module.forward_verify_block(
                params, cfg, inv_freq, tokens, entry_pos, kc, vc, page_tables,
                rope_delta=rope_delta, kv_lanes_sharded=self.kv_lanes_sharded,
            )  # [B, W, V], [L, B, W, KD] x2
            # same lane-sharding hint as the megastep's horizon carry: keep
            # the accepted-column scatter shard-local against the kv cache
            bk = shard_hint(bk, ("layers", None, None, "kv_lanes"),
                            self.mesh, self.rules)
            bv = shard_hint(bv, ("layers", None, None, "kv_lanes"),
                            self.mesh, self.rules)
            props = tokens[:, 1:]  # [B, W-1] drafted columns
            greedy = temps <= 0.0
            # greedy chain: accept while draft matches the running argmax
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, W]
            cols = jnp.arange(W - 1)
            match = (props == g[:, :-1]) & (cols[None, :] < draft_n[:, None])
            n_acc_g = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
            final_g = jnp.take_along_axis(g, n_acc_g[:, None], axis=1)[:, 0]
            # sampled lanes: rejection sampling, one split key per lane off
            # the launch fold (column-0's megastep key)
            kj = jax.random.fold_in(base_key, step0 + jnp.uint32(1))
            keys = jax.random.split(kj, B)
            safe_t = jnp.where(greedy, 1.0, temps)  # discarded for greedy rows

            def one(row_logits, row_props, k_real, key, t, tk, tp, m):
                return spec_accept_sample(row_logits, row_props, k_real, key,
                                          t, tk, tp, m)

            final_s, n_acc_s = jax.vmap(one)(
                logits, props, draft_n, keys, safe_t, topks, topps, minps
            )
            n_acc = jnp.where(greedy, n_acc_g, n_acc_s).astype(jnp.int32)
            final = jnp.where(greedy, final_g, final_s).astype(jnp.int32)
            # emitted row: accepted drafts then the bonus/correction token
            c = jnp.arange(W)[None, :]
            props_pad = jnp.concatenate(
                [props, jnp.zeros((B, 1), jnp.int32)], axis=1
            )
            emitted = jnp.where(c < n_acc[:, None], props_pad, 0)
            emitted = jnp.where(c == n_acc[:, None], final[:, None], emitted)
            n_emit = n_acc + 1
            # per-token logprobs, OpenAI semantics (log softmax of the RAW
            # logits at the emitted token — same rule as sampling.py):
            # emitted column c was chosen from column c's distribution
            all_lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            lps = jnp.take_along_axis(
                all_lp, emitted[..., None].astype(jnp.int32), axis=-1
            )[..., 0]
            # KV discipline: column c's K/V (input token at entry+c) lands in
            # its real slot only when that token is COMMITTED — c=0 is the
            # already-committed y0, c>=1 iff the draft was accepted.  Every
            # rejected column and every out-of-table position masks to the
            # garbage page, so a bad draft can never poison a real slot.
            kc, vc = land_side_buffers(
                kc, vc, bk, bv, page_tables, entry_pos, c <= n_acc[:, None]
            )
            return emitted, n_emit, lps, kc, vc

        donate = (5, 6) if self.donation.donate_kv else ()
        if self.mesh is not None:
            r = self._replicated
            in_sh = (self.param_shardings, r, r, r, r,
                     self.kv_sharding, self.kv_sharding, r, r, r, r, r, r, r)
            in_sh = in_sh + ((r,) if use_mrope else ())
            fn = jax.jit(spec, in_shardings=in_sh,
                         out_shardings=(r, r, r, self.kv_sharding,
                                        self.kv_sharding),
                         donate_argnums=donate)
        else:
            in_sh = None
            fn = jax.jit(spec, donate_argnums=donate)
        return self._register(k, fn, donate=donate, in_shardings=in_sh,
                              attn="xla", products=self.xla_decode_products)

    def decode_spec_async(
        self,
        tokens,  # [B, W] int32: [last_committed, drafts..., pad]
        draft_n,  # [B] int32 valid drafts per lane (0 = plain 1-token decode)
        positions,  # [B] int32 entry positions (= seq_len per lane)
        page_tables,  # [B, mp] int32
        temps,
        topks,
        topps,
        minps,
        rope_delta=None,  # [B] M-RoPE decode offsets
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Dispatch one fused draft-verify block and return UNMATERIALIZED
        (emitted [B, W], n_emit [B], logprobs [B, W]).  Consumes exactly ONE
        sampling-key
        counter fold (the caller's frame records ``folds=1`` so a discarded
        frame rewinds it); per-lane draft counts ride device scalars, so the
        trace is keyed only on (B, mp, W).  All uploads are explicit
        ``device_put``s — the steady-state transfer guard stays clean with
        speculation on."""
        B, mp = page_tables.shape
        W = tokens.shape[1]
        use_mrope = rope_delta is not None
        fn = self._decode_spec_fn(B, mp, W, use_mrope)
        mark = self._consume_folds(1)
        up = self._replicated
        args = [
            self.params,
            self.inv_freq,
            _dev(tokens, jnp.int32, up),
            _dev(draft_n, jnp.int32, up),
            _dev(positions, jnp.int32, up),
            self.k_cache,
            self.v_cache,
            _dev(page_tables, jnp.int32, up),
            self._rng_key,
            self._scalar_up(np.uint32(mark)),
            _dev(temps, jnp.float32, up),
            _dev(topks, jnp.int32, up),
            _dev(topps, jnp.float32, up),
            _dev(minps, jnp.float32, up),
        ]
        if use_mrope:
            args.append(_dev(rope_delta, jnp.int32, up))
        emitted, n_emit, lps, self.k_cache, self.v_cache = fn(*args)
        return emitted, n_emit, lps

    @property
    def kv_transfer(self):
        """Lazy per-runner TransferManager (cross-host KV pulls)."""
        if getattr(self, "_kv_transfer", None) is None:
            from smg_tpu.engine.kv_transfer import TransferManager

            device = next(iter(self.k_cache.devices()))
            self._kv_transfer = TransferManager(device)
        return self._kv_transfer

    @property
    def supports_kv_transfer(self) -> bool:
        """True when this engine can serve/accept cross-host KV pulls —
        single-device legs only (sharded multi-controller pulls are future
        work; see engine/kv_transfer.py)."""
        from smg_tpu.engine.kv_transfer import transfer_available

        return transfer_available() and self.mesh is None

    def export_pages(self, pages: "list[int]") -> tuple[np.ndarray, np.ndarray]:
        """Fetch KV pages to host: ([L, n, ps, KD] k, v).

        PD disaggregation fallback path (host-mediated).  On multi-chip
        deployments the production path moves pages device-to-device over
        ICI/DCN (jax device transfer) — this host round trip is the portable
        seam the connector abstraction plugs into (reference analogue:
        NIXL/Mooncake connectors, request_execution.rs:38-82)."""
        idx = jnp.asarray(pages, jnp.int32)
        k = jax.device_get(self.k_cache[:, idx])  # intended fetch (KV export)
        v = jax.device_get(self.v_cache[:, idx])
        return k, v

    def import_pages(self, pages: "list[int]", k: np.ndarray, v: np.ndarray) -> None:
        """Scatter host KV pages into the device cache at ``pages``."""
        idx = jnp.asarray(pages, jnp.int32)
        self.k_cache = self.k_cache.at[:, idx].set(jnp.asarray(k, self.k_cache.dtype))
        self.v_cache = self.v_cache.at[:, idx].set(jnp.asarray(v, self.v_cache.dtype))

    def export_pages_device(self, pages: "list[int]") -> tuple:
        """Gather KV pages as on-device jax.Arrays ([L, n, ps, KD] k, v).

        The gather copies into fresh arrays, so the source pages can be freed
        immediately; the payload stays resident on this engine's devices until
        the decode engine lands it with ``import_pages_device`` (device
        connector, SURVEY.md §7.5 ICI/DCN KV movement)."""
        idx = jnp.asarray(pages, jnp.int32)
        return self.k_cache[:, idx], self.v_cache[:, idx]

    def import_pages_device(self, pages: "list[int]", k, v) -> None:
        """Land a device KV payload on this cache's devices and scatter it.

        ``jax.device_put`` performs the cross-device (or cross-mesh reshard)
        copy — ICI within a slice, DCN across slices — with no host round
        trip, replacing the reference's NIXL/Mooncake RDMA transfer."""
        idx = jnp.asarray(pages, jnp.int32)
        if self.kv_sharding is not None:
            dst = self.kv_sharding
        else:
            dst = next(iter(self.k_cache.devices()))
        k = jax.device_put(k, dst)
        v = jax.device_put(v, dst)
        self.k_cache = self.k_cache.at[:, idx].set(k.astype(self.k_cache.dtype))
        self.v_cache = self.v_cache.at[:, idx].set(v.astype(self.v_cache.dtype))

    def embed(self, batches: "list[list[int]]") -> np.ndarray:
        """Sequence embeddings for a batch of token-id lists: [n, hidden]."""
        n = len(batches)
        B = 1
        while B < n:
            B *= 2
        cap = max(self.config.scheduler.prefill_token_buckets)
        if self.model_cfg.sliding_window:
            # forward_embed's shared layer body has no per-layer window
            # alternation: bound REAL lengths (not the padded bucket) to
            # the window, where global == local exactly
            cap = min(cap, self.model_cfg.sliding_window)
        # embeddings truncate at the context budget (OpenAI-style) rather than fail
        batches = [b[:cap] for b in batches]
        t_max = max(len(b) for b in batches)
        T = self.config.scheduler.coarse_prefill_bucket(t_max)
        tokens = np.zeros((B, T), np.int32)
        lengths = np.zeros(B, np.int32)
        for i, ids in enumerate(batches):
            tokens[i, : len(ids)] = ids
            lengths[i] = len(ids)
        key = ("embed", B, T)
        if key not in self._compiled:
            cfg = self.model_cfg
            module = self.module
            fn = jax.jit(
                lambda params, inv_freq, toks, lens: module.forward_embed(
                    params, cfg, inv_freq, toks, lens
                )
            )
            in_sh = None
            if self.mesh is not None:
                r = self._replicated
                in_sh = (self.param_shardings, r, r, r)
            self._compiled[key] = self._programs.wrap(
                key, fn, donate=(), in_shardings=in_sh
            )
        out = self._compiled[key](
            self.params, self.inv_freq,
            self.upload(tokens), self.upload(lengths),
        )
        return jax.device_get(out)[:n]  # intended blocking fetch

    def flush_cache_buffers(self) -> None:
        """Zero the KV buffers (used by flush_cache after the radix reset)."""
        self.k_cache, self.v_cache = create_kv_buffers(self.spec, self.kv_sharding)

    # What the scheduler asks a runner (``Scheduler._mp_bucket``): whether
    # decode programs exist at the widest page table alone.  The Llama family
    # says no.
    widest_table_only = False
