"""The runner of a model whose layers are not all attention.

``RecurrentModelRunner`` is ``ModelRunner`` with a second kind of
per-sequence memory: beside the pages of the full-attention layers every
sequence holds one **state slot** (``kv_cache.StateSlotPool`` hands them out;
the two device pools are ``s_pool`` and ``c_pool``, laid out as the module's
``state_shapes`` says: ``models/olmo_hybrid.py``'s gated delta rule,
``models/nemotron_h.py``'s state-space layers, ``models/kimi_linear.py``'s
Kimi Delta Attention).  What the recurrent layers' decode step is, and whether
its kernel fits the model's shape, is the module's to say (``decode_step``); a
module with routed experts beside its state has their grouped products bound
as the latent runner binds them and its frames carry the routed counts
(``ROUTED_COUNTS``).  **What the pages hold is data too** (``spec``): K and V
of the full-attention layers, or, for a model whose cache is latent
(``kimi_linear``), one entry a token and no V: ``v_cache`` is then of zero
size and goes through the prefill families untouched, the decode frame
carries one side buffer where it carries two, landed through
``land_side_buffer``, and ``loads()`` has ``latent_cache`` beside the state
slots.  It builds the same four program
families under the same names and positional signatures (``prefill``,
``prefill_extend``, ``prefill_batched``, ``decode_multi_async``); the slot of
each row arrives as a keyword whose default is the garbage slot 0, so a caller
that knows nothing of slots (a warm-up) compiles and runs exactly the programs
the scheduler then launches.

Prefill starts a sequence from zero state when its chunk starts at position 0
and carries the state in its slot from chunk to chunk otherwise.  A decode
frame advances the state of its lanes in place, column by column; a lane on
the garbage slot does not run.  A frame launched ahead of the one before it
(the overlapped schedule's lookahead) runs no column when that frame met a
finish, because the scheduler then throws the lookahead away and its tokens
must not have reached the state: ``frame_clean`` carries that from launch to
launch on the device.

What this runner refuses: everything in the module's ``SERVING_LIMITS``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from functools import partial

from smg_tpu.engine import prefill_pack
from smg_tpu.engine.kv_cache import plan_recurrent_cache
from smg_tpu.engine.latent_runner import LatentModelRunner
from smg_tpu.engine.runner import (
    ModelRunner,
    _attn_label,
    _dev,
    _pick_sampler,
    logger,
    one_token_column,
)
from smg_tpu.ops.attention import land_side_buffers
from smg_tpu.ops.latent_attention import land_side_buffer


class RecurrentModelRunner(ModelRunner):
    # a decode frame changes what the slots hold and cannot take it back: a
    # frame thrown away, or trimmed short of what the device ran, costs its
    # lanes their state (``Scheduler._state_lost``)
    frames_advance_state = True

    def __init__(self, config, params=None, devices=None):
        super().__init__(config, params=params, devices=devices)
        if hasattr(self.module, "ROUTED_COUNTS"):
            # the expert layers' grouped products: the kernel on a TPU, XLA's
            # ragged product elsewhere.  ``moe_info`` is the scheduler's sign
            # that this runner's frames carry routed counts
            self._bind_moe_impl("pallas" if self.platform == "tpu"
                                and config.attention_impl != "xla" else "xla")
            self.moe_info = self._moe_info
            logger.info("expert layers %s, experts held %s of %d", self.moe_impl,
                        self.model_cfg.held_experts, self.model_cfg.num_experts)
        if self.spec.latent_lanes:
            # the scheduler's sign that ``loads()`` has a latent cache to show:
            # the latent runner's account of the same layout
            self.latent_info = partial(LatentModelRunner.latent_info, self)

    def _moe_info(self) -> dict:
        cfg = self.model_cfg
        return {"experts": cfg.num_experts, "experts_held": cfg.held_experts[1],
                "top_k": cfg.num_experts_per_tok, "impl": self.moe_impl}

    # ---- what a sequence holds ----

    def _plan_cache(self, param_bytes: int):
        """Slots first, pages from what is left (``plan_recurrent_cache``).
        The device is read after the weights are on it."""
        cfg, sched = self.model_cfg, self.config.scheduler
        if self.mesh is not None:
            raise ValueError(self.module.SERVING_LIMITS["mesh"])
        limit = in_use = None
        stats = self.local_devices()[0].memory_stats() or {}
        if "bytes_limit" in stats:
            limit, in_use = stats["bytes_limit"], stats.get("bytes_in_use", 0)
        elif self.platform == "tpu":
            raise RuntimeError("the TPU reports no memory_stats(); cannot size the caches")
        workspace = getattr(self.module, "prefill_workspace_bytes", None)
        spec, self.state_spec = plan_recurrent_cache(
            cfg, self.config.cache, sched.max_batch_size + sched.max_prefill_group,
            self.module.state_shapes, limit, in_use,
            workspace(cfg, sched.max_prefill_tokens, self.config.dtype) if workspace else 0)
        return spec

    def _create_state_buffers(self) -> None:
        st = self.state_spec
        self.s_pool = jnp.zeros(st.state_shape, jnp.float32)
        self.c_pool = jnp.zeros(st.conv_shape, jnp.dtype(st.conv_dtype))
        if self._device is not None:
            self.s_pool = jax.device_put(self.s_pool, self._device)
            self.c_pool = jax.device_put(self.c_pool, self._device)
        # what a frame that chains on none is given for the ``frame_clean``
        # of the frame before it (see the module docstring)
        self._unchained = self._scalar_up(np.bool_(True))
        # the recurrent layers' decode step, as the module describes it: the
        # kernel on a TPU where its blocks fit the state's shape, the XLA
        # form elsewhere
        self.state_step = step = self.module.decode_step(self.model_cfg)
        self.state_kernel_fits = step["kernel_fits"]
        self.state_impl = ("pallas" if self.platform == "tpu" and self.state_kernel_fits
                           and self.config.attention_impl != "xla" else "xla")
        logger.info(
            "state slots: %d x %.1f MiB (%d %s layers), decode step %s; "
            "%s for %d full-attention layers",
            st.num_slots - 1, st.slot_bytes / 2**20, st.state_shape[0], step["layers"],
            self.state_impl, "latent pages" if self.spec.latent_lanes else "pages",
            self.spec.num_layers)

    # the names ``benchmark/architectures/olmo_hybrid.py`` reads the two by
    linattn_impl = property(lambda self: self.state_impl)
    linattn_kernel_fits = property(lambda self: self.state_kernel_fits)

    def state_info(self) -> dict:
        """Slots, a slot's bytes and which decode step runs, under the
        module's name for it (``linattn_decode``, ``ssm_decode``,
        ``kda_decode``)."""
        st = self.state_spec
        return {"slots_total": st.num_slots - 1, "slot_bytes": st.slot_bytes,
                self.state_step["name"]: self.state_impl}

    @property
    def widest_table_only(self) -> bool:
        """Decode programs are compiled at the widest page table alone where
        the paged kernel runs: it reads each lane's own pages by its
        ``entry`` whatever the table's width, and the recurrent layers read
        no table (the latent and the window runners' rule).  Only decode
        frames take a table bucket (``Scheduler._mp_bucket``): a prefill's
        table is the whole one either way."""
        return self._attn_impl_for(0, 0) == "pallas"

    def _prefill_impl_for(self, T: int, mp: int) -> str:
        """A chunk that continues a prompt runs on XLA attention here: the
        paged prefill kernel takes 3.2 s for a 4,096-token chunk where the
        XLA form takes a tenth of that (PERF.md 7.3h), and every lane waits
        meanwhile.  ``attention_impl='pallas'`` still forces the kernel."""
        if self.attn_impl == "pallas":
            return super()._prefill_impl_for(T, mp)
        return "xla"

    def _grouped_prefill_impl_for(self, G: int, T: int, no_ctx: bool) -> str:
        """The rule of the kind of cache the model has: the Llama path's for
        K and V pages, the latent runner's for latent pages (the kernel takes
        the key the heads share as an operand of its own)."""
        if self.spec.latent_lanes:
            return LatentModelRunner._grouped_prefill_impl_for(self, G, T, no_ctx)
        return super()._grouped_prefill_impl_for(G, T, no_ctx)

    def _prefill_rung(self, chunks) -> int:
        """A module may take the ladder's octaves alone (``OCTAVE_RUNGS_ONLY``:
        a rung between two of them is then no program of its own, and a single
        cold row pads to the next octave as every other launch does)."""
        if getattr(self.module, "OCTAVE_RUNGS_ONLY", False):
            return self.config.scheduler.coarse_prefill_bucket(max(len(c[0]) for c in chunks))
        return super()._prefill_rung(chunks)

    def _chunk_bucket(self, n_tokens: int) -> int:
        """Every second octave of the ladder, from the top: a prompt cut by a
        step's budget is rare, and each bucket is a program of 5 MB that takes
        10-20 s to compile (of this model's programs the compile cache a chip
        machine keeps, 192 MiB, holds about forty)."""
        ladder = self.config.scheduler.coarse_prefill_buckets[::-1][::2]
        return min((b for b in ladder if b >= n_tokens), default=ladder[0])

    def flush_cache_buffers(self) -> None:
        super().flush_cache_buffers()
        self._create_state_buffers()

    # ---- refusals ----

    def load_lora(self, name, weights):
        raise ValueError(self.module.SERVING_LIMITS["lora"])

    def embed(self, batches):
        raise ValueError(self.module.SERVING_LIMITS["embeddings"])

    @property
    def supports_kv_transfer(self) -> bool:
        return False

    def _no_transfer(self, *_a, **_k):
        raise ValueError(self.module.SERVING_LIMITS["kv_transfer"])

    export_pages = import_pages = export_pages_device = import_pages_device = _no_transfer

    def _decode_spec_fn(self, *_a, **_k):
        raise ValueError(self.module.SERVING_LIMITS["speculative"])

    # ---- programs ----

    def _plain(self, what: str, **flags) -> None:
        on = [k for k, v in flags.items() if v]
        if on:
            raise ValueError(f"{self.model_cfg.arch} {what} does not take {', '.join(on)}")

    def _prefill_fn(self, T: int, mp: int, use_pen: bool = False,
                    use_mask: bool = False, use_lora: bool = False,
                    use_ring: bool = False, use_embeds: bool = False,
                    use_mrope: bool = False):
        self._plain("prefill", lora=use_lora, ring=use_ring, embeds=use_embeds, mrope=use_mrope)
        impl = self._prefill_impl_for(T, mp)
        k = ("prefill", T, mp, impl, use_pen, use_mask)
        if k in self._compiled:
            return self._compiled[k]
        cfg, module = self.model_cfg, self.module
        from smg_tpu.engine.sampling import apply_penalties

        def step(params, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table,
                 sp, cp, slot, key, temp, topk, topp, minp, *extra):
            logits, kc, vc, sp, cp = module.forward_prefill(
                params, cfg, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table,
                sp, cp, slot, attn_impl=impl)
            logits = logits[None]
            i = 0
            if use_pen:
                with jax.named_scope("smg.sample"):
                    logits = apply_penalties(logits, *extra[:5])
                i = 5
            mask = extra[i] if use_mask else None
            toks, lps = _pick_sampler()(logits, key, temp, topk, topp, minp, mask=mask)
            return toks[0], lps[0], kc, vc, sp, cp

        donate = (5, 6, 8, 9)
        return self._register(k, jax.jit(step, donate_argnums=donate), donate=donate,
                              in_shardings=None, attn=_attn_label("prefill", impl))

    def _prefill_batched_fn(self, G: int, T: int, mp: int, no_ctx: bool = False,
                            use_pen: bool = False, use_mask: bool = False,
                            use_lora: bool = False, use_embeds: bool = False,
                            use_mrope: bool = False):
        self._plain("prefill_batched", lora=use_lora, embeds=use_embeds, mrope=use_mrope)
        k = ("prefill_batched", G, T, mp, no_ctx, use_pen, use_mask)
        if k in self._compiled:
            return self._compiled[k]
        cfg, module = self.model_cfg, self.module
        impl = self._grouped_prefill_impl_for(G, T, no_ctx)
        from smg_tpu.engine.sampling import apply_penalties

        def step(params, inv_freq, packed, kc, vc, sp, cp, rng_key, *extra):
            with jax.named_scope("smg.prefill.unpack"):
                (tokens, page_tables, prefix_lens, t_reals, topks, temps, topps, minps,
                 counter, slots) = prefill_pack.unpack(packed, G, T, mp, slots=True)
                key = jax.random.fold_in(rng_key, counter)
            logits, kc, vc, sp, cp = module.forward_prefill_batched(
                params, cfg, inv_freq, tokens, prefix_lens, t_reals, kc, vc, page_tables,
                sp, cp, slots, no_ctx=no_ctx, attn_impl=impl)
            i = 0
            if use_pen:
                with jax.named_scope("smg.sample"):
                    logits = apply_penalties(logits, *extra[:5])
                i = 5
            mask = extra[i] if use_mask else None
            toks, lps = _pick_sampler()(logits, key, temps, topks, topps, minps, mask=mask)
            return toks, lps, kc, vc, sp, cp

        donate = (3, 4, 5, 6)
        return self._register(k, jax.jit(step, donate_argnums=donate), donate=donate,
                              in_shardings=None, attn=_attn_label("prefill", impl))

    def _decode_multi_fn(self, B: int, mp: int, N: int, E: int = 0,
                         use_pen: bool = False, use_mask: bool = False,
                         use_lora: bool = False, use_mrope: bool = False):
        """This model's decode frame, for ``ModelRunner._decode_frame_fn``'s
        loop: the state pools are carried through the columns beside the side
        buffers the cache's spec asks for (K's and V's, or the one of a latent
        cache), and the frame is ``chained``: launched ahead of one that met a
        finish it runs no column at all."""
        self._plain("decode", lora=use_lora, mrope=use_mrope)
        step_impl = {self.state_step["arg"]: self.state_impl}
        cfg, module = self.model_cfg, self.module
        routed = hasattr(module, "ROUTED_COUNTS")
        L, lanes, latent = cfg.num_cache_layers, self.spec.lanes, bool(self.spec.latent_lanes)

        def frame(params, inv_freq, entry_pos, kc, vc, page_tables, sp, cp, slots, _chain, *,
                  attn_impl, arms):
            runs = slots > 0  # a lane on the garbage slot does not run and picks no expert

            def column(cur, j, side):
                logits, *side = module.forward_decode_horizon(
                    params, cfg, inv_freq, cur, entry_pos + j, entry_pos, j,
                    kc, vc, page_tables, *side, slots, runs,
                    attn_impl=attn_impl, **step_impl)
                counts = side.pop() if routed else None
                return logits, tuple(side), counts

            def land(side, ran, _last):
                *bufs, sp, cp = side
                if latent:  # one buffer of entries; ``vc`` is of zero size
                    caches = (land_side_buffer(kc, *bufs, page_tables, entry_pos, ran), vc)
                else:
                    caches = land_side_buffers(kc, vc, *bufs, page_tables, entry_pos, ran)
                return (*caches, sp, cp), None

            bufs = [jnp.zeros((L, B, N, lanes), kc.dtype) for _ in range(1 if latent else 2)]
            return (*bufs, sp, cp), one_token_column(column), land

        variant = (self.state_impl, *((self.moe_impl,) if routed else ()))
        return self._decode_frame_fn(B, mp, N, E, use_pen, use_mask, frame,
                                     variant=variant, n_held=4, donate_held=(0, 1),
                                     chained=True)

    # ---- host-facing API: ModelRunner's, with the rows' slots as keywords ----

    def _pools(self) -> list:
        """What the sequences hold besides their pages, as the programs take
        it behind the pages and return it behind them."""
        return [self.s_pool, self.c_pool]

    def _take_state(self, state: list) -> None:
        self.s_pool, self.c_pool = state

    def _state_args(self, slots) -> list:
        return [*self._pools(), _dev(slots, jnp.int32)]

    def prefill(self, token_ids, prefix_len, page_table, temperature, top_k, top_p,
                min_p, pen=None, mask=None, lora_idx=0, mm=None, rope_pos=None,
                state_slot: int = 0):
        """``ModelRunner.prefill`` from the state in ``state_slot`` (zero
        state where ``prefix_len`` is 0)."""
        T, mp, use_lora, use_ring, host = self._prefill_chunk_prep(
            token_ids, prefix_len, page_table, lora_idx, mm, rope_pos)
        fn = self._prefill_fn(T, mp, use_pen=pen is not None, use_mask=mask is not None,
                              use_lora=use_lora, use_ring=use_ring,
                              use_embeds=mm is not None, use_mrope=rope_pos is not None)
        with self.account.span("smg.step.admit.dispatch"):
            base, _tail = self._chunk_args(host)
            args = base + self._state_args(np.int32(state_slot)) + self._solo_sampling_args(
                temperature, top_k, top_p, min_p, pen, mask)
            tok, lp, self.k_cache, self.v_cache, *state = fn(*args)
            self._take_state(state)
        return self._fetch_solo(tok, lp)

    def prefill_extend(self, token_ids, prefix_len, page_table, lora_idx=0, mm=None,
                       rope_pos=None, state_slot: int = 0) -> None:
        """``ModelRunner.prefill_extend``: a chunk that is not the prompt's
        last; the state in ``state_slot`` moves on by the chunk.  It runs
        the ``prefill`` program (one program a bucket instead of two) with the
        unfolded key, so the key counter stands still, and fetches nothing:
        the token it samples from one row of logits is dropped on the device."""
        T, mp, use_lora, use_ring, host = self._prefill_chunk_prep(
            token_ids, prefix_len, page_table, lora_idx, mm, rope_pos)
        fn = self._prefill_fn(T, mp, use_lora=use_lora, use_ring=use_ring,
                              use_embeds=mm is not None, use_mrope=rope_pos is not None)
        with self.account.span("smg.step.admit.dispatch"):
            base, _tail = self._chunk_args(host)
            _tok, _lp, self.k_cache, self.v_cache, *state = fn(
                *base, *self._state_args(np.int32(state_slot)), *self._extend_sampling_args())
            self._take_state(state)

    def _extend_sampling_args(self) -> list:
        """What ``prefill_extend`` gives the ``prefill`` program behind the
        state: the unfolded key and a greedy row."""
        up = self.upload
        return [self._rng_key, up([0.0], jnp.float32), up([-1], jnp.int32),
                up([1.0], jnp.float32), up([0.0], jnp.float32)]

    def _launch_group(self, chunks, temps, topks, topps, minps, pen, mask, lora_idx, mm,
                      rope, state_slots=None):
        """``ModelRunner._launch_group``; ``state_slots`` [G_real] names each
        row's slot (padded rows get the garbage slot).  That a group goes up
        in parts (``_split_group``) is a need here: the chunked recurrence
        keeps float32 operands of every padded token, and a program of
        8 rows x 4,096 tokens does not fit beside the caches."""
        from smg_tpu.engine.runner import _pad_rows, _pad_vec

        self._plain("prefill_batched", lora=lora_idx is not None and self._lora_bank is not None,
                    embeds=mm is not None and any(m is not None for m in mm),
                    mrope=rope is not None and any(r is not None for r in rope))
        with self.account.span("smg.step.admit.pack"):
            G, T = self._group_shape(chunks)
            mp = len(chunks[0][2])
            fn = self._prefill_batched_fn(G, T, mp, all(c[1] == 0 for c in chunks),
                                          use_pen=pen is not None, use_mask=mask is not None)
            if state_slots is None:
                state_slots = np.zeros(len(chunks), np.int32)
            packed = self._pack_prefill(chunks, temps, topks, topps, minps, G, T,
                                        state_slots=state_slots)
        with self.account.span("smg.step.admit.dispatch"):
            up = self._prefill_upload
            self.prefill_uploads["launches"] += 1
            args = [self.params, self.inv_freq, up(packed), self.k_cache, self.v_cache,
                    *self._pools(), self._rng_key]
            if pen is not None:
                counts, pmask, freqs, pres, reps = pen
                args += [up(_pad_rows(counts, G).astype(np.int32)), up(_pad_rows(pmask, G)),
                         up(_pad_vec(freqs, G, 0.0), jnp.float32),
                         up(_pad_vec(pres, G, 0.0), jnp.float32),
                         up(_pad_vec(reps, G, 1.0), jnp.float32)]
            if mask is not None:
                args.append(up(_pad_rows(mask, G, fill=True)))
            # smglint: disable-next=DONATE the linter counts ``*self._pools()`` as one argument: the donated positions behind the caches are the pools, rebound in ``_take_state``
            toks, lps, self.k_cache, self.v_cache, *state = fn(*args)
            self._take_state(state)
        return toks, lps

    def _frame_state_args(self, state_slots, chain) -> list:
        """What a decode program takes between the page tables and the key."""
        return [*self._state_args(state_slots), self._unchained if chain is None else chain]

    def _take_frame_state(self, state: list) -> None:
        self.s_pool, self.c_pool = state
