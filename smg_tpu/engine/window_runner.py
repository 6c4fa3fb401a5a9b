"""The runner of a model with sliding-window layers beside full ones.

``WindowModelRunner`` serves ``models/mimo.py``.  A sequence holds two kinds
of memory: pages for the full-attention layers (``k_cache`` and ``v_cache``,
K and V of lanes of their own) and one **slot** for the window layers, a ring
of the last tokens a layer (``kv_cache.WindowSpec``,
``ops/window_attention.py``), whose size does not grow with the context.  The
slots are handed out as a recurrent model's state slots are
(``kv_cache.StateSlotPool``), so this runner is ``RecurrentModelRunner`` with
the two pools holding the rings (``s_pool`` the keys, ``c_pool`` the values):
the three prefill families are that runner's own programs, names and
positional signatures, the row's slot a keyword whose default is the garbage
slot 0.

The decode family is this file's.  The rings are read-only during a frame, as
the pages are; a column's window-layer keys and values go to side buffers and
land in the rings at the frame's end, on the device.  Which position a ring
entry holds is derived from the length the host hands the next launch, so
what a frame thrown away or rolled back wrote reads as positions outside
every later window until it is written again: a discarded frame costs
nothing here (``frames_advance_state`` false), and a frame may be launched
behind a prefill or ahead of its predecessor as for a model whose layers are
all attention.  The expert layers' counts ride out with the frame's tokens
(``frame_counts``), as the latent runner's do.

``SelfDraftingRunner`` serves a model of this family that has a next-token
module of its own (``models/exaone_moe.py``) with speculation on: the same
four entry points under the same names and positional signatures, whose
decode frame is a **verify frame** (two rows a lane a column, the module's
draft behind the last accepted token) and whose prefills run the module
behind the stack and leave each sequence's first draft on the device.

What this runner refuses: everything in the module's ``SERVING_LIMITS``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from smg_tpu.engine import prefill_pack
from smg_tpu.engine.kv_cache import plan_window_cache
from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
from smg_tpu.engine.runner import (
    ModelRunner,
    _attn_label,
    _dev,
    _pick_sampler,
    logger,
    one_token_column,
)
from smg_tpu.engine.sampling import apply_penalties
from smg_tpu.ops.attention import land_side_buffers
from smg_tpu.ops.window_attention import land_ring_side


class WindowModelRunner(RecurrentModelRunner):
    frames_advance_state = False
    # tokens a lane may emit a column: what the rings' slack, the pages a
    # frame needs and the frame's side buffers are counted in
    tokens_per_column = 1

    def __init__(self, config, params=None, devices=None):
        super().__init__(config, params=params, devices=devices)  # binds the experts' products
        w = self.state_spec
        logger.info(
            "window slots: %d x %.2f MiB (%d window layers, rings of %d entries for a window "
            "of %d); pages for %d full-attention layers, %d B a token; expert layers %s, "
            "experts held %s of %d",
            w.num_slots - 1, w.slot_bytes / 2**20, w.num_layers, w.ring_tokens, w.window,
            self.spec.num_layers, self.spec.bytes_per_page // self.spec.page_size,
            self.moe_impl, self.model_cfg.held_experts, self.model_cfg.num_experts)

    # ---- what a sequence holds ----

    def _plan_cache(self, param_bytes: int):
        """Slots first, pages from what is left (``plan_window_cache``).  The
        device is read after the weights are on it."""
        sched = self.config.scheduler
        if self.mesh is not None:
            raise ValueError(self.module.SERVING_LIMITS["mesh"])
        limit = in_use = None
        stats = self.local_devices()[0].memory_stats() or {}
        if "bytes_limit" in stats:
            limit, in_use = stats["bytes_limit"], stats.get("bytes_in_use", 0)
        elif self.platform == "tpu":
            raise RuntimeError("the TPU reports no memory_stats(); cannot size the caches")
        workspace = self.module.prefill_workspace_bytes(
            self.model_cfg, sched.max_prefill_tokens, self.config.dtype)
        # a frame and its lookahead may both lie on the device unaccepted
        spec, self.state_spec = plan_window_cache(
            self.model_cfg, self.config.cache, sched.max_batch_size + sched.max_prefill_group,
            2 * sched.horizon_cap * self.tokens_per_column, limit, in_use, workspace)
        return spec

    def _create_state_buffers(self) -> None:
        w = self.state_spec
        self.s_pool = jnp.zeros(w.k_shape, jnp.dtype(w.dtype))  # the rings' keys
        self.c_pool = jnp.zeros(w.v_shape, jnp.dtype(w.dtype))  # and values
        if self._device is not None:
            self.s_pool = jax.device_put(self.s_pool, self._device)
            self.c_pool = jax.device_put(self.c_pool, self._device)

    def window_info(self) -> dict:
        w = self.state_spec
        return {"layers": w.num_layers, "window": w.window, "ring_tokens": w.ring_tokens,
                "slot_bytes": w.slot_bytes, "slots_total": w.num_slots - 1}

    def attention_info(self) -> dict:
        kernel = self._attn_impl_for(0, 0) == "pallas"
        return {**super().attention_info(), "decode_forms": {
            "full": "smg.attn.decode: " + ("paged kernel" if kernel else "xla") + " over pages",
            "window": "smg.attn.window_decode: " + ("ring kernel" if kernel else "xla")
                      + " over a lane's ring, with the sink"}}

    def _prefill_impl_for(self, T: int, mp: int) -> str:
        """Prefill attention has one form here (XLA, queries in blocks), so a
        prompt cut by a step's budget continues at the grouped path's speed."""
        return "xla"

    def _grouped_prefill_impl_for(self, G: int, T: int, no_ctx: bool) -> str:
        return "xla"  # the same one form

    _chunk_bucket = ModelRunner._chunk_bucket  # every bucket: a cut prompt is common here

    # ---- the decode family ----

    def _decode_multi_fn(self, B: int, mp: int, N: int, E: int = 0,
                         use_pen: bool = False, use_mask: bool = False,
                         use_lora: bool = False, use_mrope: bool = False):
        """This model's decode frame, for ``ModelRunner._decode_frame_fn``'s
        loop: four side buffers (the full layers' and the window layers', K
        and V).  The rings are read and not written until the frame's end."""
        self._plain("decode", lora=use_lora, mrope=use_mrope)
        cfg, module = self.model_cfg, self.module

        def frame(params, inv_freq, entry_pos, kc, vc, page_tables, rk, rv, slots, *,
                  attn_impl, arms):
            holds = slots > 0  # a padded lane names the garbage slot and picks no expert

            def column(cur, j, side):
                return module.forward_decode_horizon(
                    params, cfg, inv_freq, cur, entry_pos + j, entry_pos, j,
                    kc, vc, page_tables, rk, rv, slots, side, holds, attn_impl=attn_impl)

            def land(side, ran, _last):
                hk, hv, wk, wv = side
                return (*land_side_buffers(kc, vc, hk, hv, page_tables, entry_pos, ran),
                        *land_ring_side(rk, rv, wk, wv, slots, entry_pos, ran)), None

            return module.side_buffers(cfg, B, N, kc.dtype), one_token_column(column), land

        return self._decode_frame_fn(B, mp, N, E, use_pen, use_mask, frame,
                                     variant=(self.moe_impl,), n_held=3, donate_held=(0, 1))

    # no frame is chained on another's ``clean`` here, and none returns one

    def _frame_state_args(self, state_slots, chain) -> list:
        return self._state_args(state_slots)



class SelfDraftingRunner(WindowModelRunner):
    """``WindowModelRunner`` with the model's own next-token module drafting
    (``--speculative``, tier ``mtp``).

    **A column** is (a) the stack over two rows a lane, ``[last accepted
    token, draft]`` at positions ``p, p + 1``, through rings, pages and the
    frame's side rows; (b) the first row's token is sampled as the one-row
    column samples it, and the draft is accepted iff it is that token and the
    lane is greedy, in which case the second row's greedy token is emitted
    too; (c) the module over the rows just accepted, whose logits behind the
    last of them are the next draft; (d) ``p += 1 or 2``.  A lane's side rows
    are indexed by the tokens it has accepted in the frame (``held``), so a
    rejected row is written over by the lane's next column and lands
    nowhere: not in the pages, the rings or the module's pages.  The stop
    detection and the token limits are the one-row frame's, per token: a lane
    with one token left, or whose first token ends it, emits one.

    **What a sequence holds besides**: its draft, one int32 a slot on the
    device (``draft_buf``), written by the prefill that samples its first
    token and by every frame at its end, read by the next frame; it never
    visits the host.  After a frame thrown away or trimmed it is the draft of
    a position the sequence is not at, which costs that column its second
    token and nothing else: a draft decides only whether it is accepted.

    **What a frame returns**: ``toks`` and ``lps`` ``[B, N, 2]``, and behind
    ``frame_counts`` the frame's ``frame_tail``: ``(emitted [B, N] (tokens a
    lane emitted in each column run), last [B] (each lane's last accepted
    token), positions [B] (where each lane stands), [drafted, accepted])``,
    which a frame launched ahead takes its tokens and positions from.
    """

    self_drafting = True
    tokens_per_column = 2

    def _create_state_buffers(self) -> None:
        super()._create_state_buffers()
        self.draft_buf = jnp.zeros((self.state_spec.num_slots,), jnp.int32)
        if self._device is not None:
            self.draft_buf = jax.device_put(self.draft_buf, self._device)
        self._next_token = -1

    def attention_info(self) -> dict:
        info = super().attention_info()
        kernel = self._attn_impl_for(0, 0) == "pallas"
        info["decode_forms"] = {
            "full": "smg.attn.decode: " + ("paged kernel" if kernel else "xla")
                    + " over pages, two rows a lane",
            "window": "smg.attn.window_verify: " + ("ring kernel" if kernel else "xla")
                      + " over a lane's ring, two rows a lane"}
        return info

    # ---- what the programs take and return behind the pages ----

    def _pools(self) -> list:
        return [self.s_pool, self.c_pool, self.draft_buf]

    def _take_state(self, state: list) -> None:
        self.s_pool, self.c_pool, self.draft_buf = state

    def _state_args(self, slots) -> list:
        # a solo program takes the drafts behind the row's slot
        return [self.s_pool, self.c_pool, _dev(slots, jnp.int32), self.draft_buf]

    def _take_frame_state(self, state: list) -> None:
        self.s_pool, self.c_pool, self.draft_buf = state

    # ---- prefill: the stack, the first token, the module, the first draft ----

    def prefill(self, *args, next_token: int = -1, **kw):
        """``next_token``: the prompt's token behind this chunk where the
        chunk is not the prompt's last and its sampled token is dropped (the
        module's last position then takes it); -1: the token sampled here."""
        self._next_token = next_token
        try:
            return super().prefill(*args, **kw)
        finally:
            self._next_token = -1

    def prefill_extend(self, *args, next_token: int = -1, **kw) -> None:
        self._next_token = next_token
        try:
            super().prefill_extend(*args, **kw)
        finally:
            self._next_token = -1

    def _solo_sampling_args(self, *args) -> list:
        return super()._solo_sampling_args(*args) + [self._scalar_up(np.int32(self._next_token))]

    def _extend_sampling_args(self) -> list:
        return super()._extend_sampling_args() + [self._scalar_up(np.int32(self._next_token))]

    def _prefill_fn(self, T: int, mp: int, use_pen: bool = False, use_mask: bool = False,
                    use_lora: bool = False, use_ring: bool = False, use_embeds: bool = False,
                    use_mrope: bool = False):
        self._plain("prefill", lora=use_lora, ring=use_ring, embeds=use_embeds, mrope=use_mrope)
        impl = self._prefill_impl_for(T, mp)
        k = ("prefill", T, mp, impl, use_pen, use_mask)
        if k in self._compiled:
            return self._compiled[k]
        cfg, module = self.model_cfg, self.module

        def step(params, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table,
                 sp, cp, slot, drafts, key, temp, topk, topp, minp, *extra):
            *extra, next_tok = extra
            logits, kc, vc, sp, cp, hidden = module.forward_prefill(
                params, cfg, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table,
                sp, cp, slot, attn_impl=impl, with_hidden=True)
            logits = logits[None]
            i = 0
            if use_pen:
                with jax.named_scope("smg.sample"):
                    logits = apply_penalties(logits, *extra[:5])
                i = 5
            mask = extra[i] if use_mask else None
            toks, lps = _pick_sampler()(logits, key, temp, topk, topp, minp, mask=mask)
            first = jnp.where(next_tok >= 0, next_tok, toks[0].astype(jnp.int32))
            m_logits, kc, vc = module.forward_mtp_prefill(
                params, cfg, inv_freq, hidden, tokens[None], first[None], prefix_len[None],
                t_real[None], kc, vc, page_table[None])
            drafts = drafts.at[slot].set(jnp.argmax(m_logits[0]).astype(jnp.int32))
            return toks[0], lps[0], kc, vc, sp, cp, drafts

        donate = (5, 6, 8, 9, 11)
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

        def step(params, inv_freq, packed, kc, vc, sp, cp, drafts, rng_key, *extra):
            with jax.named_scope("smg.prefill.unpack"):
                (tokens, page_tables, prefix_lens, t_reals, topks, temps, topps, minps,
                 counter, slots) = prefill_pack.unpack(packed, G, T, mp, slots=True)
                key = jax.random.fold_in(rng_key, counter)
            logits, kc, vc, sp, cp, hidden = module.forward_prefill_batched(
                params, cfg, inv_freq, tokens, prefix_lens, t_reals, kc, vc, page_tables,
                sp, cp, slots, no_ctx=no_ctx, attn_impl=impl, with_hidden=True)
            i = 0
            if use_pen:
                with jax.named_scope("smg.sample"):
                    logits = apply_penalties(logits, *extra[:5])
                i = 5
            mask = extra[i] if use_mask else None
            toks, lps = _pick_sampler()(logits, key, temps, topks, topps, minps, mask=mask)
            m_logits, kc, vc = module.forward_mtp_prefill(
                params, cfg, inv_freq, hidden, tokens, toks.astype(jnp.int32), prefix_lens,
                t_reals, kc, vc, page_tables, no_ctx=no_ctx)
            # a padded row names the garbage slot
            drafts = drafts.at[slots].set(jnp.argmax(m_logits, axis=-1).astype(jnp.int32))
            return toks, lps, kc, vc, sp, cp, drafts

        donate = (3, 4, 5, 6, 7)
        return self._register(k, jax.jit(step, donate_argnums=donate), donate=donate,
                              in_shardings=None, attn=_attn_label("prefill", impl))

    # ---- the verify frame ----

    def _decode_multi_fn(self, B: int, mp: int, N: int, E: int = 0,
                         use_pen: bool = False, use_mask: bool = False,
                         use_lora: bool = False, use_mrope: bool = False):
        """The verify frame, for ``ModelRunner._decode_frame_fn``'s loop (the
        same stop detection and in-loop key folds, a column a fold): beside
        the side buffers it carries each lane's draft, the tokens it has
        accepted in the frame (``held``), what it emitted column by column
        and ``[drafted, accepted]``.  A lane accepts where nothing but its
        greedy token decides what it emits: temperature 0, no penalties, no
        grammar, and the device's stop state at hand (``E`` > 0) to hold it to
        its limit."""
        self._plain("decode", lora=use_lora, mrope=use_mrope)
        may_accept = E > 0 and not use_pen and not use_mask
        cfg, module = self.model_cfg, self.module
        W = self.tokens_per_column

        def frame(params, inv_freq, entry_pos, kc, vc, page_tables, rk, rv, slots, drafts, *,
                  attn_impl, arms):
            holds = slots > 0  # a padded lane names the garbage slot and picks no expert
            greedy = holds & (arms.temps <= 0.0)

            def column(cur, j, own, sample):
                side, draft, held, emitted, spec = own
                with jax.named_scope("smg.frame.emit"):
                    pos = entry_pos + held  # where ``cur`` stands
                    rows = jnp.stack([cur, draft], axis=1)
                logits, hidden, side, c = module.forward_verify_column(
                    params, cfg, inv_freq, rows, held, entry_pos,
                    kc, vc, page_tables, rk, rv, slots, side, holds, attn_impl=attn_impl)
                t0, lp0 = sample(logits[:, 0])
                with jax.named_scope("smg.sample"):
                    t0 = t0.astype(jnp.int32)
                    # the second row's token, greedy: emitted where the draft
                    # was the first row's
                    second = logits[:, 1]
                    t1 = jnp.argmax(second, axis=-1).astype(jnp.int32)
                    lp1 = (jnp.take_along_axis(second, t1[:, None], axis=1)[:, 0]
                           - jax.nn.logsumexp(second, axis=-1))
                with jax.named_scope("smg.frame.emit"):
                    if may_accept:
                        # not where the first token ends the lane (a stop token,
                        # or the last one its limit leaves it)
                        accept = (greedy & (draft == t0) & ~arms.ends(t0)
                                  & (pos + 2 < arms.limits))
                    else:
                        accept = jnp.zeros((B,), jnp.bool_)
                    n = 1 + accept.astype(jnp.int32)
                    toks = jnp.stack([t0, t1], axis=1)
                    emitted = lax.dynamic_update_slice(emitted, n[:, None], (0, j))
                    last, reach = jnp.where(accept, t1, t0), pos + n
                # the module over the rows accepted, for the next draft
                draft, side, c2 = module.forward_mtp_draft(
                    params, cfg, inv_freq, hidden, toks, accept, held, entry_pos,
                    kc, vc, page_tables, side, holds, attn_impl=attn_impl)
                with jax.named_scope("smg.frame.emit"):
                    spec = spec + jnp.stack([jnp.sum(greedy) if may_accept else 0,
                                             jnp.sum(accept)]).astype(jnp.int32)
                    return (toks, jnp.stack([lp0, lp1], axis=1), last, reach,
                            (side, draft, held + n, emitted, spec),
                            module.merge_counts(c, c2))

            def land(own, _ran, last):
                (hk, hv, wk, wv), draft, held, emitted, spec = own
                # a lane's side rows below ``held`` are the tokens it accepted
                keep = jnp.arange(W * N)[None, :] < held[:, None]
                return ((*land_side_buffers(kc, vc, hk, hv, page_tables, entry_pos, keep),
                         *land_ring_side(rk, rv, wk, wv, slots, entry_pos, keep),
                         drafts.at[slots].set(draft)),
                        (emitted, last, entry_pos + held, spec))

            own0 = (module.side_buffers(cfg, B, W * N, kc.dtype), drafts[slots],
                    jnp.zeros((B,), jnp.int32), jnp.zeros((B, N), jnp.int32),
                    jnp.zeros((2,), jnp.int32))
            return own0, column, land

        return self._decode_frame_fn(B, mp, N, E, use_pen, use_mask, frame,
                                     variant=(self.moe_impl,), n_held=4,
                                     donate_held=(0, 1, 3), W=W)
