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

What this runner refuses: everything in the module's ``SERVING_LIMITS``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from smg_tpu.engine.kv_cache import plan_window_cache
from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
from smg_tpu.engine.runner import ModelRunner, logger
from smg_tpu.ops.attention import land_side_buffers
from smg_tpu.ops.window_attention import land_ring_side


class WindowModelRunner(RecurrentModelRunner):
    frames_advance_state = False

    def __init__(self, config, params=None, devices=None):
        super().__init__(config, params=params, devices=devices)
        # the expert layers' grouped products: the kernel on a TPU, XLA's
        # ragged product elsewhere
        self._bind_moe_impl("pallas" if self.platform == "tpu"
                            and config.attention_impl != "xla" else "xla")
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
            2 * sched.horizon_cap, limit, in_use, workspace)
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

    def moe_info(self) -> dict:
        cfg = self.model_cfg
        return {"experts": cfg.num_experts, "experts_held": cfg.held_experts[1],
                "top_k": cfg.num_experts_per_tok, "impl": self.moe_impl}

    def attention_info(self) -> dict:
        kernel = self._attn_impl_for(0, 0) == "pallas"
        return {**super().attention_info(), "decode_forms": {
            "full": "smg.attn.decode: " + ("paged kernel" if kernel else "xla") + " over pages",
            "window": "smg.attn.window_decode: " + ("ring kernel" if kernel else "xla")
                      + " over a lane's ring, with the sink"}}

    @property
    def widest_table_only(self) -> bool:
        """Decode programs are compiled at the widest page table alone: the
        paged kernel reads each lane's own pages by its ``entry``, and the
        ring kernel has no table."""
        return self._attn_impl_for(0, 0) == "pallas"

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
        """``ModelRunner._decode_multi_routed_fn`` over four side buffers
        (the full layers' and the window layers', K and V).  The rings are
        read and not written until the frame's end."""
        self._plain("decode", lora=use_lora, mrope=use_mrope)
        cfg, module = self.model_cfg, self.module

        def frame(params, inv_freq, entry_pos, kc, vc, page_tables, rk, rv, slots, attn_impl):
            holds = slots > 0  # a padded lane names the garbage slot and picks no expert

            def column(cur, j, side):
                return module.forward_decode_horizon(
                    params, cfg, inv_freq, cur, entry_pos + j, entry_pos, j,
                    kc, vc, page_tables, rk, rv, slots, side, holds, attn_impl=attn_impl)

            def land(side, ran):
                hk, hv, wk, wv = side
                return (*land_side_buffers(kc, vc, hk, hv, page_tables, entry_pos, ran),
                        *land_ring_side(rk, rv, wk, wv, slots, entry_pos, ran))

            return module.side_buffers(cfg, B, N, kc.dtype), column, land

        return self._decode_multi_routed_fn(B, mp, N, E, use_pen, use_mask, frame,
                                            n_held=3, donate_held=(0, 1))

    # ``RecurrentModelRunner.decode_multi_async`` launches it: no frame is
    # chained on another's ``clean`` here, and none returns one

    def _frame_state_args(self, state_slots, chain) -> list:
        return self._state_args(state_slots)

    def _take_frame_state(self, out: list) -> list:
        self.s_pool, self.c_pool, *rest = out
        return rest
