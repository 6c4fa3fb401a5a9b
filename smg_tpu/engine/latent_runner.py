"""The runner of a model whose cache holds one latent entry a token and no V.

``LatentModelRunner`` is ``ModelRunner`` for ``models/pangu_moe.py``,
``models/longcat_flash.py`` (two attention sublayers a layer: the cache and the
side buffer are ``cfg.num_cache_layers`` deep, not ``cfg.num_layers``) and
``models/glm_moe_dsa.py`` (a learned selector chooses the cached tokens a
query reads).  The cache is one buffer (``kv_cache.plan_latent_cache``: sized
from the device read after the weights are on it, so the weights come off
once); ``v_cache`` stays an attribute and goes through every program, so the
three prefill families are ``ModelRunner``'s own programs, names and
positional signatures.  It is of zero size and untouched, but for a model
with indexers (``cfg.num_index_layers``): there it holds the index keys of
those layers, on the latent cache's own pages and sized with it from one
budget, and the prefill forwards write it where they write the entries.  The
decode family is this file's: its side buffer holds latent entries (and a
second one the frame's index keys) and lands through ``land_side_buffer``, a
padded lane picks no expert, and the expert layers' counts ride out with the
frame's tokens (``frame_counts``; the scheduler fetches them with the tokens).

Where the decode kernel runs it reads each lane's own pages by the lane's
``entry``, whatever the table's width, so a decode program is compiled for
the batch bucket alone, at the widest table (``widest_table_only``).

What this runner refuses: everything in the module's ``SERVING_LIMITS``.
"""

from __future__ import annotations

import jax.numpy as jnp

from smg_tpu.engine.kv_cache import plan_latent_cache
from smg_tpu.engine.runner import (
    FLASH_PREFILL_MIN_SCORE_BYTES,
    ModelRunner,
    logger,
    one_token_column,
)
from smg_tpu.ops.latent_attention import land_side_buffer


class LatentModelRunner(ModelRunner):
    def __init__(self, config, params=None, devices=None):
        super().__init__(config, params=params, devices=devices)
        # the expert layers' grouped products: the kernel on a TPU, XLA's
        # ragged product elsewhere
        self._bind_moe_impl("pallas" if self.platform == "tpu"
                            and config.attention_impl != "xla" else "xla")
        itemsize = jnp.dtype(self.spec.dtype).itemsize
        logger.info("latent cache: %d lanes an entry (%d B a token and layer as laid out); "
                    "second buffer: %s; expert layers %s, experts held %s of %d",
                    self.spec.lanes, self.spec.lanes * itemsize,
                    f"index keys of {self.spec.index_layers} layers, {self.spec.index_lanes} "
                    f"lanes ({self.spec.index_lanes * itemsize} B a token and layer)"
                    if self.spec.index_layers else "of zero size (no V, no index keys)",
                    self.moe_impl, self.model_cfg.held_experts, self.model_cfg.num_experts)
        # rows that attended in prefill launches, and those behind more than
        # ``index_topk`` tokens; the decode frames count theirs on the device
        self._dsa = {"prefill_rows": 0, "prefill_rows_selecting": 0,
                     "prefill_index_tokens_scored": 0}

    # ---- what a sequence holds ----

    def _plan_cache(self, param_bytes: int):
        if self.mesh is not None:
            raise ValueError(self.module.SERVING_LIMITS["mesh"])
        limit = in_use = None
        stats = self.local_devices()[0].memory_stats() or {}
        if "bytes_limit" in stats:
            limit, in_use = stats["bytes_limit"], stats.get("bytes_in_use", 0)
        elif self.platform == "tpu":
            raise RuntimeError("the TPU reports no memory_stats(); cannot size the cache")
        sched = self.config.scheduler
        # a selector scores a chunk's queries over the whole table
        over = {"context": sched.max_seq_len} if self.model_cfg.num_index_layers else {}
        workspace = self.module.prefill_workspace_bytes(
            self.model_cfg, sched.max_prefill_tokens, self.config.dtype, **over)
        return plan_latent_cache(self.model_cfg, self.config.cache, limit, in_use, workspace)

    def latent_info(self) -> dict:
        cfg, itemsize = self.model_cfg, jnp.dtype(self.spec.dtype).itemsize
        second = "no V buffer"
        index = {}
        if self.spec.index_layers:
            second = (f"no V buffer; index keys [{self.spec.index_layers}, pages, "
                      f"{self.spec.page_size}, {self.spec.index_lanes}] on the same pages")
            index = {"index_key_bytes": self.spec.index_lanes * itemsize,
                     "index_layers": self.spec.index_layers}
        return {"entry_bytes_published": (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * itemsize,
                "entry_bytes_laid_out": self.spec.lanes * itemsize, **index,
                "layout": f"one buffer [layers, pages, {self.spec.page_size}, "
                          f"{self.spec.lanes}]: latent {cfg.kv_lora_rank}, shared key "
                          f"{cfg.qk_rope_head_dim}, padded to whole 128-lane tiles; {second}"}

    def dsa_info(self) -> "dict | None":
        """What the selector did in prefill launches (host counts of the rows
        launched; the decode frames' are in ``loads()["moe"]``, counted on the
        device): None for a model without one."""
        if not self.model_cfg.num_index_layers:
            return None
        return {"index_topk": self.model_cfg.index_topk, **self._dsa}

    def _count_prefill_rows(self, chunks) -> None:
        """``chunks``: (new tokens, prefix length) of the rows of one launch."""
        cfg = self.model_cfg
        if not cfg.num_index_layers:
            return
        for n, lo in chunks:
            self._dsa["prefill_rows"] += n
            self._dsa["prefill_rows_selecting"] += max(min(n, lo + n - cfg.index_topk), 0)
            # row ``t`` scores ``t + 1`` cached tokens, in every layer with an indexer
            self._dsa["prefill_index_tokens_scored"] += \
                cfg.num_index_layers * ((lo + n) * (lo + n + 1) - lo * (lo + 1)) // 2

    def moe_info(self) -> dict:
        cfg = self.model_cfg
        zero = {"experts_zero": cfg.zero_experts} if cfg.zero_experts else {}
        return {"experts": cfg.num_experts, "experts_held": cfg.held_experts[1], **zero,
                "top_k": cfg.num_experts_per_tok, "impl": self.moe_impl}

    def attention_info(self) -> dict:
        return {**super().attention_info(),
                "form": "latent: expanded prefill (XLA's score blocks, or the online-softmax "
                        "kernel for cold groups: launches say which), absorbed decode"}

    @property
    def widest_table_only(self) -> bool:
        """Decode programs are compiled at the widest page table alone."""
        return self._attn_impl_for(0, 0) == "pallas"

    def prefill(self, token_ids, prefix_len, page_table, *a, **kw):
        self._count_prefill_rows([(len(token_ids), prefix_len)])
        return super().prefill(token_ids, prefix_len, page_table, *a, **kw)

    def _prefill_impl_for(self, T: int, mp: int) -> str:
        """A solo chunk attends expanded in XLA's form over what its pages
        hold (``latent_attention_prefill_cached``), as a grouped chunk behind
        a prefix does: a prompt cut by a step's budget continues at that
        path's speed."""
        return "xla"

    def _grouped_prefill_impl_for(self, G: int, T: int, no_ctx: bool) -> str:
        """Attention of one grouped-prefill program of ``G`` rows in the
        ``T``-token bucket, expanded either way.  Behind a prefix it is XLA's
        over the pages.  Cold rows meet their own rebuilt keys and values:
        in XLA's form (``latent_attention_prefill``: float32 scores ``[G, H,
        qb, T]`` in query blocks) or in the online-softmax kernel
        (``ops/pallas/flash_prefill.py``, the rotary key as the operand the
        heads share), which is chosen where kernels run at all
        (``_resolve_attn_impl``) and the heads' widths are whole 128-lane
        tiles, from the size on at which XLA's scores leave the chip: the
        Llama path's rule and its constant (``FLASH_PREFILL_MIN_SCORE_BYTES``),
        which the sweep at these widths found again (128 and 64 heads of
        192/128; ``PERF.md``, Findings, PR 44: at 32 MiB and under XLA's form
        is twice as fast alone and 1 to 7 % faster in the launch whole, at 64
        MiB the two are within 2.3 % of each other either way in the launch
        whole, from 128 MiB on the kernel wins by 15 % of a launch to 3.3
        times).  ``attention_impl='pallas'`` forces it at every size."""
        cfg = self.model_cfg
        if (not no_ctx or self.attn_impl == "xla" or cfg.qk_nope_head_dim % 128
                or cfg.v_head_dim % 128):
            return "xla"
        if self.attn_impl != "auto":
            return self.attn_impl
        scores = G * T * cfg.num_heads * T * 4
        return "pallas" if scores > FLASH_PREFILL_MIN_SCORE_BYTES else "xla"

    def _split_group(self, lengths: "list[int]") -> "list[list[int]]":
        """The rows go into parts of one octave each, the widest first, and a
        part's padded size stays inside the step's budget (a row longer than
        the budget alone): a launch here reads 9 GB of weights whatever it
        holds, and under a burst the padding of a mixed group feeds itself."""
        sched = self.config.scheduler
        by_bucket: dict[int, list[int]] = {}
        for i, n in enumerate(lengths):
            by_bucket.setdefault(sched.coarse_prefill_bucket(n), []).append(i)
        parts = []
        for T in sorted(by_bucket, reverse=True):
            most = self._rows_inside_budget(T)
            rows = by_bucket[T]
            parts += [rows[k:k + most] for k in range(0, len(rows), most)]
        return parts

    def prefill_batched_async(self, chunks, temps, topks, topps, minps, pen=None,
                              mask=None, lora_idx=None, mm=None, rope=None):
        if lora_idx is not None or mm is not None or rope is not None:
            raise ValueError(self.module.SERVING_LIMITS["lora"])
        self._count_prefill_rows([(len(c[0]), c[1]) for c in chunks])
        return super().prefill_batched_async(chunks, temps, topks, topps, minps,
                                             pen=pen, mask=mask)

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

    def prefill_extend(self, token_ids, prefix_len, page_table, lora_idx=0, mm=None,
                       rope_pos=None) -> None:
        """A chunk that is not the prompt's last runs the ``prefill`` program
        (one program a bucket instead of two) with the unfolded key, so the
        key counter stands still, and fetches nothing."""
        self._count_prefill_rows([(len(token_ids), prefix_len)])
        T, mp, _lora, _ring, host = self._prefill_chunk_prep(
            token_ids, prefix_len, page_table, lora_idx, mm, rope_pos)
        fn, up = self._prefill_fn(T, mp), self.upload
        with self.account.span("smg.step.admit.dispatch"):
            base, _tail = self._chunk_args(host)
            _tok, _lp, self.k_cache, self.v_cache = fn(
                *base, self._rng_key, up([0.0], jnp.float32), up([-1], jnp.int32),
                up([1.0], jnp.float32), up([0.0], jnp.float32))

    # ---- the decode family ----

    def _decode_multi_fn(self, B: int, mp: int, N: int, E: int = 0,
                         use_pen: bool = False, use_mask: bool = False,
                         use_lora: bool = False, use_mrope: bool = False):
        """This model's decode frame, for ``ModelRunner._decode_frame_fn``'s
        loop: one latent side buffer, and for a model with indexers a second
        one, the frame's index keys, which lands in ``vc`` as the first lands
        in ``kc`` (the column then takes the caches and the side buffers as
        pairs)."""
        if use_lora or use_mrope:
            raise ValueError(self.module.SERVING_LIMITS["lora"])
        cfg, module = self.model_cfg, self.module
        L, lanes = cfg.num_cache_layers, self.spec.lanes
        indexed = self.spec.index_layers > 0

        def frame(params, inv_freq, entry_pos, kc, vc, page_tables, *, attn_impl, arms):
            # a padded lane sits past its table (``Scheduler._launch_frame``)
            holds = entry_pos < page_tables.shape[1] * kc.shape[2]

            def column(cur, j, side):
                return module.forward_decode_horizon(
                    params, cfg, inv_freq, cur, entry_pos + j, entry_pos, j,
                    (kc, vc) if indexed else kc, page_tables, side, holds, attn_impl=attn_impl)

            def land(side, ran, _last):
                if indexed:
                    return tuple(land_side_buffer(c, s, page_tables, entry_pos, ran)
                                 for c, s in zip((kc, vc), side)), None
                return (land_side_buffer(kc, side, page_tables, entry_pos, ran), vc), None

            side0 = jnp.zeros((L, B, N, lanes), kc.dtype)
            if indexed:
                side0 = (side0, jnp.zeros((vc.shape[0], B, N, vc.shape[3]), vc.dtype))
            return side0, one_token_column(column), land

        return self._decode_frame_fn(B, mp, N, E, use_pen, use_mask, frame,
                                     variant=(self.moe_impl,))
