"""Draft-model speculative proposer.

Reference analogue: the EAGLE/draft-model support in the engines the
reference gateway fronts (``sglang_scheduler.proto`` speculative fields).
TPU-native design: the draft model is a second, much smaller decoder that
shares the TARGET's page-table geometry — one paged KV cache of its own
(``[L_draft, P, ps, K_draft*D_draft]``) indexed by the scheduler's existing
per-request page rows, so no extra allocator or page bookkeeping exists.

Context discipline: the draft cache lazily mirrors the committed token
stream.  ``ensure_context`` prefills whatever committed range the draft has
not seen (``req.draft_len .. seq_len``); ``propose`` then feeds the last
committed token and rolls K greedy single-token forwards.  Draft KV written
for rejected proposals lands past the committed ``seq_len`` and is simply
overwritten by the next ``ensure_context`` — the same overshoot convention
the target cache already relies on.  Draft state never affects correctness
(the target verify gates every token); it only affects acceptance rate.

Overlap interaction: drafting needs last step's committed tokens host-side,
so the chained lookahead never engages — but the scheduler's pipelined
speculative schedule (``Scheduler._step_spec``) keeps the fused VERIFY
frame in flight across steps, so ``ensure_context``/``propose`` host work
overlaps the target model's device pass.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from smg_tpu.models.registry import get_model
from smg_tpu.ops.attention import land_side_buffers
from smg_tpu.ops.rope import rope_frequencies
from smg_tpu.utils import get_logger

logger = get_logger("engine.draft")


class DraftRunner:
    """Single-device draft proposer (multi-host/mesh drafting is future
    work — the engine only builds one when it runs without a mesh)."""

    def __init__(self, model_cfg, num_pages: int, page_size: int,
                 prefill_bucket, dtype: str = "float32", seed: int = 1,
                 params=None, device=None, max_prefill_tokens: int = 256):
        self.model_cfg = model_cfg
        self.module = get_model(model_cfg.arch)
        self.ps = page_size
        self.prefill_bucket = prefill_bucket
        # chunk bound for ensure_context: prefill() pads to a bucket, and
        # prefill_bucket CLAMPS to the largest configured bucket — a chunk
        # beyond it would not fit the padded array
        self.max_prefill_tokens = max_prefill_tokens
        self._device = device
        self.inv_freq = jnp.asarray(rope_frequencies(
            model_cfg.head_dim, model_cfg.rope_theta, model_cfg.rope_scaling
        ))
        if params is None:
            # smglint: disable-next=RETRACE one-shot weight init at construction
            params = jax.jit(partial(self.module.init_params, model_cfg))(
                jax.random.PRNGKey(seed)
            )
        self.params = params
        KD = model_cfg.num_kv_heads * model_cfg.head_dim
        shape = (model_cfg.num_layers, num_pages, page_size, KD)
        cd = jnp.dtype(dtype)
        self.k_cache = jnp.zeros(shape, cd)
        self.v_cache = jnp.zeros(shape, cd)
        if device is not None:
            self.params = jax.device_put(self.params, device)
            self.k_cache = jax.device_put(self.k_cache, device)
            self.v_cache = jax.device_put(self.v_cache, device)
        self._compiled: dict = {}

    # ---- jitted steps ----

    def _prefill_fn(self, T: int, mp: int):
        k = ("draft_prefill", T, mp)
        if k in self._compiled:
            return self._compiled[k]
        cfg = self.model_cfg
        module = self.module

        def step(params, inv_freq, tokens, prefix_len, t_real, kc, vc, page_table):
            _, kc, vc = module.forward_prefill(
                params, cfg, inv_freq, tokens, prefix_len, t_real, kc, vc,
                page_table,
            )
            return kc, vc

        fn = jax.jit(step, donate_argnums=(5, 6))
        self._compiled[k] = fn
        return fn

    def _propose_fn(self, mp: int, k: int):
        key = ("draft_propose", mp, k)
        if key in self._compiled:
            return self._compiled[key]
        cfg = self.model_cfg
        module = self.module

        def step(params, inv_freq, token, position, kc, vc, page_table):
            # the server's decode frame at one lane: k columns over side
            # buffers against the frozen cache, landed in one scatter
            entry, tables = position[None], page_table[None]
            side = jnp.zeros((kc.shape[0], 1, k, kc.shape[-1]), kc.dtype)

            def body(carry, j):
                tok, hk, hv = carry
                logits, hk, hv = module.forward_decode_horizon(
                    params, cfg, inv_freq, tok[None], entry + j, entry, j,
                    kc, vc, tables, hk, hv,
                )
                nxt = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
                return (nxt, hk, hv), nxt

            (_, hk, hv), drafts = jax.lax.scan(
                body, (token, side, side), jnp.arange(k)
            )
            kc, vc = land_side_buffers(kc, vc, hk, hv, tables, entry, True)
            return drafts, kc, vc

        fn = jax.jit(step, donate_argnums=(4, 5))
        self._compiled[key] = fn
        return fn

    # ---- host API ----

    def prefill(self, token_ids: "list[int]", prefix_len: int,
                page_table: np.ndarray) -> None:
        t = len(token_ids)
        if t == 0:
            return
        T = self.prefill_bucket(t)
        mp = len(page_table)
        tokens = np.zeros(T, np.int32)
        tokens[:t] = token_ids
        fn = self._prefill_fn(T, mp)
        self.k_cache, self.v_cache = fn(
            self.params, self.inv_freq, jnp.asarray(tokens),
            jnp.int32(prefix_len), jnp.int32(t),
            self.k_cache, self.v_cache,
            jnp.asarray(page_table, jnp.int32),
        )

    def ensure_context(self, req, page_table: np.ndarray) -> None:
        """Mirror the committed stream [req.draft_len, req.seq_len) into the
        draft cache (chunked; cheap — the draft model is small)."""
        all_ids = req.all_token_ids
        start = req.draft_len
        while start < req.seq_len:
            chunk = all_ids[start : min(start + self.max_prefill_tokens,
                                        req.seq_len)]
            self.prefill(chunk, start, page_table)
            start += len(chunk)
        req.draft_len = req.seq_len

    def propose(self, last_token: int, position: int, page_table: np.ndarray,
                k: int) -> "list[int]":
        """K greedy draft tokens continuing after ``last_token`` (fed at
        ``position``, writing draft KV for it and the first k-1 drafts)."""
        if k <= 0:
            return []
        mp = len(page_table)
        fn = self._propose_fn(mp, k)
        drafts, self.k_cache, self.v_cache = fn(
            self.params, self.inv_freq, jnp.int32(last_token),
            jnp.int32(position),
            self.k_cache, self.v_cache,
            jnp.asarray(page_table, jnp.int32),
        )
        return [int(t) for t in np.asarray(drafts)]
