"""Whether the latent runner's programs compile for a TPU v5e, at the widths of
the benchmark's cuts (``test_tpu_compile.py`` says what such a compile shows
and what it does not)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.v5e_compile import BF16, PS, _relayouts, benchmark_cut, kernel_calls, v5e  # noqa: F401

PANGU_CUT = {
    "model_type": "pangu_ultra_moe", "sandwich_norm": True, "hidden_size": 7680,
    "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_attention_heads": 128,
    "num_key_value_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "routed_scaling_factor": 2.5, "rope_theta": 25600000,
    # the benchmark's cut (benchmark/configs/openpangu-ultra-moe-718b.json)
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
    "router_num_experts": 256, "vocab_size": 19200}


class TestLatentModelCompilesForV5e:
    """``models/pangu_moe.py`` at the widths of the benchmark's cut."""

    @pytest.mark.parametrize("B", [8, 64])
    def test_a_decode_frame_runs_both_kernels_and_copies_no_weights(self, v5e, B):
        """A frame is a loop of columns over a scan of layers.  Both kernels
        are in it under their own names, and nothing moves a weight into
        another layout: stored otherwise, the heads' projections were copied
        a launch (0.6 GB of temporaries) or a layer and column (75 MB), and
        an expert layer sliced out of its stack for the kernel would be a
        copy of 1.4 GB (``models/pangu_moe.init_params``,
        ``ops/pallas/moe_experts.py``)."""
        from smg_tpu.models import pangu_moe as M
        from smg_tpu.models.config import ModelConfig
        from smg_tpu.ops.latent_attention import land_side_buffer

        cfg = ModelConfig.from_hf_config(PANGU_CUT)
        one = SingleDeviceSharding(v5e[0])
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        i32 = jnp.int32
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        L, mp, N, P, W = cfg.num_layers, 512, 8, 30000, M.cache_lanes(cfg)

        def frame(p, inv, tok, entry, kc, tables, n_steps):
            holds = entry < mp * PS

            def body(c):
                j, cur, side, counts = c
                logits, side, k = M.forward_decode_horizon(
                    p, cfg, inv, cur, entry + j, entry, j, kc, tables, side, holds,
                    attn_impl="pallas", moe_impl="pallas")
                return j + 1, jnp.argmax(logits, -1).astype(i32), side, counts + k

            j, cur, side, counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, jnp.zeros((L, B, N, W), kc.dtype), jnp.zeros((4,), i32)))
            return cur, land_side_buffer(kc, side, tables, entry, jnp.arange(N)[None] < j), counts

        compiled = jax.jit(frame, donate_argnums=(4,)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((B,), i32), s((B,), i32),
            s((L, P, PS, W)), s((B, mp), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
        hlo = compiled.as_text()
        assert _relayouts(hlo, 8 * 2**20) == []  # a 64-lane column moves its own 4 M queries
        calls = kernel_calls(hlo)
        # once in each scanned stack's body: attention in both stacks, the
        # three grouped products in the expert stack
        assert calls == {"smg.attn.decode": 2, "smg.moe.experts": 3}


class TestDoubleBlockModelCompilesForV5e:
    """``models/longcat_flash.py`` at the widths of the benchmark's cut
    (``benchmark/configs/longcat-flash-chat.json``): two attention sublayers a
    layer over 8 cache layers, the expert branch a shortcut round the second."""

    @staticmethod
    def cut():
        return benchmark_cut("longcat-flash-chat")

    @staticmethod
    def shapes(cfg, device):
        from smg_tpu.models import longcat_flash as M

        one = SingleDeviceSharding(device)
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        return s, params

    @pytest.mark.parametrize("B", [8, 64])
    def test_a_decode_frame_runs_its_kernels_and_copies_no_weights(self, v5e, B):
        """One scanned body: the latent decode kernel twice (a sublayer each),
        the three grouped products once, and no
        weight moved into another layout (the two sublayers' matrices are two
        stacks, so that none is sliced out of a pair)."""
        from smg_tpu.models import longcat_flash as M
        from smg_tpu.ops.latent_attention import land_side_buffer

        cfg = self.cut()
        assert (cfg.num_layers, cfg.num_cache_layers, cfg.num_heads) == (4, 8, 64)
        s, params = self.shapes(cfg, v5e[0])
        i32 = jnp.int32
        L, mp, N, P, W = cfg.num_cache_layers, 512, 8, 20000, M.cache_lanes(cfg)

        def frame(p, inv, tok, entry, kc, tables, n_steps):
            holds = entry < mp * PS

            def body(c):
                j, cur, side, counts = c
                logits, side, k = M.forward_decode_horizon(
                    p, cfg, inv, cur, entry + j, entry, j, kc, tables, side, holds,
                    attn_impl="pallas", moe_impl="pallas")
                return j + 1, jnp.argmax(logits, -1).astype(i32), side, M.merge_counts(counts, k)

            j, cur, side, counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, jnp.zeros((L, B, N, W), kc.dtype),
                 jnp.zeros((len(M.ROUTED_COUNTS),), i32)))
            return cur, land_side_buffer(kc, side, tables, entry, jnp.arange(N)[None] < j), counts

        compiled = jax.jit(frame, donate_argnums=(4,)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((B,), i32), s((B,), i32),
            s((L, P, PS, W)), s((B, mp), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
        hlo = compiled.as_text()
        assert _relayouts(hlo, 8 * 2**20) == []
        calls = kernel_calls(hlo)
        assert calls == {"smg.attn.decode": 2, "smg.moe.experts": 3}

    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e):
        """4,096 tokens in one row, 12 picks a token through the rows' buffer
        in passes: the program's temporaries inside what ``plan_latent_cache``
        keeps free of pages; the branch's scopes are in the program's text."""
        from smg_tpu.models import longcat_flash as M

        cfg = self.cut()
        s, params = self.shapes(cfg, v5e[0])
        i32 = jnp.int32
        T, mp, P, W = 4096, 512, 20000, M.cache_lanes(cfg)
        compiled = jax.jit(
            lambda p, inv, *a: M.forward_prefill(p, cfg, inv, *a, moe_impl="pallas"),
            donate_argnums=(5,)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((T,), i32), s((), i32), s((), i32),
            s((cfg.num_cache_layers, P, PS, W)), s((cfg.num_cache_layers, 0, PS, W)),
            s((mp,), i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < M.prefill_workspace_bytes(cfg, T, "bfloat16") < 2 * 2**30
        hlo = compiled.as_text()
        assert "smg.scmoe.shortcut" in hlo and "smg.moe.zero" in hlo


class TestSelectorModelCompilesForV5e:
    """``models/glm_moe_dsa.py`` at the widths of the benchmark's cut
    (``benchmark/configs/glm-5.2.json``) behind the cell's table of 1,096
    pages: the programs that score a context, choose 2,048 of it and attend
    over the choice, with the index keys as the second cache buffer."""

    MP = 1096  # ``--max-seq-len 17536``

    @staticmethod
    def shapes(device):
        from smg_tpu.models import glm_moe_dsa as M

        cfg = benchmark_cut("glm-5.2")
        assert (cfg.num_layers, cfg.num_index_layers, cfg.index_topk) == (5, 2, 2048)
        one = SingleDeviceSharding(device)
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        P, W, D = 36000, M.cache_lanes(cfg), cfg.index_head_dim
        caches = (s((cfg.num_cache_layers, P, PS, W)), s((cfg.num_index_layers, P, PS, D)))
        return M, cfg, s, params, caches

    def test_a_decode_frame_scores_selects_and_gathers_and_copies_no_weights(self, v5e):
        """32 lanes behind the whole table: the experts' three kernels, no
        decode attention kernel (the attention over the gathered block is
        XLA's), no weight moved into another layout (the index queries'
        projection is stored by head for that), and the frame's temporaries
        (the lanes' index keys gathered, the scores by head, the gathered
        entries) under a gigabyte beside a cache that fills the chip."""
        from smg_tpu.ops.latent_attention import land_side_buffer

        M, cfg, s, params, caches = self.shapes(v5e[0])
        i32, B, N, mp = jnp.int32, 32, 8, self.MP
        L, Lf, W, D = cfg.num_cache_layers, cfg.num_index_layers, M.cache_lanes(cfg), \
            cfg.index_head_dim

        def frame(p, inv, tok, entry, kc, vc, tables, n_steps):
            holds = entry < mp * PS

            def body(c):
                j, cur, side, counts = c
                logits, side, k = M.forward_decode_horizon(
                    p, cfg, inv, cur, entry + j, entry, j, (kc, vc), tables, side, holds,
                    attn_impl="pallas", moe_impl="pallas")
                return j + 1, jnp.argmax(logits, -1).astype(i32), side, M.merge_counts(counts, k)

            side0 = (jnp.zeros((L, B, N, W), kc.dtype), jnp.zeros((Lf, B, N, D), vc.dtype))
            j, cur, side, counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, side0, jnp.zeros((len(M.ROUTED_COUNTS),), i32)))
            ran = jnp.arange(N)[None] < j
            return (cur, *(land_side_buffer(c, sb, tables, entry, ran)
                           for c, sb in zip((kc, vc), side)), counts)

        compiled = jax.jit(frame, donate_argnums=(4, 5)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((B,), i32), s((B,), i32), *caches,
            s((B, mp), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 2**30
        hlo = compiled.as_text()
        assert _relayouts(hlo, 8 * 2**20) == []
        assert kernel_calls(hlo) == {"smg.moe.experts": 3}
        for scope in ("smg.mla.index.q", "smg.mla.index.k", "smg.mla.index.score",
                      "smg.mla.index.select", "smg.mla.sparse", "smg.attn.decode"):
            assert scope in hlo, scope

    @pytest.mark.parametrize("cold", [False, True], ids=["behind-a-prefix", "cold"])
    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e, cold):
        """4,096 tokens in one row, behind a live prefix (the selection a mask
        over the table's 17,536 positions) and cold (over the chunk): the
        program's temporaries inside what ``plan_latent_cache`` keeps free of
        pages, the selector's score block and mask counted."""
        M, cfg, s, params, caches = self.shapes(v5e[0])
        i32, T, mp = jnp.int32, 4096, self.MP
        inv = s((cfg.rope_dim // 2,), jnp.float32)
        if cold:
            compiled = jax.jit(
                lambda p, inv, *a: M.forward_prefill_batched(p, cfg, inv, *a, no_ctx=True,
                                                             moe_impl="pallas"),
                donate_argnums=(5, 6)).lower(
                params, inv, s((1, T), i32), s((1,), i32), s((1,), i32), *caches,
                s((1, mp), i32)).compile()
        else:
            compiled = jax.jit(
                lambda p, inv, *a: M.forward_prefill(p, cfg, inv, *a, moe_impl="pallas"),
                donate_argnums=(5, 6)).lower(
                params, inv, s((T,), i32), s((), i32), s((), i32), *caches,
                s((mp,), i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        room = M.prefill_workspace_bytes(cfg, T, "bfloat16", context=mp * PS)
        assert temp < room < 2.5 * 2**30
        hlo = compiled.as_text()
        assert kernel_calls(hlo) == {"smg.moe.experts": 3}
        assert "smg.mla.index.select" in hlo and "smg.mla.index.score" in hlo
