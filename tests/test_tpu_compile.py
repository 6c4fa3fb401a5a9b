"""The attention dispatch rule, and whether what it can choose compiles for a
TPU v5e.

libtpu compiles for a topology it does not have
(``jax.experimental.topologies``), so Mosaic and XLA:TPU can refuse a kernel
here, on the CPU, before it costs chip time.  This checks compilation and
compile-time memory only; what the kernels compute on the chip is
``chip_smoke.py`` phase b's business, and interpret-mode parity is
``test_pallas_*.py``'s.  The Llama family's kernels and programs are here;
the latent runner's are in ``test_tpu_compile_latent.py`` and the window
runners' in ``test_tpu_compile_window.py`` (``v5e_compile.py`` is what the
three share).
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from smg_tpu.engine.config import EngineConfig, ParallelConfig
from smg_tpu.engine.config import CacheConfig
from smg_tpu.engine.kv_cache import KvCacheSpec, plan_latent_cache
from smg_tpu.engine.latent_runner import LatentModelRunner
from smg_tpu.engine.runner import (
    FLASH_PREFILL_MIN_SCORE_BYTES,
    PREFILL_KERNEL_MAX_T,
    ModelRunner,
    _attn_label,
)
from smg_tpu.engine.window_runner import WindowModelRunner
from smg_tpu.engine.sampling import sample_tokens
from smg_tpu.models.config import (
    llama32_1b_config,
    tiny_longcat_flash_config,
    tiny_pangu_moe_config,
)
from smg_tpu.models.registry import get_model
from smg_tpu.ops.attention import (
    SCORE_BLOCK_BYTES,
    attention_decode_cached,
    attention_prefill,
    scatter_kv_rows,
)
from smg_tpu.ops.pallas.decode_attention import paged_attention_decode_cached
from smg_tpu.ops.pallas.flash_prefill import flash_attention_prefill
from smg_tpu.ops.pallas.prefill_attention import paged_attention_prefill
from smg_tpu.parallel.mesh import build_mesh
from smg_tpu.parallel.sharding import ShardingRules, logical_to_sharding, tree_shardings

from tests.v5e_compile import BF16, PS, _collectives, _compile, _relayouts, v5e  # noqa: F401

CFG = llama32_1b_config()
KD = CFG.num_kv_heads * CFG.head_dim


def _rule(platform, mesh=None, attention_impl="auto", model=CFG, cls=ModelRunner) -> ModelRunner:
    """A runner with just the state the dispatch rule reads."""
    r = object.__new__(cls)
    r.config = EngineConfig(model=model, attention_impl=attention_impl)
    r.model_cfg = model
    r.platform = platform
    r.mesh = mesh
    r.use_pp = False
    r.spec = (plan_latent_cache(model, CacheConfig(dtype="bfloat16")) if model.latent_cache
              else KvCacheSpec(model.num_layers, 64, PS, model.num_kv_heads, model.head_dim,
                               "bfloat16"))
    r.attn_impl = r._resolve_attn_impl()
    return r


# heads of the two latent configurations the benchmark serves, at the per-head
# widths both publish: keys of 128 + 64 lanes, values of 128
LATENT_HEADS = {"openpangu-ultra-moe-718b": 128, "longcat-flash-chat": 64}


def _latent_rule(platform, model="openpangu-ultra-moe-718b", attention_impl="auto",
                 **widths) -> LatentModelRunner:
    """A latent runner with just the state its dispatch rule reads."""
    tiny = (tiny_longcat_flash_config(held=(6, 6)) if model == "longcat-flash-chat"
            else tiny_pangu_moe_config(held=(4, 8)))
    cfg = dataclasses.replace(tiny, **{
        "num_heads": LATENT_HEADS[model], "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, **widths})
    return _rule(platform, attention_impl=attention_impl, model=cfg, cls=LatentModelRunner)


class TestDispatchRule:
    def test_one_tpu_chip_chooses_from_shapes(self):
        r = _rule("tpu")
        assert r.attn_impl == "auto"
        # prefill: the kernel for wide tables, up to its bound and no further
        assert r._prefill_impl_for(PREFILL_KERNEL_MAX_T, 512) == "pallas"
        assert r._prefill_impl_for(64, 512) == "pallas"
        assert r._prefill_impl_for(64, 128) == "xla"  # 2048 slots: gather is cheap
        assert r._prefill_impl_for(2 * PREFILL_KERNEL_MAX_T, 1024) == "xla"
        # decode: the kernel at every shape (PERF.md 7.11: it won the whole
        # sweep, the three shapes the old 131072-token constant split too)
        assert r._attn_impl_for(64, 256) == "pallas"
        assert r._attn_impl_for(32, 256) == "pallas"
        assert r._attn_impl_for(8, 512) == "pallas"
        assert r._attn_impl_for(1, 8) == "pallas"

    @pytest.mark.parametrize("model", ["qwen3-1.7b", "olmo-hybrid-7b"])
    @pytest.mark.parametrize("B,mp", [(16, 128), (16, 256)])
    def test_the_cells_decode_programs_take_the_kernel(self, model, B, mp):
        """The two table widths the benchmark's cells decode behind, at both
        cells' page widths (16/8 heads of 128: 32 KB a page; 30/30 heads of
        128: 122,880 B)."""
        heads, kv = {"qwen3-1.7b": (16, 8), "olmo-hybrid-7b": (30, 30)}[model]
        cfg = dataclasses.replace(CFG, num_heads=heads, num_kv_heads=kv, head_dim=128)
        assert _rule("tpu", model=cfg)._attn_impl_for(B, mp) == "pallas"
        assert _rule("cpu", model=cfg)._attn_impl_for(B, mp) == "xla"

    @pytest.mark.parametrize("model", ["qwen3-1.7b", "olmo-hybrid-7b"])
    def test_cold_grouped_prefill_takes_the_kernel_from_its_size_on(self, model):
        """The online-softmax kernel only for a group of cold rows, from the
        size on at which XLA's float32 scores ``G x T x H x T`` no longer stay
        on the chip (PERF.md, PR 38: 96 MiB XLA's, 120 MiB the kernel's), at
        both cells' head counts; its launches count as ``pallas_prefill``."""
        heads, kv = {"qwen3-1.7b": (16, 8), "olmo-hybrid-7b": (30, 30)}[model]
        cfg = dataclasses.replace(CFG, num_heads=heads, num_kv_heads=kv, head_dim=128)
        r = _rule("tpu", model=cfg)
        for G in (1, 2, 4, 8):
            for T in (256, 512, 1024, 2048, 4096):
                want = ("pallas" if G * T * heads * T * 4 > FLASH_PREFILL_MIN_SCORE_BYTES
                        else "xla")
                assert r._grouped_prefill_impl_for(G, T, True) == want, (G, T)
                assert r._grouped_prefill_impl_for(G, T, False) == "xla"  # behind a prefix
        # what the sweep timed on either side of the constant
        took = {(G, T) for G in (1, 2, 4, 8) for T in (512, 1024, 2048)
                if r._grouped_prefill_impl_for(G, T, True) == "pallas"}
        assert took == {"qwen3-1.7b": {(8, 512), (2, 1024), (4, 1024), (8, 1024), (1, 2048),
                                       (2, 2048), (4, 2048), (8, 2048)},
                        "olmo-hybrid-7b": {(4, 512), (8, 512), (1, 1024), (2, 1024), (4, 1024),
                                           (8, 1024), (1, 2048), (2, 2048), (4, 2048),
                                           (8, 2048)}}[model]
        assert _attn_label("prefill", r._grouped_prefill_impl_for(1, 4096, True)) == (
            "pallas_prefill")
        assert _attn_label("prefill", r._grouped_prefill_impl_for(1, 256, True)) == "xla"
        assert _rule("cpu", model=cfg)._grouped_prefill_impl_for(1, 4096, True) == "xla"

    def test_cold_grouped_prefill_stays_on_xla_where_the_kernel_cannot_serve(self, cpu_devices):
        """Never under a mesh or pp, with a softcap, a window or heads that
        are not whole 128-lane tiles, nor in the runners whose models have
        one prefill attention of their own; forced, at every size."""
        cfg = dataclasses.replace(CFG, num_heads=16, num_kv_heads=8, head_dim=128)
        big = (1, 4096, True)
        assert _rule("tpu", model=cfg)._grouped_prefill_impl_for(*big) == "pallas"
        mesh = build_mesh(ParallelConfig(tp=4), devices=cpu_devices[:4])
        assert _rule("tpu", mesh=mesh, model=cfg)._grouped_prefill_impl_for(*big) == "xla"
        pp = _rule("tpu", model=cfg)
        pp.use_pp = True
        assert pp._grouped_prefill_impl_for(*big) == "xla"
        for change in ({"attn_logit_softcap": 50.0}, {"sliding_window": 4096},
                       {"head_dim": 64, "num_kv_heads": 16}):
            other = dataclasses.replace(cfg, **change)
            assert _rule("tpu", model=other)._grouped_prefill_impl_for(*big) == "xla", change
            assert _rule("tpu", attention_impl="pallas", model=other)._grouped_prefill_impl_for(
                *big) == "xla", change
        assert _rule("tpu", attention_impl="xla", model=cfg)._grouped_prefill_impl_for(
            *big) == "xla"
        forced = _rule("tpu", attention_impl="pallas", model=cfg)
        assert forced._grouped_prefill_impl_for(1, 256, True) == "pallas"
        assert forced._grouped_prefill_impl_for(1, 256, False) == "xla"
        assert WindowModelRunner._grouped_prefill_impl_for(
            object.__new__(WindowModelRunner), *big) == "xla"

    @pytest.mark.parametrize("model", list(LATENT_HEADS))
    def test_latent_cold_grouped_prefill_takes_the_kernel_from_its_size_on(self, model):
        """The latent runner's rule at 128 and at 64 heads: the online-softmax
        kernel for a group of cold rows from the size on at which the float32
        scores of XLA's expanded form, ``G x T x H x T``, leave the chip: the
        Llama path's constant (PERF.md, PR 44: in the launch whole XLA's form
        1 to 7 % faster at 32 MiB and under, the two within 2.3 % of each
        other at 64 MiB, the kernel 15 % of a launch to 3.3 times faster from
        128 MiB on)."""
        heads = LATENT_HEADS[model]
        r = _latent_rule("tpu", model)
        assert r.attn_impl == "auto" and r.model_cfg.num_heads == heads
        for G in (1, 2, 4):
            for T in (128, 256, 512, 1024, 1536, 2048, 4096):
                want = ("pallas" if G * T * heads * T * 4 > FLASH_PREFILL_MIN_SCORE_BYTES
                        else "xla")
                assert r._grouped_prefill_impl_for(G, T, True) == want, (G, T)
                assert r._grouped_prefill_impl_for(G, T, False) == "xla"  # behind a prefix
        # what the sweep timed on either side of the constant
        xla = {(G, T) for G in (1, 2) for T in (256, 512, 1024, 1536, 2048)
               if r._grouped_prefill_impl_for(G, T, True) == "xla"}
        assert xla == {"openpangu-ultra-moe-718b": {(1, 256), (2, 256)},
                       "longcat-flash-chat": {(1, 256), (2, 256), (1, 512)}}[model]
        assert _attn_label("prefill", r._grouped_prefill_impl_for(1, 1536, True)) == (
            "pallas_prefill")
        assert _attn_label("prefill", r._grouped_prefill_impl_for(1, 256, True)) == "xla"

    @pytest.mark.parametrize("model", list(LATENT_HEADS))
    @pytest.mark.parametrize("G,T", [(1, 512), (2, 2048), (1, 4096)])
    def test_latent_cold_grouped_prefill_stays_on_xla_where_the_kernel_cannot_serve(
            self, model, G, T):
        """Off the TPU, under ``attention_impl='xla'``, behind a prefix and at
        head widths that are not whole 128-lane tiles; forced, at every size
        for cold rows and never behind a prefix."""
        assert _latent_rule("cpu", model)._grouped_prefill_impl_for(G, T, True) == "xla"
        assert _latent_rule("tpu", model, attention_impl="xla")._grouped_prefill_impl_for(
            G, T, True) == "xla"
        for widths in ({"qk_nope_head_dim": 64}, {"v_head_dim": 64},
                       {"qk_nope_head_dim": 192, "v_head_dim": 192}):
            for impl in ("auto", "pallas"):
                assert _latent_rule("tpu", model, attention_impl=impl, **widths
                                    )._grouped_prefill_impl_for(G, T, True) == "xla", widths
        forced = _latent_rule("tpu", model, attention_impl="pallas")
        assert forced._grouped_prefill_impl_for(1, 128, True) == "pallas"
        assert forced._grouped_prefill_impl_for(G, T, False) == "xla"
        # a solo chunk attends over its pages in XLA's form whatever the mode
        assert forced._prefill_impl_for(T, 512) == "xla"

    def test_kernel_never_above_its_bound_even_when_forced(self):
        r = _rule("tpu", attention_impl="pallas")
        assert r._prefill_impl_for(PREFILL_KERNEL_MAX_T, 8) == "pallas"
        assert r._prefill_impl_for(2 * PREFILL_KERNEL_MAX_T, 1024) == "xla"

    def test_mesh_answers_xla(self, cpu_devices):
        mesh = build_mesh(ParallelConfig(tp=4), devices=cpu_devices[:4])
        r = _rule("tpu", mesh=mesh)
        assert r.attn_impl == "xla"
        assert r._prefill_impl_for(4096, 512) == "xla"
        assert r._attn_impl_for(64, 512) == "xla"
        with pytest.raises(ValueError, match="under a mesh"):
            _rule("tpu", mesh=mesh, attention_impl="pallas")

    def test_other_platforms_and_narrow_heads_answer_xla(self, tiny_cfg):
        assert _rule("cpu").attn_impl == "xla"
        assert _rule("tpu", model=tiny_cfg).attn_impl == "xla"  # 32 KV lanes

    def test_no_environment_variable_takes_part(self, monkeypatch):
        """The rule reads the config, the platform, the mesh and shapes."""
        import os

        class Untouchable(dict):
            def _read(self, *_):
                raise AssertionError("the dispatch rule read the environment")

            get = __getitem__ = __contains__ = _read

        monkeypatch.setattr(os, "environ", Untouchable())
        r = _rule("tpu")
        assert r.attn_impl == "auto"
        assert r._prefill_impl_for(4096, 512) == "pallas"
        assert r._attn_impl_for(64, 256) == "pallas"


class TestCompilesForV5e:
    """Each side of the rule, at the llama3.2-1b widths and serving defaults
    (page 16, 8192-token tables, 4096-token chunks, batch 64)."""

    def _sds(self, v5e):
        one = SingleDeviceSharding(v5e[0])
        return lambda shape, dtype=BF16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def test_prefill_kernel_at_its_largest_chunk(self, v5e):
        s = self._sds(v5e)
        T, mp, L, P = PREFILL_KERNEL_MAX_T, 512, CFG.num_layers, 1024
        _compile(
            functools.partial(paged_attention_prefill, scale=0.125),
            s((T, CFG.num_heads, CFG.head_dim)), s((T, KD)), s((T, KD)),
            s((L, P, PS, KD)), s((L, P, PS, KD)), s((), jnp.int32),
            s((mp,), jnp.int32), s((), jnp.int32), s((), jnp.int32),
        )

    @pytest.mark.parametrize("B,mp,N,H,K,D", [
        (64, 256, 8, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim),
        (64, 512, 8, 16, 8, 128),   # the widest page table in SMEM: 128 KB
        (16, 256, 8, 30, 30, 128),  # 122,880 B pages, H not a multiple of 8
        (16, 128, 1, 16, 8, 128),   # one side row (a K=1 frame)
        (1, 8, 2, 16, 8, 128),      # the smallest program: a block of 8 pages
    ], ids=["llama1b_64x256", "qwen_64x512", "olmo_hybrid_16x256", "one_side_row",
            "smallest"])
    def test_decode_kernel(self, v5e, B, mp, N, H, K, D):
        """The decode kernel at the shapes ``_attn_impl_for`` now sends it:
        every batch bucket and table width, so the corners as well."""
        s = self._sds(v5e)
        L, P, kd = 4, 1024, K * D
        _compile(
            functools.partial(paged_attention_decode_cached, scale=0.125),
            s((B, H, D)), s((L, P, PS, kd)), s((L, P, PS, kd)),
            s((B, N, kd)), s((B, N, kd)), s((), jnp.int32),
            s((), jnp.int32), s((B, mp), jnp.int32), s((B,), jnp.int32),
        )

    @pytest.mark.parametrize("G,T", [(1, 4096), (2, 2048), (4, 1024)])
    @pytest.mark.parametrize("H,K", [(16, 8), (30, 30)], ids=["qwen", "olmo_hybrid"])
    def test_flash_prefill_kernel_at_the_cells_shapes(self, v5e, G, T, H, K):
        """The cold grouped prefill's kernel at the widest programs of the two
        cells that reach it, queries, keys and values flat as the projections
        leave them: one custom call under its own name, and no temporary the
        size of a ``[T, T]`` score tensor beside it."""
        s = self._sds(v5e)
        D = 128

        def attend(q, k, v, t_reals):
            out = flash_attention_prefill(q.reshape(G, T, H, D), k.reshape(G, T, K, D),
                                          v.reshape(G, T, K, D), t_reals, scale=0.088)
            return out.reshape(G, T, H * D)

        compiled = _compile(attend, s((G, T, H * D)), s((G, T, K * D)), s((G, T, K * D)),
                            s((G,), jnp.int32))
        calls = [line for line in compiled.as_text().splitlines() if "custom-call(" in line]
        assert len(calls) == 1 and "%smg.attn.prefill" in calls[0], calls
        assert "tpu_custom_call" in calls[0]
        assert compiled.memory_analysis().temp_size_in_bytes < T * T * 4

    @pytest.mark.parametrize("G,T", [(1, 512), (1, 1536), (2, 2048), (1, 4096)])
    @pytest.mark.parametrize("H", [128, 64], ids=["openpangu", "longcat"])
    def test_flash_prefill_kernel_at_the_latent_cells_widths(self, v5e, G, T, H):
        """The same kernel at the published widths of the two latent
        configurations: keys of 128 lanes a head and 64 rotary lanes that the
        heads of a row share (the operand of its own), values of 128, keys and
        values with the heads first as the models' up-projections leave them;
        from the smallest program the rule sends it to the widest a launch
        can be."""
        s = self._sds(v5e)
        dn, dr, dv = 128, 64, 128

        def attend(q, k, v, t_reals, q_pe, k_pe):
            out = flash_attention_prefill(
                q.reshape(G, T, H, dn), k, v, t_reals, scale=0.072,
                q_pe=q_pe.reshape(G, T, H, dr), k_pe=k_pe, kv_heads_first=True)
            return out.reshape(G, T, H * dv)

        compiled = _compile(attend, s((G, T, H * dn)), s((G, H, T, dn)), s((G, H, T, dv)),
                            s((G,), jnp.int32), s((G, T, H * dr)), s((G, T, dr)))
        calls = [line for line in compiled.as_text().splitlines() if "custom-call(" in line]
        assert len(calls) == 1 and "%smg.attn.prefill" in calls[0], calls
        assert "tpu_custom_call" in calls[0]
        # the rotary queries padded to whole tiles are the one temporary: no score block
        assert compiled.memory_analysis().temp_size_in_bytes < min(
            T * T * H * 4, 2 * G * T * H * 128 * 2 + 2**20)

    @pytest.mark.parametrize("B,V", [(16, 151936), (1, 151936), (16, 100352)],
                             ids=["qwen_decode", "qwen_first_token", "olmo_hybrid_decode"])
    def test_sampler_sorts_no_row_of_the_vocabulary(self, v5e, B, V):
        """``lax.top_k`` of a whole row, with the row compared against its
        result as the sampler's thresholds are, compiles for the v5e as a
        sort of the vocabulary (3.2 ms a decode column at [16, 151936]; PERF.md,
        PR 33).  No sort or TopK of the compiled sampler may be that wide."""
        s = self._sds(v5e)
        hlo = _compile(
            sample_tokens, s((B, V), jnp.float32), s((2,), jnp.uint32),
            s((B,), jnp.float32), s((B,), jnp.int32), s((B,), jnp.float32),
            s((B,), jnp.float32),
        ).as_text()
        sorts = [line.strip() for line in hlo.splitlines()
                 if re.search(r' sort\(|custom_call_target="TopK"', line)]
        assert sorts, "the sampler's top_k should show in the compiled text"
        assert not [line for line in sorts if f",{V}]" in line], sorts

    def test_xla_prefill_stays_under_its_score_block(self, v5e):
        """The other side of the switch at the largest chunk: one-shot
        scores would be 4 GiB; blocked, the program's temporaries must stay
        within a few score blocks."""
        s = self._sds(v5e)
        T, S = 4096, 8192
        compiled = _compile(
            functools.partial(attention_prefill, scale=0.125),
            s((T, CFG.num_heads, CFG.head_dim)),
            s((S, CFG.num_kv_heads, CFG.head_dim)),
            s((S, CFG.num_kv_heads, CFG.head_dim)),
            s((T,), jnp.int32), s((), jnp.int32),
        )
        assert compiled.memory_analysis().temp_size_in_bytes <= 4 * SCORE_BLOCK_BYTES

    def test_decode_scatter_leaves_the_cache_in_place(self, v5e):
        """The scatter that lands a decode horizon in the donated cache must
        not relayout it: with the layer as a window dimension
        (``cache.at[:, dest]``) XLA:TPU copies the whole buffer into a
        scatter-friendly layout and back, one buffer of temporaries, and the
        decode program no longer fits beside an auto-sized cache."""
        s = self._sds(v5e)
        L, P, n = CFG.num_layers, 4096, 64 * 8
        cache, rows = s((L, P, PS, KD)), s((L, n, KD))
        compiled = jax.jit(scatter_kv_rows, donate_argnums=(0, 1)).lower(
            cache, cache, rows, rows, s((n,), jnp.int32)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == 2 * L * P * PS * KD * 2  # both buffers
        assert mem.temp_size_in_bytes < 2**20

    def test_xla_decode_reads_the_cache_where_it_lies(self, v5e):
        """XLA decode attention inside a scan over layers, at the shapes of
        the ``qwen3-1.7b.eval`` cell (28 layers over an auto-sized cache of
        4,725 pages, 16 lanes behind 256-page tables, 16/8 heads of 128):
        between the cache and the matmuls there is the page gather and no
        other copy of K or V.  With ``k_cache[layer][page_tables]``, per-head
        products and one concatenated softmax this program sliced a whole
        layer out of the cache (155 MB) and relaid the gathered pages out
        twice (134 MB each), for K and for V, per layer: 536 MiB of
        temporaries, 62 % of the cell's device time."""
        s = self._sds(v5e)
        L, P, B, mp, N, H, K, D = 28, 4725, 16, 256, 8, 16, 8, 128
        kd = K * D

        def columns(q, kc, vc, hk_all, hv_all, n_extra, tables, entry):
            def layer_body(h, xs):
                l, hk, hv = xs
                return h + attention_decode_cached(
                    q + h, kc, vc, hk, hv, n_extra, l, tables, entry, 0.088), None

            return jax.lax.scan(layer_body, jnp.zeros_like(q),
                                (jnp.arange(L), hk_all, hv_all))[0]

        compiled = _compile(
            columns, s((B, H, D)), s((L, P, PS, kd)), s((L, P, PS, kd)),
            s((L, B, N, kd)), s((L, B, N, kd)), s((), jnp.int32),
            s((B, mp), jnp.int32), s((B,), jnp.int32),
        )
        hlo = compiled.as_text()
        assert f"[{P},{PS},{kd}]" not in hlo  # no layer sliced out of the cache
        assert _relayouts(hlo, B * mp * PS * kd) == []
        assert compiled.memory_analysis().temp_size_in_bytes < 192 * 2**20

    def test_decode_step_under_tp4(self, v5e):
        """A tp=4 mesh takes the XLA side (the rule's mesh answer): one
        decode step, depth cut to two layers, must partition and compile,
        with the collectives the matmuls' row-parallel halves need (two in
        the layer body, one for the logits) and none for attention: the
        cache's lane axis is sharded, so the products run per head."""
        cfg = dataclasses.replace(CFG, num_layers=2)
        module = get_model(cfg.arch)
        mesh = build_mesh(ParallelConfig(tp=4), devices=v5e)
        rules = ShardingRules()
        impl = _rule("tpu", mesh=mesh)._attn_impl_for(64, 256)
        assert impl == "xla"
        rep = logical_to_sharding((), mesh, rules)
        shapes = jax.eval_shape(
            functools.partial(module.init_params, cfg), jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            shapes, tree_shardings(module.logical_axes(cfg), mesh, rules, shapes=shapes),
        )
        B, mp, N, L, P = 64, 256, 8, cfg.num_layers, 1024

        def s(shape, dtype=BF16, axes=()):
            sharding = logical_to_sharding(axes, mesh, rules, shape=shape) if axes else rep
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        cache = s((L, P, PS, KD), axes=("layers", "pages", None, "kv_lanes"))
        side = s((L, B, N, KD), axes=("layers", None, None, "kv_lanes"))
        assert cache.sharding.spec[3] == "tp"
        compiled = _compile(
            lambda p, inv, *a: module.forward_decode_horizon(
                p, cfg, inv, *a, attn_impl=impl, kv_lanes_sharded=True),
            params, s((cfg.head_dim // 2,), jnp.float32), s((B,), jnp.int32),
            s((B,), jnp.int32), s((B,), jnp.int32), s((), jnp.int32), cache, cache,
            s((B, mp), jnp.int32), side, side,
        )
        assert _collectives(compiled.as_text()) == {"all-reduce": 3}
