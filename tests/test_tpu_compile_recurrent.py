"""Whether the recurrent runner's programs of ``models/kimi_linear.py`` and of
``models/olmo_hybrid.py`` compile for a TPU v5e, at the widths of the
benchmark's cuts (``test_tpu_compile.py`` says what such a compile shows and
what it does not), what the compiled chunked prefill form of the two delta
rules holds and how the programs reach the two state pools
(``ops/linear_attention.py``).  ``models/nemotron_h.py``'s programs are in
``test_tpu_compile_state_space.py``: a file a worker, and each of the two holds
some four minutes of compiles."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.v5e_compile import (  # noqa: F401
    BF16, PS, _relayouts, assert_pool_updates_in_place, benchmark_cut, kernel_calls, v5e)


def loop_bounds(hlo: str, scope: str) -> list[int]:
    """Of every ``while`` of the compiled text under the named scope, the
    bound its condition compares the counter with (this libtpu writes no
    ``known_trip_count``: the bound is the condition's integer constant)."""
    body = {m.group(1): m.group(2) for m in re.finditer(
        r"^%?([\w.\-]+) \(.*?\) -> .*? \{\n(.*?)^\}", hlo, re.M | re.S)}
    bounds = []
    for line in hlo.splitlines():
        m = re.search(r" while\(.*condition=%?([\w.\-]+),", line)
        if m and scope in line:
            bounds.append(max(int(c) for c in re.findall(
                r"s32\[\]\S* constant\((\d+)\)", body[m.group(1)])))
    return bounds


def assert_sub_block_form(hlo: str, scope: str, chunks: int, dk: int = 0):
    """What ISSUE 52 took out of the chunked form stays out: no loop under the
    scope runs more than ``SUB`` dependent steps but the scan over the
    sequence's chunks (the row-at-a-time inverse ran ``CHUNK``), and nothing
    is elementwise over a whole chunk's ``[CHUNK, CHUNK, dk]`` decay weights
    (a fused computation names the shape it reduces)."""
    from smg_tpu.ops.linear_attention import CHUNK, SUB

    bounds = loop_bounds(hlo, scope)
    assert bounds and chunks in bounds, bounds
    assert [b for b in bounds if b > SUB and b != chunks] == [], bounds
    if dk:
        assert re.search(rf"\[[\d,]*{SUB},{SUB},{dk}\]", hlo)
        assert not re.search(rf"\[[\d,]*{CHUNK},{CHUNK},{dk}\]", hlo)


def assert_tails_move_as_whole_blocks(hlo: str, cp, moves: int):
    """A decode program reaches the tail pool ``cp`` a slot's block at a time
    (ISSUE 54): ``moves`` slices of ``[1, 1, R, W]`` out of it and as many
    updates of one into it (a lane a state layer written out), ``W`` whole
    128-lane tiles and the slot no tiled axis, each update where the pool lies
    (no copy of the whole pool)."""
    dims = lambda shape: ",".join(str(d) for d in shape)
    pool, block = dims(cp.shape), dims((1, 1, *cp.shape[2:]))
    assert len(cp.shape) == 4 and cp.shape[3] % 128 == 0
    assert len(re.findall(rf"= bf16\[{block}\]\S* dynamic-slice\(", hlo)) == moves
    assert len(re.findall(rf"= bf16\[{pool}\]\S* dynamic-update-slice\(", hlo)) == moves
    assert [line for line in _relayouts(hlo, cp.size) if f"bf16[{pool}]" in line] == []


@pytest.mark.parametrize("rule,G,T,H,dk,dv", [
    ("kda", 8, 512, 32, 128, 128), ("kda", 1, 2048, 32, 128, 128),
    ("linattn", 1, 1024, 30, 96, 192), ("linattn", 1, 4096, 30, 96, 192)])
def test_the_chunked_form_is_worked_in_sub_blocks(v5e, rule, G, T, H, dk, dv):
    """The two rules alone at the shapes ``kimi-linear-48b-a3b`` and
    ``olmo-hybrid-7b`` launch (a chunk is 64 rows there, four sub-blocks)."""
    from smg_tpu.ops import linear_attention as LA

    one = SingleDeviceSharding(v5e[0])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
    form, g = {"kda": (LA.kda_chunked, s(G, T, H, dk)),
               "linattn": (LA.gated_delta_chunked, s(G, T, H))}[rule]
    hlo = jax.jit(form).lower(s(G, T, H, dk), s(G, T, H, dk), s(G, T, H, dv), g, s(G, T, H),
                              s(G, H, dk, dv)).compile().as_text()
    assert_sub_block_form(hlo, f"smg.{rule}.prefill", T // LA.CHUNK, dk if rule == "kda" else 0)


class TestKimiLinearCompilesForV5e:
    """``benchmark/configs/kimi-linear-48b-a3b.json``: 9 KDA layers (32 heads of
    128 and 128), 3 unrotated latent layers of 32 heads, a dense MLP and 11
    expert layers (32 of 256 held, 2,304 x 1,024)."""

    @staticmethod
    def shapes(v5e):
        from smg_tpu.models import kimi_linear as M

        cfg = benchmark_cut("kimi-linear-48b-a3b")
        one = SingleDeviceSharding(v5e[0])
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        s_shape, c_shape = M.state_shapes(cfg, 73)
        return M, cfg, s, params, s(s_shape, jnp.float32), s(c_shape)

    @pytest.mark.parametrize("B", [8, 64])
    def test_a_decode_frame_runs_its_kernels_in_place(self, v5e, B):
        """A frame is a loop of columns over three periods, the first written
        out and the other two one scan: the KDA step under its own name (not
        the gated delta rule's) three times in each of the two bodies, which is
        nine times a column; the latent kernel at 32 heads once a body; the
        grouped products at 2,304 x 1,024 three times an expert layer.  The
        state pool is updated where it lies (no temporary of its size, 1.4 GB)
        and no weight is moved into another layout."""
        from smg_tpu.ops.latent_attention import land_side_buffer

        M, cfg, s, params, sp, cp = self.shapes(v5e)
        assert M.layout(cfg) == {"periods": [(0, 3), (4, 3), (8, 3)], "scan": (1, 2)}
        i32 = jnp.int32
        mp, N, P, W = 512, 8, 60000, 640

        def frame(p, tok, entry, kc, vc, tables, sp, cp, slots, n_steps):
            runs = slots > 0

            def body(c):
                j, cur, side, sp, cp, counts = c
                logits, side, sp, cp, k = M.forward_decode_horizon(
                    p, cfg, None, cur, entry + j, entry, j, kc, vc, tables, side, sp, cp,
                    slots, runs, attn_impl="pallas", kda_impl="pallas", moe_impl="pallas")
                return (j + 1, jnp.argmax(logits, -1).astype(i32), side, sp, cp,
                        M.merge_counts(counts, k))

            j, cur, side, sp, cp, counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, jnp.zeros((3, B, N, W), kc.dtype), sp, cp, jnp.zeros((4,), i32)))
            kc = land_side_buffer(kc, side, tables, entry, jnp.arange(N)[None] < j)
            return cur, kc, vc, sp, cp, counts

        compiled = jax.jit(frame, donate_argnums=(3, 6, 7)).lower(
            params, s((B,), i32), s((B,), i32), s((3, P, PS, W)), s((3, 0, PS, 0)),
            s((B, mp), i32), sp, cp, s((B,), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20
        hlo = compiled.as_text()
        assert _relayouts(hlo, 10 * 2**20) == []
        # period 0 written out (3 expert layers of its 4) and the scan's body (4)
        assert kernel_calls(hlo) == {"smg.attn.decode": 2, "smg.moe.experts": 21}
        # the KDA step gives two results (``kernel_calls`` reads one)
        assert len(re.findall(r"%smg\.kda\.decode\.\d+ = \(.*?\) custom-call\(", hlo)) == 6
        assert "smg.linattn.decode" not in hlo
        assert_tails_move_as_whole_blocks(hlo, cp, 6 * B)

    @pytest.mark.parametrize("G,T,cold,pool_copies", [
        (8, 512, True, 2), (2, 2048, True, 2), (1, 2048, False, 0), (1, 1024, True, 0)])
    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e, G, T, cold, pool_copies):
        """A step's budget as a group of eight rows and of two, cold (the
        online-softmax kernel with the shared key as its own operand, at 32
        heads), one chunk that continues a prompt behind a live state, tail
        and latent prefix (XLA's form over the pages), and the one cold row
        that nine launches in ten are: the ``[C, C, dk]`` decay weights of a
        few chunks at a time, the program's temporaries inside what the cache
        plan keeps free of pages, and the tail pool updated where it lies.
        **The state pool too where a launch is one row; the two cold groups
        copy it whole on the way in and on the way out** (1.4 GB twice a
        launch, found by ISSUE 56's guard: ``PERF.md``, Open questions; a
        barrier round the rows as in ``models/nemotron_h._prefill`` does not
        cut it here, where the periods are a scan).  When a PR takes those
        two copies out, their ``pool_copies`` here becomes 0."""
        M, cfg, s, params, sp, cp = self.shapes(v5e)
        i32 = jnp.int32
        mp, P, W = 512, 60000, 640
        compiled = jax.jit(
            lambda p, *a: M.forward_prefill_batched(
                p, cfg, None, *a, no_ctx=cold, attn_impl="pallas" if cold else "xla",
                moe_impl="pallas"),
            donate_argnums=(4, 7, 8)).lower(
            params, s((G, T), i32), s((G,), i32), s((G,), i32), s((3, P, PS, W)),
            s((3, 0, PS, 0)), s((G, mp), i32), sp, cp, s((G,), i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < M.prefill_workspace_bytes(cfg, 4096, "bfloat16") < 3 * 2**30
        hlo = compiled.as_text()
        calls = kernel_calls(hlo)
        assert ("smg.attn.prefill" in calls) == cold and calls["smg.moe.experts"] > 0
        assert_sub_block_form(hlo, "smg.kda.prefill", T // 64, cfg.linear_key_head_dim)
        if pool_copies:
            assert_pool_updates_in_place(compiled, hlo, cp)
            assert len(_relayouts(hlo, sp.size)) == pool_copies
        else:
            assert_pool_updates_in_place(
                compiled, hlo, cp, sp,
                workspace=M.prefill_workspace_bytes(cfg, G * T, "bfloat16"))

    def test_the_one_row_of_1536_tokens_is_the_shape_the_compiler_refuses(self, v5e):
        """Why ``kimi_linear.OCTAVE_RUNGS_ONLY``: the expert layer's gather of
        2,048 rows beside an operand of ``[1536, 2304]`` does not fit VMEM, and
        XLA:TPU stages it there all the same.  When this compiles, the rung can
        come back."""
        M, cfg, s, params, sp, cp = self.shapes(v5e)
        i32 = jnp.int32
        with pytest.raises(Exception, match="vmem"):
            jax.jit(lambda p, *a: M.forward_prefill_batched(
                p, cfg, None, *a, no_ctx=True, attn_impl="pallas", moe_impl="pallas")).lower(
                params, s((1, 1536), i32), s((1,), i32), s((1,), i32), s((3, 60000, PS, 640)),
                s((3, 0, PS, 0)), s((1, 512), i32), sp, cp, s((1,), i32)).compile()


class TestOlmoHybridCompilesForV5e:
    """``benchmark/configs/olmo-hybrid-7b.json``: four periods of three
    gated-delta layers (30 heads of 96 and 192; 11,520 convolution channels,
    which no width lays out in whole tiles of bfloat16) and one full-attention
    layer of 30 heads."""

    @staticmethod
    def shapes(v5e):
        from smg_tpu.models import olmo_hybrid as M

        cfg = benchmark_cut("olmo-hybrid-7b")
        one = SingleDeviceSharding(v5e[0])
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        s_shape, c_shape = M.state_shapes(cfg, 73)
        return M, cfg, s, params, s(s_shape, jnp.float32), s(c_shape)

    def test_a_decode_frame_moves_its_tails_as_whole_blocks(self, v5e):
        """A frame of 16 lanes: the periods are one scan and so are a period's
        linear layers, so the compiled text holds one linear layer: the gated
        delta step once, and a slice and an update of the tail pool a lane."""
        from smg_tpu.ops.attention import land_side_buffers

        M, cfg, s, params, sp, cp = self.shapes(v5e)
        assert cp.shape == (12, 73, 15, 2304)
        i32 = jnp.int32
        B, mp, N, P, KD = 16, 512, 8, 4096, cfg.num_kv_heads * cfg.head_dim

        def frame(p, inv_freq, tok, entry, kc, vc, tables, sp, cp, slots, n_steps):
            runs = slots > 0

            def body(c):
                j, cur, hk, hv, sp, cp = c
                logits, hk, hv, sp, cp = M.forward_decode_horizon(
                    p, cfg, inv_freq, cur, entry + j, entry, j, kc, vc, tables, hk, hv, sp, cp,
                    slots, runs, attn_impl="pallas", linattn_impl="pallas")
                return j + 1, jnp.argmax(logits, -1).astype(i32), hk, hv, sp, cp

            side = jnp.zeros((4, B, N, KD), kc.dtype)
            j, cur, hk, hv, sp, cp = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body, (i32(0), tok, side, side, sp, cp))
            kc, vc = land_side_buffers(kc, vc, hk, hv, tables, entry, jnp.arange(N)[None] < j)
            return cur, kc, vc, sp, cp

        compiled = jax.jit(frame, donate_argnums=(4, 5, 7, 8)).lower(
            params, s((cfg.head_dim // 2,), jnp.float32), s((B,), i32), s((B,), i32),
            s((4, P, PS, KD)), s((4, P, PS, KD)), s((B, mp), i32), sp, cp, s((B,), i32),
            s((), i32)).compile()
        # the full layers' three projections laid out again once a frame, 118 MB
        # each (ROADMAP S13), are the temporaries: no pool is among them
        assert compiled.memory_analysis().temp_size_in_bytes < 512 * 2**20
        hlo = compiled.as_text()
        # a period's three linear layers are a scan inside the scan over periods
        assert len(re.findall(r"%smg\.linattn\.decode\.\d+ = \(.*?\) custom-call\(", hlo)) == 1
        assert_tails_move_as_whole_blocks(hlo, cp, B)

    def test_a_cold_row_updates_both_pools_in_place(self, v5e):
        """One cold row of 1,024 tokens (its scores are past the rule's size,
        so the online-softmax kernel attends): the gated delta rule's state
        pool, ``dv`` 192 wide, is written where it lies as the tail pool is,
        and the program holds no temporary of its size (1.9 GB)."""
        M, cfg, s, params, sp, cp = self.shapes(v5e)
        i32 = jnp.int32
        T, mp, P, KD = 1024, 512, 4096, cfg.num_kv_heads * cfg.head_dim
        compiled = jax.jit(
            lambda p, inv_freq, *a: M.forward_prefill_batched(
                p, cfg, inv_freq, *a, no_ctx=True, attn_impl="pallas"),
            donate_argnums=(5, 6, 8, 9)).lower(
            params, s((cfg.head_dim // 2,), jnp.float32), s((1, T), i32), s((1,), i32),
            s((1,), i32), s((4, P, PS, KD)), s((4, P, PS, KD)), s((1, mp), i32), sp, cp,
            s((1,), i32)).compile()
        hlo = compiled.as_text()
        assert_sub_block_form(hlo, "smg.linattn.prefill", T // 64)
        assert_pool_updates_in_place(compiled, hlo, cp, sp, workspace=512 * 2**20)
