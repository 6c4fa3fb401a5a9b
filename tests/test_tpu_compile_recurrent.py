"""Whether the recurrent runner's programs of ``models/nemotron_h.py``, of
``models/kimi_linear.py`` and of ``models/olmo_hybrid.py`` compile for a TPU
v5e, at the widths of the benchmark's cuts (``test_tpu_compile.py`` says what
such a compile shows and what it does not), what the compiled chunked prefill
form of the two delta rules holds and how the programs reach the convolution's
tail pool (``ops/linear_attention.py``)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.v5e_compile import BF16, PS, _relayouts, benchmark_cut, kernel_calls, v5e  # noqa: F401


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


def assert_pool_updates_in_place(compiled, hlo: str, cp):
    """A prefill program still writes its rows' blocks (or flat rows) into the
    donated tail pool where it lies: the pool is an aliased output, a block
    goes in with a ``dynamic-update-slice``, and nothing copies the whole
    pool."""
    pool = ",".join(str(d) for d in cp.shape)
    assert re.search(rf"= bf16\[{pool}\]\S* dynamic-update-slice\(", hlo)
    assert [line for line in _relayouts(hlo, cp.size) if f"bf16[{pool}]" in line] == []
    assert compiled.memory_analysis().alias_size_in_bytes >= cp.size * 2


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


class TestStateSpaceModelCompilesForV5e:
    """``benchmark/configs/nemotron-3-super-120b-a12b.json``: 5 state-space
    layers, 5 latent-expert layers (128 of 512 held) and one attention layer of
    32 query and 2 key/value heads."""

    @staticmethod
    def shapes(v5e):
        from smg_tpu.models import nemotron_h as M

        cfg = benchmark_cut("nemotron-3-super-120b-a12b")
        one = SingleDeviceSharding(v5e[0])
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        s_shape, c_shape = M.state_shapes(cfg, 73)
        return M, cfg, s, params, s(s_shape, jnp.float32), s(c_shape)

    @pytest.mark.parametrize("B", [8, 64])
    def test_a_decode_frame_runs_its_kernels_in_place(self, v5e, B):
        """A frame is a loop of columns over eleven layers written out: the
        state-space step five times under its own name, the paged kernel once
        at a grouping no other cell has (16 queries a key/value head), the
        grouped products twice an expert layer (no gate matrix).  The state
        pool is updated where it lies (no temporary of its size, 1.5 GB) and
        no weight is moved into another layout."""
        from smg_tpu.ops.attention import land_side_buffers

        M, cfg, s, params, sp, cp = self.shapes(v5e)
        i32 = jnp.int32
        mp, N, P = 512, 8, 30000

        def frame(p, tok, entry, kc, vc, tables, sp, cp, slots, n_steps):
            runs = slots > 0

            def body(c):
                j, cur, hk, hv, sp, cp, counts = c
                logits, hk, hv, sp, cp, k = M.forward_decode_horizon(
                    p, cfg, None, cur, entry + j, entry, j, kc, vc, tables, hk, hv, sp, cp,
                    slots, runs, attn_impl="pallas", ssm_impl="pallas", moe_impl="pallas")
                return (j + 1, jnp.argmax(logits, -1).astype(i32), hk, hv, sp, cp,
                        M.merge_counts(counts, k))

            side = jnp.zeros((1, B, N, 256), kc.dtype)
            j, cur, hk, hv, sp, cp, counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, side, side, sp, cp, jnp.zeros((4,), i32)))
            kc, vc = land_side_buffers(kc, vc, hk, hv, tables, entry, jnp.arange(N)[None] < j)
            return cur, kc, vc, sp, cp, counts

        compiled = jax.jit(frame, donate_argnums=(3, 4, 6, 7)).lower(
            params, s((B,), i32), s((B,), i32), s((1, P, PS, 256)), s((1, P, PS, 256)),
            s((B, mp), i32), sp, cp, s((B,), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20
        hlo = compiled.as_text()
        assert _relayouts(hlo, 10 * 2**20) == []
        assert kernel_calls(hlo) == {"smg.attn.decode": 1, "smg.moe.experts": 10}
        # the state-space step gives two results (``kernel_calls`` reads one)
        assert len(re.findall(r"%smg\.ssm\.decode\.\d+ = \(.*?\) custom-call\(", hlo)) == 5
        # this model's tails are still a flat row a slot (``M.state_shapes`` says why)
        assert cp.shape == (5, 73, 3 * 10240)

    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e):
        """Two rows of 2,048 tokens, cold: the chunked scan's weights of one
        chunk at a time (all sixteen at once are a gigabyte), and the
        program's temporaries inside what the cache plan keeps free of pages."""
        M, cfg, s, params, sp, cp = self.shapes(v5e)
        i32 = jnp.int32
        G, T, mp, P = 2, 2048, 512, 30000
        compiled = jax.jit(
            lambda p, *a: M.forward_prefill_batched(p, cfg, None, *a, no_ctx=True,
                                                    attn_impl="pallas", moe_impl="pallas"),
            donate_argnums=(4, 5, 7, 8)).lower(
            params, s((G, T), i32), s((G,), i32), s((G,), i32), s((1, P, PS, 256)),
            s((1, P, PS, 256)), s((G, mp), i32), sp, cp, s((G,), i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < M.prefill_workspace_bytes(cfg, G * T, "bfloat16") < 3 * 2**30
        assert_pool_updates_in_place(compiled, compiled.as_text(), cp)


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

    @pytest.mark.parametrize("G,T,cold", [(8, 512, True), (2, 2048, True), (1, 2048, False)])
    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e, G, T, cold):
        """A step's budget as a group of eight rows and of two, cold (the
        online-softmax kernel with the shared key as its own operand, at 32
        heads), and one chunk that continues a prompt behind a live state, tail
        and latent prefix (XLA's form over the pages): the ``[C, C, dk]`` decay
        weights of a few chunks at a time, and the program's temporaries inside
        what the cache plan keeps free of pages."""
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
        assert_pool_updates_in_place(compiled, hlo, cp)

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
