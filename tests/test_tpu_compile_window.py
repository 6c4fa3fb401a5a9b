"""Whether the window runners' programs compile for a TPU v5e, at the widths
of the benchmark's cuts (``test_tpu_compile.py`` says what such a compile
shows and what it does not)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.v5e_compile import BF16, PS, _relayouts, benchmark_cut, kernel_calls, v5e  # noqa: F401

class TestWindowModelCompilesForV5e:
    """``models/mimo.py`` at the widths of the benchmark's cut
    (``benchmark/configs/mimo-v2-flash.json``)."""

    @staticmethod
    def cut():
        return benchmark_cut("mimo-v2-flash")

    @pytest.mark.parametrize("B", [8, 64])
    def test_a_decode_frame_runs_its_kernels_and_copies_no_weights(self, v5e, B):
        """A frame is a loop of columns over three scans of layers.  The paged
        kernel (K of 768 lanes, V of 512) and the ring kernel are in it under
        their own names, the second not beginning with the first's (a trace
        counts columns by the paged kernel's name), and nothing moves a
        weight into another layout: stored ``[in, out]`` the input
        projections of a window layer were copied a layer and column, 121 MB
        (``models/mimo.init_params``)."""
        from smg_tpu.models import mimo as M
        from smg_tpu.ops.attention import land_side_buffers
        from smg_tpu.ops.window_attention import land_ring_side

        cfg = self.cut()
        one = SingleDeviceSharding(v5e[0])
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        i32 = jnp.int32
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        mp, N, P, slots, R = 512, 8, 30000, 73, 144

        def frame(p, inv, tok, entry, kc, vc, tables, rk, rv, lane_slots, n_steps):
            holds = lane_slots > 0

            def body(c):
                j, cur, side, counts = c
                logits, side, k = M.forward_decode_horizon(
                    p, cfg, inv, cur, entry + j, entry, j, kc, vc, tables, rk, rv, lane_slots,
                    side, holds, attn_impl="pallas", moe_impl="pallas")
                return j + 1, jnp.argmax(logits, -1).astype(i32), side, counts + k

            j, cur, (hk, hv, wk, wv), counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, M.side_buffers(cfg, B, N, kc.dtype), jnp.zeros((4,), i32)))
            ran = jnp.arange(N)[None] < j
            kc, vc = land_side_buffers(kc, vc, hk, hv, tables, entry, ran)
            rk, rv = land_ring_side(rk, rv, wk, wv, lane_slots, entry, ran)
            return cur, kc, vc, rk, rv, counts

        compiled = jax.jit(frame, donate_argnums=(4, 5, 7, 8)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((B,), i32), s((B,), i32),
            s((2, P, PS, 768)), s((2, P, PS, 512)), s((B, mp), i32),
            s((5, slots, R, 1536)), s((5, slots, R, 1024)), s((B,), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
        hlo = compiled.as_text()
        assert _relayouts(hlo, 10 * 2**20) == []  # under a projection's size: none is copied
        calls = kernel_calls(hlo)
        # once in each scanned run's body: the paged kernel in the two full
        # runs, the ring kernel in the window run, the three grouped products
        # in the two runs with experts
        assert calls == {"smg.attn.decode": 2, "smg.attn.window_decode": 1,
                         "smg.moe.experts": 6}
        assert not "smg.attn.window_decode".startswith("smg.attn.decode")

    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e):
        """4,096 tokens in one row: no ``[heads, T, context]`` float32 array
        (4.3 GB at T = context = 4,096), and the program's temporaries inside
        what ``plan_window_cache`` keeps free of pages."""
        from smg_tpu.models import mimo as M

        cfg = self.cut()
        one = SingleDeviceSharding(v5e[0])
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        i32 = jnp.int32
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.PRNGKey(0)))
        T, mp, P, slots, R = 4096, 512, 30000, 73, 144
        compiled = jax.jit(
            lambda p, inv, *a: M.forward_prefill(p, cfg, inv, *a, moe_impl="pallas"),
            donate_argnums=(5, 6, 8, 9)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((T,), i32), s((), i32), s((), i32),
            s((2, P, PS, 768)), s((2, P, PS, 512)), s((mp,), i32),
            s((5, slots, R, 1536)), s((5, slots, R, 1024)), s((), i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < M.prefill_workspace_bytes(cfg, T, "bfloat16") < 2 * 2**30


class TestSelfDraftingModelCompilesForV5e:
    """``models/exaone_moe.py`` at the widths of the benchmark's cut
    (``benchmark/configs/k-exaone-236b-a23b.json``), the module drafting."""

    @staticmethod
    def cut():
        return benchmark_cut("k-exaone-236b-a23b")

    @staticmethod
    def shapes(cfg, device):
        from smg_tpu.models import exaone_moe as X

        one = SingleDeviceSharding(device)
        s = lambda shape, dtype=BF16: jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
        params = jax.tree.map(
            lambda x: s(x.shape, x.dtype),
            jax.eval_shape(functools.partial(X.init_params, cfg), jax.random.PRNGKey(0)))
        return s, params

    @pytest.mark.parametrize("B", [8, 64])
    def test_a_verify_frame_runs_its_kernels_and_copies_no_weights(self, v5e, B):
        """A verify frame is a loop of columns, each the stack over two rows a
        lane and the module behind it.  The paged kernel's two-row form keeps
        the paged kernel's name (a trace counts columns by it: the full layer
        and the module's attention), the ring kernel's two-row form has a name
        of its own, the module lies between its two marks, and nothing moves
        a weight into another layout."""
        from smg_tpu.models import exaone_moe as X
        from smg_tpu.ops.attention import land_side_buffers
        from smg_tpu.ops.window_attention import land_ring_side

        cfg = self.cut()
        s, params = self.shapes(cfg, v5e[0])
        i32 = jnp.int32
        mp, N, P, slots, R = 512, 8, 30000, 73, 160

        def frame(p, inv, tok, draft, entry, kc, vc, tables, rk, rv, lane_slots, n_steps):
            holds = lane_slots > 0

            def body(c):
                j, cur, draft, held, side, counts = c
                logits, hidden, side, k = X.forward_verify_column(
                    p, cfg, inv, jnp.stack([cur, draft], 1), held, entry, kc, vc, tables, rk,
                    rv, lane_slots, side, holds, attn_impl="pallas", moe_impl="pallas")
                t = jnp.argmax(logits, -1).astype(i32)
                accept = holds & (t[:, 0] == draft)
                draft, side, k2 = X.forward_mtp_draft(
                    p, cfg, inv, hidden, t, accept, held, entry, kc, vc, tables, side, holds,
                    attn_impl="pallas", moe_impl="pallas")
                return (j + 1, jnp.where(accept, t[:, 1], t[:, 0]), draft,
                        held + 1 + accept.astype(i32), side, counts + k + k2)

            j, cur, draft, held, (hk, hv, wk, wv), counts = jax.lax.while_loop(
                lambda c: c[0] < n_steps, body,
                (i32(0), tok, draft, jnp.zeros((B,), i32),
                 X.side_buffers(cfg, B, 2 * N, kc.dtype), jnp.zeros((4,), i32)))
            keep = jnp.arange(2 * N)[None] < held[:, None]
            kc, vc = land_side_buffers(kc, vc, hk, hv, tables, entry, keep)
            rk, rv = land_ring_side(rk, rv, wk, wv, lane_slots, entry, keep)
            return cur, draft, kc, vc, rk, rv, counts

        compiled = jax.jit(frame, donate_argnums=(5, 6, 8, 9)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((B,), i32), s((B,), i32),
            s((B,), i32), s((2, P, PS, 1024)), s((2, P, PS, 1024)), s((B, mp), i32),
            s((4, slots, R, 1024)), s((4, slots, R, 1024)), s((B,), i32), s((), i32)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 96 * 2**20
        hlo = compiled.as_text()
        assert _relayouts(hlo, 10 * 2**20) == []  # under a projection's size: none is copied
        calls = kernel_calls(hlo)
        # once in each scanned run's body: the ring kernel in the two window
        # runs, the paged kernel in the full run and in the module, the three
        # grouped products in the three runs with experts
        assert calls == {"smg.attn.decode": 2, "smg.attn.window_verify": 2,
                         "smg.moe.experts": 9, "smg.mtp.begin": 1, "smg.mtp.end": 1}

    def test_a_prefill_of_a_steps_budget_with_the_module_fits_its_workspace(self, v5e):
        from smg_tpu.models import exaone_moe as X

        cfg = self.cut()
        s, params = self.shapes(cfg, v5e[0])
        i32 = jnp.int32
        T, mp, P, slots, R = 4096, 512, 30000, 73, 160

        def step(p, inv, tokens, lo, n, kc, vc, table, rk, rv, slot):
            out, kc, vc, rk, rv, hidden = X.forward_prefill(
                p, cfg, inv, tokens, lo, n, kc, vc, table, rk, rv, slot, moe_impl="pallas",
                with_hidden=True)
            first = jnp.argmax(out).astype(i32)
            m_out, kc, vc = X.forward_mtp_prefill(
                p, cfg, inv, hidden, tokens[None], first[None], lo[None], n[None], kc, vc,
                table[None], moe_impl="pallas")
            return first, jnp.argmax(m_out[0]), kc, vc, rk, rv

        compiled = jax.jit(step, donate_argnums=(5, 6, 8, 9)).lower(
            params, s((cfg.rope_dim // 2,), jnp.float32), s((T,), i32), s((), i32), s((), i32),
            s((2, P, PS, 1024)), s((2, P, PS, 1024)), s((mp,), i32),
            s((4, slots, R, 1024)), s((4, slots, R, 1024)), s((), i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < X.prefill_workspace_bytes(cfg, T, "bfloat16") < 2 * 2**30
