"""Whether the recurrent runner's programs of ``models/nemotron_h.py`` compile
for a TPU v5e at the widths of the benchmark's cut, and how they reach the two
state pools (``test_tpu_compile.py`` says what such a compile shows and what it
does not; ``test_tpu_compile_recurrent.py`` has the other two state models)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.v5e_compile import (  # noqa: F401
    BF16, PS, _relayouts, assert_pool_updates_in_place, benchmark_cut, kernel_calls, v5e)


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

    @pytest.mark.parametrize("G,T,cold", [(1, 512, True), (1, 1024, True), (2, 2048, True),
                                          (1, 1024, False), (0, 1024, False)],
                             ids=["1x512", "1x1024", "2x2048", "1x1024_behind_a_prefix", "solo"])
    def test_a_prefill_of_a_steps_budget_fits_its_workspace(self, v5e, G, T, cold):
        """The prefill programs the cell launches: one cold row at two rungs
        (86-94 % of its grouped launches), a step's budget as two rows of 2,048
        tokens, a row behind a prefix and the solo chunk (``G`` 0).  The
        chunked scan's weights of one chunk at a time (all sixteen at once are
        a gigabyte), the program's temporaries inside what the cache plan keeps
        free of pages, and **both pools updated where they lie**: a head of 64
        lanes makes the scan carry its state with the state size on the lanes,
        and before ISSUE 56 layout assignment carried that back to the state
        pool, which every launch then copied whole on the way in and on the way
        out (1.5 GB twice, and a second pool among the temporaries)."""
        M, cfg, s, params, sp, cp = self.shapes(v5e)
        i32 = jnp.int32
        mp, P = 512, 30000
        if G:
            forward = lambda p, *a: M.forward_prefill_batched(
                p, cfg, None, *a, no_ctx=cold, attn_impl="pallas" if cold else "xla",
                moe_impl="pallas")
            rows = (G,)
        else:
            forward = lambda p, *a: M.forward_prefill(p, cfg, None, *a, moe_impl="pallas")
            rows = ()
        compiled = jax.jit(forward, donate_argnums=(4, 5, 7, 8)).lower(
            params, s((*rows, T), i32), s(rows, i32), s(rows, i32), s((1, P, PS, 256)),
            s((1, P, PS, 256)), s((*rows, mp), i32), sp, cp, s(rows, i32)).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < M.prefill_workspace_bytes(cfg, 4096, "bfloat16") < 3 * 2**30
        assert_pool_updates_in_place(
            compiled, compiled.as_text(), cp, sp,
            workspace=M.prefill_workspace_bytes(cfg, max(G, 1) * T, "bfloat16"))
