"""Whether the recurrent runner's programs of ``models/nemotron_h.py`` compile
for a TPU v5e, at the widths of the benchmark's cut (``test_tpu_compile.py``
says what such a compile shows and what it does not)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.v5e_compile import BF16, PS, _relayouts, benchmark_cut, kernel_calls, v5e  # noqa: F401


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
