"""Parity tests: pallas paged prefill attention (interpret mode) vs the XLA
gather path — the two implementations the runner switches between
(SURVEY.md §7 hard part (b))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.ops.attention import attention_prefill, gather_seq_kv
from smg_tpu.ops.pallas.prefill_attention import paged_attention_prefill


def _setup(T, H, D, K, ps, mp, prefix_len, t_real, P=64, seed=0):
    """Build a cache holding a real prefix + the scattered chunk, exactly as
    forward_prefill does, and return everything both paths need."""
    rng = np.random.default_rng(seed)
    L = 3
    layer = 1
    KD = K * D
    k_cache = jnp.asarray(rng.standard_normal((L, P, ps, KD)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((L, P, ps, KD)), jnp.float32)
    # one sequence owning mp distinct pages (skip garbage page 0)
    page_table = jnp.asarray(rng.permutation(P - 1)[:mp] + 1, jnp.int32)

    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((T, KD)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((T, KD)), jnp.float32)

    # scatter the chunk into the cache (prefill does this before attention,
    # so the XLA gather sees chunk tokens through the page table)
    pos = prefix_len + np.arange(T)
    valid = (np.arange(T) < t_real) & (pos < mp * ps)
    pos_c = np.minimum(pos, mp * ps - 1)
    pt_np = np.asarray(page_table)
    dest = np.where(valid, pt_np[pos_c // ps] * ps + pos_c % ps, 0)
    kf = k_cache.reshape(L, P * ps, KD)
    vf = v_cache.reshape(L, P * ps, KD)
    kf = kf.at[layer, dest].set(ck)
    vf = vf.at[layer, dest].set(cv)
    k_cache = kf.reshape(L, P, ps, KD)
    v_cache = vf.reshape(L, P, ps, KD)
    return q, ck, cv, k_cache, v_cache, layer, page_table


def _xla_reference(q, k_cache, v_cache, layer, page_table, prefix_len, t_real, K,
                   softcap=None, window=None):
    T, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    k_ctx, v_ctx = gather_seq_kv(k_cache, v_cache, layer, page_table, K)
    pos = prefix_len + jnp.arange(T)
    return attention_prefill(q, k_ctx, v_ctx, pos, jnp.int32(prefix_len + t_real),
                             scale, softcap=softcap, window=window)


@pytest.mark.parametrize(
    "T,H,D,K,prefix_len,t_real",
    [
        (16, 8, 64, 8, 160, 16),   # llama-1B shape: MHA-ish, C=2 lane fold
        (16, 8, 64, 2, 160, 16),   # GQA 4:1 with C=2
        (32, 4, 128, 2, 96, 32),   # D=128: C=1 plain slice
        (16, 8, 64, 8, 0, 16),     # cold chunk: no prefix pages at all
        (16, 8, 64, 8, 137, 11),   # ragged: prefix not page-aligned, padded rows
    ],
)
def test_parity_vs_xla(T, H, D, K, prefix_len, t_real):
    ps, mp = 16, 24
    q, ck, cv, k_cache, v_cache, layer, page_table = _setup(
        T, H, D, K, ps, mp, prefix_len, t_real
    )
    scale = 1.0 / np.sqrt(D)
    got = paged_attention_prefill(
        q, ck, cv, k_cache, v_cache, layer, page_table,
        prefix_len, t_real, scale, interpret=True,
    )
    want = _xla_reference(q, k_cache, v_cache, layer, page_table,
                          prefix_len, t_real, K)
    # rows beyond t_real are garbage in both paths; compare valid rows only
    np.testing.assert_allclose(
        np.asarray(got[:t_real]), np.asarray(want[:t_real]), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "softcap,window",
    [
        (30.0, None),   # Gemma-2 softcap only
        (None, 100),    # window cuts into the prefix (prefix 160)
        (None, 8),      # window smaller than the chunk: cuts intra-chunk too
        (30.0, 100),    # both together (Gemma-2 local layers)
        (None, 4096),   # window wider than everything = global
        (None, 0),      # window<=0 means global
    ],
)
def test_parity_softcap_window(softcap, window):
    """Sliding-window + logit-softcap masks in the pallas prefill kernel
    match the XLA path."""
    T, H, D, K, prefix_len, t_real = 16, 8, 64, 8, 160, 16
    ps, mp = 16, 24
    q, ck, cv, k_cache, v_cache, layer, page_table = _setup(
        T, H, D, K, ps, mp, prefix_len, t_real
    )
    scale = 1.0 / np.sqrt(D)
    w = None if window is None else jnp.int32(window)
    got = paged_attention_prefill(
        q, ck, cv, k_cache, v_cache, layer, page_table,
        prefix_len, t_real, scale, softcap=softcap, window=w, interpret=True,
    )
    want = _xla_reference(q, k_cache, v_cache, layer, page_table,
                          prefix_len, t_real, K, softcap=softcap, window=w)
    np.testing.assert_allclose(
        np.asarray(got[:t_real]), np.asarray(want[:t_real]), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "prefix_len,t_real,window",
    [
        (0, 64, None),     # cold chunk: tile i attends chunk key blocks 0..i
        (137, 64, None),   # ragged prefix streamed once per tile
        (137, 37, None),   # t_real mid-tile: one partial tile, one padding tile
        (137, 64, 24),     # window wider than a tile, narrower than the chunk
        (160, 50, 8),      # window narrower than a tile
    ],
)
def test_query_tiles_match_xla(prefix_len, t_real, window):
    """Several query tiles per chunk (the grid axis that lets T >= 2048
    compile): every tile's prefix pass, its causal chunk blocks and the
    skipped padding tiles must add up to the one-shot XLA result."""
    T, H, D, K, ps, mp = 64, 8, 64, 2, 16, 24
    q, ck, cv, k_cache, v_cache, layer, page_table = _setup(
        T, H, D, K, ps, mp, prefix_len, t_real
    )
    w = None if window is None else jnp.int32(window)
    got = paged_attention_prefill(
        q, ck, cv, k_cache, v_cache, layer, page_table,
        prefix_len, t_real, 1.0 / np.sqrt(D), window=w, interpret=True,
        q_tile=16,
    )
    want = _xla_reference(q, k_cache, v_cache, layer, page_table,
                          prefix_len, t_real, K, window=w)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got[:t_real]), np.asarray(want[:t_real]), rtol=2e-5, atol=2e-5
    )


def test_window_skips_out_of_window_prefix_blocks():
    """Prefix blocks wholly below every query's window must never be read:
    poison them with NaN and require a finite, XLA-matching result."""
    T, H, D, K, ps = 16, 8, 64, 8, 16
    mp, P = 40, 96
    prefix_len, t_real = 37 * 16 + 5, 16  # 597 tokens
    window = 64  # earliest query at 597: window floor 534 → blocks 0-3 dead
    q, ck, cv, k_cache, v_cache, layer, page_table = _setup(
        T, H, D, K, ps, mp, prefix_len, t_real, P=P
    )
    want = _xla_reference(q, k_cache, v_cache, layer, page_table,
                          prefix_len, t_real, K, window=jnp.int32(window))
    # poison pages holding positions < 512 (first 4 of 5 128-token blocks)
    pt = np.asarray(page_table)
    kc, vc = np.array(k_cache), np.array(v_cache)
    for i in range(32):
        kc[layer, pt[i]] = np.nan
        vc[layer, pt[i]] = np.nan
    scale = 1.0 / np.sqrt(D)
    got = paged_attention_prefill(
        q, ck, cv, jnp.asarray(kc), jnp.asarray(vc), layer, page_table,
        prefix_len, t_real, scale, window=jnp.int32(window), interpret=True,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_long_prefix_multiblock():
    """Prefix spanning several 128-token DMA blocks exercises the streaming
    loop + online softmax merge across blocks."""
    T, H, D, K, ps = 16, 8, 64, 8, 16
    mp, P = 40, 96
    prefix_len, t_real = 37 * 16 + 5, 16  # 597 tokens: 5 blocks, ragged tail
    q, ck, cv, k_cache, v_cache, layer, page_table = _setup(
        T, H, D, K, ps, mp, prefix_len, t_real, P=P
    )
    scale = 1.0 / np.sqrt(D)
    got = paged_attention_prefill(
        q, ck, cv, k_cache, v_cache, layer, page_table,
        prefix_len, t_real, scale, interpret=True,
    )
    want = _xla_reference(q, k_cache, v_cache, layer, page_table,
                          prefix_len, t_real, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_prefill_pallas_impl_matches_xla(tiny_cfg):
    """End-to-end through forward_prefill: attn_impl='pallas' (interpret)
    token-exact vs the default XLA path."""
    from smg_tpu.models.registry import get_model
    from smg_tpu.ops.rope import rope_frequencies

    cfg = tiny_cfg
    module = get_model(cfg.arch)
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta,
                                            cfg.rope_scaling))
    P, ps, mp = 32, 16, 8
    KD = cfg.num_kv_heads * cfg.head_dim
    kc = jnp.zeros((cfg.num_layers, P, ps, KD), jnp.float32)
    vc = jnp.zeros_like(kc)
    page_table = jnp.arange(1, mp + 1, dtype=jnp.int32)
    tokens = jnp.arange(5, 5 + 32, dtype=jnp.int32) % cfg.vocab_size

    lo_x, kcx, vcx = module.forward_prefill(
        params, cfg, inv_freq, tokens, jnp.int32(0), jnp.int32(32),
        kc, vc, page_table,
    )
    lo_p, kcp, vcp = module.forward_prefill(
        params, cfg, inv_freq, tokens, jnp.int32(0), jnp.int32(32),
        kc, vc, page_table, attn_impl="pallas_interpret",
    )
    np.testing.assert_allclose(np.asarray(lo_x), np.asarray(lo_p),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(kcx), np.asarray(kcp), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vcx), np.asarray(vcp), atol=1e-6)


@pytest.mark.parametrize("window", [None, 40])
def test_xla_query_blocks_match_one_shot(monkeypatch, window):
    """The XLA side past SCORE_BLOCK_BYTES: solo prefill in query blocks and
    grouped prefill row by row must equal the one-shot computation."""
    import smg_tpu.ops.attention as ops

    rng = np.random.default_rng(3)
    G, T, H, K, D, S = 3, 64, 8, 2, 16, 160
    q = jnp.asarray(rng.standard_normal((G, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((G, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((G, S, K, D)), jnp.float32)
    prefix = jnp.asarray([0, 37, 96], jnp.int32)
    pos = prefix[:, None] + jnp.arange(T)[None, :]
    ctx = prefix + jnp.asarray([64, 50, 64], jnp.int32)
    w = None if window is None else jnp.int32(window)
    want_rows = ops.attention_prefill_batched(q, k, v, pos, ctx, 0.25, window=w)
    want_solo = ops.attention_prefill(q[1], k[1], v[1], pos[1], ctx[1], 0.25, window=w)

    monkeypatch.setattr(ops, "SCORE_BLOCK_BYTES", 16 * H * S * 4)  # 16-query blocks
    assert ops._query_block(T, H, S) == 16
    got_solo = ops.attention_prefill(q[1], k[1], v[1], pos[1], ctx[1], 0.25, window=w)
    got_rows = ops.attention_prefill_batched(q, k, v, pos, ctx, 0.25, window=w)
    np.testing.assert_allclose(np.asarray(got_solo), np.asarray(want_solo),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_rows), np.asarray(want_rows),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_rows[1]), np.asarray(got_solo),
                               rtol=1e-5, atol=1e-5)
