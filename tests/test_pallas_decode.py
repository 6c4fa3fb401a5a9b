"""Parity tests: pallas paged decode attention (interpret mode) vs the XLA
gather path (``ops/attention.py::attention_decode_cached``) — the two
implementations ``runner._attn_impl_for`` switches between, including the
sliding-window and logit-softcap masks (VERDICT r4 next-round #1: Gemma-2 /
Mistral shapes must not fall back to XLA)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.ops.attention import attention_decode_cached
from smg_tpu.ops.pallas.decode_attention import (
    LATENT_BLOCK_TOKENS,
    _pages_per_block,
    latent_attention_decode_cached,
    paged_attention_decode_cached,
    paged_attention_verify_cached,
)


def _setup(B, H, D, K, ps, mp, N, entries, P=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    L, layer = 3, 1
    KD = K * D
    k_cache = jnp.asarray(rng.standard_normal((L, P, ps, KD)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((L, P, ps, KD)), dtype)
    # distinct pages per sequence (page 0 reserved as garbage)
    pt = rng.permutation(P - 1)[: B * mp].reshape(B, mp) + 1
    page_tables = jnp.asarray(pt, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    hk = jnp.asarray(rng.standard_normal((B, N, KD)), dtype)
    hv = jnp.asarray(rng.standard_normal((B, N, KD)), dtype)
    entry_positions = jnp.asarray(entries, jnp.int32)
    return q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions


CASES = [
    # B, H, D, K, entries, n_extra, softcap, window
    (2, 8, 64, 8, [100, 37], 1, None, None),      # plain, ragged entries
    (2, 8, 64, 2, [100, 37], 3, None, None),      # GQA 4:1, mid-horizon
    (2, 8, 64, 8, [100, 37], 1, 30.0, None),      # softcap only (Gemma-2)
    (2, 8, 64, 8, [100, 37], 1, None, 40),        # window cuts into the cache
    (2, 8, 64, 8, [100, 37], 2, 30.0, 40),        # softcap + window together
    (2, 8, 64, 8, [100, 37], 1, None, 7),         # window smaller than a page
    (2, 8, 64, 8, [100, 37], 1, None, 4096),      # window wider than context
    (2, 8, 64, 8, [100, 37], 1, None, 0),         # window<=0 means global
    (2, 4, 128, 2, [190, 5], 1, 50.0, 64),        # D=128 lanes, deep entry
]


@pytest.mark.parametrize("B,H,D,K,entries,n_extra,softcap,window", CASES)
def test_decode_parity_vs_xla(B, H, D, K, entries, n_extra, softcap, window):
    ps, mp, N = 16, 13, 4
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, mp, N, entries
    )
    scale = 1.0 / np.sqrt(D)
    w = None if window is None else jnp.int32(window)
    got = paged_attention_decode_cached(
        q, k_cache, v_cache, hk, hv, jnp.int32(n_extra), layer,
        page_tables, entry_positions, scale,
        softcap=softcap, window=w, interpret=True,
    )
    want = attention_decode_cached(
        q, k_cache, v_cache, hk, hv, jnp.int32(n_extra), layer,
        page_tables, entry_positions, scale,
        softcap=softcap, window=w,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_block", [None, 4, 2])
def test_decode_window_skips_out_of_window_pages(pages_per_block):
    """With a window, pages wholly below the window must not affect the
    output — poison them with NaN and check the kernel never reads them
    (the DMA loop starts at the window's first live page: blocks are
    counted from it, wherever it lies in the table)."""
    B, H, D, K, ps, mp, N = 1, 8, 64, 8, 16, 13, 4
    entries = [150]
    window = 33  # query at 150: window covers positions 118..150 → pages 7+
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, mp, N, entries
    )
    # poison every page below the window start (positions < 112, pages 0-6)
    pt = np.asarray(page_tables)
    kc = np.array(k_cache)
    vc = np.array(v_cache)
    for i in range(7):
        kc[layer, pt[0, i]] = np.nan
        vc[layer, pt[0, i]] = np.nan
    scale = 1.0 / np.sqrt(D)
    got = paged_attention_decode_cached(
        q, jnp.asarray(kc), jnp.asarray(vc), hk, hv, jnp.int32(1), layer,
        page_tables, entry_positions, scale,
        window=jnp.int32(window), interpret=True,
        pages_per_block=pages_per_block,
    )
    assert np.isfinite(np.asarray(got)).all()
    want = attention_decode_cached(
        q, k_cache, v_cache, hk, hv, jnp.int32(1), layer,
        page_tables, entry_positions, scale, window=jnp.int32(window),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_padded_row_stays_finite():
    """Rows whose entry position is past the table capacity (decode-bucket
    padding) must produce finite output under softcap+window too."""
    B, H, D, K, ps, mp, N = 2, 8, 64, 8, 16, 13, 4
    entries = [100, mp * 16]  # row 1 is padding (entry == capacity)
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, mp, N, entries
    )
    scale = 1.0 / np.sqrt(D)
    got = paged_attention_decode_cached(
        q, k_cache, v_cache, hk, hv, jnp.int32(1), layer,
        page_tables, entry_positions, scale,
        softcap=30.0, window=jnp.int32(24), interpret=True,
    )
    assert np.isfinite(np.asarray(got)).all()


MP, CAP = 13, 13 * 16  # the table of the cases below; ``CAP`` marks a padded row
BLOCK_CASES = {
    # B, H, D, K, entries, n_extra, window, softcap, pages_per_block, dtype
    "fewer_pages_than_a_block": (2, 8, 64, 8, [20, 37], 1, None, None, 4, jnp.float32),
    "exactly_one_block": (2, 8, 64, 8, [64, 128], 2, None, None, 4, jnp.float32),
    "one_token_past_a_block": (2, 8, 64, 8, [65, 129], 1, None, None, 4, jnp.float32),
    "one_token_short_of_a_block": (2, 8, 64, 8, [63, 127], 1, None, None, 4, jnp.float32),
    "no_page_side_rows_only": (2, 8, 64, 8, [0, 0], 3, None, None, 4, jnp.float32),
    "one_page_a_block": (2, 8, 64, 2, [100, 37], 3, None, None, 1, jnp.float32),
    "window_starts_inside_a_block": (2, 8, 64, 8, [200, 90], 1, 40, None, 4, jnp.float32),
    "window_and_softcap_over_blocks": (2, 8, 64, 8, [200, 90], 2, 70, 30.0, 2, jnp.float32),
    "three_lanes_one_empty_between": (3, 8, 64, 8, [100, 0, 37], 1, None, None, 2, jnp.float32),
    "three_lanes_first_two_empty": (3, 8, 64, 8, [0, CAP, 50], 2, None, None, 4, jnp.float32),
    "five_lanes_padded_and_empty": (5, 8, 64, 2, [CAP, 100, 0, 70, 16], 1, None, None, 2,
                                    jnp.float32),
    "five_lanes_last_empty": (5, 8, 64, 8, [33, 100, 207, 1, 0], 4, None, None, 4, jnp.float32),
    "heads_30_of_128": (2, 30, 128, 30, [100, 37], 2, None, None, 4, jnp.float32),
    "heads_30_of_128_default_block": (2, 30, 128, 30, [190, 65], 1, None, None, None,
                                      jnp.float32),
    "gqa_16_8_of_128": (2, 16, 128, 8, [129, 64], 1, None, None, 4, jnp.float32),
    "bfloat16_cache": (2, 16, 128, 8, [200, 37], 2, None, None, 4, jnp.bfloat16),
    "bfloat16_cache_one_block": (3, 8, 64, 8, [100, 0, 207], 1, None, None, None, jnp.bfloat16),
    "bfloat16_heads_30_of_128": (2, 30, 128, 30, [150, 16], 3, None, None, 8, jnp.bfloat16),
    "bfloat16_window_softcap": (2, 8, 64, 8, [200, 90], 2, 70, 30.0, 2, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_block_loop_parity_vs_xla(case):
    """What a loop over blocks of pages can get wrong: a short last block,
    a block boundary, no block at all, a window that opens inside a block,
    lanes without blocks between lanes that hand their first block on, and
    the served dtype (bfloat16 against the XLA form in bfloat16: the same
    operands, another order of summation)."""
    B, H, D, K, entries, n_extra, window, softcap, n, dtype = BLOCK_CASES[case]
    ps, N = 16, 4
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, MP, N, entries, P=128, dtype=dtype)
    w = None if window is None else jnp.int32(window)
    args = (q, k_cache, v_cache, hk, hv, jnp.int32(n_extra), layer, page_tables,
            entry_positions, 1.0 / np.sqrt(D))
    got = paged_attention_decode_cached(*args, softcap=softcap, window=w,
                                        interpret=True, pages_per_block=n)
    want = attention_decode_cached(*args, softcap=softcap, window=w)
    assert got.dtype == want.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    real = np.asarray(entries) < CAP  # a padded row's output is nobody's
    np.testing.assert_allclose(got[real], want[real], rtol=tol, atol=tol)


@pytest.mark.parametrize("pages_per_block", [None, 4, 1])
def test_pages_past_entry_are_not_fetched(pages_per_block):
    """A lane's last block fetches the pages the lane holds: poison every
    page of the table past them and the output stays finite and equal (the
    XLA form gathers the whole table and could not pass this)."""
    B, H, D, K, ps, N = 3, 8, 64, 8, 16, 4
    entries = [70, 0, 129]
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, MP, N, entries, P=128)
    pt = np.asarray(page_tables)
    kc, vc = np.array(k_cache), np.array(v_cache)
    for b, e in enumerate(entries):
        for i in range(-(-e // ps), MP):
            kc[layer, pt[b, i]] = np.nan
            vc[layer, pt[b, i]] = np.nan
    args = (hk, hv, jnp.int32(2), layer, page_tables, entry_positions, 1.0 / np.sqrt(D))
    got = paged_attention_decode_cached(q, jnp.asarray(kc), jnp.asarray(vc), *args,
                                        interpret=True, pages_per_block=pages_per_block)
    want = attention_decode_cached(q, k_cache, v_cache, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ps,lanes,itemsize,mp,want", [
    (16, 1024, 2, 256, 16),   # qwen3-1.7b: 32 KB pages, 256 tokens a block
    (16, 3840, 2, 256, 8),    # olmo-hybrid-7b: 122,880 B pages, 128 tokens
    (16, 512, 2, 512, 16),    # llama3.2-1b
    (16, 1024, 2, 8, 8),      # a table narrower than a block
    (16, 8192, 4, 64, 2),     # a page of 512 KB
])
def test_pages_per_block(ps, lanes, itemsize, mp, want):
    assert _pages_per_block(ps, lanes, itemsize, mp) == want


def _latent_setup(B, H, W, mp, N, entries, P, dtype, seed=0):
    rng = np.random.default_rng(seed)
    cache = jnp.asarray(rng.standard_normal((3, P, 16, W)), dtype)
    tables = jnp.asarray(rng.permutation(P - 1)[: B * mp].reshape(B, mp) + 1, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, W)), dtype)
    side = jnp.asarray(rng.standard_normal((B, N, W)), dtype)
    return q, cache, side, tables, jnp.asarray(entries, jnp.int32)


LONG_MP = 70  # a table past two of the latent kernel's own blocks (32 pages each)
LATENT_BLOCK_CASES = {
    # B, H, W, latent, mp, N, entries, n_extra, pages_per_block, dtype
    "fewer_pages_than_a_block": (2, 8, 256, 128, MP, 4, [20, 37], 1, 4, jnp.float32),
    "exactly_one_block": (2, 8, 256, 128, MP, 4, [64, 128], 2, 4, jnp.float32),
    "one_entry_past_a_block": (2, 8, 256, 128, MP, 4, [65, 129], 1, 4, jnp.float32),
    "one_entry_short_of_a_block": (2, 8, 256, 128, MP, 4, [63, 127], 1, 4, jnp.float32),
    "side_rows_only": (2, 8, 256, 128, MP, 4, [0, 0], 3, 4, jnp.float32),
    "side_buffer_of_one_row": (2, 8, 256, 128, MP, 1, [100, 37], 1, 4, jnp.float32),
    "empty_lane_between_two_full": (3, 8, 256, 128, MP, 4, [100, 0, 37], 1, 2, jnp.float32),
    "first_two_lanes_empty": (3, 8, 256, 128, MP, 4, [0, 0, 50], 2, 4, jnp.float32),
    "padded_row": (3, 8, 256, 128, MP, 4, [CAP, 100, 70], 2, 4, jnp.float32),
    "block_of_a_table_not_a_power_of_two": (2, 8, 256, 128, MP, 4, [200, 90], 1, None,
                                            jnp.float32),
    "heads_64_on_640_lanes": (2, 64, 640, 512, MP, 8, [150, 33], 3, 4, jnp.float32),
    "heads_128_on_640_lanes": (2, 128, 640, 512, MP, 8, [150, 33], 3, None, jnp.float32),
    "bfloat16_cache": (3, 16, 256, 128, MP, 4, [200, 0, 37], 2, 4, jnp.bfloat16),
    "bfloat16_heads_64_on_640_lanes": (2, 64, 640, 512, MP, 8, [190, 65], 1, None, jnp.bfloat16),
    "longer_than_two_of_its_own_blocks": (2, 8, 256, 128, LONG_MP, 4, [1100, 513], 2, None,
                                          jnp.float32),
    "bfloat16_longer_than_two_blocks": (2, 16, 256, 128, LONG_MP, 4, [1025, 1024], 1, None,
                                        jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(LATENT_BLOCK_CASES))
def test_latent_block_loop_parity_vs_xla(case):
    """The latent cache's own kernel against the XLA form with one head as
    wide as the entry and the cache as its own V: what its loop can get wrong
    that the paged kernel's cannot (a predicated start a page, the waits a
    set bit of the count, blocks of 512 entries), at its own widths."""
    B, H, W, latent, mp, N, entries, n_extra, n, dtype = LATENT_BLOCK_CASES[case]
    assert LATENT_BLOCK_TOKENS == 512
    q, cache, side, tables, entry = _latent_setup(B, H, W, mp, N, entries, B * mp + 8, dtype)
    scale = 1.0 / np.sqrt(W)
    got = latent_attention_decode_cached(q, cache, side, jnp.int32(n_extra), 1, tables, entry,
                                         latent=latent, scale=scale, interpret=True,
                                         pages_per_block=n)
    want = attention_decode_cached(q, cache, cache, side, side, jnp.int32(n_extra), 1, tables,
                                   entry, scale)[..., :latent]
    assert got.dtype == want.dtype == dtype and got.shape == (B, H, latent)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    real = np.asarray(entries) < mp * 16  # a padded row's output is nobody's
    np.testing.assert_allclose(got[real], want[real], rtol=tol, atol=tol)


@pytest.mark.parametrize("pages_per_block", [None, 4, 1])
def test_latent_pages_past_entry_are_not_fetched(pages_per_block):
    """The latent twin of ``test_pages_past_entry_are_not_fetched``: a page a
    lane does not hold is never started, whatever it is predicated on."""
    B, H, W, latent, N = 3, 8, 256, 128, 4
    entries = [70, 0, 129]
    q, cache, side, tables, entry = _latent_setup(B, H, W, MP, N, entries, 128, jnp.float32)
    pt, poisoned = np.asarray(tables), np.array(cache)
    for b, e in enumerate(entries):
        for i in range(-(-e // 16), MP):
            poisoned[1, pt[b, i]] = np.nan
    args = (side, jnp.int32(2), 1, tables, entry)
    got = latent_attention_decode_cached(q, jnp.asarray(poisoned), *args, latent=latent,
                                         scale=0.1, interpret=True,
                                         pages_per_block=pages_per_block)
    want = attention_decode_cached(q, cache, cache, side, side, *args[1:], 0.1)[..., :latent]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def _paged_program(kind, B, H, D, K, Dv, mp, N, rows=None):
    """The traced program (jaxpr text) of a paged kernel's wrapper."""
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    i = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    caches = (s(3, 64, 16, K * D), s(3, 64, 16, K * Dv), s(B, N, K * D), s(B, N, K * Dv))
    if kind == "decode":
        fn = lambda q, kc, vc, hk, hv, n, l, t, e: paged_attention_decode_cached(
            q, kc, vc, hk, hv, n, l, t, e, D ** -0.5, window=jnp.int32(0))
        return str(jax.make_jaxpr(fn)(s(B, H, D), *caches, i(), i(), i(B, mp), i(B)))
    fn = lambda q, kc, vc, hk, hv, held, l, t, e: paged_attention_verify_cached(
        q, kc, vc, hk, hv, held, l, t, e, D ** -0.5)
    return str(jax.make_jaxpr(fn)(s(B, rows, H, D), *caches, i(B), i(), i(B, mp), i(B)))


@pytest.mark.parametrize("kind,shape,digest", [
    # sha256 of the text the parent of PR 45 (39194d3) traces, jax 0.9.0
    ("decode", dict(B=4, H=16, D=128, K=8, Dv=128, mp=32, N=8), "2cd8b40e7e3fc949"),
    ("decode", dict(B=2, H=64, D=192, K=4, Dv=128, mp=16, N=4), "d60ba84110a3e18d"),
    ("verify", dict(B=4, H=16, D=128, K=8, Dv=128, mp=32, N=8, rows=2), "598e600d752dbfb1"),
    ("verify", dict(B=2, H=8, D=128, K=4, Dv=128, mp=16, N=6, rows=3), "fc5c682e18c32e9f"),
])
def test_paged_and_verify_kernels_trace_to_the_programs_they_did(kind, shape, digest):
    """The latent cache took a kernel body of its own (PR 45); the paged
    kernel and the verify column were to stay the programs they were, and
    this holds them to it: the whole traced text, kernel body included."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's printing")
    text = _paged_program(kind, **shape)
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
