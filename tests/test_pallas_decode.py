"""Parity tests: pallas paged decode attention (interpret mode) vs the XLA
gather path (``ops/attention.py::attention_decode_cached``) — the two
implementations ``runner._attn_impl_for`` switches between, including the
sliding-window and logit-softcap masks (VERDICT r4 next-round #1: Gemma-2 /
Mistral shapes must not fall back to XLA)."""

import collections
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from smg_tpu.ops.attention import attention_decode_cached, attention_verify_cached
from smg_tpu.ops.pallas.decode_attention import (
    BLOCK_TOKENS,
    _pages_per_block,
    latent_attention_decode_cached,
    paged_attention_decode_cached,
    paged_attention_verify_cached,
)


def _setup(B, H, D, K, ps, mp, N, entries, P=64, seed=0, dtype=jnp.float32, Dv=None):
    rng = np.random.default_rng(seed)
    L, layer = 3, 1
    KD, VD = K * D, K * (Dv or D)  # values may be narrower than keys (mimo-v2-flash)
    k_cache = jnp.asarray(rng.standard_normal((L, P, ps, KD)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((L, P, ps, VD)), dtype)
    # distinct pages per sequence (page 0 reserved as garbage)
    pt = rng.permutation(P - 1)[: B * mp].reshape(B, mp) + 1
    page_tables = jnp.asarray(pt, jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    hk = jnp.asarray(rng.standard_normal((B, N, KD)), dtype)
    hv = jnp.asarray(rng.standard_normal((B, N, VD)), dtype)
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
LONG_MP = 70  # a table past two of the kernels' own blocks (32 pages each)
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
    # the cells' other widths, behind tables longer than two of the kernel's own blocks
    # (a trailing value head dim and table width): mimo-v2-flash's 64 heads on 4 KV heads,
    # keys of 192 and values of 128; nemotron-3-super-120b-a12b's 32 on 2 of 128
    "mimo_widths": (2, 64, 192, 4, [150, 33], 3, None, None, 4, jnp.float32, 128, MP),
    "mimo_widths_own_block": (2, 64, 192, 4, [1100, 513], 2, None, None, None, jnp.float32,
                              128, LONG_MP),
    "bfloat16_mimo_widths_own_block": (3, 64, 192, 4, [1025, 0, 1024], 1, None, None, None,
                                       jnp.bfloat16, 128, LONG_MP),
    "nemotron_widths": (3, 32, 128, 2, [200, 0, 37], 2, None, None, 4, jnp.float32, 128, MP),
    "nemotron_widths_own_block": (2, 32, 128, 2, [1100, 513], 1, None, None, None, jnp.float32,
                                  128, LONG_MP),
    "bfloat16_nemotron_widths_own_block": (2, 32, 128, 2, [1119, 512], 4, None, None, None,
                                           jnp.bfloat16, 128, LONG_MP),
    "window_over_own_blocks": (2, 16, 128, 8, [1100, 600], 2, 530, None, None, jnp.float32,
                               128, LONG_MP),
    "block_of_24_pages": (2, 8, 64, 8, [1100, 385], 1, None, None, 24, jnp.float32, 64, LONG_MP),
}


@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_block_loop_parity_vs_xla(case):
    """What a loop over blocks of pages can get wrong: a short last block,
    a block boundary, no block at all, a window that opens inside a block,
    lanes without blocks between lanes that hand their first block on, and
    the served dtype (bfloat16 against the XLA form in bfloat16: the same
    operands, another order of summation)."""
    B, H, D, K, entries, n_extra, window, softcap, n, dtype, *wider = BLOCK_CASES[case]
    Dv, mp = wider or (D, MP)
    ps, N = 16, 4
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, mp, N, entries, P=B * mp + 8 if wider else 128, dtype=dtype, Dv=Dv)
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
    real = np.asarray(entries) < mp * ps  # a padded row's output is nobody's
    np.testing.assert_allclose(got[real], want[real], rtol=tol, atol=tol)


def _verify_args(B, W, H, D, K, mp, N, entries, held, dtype, seed=0):
    """A verify column's arguments: ``W`` query rows a lane over ``_setup``'s caches."""
    _, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, 16, mp, N, entries, P=B * mp + 8, seed=seed, dtype=dtype)
    q = jnp.asarray(np.random.default_rng(seed + 1).standard_normal((B, W, H, D)), dtype)
    return (q, k_cache, v_cache, hk, hv, jnp.asarray(held, jnp.int32), layer, page_tables,
            entry_positions, 1.0 / np.sqrt(D))


VERIFY_CASES = {
    # B, W (rows a lane), H, D, K, mp, N, entries, held, pages_per_block, dtype
    "two_rows": (3, 2, 16, 128, 8, MP, 6, [100, 0, 207], [0, 4, 2], 4, jnp.float32),
    "three_rows_padded_lane": (3, 3, 8, 128, 4, MP, 6, [CAP, 150, 65], [3, 0, 1], 2, jnp.float32),
    "two_rows_one_page_a_block": (2, 2, 8, 64, 2, MP, 4, [100, 37], [2, 0], 1, jnp.float32),
    "k_exaone_widths_own_block": (2, 2, 64, 128, 8, LONG_MP, 16, [1100, 513], [14, 6], None,
                                  jnp.float32),
    "bfloat16_k_exaone_widths_own_block": (3, 2, 64, 128, 8, LONG_MP, 16, [1025, 0, 1024],
                                           [0, 7, 14], None, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(VERIFY_CASES))
def test_verify_block_loop_parity_vs_xla(case):
    """The verify column (``rows`` query rows a lane on the query's head
    axis, each row its own count of side rows) through the same loop over
    blocks, against ``attention_verify_cached``."""
    B, W, H, D, K, mp, N, entries, held, n, dtype = VERIFY_CASES[case]
    args = _verify_args(B, W, H, D, K, mp, N, entries, held, dtype)
    got = paged_attention_verify_cached(*args, interpret=True, pages_per_block=n)
    want = attention_verify_cached(*args)
    assert got.dtype == want.dtype == dtype and got.shape == (B, W, H, D)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    real = np.asarray(entries) < mp * 16
    np.testing.assert_allclose(got[real], want[real], rtol=tol, atol=tol)


PAST_ENTRY_WIDTHS = {
    # H, D, K, value head dim, rows of a verify column (0: the decode kernel)
    "llama": (8, 64, 8, 64, 0),
    "mimo": (64, 192, 4, 128, 0),
    "nemotron": (32, 128, 2, 128, 0),
    "verify": (16, 128, 8, 128, 2),
}


@pytest.mark.parametrize("pages_per_block", [None, 4, 1])
@pytest.mark.parametrize("widths", list(PAST_ENTRY_WIDTHS))
def test_pages_past_entry_are_not_fetched(widths, pages_per_block):
    """A lane's last block fetches the pages the lane holds: poison every
    page of the table past them and the output stays finite and equal (the
    XLA form gathers the whole table and could not pass this).  A page's
    copy is predicated on the lane's page count, whatever the widths and
    whichever wrapper."""
    H, D, K, Dv, rows = PAST_ENTRY_WIDTHS[widths]
    B, ps, N, mp = 3, 16, 4, 40  # past one of the kernel's own blocks
    entries = [70, 0, 529]
    q, k_cache, v_cache, hk, hv, layer, page_tables, entry_positions = _setup(
        B, H, D, K, ps, mp, N, entries, P=128, Dv=Dv)
    pt = np.asarray(page_tables)
    kc, vc = np.array(k_cache), np.array(v_cache)
    for b, e in enumerate(entries):
        for i in range(-(-e // ps), mp):
            kc[layer, pt[b, i]] = np.nan
            vc[layer, pt[b, i]] = np.nan
    tail = (layer, page_tables, entry_positions, 1.0 / np.sqrt(D))
    if rows:
        q = jnp.stack([q, q + 1.0], axis=1)
        args = (hk, hv, jnp.asarray([0, 2, 1], jnp.int32), *tail)
        kernel, xla = paged_attention_verify_cached, attention_verify_cached
    else:
        args = (hk, hv, jnp.int32(2), *tail)
        kernel, xla = paged_attention_decode_cached, attention_decode_cached
    got = kernel(q, jnp.asarray(kc), jnp.asarray(vc), *args, interpret=True,
                 pages_per_block=pages_per_block)
    want = xla(q, k_cache, v_cache, *args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ps,lanes,itemsize,mp,want", [
    (16, 1024, 2, 256, 32),   # qwen3-1.7b, k-exaone-236b-a23b: 32 KB pages, 512 tokens are the limit
    (16, 3840, 2, 256, 8),    # olmo-hybrid-7b: 122,880 B pages, 128 tokens
    (16, 512, 2, 512, 32),    # llama3.2-1b
    (16, 1024, 2, 8, 8),      # a table narrower than a block
    (16, 8192, 4, 64, 2),     # a page of 512 KB
    (16, 768, 2, 256, 32),    # mimo-v2-flash: K pages of 24,576 B, a slot of 786,432 B
    (16, 256, 2, 128, 32),    # nemotron-3-super-120b-a12b: 8,192 B pages, a slot of 262,144 B
    (16, 640, 2, 256, 32),    # the latent cache: 20,480 B entries a page, 655,360 B
    (16, 640, 4, 256, 25),    # the same in float32: the byte limit cuts it
    (16, 2048, 2, 256, 16),   # 16 KV heads of 128: 256 tokens
    (32, 1024, 2, 64, 16),    # pages of 32 tokens
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
    assert BLOCK_TOKENS == 512
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


def _latent_program(B, H, W, latent, mp, N):
    """The traced program (jaxpr text) of the latent kernel's wrapper."""
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    i = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    fn = lambda q, c, side, n, l, t, e: latent_attention_decode_cached(
        q, c, side, n, l, t, e, latent=latent, scale=W ** -0.5)
    return str(jax.make_jaxpr(fn)(s(B, H, W), s(3, 64, 16, W), s(B, N, W), i(), i(), i(B, mp),
                                  i(B)))


@pytest.mark.parametrize("kind,shape,digest", [
    # sha256 of the text this tree (PR 48) traces, jax 0.9.0
    ("decode", dict(B=4, H=16, D=128, K=8, Dv=128, mp=32, N=8), "698214092b87cb95"),
    ("decode", dict(B=2, H=64, D=192, K=4, Dv=128, mp=16, N=4), "085cb1c58569a119"),
    ("verify", dict(B=4, H=16, D=128, K=8, Dv=128, mp=32, N=8, rows=2), "d4de83902fbbad67"),
    ("verify", dict(B=2, H=8, D=128, K=4, Dv=128, mp=16, N=6, rows=3), "753cd7fab8536765"),
    # sha256 of the text the parent of PR 48 (4d6ca1c) traces: the latent wrapper's
    ("latent", dict(B=4, H=128, W=640, latent=512, mp=64, N=8), "27f871576a73cc19"),
    ("latent", dict(B=2, H=64, W=640, latent=512, mp=16, N=1), "2ce33e3be56023e8"),
])
def test_decode_kernels_trace_to_the_programs_they_did(kind, shape, digest):
    """The whole traced text of a wrapper, kernel body included, held to a
    digest.  PR 45 gave the latent cache a body of its own and held the paged
    kernel and the verify column to the programs they were; PR 48 rebuilt the
    paged body's loop as the latent one's, with the starts, the waits and the
    zeroing as helpers that both bodies call, so **its four digests moved on
    purpose and are this tree's**, and the latent wrapper, whose cells were
    not to move, is held to the text its parent traced."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's printing")
    text = _latent_program(**shape) if kind == "latent" else _paged_program(kind, **shape)
    assert "pallas_call" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def _walk_lanes(entries, n, ps, mp, window=0, n_extra=1):
    """The block loop's rule in plain Python (``_decode_kernel`` and, with no
    window, ``_latent_decode_kernel``): who starts which pages of which lane
    into which slot (lane 0 its own first block, a lane without blocks the
    next lane's first, the lane's top its second, the loop every later one
    and the next lane's first) and who waits for them.  Returns the pages
    fetched a lane, and fails where a semaphore is not back at zero, a wait
    asks for more than was started (the chip would hang), or a block is
    started into a slot whose last block was not awaited."""
    B = len(entries)
    sem = [0, 0]
    fetched = [collections.Counter() for _ in range(B)]

    def lane_pages(b):
        entry = entries[b]
        n_pages = 0 if entry >= mp * ps else -(-entry // ps)
        lo = max(entry + n_extra - window, 0) if window > 0 else 0
        first = min(lo // ps, n_pages)
        return first, n_pages, -(-(n_pages - first) // n)

    def start(lane, page0, held, slot):
        assert sem[slot] == 0 or page0 >= held, "a slot started before its last block was awaited"
        for i in range(n):
            if page0 + i < held:
                assert page0 + i < mp, "a page past the lane's table"
                sem[slot] += 1
                fetched[lane][page0 + i] += 1

    def wait(count, slot):
        for bit in range(n.bit_length()):
            if count & (1 << bit):
                assert sem[slot] >= 1 << bit, "a wait for pages nobody started"
                sem[slot] -= 1 << bit

    slot_ref = 0
    for b in range(B):
        first, n_pages, blocks = lane_pages(b)
        nxt = min(b + 1, B - 1)
        next_first, next_pages, next_blocks = lane_pages(nxt)
        next_has_blocks = b + 1 < B and next_blocks > 0
        slot0 = 0 if b == 0 else slot_ref
        own_first = b == 0 and blocks > 0
        if own_first or (blocks == 0 and next_has_blocks):
            start(b if own_first else nxt, first if own_first else next_first,
                  n_pages if own_first else next_pages, slot0)
        start(b, first + n, n_pages if blocks > 1 else 0, 1 - slot0)
        for j in range(blocks):
            slot = (slot0 + j) & 1
            page0 = first + j * n
            if j + 1 < blocks:
                start(b, page0 + n, n_pages if j > 0 else 0, 1 - slot)
            else:
                start(nxt, next_first, next_pages if next_has_blocks else 0, 1 - slot)
            wait(min(n_pages - page0, n), slot)
        slot_ref = (slot0 + blocks) & 1
    assert sem == [0, 0], "a semaphore above zero at the kernel's exit"
    return fetched, [lane_pages(b) for b in range(B)]


@pytest.mark.parametrize("windowed", [False, True], ids=["global", "window"])
@pytest.mark.parametrize("seed", range(4))
def test_every_page_is_started_once_and_awaited(seed, windowed):
    """What the interpreter cannot refuse and the chip does (a block started
    twice leaves its semaphore above zero and halts the chip at the kernel's
    exit, ``PERF.md`` section 6, PR 45): the loop's rule walked in plain
    Python over a thousand random tables a case, with empty lanes, padded
    rows (``entry`` at or past the table), lanes of one and two blocks, and a
    window whose first live page is not 0.  Every semaphore returns to zero
    and every page a lane holds inside its window is fetched once, none
    else.  Without a window this is the latent body's rule too."""
    rng = np.random.default_rng(seed)
    ps = 16
    for _ in range(1000):
        n = int(rng.choice([1, 2, 3, 4, 8, 16, 24, 32]))
        mp = int(rng.integers(1, 5 * n + 2))
        B = int(rng.integers(1, 7))
        kinds = rng.integers(0, 6, B)
        entries = [int(e) for e in np.select(
            [kinds == 0, kinds == 1, kinds == 2, kinds == 3],
            [0, mp * ps + rng.integers(0, 3, B),  # empty; padded
             rng.integers(0, n * ps + 1, B), rng.integers(n * ps, 2 * n * ps + 1, B)],
            rng.integers(0, mp * ps + 1, B))]
        window = int(rng.integers(1, mp * ps + 40)) if windowed else 0
        n_extra = int(rng.integers(1, 9))
        fetched, lanes = _walk_lanes(entries, n, ps, mp, window, n_extra)
        for got, (first, n_pages, _) in zip(fetched, lanes):
            assert got == collections.Counter(range(first, n_pages)), (entries, n, mp, window)


EAGER = pltpu.InterpretParams(dma_execution_mode="eager")
SEMAPHORE_CASES = {
    # kind, B, H, D, K, Dv, mp, entries, window, pages_per_block
    "decode_two_block_lanes": ("decode", 3, 8, 64, 8, 64, 13, [100, 0, 207], None, 4),
    "decode_padded_and_empty": ("decode", 4, 8, 64, 2, 64, 13, [CAP, 129, 0, 64], None, 2),
    "decode_window_first_page_not_0": ("decode", 3, 8, 64, 8, 64, 13, [200, 90, 7], 40, 2),
    "decode_mimo_widths": ("decode", 2, 64, 192, 4, 128, 40, [600, 513], None, None),
    "decode_nemotron_widths": ("decode", 3, 32, 128, 2, 128, 40, [640, 0, 31], None, None),
    "verify_rows_2": ("verify2", 3, 16, 128, 8, 128, 13, [100, 0, 207], None, 4),
    "verify_rows_3": ("verify3", 3, 8, 128, 4, 128, 13, [CAP, 150, 65], None, 2),
    "latent": ("latent", 3, 8, 256, 0, 128, 13, [100, 0, 207], None, 4),
}


@pytest.mark.parametrize("case", list(SEMAPHORE_CASES))
def test_no_semaphore_above_zero_at_the_kernels_exit(case, capfd):
    """The kernels themselves under the interpreter that models the chip's
    semaphores, with every copy run when it is started: it prints a semaphore
    left above zero at a kernel's exit, which the plain interpreter does not
    see (a loop that started a lane's second block twice printed here; a wait
    for pages nobody started would hang, which the walk above refuses
    first)."""
    kind, B, H, D, K, Dv, mp, entries, window, n = SEMAPHORE_CASES[case]
    rng = np.random.default_rng(0)
    P, N, ps = B * mp + 8, 6, 16
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tables = jnp.asarray(rng.permutation(P - 1)[: B * mp].reshape(B, mp) + 1, jnp.int32)
    entry = jnp.asarray(entries, jnp.int32)
    if kind == "latent":
        q, cache, side = arr(B, H, D), arr(2, P, ps, D), arr(B, N, D)
        got = latent_attention_decode_cached(q, cache, side, jnp.int32(2), 1, tables, entry,
                                             latent=Dv, scale=0.1, interpret=EAGER,
                                             pages_per_block=n)
        want = attention_decode_cached(q, cache, cache, side, side, jnp.int32(2), 1, tables,
                                       entry, 0.1)[..., :Dv]
    else:
        kc, vc = arr(2, P, ps, K * D), arr(2, P, ps, K * Dv)
        hk, hv = arr(B, N, K * D), arr(B, N, K * Dv)
        if kind == "decode":
            w = None if window is None else jnp.int32(window)
            args = (arr(B, H, D), kc, vc, hk, hv, jnp.int32(2), 1, tables, entry, 0.1)
            got = paged_attention_decode_cached(*args, window=w, interpret=EAGER,
                                                pages_per_block=n)
            want = attention_decode_cached(*args, window=w)
        else:
            rows = int(kind[-1])
            held = jnp.asarray(rng.integers(0, N - rows + 1, B), jnp.int32)
            args = (arr(B, rows, H, D), kc, vc, hk, hv, held, 1, tables, entry, 0.1)
            got = paged_attention_verify_cached(*args, interpret=EAGER, pages_per_block=n)
            want = attention_verify_cached(*args)
    real = np.asarray(entries) < mp * ps
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], rtol=2e-5,
                               atol=2e-5)
    assert "non-zero count" not in capfd.readouterr().out
