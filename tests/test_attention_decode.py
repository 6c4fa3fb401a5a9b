"""XLA decode and verify attention (``ops/attention.py``) against a per-head
float32 reference written here: pages gathered by a Python loop, one head at
a time, a plain softmax.  Both forms of the products run on every case: on
the fused lanes (lane axis whole on the device) and per head (lane axis
sharded over a mesh); on one device they must agree with the reference and
so with each other."""

import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.ops.attention import (
    attention_decode_cached,
    attention_verify_block,
    gather_layer_pages,
    gather_seq_kv,
)

PS, MP, N, L, P, LAYER = 16, 4, 4, 3, 24, 2
S = MP * PS
FORMS = pytest.mark.parametrize("lanes_sharded", [False, True],
                                ids=["fused_lanes", "per_head"])


def _inputs(G, D, K=2, W=None, dtype=jnp.float32, seed=0):
    """Four lanes: a ragged one, an empty cache (entry 0), a padded lane
    (entry past the table) and one that shares its first page with lane 0."""
    rng = np.random.default_rng(seed)
    KD, H = K * D, K * G
    k_cache = rng.standard_normal((L, P, PS, KD)).astype(np.float32)
    v_cache = rng.standard_normal((L, P, PS, KD)).astype(np.float32)
    pt = rng.permutation(P - 1)[: 4 * MP].reshape(4, MP) + 1
    pt[3, 0] = pt[0, 0]
    entries = np.array([37, 0, S + 5, 29], np.int32)
    q = rng.standard_normal((4, H, D) if W is None else (4, W, H, D))
    rows = N if W is None else W
    sk = rng.standard_normal((4, rows, KD)).astype(np.float32)
    sv = rng.standard_normal((4, rows, KD)).astype(np.float32)
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    return (cast(q), cast(k_cache), cast(v_cache), cast(sk), cast(sv),
            jnp.asarray(pt, jnp.int32), jnp.asarray(entries))


def _reference(q, k_cache, v_cache, sk, sv, pt, entries, side_rows, scale,
               softcap, window):
    """q [B, W, H, D]; ``side_rows[w]`` side rows are keys of query w, which
    sits at ``entry + side_rows[w] - 1`` (decode: W 1; verify: w + 1)."""
    q, k_cache, v_cache, sk, sv = (
        np.asarray(a, np.float32) for a in (q, k_cache, v_cache, sk, sv))
    B, W, H, D = q.shape
    K = k_cache.shape[-1] // D
    G = H // K
    out = np.zeros((B, W, H, D), np.float32)
    for b in range(B):
        keys = np.concatenate([k_cache[LAYER, p] for p in np.asarray(pt[b])])
        vals = np.concatenate([v_cache[LAYER, p] for p in np.asarray(pt[b])])
        entry = int(entries[b])
        n_cache = min(entry, S)
        for w in range(W):
            n_side = side_rows[w]
            pos = np.concatenate([np.arange(n_cache), entry + np.arange(n_side)])
            q_pos = entry + n_side - 1
            keep = np.ones_like(pos, bool)
            if window is not None and window > 0:
                keep = pos > q_pos - window
            for h in range(H):
                lanes = slice((h // G) * D, (h // G + 1) * D)
                kk = np.concatenate([keys[:n_cache, lanes], sk[b, :n_side, lanes]])
                vv = np.concatenate([vals[:n_cache, lanes], sv[b, :n_side, lanes]])
                s = (kk[keep] @ q[b, w, h]) * scale
                if softcap:
                    s = softcap * np.tanh(s / softcap)
                p = np.exp(s - s.max())
                out[b, w, h] = (p / p.sum()) @ vv[keep]
    return out


def _check_decode(G, D, n_extra, softcap, window, lanes_sharded,
                  dtype=jnp.float32, tol=2e-5):
    q, kc, vc, hk, hv, pt, entries = _inputs(G, D, dtype=dtype)
    scale = 1.0 / np.sqrt(D)
    got = attention_decode_cached(
        q, kc, vc, hk, hv, jnp.int32(n_extra), jnp.int32(LAYER), pt, entries,
        scale, softcap=softcap,
        window=None if window is None else jnp.int32(window),
        lanes_sharded=lanes_sharded,
    )
    want = _reference(q[:, None], kc, vc, hk, hv, pt, entries, [n_extra],
                      scale, softcap, window)[:, 0]
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


@FORMS
@pytest.mark.parametrize("n_extra", [1, N])
@pytest.mark.parametrize("D", [16, 64, 128])
@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_decode_matches_per_head_reference(G, D, n_extra, lanes_sharded):
    _check_decode(G, D, n_extra, None, None, lanes_sharded)


@FORMS
@pytest.mark.parametrize("softcap,window", [
    (30.0, None),  # softcap only (Gemma-2)
    (None, 20),  # window shorter than the cache: cuts into lanes 0 and 3
    (30.0, 20),
    (None, 3),  # window inside the side rows
    (None, 0),  # window <= 0 means global
    (None, 4096),  # window wider than the context
])
@pytest.mark.parametrize("G,D", [(2, 64), (4, 16)])
def test_decode_softcap_and_window(G, D, softcap, window, lanes_sharded):
    _check_decode(G, D, N, softcap, window, lanes_sharded)


@FORMS
def test_decode_in_the_cache_dtype(lanes_sharded):
    """bfloat16 cache and queries: the products run in bfloat16 with float32
    accumulation, so the distance to the float32 reference is bfloat16's."""
    _check_decode(2, 64, 2, None, None, lanes_sharded, dtype=jnp.bfloat16,
                  tol=3e-2)


@FORMS
@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, 20), (None, 2)])
@pytest.mark.parametrize("G,D", [(2, 64), (8, 16)])
def test_verify_block_matches_per_head_reference(G, D, softcap, window,
                                                 lanes_sharded):
    W = 5
    q, kc, vc, bk, bv, pt, entries = _inputs(G, D, W=W, seed=1)
    scale = 1.0 / np.sqrt(D)
    got = attention_verify_block(
        q, kc, vc, bk, bv, jnp.int32(LAYER), pt, entries, scale,
        softcap=softcap, window=None if window is None else jnp.int32(window),
        lanes_sharded=lanes_sharded,
    )
    want = _reference(q, kc, vc, bk, bv, pt, entries,
                      [w + 1 for w in range(W)], scale, softcap, window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, LAYER])
@pytest.mark.parametrize("lanes", [None, 1, 4], ids=["one_table", "B1", "B4"])
def test_gather_layer_pages_is_the_python_loop(lanes, layer):
    _, kc, vc, _, _, pt, _ = _inputs(2, 16)
    tables = pt[0] if lanes is None else pt[:lanes]
    k, v = gather_layer_pages(kc, vc, jnp.int32(layer), tables)
    flat = np.asarray(tables).reshape(-1)
    for got, cache in ((k, kc), (v, vc)):
        want = np.stack([np.asarray(cache)[layer, p] for p in flat])
        assert got.shape == tables.shape + kc.shape[2:]
        np.testing.assert_array_equal(
            np.asarray(got).reshape(want.shape), want)
    if lanes is None:
        ks, vs = gather_seq_kv(kc, vc, jnp.int32(layer), tables, 2)
        np.testing.assert_array_equal(np.asarray(ks).reshape(MP, PS, -1),
                                      np.asarray(k))
        np.testing.assert_array_equal(np.asarray(vs).reshape(MP, PS, -1),
                                      np.asarray(v))
