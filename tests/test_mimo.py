"""``models/mimo.py`` (window layers with a sink beside full layers, keys wider
than values, rotary over part of a head, routed experts picked by a biased
score on a chip's share) and its ops on the CPU in float32, held to the plain
reference ``benchmark/architectures/mimo_v2_flash.py``: the serving forwards
through pages and rings, both decode kernels interpreted, the ring's
arithmetic, the share test of the model-configs guide, the router, the loader
and the cache plan."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import mimo as M
from smg_tpu.models.config import ModelConfig, tiny_mimo_config
from smg_tpu.ops import moe
from smg_tpu.ops import window_attention as wa
from smg_tpu.ops.rope import rope_frequencies

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark"))
import catalog  # noqa: E402

ARCH = catalog.architecture("mimo_v2_flash")
PS = 16
N = 8  # columns a frame
HELD = (4, 8)  # experts 4..11 of the router's 16


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration."""
    return {"head_dim": cfg.head_dim, "v_head_dim": cfg.v_head_dim,
            "partial_rotary_factor": (cfg.qk_rope_head_dim + 0.5) / cfg.head_dim,
            "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.swa_rope_theta,
            "sliding_window": cfg.sliding_window,
            "add_swa_attention_sink_bias": cfg.swa_sink_bias,
            "attention_value_scale": cfg.attention_value_scale,
            "layernorm_epsilon": cfg.rms_norm_eps,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_expert_offset": cfg.held_experts[0],
            "hybrid_layer_pattern": [int(t == "sliding_attention") for t in cfg.layer_types],
            "moe_layer_freq": list(cfg.moe_layer_freq)}


class World:
    def __init__(self, cfg, slots=5):
        self.cfg = cfg
        self.params = M.init_params(cfg, jax.random.PRNGKey(0))
        self.inv = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None))
        self.R = wa.ring_tokens(cfg.sliding_window, 2 * N)
        self.slots = slots

    def caches(self, pages=40):
        (fk, fv), (wk, wv) = self.cfg.kv_lanes(False), self.cfg.kv_lanes(True)
        full = lambda lanes: jnp.zeros((self.cfg.num_cache_layers, pages, PS, lanes), jnp.float32)
        ring = lambda lanes: jnp.zeros((self.cfg.num_window_layers, self.slots, self.R, lanes),
                                       jnp.float32)
        return full(fk), full(fv), ring(wk), ring(wv)

    def prefill(self, state, toks, lo, table, slot, bucket=64):
        chunk = np.zeros(bucket, np.int32)
        chunk[: len(toks)] = toks
        kc, vc, rk, rv = state
        logits, *state = jax.jit(lambda *a: M.forward_prefill(self.params, self.cfg, self.inv, *a))(
            jnp.asarray(chunk), jnp.int32(lo), jnp.int32(len(toks)), kc, vc, jnp.asarray(table),
            rk, rv, jnp.int32(slot))
        return logits, tuple(state)

    def decoder(self, impl, cfg=None):
        return jax.jit(lambda *a: M.forward_decode_horizon(
            self.params, cfg or self.cfg, self.inv, *a, attn_impl=impl, moe_impl=impl))


@pytest.fixture(scope="module")
def world():
    return World(tiny_mimo_config(held=HELD))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


def decode_frame(world, dec, state, toks, n, table, slot, cols, B=4, lane=1):
    """``cols`` columns of one frame for a sequence of ``n`` tokens in lane
    ``lane``, beside padded rows; returns each column's logits of the lane."""
    kc, vc, rk, rv = state
    side = M.side_buffers(world.cfg, B, N, jnp.float32)
    tables = np.zeros((B, len(table)), np.int32)
    tables[lane] = table
    entry = np.full(B, len(table) * PS, np.int32)  # padded rows sit past the table
    entry[lane] = n
    slots = np.zeros(B, np.int32)
    slots[lane] = slot
    out = []
    for j in range(cols):
        cur = np.zeros(B, np.int32)
        cur[lane] = toks[n + j]
        logits, side, _counts = dec(
            jnp.asarray(cur), jnp.asarray(entry + j), jnp.asarray(entry), jnp.int32(j), kc, vc,
            jnp.asarray(tables), rk, rv, jnp.asarray(slots), side, jnp.asarray(slots > 0))
        out.append(logits[lane])
    return out, side, (jnp.asarray(tables), jnp.asarray(entry), jnp.asarray(slots))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_chunks_then_decode_through_pages_and_rings_is_one_full_forward(world, impl):
    """A context past the window (8) and past a ring's wrap (32), the second
    chunk behind a live prefix longer than the window."""
    rng = np.random.default_rng(0)
    n, n_dec = 70, 6
    toks = rng.integers(2, 512, size=n + n_dec).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(world.cfg), toks, list(range(n - 1, n + n_dec)))
    table = np.arange(1, 9, dtype=np.int32)
    state = world.caches()
    for lo, hi in ((0, 30), (30, n)):
        logits, state = world.prefill(state, toks[lo:hi], lo, table, slot=2)
    assert rel_err(logits, ref[0]) < 1e-4
    got, _side, _ = decode_frame(world, world.decoder(impl), state, toks, n, table, 2, n_dec)
    for j, row in enumerate(got):
        assert rel_err(row, ref[1 + j]) < 1e-4, (impl, j)


def test_frames_landed_in_the_ring_carry_a_sequence_around_it(world):
    """Five frames of eight columns from a 20-token prompt: every entry of the
    ring (32) is written by a landing, and the logits stay the reference's."""
    rng = np.random.default_rng(1)
    n, frames = 20, 5
    toks = rng.integers(2, 512, size=n + frames * N).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(world.cfg), toks, list(range(n, n + frames * N)))
    table = np.arange(1, 9, dtype=np.int32)
    _logits, state = world.prefill(world.caches(), toks[:n], 0, table, slot=3)
    dec = world.decoder("xla")
    from smg_tpu.ops.attention import land_side_buffers

    for f in range(frames):
        at = n + f * N
        got, (hk, hv, wk, wv), (tables, entry, slots) = decode_frame(
            world, dec, state, toks, at, table, 3, N)
        for j, row in enumerate(got):
            assert rel_err(row, ref[f * N + j]) < 1e-4, (f, j)
        kc, vc, rk, rv = state
        ran = jnp.ones((1, N), bool)
        kc, vc = land_side_buffers(kc, vc, hk, hv, tables, entry, ran)
        rk, rv = wa.land_ring_side(rk, rv, wk, wv, slots, entry, ran)
        state = (kc, vc, rk, rv)
    # padded lanes wrote entry 0 of the garbage slot and nothing else
    assert not np.any(np.asarray(state[2][:, 0, 1:])) and not np.any(np.asarray(state[2][:, 1]))


def test_what_a_discarded_frame_wrote_is_outside_every_later_window(world):
    """A frame and its lookahead land sixteen columns of garbage past the
    accepted length; the next frame from that length reads the reference."""
    rng = np.random.default_rng(2)
    n = 45
    toks = rng.integers(2, 512, size=n + N).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(world.cfg), toks, list(range(n, n + 4)))
    table = np.arange(1, 9, dtype=np.int32)
    _logits, (kc, vc, rk, rv) = world.prefill(world.caches(), toks[:n], 0, table, slot=1)
    junk = lambda x, w: jnp.full((x.shape[0], 1, 2 * N, x.shape[3]), w, jnp.float32)
    rk, rv = wa.land_ring_side(rk, rv, junk(rk, 9.0), junk(rv, -7.0), jnp.asarray([1]),
                               jnp.asarray([n]), jnp.ones((1, 2 * N), bool))
    got, _side, _ = decode_frame(world, world.decoder("xla"), (kc, vc, rk, rv), toks, n, table,
                                 1, 4)
    for j, row in enumerate(got):
        assert rel_err(row, ref[j]) < 1e-4


def test_grouped_prefill_with_and_without_context_matches_the_solo_chunks(world):
    rng = np.random.default_rng(3)
    lens = (33, 50, 12)
    toks = [rng.integers(2, 512, size=n).astype(np.int32) for n in lens]
    tables = np.stack([np.arange(1 + 8 * i, 9 + 8 * i, dtype=np.int32) for i in range(4)])
    solo = []
    for whole in (True, False):
        state = world.caches()
        rows = []
        for i, t in enumerate(toks):
            cuts = ((0, len(t)),) if whole else ((0, 10), (10, len(t)))
            for lo, hi in cuts:
                logits, state = world.prefill(state, t[lo:hi], lo, tables[i], slot=i + 1)
            rows.append(logits)
        solo.append(rows)
    batched = jax.jit(lambda *a, no_ctx: M.forward_prefill_batched(
        world.params, world.cfg, world.inv, *a, no_ctx=no_ctx), static_argnames="no_ctx")
    pack = lambda parts: jnp.asarray(np.stack(
        [np.pad(p, (0, 64 - len(p))) for p in parts] + [np.zeros(64, np.int32)]))
    slots = jnp.asarray([1, 2, 3, 0])
    n = jnp.asarray([*lens, 0])
    logits, *state = batched(pack(toks), jnp.zeros(4, jnp.int32), n, *world.caches()[:2],
                             jnp.asarray(tables), *world.caches()[2:], slots, no_ctx=True)
    for i in range(3):
        assert rel_err(logits[i], np.asarray(solo[0][i])) < 1e-4
    # every row's first ten tokens alone, then the rest as one group behind them
    kc, vc, rk, rv = world.caches()
    _, kc, vc, rk, rv = batched(pack([t[:10] for t in toks]), jnp.zeros(4, jnp.int32),
                                jnp.asarray([10, 10, 10, 0]), kc, vc, jnp.asarray(tables), rk, rv,
                                slots, no_ctx=True)
    logits, *_ = batched(pack([t[10:] for t in toks]), jnp.asarray([10, 10, 10, 0]),
                         jnp.asarray([n - 10 for n in lens] + [0]), kc, vc, jnp.asarray(tables),
                         rk, rv, slots, no_ctx=False)
    for i in range(3):
        assert rel_err(logits[i], np.asarray(solo[1][i])) < 1e-4


# --------------------------------------------------------------------------
# the attention ops against einsums written by hand


def by_hand(q, k, v, pos_q, pos_k, scale, window=0, sink=None):
    """One sequence: ``q`` [T, H, Dk], ``k`` [S, G, Dk], ``v`` [S, G, Dv]."""
    H, G = q.shape[1], k.shape[1]
    k, v = np.repeat(k, H // G, axis=1), np.repeat(v, H // G, axis=1)
    s = np.einsum("thd,shd->hts", q, k) * scale
    seen = pos_q[:, None] >= pos_k[None, :]
    if window:
        seen &= pos_k[None, :] > pos_q[:, None] - window
    s = np.where(seen[None], s, -np.inf)
    e = np.exp(s - s.max(axis=-1, keepdims=True))
    denom = e.sum(axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + np.exp(sink[:, None, None] - s.max(axis=-1, keepdims=True))
    return np.einsum("hts,shd->thd", e / denom, v)


def qkv(rng, T, S, H, G, Dk, Dv):
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    return f(T, H, Dk), f(S, G, Dk), f(S, G, Dv)


@pytest.mark.parametrize("score_bytes", [2**30, 2**12])  # queries whole, and in blocks of 16
def test_full_prefill_attention_with_keys_wider_than_values(monkeypatch, score_bytes):
    monkeypatch.setattr("smg_tpu.ops.attention.SCORE_BLOCK_BYTES", score_bytes)
    rng = np.random.default_rng(4)
    T, S, H, G, Dk, Dv = 32, 48, 8, 2, 24, 16
    q, k, v = qkv(rng, T, S, H, G, Dk, Dv)
    pos = 16 + np.arange(T)  # a chunk behind sixteen tokens
    got = wa.attention_prefill_blocked(jnp.asarray(q)[None], jnp.asarray(k)[None],
                                       jnp.asarray(v)[None], jnp.asarray(pos)[None],
                                       jnp.asarray([S]), 0.2)
    assert got.shape == (1, T, H, Dv)
    np.testing.assert_allclose(got[0], by_hand(q, k, v, pos, np.arange(S), 0.2), atol=2e-5)


@pytest.mark.parametrize("T, prefix", [(32, 0), (24, 13), (5, 40)])  # T a multiple of the window or not
def test_window_prefill_attention_reads_the_chunk_and_the_window_before_it(T, prefix):
    rng = np.random.default_rng(5)
    W, H, G, Dk, Dv = 8, 8, 4, 24, 16
    q, k, v = qkv(rng, T, T, H, G, Dk, Dv)
    _, pk, pv = qkv(rng, 1, W, H, G, Dk, Dv)  # the window before the chunk
    sink = rng.standard_normal(H).astype(np.float32)
    pos = prefix + np.arange(T)
    got = wa.window_attention_prefill(*(jnp.asarray(x)[None] for x in (q, k, v, pk, pv, pos)),
                                      W, jnp.asarray(sink), 0.2)
    keys, vals = np.concatenate([pk, k]), np.concatenate([pv, v])
    key_pos = np.concatenate([prefix - W + np.arange(W), pos])
    real = key_pos >= 0
    want = by_hand(q, keys[real], vals[real], pos, key_pos[real], 0.2, window=W, sink=sink)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_the_sink_takes_mass_and_adds_no_value():
    rng = np.random.default_rng(6)
    T, W, H, G, Dk, Dv = 16, 8, 4, 2, 24, 16
    q, k, v = qkv(rng, T, T, H, G, Dk, Dv)
    none = jnp.zeros((1, W, G, Dk)), jnp.zeros((1, W, G, Dv))
    run = lambda sink: np.asarray(wa.window_attention_prefill(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None], *none,
        jnp.arange(T)[None], W, sink, 0.2)[0])
    plain = run(None)
    np.testing.assert_allclose(run(jnp.full((H,), -1e9)), plain, atol=1e-6)  # no mass: a softmax
    with_sink = run(jnp.asarray([0.0, 1.0, 2.0, 3.0]))
    shrink = np.linalg.norm(with_sink, axis=(0, 2)) / np.linalg.norm(plain, axis=(0, 2))
    assert np.all(shrink < 0.95) and np.all(np.diff(shrink) < 0)  # the larger, the more it takes
    # the first query sees one key: its output is v_0 * 1 / (1 + exp(b - s_00))
    s00 = float(np.dot(q[0, 3], k[0, 1]) * 0.2)
    np.testing.assert_allclose(with_sink[0, 3], v[0, 1] / (1 + math.exp(3.0 - s00)), atol=1e-5)


@pytest.mark.parametrize("entry, n_extra", [(0, 1), (5, 3), (31, 8), (32, 1), (77, 5)])
def test_both_window_decode_forms_read_the_ring_by_position(entry, n_extra):
    """A ring filled as a prefill and frames would fill it, at lengths below,
    at and past its wrap: the XLA form and the kernel interpreted against the
    einsum by hand over the positions inside the window."""
    from smg_tpu.ops.pallas.window_decode import window_attention_decode as kernel

    rng = np.random.default_rng(7)
    W, R, H, G, Dk, Dv, B, Nc = 8, 32, 16, 8, 64, 32, 3, 8
    total = entry + n_extra
    _, k, v = qkv(rng, 1, total, H, G, Dk, Dv)
    q = rng.standard_normal((B, H, Dk)).astype(np.float32)
    sink = rng.standard_normal(H).astype(np.float32)
    ring_k = rng.standard_normal((2, 4, R, G * Dk)).astype(np.float32)  # stale entries everywhere
    ring_v = rng.standard_normal((2, 4, R, G * Dv)).astype(np.float32)
    for p in range(max(0, entry - R), entry):
        ring_k[1, 2, p % R], ring_v[1, 2, p % R] = k[p].reshape(-1), v[p].reshape(-1)
    side_k = np.zeros((B, Nc, G * Dk), np.float32)
    side_v = np.zeros((B, Nc, G * Dv), np.float32)
    side_k[1, :n_extra] = k[entry:total].reshape(n_extra, -1)
    side_v[1, :n_extra] = v[entry:total].reshape(n_extra, -1)
    args = (jnp.asarray(q), jnp.asarray(ring_k), jnp.asarray(ring_v), jnp.asarray(side_k),
            jnp.asarray(side_v), jnp.int32(n_extra), jnp.int32(1), jnp.asarray([0, 2, 0]),
            jnp.asarray([256, entry, 256]), W, jnp.asarray(sink), 0.125)
    want = by_hand(q[1][None], k, v, np.asarray([total - 1]), np.arange(total), 0.125,
                   window=W, sink=sink)[0]
    np.testing.assert_allclose(wa.window_attention_decode(*args)[1], want, atol=2e-5)
    np.testing.assert_allclose(kernel(*args, interpret=True)[1], want, atol=2e-5)


def test_the_paged_decode_kernel_takes_values_narrower_than_keys():
    from smg_tpu.ops.attention import attention_decode_cached
    from smg_tpu.ops.pallas.decode_attention import paged_attention_decode_cached

    rng = np.random.default_rng(8)
    B, H, G, Dk, Dv, P, mp, Nc = 3, 16, 4, 64, 32, 13, 4, 4
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    q, kc, vc = f(B, H, Dk), f(2, P, PS, G * Dk), f(2, P, PS, G * Dv)
    hk, hv = f(B, Nc, G * Dk), f(B, Nc, G * Dv)
    tables = jnp.asarray(rng.permutation(np.arange(1, P))[: B * mp].reshape(B, mp))
    entry = jnp.asarray([37, 60, 5])
    args = (q, kc, vc, hk, hv, jnp.int32(2), jnp.int32(1), tables, entry, 0.125)
    want = attention_decode_cached(*args)
    got = paged_attention_decode_cached(*args, interpret=True)
    assert got.shape == (B, H, Dv)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # against the einsum by hand, for one lane
    pages = np.asarray(tables[0])
    k = np.concatenate([np.asarray(kc[1, pages]).reshape(-1, G, Dk)[:37],
                        np.asarray(hk[0, :2]).reshape(2, G, Dk)])
    v = np.concatenate([np.asarray(vc[1, pages]).reshape(-1, G, Dv)[:37],
                        np.asarray(hv[0, :2]).reshape(2, G, Dv)])
    hand = by_hand(np.asarray(q[0])[None], k, v, np.asarray([38]), np.arange(39), 0.125)[0]
    np.testing.assert_allclose(want[0], hand, atol=2e-5)


def test_ring_positions_are_derived_from_the_length():
    got = np.asarray(wa.ring_positions(jnp.asarray([0, 3, 8, 13]), 8))
    assert got[0].tolist() == [-8, -7, -6, -5, -4, -3, -2, -1]  # nothing held
    assert got[1].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert got[2].tolist() == [0, 1, 2, 3, 4, 5, 6, 7]
    assert got[3].tolist() == [8, 9, 10, 11, 12, 5, 6, 7]
    assert wa.ring_tokens(128, 16) == 144 and wa.ring_tokens(8, 16) == 32


def test_a_long_chunk_leaves_its_last_entries_in_the_ring():
    R, T = 16, 40
    rk, rv = jnp.zeros((2, 3, R, 4)), jnp.zeros((2, 3, R, 2))
    k = jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32)[None, :, None], (2, T, 4)) + 1
    rk, rv = wa.write_ring_chunk(rk, rv, 1, k, k[..., :2], jnp.asarray([2, 0]),
                                 jnp.asarray([5, 0]), jnp.asarray([37, 40]))
    held = np.asarray(rk[1, 2, :, 0])  # chunk rows 21..36 at positions 26..41
    assert sorted(held.tolist()) == list(range(22, 38))
    assert held[26 % R] == 22 and held[41 % R] == 37
    assert not np.any(np.asarray(rk[0])) and not np.any(np.asarray(rk[1, 1]))  # other layer, other slot


def test_partial_rotary_turns_the_first_lanes_with_each_kinds_base():
    cfg = tiny_mimo_config()
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((1, 6, 4, cfg.head_dim)).astype(np.float32))
    pos = jnp.asarray([[0, 1, 2, 50, 51, 52]])
    n = cfg.qk_rope_head_dim
    for theta in (cfg.rope_theta, cfg.swa_rope_theta):
        got = np.asarray(M._rotary(x, pos, jnp.asarray(rope_frequencies(n, theta))))
        np.testing.assert_array_equal(got[..., n:], np.asarray(x)[..., n:])  # the other lanes pass
        np.testing.assert_allclose(got[0, 0], np.asarray(x)[0, 0], atol=1e-6)  # position 0
        want = np.asarray(ARCH._rope(x[0], pos[0], theta, n))
        np.testing.assert_allclose(got[0], want, atol=1e-5)
    a = M._rotary(x, pos, jnp.asarray(rope_frequencies(n, cfg.rope_theta)))
    b = M._rotary(x, pos, jnp.asarray(rope_frequencies(n, cfg.swa_rope_theta)))
    assert float(jnp.max(jnp.abs(a - b))) > 0.1  # two bases, two rotations


# --------------------------------------------------------------------------
# routed experts


def test_the_router_picks_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((12, 32)).astype(np.float32))
    router = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    bias = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    kw = dict(top_k=4, scoring="sigmoid", norm_topk=True, scale=1.0)
    plain = moe.route(x, router, **kw)
    biased = moe.route(x, router, **kw, select_bias=bias)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(router))))
    assert np.asarray(plain.experts).tolist() == np.argsort(-s, axis=-1)[:, :4].tolist()
    picked = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :4]
    assert np.asarray(biased.experts).tolist() == picked.tolist()
    assert picked.tolist() != np.asarray(plain.experts).tolist()
    top = np.take_along_axis(s, picked, axis=-1)
    np.testing.assert_allclose(biased.weights, top / top.sum(-1, keepdims=True), rtol=1e-5)
    # a bias of zeros picks and weighs as no bias does
    zero = moe.route(x, router, **kw, select_bias=jnp.zeros(16))
    np.testing.assert_array_equal(zero.experts, plain.experts)
    np.testing.assert_allclose(zero.weights, plain.weights, rtol=1e-6)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: four chips hold four experts
    each; what they give for the same tokens adds up to what a chip that
    holds all sixteen gives, in the program and in the reference alike."""
    whole = tiny_mimo_config()
    params = M.init_params(whole, jax.random.PRNGKey(1))
    layer = jax.tree.map(lambda x: x[1], params["window_moe"])
    experts = {k: params["window_moe"][k] for k in M._ROUTED}
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.standard_normal((9, whole.hidden_size)).astype(np.float32))
    live = jnp.ones(9, bool)

    def routed(cfg, ex):
        out, counts = M._moe_residual(h, layer, ex, 1, cfg, live, "xla")
        return np.asarray(out - h), np.asarray(counts)

    full, counts = routed(whole, experts)
    assert counts[0] == counts[1] == 9 * 4
    parts, held = [], 0
    for first in range(0, 16, 4):
        cut = dataclasses.replace(whole, experts_held=(first, 4))
        share, c = routed(cut, {k: v[:, first:first + 4] for k, v in experts.items()})
        parts.append(share)
        held += int(c[1])
        w = ARCH._Weights({**params["window_moe"],
                           **{k: v[:, first:first + 4] for k, v in experts.items()}}, 1, True)
        x = ARCH._rms(h, w("mlp_norm"), whole.rms_norm_eps)
        with jax.default_matmul_precision("highest"):
            ref = ARCH._routed(x, w, shape={**ARCH._shape(hf_of(whole)), "first": first})
        np.testing.assert_allclose(share, ref, atol=1e-5)
    assert held == 9 * 4
    np.testing.assert_allclose(sum(parts), full, atol=1e-5)


# --------------------------------------------------------------------------
# the loader and the plan


def published() -> dict:
    cell = catalog.Cell(catalog.load_benchmark(), "mimo-v2-flash.mixed")
    return cell.hf_config


def test_from_hf_config_reads_the_published_keys_and_picks_the_module():
    cfg = ModelConfig.from_hf_config(published())
    assert cfg.arch == "mimo_v2_flash" and cfg.window_cache
    assert not cfg.recurrent and not cfg.latent_cache
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.swa_num_kv_heads) \
        == (4096, 64, 4, 8)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rope_dim, cfg.sliding_window) == (192, 128, 64, 128)
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.attention_value_scale) \
        == (5e6, 1e4, 0.707)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (16384, 2048)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.held_experts) == (256, 8, (0, 16))
    assert cfg.moe_select_bias and cfg.swa_sink_bias and cfg.routed_scaling_factor == 1.0
    assert cfg.num_cache_layers == 2 and cfg.num_window_layers == 5
    assert cfg.kv_lanes(False) == (768, 512) and cfg.kv_lanes(True) == (1536, 1024)
    assert [r[0] for r in M.layer_runs(cfg)] == ["full_dense", "window_moe", "full_moe"]
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    count = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert abs(count - 3.43e9) < 0.01e9  # the issue's arithmetic


def test_the_random_routed_experts_are_drawn_as_loud_as_the_config_says():
    """The program draws the routed experts' output projections at one scale;
    a configuration (a benchmark's, for its comparison) may say otherwise,
    and nothing else moves with it."""
    hf = {k: v for k, v in published().items() if k != "random_routed_out_gain"}
    assert ModelConfig.from_hf_config(hf).random_routed_out_gain == 1.0
    assert ModelConfig.from_hf_config(
        {**hf, "random_routed_out_gain": 0.5}).random_routed_out_gain == 0.5
    cfg = tiny_mimo_config()
    whole = M.init_params(cfg, jax.random.PRNGKey(1))
    half = M.init_params(dataclasses.replace(cfg, random_routed_out_gain=0.5),
                         jax.random.PRNGKey(1))
    for kind, stack in whole.items():
        for name, w in (stack.items() if isinstance(stack, dict) else [("", stack)]):
            other = half[kind][name] if name else half[kind]
            scale = 0.5 if name == "w_down" and "router" in stack else 1.0
            np.testing.assert_allclose(np.asarray(other), scale * np.asarray(w), rtol=1e-6)


def test_the_published_pattern_is_runs_of_its_stacks():
    """48 layers: full, 4 window, full, then 5 window + 1 full seven times."""
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    cfg = ModelConfig.from_hf_config({**published(), "num_hidden_layers": 48,
                                      "hybrid_layer_pattern": pattern,
                                      "moe_layer_freq": [0] + [1] * 47})
    runs = M.layer_runs(cfg)
    assert runs[:3] == [("full_dense", 0, 1, 0), ("window_moe", 0, 4, 0), ("full_moe", 0, 1, 1)]
    assert runs[3:5] == [("window_moe", 4, 5, 4), ("full_moe", 1, 1, 2)]
    assert sum(n for _k, _a, n, _c in runs) == 48 and cfg.num_cache_layers == 9


@pytest.mark.parametrize("change, needle", [
    ({"mtp_num_layers": 3}, "does not consume"),
    ({"sliding_window_size": 256}, "differs from sliding_window"),
    ({"attention_chunk_size": 64}, "differs from sliding_window"),
    ({"swa_head_dim": 128}, "differs from head_dim"),
    ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_group": 8}, "n_group"),
    ({"routed_scaling_factor": 2.5}, "routed_scaling_factor"),
    ({"hybrid_layer_pattern": [0, 1, 1]}, "for each of 7 layers"),
    ({"hybrid_layer_pattern": [0] * 7}, "no sliding-window layer"),
    ({"routed_expert_offset": 250}, "not among"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**published(), **change})


def test_the_llama_loader_refuses_this_file():
    """What the parent commit does with the configuration: an error at start."""
    with pytest.raises(ValueError, match="does not consume"):
        ModelConfig.from_hf_config({**published(), "model_type": "llama"})


def test_the_cache_plan_takes_the_slots_first_and_the_weights_off_once():
    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import plan_window_cache

    cfg = ModelConfig.from_hf_config(published())
    cache = CacheConfig(page_size=16, auto_size=True, hbm_utilization=0.9)
    GB = 10**9
    spec, window = plan_window_cache(cfg, cache, 72, 16, hbm_limit=16 * GB, hbm_in_use=7 * GB,
                                     workspace=1 * GB)
    assert (window.num_layers, window.window, window.ring_tokens) == (5, 128, 144)
    assert (window.k_lanes, window.v_lanes, window.num_slots) == (1536, 1024, 73)
    assert window.slot_bytes == 5 * 144 * 5120  # what a sequence holds: no context in it
    assert (spec.num_layers, spec.lanes, spec.v_shape[3]) == (2, 768, 512)
    assert spec.bytes_per_page == 16 * 5120
    budget = int(16 * GB * 0.9) - 7 * GB - 1 * GB - 73 * window.slot_bytes
    assert spec.num_pages == budget // spec.bytes_per_page
    # with no device to read, the configured number of pages
    spec2, window2 = plan_window_cache(cfg, CacheConfig(page_size=16, num_pages=99,
                                                        auto_size=False), 72, 16)
    assert spec2.num_pages == 99 and window2.slot_bytes == window.slot_bytes


def test_workspace_is_on_the_high_side_of_a_prefill():
    cfg = ModelConfig.from_hf_config(published())
    got = M.prefill_workspace_bytes(cfg, 4096, "bfloat16")
    assert 1.0e9 < got < 2e9  # 0.57 GB compiled for a v5e
