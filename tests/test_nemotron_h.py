"""``models/nemotron_h.py`` (Mamba-2 state-space layers in the state slots,
routed experts that work in a latent with ungated squared-ReLU MLPs, unrotated
attention; one mixer or one feed-forward part a layer in any order) on the CPU
in float32, held to the plain reference ``benchmark/architectures/nemotron_h.py``:
the serving forwards through slots and pages, the chunked scan against the
position-by-position recurrence, the decode kernel against its XLA form, the
share test of the model-configs guide, the expert form, the router, the loader,
and the engine through the scheduler."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import get_model
from smg_tpu.models import nemotron_h as M
from smg_tpu.models.config import ModelConfig, tiny_nemotron_h_config
from smg_tpu.ops import moe, ssm
from smg_tpu.ops.linear_attention import conv_token
from smg_tpu.ops.pallas import ssm_decode as kernel

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark"))
import catalog  # noqa: E402

ARCH = catalog.architecture("nemotron_h")
PS, PAGES, MP, SLOTS = 16, 40, 16, 4
#: float32 against float32: the served path's own error is rounding; what a
#: fault must pass is a hundred times that
SOUND, BROKEN = 1e-4, 1e-2
LETTER = {v: k for k, v in ModelConfig.NEMOTRON_H_LETTERS.items()}


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration."""
    return {"model_type": "nemotron_h", "hidden_size": cfg.hidden_size,
            "vocab_size": cfg.vocab_size, "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "hybrid_override_pattern": "".join(LETTER[t] for t in cfg.layer_types),
            "mamba_num_heads": cfg.ssm_num_heads, "mamba_head_dim": cfg.ssm_head_dim,
            "ssm_state_size": cfg.ssm_state_size, "n_groups": cfg.ssm_groups,
            "conv_kernel": cfg.ssm_conv_kernel, "chunk_size": cfg.ssm_chunk_size,
            "moe_latent_size": cfg.moe_latent_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "moe_shared_expert_intermediate_size": cfg.moe_shared_intermediate_size,
            "n_routed_experts": cfg.held_experts[1], "router_num_experts": cfg.num_experts,
            "routed_expert_offset": cfg.held_experts[0],
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob, "norm_eps": cfg.rms_norm_eps}


def err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


class World:
    """A configuration, its random weights and the serving forwards over a
    fresh cache and fresh pools."""

    def __init__(self, cfg, key=0):
        self.cfg = cfg
        self.params = M.init_params(cfg, jax.random.PRNGKey(key))
        self.table = jnp.arange(1, MP + 1, dtype=jnp.int32)

    def empty(self):
        kc = jnp.zeros((self.cfg.num_cache_layers, PAGES, PS,
                        self.cfg.num_kv_heads * self.cfg.head_dim), jnp.float32)
        s_shape, c_shape = M.state_shapes(self.cfg, SLOTS)
        return kc, kc, jnp.zeros(s_shape, jnp.float32), jnp.zeros(c_shape, jnp.float32)

    def reference(self, toks, rows, cfg=None, params=None):
        return ARCH.logits(params or self.params, hf_of(cfg or self.cfg), toks, rows)

    def prefill(self, impl, chunk, lo, state, slot, T=64):
        padded = np.zeros(T, np.int32)
        padded[: len(chunk)] = chunk
        fn = jax.jit(lambda *a: M.forward_prefill(self.params, self.cfg, None, *a,
                                                  attn_impl=impl, moe_impl=impl))
        return fn(jnp.asarray(padded), jnp.int32(lo), jnp.int32(len(chunk)), *state[:2],
                  self.table, *state[2:], jnp.int32(slot))

    def decode(self, impl, state, tokens, entry, slots, columns):
        """``columns`` decode columns of one frame; the logits of each."""
        B, cfg = len(slots), self.cfg
        kc, vc, sp, cp = state
        hk = jnp.zeros((cfg.num_cache_layers, B, columns, kc.shape[-1]), jnp.float32)
        hv = jnp.zeros_like(hk)
        tables = jnp.stack([self.table if s else jnp.zeros_like(self.table) for s in slots])
        fn = jax.jit(lambda *a: M.forward_decode_horizon(
            self.params, cfg, None, *a, attn_impl=impl, ssm_impl=impl, moe_impl=impl))
        slots, entry = jnp.asarray(slots, jnp.int32), jnp.asarray(entry, jnp.int32)
        out = []
        for j in range(columns):
            logits, hk, hv, sp, cp, counts = fn(
                jnp.asarray(tokens[j], jnp.int32), entry + j, entry, jnp.int32(j), kc, vc,
                tables, hk, hv, sp, cp, slots, slots > 0)
            out.append((logits, counts))
        return out, (kc, vc, sp, cp)


@pytest.fixture(scope="module")
def world():
    w = World(tiny_nemotron_h_config())
    rng = np.random.default_rng(0)
    w.n, w.n_dec = 100, 4
    w.toks = rng.integers(2, w.cfg.vocab_size, size=w.n + w.n_dec).astype(np.int32)
    w.ref = w.reference(w.toks, list(range(w.n + w.n_dec)))
    return w


# --------------------------------------------------------------------------
# the forwards against the reference


@pytest.mark.parametrize("pattern", ["MEM*EME", "*ME", "EEMM*", "M", "ME*M*E"])
def test_the_dense_forward_is_the_reference_for_any_pattern(pattern):
    w = World(tiny_nemotron_h_config(pattern=pattern), key=3)
    toks = np.random.default_rng(1).integers(2, 512, size=37).astype(np.int32)
    got = M.forward_train(w.params, w.cfg, None, jnp.asarray(toks)[None])[0]
    assert err(got, w.reference(toks, list(range(37)))) < SOUND


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("cut", [37, 64])
def test_two_chunks_then_decode_through_slots_and_pages_is_one_full_forward(world, impl, cut):
    """The first chunk ends off a multiple of the scan's chunk (8) and of a
    page at 37, on both at 64; the second starts from the slot's state, the
    convolution's tail and the pages' prefix."""
    w = world
    state = w.empty()
    _, *state = w.prefill(impl, w.toks[:cut], 0, state, 2)
    logits, *state = w.prefill(impl, w.toks[cut:w.n], cut, state, 2)
    assert err(logits, w.ref[w.n - 1]) < SOUND
    tokens = [[w.toks[w.n + j], 0] for j in range(w.n_dec)]
    out, _ = w.decode(impl, state, tokens, [w.n, MP * PS], [2, 0], w.n_dec)
    for j, (logits, _counts) in enumerate(out):
        assert err(logits[0], w.ref[w.n + j]) < SOUND


def test_a_grouped_prefill_is_its_rows_solo_and_a_padded_row_writes_the_garbage_slot(world):
    w = world
    rows = [w.toks[:50], w.toks[20:43]]
    tokens = np.zeros((4, 64), np.int32)
    for i, r in enumerate(rows):
        tokens[i, : len(r)] = r
    kc, vc, sp, cp = w.empty()
    tables = jnp.stack([w.table, w.table + MP, jnp.zeros_like(w.table), jnp.zeros_like(w.table)])
    logits, kc, vc, sp2, cp2 = M.forward_prefill_batched(
        w.params, w.cfg, None, jnp.asarray(tokens), jnp.zeros(4, jnp.int32),
        jnp.asarray([50, 23, 0, 0], jnp.int32), kc, vc, tables, sp, cp,
        jnp.asarray([1, 3, 0, 0], jnp.int32), no_ctx=True)
    assert err(logits[0], w.ref[49]) < SOUND
    assert err(logits[1], w.reference(rows[1], [22])[0]) < SOUND
    assert float(jnp.abs(sp2[:, 2]).max()) == 0.0  # the slot no row names
    assert float(jnp.abs(sp2[:, 1]).max()) > 0.0 and float(jnp.abs(sp2[:, 3]).max()) > 0.0


def test_a_grouped_prefill_moves_its_rows_states_alone_and_they_are_the_decode_walks(world):
    """However a prefill program moves a row between the pool and the scan
    (``_prefill``'s barriers): of four rows, one cold, one behind a prefix that
    a solo chunk left in its slot, two padded on the garbage slot, the two
    named slots come to hold the state and the tail that decode leaves when it
    walks each whole sequence a token at a time from nothing, and a slot no row
    names keeps another sequence's state bit for bit, in both pools."""
    w = world
    seqs, cut = [w.toks[:40], w.toks[10:60]], 27
    rng = np.random.default_rng(5)
    kc, vc, sp, cp = w.empty()
    sp = sp.at[:, 2].set(rng.normal(size=sp.shape[2:]).astype(np.float32))
    cp = cp.at[:, 2].set(rng.normal(size=cp.shape[2:]).astype(np.float32))
    _, kc, vc, sp, cp = w.prefill("xla", seqs[1][:cut], 0, (kc, vc, sp, cp), 3)
    tokens = np.zeros((4, 64), np.int32)
    tokens[0, :40], tokens[1, : 50 - cut] = seqs[0], seqs[1][cut:]
    empty = jnp.zeros_like(w.table)
    grouped = jax.jit(lambda *a: M.forward_prefill_batched(w.params, w.cfg, None, *a))
    _, _, _, sp2, cp2 = grouped(
        jnp.asarray(tokens), jnp.asarray([0, cut, 0, 0], jnp.int32),
        jnp.asarray([40, 50 - cut, 0, 0], jnp.int32), kc, vc,
        jnp.stack([w.table + MP, w.table, empty, empty]), sp, cp,
        jnp.asarray([1, 3, 0, 0], jnp.int32))
    assert np.array_equal(sp2[:, 2], sp[:, 2]) and np.array_equal(cp2[:, 2], cp[:, 2])
    for slot, seq in zip((1, 3), seqs):
        _, (_, _, want_s, want_c) = w.decode("xla", w.empty(), [[t] for t in seq], [0], [slot],
                                             len(seq))
        for got, want in ((sp2, want_s), (cp2, want_c)):
            assert np.abs(got[:, slot] - want[:, slot]).max() < SOUND * np.abs(want[:, slot]).max()


def test_a_lane_on_the_garbage_slot_does_not_run_and_picks_no_expert(world):
    """Two lanes of four hold a sequence: the others leave every slot bit for
    bit and add no pick; a column that no lane runs (a discarded lookahead's
    would be) changes nothing at all."""
    w = world
    state = w.empty()
    _, *state = w.prefill("xla", w.toks[:40], 0, state, 1)
    _, *state = w.prefill("xla", w.toks[:40], 0, state, 3)
    out, after = w.decode("xla", state, [[5, 6, 7, 8]], [40, MP * PS, 40, MP * PS],
                          [1, 0, 3, 0], 1)
    _, counts = out[0]
    k, layers = w.cfg.num_experts_per_tok, M.count(w.cfg, "moe")
    assert int(counts[0]) == 2 * k * layers  # the two live lanes' picks alone
    assert np.array_equal(after[2][:, [0, 2]], state[2][:, [0, 2]])
    assert not np.array_equal(after[2][:, 1], state[2][:, 1])
    _, idle = w.decode("xla", state, [[5, 6, 7, 8]], [MP * PS] * 4, [0, 0, 0, 0], 1)
    assert np.array_equal(idle[2], state[2]) and np.array_equal(idle[3], state[3])


# --------------------------------------------------------------------------
# the recurrence: chunks, the step, the kernel


def recurrence(x, dt, g, B, C, S0):
    """Position by position, in numpy float64."""
    G, T, H, P = x.shape
    M_ = H // B.shape[2]
    S = np.asarray(S0, np.float64).copy()  # [G, H, N, P]
    y = np.zeros((G, T, H, P))
    for t in range(T):
        for h in range(H):
            r = h // M_
            S[:, h] = (np.exp(g[:, t, h])[:, None, None] * S[:, h]
                       + B[:, t, r][:, :, None] * (dt[:, t, h, None] * x[:, t, h])[:, None, :])
            y[:, t, h] = np.einsum("gnp,gn->gp", S[:, h], C[:, t, r])
    return y, S


def drawn(G, T, H=4, P=8, R=2, N=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    dt = np.abs(f(G, T, H)) * 0.5
    return f(G, T, H, P), dt, -dt * np.abs(f(H)), f(G, T, R, N), f(G, T, R, N), f(G, H, N, P)


@pytest.mark.parametrize("T,chunk", [(37, 8), (64, 16), (5, 8), (128, 128), (130, 128)])
def test_the_chunked_scan_is_the_recurrence_from_a_carried_state(T, chunk):
    x, dt, g, B, C, S0 = drawn(2, T)
    y, S = ssm.ssd_chunked(*map(jnp.asarray, (x, dt, g, B, C, S0)), chunk=chunk)
    want_y, want_S = recurrence(x, dt, g, B, C, S0)
    assert np.abs(y - want_y).max() < 1e-4 * np.abs(want_y).max()
    assert np.abs(S - want_S).max() < 1e-4 * np.abs(want_S).max()


def test_padded_positions_of_a_chunk_write_nothing_and_decay_nothing():
    x, dt, g, B, C, S0 = drawn(1, 24)
    real = np.arange(24) < 13
    y, S = ssm.ssd_chunked(jnp.asarray(x), jnp.asarray(dt * real[None, :, None]),
                           jnp.asarray(g * real[None, :, None]), jnp.asarray(B),
                           jnp.asarray(C), jnp.asarray(S0), chunk=8)
    _, want = recurrence(x[:, :13], dt[:, :13], g[:, :13], B[:, :13], C[:, :13], S0)
    assert np.abs(S - want).max() < 1e-4 * np.abs(want).max()


def test_the_convolution_takes_its_bias_and_keeps_the_last_real_inputs():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 10, 6)).astype(np.float32)
    tail = rng.normal(size=(2, 3, 6)).astype(np.float32)
    w, b = rng.normal(size=(4, 6)).astype(np.float32), rng.normal(size=(6,)).astype(np.float32)
    y, new = ssm.causal_conv(jnp.asarray(x), jnp.asarray(tail), jnp.asarray(w),
                             jnp.asarray([10, 4]), jnp.asarray(b))
    full = np.concatenate([tail, x], axis=1)
    pre = sum(full[:, i:i + 10] * w[i] for i in range(4)) + b
    assert np.allclose(y, pre / (1 + np.exp(-pre)), atol=1e-5)
    assert np.array_equal(new[0], x[0, 7:10]) and np.array_equal(new[1], full[1, 4:7])
    y1, step = conv_token(jnp.asarray(x[:, 0]), jnp.asarray(tail), jnp.asarray(w), jnp.asarray(b))
    assert np.allclose(y1, y[:, 0], atol=1e-5) and np.array_equal(step[:, -1], x[:, 0])


@pytest.mark.parametrize("shape", [(4, 8, 2, 16), (8, 16, 8, 16), (2, 64, 1, 128)])
def test_the_decode_kernel_interpreted_is_its_xla_form_and_touches_its_lanes_slots_alone(shape):
    H, P, R, N = shape
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    pool = f(2, 6, N, H * P)
    x, B, C = f(3, H, P), f(3, R, N), f(3, R, N)
    dt = jnp.abs(f(3, H)).at[2].set(0.0)  # the third lane does not run
    decay = jnp.exp(-dt)
    slots = jnp.asarray([4, 1, 0], jnp.int32)
    y1, p1 = ssm.ssd_step(pool, 1, slots, x, dt, decay, B, C)
    y2, p2 = kernel.ssm_decode(pool, 1, slots, x, dt, decay, B, C, interpret=True)
    assert np.allclose(y1, y2, atol=1e-4) and np.allclose(p1, p2, atol=1e-5)
    assert np.array_equal(p2[0], pool[0])  # the other layer
    assert np.array_equal(p2[1, [0, 2, 3, 5]], pool[1, [0, 2, 3, 5]])  # garbage and strangers
    assert not np.array_equal(p2[1, 4], pool[1, 4])


def test_the_kernel_fits_the_published_shape_and_says_where_it_does_not():
    assert kernel.supported(128, 64, 128, 8)  # a group's block: 128 x 1,024 float32
    assert not kernel.supported(4, 16, 16, 2)  # toy widths: the XLA form serves
    assert not kernel.supported(128, 64, 128, 2)  # a block of 2 MiB
    assert M.decode_step(tiny_nemotron_h_config()) == {
        "name": "ssm_decode", "arg": "ssm_impl", "layers": "state-space", "kernel_fits": False}
    with pytest.raises(ValueError, match="use the XLA form"):
        kernel.ssm_decode(jnp.zeros((1, 2, 16, 64)), 0, jnp.zeros(1, jnp.int32),
                          jnp.zeros((1, 4, 16)), jnp.zeros((1, 4)), jnp.zeros((1, 4)),
                          jnp.zeros((1, 2, 16)), jnp.zeros((1, 2, 16)))


# --------------------------------------------------------------------------
# the expert layer


def moe_out(w: World, cfg, h, params=None):
    """What one expert layer (the first) adds to ``h`` under ``cfg``."""
    p = params or w.params
    layer = jax.tree.map(lambda x: x[0], p["moe"])
    out, counts = M.moe_layer(h, layer, p["experts"], 0, cfg, jnp.ones(h.shape[:-1], bool), "xla")
    return out - h, counts


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts of four chips' shares, each taken through ``W_ul``, plus
    the shared expert counted once, are the uncut layer; the reference given
    one share gives that share."""
    full = tiny_nemotron_h_config(pattern="E")
    w = World(full, key=7)
    h = jax.random.normal(jax.random.PRNGKey(1), (24, full.hidden_size)) * 0.02
    whole, counts = moe_out(w, full, h)
    layer = jax.tree.map(lambda x: x[0], w.params["moe"])
    shared = M.shared_expert(layer, M._norm(h, layer["norm"], full))
    routed = 0.0
    picks_held = 0
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(full, experts_held=(first, 4))
        held = {k: v[:, first:first + 4] for k, v in w.params["experts"].items()}
        share, c = moe_out(w, cfg, h, {**w.params, "experts": held})
        routed = routed + (share - shared)
        picks_held += int(c[1])
        if first == 4:
            toks = np.arange(2, 26, dtype=np.int32)
            ref = ARCH.logits({**w.params, "experts": held}, hf_of(cfg), toks, [23])
            got = M.forward_train({**w.params, "experts": held}, cfg, None,
                                  jnp.asarray(toks)[None])[0, 23]
            assert err(got, ref[0]) < SOUND
    assert float(jnp.abs(routed + shared - whole).max()) < 1e-5 * float(jnp.abs(whole).max()) + 1e-7
    assert picks_held == int(counts[0]) == 24 * full.num_experts_per_tok
    assert float(jnp.abs(routed).max()) > 10 * float(jnp.abs(shared).max()) * 0.01  # both speak


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("tokens,top_k", [(1, 22), (3, 22), (16, 4), (100, 22)])
def test_ungated_squared_relu_experts_through_the_grouped_products_are_a_dense_loop(
        impl, tokens, top_k):
    """In a width that is not the model's, at a top-k no tile of eight rows
    divides, and past the rows one pass computes."""
    rng = np.random.default_rng(3)
    Z, F, X, first, held = 128, 256, 32, 8, 8
    x = jnp.asarray(rng.normal(size=(tokens, Z)).astype(np.float32))
    w_up = jnp.asarray(rng.normal(size=(held, Z, F)).astype(np.float32) * Z ** -0.5)
    w_down = jnp.asarray(rng.normal(size=(held, F, Z)).astype(np.float32) * F ** -0.5)
    experts = np.stack([rng.permutation(X)[:top_k] for _ in range(tokens)]).astype(np.int32)
    weights = rng.uniform(0.1, 1.0, size=(tokens, top_k)).astype(np.float32)
    got, (rows, hit) = moe.expert_layer(
        x, moe.Routing(jnp.asarray(experts), jnp.asarray(weights)), None, w_up, w_down,
        (first, held), impl)
    want = np.zeros((tokens, Z), np.float32)
    for e in range(held):
        on = np.sum(np.where(experts == first + e, weights, 0.0), axis=-1, keepdims=True)
        want += on * (np.square(np.maximum(np.asarray(x @ w_up[e]), 0.0)) @ np.asarray(w_down[e]))
    assert np.allclose(got, want, atol=2e-4 * np.abs(want).max() + 1e-6)
    mine = (experts >= first) & (experts < first + held)
    assert int(rows) == mine.sum() and int(hit) == len(set(experts[mine].tolist()))


@pytest.mark.parametrize("pairs,rows", [(22, 32), (44, 48), (8, 8), (1408, 1408), (12, 16),
                                        (2048, 2048), (45056, 5632)])
def test_the_rows_buffer_is_whole_tiles_of_rows(pairs, rows):
    assert moe.rows_buffer(pairs) == rows


def test_the_selection_bias_picks_and_does_not_weigh():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(5, 16)).astype(np.float32))
    router = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
    bias = jnp.zeros(12).at[3].set(10.0)  # output 3 is always picked
    plain = moe.route(x, router, top_k=4, scoring="sigmoid", norm_topk=True, scale=5.0)
    biased = moe.route(x, router, top_k=4, scoring="sigmoid", norm_topk=True, scale=5.0,
                       select_bias=bias)
    assert np.all(np.any(np.asarray(biased.experts) == 3, axis=1))
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, biased.experts, axis=-1)
    want = 5.0 * picked / jnp.sum(picked, axis=-1, keepdims=True)
    assert np.allclose(biased.weights, want, atol=1e-6)  # the scores alone, renormalised
    assert np.allclose(jnp.sum(plain.weights, -1), 5.0) and np.allclose(jnp.sum(want, -1), 5.0)


@pytest.mark.parametrize("fault", ["gated", "relu_not_squared", "experts_read_the_model_width",
                                   "bias_weighs", "not_renormalised", "norm_before_gate",
                                   "rotary_attention", "state_never_decays"])
def test_a_fault_in_a_layer_misses_the_tolerance(fault, monkeypatch):
    """Each way a reader of the row could get a layer wrong moves the logits
    by more than a hundred roundings."""
    cfg = tiny_nemotron_h_config()
    w = World(cfg, key=5)
    toks = np.random.default_rng(6).integers(2, 512, size=40).astype(np.int32)
    ref = w.reference(toks, [39])[0]
    assert err(M.forward_train(w.params, cfg, None, jnp.asarray(toks)[None])[0, 39], ref) < SOUND
    params = w.params
    if fault == "gated":
        monkeypatch.setattr(moe, "_experts", lambda rows, g, up, down, sizes, impl, layer: (
            moe.grouped_matmul(jax.nn.silu(moe.grouped_matmul(rows, up, sizes, impl, layer))
                               * moe.grouped_matmul(rows, up, sizes, impl, layer),
                               down, sizes, impl, layer)))
    elif fault == "relu_not_squared":
        monkeypatch.setattr(M, "_relu2", jax.nn.relu)
    elif fault == "experts_read_the_model_width":
        z = cfg.moe_latent_size  # the latent is a slice of the input, not W_dl's
        eye = jnp.zeros_like(params["moe"]["w_dl"]).at[:, :z, :].set(jnp.eye(z))
        params = {**params, "moe": {**params["moe"], "w_dl": eye}}
    elif fault == "bias_weighs":
        real = moe.route
        monkeypatch.setattr(moe, "route", lambda x, r, *, select_bias, **kw: real(
            x, r, **kw)._replace(experts=real(x, r, select_bias=select_bias, **kw).experts))
    elif fault == "not_renormalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif fault == "norm_before_gate":
        monkeypatch.setattr(jax.nn, "silu", lambda x: jnp.ones_like(x), raising=True)
    elif fault == "rotary_attention":
        wq = params["attn"]["wq"]
        params = {**params, "attn": {**params["attn"], "wq": jnp.roll(wq, 1, axis=1)}}
    elif fault == "state_never_decays":
        params = {**params, "mamba": {**params["mamba"],
                                      "A_log": jnp.full_like(params["mamba"]["A_log"], -30.0)}}
    got = M.forward_train(params, cfg, None, jnp.asarray(toks)[None])[0, 39]
    assert err(got, ref) > BROKEN


def test_the_routers_read_lanes_that_only_the_embedding_writes():
    """Through every layer those lanes are the token's signs times one
    magnitude, so a rounded stream picks the experts the float32 one does."""
    cfg = tiny_nemotron_h_config()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    E, R = cfg.hidden_size, M.route_lanes(cfg.hidden_size)
    toks = jnp.asarray(np.random.default_rng(8).integers(2, 512, size=20))
    for name, w in (("mamba", "w_out"), ("attn", "wo"), ("moe", "w_ul"), ("moe", "ws_down")):
        assert float(jnp.abs(params[name][w][..., E - R:]).max()) == 0.0
    assert float(jnp.abs(params["moe"]["router"][:, : E - R]).max()) == 0.0
    lanes = np.abs(np.asarray(params["embed"][toks][:, E - R:]))
    assert np.all(lanes == lanes[:, :1])
    seen = []
    real = moe.route

    def spy(x, router, **kw):
        seen.append(x)
        return real(x, router, **kw)

    moe.route, keep = spy, moe.route
    try:
        M.forward_train(params, cfg, None, toks[None])
    finally:
        moe.route = keep
    assert len(seen) == M.count(cfg, "moe")
    for x in seen:
        lanes = np.abs(np.asarray(x[:, E - R:]))
        assert np.allclose(lanes, lanes[:, :1], rtol=1e-5)
    sizes = M._stream_sizes(cfg)
    assert sizes == sorted(sizes) and len(sizes) == M.count(cfg, "moe")


def plainly_routed(cfg, key):
    """``init_params`` with the routers drawn as a checkpoint's are in kind:
    normal weights over the whole normed stream, a selection bias that moves
    picks, so that a token's picks follow its context."""
    params = M.init_params(cfg, jax.random.PRNGKey(key))
    ka, kb = jax.random.split(jax.random.PRNGKey(key + 100))
    router = jax.random.normal(ka, params["moe"]["router"].shape, jnp.float32) * 0.5
    bias = jax.random.normal(kb, params["moe"]["select_bias"].shape, jnp.float32) * 0.05
    return {**params, "moe": {**params["moe"], "router": router, "select_bias": bias}}


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_routers_that_read_the_whole_stream_are_the_reference_too(impl):
    """The random drawing routes on lanes only the embedding writes, so a
    token's picks do not follow its context and the comparison of logits never
    sees the router's input path at work.  Here the routers read the whole
    stream: the picks of one token differ between two contexts, and the dense
    forward, two chunks and a decode frame through slots and pages are the
    reference's rows all the same."""
    cfg = tiny_nemotron_h_config(held=(4, 8))
    w = World(cfg)
    w.params = plainly_routed(cfg, 5)
    rng = np.random.default_rng(3)
    n, n_dec, cut = 60, 3, 29
    toks = rng.integers(2, cfg.vocab_size, size=n + n_dec).astype(np.int32)
    other = toks.copy()
    other[: n - 1] = rng.integers(2, cfg.vocab_size, size=n - 1)  # another context, the same last tokens
    seen = []
    real = moe.route

    def spy(x, router, **kw):
        r = real(x, router, **kw)
        seen.append(np.asarray(r.experts))
        return r

    moe.route, keep = spy, moe.route
    try:
        got = M.forward_train(w.params, cfg, None, jnp.asarray(np.stack([toks, other])))
    finally:
        moe.route = keep
    layers = M.count(cfg, "moe")
    assert len(seen) == layers
    picks = [e.reshape(2, n + n_dec, -1)[:, n - 1] for e in seen]
    assert any(set(a.tolist()) != set(b.tolist()) for a, b in picks[1:]), (
        "the same token behind two contexts picked the same experts in every layer")
    ref = w.reference(toks, list(range(n + n_dec)))
    assert err(got[0], ref) < SOUND
    state = w.empty()
    _, *state = w.prefill(impl, toks[:cut], 0, state, 1)
    logits, *state = w.prefill(impl, toks[cut:n], cut, state, 1)
    assert err(logits, ref[n - 1]) < SOUND
    cols, _ = w.decode(impl, state, [[t, 0] for t in toks[n:]], [n, MP * PS], [1, 0], n_dec)
    for j, (logits, _) in enumerate(cols):
        assert err(logits[0], ref[n + j]) < SOUND


def test_the_sizes_of_the_drawing_are_the_configurations_to_set():
    """``DRAW`` has every part at one unit; ``random_weights`` in a
    config.json (``ModelConfig.random_init``) sets what it names, a name the
    drawing does not have is refused by a sentence, and the benchmark's
    configuration and the toy set the same sizes."""
    toy = tiny_nemotron_h_config()
    row = json.load(open(os.path.join(os.path.dirname(catalog.__file__), "configs",
                                      "nemotron-3-super-120b-a12b.json")))
    assert dict(toy.random_init) == row["random_weights"]
    assert M.drawing(toy) == {**M.DRAW, **row["random_weights"]}
    plain = dataclasses.replace(toy, random_init=())
    assert M.drawing(plain) == M.DRAW and set(M.DRAW.values()) == {1.0, 0.001, 0.1}
    with pytest.raises(ValueError, match="random_weights names .'loudness'."):
        M.init_params(dataclasses.replace(toy, random_init=(("loudness", 2.0),)),
                      jax.random.PRNGKey(0))
    a, b = (M.init_params(c, jax.random.PRNGKey(0)) for c in (toy, plain))
    size = lambda p, group, name: float(jnp.std(p[group][name].astype(jnp.float32)))
    assert abs(size(a, "attn", "wq") / size(b, "attn", "wq") - 1.5) < 1e-3
    assert abs(size(a, "moe", "ws_down") / size(b, "moe", "ws_down") - 0.5) < 1e-3
    for p, (lo, hi) in ((a, (0.02, 0.5)), (b, (0.001, 0.1))):
        dt = np.asarray(jax.nn.softplus(p["mamba"]["dt_bias"]))
        assert lo * 0.999 <= dt.min() and dt.max() <= hi * 1.001
    # the plain drawing is a model like any other: the forward is the reference's
    w = World(plain, key=1)
    toks = np.random.default_rng(2).integers(2, 512, size=33).astype(np.int32)
    got = M.forward_train(w.params, plain, None, jnp.asarray(toks)[None])[0]
    assert err(got, w.reference(toks, list(range(33)))) < SOUND


# --------------------------------------------------------------------------
# the loader


def catalog_row() -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark",
                        "configs", "nemotron-3-super-120b-a12b.json")
    with open(path) as f:
        conf = json.load(f)
    own = {"assumed", "deployment", "chips", "serve_args", "rehearsal", "architecture",
           "reduced", "published"}
    return {k: v for k, v in conf.items() if k not in own}


def test_from_hf_config_reads_the_rows_own_keys_and_picks_the_module():
    cfg = ModelConfig.from_hf_config(catalog_row())
    assert cfg.arch == "nemotron_h" and get_model(cfg.arch) is M
    assert cfg.layer_types == tuple(
        ModelConfig.NEMOTRON_H_LETTERS[c] for c in "MEMEMEM*EME") and cfg.num_layers == 11
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4096, 32, 2, 128)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups,
            cfg.ssm_conv_kernel, cfg.ssm_chunk_size) == (128, 64, 128, 8, 4, 128)
    assert (cfg.moe_latent_size, cfg.moe_intermediate_size,
            cfg.moe_shared_intermediate_size) == (1024, 2688, 5376)
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok) == (512, (0, 128), 22)
    assert (cfg.moe_scoring, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.moe_select_bias) == ("sigmoid", True, 5.0, True)
    assert cfg.rope_theta == 0.0 and cfg.rms_norm_eps == 1e-5 and cfg.vocab_size == 32768
    assert cfg.recurrent and not cfg.window_cache and not cfg.latent_cache
    assert cfg.num_cache_layers == 1 and M.count(cfg, "mamba") == M.count(cfg, "moe") == 5
    s_shape, c_shape = M.state_shapes(cfg, 73)
    assert s_shape == (5, 73, 128, 8192) and c_shape == (5, 73, 3 * 10240)
    assert M.decode_step(cfg)["kernel_fits"]
    # the whole model's own keys load too: 88 layers, every expert here
    whole = ModelConfig.from_hf_config({
        **{k: v for k, v in catalog_row().items()
           if k not in ("router_num_experts", "routed_expert_offset")},
        "num_hidden_layers": 88, "n_routed_experts": 512, "vocab_size": 131072,
        "num_nextn_predict_layers": 1,
        "hybrid_override_pattern": ("MEMEMEM*E" * 3 + "MEMEMEMEM*E" * 4 + "MEMEMEM*E"
                                    + "MEMEMEME")})
    assert whole.num_layers == 88 and whole.held_experts == (0, 512)
    assert [M.count(whole, k) for k in M.KINDS] == [40, 40, 8]


@pytest.mark.parametrize("change,needle", [
    ({"hybrid_override_pattern": "MEMEMEM*EMX"}, r"letters \['X'\] that name no layer"),
    ({"hybrid_override_pattern": "MEMEMEM*EM-"}, "a '-' layer"),
    ({"num_hidden_layers": 12}, "11 letters for 12 layers"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act 'silu' is not served"),
    ({"use_conv_bias": False}, "use_conv_bias False is not served"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias True is not served"),
    ({"n_group": 8, "topk_group": 4}, "group limit"),
    ({"expand": 4}, "are not expand 4 x hidden_size"),
    ({"n_groups": 7}, "n_groups 7 does not divide"),
    ({"routed_expert_offset": 448}, "experts 448..575 are not among the router's 512"),
    ({"n_shared_experts": 2}, "n_shared_experts 2 is not served"),
    ({"norm_eps": 1e-6}, "norm_eps and layer_norm_epsilon disagree"),
    ({"moe_gate": True}, "keys this loader does not consume"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**catalog_row(), **change})


def test_the_llama_loader_refuses_the_file_when_the_model_type_is_not_known():
    """What the parent commit's program does with the new cell's file."""
    with pytest.raises(ValueError, match="n_routed_experts"):
        ModelConfig.from_hf_config({**catalog_row(), "model_type": "nemotron_g"})


def test_the_other_stack_names_this_module_for_other_patterns():
    from smg_tpu.models.olmo_hybrid import period_of

    with pytest.raises(ValueError, match="models/nemotron_h.py"):
        period_of(("linear_attention", "full_attention", "full_attention"))


def test_the_cache_plan_takes_slots_and_workspace_first_and_pages_from_the_rest():
    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import PagePool, plan_recurrent_cache

    cfg = ModelConfig.from_hf_config(catalog_row())
    limit, in_use = 16 * 10**9, int(9.3e9)
    work = M.prefill_workspace_bytes(cfg, 4096, "bfloat16")
    spec, state = plan_recurrent_cache(cfg, CacheConfig(), 72, M.state_shapes, limit, in_use, work)
    assert state.slot_bytes == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)  # 21.3 MB
    assert spec.num_layers == 1 and spec.bytes_per_page == 16 * 2 * 2 * 128 * 2
    want = (int(limit * 0.9) - in_use - 73 * state.slot_bytes - work) // spec.bytes_per_page
    assert spec.num_pages == want and 30_000 < spec.num_pages < 400_000
    # the free list at the size a chip gives it: allocation does not walk it
    pool = PagePool(300_000)
    got = pool.alloc(4096)
    assert len(set(got)) == 4096 and 0 not in got and pool.free_count == 300_000 - 1 - 4096
    pool.free(got)
    assert pool.free_count == 300_000 - 1


# --------------------------------------------------------------------------
# the engine: ``RecurrentModelRunner`` with routed counts beside the state


def make_engine(num_pages=128, max_batch=8, max_seq_len=256, horizon=4, overlap=True,
                held=(4, 4), model=None, **sched_kw):
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.tokenizer import MockTokenizer

    return Engine(EngineConfig(
        model=model or tiny_nemotron_h_config(held=held), dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=num_pages, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=max_batch, max_seq_len=max_seq_len, max_prefill_tokens=64,
            prefill_token_buckets=(16, 32, 64), decode_batch_buckets=(4, 8),
            decode_horizon=horizon, overlap_schedule=overlap, **sched_kw)),
        tokenizer=MockTokenizer())


def reference_tokens(engine, prompt, n) -> list:
    hf, toks = hf_of(engine.config.model), list(prompt)
    for _ in range(n):
        row = ARCH.logits(engine.runner.params, hf, np.asarray(toks, np.int32), [len(toks) - 1])
        toks.append(int(np.argmax(row[0])))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def test_the_engine_serves_it_through_slots_pages_and_the_one_decode_frame(engine):
    from smg_tpu.engine.flight_recorder import MOE_STEP_RECORD_KEYS, STEP_RECORD_KEYS
    from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
    from tests.test_recurrent_engine import greedy, prompts, run_all

    assert type(engine.runner) is RecurrentModelRunner
    assert engine.runner.spec.num_layers == 1 and engine.runner.s_pool.shape[0] == 3
    (short, long_, a, b, c) = prompts(1, 40, 150, 20, 70, 33)
    r = engine.generate(prompt_ids=short, sampling=greedy(10))
    assert r.token_ids == reference_tokens(engine, short, 10)
    # 150 tokens over a 64-token budget: two continuing chunks and a final one
    r = engine.generate(prompt_ids=long_, sampling=greedy(9))
    assert r.token_ids == reference_tokens(engine, long_, 9)
    out = run_all(engine, [(a, greedy(12)), (b, greedy(5)), (c, greedy(17))])
    for i, (p, n) in enumerate(((a, 12), (b, 5), (c, 17))):
        assert out[i] == reference_tokens(engine, p, n)
    loads = engine.loads()
    assert loads["lookahead_kept"] > 0 and loads["audit"]["clean"]
    assert loads["state_slots_total"] == 8 + 8 and loads["state_slots_in_use"] == 0
    assert loads["state_slot_bytes"] == 3 * (16 * 64 * 4 + 3 * 128 * 4)
    assert loads["ssm_decode"] == "xla" and "linattn_decode" not in loads
    # one decode program a batch bucket only where the paged kernel runs
    assert not engine.runner.widest_table_only
    info = loads["moe"]
    assert (info["experts"], info["experts_held"], info["top_k"], info["impl"]) == (16, 4, 4, "xla")
    assert 0 < info["picks_held"] < info["picks"] and info["picks"] % 4 == 0
    assert 0 < info["experts_hit"] <= info["picks_held"] and info["rows_max"] <= 8 * 4
    ring = engine.scheduler.flight.snapshot("test")["ring"]
    decoded = [r for r in ring if "moe_picks_held" in r]
    assert decoded and all(STEP_RECORD_KEYS <= set(r) <= STEP_RECORD_KEYS | MOE_STEP_RECORD_KEYS
                           for r in ring)
    assert sum(r["moe_picks_held"] for r in decoded) == info["picks_held"]
    assert all(r["state_lanes"] > 0 for r in decoded)
    count = lambda h: engine.metrics.moe_picks.labels(held=h)._value.get()
    assert count("true") == info["picks_held"]
    assert count("false") == info["picks"] - info["picks_held"]


@pytest.mark.parametrize("preset", ["tiny-nemotron-h", "tiny-olmo-hybrid"])
def test_where_the_paged_kernel_runs_a_decode_frame_gets_the_widest_table(preset, monkeypatch):
    """The rule is the recurrent runner's, not a module's: under the paged
    kernel, which reads each lane's own pages whatever the table's width, the
    scheduler asks for one decode program a batch bucket; under XLA attention
    (here, on the CPU) it keeps its table buckets."""
    from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
    from smg_tpu.models.config import PRESETS

    model = PRESETS[preset]()
    sched = make_engine(model=model).scheduler
    assert not sched.runner.widest_table_only
    assert [sched._mp_bucket(n) for n in (1, 9, 99)] == [8, 16, 16]
    monkeypatch.setattr(RecurrentModelRunner, "_attn_impl_for", lambda self, B, mp: "pallas")
    assert sched.runner.widest_table_only
    from smg_tpu.engine.scheduler import Scheduler

    again = Scheduler(sched.runner, sched.config)
    assert [again._mp_bucket(n) for n in (1, 9, 99)] == [again.mp] * 3


def test_a_radix_match_without_a_snapshot_prefills_from_the_first_token(engine):
    from tests.test_recurrent_engine import greedy, prompts

    (p,) = prompts(3, 80)
    before = engine.loads()["state_prefix_hits_declined"]
    first = engine.generate(prompt_ids=p, sampling=greedy(8))
    again = engine.generate(prompt_ids=p, sampling=greedy(8))  # its pages are cached now
    assert again.token_ids == first.token_ids == reference_tokens(engine, p, 8)
    assert again.cached_tokens == 0
    assert engine.loads()["state_prefix_hits_declined"] == before + 1


def test_a_preempted_request_prefills_its_state_again_and_comes_out_undisturbed():
    from tests.test_recurrent_engine import greedy, prompts, run_all

    eng = make_engine(num_pages=12, max_batch=4, max_seq_len=128, watermark_pages=1)
    ps = prompts(4, 30, 33, 36)
    out = run_all(eng, [(p, greedy(40)) for p in ps])
    loads = eng.loads()
    assert loads["preemptions"] > 0 and loads["state_recomputed_tokens"] > 0
    for i, p in enumerate(ps):
        assert out[i] == reference_tokens(eng, p, 40)
    assert loads["audit"]["clean"] and loads["state_slots_in_use"] == 0


@pytest.mark.parametrize("overlap", [True, False])
def test_a_finish_inside_a_frame_costs_the_other_lanes_nothing(overlap):
    from tests.test_recurrent_engine import greedy, prompts, run_all

    eng = make_engine(overlap=overlap)
    ps = prompts(5, 25, 31, 28, 40)
    lengths = (6, 8, 13, 21)  # 8 ends a frame of four columns exactly
    out = run_all(eng, [(p, greedy(n)) for p, n in zip(ps, lengths)])
    for i, (p, n) in enumerate(zip(ps, lengths)):
        assert out[i] == reference_tokens(eng, p, n)
    loads = eng.loads()
    assert loads["state_recomputed_tokens"] == 0 and loads["preemptions"] == 0


def test_a_discarded_lookahead_runs_no_column_and_leaves_state_and_counts_as_they_were():
    """A stop token the host cannot foresee, with a lookahead in flight: the
    frame chained on the one that met it runs no column on the device
    (``frame_clean``), so the surviving lane's state holds exactly its
    accepted tokens, and the discarded frame's picks are in no count."""
    from tests.test_recurrent_engine import greedy, prompts, run_all

    eng = make_engine()
    (p, q) = prompts(6, 30, 44)
    want = reference_tokens(eng, p, 12)
    stop = want[5]
    cut = want[: want.index(stop) + 1]
    out = run_all(eng, [(p, greedy(12, stop_token_ids=[stop])), (q, greedy(20))])
    assert out[0] == cut and out[1] == reference_tokens(eng, q, 20)
    loads = eng.loads()
    assert loads["lookahead_discarded"] > 0 and loads["state_recomputed_tokens"] == 0
    assert loads["preemptions"] == 0
    # every accepted decode token is a lane-column that routed in three layers
    assert loads["moe"]["picks"] == 3 * 4 * (len(cut) - 1 + 20 - 1)


def test_what_the_module_does_not_serve_is_refused_at_start():
    from smg_tpu.config.validation import ConfigError
    from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.weights import load_params
    from tests.test_recurrent_engine import greedy

    model = tiny_nemotron_h_config()
    cache = CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32")
    with pytest.raises(ConfigError, match="does not load its next-token module"):
        Engine(EngineConfig(model=model, cache=cache, dtype="float32",
                            scheduler=SchedulerConfig(speculative=True)))
    with pytest.raises(ConfigError, match="one device"):
        Engine(EngineConfig(model=model, cache=cache, dtype="float32",
                            parallel=ParallelConfig(tp=2)))
    with pytest.raises(ValueError, match="key map"):
        load_params(EngineConfig(model=model, model_path="/nonexistent", dtype="float32"))
    eng = make_engine()
    with pytest.raises(ValueError, match="LoRA"):
        eng.runner.load_lora("a", {})
    with pytest.raises(ValueError, match="embedding"):
        eng.embed([[1, 2, 3]])
    with pytest.raises(ValueError, match="recurrent state is not in the pages"):
        eng.runner.export_pages([1])
    with pytest.raises(ValueError, match="recurrent state"):
        eng.scheduler.prefill_only([1, 2, 3], greedy(1))
    assert set(M.SERVING_LIMITS) == {"speculative", "lora", "embeddings", "mesh",
                                     "kv_transfer", "checkpoint", "dense_mlp_layer"}


def test_the_preset_is_registered_for_serve():
    from smg_tpu.models.config import PRESETS

    cfg = PRESETS["tiny-nemotron-h"]()
    assert cfg.arch == "nemotron_h" and cfg.recurrent and cfg.num_cache_layers == 1
