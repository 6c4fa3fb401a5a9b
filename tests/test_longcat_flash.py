"""``models/longcat_flash.py`` (two latent-attention sublayers a layer, the
routed experts as a shortcut round the second, identity experts among the
router's outputs) on the CPU in float32, held to the plain reference
``benchmark/architectures/longcat_flash.py``: the serving forwards through the
latent cache's two cache layers a layer, the share test of the model-configs
guide with the identity term counted once, every fault of the block a reader
of the equations could make, the padded lanes, the loader and the cache plan,
and the engine's counters of the identity picks."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import longcat_flash as M
from smg_tpu.models import pangu_moe
from smg_tpu.models.config import ModelConfig, tiny_longcat_flash_config
from smg_tpu.models.llama import _mlp, _mlp_residual, _norm
from smg_tpu.ops import moe
from smg_tpu.ops.rope import rope_frequencies

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark"))
import catalog  # noqa: E402

ARCH = catalog.architecture("longcat_flash")
PS = 16
#: float32 against float32: the served path's own error is rounding; what a
#: fault must pass is a hundred times that
SOUND, BROKEN = 1e-4, 1e-2


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration."""
    real = cfg.num_experts - cfg.zero_experts
    return {"hidden_size": cfg.hidden_size, "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "mla_scale_q_lora": cfg.mla_q_scale != 1.0, "mla_scale_kv_lora": cfg.mla_kv_scale != 1.0,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "moe_topk": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "n_routed_experts": cfg.held_experts[1], "router_num_experts": real,
            "routed_expert_offset": cfg.held_experts[0], "zero_expert_num": cfg.zero_experts}


def louder_mlps(params, gain=100.0):
    """At a hidden size of 128 a dense MLP drawn at 0.02 returns 0.003 of its
    input; the tests give it a voice, so that what reads it shows."""
    layers = params["layers"]
    sub = tuple({**s, "w_down": s["w_down"] * gain} for s in layers["sub"])
    return {**params, "layers": {**layers, "sub": sub}}


class World:
    def __init__(self, cfg, key=0, bias_std=None):
        self.cfg = cfg
        self.params = louder_mlps(M.init_params(cfg, jax.random.PRNGKey(key)))
        if bias_std is not None:
            layers = self.params["layers"]
            bias = jax.random.normal(jax.random.PRNGKey(9), layers["select_bias"].shape) * bias_std
            self.params = {**self.params, "layers": {**layers, "select_bias": bias}}
        self.inv = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None))
        self.W = M.cache_lanes(cfg)

    def cache(self, pages=40):
        z = lambda p: jnp.zeros((self.cfg.num_cache_layers, p, PS, self.W), jnp.float32)
        return z(pages), z(0)

    def prefill(self, kc, vc, toks, lo, table, bucket=64, impl="xla", cfg=None, params=None,
                stack=None):
        chunk = np.zeros(bucket, np.int32)
        chunk[: len(toks)] = toks
        kw = {"stack": stack} if stack is not None else {}
        forward = pangu_moe.forward_prefill if stack is not None else M.forward_prefill
        return jax.jit(lambda *a: forward(
            self.params if params is None else params, cfg or self.cfg, self.inv, *a,
            moe_impl=impl, **kw))(
            jnp.asarray(chunk), jnp.int32(lo), jnp.int32(len(toks)), kc, vc, jnp.asarray(table))

    def decode(self, kc, toks, entry, tables, column=0, side=None, impl="xla", cfg=None, N=8):
        """One column for the lanes ``toks``; lanes at ``entry`` past the table
        are padding."""
        B = len(toks)
        if side is None:
            side = jnp.zeros((self.cfg.num_cache_layers, B, N, self.W), jnp.float32)
        entry = np.asarray(entry, np.int32)
        return jax.jit(lambda *a: M.forward_decode_horizon(
            self.params, cfg or self.cfg, self.inv, *a, attn_impl=impl, moe_impl=impl))(
            jnp.asarray(toks, jnp.int32), jnp.asarray(entry + column), jnp.asarray(entry),
            jnp.int32(column), kc, jnp.asarray(tables), side,
            jnp.asarray(entry < tables.shape[1] * PS))


@pytest.fixture(scope="module")
def world():
    return World(tiny_longcat_flash_config(held=(6, 6)))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_chunks_then_decode_through_both_cache_layers_is_one_full_forward(world, impl):
    rng = np.random.default_rng(0)
    n, n_dec, B, N = 70, 5, 4, 8
    toks = rng.integers(2, 512, size=n + n_dec).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(world.cfg), toks, list(range(n - 1, n + n_dec)))
    table = np.arange(1, 9, dtype=np.int32)
    kc, vc = world.cache()
    assert kc.shape[0] == 2 * world.cfg.num_layers == 4
    for lo, hi in ((0, 30), (30, n)):  # the second chunk behind a live prefix
        logits, kc, vc = world.prefill(kc, vc, toks[lo:hi], lo, table, impl=impl)
    assert vc.size == 0 and rel_err(logits, ref[0]) < SOUND
    # every cache layer holds the prompt's entries, and they differ by sublayer
    held = np.asarray(kc[:, 1, :, : world.cfg.kv_lora_rank])
    assert all(np.abs(held[l]).max() > 0 for l in range(4))
    assert not np.allclose(held[0], held[1]) and not np.allclose(held[1], held[2])
    tables = np.zeros((B, 8), np.int32)
    tables[0] = table
    entry = np.full(B, 8 * PS, np.int32)  # padded lanes sit past the table
    entry[0] = n
    side = None
    for j in range(n_dec):
        cur = np.zeros(B, np.int32)
        cur[0] = toks[n + j]
        logits, side, counts = world.decode(kc, cur, entry, tables, j, side, impl=impl)
        assert rel_err(logits[0], ref[1 + j]) < SOUND
        picks, held_picks, hit, most, zero = (int(c) for c in counts)
        # one live lane, two layers, top 6: the padded lanes pick nothing of any kind
        assert picks == 2 * 6 and 0 <= zero <= picks and held_picks + zero <= picks
        assert hit <= held_picks and most <= 6


def test_grouped_prefill_with_and_without_context_matches_the_solo_chunks(world):
    rng = np.random.default_rng(1)
    a, b = rng.integers(2, 512, size=50), rng.integers(2, 512, size=23)
    ta, tb = np.arange(1, 5, dtype=np.int32), np.arange(5, 9, dtype=np.int32)
    kc, vc = world.cache()
    la, kc, vc = world.prefill(kc, vc, a, 0, ta)
    lb, kc, vc = world.prefill(kc, vc, b, 0, tb)
    ref = ARCH.logits(world.params, hf_of(world.cfg), a, [49])
    assert rel_err(la, ref[0]) < SOUND
    batched = jax.jit(lambda *x, no_ctx: M.forward_prefill_batched(
        world.params, world.cfg, world.inv, *x, no_ctx=no_ctx), static_argnames="no_ctx")
    rows = np.zeros((2, 64), np.int32)
    rows[0, :50], rows[1, :23] = a, b
    k2, v2 = world.cache()
    lg, k2, v2 = batched(jnp.asarray(rows), jnp.zeros(2, jnp.int32), jnp.asarray([50, 23]),
                         k2, v2, jnp.asarray(np.stack([ta, tb])), no_ctx=True)
    np.testing.assert_allclose(lg[0], la, atol=2e-4)
    np.testing.assert_allclose(lg[1], lb, atol=2e-4)
    np.testing.assert_allclose(k2[:, 1:9], kc[:, 1:9], atol=1e-4)
    # the same rows continuing behind 16 cached tokens each
    k3, v3 = world.cache()
    for toks, table in ((a, ta), (b, tb)):
        _, k3, v3 = world.prefill(k3, v3, toks[:16], 0, table)
    rows = np.zeros((2, 64), np.int32)
    rows[0, :34], rows[1, :7] = a[16:], b[16:]
    lg, k3, v3 = batched(jnp.asarray(rows), jnp.asarray([16, 16]), jnp.asarray([34, 7]),
                         k3, v3, jnp.asarray(np.stack([ta, tb])), no_ctx=False)
    np.testing.assert_allclose(lg[0], la, atol=2e-4)
    np.testing.assert_allclose(lg[1], lb, atol=2e-4)


@pytest.mark.parametrize("t_reals", [[50], [64, 0, 23]], ids=["one-row", "three-rows-one-padded"])
def test_cold_grouped_prefill_under_the_kernel_matches_xla_at_the_published_head_widths(t_reals):
    """Both sublayers of a layer through the online-softmax kernel
    (interpreted), keys of 128 + 64 lanes a head and values of 128: the same
    logits and the same entries in all four cache layers as XLA's form."""
    from tests.test_pangu_moe import _cold_group_under_both_attentions

    W = World(dataclasses.replace(tiny_longcat_flash_config(held=(6, 6)), num_heads=2,
                                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128))
    lx, lp, kx, kp = _cold_group_under_both_attentions(M, W, t_reals)
    np.testing.assert_allclose(lp, lx, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(kp, kx, atol=1e-5)
    assert rel_err(lp, lx) < 1e-4 and kx.shape[0] == 4 and np.std(lx) > 0.1


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The guide's share test: a router of 24 real and 8 identity outputs, 4
    shares of 6 experts.  What each share's expert branch gives beyond the
    identity term, summed over the shares, with the identity term and the
    dense path counted once, is the uncut reference's layer."""
    whole = tiny_longcat_flash_config()
    assert whole.held_experts == (0, 24) and whole.zero_experts == 8
    params = louder_mlps(M.init_params(whole, jax.random.PRNGKey(3)))
    layers, experts = params["layers"], params["experts"]
    rng = np.random.default_rng(3)
    T = 37
    h = jnp.asarray(rng.standard_normal((T, whole.hidden_size)), jnp.float32)
    shape = ARCH._shape(hf_of(whole))
    with jax.default_matmul_precision("highest"):
        uncut = ARCH._layer(h, layers, experts, 0, shape)
        first = layers["sub"][0]
        w = lambda name: first[name][0]
        u = h + ARCH._attention(ARCH._rms(h, w("attn_norm"), shape["eps"]), w, shape)
        x0 = ARCH._rms(u, w("mlp_norm"), shape["eps"])
        # the layer with an expert branch that gives nothing: the dense path
        silent = ARCH._layer(h, layers, {k: v[:, :0] for k, v in experts.items()}, 0,
                             {**shape, "real": 10**6})
    layer = {"router": layers["router"][0], "select_bias": layers["select_bias"][0]}
    live = jnp.ones((T,), bool)
    identity, rows, zero = None, 0, None
    total = 0.0
    for start in (0, 6, 12, 18):
        share = dataclasses.replace(whole, experts_held=(start, 6))
        part = {k: v[:, start:start + 6] for k, v in experts.items()}
        m, counts = M.shortcut(x0, layer, part, 0, share, live, "xla")
        nothing = {k: jnp.zeros_like(v) for k, v in part.items()}
        alike, _ = M.shortcut(x0, layer, nothing, 0, share, live, "xla")  # the identity term
        identity = alike if identity is None else identity
        np.testing.assert_allclose(alike, identity, atol=1e-6)  # every chip computes it alike
        total = total + (m - alike)
        rows += int(counts[1])
        zero = int(counts[4]) if zero is None else zero
        assert int(counts[0]) == T * 6 and int(counts[4]) == zero and 0 < int(counts[2]) <= 6
    assert rows + zero == T * 6  # every pick fell on one share or on an identity expert
    assert 0 < zero < T * 6 and float(jnp.abs(identity).max()) > 0.05
    np.testing.assert_allclose(silent + total + identity, uncut, atol=5e-4)
    assert rel_err(silent + total, uncut) > BROKEN and rel_err(silent + identity, uncut) > BROKEN


# --------------------------------------------------------------------------
# the faults


def _stack_with(fault: str):
    """``M._stack`` with one line of the block wrong."""

    def stack(params, cfg, inv_freq, h, positions, live, state, attend, moe_impl):
        experts = params["experts"]

        def block(carry, xs):
            (h, state, counts), (layer, l) = carry, xs
            first, second = layer["sub"]
            o, state = M.latent_attention(first, cfg, _norm(h, first["attn_norm"], cfg),
                                          positions, inv_freq, attend, 2 * l, state)
            u = h + o
            x0 = _norm(u, first["mlp_norm"], cfg)
            m, c = M.shortcut(x0, layer, experts, l, cfg, live, moe_impl)
            v = u + _mlp(first, x0, cfg)
            o, state = M.latent_attention(second, cfg, _norm(v, second["attn_norm"], cfg),
                                          positions, inv_freq, attend, 2 * l + 1, state)
            w = v + o
            if fault == "branch_fed_from_the_second_norm":
                m, c = M.shortcut(_norm(w, second["mlp_norm"], cfg), layer, experts, l, cfg,
                                  live, moe_impl)
            if fault == "branch_added_before_ffn1_reads":
                return (_mlp_residual(w + m.astype(w.dtype), second, cfg), state,
                        M.merge_counts(counts, c)), None
            z = _mlp_residual(w, second, cfg)
            return ((z + m).astype(h.dtype), state, M.merge_counts(counts, c)), None

        (h, state, counts), _ = jax.lax.scan(
            block, (h, state, jnp.zeros((len(M.ROUTED_COUNTS),), jnp.int32)),
            (params["layers"], jnp.arange(cfg.num_layers)))
        return h, state, counts

    return stack


FAULTS = ["none", "identity_dropped", "identity_unweighted", "branch_fed_from_the_second_norm",
          "branch_added_before_ffn1_reads", "q_scale_missing", "kv_scale_missing",
          "picked_by_score_alone"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_block_misses_the_tolerance(fault, monkeypatch):
    """A prompt prefilled whole by the program with one fault, against the
    sound reference.  ``none`` is the control of the controls: the stack
    written out here is the module's."""
    world = World(tiny_longcat_flash_config(held=(6, 6)), key=1, bias_std=0.02)
    rng = np.random.default_rng(5)
    toks = rng.integers(2, 512, size=60).astype(np.int32)
    want = ARCH.logits(world.params, hf_of(world.cfg), toks, [59])[0]
    cfg, params, stack = world.cfg, world.params, _stack_with(fault)
    if fault == "identity_dropped":
        cfg = dataclasses.replace(cfg, zero_experts=0)
    elif fault == "identity_unweighted":
        def unweighted(x, routing, first):
            on = routing.experts >= first
            return (jnp.sum(on, axis=-1, keepdims=True) * x.astype(jnp.float32),
                    jnp.sum(on).astype(jnp.int32))

        monkeypatch.setattr(moe, "identity_picks", unweighted)
    elif fault == "q_scale_missing":
        cfg = dataclasses.replace(cfg, mla_q_scale=1.0)
    elif fault == "kv_scale_missing":
        cfg = dataclasses.replace(cfg, mla_kv_scale=1.0)
    elif fault == "picked_by_score_alone":
        layers = params["layers"]
        params = {**params, "layers": {**layers,
                                       "select_bias": jnp.zeros_like(layers["select_bias"])}}
    kc, vc = world.cache()
    got, _, _ = world.prefill(kc, vc, toks, 0, np.arange(1, 5, dtype=np.int32), cfg=cfg,
                              params=params, stack=stack)
    err = rel_err(got, want)
    assert err < SOUND if fault == "none" else err > BROKEN, err


def test_a_router_that_reads_the_whole_stream_is_served_as_the_reference_has_it():
    """The random routers read the routing lanes alone; a checkpoint's read
    every lane.  With such a router (normal 0.02 over all rows) the program
    and the reference still agree, and the picks do depend on the context."""
    world = World(tiny_longcat_flash_config(held=(6, 6)), key=2)
    layers = world.params["layers"]
    dense = jax.random.normal(jax.random.PRNGKey(6), layers["router"].shape) * 0.02
    params = {**world.params, "layers": {**layers, "router": dense}}
    rng = np.random.default_rng(6)
    toks = rng.integers(2, 512, size=60).astype(np.int32)
    want = ARCH.logits(params, hf_of(world.cfg), toks, [59])[0]
    kc, vc = world.cache()
    got, _, _ = world.prefill(kc, vc, toks, 0, np.arange(1, 5, dtype=np.int32), params=params)
    assert rel_err(got, want) < SOUND
    lanes_only = ARCH.logits(world.params, hf_of(world.cfg), toks, [59])[0]
    assert rel_err(lanes_only, want) > BROKEN


def test_the_selection_bias_picks_and_does_not_weigh():
    """With a bias that decides, the picks are those of ``s + b`` and the
    weights ``6 s``: neither ``s`` alone nor ``6 (s + b)``."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 32)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(32) * 0.02, jnp.float32)
    s = np.asarray(jax.nn.softmax(x @ router, axis=-1))
    r = moe.route(x, router, top_k=6, scoring="softmax", norm_topk=False, scale=6.0,
                  select_bias=bias)
    by_sum = np.sort(np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :6], axis=-1)
    by_score = np.sort(np.argsort(-s, axis=-1)[:, :6], axis=-1)
    assert np.array_equal(np.sort(r.experts, axis=-1), by_sum) and not np.array_equal(by_sum, by_score)
    np.testing.assert_allclose(r.weights, 6.0 * np.take_along_axis(s, np.asarray(r.experts), -1),
                               rtol=1e-5)
    assert float(np.abs(np.sum(r.weights, -1) - 6.0).min()) > 0.3  # not renormalised


def test_cache_layers_of_a_layer_are_2l_and_2l_plus_1_and_swapped_they_miss(world):
    rng = np.random.default_rng(7)
    n = 45
    toks = rng.integers(2, 512, size=n + 1).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(world.cfg), toks, [n])[0]
    table = np.arange(1, 5, dtype=np.int32)
    kc, vc = world.cache()
    _, kc, vc = world.prefill(kc, vc, toks[:n], 0, table)
    sound, side, _ = world.decode(kc, toks[n:], [n], table[None])
    assert rel_err(sound[0], ref) < SOUND
    # the column's entries went to the side buffer's layers in the same order
    again, kc_full, _ = world.prefill(*world.cache(), toks, 0, table)
    np.testing.assert_allclose(side[:, 0, 0], kc_full[:, table[n // PS], n % PS], atol=1e-4)
    np.testing.assert_allclose(again, sound[0], atol=2e-4)  # expanded and absorbed agree
    swapped = kc.reshape(2, 2, *kc.shape[1:])[:, ::-1].reshape(kc.shape)
    broken, _, _ = world.decode(swapped, toks[n:], [n], table[None])
    assert rel_err(broken[0], ref) > BROKEN


def test_a_padded_lane_or_token_adds_no_pick_of_any_kind(world):
    cfg = world.cfg
    rng = np.random.default_rng(8)
    x = rng.standard_normal((5, cfg.hidden_size)).astype(np.float32)
    # the routing lanes as a normed stream has them: signs of one small magnitude
    R = M.route_lanes(cfg.hidden_size)
    x[:, -R:] = np.sign(x[:, -R:]) * M.EMBED_STD / 4
    x = jnp.asarray(x)
    layers = world.params["layers"]
    layer = {"router": layers["router"][0], "select_bias": layers["select_bias"][0]}
    live = jnp.asarray([True, False, True, False, False])
    m, counts = M.shortcut(x, layer, world.params["experts"], 0, cfg, live, "xla")
    assert np.all(np.asarray(m)[[1, 3, 4]] == 0) and np.all(np.abs(np.asarray(m)[[0, 2]]).max(-1) > 0)
    alone, alone_counts = M.shortcut(x[jnp.asarray([0, 2])], layer, world.params["experts"], 0,
                                     cfg, jnp.ones((2,), bool), "xla")
    np.testing.assert_allclose(np.asarray(m)[[0, 2]], alone, atol=1e-6)
    assert np.array_equal(counts, alone_counts) and int(counts[0]) == 2 * 6
    none, no_counts = M.shortcut(x, layer, world.params["experts"], 0, cfg,
                                 jnp.zeros((5,), bool), "xla")
    assert not np.asarray(none).any() and not np.asarray(no_counts).any()
    # a frame of padded lanes alone counts nothing
    tables = np.zeros((3, 4), np.int32)
    kc, _ = world.cache()
    _, _, c = world.decode(kc, [0, 0, 0], [4 * PS] * 3, tables)
    assert not np.asarray(c).any()


def test_identity_picks_weigh_the_token_by_their_scores():
    x = jnp.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]], jnp.float32)
    routing = moe.Routing(jnp.asarray([[0, 5, 7], [4, 1, 2], [-1, -1, -1]], jnp.int32),
                          jnp.asarray([[0.5, 0.25, 0.125], [1.0, 1.0, 1.0], [9.0, 9.0, 9.0]]))
    y, n = moe.identity_picks(x, routing, 4)
    np.testing.assert_allclose(y, [[0.375, 0.75], [3.0, 4.0], [0.0, 0.0]])
    assert int(n) == 3
    # to dispatch an identity pick is a pick that is not on a held expert
    d = moe.dispatch(routing.experts, (0, 4))
    assert int(d.rows) == 3 and np.asarray(d.group_sizes).tolist() == [1, 1, 1, 0]


def test_merge_counts_adds_all_but_the_most_rows():
    a, b = jnp.asarray([10, 4, 3, 2, 5], jnp.int32), jnp.asarray([7, 1, 1, 9, 2], jnp.int32)
    assert np.asarray(M.merge_counts(a, b)).tolist() == [17, 5, 4, 9, 7]
    assert M.ROUTED_COUNTS == (*pangu_moe.ROUTED_COUNTS, "picks_zero") and len(M.ROUTED_COUNTS) == 5


# --------------------------------------------------------------------------
# the loader and the cache plan


PUBLISHED = {
    "model_type": "longcat_flash", "attention_bias": False, "vocab_size": 131072,
    "hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_from_hf_config_reads_the_rows_own_keys_and_picks_the_module():
    from smg_tpu.models.registry import get_model

    cfg = ModelConfig.from_hf_config(PUBLISHED)
    assert cfg.arch == "longcat_flash" and get_model(cfg.arch) is M
    assert (cfg.num_layers, cfg.num_cache_layers, cfg.num_heads) == (28, 56, 64)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (12288, 2048)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.head_dim, cfg.rope_dim) == (1536, 512, 192, 64)
    assert (cfg.num_experts, cfg.zero_experts, cfg.num_experts_per_tok) == (768, 256, 12)
    assert cfg.held_experts == (0, 512) and cfg.n_shared_experts == 0
    assert (cfg.moe_scoring, cfg.norm_topk_prob, cfg.routed_scaling_factor) == ("softmax", False, 6.0)
    assert cfg.moe_select_bias and not cfg.tie_word_embeddings
    assert cfg.mla_q_scale == 2.0 and abs(cfg.mla_kv_scale - 12 ** 0.5) < 1e-12
    assert cfg.latent_cache and not cfg.recurrent and not cfg.window_cache
    assert M.cache_lanes(cfg) == 640
    share = ModelConfig.from_hf_config({**PUBLISHED, "n_routed_experts": 16,
                                        "router_num_experts": 512, "routed_expert_offset": 32})
    assert share.held_experts == (32, 16) and share.num_experts == 768
    # how loud the random weights are is the module's to say, not a key's
    with pytest.raises(ValueError, match="random_routed_out_gain"):
        ModelConfig.from_hf_config({**PUBLISHED, "random_routed_out_gain": 3.0})
    plain = ModelConfig.from_hf_config({**PUBLISHED, "mla_scale_q_lora": False,
                                        "mla_scale_kv_lora": False, "zero_expert_num": 0})
    assert (plain.mla_q_scale, plain.mla_kv_scale, plain.num_experts) == (1.0, 1.0, 512)
    # the other latent model is as it was: one cache layer a layer, no scale
    from smg_tpu.models.config import tiny_pangu_moe_config

    other = tiny_pangu_moe_config()
    assert other.num_cache_layers == other.num_layers and other.zero_experts == 0
    assert (other.mla_q_scale, other.mla_kv_scale, other.attention_sublayers) == (1.0, 1.0, 1)


@pytest.mark.parametrize("change, needle", [
    ({"some_new_key": 1}, "some_new_key"),
    ({"zero_expert_type": "copy"}, "zero_expert_type"),
    ({"attention_method": "GQA"}, "attention_method"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"norm_topk_prob": True}, "norm_topk_prob"),
    ({"router_bias": True}, "router_bias"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"n_routed_experts": 16, "router_num_experts": 512, "routed_expert_offset": 500},
     "not among"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**PUBLISHED, **change})


def test_the_llama_loader_refuses_the_file_when_the_model_type_is_not_known():
    """What the parent commit's program does with the benchmark's file: it
    stops, and does not serve a dense Llama of these widths under this name."""
    with pytest.raises(ValueError, match="kv_lora_rank"):
        ModelConfig.from_hf_config({**PUBLISHED, "model_type": "longcat_flash_next"})


def test_the_cache_plan_counts_two_cache_layers_a_layer():
    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import create_kv_buffers, plan_latent_cache

    cfg = ModelConfig.from_hf_config({**PUBLISHED, "num_layers": 4, "n_routed_experts": 16,
                                      "router_num_experts": 512, "vocab_size": 16384})
    cache = CacheConfig(page_size=16, auto_size=True, hbm_utilization=0.9, dtype="bfloat16")
    spec = plan_latent_cache(cfg, cache, hbm_limit=int(16.9e9), hbm_in_use=int(10.35e9))
    assert spec.num_layers == 8 and spec.lanes == 640 and spec.v_shape[1] == 0
    assert spec.bytes_per_page == 8 * 16 * 640 * 2  # 10,240 B a token as laid out
    room = M.prefill_workspace_bytes(cfg, 4096, "bfloat16")
    assert 1.0e9 < room < 2.0e9  # compiled for a v5e such a program holds 0.91-1.02 GB
    tight = plan_latent_cache(cfg, cache, int(16.9e9), int(10.35e9), workspace=room)
    assert tight.num_pages == (int(16.9e9 * 0.9) - int(10.35e9) - room) // spec.bytes_per_page
    assert tight.num_pages * 16 > 64 * 3072  # the cell's 64 callers at their longest
    fixed = plan_latent_cache(cfg, dataclasses.replace(cache, auto_size=False, num_pages=8))
    k, v = create_kv_buffers(dataclasses.replace(fixed, dtype="float32"))
    assert k.shape == (8, 8, 16, 640) and v.size == 0


def test_the_random_weights_are_as_loud_as_the_module_says():
    cfg = tiny_longcat_flash_config(held=(6, 6))
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    E, R = cfg.hidden_size, M.route_lanes(cfg.hidden_size)
    std = lambda *path: float(jnp.std(_at(params, path)[..., : E - R]))
    assert abs(std("layers", "sub", 0, "wo") / (cfg.num_heads * cfg.v_head_dim) ** -0.5
               - M.ATTN_OUT_GAIN) < 0.8
    # queries of SCORE_STD times unit size, keys and values of unit size, the scales in
    q = float(jnp.std(params["layers"]["sub"][0]["w_uq_pe"]))
    assert abs(q * cfg.mla_q_scale * cfg.q_lora_rank ** 0.5 - M.SCORE_STD) < 0.1
    k = float(jnp.std(params["layers"]["sub"][0]["w_uk"]))
    assert abs(k * cfg.mla_kv_scale * cfg.kv_lora_rank ** 0.5 - 1.0) < 0.05
    bias = params["layers"]["select_bias"]
    assert bias.dtype == jnp.float32 and 0 < float(jnp.abs(bias).max()) < 0.01
    # an expert's result is ROUTED_OUT times its input's size
    x = jax.random.normal(jax.random.PRNGKey(2), (64, E))
    ex = {k: v[0, 0] for k, v in params["experts"].items()}
    y = (jax.nn.silu(x @ ex["w_gate"]) * (x @ ex["w_up"])) @ ex["w_down"]
    assert abs(float(jnp.std(y[:, : E - R])) / M.ROUTED_OUT - 1.0) < 0.15


def test_the_routers_read_lanes_that_only_the_embedding_writes():
    """The last ``route_lanes`` lanes: a sign a token and lane in the
    embedding, zeros in every projection that writes the stream, and the only
    rows of the routers that are not zero.  A token's picks are then those of
    its signs whatever magnitude the stream has there, so a rounding of the
    stream moves no pick."""
    cfg = tiny_longcat_flash_config(held=(6, 6))
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    E, R = cfg.hidden_size, M.route_lanes(cfg.hidden_size)
    assert R == 32
    assert np.all(np.abs(np.asarray(params["embed"][:, E - R:])) == np.float32(M.EMBED_STD))
    layers = params["layers"]
    for sub in layers["sub"]:
        assert not np.asarray(sub["wo"][..., E - R:]).any()
        assert not np.asarray(sub["w_down"][..., E - R:]).any()
    assert not np.asarray(params["experts"]["w_down"][..., E - R:]).any()
    assert not np.asarray(layers["router"][:, : E - R]).any()
    assert np.asarray(layers["router"][:, E - R:]).all()
    # the stream through both layers, and the same with everything but the
    # routing lanes rounded to bfloat16 on the way into each layer: the
    # magnitudes move, no pick does
    rng = np.random.default_rng(4)
    toks = rng.integers(2, 512, size=48).astype(np.int32)
    shape = ARCH._shape(hf_of(cfg))
    picks = {False: [], True: []}
    with jax.default_matmul_precision("highest"):
        for rounded in picks:
            h = params["embed"][jnp.asarray(toks)]
            for l in range(cfg.num_layers):
                if rounded:
                    h = h.astype(jnp.bfloat16).astype(jnp.float32) * (1 + 3e-3 * l)
                first = lambda name: layers["sub"][0][name][l]
                u = h + ARCH._attention(ARCH._rms(h, first("attn_norm"), shape["eps"]), first, shape)
                x0 = ARCH._rms(u, first("mlp_norm"), shape["eps"])
                lanes = np.abs(np.asarray(x0[:, E - R:]))
                assert np.all(lanes == lanes[:, :1])  # one magnitude a token
                scores = jax.nn.softmax(x0 @ layers["router"][l], axis=-1)
                picks[rounded].append(np.sort(np.asarray(jax.lax.top_k(
                    scores + layers["select_bias"][l][None], cfg.num_experts_per_tok)[1])))
                h = ARCH._layer(h, layers, params["experts"], l, shape)
    assert all(np.array_equal(a, b) for a, b in zip(picks[False], picks[True]))
    assert not np.array_equal(picks[False][0], picks[False][1])  # a layer routes its own way


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


# --------------------------------------------------------------------------
# the engine: ``LatentModelRunner`` over 2 x layers cache layers, the counters


@pytest.fixture(scope="module")
def engine():
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.tokenizer import MockTokenizer

    model = tiny_longcat_flash_config(held=(6, 6))
    return Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=128, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=512, max_prefill_tokens=128, decode_horizon=4)),
        tokenizer=MockTokenizer())


def test_the_engine_serves_it_through_the_latent_runner_and_counts_the_identity_picks(engine):
    from smg_tpu.engine.flight_recorder import MOE_STEP_RECORD_KEYS, STEP_RECORD_KEYS
    from smg_tpu.engine.latent_runner import LatentModelRunner
    from smg_tpu.engine.request import SamplingParams

    assert isinstance(engine.runner, LatentModelRunner)
    assert engine.runner.spec.num_layers == 4 and engine.runner.k_cache.shape[0] == 4
    prompt = list(range(5, 45))
    greedy = SamplingParams(temperature=0.0, max_new_tokens=12, ignore_eos=True)
    first = engine.generate(prompt_ids=prompt, sampling=greedy)
    again = engine.generate(prompt_ids=prompt, sampling=greedy)
    assert first.token_ids == again.token_ids and len(first.token_ids) == 12
    assert again.cached_tokens > 0  # a latent prefix of both cache layers is reused
    loads = engine.loads()
    info = loads["moe"]
    assert (info["experts"], info["experts_held"], info["experts_zero"], info["top_k"]) == (
        32, 6, 8, 6)
    assert 0 < info["picks_zero"] < info["picks"] and info["picks"] % 6 == 0
    assert info["picks_held"] + info["picks_zero"] <= info["picks"]
    assert loads["latent_cache"]["entry_bytes_published"] == (96 + 16) * 4
    assert loads["audit"]["clean"]
    ring = engine.scheduler.flight.snapshot("test")["ring"]
    decoded = [r for r in ring if "moe_picks_zero" in r]
    assert decoded and all(STEP_RECORD_KEYS <= set(r) <= STEP_RECORD_KEYS | MOE_STEP_RECORD_KEYS
                           for r in ring)
    assert sum(r["moe_picks_zero"] for r in decoded) == info["picks_zero"]
    count = lambda h: engine.metrics.moe_picks.labels(held=h)._value.get()
    assert count("identity") == info["picks_zero"] and count("true") == info["picks_held"]
    assert count("false") == info["picks"] - info["picks_held"] - info["picks_zero"]


def test_the_served_tokens_are_the_references_argmax(engine):
    from smg_tpu.engine.request import SamplingParams

    rng = np.random.default_rng(11)
    prompt = rng.integers(2, 512, size=33).astype(np.int32).tolist()
    out = engine.generate(prompt_ids=prompt, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=6, ignore_eos=True))
    seq = np.asarray(prompt + out.token_ids, np.int32)
    ref = ARCH.logits(engine.runner.params, hf_of(engine.config.model), seq,
                      list(range(32, 32 + 6)))
    assert np.argmax(ref, axis=-1).tolist() == out.token_ids


def test_what_the_module_does_not_serve_is_refused_at_start():
    from smg_tpu.config.validation import validate_engine_config
    from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig

    model = tiny_longcat_flash_config()
    base = dict(model=model, dtype="float32",
                cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"))
    spec = EngineConfig(**base, scheduler=SchedulerConfig(
        max_seq_len=256, max_prefill_tokens=64, speculative=True))
    assert any(M.SERVING_LIMITS["speculative"] in str(i) for i in validate_engine_config(spec))
    mesh = EngineConfig(**base, parallel=ParallelConfig(tp=2), scheduler=SchedulerConfig(
        max_seq_len=256, max_prefill_tokens=64))
    assert any(M.SERVING_LIMITS["mesh"] in str(i) for i in validate_engine_config(mesh))
    assert set(M.SERVING_LIMITS) == set(pangu_moe.SERVING_LIMITS)
    with pytest.raises(ValueError, match="longcat_flash does not take lora"):
        M.forward_prefill(None, model, None, None, None, None, None, None, None, lora=object())
