"""``models/pangu_moe.py`` (latent attention, routed experts on a chip's share,
sandwich norms) and its ops on the CPU in float32, held to the plain reference
``benchmark/architectures/pangu_ultra_moe.py``: the serving forwards through
the latent cache, absorbed against expanded attention, the share test of the
model-configs guide, the router, the grouped-product kernel interpreted, the
loader and the cache plan."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import pangu_moe as M
from smg_tpu.models.config import ModelConfig, tiny_pangu_moe_config
from smg_tpu.ops import moe
from smg_tpu.ops.rope import rope_frequencies

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark"))
import catalog  # noqa: E402

ARCH = catalog.architecture("pangu_ultra_moe")
PS = 16


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration."""
    return {"qk_nope_head_dim": cfg.qk_nope_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok, "scoring_func": cfg.moe_scoring,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "routed_expert_offset": cfg.held_experts[0]}


class World:
    def __init__(self, cfg):
        self.cfg = cfg
        self.params = M.init_params(cfg, jax.random.PRNGKey(0))
        self.inv = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None))
        self.W = M.cache_lanes(cfg)

    def cache(self, pages=40):
        z = lambda p: jnp.zeros((self.cfg.num_layers, p, PS, self.W), jnp.float32)
        return z(pages), z(0)

    def prefill(self, kc, vc, toks, lo, table, bucket=64, impl="xla"):
        chunk = np.zeros(bucket, np.int32)
        chunk[: len(toks)] = toks
        return jax.jit(lambda *a: M.forward_prefill(self.params, self.cfg, self.inv, *a,
                                                    moe_impl=impl))(
            jnp.asarray(chunk), jnp.int32(lo), jnp.int32(len(toks)), kc, vc, jnp.asarray(table))


@pytest.fixture(scope="module")
def world():
    return World(tiny_pangu_moe_config(held=(4, 8)))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_chunks_then_decode_through_the_latent_cache_is_one_full_forward(world, impl):
    rng = np.random.default_rng(0)
    n, n_dec, B, N = 70, 5, 4, 8
    toks = rng.integers(2, 512, size=n + n_dec).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(world.cfg), toks, list(range(n - 1, n + n_dec)))
    table = np.arange(1, 9, dtype=np.int32)
    kc, vc = world.cache()
    for lo, hi in ((0, 30), (30, n)):  # the second chunk behind a live prefix
        logits, kc, vc = world.prefill(kc, vc, toks[lo:hi], lo, table, impl=impl)
    assert vc.size == 0 and rel_err(logits, ref[0]) < 1e-4
    side = jnp.zeros((world.cfg.num_layers, B, N, world.W), jnp.float32)
    tables = np.zeros((B, 8), np.int32)
    tables[0] = table
    entry = np.full(B, 8 * PS, np.int32)  # padded lanes sit past the table
    entry[0] = n
    decode = jax.jit(lambda *a: M.forward_decode_horizon(
        world.params, world.cfg, world.inv, *a, attn_impl=impl, moe_impl=impl))
    for j in range(n_dec):
        cur = np.zeros(B, np.int32)
        cur[0] = toks[n + j]
        logits, side, counts = decode(jnp.asarray(cur), jnp.asarray(entry + j),
                                      jnp.asarray(entry), jnp.int32(j), kc, jnp.asarray(tables),
                                      side, jnp.asarray(entry < 8 * PS))
        assert rel_err(logits[0], ref[1 + j]) < 1e-4
        # one live lane, two expert layers, top 4: the padded lanes pick nothing
        assert int(counts[0]) == 2 * 4 and 0 <= int(counts[1]) <= 8
        assert int(counts[2]) <= int(counts[1]) and int(counts[3]) <= 4


def test_grouped_prefill_with_and_without_context_matches_the_solo_chunks(world):
    rng = np.random.default_rng(1)
    a, b = rng.integers(2, 512, size=50), rng.integers(2, 512, size=23)
    ta, tb = np.arange(1, 5, dtype=np.int32), np.arange(5, 9, dtype=np.int32)
    kc, vc = world.cache()
    la, kc, vc = world.prefill(kc, vc, a, 0, ta)
    lb, kc, vc = world.prefill(kc, vc, b, 0, tb)
    batched = jax.jit(lambda *x, no_ctx: M.forward_prefill_batched(
        world.params, world.cfg, world.inv, *x, no_ctx=no_ctx), static_argnames="no_ctx")
    rows = np.zeros((2, 64), np.int32)
    rows[0, :50], rows[1, :23] = a, b
    k2, v2 = world.cache()
    lg, k2, v2 = batched(jnp.asarray(rows), jnp.zeros(2, jnp.int32), jnp.asarray([50, 23]),
                         k2, v2, jnp.asarray(np.stack([ta, tb])), no_ctx=True)
    np.testing.assert_allclose(lg[0], la, atol=2e-5)
    np.testing.assert_allclose(lg[1], lb, atol=2e-5)
    np.testing.assert_allclose(k2[:, 1:9], kc[:, 1:9], atol=1e-5)
    # the same rows continuing behind 16 cached tokens each
    k3, v3 = world.cache()
    for toks, table in ((a, ta), (b, tb)):
        _, k3, v3 = world.prefill(k3, v3, toks[:16], 0, table)
    rows = np.zeros((2, 64), np.int32)
    rows[0, :34], rows[1, :7] = a[16:], b[16:]
    lg, k3, v3 = batched(jnp.asarray(rows), jnp.asarray([16, 16]), jnp.asarray([34, 7]),
                         k3, v3, jnp.asarray(np.stack([ta, tb])), no_ctx=False)
    np.testing.assert_allclose(lg[0], la, atol=2e-5)
    np.testing.assert_allclose(lg[1], lb, atol=2e-5)


def _cold_group_under_both_attentions(module, W, t_reals, T=64):
    """``forward_prefill_batched`` of cold rows under XLA's attention and under
    the online-softmax kernel (interpreted): the logits of the real rows and
    every page but the garbage page."""
    G = len(t_reals)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(2, 512, (G, T)), jnp.int32)
    tables = jnp.asarray(1 + np.arange(G * 4).reshape(G, 4), jnp.int32)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        kc, vc = W.cache()
        out[impl] = jax.jit(lambda kc, vc, impl=impl: module.forward_prefill_batched(
            W.params, W.cfg, W.inv, tokens, jnp.zeros(G, jnp.int32), jnp.asarray(t_reals), kc,
            vc, tables, no_ctx=True, attn_impl=impl))(kc, vc)
    real = np.asarray(t_reals) > 0
    (lx, kx, _), (lp, kp, _) = out["xla"], out["pallas_interpret"]
    return np.asarray(lx)[real], np.asarray(lp)[real], np.asarray(kx)[:, 1:], np.asarray(kp)[:, 1:]


@pytest.mark.parametrize("t_reals", [[50], [64, 0, 23]], ids=["one-row", "three-rows-one-padded"])
def test_cold_grouped_prefill_under_the_kernel_matches_xla_at_the_published_head_widths(t_reals):
    """Keys of 128 + 64 lanes a head and values of 128, two heads: the same
    logits and the same entries under either form of the expanded attention;
    behind a prefix ``attn_impl`` changes nothing."""
    W = World(dataclasses.replace(tiny_pangu_moe_config(held=(4, 8)), num_heads=2,
                                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128))
    lx, lp, kx, kp = _cold_group_under_both_attentions(M, W, t_reals)
    np.testing.assert_allclose(lp, lx, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(kp, kx, atol=1e-5)
    assert rel_err(lp, lx) < 1e-4 and np.std(lx) > 0.1


def test_absorbed_and_expanded_attention_agree_on_one_cache(world):
    """The last token of a prefill (expanded) and the same token decoded
    behind the others (absorbed) see the same cache and give the same logits."""
    rng = np.random.default_rng(2)
    toks = rng.integers(2, 512, size=41).astype(np.int32)
    table = np.arange(1, 5, dtype=np.int32)
    kc, vc = world.cache()
    expanded, kc_full, _ = world.prefill(kc, vc, toks, 0, table)
    _, kc, vc = world.prefill(kc, vc, toks[:40], 0, table)
    side = jnp.zeros((world.cfg.num_layers, 1, 8, world.W), jnp.float32)
    absorbed, side, _ = M.forward_decode_horizon(
        world.params, world.cfg, world.inv, jnp.asarray(toks[40:]), jnp.asarray([40]),
        jnp.asarray([40]), jnp.int32(0), kc, jnp.asarray(table[None]), side,
        jnp.asarray([True]))
    np.testing.assert_allclose(absorbed[0], expanded, atol=2e-5)
    # what the column left in the side buffer is what the prefill wrote
    np.testing.assert_allclose(side[:, 0, 0], kc_full[:, table[40 // PS], 40 % PS], atol=1e-5)


@pytest.mark.parametrize("score_bytes", [2**30, 2**14])  # queries whole, and in blocks of 16
def test_cached_prefill_attention_walks_the_blocks_the_context_needs(monkeypatch, score_bytes):
    """The cached form against the plain one over the same entries: blocks of
    two pages over a table of nine (padded to ten), two sequences whose
    contexts end in the second and the fourth block."""
    from smg_tpu.ops import latent_attention as LA

    monkeypatch.setattr(LA, "CONTEXT_BLOCK_PAGES", 2)
    monkeypatch.setattr(LA, "SCORE_BLOCK_BYTES", score_bytes)
    rng = np.random.default_rng(4)
    G, T, H, dn, dr, dv, rkv, W, mp = 2, 32, 3, 8, 4, 8, 12, 128, 9
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    cache = f(2, 30, PS, W)
    tables = jnp.asarray(rng.permutation(np.arange(1, 30))[: G * mp].reshape(G, mp), jnp.int32)
    w_uk, w_uv = f(H, rkv, dn), f(H, rkv, dv)
    q_nope, q_pe = f(G, T, H, dn), f(G, T, H, dr)
    prefix = jnp.asarray([20, 90])
    pos = prefix[:, None] + jnp.arange(T)[None]
    ctx_lens = prefix + jnp.asarray([30, 32])  # the first row's last two queries are padding
    got = LA.latent_attention_prefill_cached(q_nope, q_pe, cache, 1, tables, w_uk, w_uv, pos,
                                             ctx_lens, 0.3, rkv, dr)
    ent = cache[1, tables].reshape(G, mp * PS, W)
    want = LA.latent_attention_prefill(
        q_nope, q_pe, jnp.einsum("gsc,hcd->gshd", ent[..., :rkv], w_uk), ent[..., rkv:rkv + dr],
        jnp.einsum("gsc,hcd->gshd", ent[..., :rkv], w_uv), pos, ctx_lens, 0.3)
    real = np.arange(T)[None, :] < np.asarray([30, 32])[:, None]
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The guide's share test: 4 shares of 4 of 16 tiny experts.  What each
    share's expert layer gives beyond the shared expert, summed over the
    shares, with the shared expert counted once, is the uncut layer."""
    whole_cfg = tiny_pangu_moe_config()
    p = M.init_params(whole_cfg, jax.random.PRNGKey(3))["moe"]
    layer = {k: v[0] for k, v in p.items()}
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((37, whole_cfg.hidden_size)), jnp.float32)
    x = M._norm(h, layer["mlp_norm"], whole_cfg)
    with jax.default_matmul_precision("highest"):
        uncut = ARCH._routed(x, ARCH._Weights(p, 0, routed=True), top_k=4, scoring="sigmoid", norm_topk=True,
                             scale=2.5, first=0)
    r = moe.route(x, layer["router"], top_k=4, scoring="sigmoid", norm_topk=True, scale=2.5)
    total, rows = 0.0, 0
    for first in (0, 4, 8, 12):
        part = {k: layer[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}
        y, (n, hit) = moe.expert_layer(x, r, part["w_gate"], part["w_up"], part["w_down"],
                                       (first, 4))
        total, rows = total + y, rows + int(n)
        assert 0 < int(hit) <= 4
    assert rows == 37 * 4  # every pick fell on exactly one share
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    # and through the module: the sum over the shares of (layer output - the
    # shared-only output) plus the shared-only output once is the uncut layer
    live = jnp.ones((37,), bool)
    experts = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    uncut_out, counts = M._moe_residual(h, layer, experts, 0, whole_cfg, live, "xla")
    assert [int(c) for c in counts[:3]] == [37 * 4, 37 * 4, 16] and int(counts[3]) == 37 * 4


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_no_token_is_dropped_when_every_pick_falls_on_one_held_expert(impl, monkeypatch):
    """256 tokens whose four picks include held expert 5, and nothing else
    held: 256 rows on one expert, through a buffer of 128 rows in two passes."""
    monkeypatch.setattr(moe, "rows_buffer", lambda pairs: 128)
    T, E, F = 256, 64, 32
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
         for s in ((4, E, F), (4, E, F), (4, F, E))]
    experts = np.tile(np.asarray([5, 20, 21, 22], np.int32), (T, 1))
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (T, 4)), jnp.float32)
    y, (rows, hit) = jax.jit(lambda x: moe.expert_layer(
        x, moe.Routing(jnp.asarray(experts), weights), *w, (4, 4), impl))(x)
    assert (int(rows), int(hit)) == (256, 1)
    want = weights[:, :1] * ((jax.nn.silu(x @ w[0][1]) * (x @ w[1][1])) @ w[2][1])
    np.testing.assert_allclose(y, want, atol=1e-4)


def test_the_router_is_the_configs():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((9, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x @ router, np.float64)))
    top = np.argsort(-s, axis=-1)[:, :4]
    r = moe.route(x, router, top_k=4, scoring="sigmoid", norm_topk=True, scale=2.5)
    assert np.array_equal(np.sort(r.experts, axis=-1), np.sort(top, axis=-1))
    picked = np.take_along_axis(s, np.asarray(r.experts), axis=-1)
    np.testing.assert_allclose(r.weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(r.weights.sum(-1), 2.5, rtol=1e-5)
    plain = moe.route(x, router, top_k=4, scoring="sigmoid", norm_topk=False, scale=2.5)
    np.testing.assert_allclose(plain.weights, 2.5 * picked, rtol=1e-5)
    assert not np.allclose(plain.weights, r.weights)
    soft = moe.route(x, router, top_k=4, scoring="softmax", norm_topk=True, scale=1.0)
    logits = np.asarray(x @ router)
    lt = np.take_along_axis(logits, np.asarray(soft.experts), axis=-1)
    np.testing.assert_allclose(soft.weights, jax.nn.softmax(lt, axis=-1), rtol=1e-5)  # Qwen-MoE's


def test_the_grouped_product_kernel_interpreted_is_the_ragged_product():
    from smg_tpu.ops.pallas.moe_experts import grouped_matmul, tiling, visits

    rng = np.random.default_rng(6)
    for R, K, N, sizes, tiles in [(256, 256, 384, [0, 100, 3, 60], (64, 128, 128)),
                                  (256, 128, 128, [0, 0, 0, 0], (64, 128, 128)),
                                  (512, 128, 256, [200, 0, 300, 12, 0], (128, 128, 128)),
                                  (64, 128, 128, [1, 2, 3], None)]:
        rows = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((2, len(sizes), K, N)), jnp.float32)
        gs = jnp.asarray(sizes, jnp.int32)
        got = grouped_matmul(rows, w, gs, 1, interpret=True, tiles=tiles)
        np.testing.assert_allclose(got, jax.lax.ragged_dot(rows, w[1], gs), atol=1e-4)
    # an expert without rows has no visit: the kernel never fetches its weights
    group, tile, bounds, n = visits(jnp.asarray([0, 100, 3, 60], jnp.int32), 256, 64)
    assert int(n[0]) == 2 + 1 + 2 and 0 not in np.asarray(group)[: int(n[0])].tolist()
    assert np.asarray(bounds).tolist() == [0, 0, 100, 103, 163]
    assert tiling(512, 7680, 2048) == (128, 1536, 1024) and tiling(8192, 2048, 7680)[0] == 256


PUBLISHED = {
    "model_type": "pangu_ultra_moe", "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5, "sandwich_norm": True, "tie_word_embeddings": False,
    "v_head_dim": 128, "vocab_size": 153600}


def test_from_hf_config_reads_the_published_keys_and_picks_the_module():
    from smg_tpu.models.registry import get_model

    cfg = ModelConfig.from_hf_config(PUBLISHED)
    assert cfg.arch == "pangu_ultra_moe" and get_model(cfg.arch).__name__.endswith("pangu_moe")
    assert (cfg.num_layers, cfg.first_k_dense_replace, cfg.num_heads) == (61, 3, 128)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.head_dim, cfg.rope_dim) == (1536, 512, 192, 64)
    assert cfg.held_experts == (0, 256) and cfg.num_experts == 256 and cfg.post_norms
    assert (cfg.moe_scoring, cfg.norm_topk_prob, cfg.routed_scaling_factor) == ("sigmoid", True, 2.5)
    assert cfg.latent_cache and not cfg.recurrent and M.cache_lanes(cfg) == 640
    share = ModelConfig.from_hf_config({**PUBLISHED, "n_routed_experts": 16,
                                        "router_num_experts": 256, "routed_expert_offset": 32,
                                        "rope_scaling": "none"})
    assert share.held_experts == (32, 16) and share.num_experts == 256


def test_the_random_routed_experts_are_drawn_as_loud_as_the_config_says():
    """Random weights draw every expert alike unless the configuration (a
    benchmark's, for its comparison) says otherwise; the program tunes none."""
    assert ModelConfig.from_hf_config(PUBLISHED).random_routed_out_gain == 1.0
    assert ModelConfig.from_hf_config(
        {**PUBLISHED, "random_routed_out_gain": 0.5}).random_routed_out_gain == 0.5
    cfg = tiny_pangu_moe_config()
    std = lambda c, name: float(jnp.std(M.init_params(c, jax.random.PRNGKey(1))["moe"][name]))
    alike = std(cfg, "w_down") / std(cfg, "ws_down")
    half = dataclasses.replace(cfg, random_routed_out_gain=0.5)
    assert abs(alike - 1.0) < 0.05 and abs(std(half, "w_down") / std(half, "ws_down") - 0.5) < 0.03


@pytest.mark.parametrize("change, needle", [
    ({"n_group": 8}, "n_group"),
    ({"sandwich_norm": False}, "sandwich_norm"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"num_key_value_heads": 8}, "num_key_value_heads"),
    ({"scoring_func": "tanh"}, "scoring_func"),
    ({"n_routed_experts": 16, "router_num_experts": 256, "routed_expert_offset": 250},
     "not among"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**PUBLISHED, **change})


@pytest.mark.parametrize("key, value", [
    ("n_routed_experts", 64), ("n_shared_experts", 2), ("kv_lora_rank", 512),
    ("q_lora_rank", 1536), ("scoring_func", "sigmoid"), ("norm_topk_prob", False)])
def test_the_llama_loader_refuses_keys_it_would_drop(key, value):
    """A config.json of another model type that carries routed-expert or
    latent-attention keys is not served as a dense Llama of its widths."""
    base = {"architectures": ["DeepseekV3ForCausalLM"], "vocab_size": 1000, "hidden_size": 256,
            "num_hidden_layers": 2, "num_attention_heads": 4}
    assert ModelConfig.from_hf_config(base).arch == "llama"
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config({**base, key: value})


def test_the_cache_plan_is_one_buffer_sized_after_the_weights():
    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import create_kv_buffers, plan_latent_cache

    cfg = ModelConfig.from_hf_config({**PUBLISHED, "num_hidden_layers": 5,
                                      "first_k_dense_replace": 1})
    cache = CacheConfig(page_size=16, auto_size=True, hbm_utilization=0.9, dtype="bfloat16")
    spec = plan_latent_cache(cfg, cache, hbm_limit=int(16.9e9), hbm_in_use=int(9.84e9))
    assert spec.lanes == 640 and spec.shape[-1] == 640 and spec.v_shape[1] == 0
    assert spec.bytes_per_page == 5 * 16 * 640 * 2
    # the weights come off once: 0.9 x 16.9 - 9.84 = 5.37 GB of pages
    assert spec.num_pages == (int(16.9e9 * 0.9) - int(9.84e9)) // spec.bytes_per_page
    assert abs(spec.num_pages * spec.bytes_per_page - 5.37e9) < 0.01e9
    # and what the largest prefill holds beside its arguments stays free of pages
    room = M.prefill_workspace_bytes(cfg, 4096, "bfloat16")
    assert 1.3e9 < room < 2.0e9  # compiled for a v5e such a program holds 1.07-1.33 GB
    tight = plan_latent_cache(cfg, cache, int(16.9e9), int(9.84e9), workspace=room)
    assert tight.num_pages == (int(16.9e9 * 0.9) - int(9.84e9) - room) // spec.bytes_per_page
    fixed = plan_latent_cache(cfg, dataclasses.replace(cache, auto_size=False, num_pages=8))
    k, v = create_kv_buffers(dataclasses.replace(fixed, dtype="float32"))
    assert k.shape == (5, 8, 16, 640) and v.size == 0
