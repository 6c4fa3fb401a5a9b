"""``models/glm_moe_dsa.py`` (latent attention over a learned selection of the
cache: an indexer with a key cache of its own, one selection shared by the
layers behind it) on the CPU in float32, held to the plain reference
``benchmark/architectures/glm_moe_dsa.py``: the serving forwards with the
selection live (``index_topk`` below every compared length) and in the dense
regime, through chunked prefill, a cut prompt and decode frames; the selection a
``shared`` layer reads; the exact threshold against a sort on rows with ties;
the share test of the model-configs guide; the controls on both caches; the
loader and its refusals; the cache plan; and the engine's counters."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import glm_moe_dsa as M
from smg_tpu.models import pangu_moe
from smg_tpu.models.config import ModelConfig, tiny_glm_dsa_config
from smg_tpu.ops import sparse_attention as sparse
from smg_tpu.ops.rope import rope_frequencies

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark"))
import catalog  # noqa: E402

ARCH = catalog.architecture("glm_moe_dsa")
PS = 16
#: float32 against float32: the served path's own error is rounding; what a
#: fault must pass is a hundred times that
SOUND, BROKEN = 1e-4, 1e-2


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration."""
    return {"hidden_size": cfg.hidden_size, "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_parameters": {"rope_theta": cfg.rope_theta, "rope_type": "default"},
            "index_topk": cfg.index_topk, "index_n_heads": cfg.index_n_heads,
            "index_head_dim": cfg.index_head_dim, "indexer_types": list(cfg.indexer_types),
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "n_routed_experts": cfg.held_experts[1], "router_num_experts": cfg.num_experts,
            "routed_expert_offset": cfg.held_experts[0]}


class World:
    def __init__(self, cfg, key=0):
        self.cfg = cfg
        self.params = M.init_params(cfg, jax.random.PRNGKey(key))
        self.inv = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None))
        self.W = M.cache_lanes(cfg)

    def cache(self, pages=40):
        return (jnp.zeros((self.cfg.num_cache_layers, pages, PS, self.W), jnp.float32),
                jnp.zeros((self.cfg.num_index_layers, pages, PS, self.cfg.index_head_dim),
                          jnp.float32))

    def prefill(self, kc, vc, toks, lo, table, bucket=64, impl="xla", cfg=None):
        chunk = np.zeros(bucket, np.int32)
        chunk[: len(toks)] = toks
        return jax.jit(lambda *a: M.forward_prefill(
            self.params, cfg or self.cfg, self.inv, *a, moe_impl=impl))(
            jnp.asarray(chunk), jnp.int32(lo), jnp.int32(len(toks)), kc, vc, jnp.asarray(table))

    def decode(self, kc, vc, toks, entry, tables, column=0, side=None, impl="xla", cfg=None,
               N=8):
        """One column for the lanes ``toks``; lanes at ``entry`` past the table
        are padding."""
        B = len(toks)
        if side is None:
            side = (jnp.zeros((kc.shape[0], B, N, self.W), jnp.float32),
                    jnp.zeros((vc.shape[0], B, N, vc.shape[3]), jnp.float32))
        entry = np.asarray(entry, np.int32)
        return jax.jit(lambda *a: M.forward_decode_horizon(
            self.params, cfg or self.cfg, self.inv, *a, attn_impl=impl, moe_impl=impl))(
            jnp.asarray(toks, jnp.int32), jnp.asarray(entry + column), jnp.asarray(entry),
            jnp.int32(column), (kc, vc), jnp.asarray(tables), side,
            jnp.asarray(entry < tables.shape[1] * PS))


@pytest.fixture(scope="module")
def world():
    return World(tiny_glm_dsa_config(held=(4, 8)))


def rel_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


def served(world, cfg, toks, n, chunks, n_dec, impl="xla", frames=None, pages=24):
    """Logits of the rows ``n - 1 .. n - 1 + n_dec`` through the serving path:
    the prompt's ``n`` tokens in ``chunks``, then ``n_dec`` decode columns in
    frames of ``frames`` columns (None: one frame)."""
    B, N = 4, frames or n_dec
    table = np.arange(1, pages + 1, dtype=np.int32)
    kc, vc = world.cache(pages + 1)
    for lo, hi in chunks:
        logits, kc, vc = world.prefill(kc, vc, toks[lo:hi], lo, table, impl=impl, cfg=cfg,
                                       bucket=-(-(hi - lo) // 64) * 64)
    out = [np.asarray(logits)]
    tables = np.zeros((B, pages), np.int32)
    tables[0] = table
    counts = []
    for j in range(n_dec):
        entry = np.full(B, pages * PS, np.int32)  # padded lanes sit past the table
        entry[0] = n + j - j % N
        if j % N == 0:
            side = None
        cur = np.zeros(B, np.int32)
        cur[0] = toks[n + j]
        logits, side, c = world.decode(kc, vc, cur, entry, tables, j % N, side, impl=impl,
                                       cfg=cfg, N=N)
        out.append(np.asarray(logits[0]))
        counts.append([int(x) for x in c])
        if j % N == N - 1 or j == n_dec - 1:
            from smg_tpu.ops.latent_attention import land_side_buffer

            ran = jnp.arange(N)[None, :] <= j % N
            kc, vc = (land_side_buffer(c_, s_, jnp.asarray(tables), jnp.asarray(entry), ran)
                      for c_, s_ in zip((kc, vc), side))
    return np.stack(out), counts, (kc, vc)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("topk, n", [(16, 70), (32, 200), (4096, 70)],
                         ids=["top16-of-70", "top32-of-200", "dense-regime"])
def test_two_chunks_then_decode_through_the_selection_is_one_full_forward(world, impl, topk, n):
    """Prefill in two chunks (the second behind a live prefix), then a decode
    frame, against the reference's full forward: with ``index_topk`` below the
    lengths the selection is live in every compared row."""
    cfg = dataclasses.replace(world.cfg, index_topk=topk)
    rng = np.random.default_rng(topk + n)
    n_dec = 5
    toks = rng.integers(2, 512, size=n + n_dec).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(cfg), toks, list(range(n - 1, n + n_dec)))
    got, counts, _ = served(world, cfg, toks, n, ((0, n // 2 - 3), (n // 2 - 3, n)), n_dec, impl)
    for j in range(1 + n_dec):
        assert rel_err(got[j], ref[j]) < SOUND, j
    for j, c in enumerate(counts):
        picks, held, hit, most, rows, selecting, scored = c
        # one live lane, four expert layers, top 4; the padded lanes pick nothing
        assert picks == 4 * 4 and hit <= held <= picks and most <= 4
        assert (rows, selecting, scored) == (1, int(n + j + 1 > topk), 2 * (n + j + 1))
    if topk < n:  # the selection decides: the dense reading misses
        dense = ARCH.logits(world.params, {**hf_of(cfg), "index_topk": 10**6}, toks, [n - 1])
        assert rel_err(got[0], dense[0]) > BROKEN


def test_a_cut_prompt_selects_across_prefix_and_chunk_and_frames_select_fresh_tokens(world):
    """Three chunks, the later ones behind prefixes longer than ``index_topk``,
    then decode in frames of one, of three and of eight columns: a frame's
    fresh tokens are scored and chosen from the side buffer as they are from
    the pages once landed."""
    cfg, n, n_dec = world.cfg, 150, 8
    rng = np.random.default_rng(5)
    toks = rng.integers(2, 512, size=n + n_dec).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(cfg), toks, list(range(n - 1, n + n_dec)))
    for frames in (1, 3, 8):
        got, _, _ = served(world, cfg, toks, n, ((0, 64), (64, 100), (100, n)), n_dec,
                           frames=frames)
        assert max(rel_err(got[j], ref[j]) for j in range(1 + n_dec)) < SOUND, frames


def test_grouped_prefill_with_and_without_context_matches_the_solo_chunks(world):
    rng = np.random.default_rng(1)
    a, b = rng.integers(2, 512, size=50), rng.integers(2, 512, size=23)
    tables = np.stack([np.arange(1, 9), np.arange(9, 17)]).astype(np.int32)
    run = lambda no_ctx: jax.jit(lambda *x: M.forward_prefill_batched(
        world.params, world.cfg, world.inv, *x, no_ctx=no_ctx))
    pad = lambda t: np.concatenate([t, np.zeros(64 - len(t), t.dtype)]).astype(np.int32)
    kc, vc = world.cache()
    cold, kc, vc = run(True)(jnp.asarray(np.stack([pad(a), pad(b)])), jnp.zeros(2, jnp.int32),
                             jnp.asarray([50, 23], jnp.int32), kc, vc, jnp.asarray(tables))
    a2, b2 = rng.integers(2, 512, size=40), rng.integers(2, 512, size=64)
    warm, kc, vc = run(False)(jnp.asarray(np.stack([pad(a2), pad(b2)])),
                              jnp.asarray([50, 23], jnp.int32), jnp.asarray([40, 64], jnp.int32),
                              kc, vc, jnp.asarray(tables))
    hf = hf_of(world.cfg)
    for row, (first, second) in enumerate(((a, a2), (b, b2))):
        seq = np.concatenate([first, second]).astype(np.int32)
        want = ARCH.logits(world.params, hf, seq, [len(first) - 1, len(seq) - 1])
        assert rel_err(cold[row], want[0]) < SOUND and rel_err(warm[row], want[1]) < SOUND


def test_a_shared_layer_attends_exactly_its_full_layers_set(world, monkeypatch):
    """The selection each layer's attention is given, read out of a cold
    prefill as it runs: the two ``full`` layers choose their own, and each
    ``shared`` layer reads the second's, bit for bit."""
    seen = []
    sound = pangu_moe.latent_attention_prefill

    def watched(*args):
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), args[8], ordered=True)
        return sound(*args)

    monkeypatch.setattr(pangu_moe, "latent_attention_prefill", watched)
    toks = np.random.default_rng(2).integers(2, 512, size=64).astype(np.int32)
    kc, vc = world.cache()
    table = jnp.arange(1, 5, dtype=jnp.int32)
    out = M.forward_prefill_batched(world.params, world.cfg, world.inv, jnp.asarray(toks)[None],
                                    jnp.zeros(1, jnp.int32), jnp.asarray([60], jnp.int32), kc, vc,
                                    table[None], no_ctx=True)
    jax.block_until_ready(out)
    assert len(seen) == 5 and seen[0].shape == (1, 64, 64)
    assert not np.array_equal(seen[0], seen[1])
    assert all(np.array_equal(seen[1], later) for later in seen[2:])
    rows = seen[1][0, :60, :60]
    want = np.minimum(np.arange(60) + 1, world.cfg.index_topk)
    assert (rows.sum(-1) == want).all() and not np.triu(rows, 1).any()


@pytest.mark.parametrize("k", [1, 7, 16, 40])
def test_the_exact_threshold_is_the_stable_sort_on_rows_with_ties(k):
    """``select_mask`` (32 compare-and-count passes) and ``select_decode``
    (``lax.top_k``) against a stable sort, on rows of few distinct values,
    zeros of both signs, infinities and candidates that are not."""
    rng = np.random.default_rng(k)
    rows, S = 64, 37
    scores = rng.choice(np.array([-2.5, -0.0, 0.0, 1e-30, 0.5, 0.5, 3.0, np.inf], np.float32),
                        size=(rows, S))
    scores[:8] = rng.standard_normal((8, S)).astype(np.float32)
    valid = rng.random((rows, S)) < 0.8
    valid[-1] = False
    order = np.argsort(np.where(valid, -(scores + 0.0), np.inf), axis=-1, kind="stable")
    want = np.zeros_like(valid)
    np.put_along_axis(want, order[:, :k], True, axis=-1)
    want &= valid
    got = np.asarray(sparse.select_mask(jnp.asarray(scores), jnp.asarray(valid), k))
    assert np.array_equal(got, want)
    top, ids = jax.lax.top_k(jnp.where(jnp.asarray(valid), jnp.asarray(scores) + 0.0, -jnp.inf),
                             min(k, S))
    chosen = np.zeros_like(valid)
    for r in range(rows):
        chosen[r, np.asarray(ids[r])[np.asarray(top[r]) > -np.inf]] = True
    finite = np.where(np.isinf(scores) & (scores > 0), False, True).all(-1)  # +inf passes both
    assert np.array_equal(chosen[finite], want[finite])
    # the integer image keeps float32's order
    x = jnp.asarray(np.array([-np.inf, -3.0, -1e-38, -0.0, 0.0, 1e-38, 2.0, np.inf], np.float32))
    image = np.asarray(sparse.order_key(x)).astype(np.int64)
    assert (np.diff(image) >= 0).all() and image[3] == image[4] and image.min() > 0


def test_select_decode_takes_the_side_buffers_fresh_tokens_by_position():
    rng = np.random.default_rng(0)
    B, S, N, J, D, k = 3, 32, 4, 2, 8, 6
    q, w = rng.standard_normal((B, J, D)), np.abs(rng.standard_normal((B, J)))
    keys, side = rng.standard_normal((B, S, D)), rng.standard_normal((B, N, D))
    entry = np.array([20, 3, S * 2], np.int32)
    f = lambda x: jnp.asarray(x, jnp.float32)
    ids, chosen = sparse.select_decode(f(q), f(w), f(keys), f(side), jnp.asarray(entry), 2, k)
    scores = np.einsum("bjs,bj->bs", np.maximum(np.einsum(
        "bjd,bsd->bjs", q, np.concatenate([keys, side], 1)), 0), w)
    for b in range(2):
        places = np.concatenate([np.arange(entry[b]), S + np.arange(2)])
        best = places[np.argsort(-scores[b, places], kind="stable")[:k]]
        assert set(np.asarray(ids[b])[np.asarray(chosen[b])]) == set(best)
    assert int(chosen[1].sum()) == 5 and int(chosen[0].sum()) == k


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The guide's share test: a router of 16 experts, 4 shares of 4.  What
    each share's routed experts give, summed over the shares, with the shared
    expert counted once, is the uncut reference's expert layer."""
    whole = tiny_glm_dsa_config()
    assert whole.held_experts == (0, 16)
    params = M.init_params(whole, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    T = 37
    h = jnp.asarray(rng.standard_normal((T, whole.hidden_size)), jnp.float32)
    layer = {k: v[0] for k, v in params["moe"].items()}
    experts = params["experts"]
    shape = ARCH._shape(hf_of(whole))
    with jax.default_matmul_precision("highest"):
        x = ARCH._rms(h, layer["mlp_norm"], shape["eps"])
        uncut = h + (ARCH._routed(x, params["moe"], experts, 0, shape)
                     + ARCH._swiglu(x, *ARCH._mlp(params["moe"], (0,),
                                                 ("ws_gate", "ws_up", "ws_down"))))
        alike = h + ARCH._swiglu(x, *ARCH._mlp(params["moe"], (0,),
                                               ("ws_gate", "ws_up", "ws_down")))
    live = jnp.ones((T,), bool)
    total, rows = 0.0, 0
    for start in (0, 4, 8, 12):
        share = dataclasses.replace(whole, experts_held=(start, 4))
        part = {k: v[:, start:start + 4] for k, v in experts.items()}
        out, counts = M._moe_residual(h, layer, part, 0, share, live, "xla")
        nothing = {k: jnp.zeros_like(v) for k, v in part.items()}
        silent, _ = M._moe_residual(h, layer, nothing, 0, share, live, "xla")
        np.testing.assert_allclose(silent, alike, atol=1e-5)  # every chip computes it alike
        total = total + (out - silent)
        rows += int(counts[1])
        assert int(counts[0]) == T * 4 and 0 < int(counts[2]) <= 4
    assert rows == T * 4  # every pick fell on one share
    np.testing.assert_allclose(alike + total, uncut, atol=5e-4)
    assert rel_err(alike, uncut) > BROKEN


def test_a_wrong_latent_page_and_a_wrong_index_key_page_each_miss_the_tolerance(world):
    """Two sequences of 120 and 90 tokens behind ``index_topk`` 16: the step
    through a cache whose latent page is another sequence's, or whose
    index-key page is, or under a selection of the nearest tokens, is not the
    reference's."""
    cfg, n, other = world.cfg, 120, 90
    rng = np.random.default_rng(8)
    toks = rng.integers(2, 512, size=n + 1).astype(np.int32)
    more = rng.integers(2, 512, size=other).astype(np.int32)
    ref = ARCH.logits(world.params, hf_of(cfg), toks, [n])
    kc, vc = world.cache(20)
    mine, theirs = np.arange(1, 9, dtype=np.int32), np.arange(9, 17, dtype=np.int32)
    for seq, table, m in ((toks, mine, n), (more, theirs, other)):
        _, kc, vc = world.prefill(kc, vc, seq[:m], 0, table, bucket=128)
    tables = np.zeros((2, 8), np.int32)
    tables[0] = mine
    entry = np.array([n, 8 * PS], np.int32)
    step = lambda kc, vc: np.asarray(world.decode(kc, vc, [toks[n], 0], entry, tables)[0][0])
    assert rel_err(step(kc, vc), ref[0]) < SOUND
    # every page of the sequence in turn: each wrong page is heard
    for page in range(1, 1 + n // PS):
        latent = kc.at[:, page, :, : cfg.kv_lora_rank].set(kc[:, page + 8, :, : cfg.kv_lora_rank])
        assert rel_err(step(latent, vc), ref[0]) > BROKEN, page
    heard = [rel_err(step(kc, vc.at[:, page].set(vc[:, page + 8])), ref[0])
             for page in range(1, 1 + n // PS)]
    assert max(heard) > BROKEN and sum(e > 10 * SOUND for e in heard) >= len(heard) // 2
    nearest = ARCH.logits(world.params, hf_of(cfg), toks, [n], recent=True)
    assert rel_err(nearest[0], ref[0]) > BROKEN


# --------------------------------------------------------------------------
# the loader

PUBLISHED = {
    "model_type": "glm_moe_dsa", "attention_bias": False, "ep_size": 1,
    "first_k_dense_replace": 3, "head_dim": 192, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_share_for_mtp_iteration": True,
    "index_skip_topk_offset": 3, "index_topk": 2048, "index_topk_freq": 4,
    "index_topk_pattern": None, "indexer_rope_interleave": True,
    "indexer_types": ["full"] * 3 + ["shared", "shared", "shared", "full"] * 18 + ["shared"] * 3,
    "intermediate_size": 12288, "kv_lora_rank": 512, "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75, "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64, "num_nextn_predict_layers": 1,
    "q_lora_rank": 2048, "qk_head_dim": 256, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880,
}
CUT = {**PUBLISHED, "num_hidden_layers": 5, "first_k_dense_replace": 1,
       "mlp_layer_types": ["dense"] + ["sparse"] * 4,
       "indexer_types": ["full", "full", "shared", "shared", "shared"],
       "n_routed_experts": 16, "router_num_experts": 256, "routed_expert_offset": 0,
       "vocab_size": 19360}


def test_from_hf_config_reads_the_rows_own_keys_and_picks_the_module():
    from smg_tpu.models import get_model

    from smg_tpu.models.config import PRESETS

    whole = ModelConfig.from_hf_config(PUBLISHED)
    assert PRESETS["glm-5.2"]() == whole  # the preset is the row as published
    rule = ["full" if l < 3 or (l - 3) % 4 == 3 else "shared" for l in range(78)]
    assert list(whole.indexer_types) == rule and whole.num_index_layers == 3 + 18 + 1 - 1
    cfg = ModelConfig.from_hf_config(CUT)
    assert get_model(cfg.arch) is M and cfg.latent_cache and not cfg.recurrent
    assert (cfg.num_layers, cfg.first_k_dense_replace, cfg.num_index_layers) == (5, 1, 2)
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim) == (2048, 32, 128)
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok) == (256, (0, 16), 8)
    assert cfg.rope_theta == 8e6 and cfg.head_dim == 256 and cfg.rope_dim == 64
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_select_bias and cfg.norm_topk_prob
    assert cfg.num_cache_layers == 5 and M.cache_lanes(cfg) == 640
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 3.87e9 < count < 3.90e9  # 3.88 B parameters, 7.76 GB in bfloat16
    assert shapes["indexer"]["wq"].shape == (2, 32, 2048, 128)
    assert shapes["experts"]["w_gate"].shape == (4, 16, 6144, 2048)


@pytest.mark.parametrize("change, needle", [
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"ep_size": 8}, "ep_size"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"indexer_rope_interleave": False}, "indexer_rope_interleave"),
    ({"index_topk_pattern": "FSSS"}, "index_topk_pattern"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn", "factor": 4}}, "rope_parameters"),
    ({"rope_parameters": None}, "rope_parameters"),
    ({"num_key_value_heads": 8}, "num_key_value_heads"),
    ({"qk_head_dim": 192}, "qk_head_dim"),
    ({"mlp_layer_types": ["sparse"] + ["dense"] * 4}, "mlp_layer_types"),
    ({"indexer_types": ["shared", "full", "full", "full", "full"]}, "indexer_types"),
    ({"indexer_types": ["full", "full"]}, "indexer_types"),
    ({"indexer_types": ["full", "full", "window", "shared", "shared"]}, "indexer_types"),
    ({"index_head_dim": 32}, "index_head_dim"),
    ({"routed_expert_offset": 250}, "are not among"),
    ({"sliding_window": 4096}, "does not consume"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**CUT, **change})


def test_the_llama_loader_refuses_the_file_when_the_model_type_is_not_known():
    """What the parent commit's program does with the benchmark's file: it
    stops with the model type in the message, and does not serve a dense Llama
    of these widths under this name (a ``rope_parameters`` table alone would
    not stop it: the latent attention's and the experts' keys do)."""
    with pytest.raises(ValueError, match="glm_moe_dsa_next.*kv_lora_rank"):
        ModelConfig.from_hf_config({**CUT, "model_type": "glm_moe_dsa_next"})


def test_the_cache_plan_sizes_the_index_keys_with_the_entries_from_one_budget():
    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import create_kv_buffers, plan_latent_cache

    cfg = ModelConfig.from_hf_config(CUT)
    cache = CacheConfig(page_size=16, auto_size=True, hbm_utilization=0.9, dtype="bfloat16")
    spec = plan_latent_cache(cfg, cache, hbm_limit=int(16.9e9), hbm_in_use=int(7.76e9))
    assert (spec.num_layers, spec.lanes, spec.index_layers, spec.index_lanes) == (5, 640, 2, 128)
    assert spec.bytes_per_page == 16 * (5 * 640 + 2 * 128) * 2  # 6,912 B a token as laid out
    assert spec.v_shape == (2, spec.num_pages, 16, 128)
    room = M.prefill_workspace_bytes(cfg, 4096, "bfloat16", context=17920)
    plain = pangu_moe.prefill_workspace_bytes(cfg, 4096, "bfloat16")
    assert room - plain > 3 * 128 * 2**20 + 2 * 4096 * 17920  # the score block and the mask
    tight = plan_latent_cache(cfg, cache, int(16.9e9), int(7.76e9), workspace=room)
    assert tight.num_pages == (int(16.9e9 * 0.9) - int(7.76e9) - room) // spec.bytes_per_page
    assert tight.num_pages * 16 > 560_000  # the cell's 32 callers at their fullest
    fixed = plan_latent_cache(cfg, dataclasses.replace(cache, auto_size=False, num_pages=8))
    k, v = create_kv_buffers(dataclasses.replace(fixed, dtype="float32"))
    assert k.shape == (5, 8, 16, 640) and v.shape == (2, 8, 16, 128)
    # a latent model without indexers keeps its second buffer of zero size
    from smg_tpu.models.config import tiny_pangu_moe_config

    bare = plan_latent_cache(tiny_pangu_moe_config(), dataclasses.replace(cache, auto_size=False))
    assert bare.v_shape[1] == 0 and bare.index_layers == 0


def test_the_random_weights_are_as_the_module_says():
    cfg = tiny_glm_dsa_config(held=(4, 8))
    p = M.init_params(cfg, jax.random.PRNGKey(1))
    E, R = cfg.hidden_size, M.route_lanes(cfg.hidden_size)
    C = min(M.CONST_LANES, R)
    reserved = slice(E - R - C, E)
    # only the embedding writes the reserved lanes
    for stack, name in (("dense", "wo"), ("dense", "w_down"), ("moe", "wo"), ("moe", "ws_down"),
                        ("experts", "w_down")):
        assert not np.asarray(p[stack][name])[..., reserved].any(), name
    assert np.allclose(np.asarray(p["embed"])[:, E - R - C:E - R], M.EMBED_STD)
    assert not np.asarray(p["moe"]["router"])[:, :E - R].any()
    # the index keys' rotary lanes are the attention's rotary key, the index
    # queries' the part all heads share; a pick is by score plus a bias that is not zero
    dr = cfg.qk_rope_head_dim
    assert np.allclose(np.asarray(p["indexer"]["wk"])[0, :, :dr], np.asarray(p["dense"]["w_dk_pe"])[0])
    assert np.allclose(np.asarray(p["indexer"]["wk"])[1, :, :dr], np.asarray(p["moe"]["w_dk_pe"])[0])
    assert float(jnp.abs(p["moe"]["select_bias"]).min()) > 0
    # every token weighs every index head positively
    x = jnp.asarray(np.random.default_rng(0).standard_normal((50, E)), jnp.float32)
    x = x.at[:, E - R - C:E - R].set(1.0).at[:, E - R:].set(jnp.sign(x[:, E - R:]))
    assert float((x @ p["indexer"]["ww"][0]).min()) > 0
    assert p["indexer"]["wq"].shape == (2, 4, cfg.q_lora_rank, 32)


def test_merge_counts_adds_all_but_the_most_rows():
    a = jnp.asarray([8, 3, 2, 3, 1, 1, 70], jnp.int32)
    b = jnp.asarray([8, 5, 4, 5, 1, 0, 30], jnp.int32)
    assert M.merge_counts(a, b).tolist() == [16, 8, 6, 5, 2, 1, 100]
    assert M.ROUTED_COUNTS[3] == "rows_max" and len(M.ROUTED_COUNTS) == 7


# --------------------------------------------------------------------------
# the engine: ``LatentModelRunner`` with a second buffer, the counters


@pytest.fixture(scope="module")
def engine():
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.tokenizer import MockTokenizer

    model = tiny_glm_dsa_config(held=(4, 8))
    return Engine(EngineConfig(
        model=model, dtype="float32",
        cache=CacheConfig(page_size=16, num_pages=128, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_seq_len=512, max_prefill_tokens=64, decode_horizon=4)),
        tokenizer=MockTokenizer())


def test_the_engine_serves_it_through_the_latent_runner_and_counts_the_selectors_rows(engine):
    from smg_tpu.engine.latent_runner import LatentModelRunner
    from smg_tpu.engine.request import SamplingParams

    assert isinstance(engine.runner, LatentModelRunner)
    assert engine.runner.k_cache.shape[0] == 5 and engine.runner.v_cache.shape == (2, 128, 16, 32)
    rng = np.random.default_rng(11)
    prompt = rng.integers(2, 512, size=150).astype(np.int32).tolist()  # cut by the 64-token budget
    greedy = SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True)
    first = engine.generate(prompt_ids=prompt, sampling=greedy)
    seq = np.asarray(prompt + first.token_ids, np.int32)
    ref = ARCH.logits(engine.runner.params, hf_of(engine.config.model), seq,
                      list(range(149, 149 + 6)))
    assert np.argmax(ref, axis=-1).tolist() == first.token_ids
    loads = engine.loads()
    dsa, cache = loads["dsa"], loads["latent_cache"]
    assert dsa["index_topk"] == 16 and dsa["prefill_rows"] == 150
    assert dsa["prefill_rows_selecting"] == 150 - 16
    assert dsa["prefill_index_tokens_scored"] == 2 * 150 * 151 // 2
    assert dsa["decode_rows"] == dsa["decode_rows_selecting"] >= 5
    assert dsa["decode_index_tokens_scored"] >= 2 * sum(range(151, 156))
    assert cache["index_key_bytes"] == 32 * 4 and cache["index_layers"] == 2
    assert "index keys [2, pages, 16, 32]" in cache["layout"]
    # a prefix is reused: a page holds its tokens' entries and index keys alike
    again = engine.generate(prompt_ids=prompt, sampling=greedy)
    assert again.token_ids == first.token_ids and again.cached_tokens > 16
    assert engine.loads()["audit"]["clean"]


def test_what_the_module_does_not_serve_is_refused_at_start():
    from smg_tpu.config.validation import validate_engine_config
    from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig

    model = tiny_glm_dsa_config()
    base = dict(model=model, dtype="float32",
                cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"))
    spec = EngineConfig(**base, scheduler=SchedulerConfig(
        max_seq_len=256, max_prefill_tokens=64, speculative=True))
    assert any(M.SERVING_LIMITS["speculative"] in str(i) for i in validate_engine_config(spec))
    mesh = EngineConfig(**base, parallel=ParallelConfig(tp=2), scheduler=SchedulerConfig(
        max_seq_len=256, max_prefill_tokens=64))
    assert any(M.SERVING_LIMITS["mesh"] in str(i) for i in validate_engine_config(mesh))
    assert set(M.SERVING_LIMITS) == set(pangu_moe.SERVING_LIMITS)
    with pytest.raises(ValueError, match="glm_moe_dsa does not take lora"):
        M.forward_prefill(None, model, None, None, None, None, None, None, None, lora=object())
