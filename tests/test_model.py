"""Model correctness: the paged prefill/decode serving path must agree with the
dense causal forward (the engine-level analogue of the reference's golden
pipeline-parity tests, ``routers/grpc/pipeline.rs:1194-1436``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import llama
from smg_tpu.models.config import tiny_test_config
from smg_tpu.ops.rope import rope_frequencies


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_test_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    return cfg, params, inv_freq


def _empty_cache(cfg, num_pages=32, page_size=16):
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads * cfg.head_dim)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def test_prefill_padding_is_inert(setup):
    cfg, params, inv_freq = setup
    tokens = jnp.array([5, 6, 7, 8, 9, 10, 11, 12, 13, 14], jnp.int32)
    page_table = jnp.array([1, 2, 0, 0], jnp.int32)
    kc, vc = _empty_cache(cfg)
    lo_exact, _, _ = llama.forward_prefill(
        params, cfg, inv_freq, tokens, jnp.int32(0), jnp.int32(10), kc, vc, page_table
    )
    kc, vc = _empty_cache(cfg)
    padded = jnp.concatenate([tokens, jnp.full((6,), 7, jnp.int32)])
    lo_pad, _, _ = llama.forward_prefill(
        params, cfg, inv_freq, padded, jnp.int32(0), jnp.int32(10), kc, vc, page_table
    )
    np.testing.assert_allclose(np.asarray(lo_exact), np.asarray(lo_pad), atol=1e-5)


# --------------------------------------------------------------------------
# the seam: ``llama.decoder_block`` is the one layer body, and every forward
# hands it its own ``rotate`` and ``attend``.  Each forward, on each kind of
# config the block branches on, against the dense causal forward.

SEQ = np.arange(5, 45, dtype=np.int32) * 7 % 400 + 3  # 40 tokens
ROWS = ((0, 29), (3, 17), (11, 40))  # grouped rows: slices of SEQ


def _seam_config(kind):
    from smg_tpu.models.config import tiny_gemma2_config

    if kind == "gemma":  # post-norms, softcaps, (1+w) norms, alternating window
        return dataclasses.replace(tiny_gemma2_config(), sliding_window_pattern=2)
    return dataclasses.replace(tiny_test_config(), qk_norm=(kind == "qk_norm"))


@pytest.fixture(scope="module", params=["plain", "gemma", "qk_norm"])
def seam(request):
    cfg = _seam_config(request.param)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # norm weights off their identity, so that a norm in the wrong place shows
    params["layers"] = {
        k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape, v.dtype)
        if k.endswith("norm") else v
        for i, (k, v) in enumerate(sorted(params["layers"].items()))}
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    dense = lambda ids: np.asarray(
        llama.forward_train(params, cfg, inv_freq, jnp.asarray(ids)[None])[0])
    return cfg, params, inv_freq, dense


def _prefilled(cfg, params, inv_freq, n, page_table):
    """Caches holding SEQ[:n] behind ``page_table``."""
    kc, vc = _empty_cache(cfg)
    _, kc, vc = llama.forward_prefill(
        params, cfg, inv_freq, jnp.asarray(SEQ[:n]), jnp.int32(0), jnp.int32(n),
        kc, vc, page_table)
    return kc, vc


def _side(cfg, lanes, n):
    hk = jnp.zeros((cfg.num_layers, lanes, n, cfg.num_kv_heads * cfg.head_dim), jnp.float32)
    return hk, hk


def _solo(cfg, params, inv_freq, dense):
    """One chunk, padded to its bucket."""
    kc, vc = _empty_cache(cfg)
    tokens = jnp.asarray(np.concatenate([SEQ[:10], np.full(6, 7, np.int32)]))
    logits, _, _ = llama.forward_prefill(
        params, cfg, inv_freq, tokens, jnp.int32(0), jnp.int32(10), kc, vc,
        jnp.array([1, 2, 0, 0], jnp.int32))
    return logits, dense(SEQ[:10])[-1]


def _two_chunks(cfg, params, inv_freq, dense):
    """The second chunk behind the first (the radix cache's continuation)."""
    pt = jnp.array([1, 2, 3, 0], jnp.int32)
    kc, vc = _prefilled(cfg, params, inv_freq, 16, pt)
    logits, _, _ = llama.forward_prefill(
        params, cfg, inv_freq, jnp.asarray(SEQ[16:24]), jnp.int32(16), jnp.int32(8),
        kc, vc, pt)
    return logits, dense(SEQ[:24])[-1]


def _grouped(cfg, params, inv_freq, cut):
    """ROWS as one group and a padded row; with ``cut`` each row's first
    ``cut`` tokens go first, and the rest behind them."""
    pts = jnp.array([[1, 2, 0, 0], [3, 4, 0, 0], [5, 6, 0, 0], [0, 0, 0, 0]], jnp.int32)
    kc, vc = _empty_cache(cfg)
    start = np.zeros(4, np.int32)
    for lo_cut, hi_cut, no_ctx in ((0, cut, True), (cut, 32, False)) if cut else ((0, 32, True),):
        tokens = np.zeros((4, 32), np.int32)
        t_reals = np.zeros(4, np.int32)
        for r, (lo, hi) in enumerate(ROWS):
            part = SEQ[lo:hi][lo_cut:hi_cut]
            tokens[r, :len(part)], t_reals[r] = part, len(part)
        logits, kc, vc = llama.forward_prefill_batched(
            params, cfg, inv_freq, jnp.asarray(tokens), jnp.asarray(start),
            jnp.asarray(t_reals), kc, vc, pts, no_ctx=no_ctx)
        start = start + t_reals
    return logits[:3]


def _grouped_cold(cfg, params, inv_freq, dense):
    return (_grouped(cfg, params, inv_freq, 0),
            np.stack([dense(SEQ[lo:hi])[-1] for lo, hi in ROWS]))


def _grouped_behind_prefix(cfg, params, inv_freq, dense):
    return (_grouped(cfg, params, inv_freq, 9),
            np.stack([dense(SEQ[lo:hi])[-1] for lo, hi in ROWS]))


def _horizon(cfg, params, inv_freq, dense, n):
    """``n`` columns of a decode frame; lane 1 is inactive (garbage page)."""
    pt = jnp.array([1, 2, 0, 0], jnp.int32)
    kc, vc = _prefilled(cfg, params, inv_freq, 10, pt)
    tables = jnp.stack([pt, jnp.zeros(4, jnp.int32)])
    entry = jnp.array([10, 0], jnp.int32)
    hk, hv = _side(cfg, 2, n)
    got = []
    for j in range(n):
        logits, hk, hv = llama.forward_decode_horizon(
            params, cfg, inv_freq, jnp.array([SEQ[10 + j], 0], jnp.int32), entry + j,
            entry, jnp.int32(j), kc, vc, tables, hk, hv)
        got.append(logits[0])
    return jnp.stack(got), dense(SEQ[:10 + n])[10:]


def _verify_block(cfg, params, inv_freq, dense):
    pt = jnp.array([1, 2, 0, 0], jnp.int32)
    kc, vc = _prefilled(cfg, params, inv_freq, 10, pt)
    block = jnp.asarray(np.stack([SEQ[10:14], np.zeros(4, np.int32)]))
    logits, bk, _ = llama.forward_verify_block(
        params, cfg, inv_freq, block, jnp.array([10, 0], jnp.int32), kc, vc,
        jnp.stack([pt, jnp.zeros(4, jnp.int32)]))
    assert bk.shape == (cfg.num_layers, 2, 4, cfg.num_kv_heads * cfg.head_dim)
    return logits[0], dense(SEQ[:14])[10:]


def _embed_hidden(cfg, params, inv_freq, dense):
    """``forward_embed`` gives the direction of the last token's normed hidden
    state; the dense logits before their softcap are that state through the
    output embedding, so the two directions agree."""
    tokens = np.zeros((2, 16), np.int32)
    tokens[0, :13], tokens[1, :6] = SEQ[:13], SEQ[20:26]
    e = llama.forward_embed(params, cfg, inv_freq, jnp.asarray(tokens),
                            jnp.array([13, 6], jnp.int32))
    table = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    want = np.stack([dense(SEQ[:13])[-1], dense(SEQ[20:26])[-1]])
    if cfg.final_logit_softcap:
        want = cfg.final_logit_softcap * np.arctanh(want / cfg.final_logit_softcap)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    return unit(np.asarray(e @ table)), unit(want)


SEAM_CASES = {
    "prefill": _solo,
    "prefill_two_chunks": _two_chunks,
    "grouped_cold": _grouped_cold,
    "grouped_behind_prefix": _grouped_behind_prefix,
    "decode_horizon_1": lambda *a: _horizon(*a, 1),
    "decode_horizon_4": lambda *a: _horizon(*a, 4),
    "verify_block": _verify_block,
    "embed_hidden": _embed_hidden,
}


@pytest.mark.parametrize("forward", SEAM_CASES)
def test_forward_matches_dense(seam, forward):
    got, want = SEAM_CASES[forward](*seam)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-5)


def test_forwards_with_lora_match_merged_weights():
    """The block hands its adapter bank to ``_qkv`` and ``_attn_out``: solo
    prefill, grouped prefill and a decode column under adapter 1 against the
    dense forward over weights with that adapter merged in."""
    cfg = tiny_test_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, None))
    L, E, r = cfg.num_layers, cfg.hidden_size, 4
    HD, KD = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    dims = {"wq": (E, HD), "wk": (E, KD), "wv": (E, KD), "wo": (HD, E)}
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    bank = {}
    for w, (i, o) in dims.items():  # slot 0 is the no-adapter slot
        bank[f"{w}_a"] = 0.1 * jax.random.normal(next(keys), (L, 2, i, r)).at[:, 0].set(0)
        bank[f"{w}_b"] = 0.1 * jax.random.normal(next(keys), (L, 2, r, o)).at[:, 0].set(0)
    merged = dict(params, layers=dict(params["layers"]))
    for w in dims:
        delta = jnp.einsum("lir,lro->lio", bank[f"{w}_a"][:, 1], bank[f"{w}_b"][:, 1])
        merged["layers"][w] = params["layers"][w] + delta.reshape(params["layers"][w].shape)
    dense = np.asarray(llama.forward_train(merged, cfg, inv_freq, jnp.asarray(SEQ[:11])[None])[0])
    one = jnp.array([0.0, 1.0])

    pt = jnp.array([1, 2, 0, 0], jnp.int32)
    kc, vc = _empty_cache(cfg)
    solo, kc, vc = llama.forward_prefill(
        params, cfg, inv_freq, jnp.asarray(SEQ[:10]), jnp.int32(0), jnp.int32(10), kc, vc,
        pt, lora=bank, lora_gates=one)
    np.testing.assert_allclose(np.asarray(solo), dense[9], atol=2e-5)
    hk, hv = _side(cfg, 1, 1)
    col, _, _ = llama.forward_decode_horizon(
        params, cfg, inv_freq, jnp.asarray(SEQ[10:11]), jnp.array([10]), jnp.array([10]),
        jnp.int32(0), kc, vc, pt[None], hk, hv, lora=bank, lora_gates=one[None])
    np.testing.assert_allclose(np.asarray(col[0]), dense[10], atol=2e-5)
    kc, vc = _empty_cache(cfg)
    grouped, _, _ = llama.forward_prefill_batched(
        params, cfg, inv_freq, jnp.asarray(SEQ[:10])[None], jnp.zeros(1, jnp.int32),
        jnp.array([10]), kc, vc, pt[None], no_ctx=True, lora=bank, lora_gates=one[None])
    np.testing.assert_allclose(np.asarray(grouped[0]), dense[9], atol=2e-5)


def test_gqa_and_mha_configs():
    for kv in (1, 2, 8):
        cfg = dataclasses.replace(tiny_test_config(), num_kv_heads=kv, num_layers=2)
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, None))
        out = llama.forward_train(params, cfg, inv_freq, jnp.ones((2, 6), jnp.int32))
        assert out.shape == (2, 6, cfg.vocab_size)


def test_llama3_rope_scaling_monotone():
    from smg_tpu.ops.rope import rope_frequencies as rf

    plain = rf(64, 500000.0, None)
    scaled = rf(
        64,
        500000.0,
        {"rope_type": "llama3", "factor": 32.0, "low_freq_factor": 1.0,
         "high_freq_factor": 4.0, "original_max_position_embeddings": 8192},
    )
    assert plain.shape == scaled.shape == (32,)
    assert (scaled <= plain + 1e-9).all()
    assert scaled[-1] < plain[-1]  # low-frequency tail actually scaled down


def test_moe_forward_and_serving():
    """Qwen-MoE family: dense-dispatch MoE MLP through train + serving paths."""
    from smg_tpu.models.config import tiny_moe_config

    cfg = tiny_moe_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["router"].shape == (4, 128, 4)
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, None))
    out = llama.forward_train(params, cfg, inv_freq, jnp.ones((2, 6), jnp.int32))
    assert out.shape == (2, 6, cfg.vocab_size)
    assert bool(jnp.isfinite(out).all())
    # paged serving path must match the dense forward, same as the dense model
    kc, vc = _empty_cache(cfg)
    tokens = jnp.arange(5, 15, dtype=jnp.int32)
    pt = jnp.array([1, 2, 0, 0], jnp.int32)
    lo, kc, vc = llama.forward_prefill(
        params, cfg, inv_freq, tokens, jnp.int32(0), jnp.int32(10), kc, vc, pt
    )
    dense = llama.forward_train(params, cfg, inv_freq, tokens[None])
    np.testing.assert_allclose(np.asarray(lo), np.asarray(dense[0, -1]), atol=1e-4)


def test_moe_engine_e2e():
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import tiny_moe_config
    from smg_tpu.protocols.sampling import SamplingParams

    eng = Engine(EngineConfig(
        model=tiny_moe_config(),
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=4, max_seq_len=128, max_prefill_tokens=64,
            prefill_token_buckets=(32, 64), decode_batch_buckets=(4,),
        ),
        dtype="float32",
    ))
    res = eng.generate(
        prompt_ids=list(range(5, 25)),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True),
    )
    assert len(res.token_ids) == 6
