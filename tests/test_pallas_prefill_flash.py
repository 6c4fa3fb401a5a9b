"""The online-softmax kernel of a cold grouped prefill
(``ops/pallas/flash_prefill.py``), interpreted on the CPU, against the XLA
form it stands in for (``ops.attention.attention_prefill_batched``), and the
engine with one and with the other.  Whether Mosaic takes the kernel for a
v5e is ``test_tpu_compile.py``'s business; what it costs there,
``scripts/time_prefill_attention.py``'s.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import ModelConfig, tiny_olmo_hybrid_config
from smg_tpu.models.registry import get_model
from smg_tpu.ops.attention import SCORE_BLOCK_BYTES, attention_prefill_batched
from smg_tpu.ops.pallas.flash_prefill import (
    BLOCK_K,
    BLOCK_Q,
    _block,
    flash_attention_prefill,
)
from smg_tpu.ops.rope import rope_frequencies
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer

D = 128
GQA, MHA = (16, 8), (30, 30)  # the heads of qwen3-1.7b and of olmo-hybrid-7b's full layers


def _both(heads, T, t_reals, blocks=(None, None), dtype=jnp.float32, seed=0):
    """(kernel, XLA form) on random rows of ``t_reals`` real tokens."""
    H, K = heads
    G = len(t_reals)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (G, T, H, D), dtype)
    k = jax.random.normal(kk, (G, T, K, D), dtype)
    v = jax.random.normal(kv, (G, T, K, D), dtype)
    t = jnp.asarray(t_reals, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T), (G, T))
    scale = D ** -0.5
    got = flash_attention_prefill(q, k, v, t, scale, interpret=True,
                                  block_q=blocks[0], block_k=blocks[1])
    want = attention_prefill_batched(q, k, v, pos, t, scale)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _check(got, want, T, t_reals, block_q, tol):
    """Every real query, and the padded queries of the block that holds a
    row's last token, agree; query blocks wholly past it are zeros."""
    bq = min(T, block_q or BLOCK_Q)
    for g, t_real in enumerate(t_reals):
        edge = -(-t_real // bq) * bq
        np.testing.assert_allclose(got[g, :edge], want[g, :edge], rtol=tol, atol=tol)
        assert not got[g, edge:].any()


@pytest.mark.parametrize("heads,T,t_reals,blocks", [
    (GQA, 256, [256], (None, None)),
    (GQA, 256, [1, 200], (64, 64)),
    (GQA, 256, [256, 129, 64, 0], (128, 128)),  # half a key block, a padded row
    (GQA, 256, [97, 0, 0, 0], (64, 128)),  # key blocks wider than query blocks
    (MHA, 256, [255, 0], (128, 64)),
    (MHA, 256, [31, 256, 130, 7], (None, None)),
    (GQA, 1024, [1024], (None, None)),
    (GQA, 1024, [513, 1000], (None, None)),
    (GQA, 1024, [1023, 2, 0, 600], (256, 512)),
    (MHA, 1024, [700], (None, None)),
], ids=["gqa-256-full", "gqa-256-ragged", "gqa-256-half-block-and-padded-row",
        "gqa-256-wide-key-blocks", "mha-256-padded-row", "mha-256-g4-ragged",
        "gqa-1024-full", "gqa-1024-g2-ragged", "gqa-1024-g4-padded-row", "mha-1024"])
def test_kernel_matches_the_xla_form(heads, T, t_reals, blocks):
    got, want = _both(heads, T, t_reals, blocks)
    _check(got, want, T, t_reals, blocks[0], 2e-5)


@pytest.mark.parametrize("t_reals", [[2048], [1500]], ids=["full", "ragged"])
def test_kernel_matches_the_xla_form_in_query_blocks(t_reals):
    """A bucket whose scores the XLA form sends through query blocks: 30 heads
    at 2,048 tokens are 480 MiB in float32, and the kernel walks two key
    blocks of its own size."""
    H, T = MHA[0], 2048
    assert T * H * T * 4 > SCORE_BLOCK_BYTES and T > BLOCK_K
    got, want = _both(MHA, T, t_reals)
    _check(got, want, T, t_reals, None, 2e-5)


def test_kernel_in_bfloat16_is_within_its_rounding():
    """Serving's dtype: bfloat16 operands, float32 accumulation and softmax
    statistics; the XLA form multiplies float32 probabilities, the kernel
    bfloat16 ones, so the two differ by the rounding of one output."""
    t_reals = [256, 100]
    got, want = _both(GQA, 256, t_reals, (128, 128), dtype=jnp.bfloat16)
    _check(got, want, 256, t_reals, 128, 2e-2)


# the latent models' per-head widths as published: keys of 128 lanes a head
# and 64 rotary lanes that the heads of a row share, values of 128
DN, DR, DV = 128, 64, 128


def _latent_both(H, T, t_reals, blocks=(None, None), dtype=jnp.float32, concat=False,
                 heads_first=True):
    """(kernel, ``latent_attention_prefill``) on random rows of ``t_reals`` real
    tokens; ``concat``: the rotary key written into every head's key, 256
    lanes a head against values of 128, and no shared operand;
    ``heads_first``: keys and values ``[G, H, T, d]``, as the models pass them."""
    from smg_tpu.ops.latent_attention import latent_attention_prefill

    G = len(t_reals)
    ks = jax.random.split(jax.random.PRNGKey(T + H), 5)
    q_nope = jax.random.normal(ks[0], (G, T, H, DN), dtype)
    q_pe = jax.random.normal(ks[1], (G, T, H, DR), dtype)
    k_nope = jax.random.normal(ks[2], (G, T, H, DN), dtype)
    k_pe = jax.random.normal(ks[3], (G, T, DR), dtype)
    v = jax.random.normal(ks[4], (G, T, H, DV), dtype)
    t = jnp.asarray(t_reals, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(T), (G, T))
    scale = (DN + DR) ** -0.5
    kw = dict(interpret=True, block_q=blocks[0], block_k=blocks[1])
    if concat:
        zeros = jnp.zeros((G, T, H, 256 - DN - DR), dtype)
        keys = jnp.broadcast_to(k_pe[:, :, None], (G, T, H, DR))
        got = flash_attention_prefill(jnp.concatenate([q_nope, q_pe, zeros], -1),
                                      jnp.concatenate([k_nope, keys, zeros], -1), v, t, scale,
                                      **kw)
    elif heads_first:
        got = flash_attention_prefill(q_nope, k_nope.swapaxes(1, 2), v.swapaxes(1, 2), t, scale,
                                      q_pe=q_pe, k_pe=k_pe, kv_heads_first=True, **kw)
    else:
        got = flash_attention_prefill(q_nope, k_nope, v, t, scale, q_pe=q_pe, k_pe=k_pe, **kw)
    want = latent_attention_prefill(q_nope, q_pe, k_nope, k_pe, v, pos, t, scale)
    assert got.shape == want.shape == (G, T, H, DV)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("H,T,t_reals,blocks", [
    (2, 256, [256], (None, None)),
    (3, 256, [256, 200, 1], (128, 64)),  # 200: inside a query block and inside a key block
    (2, 256, [97, 0], (64, 128)),  # a padded row; key blocks wider than query blocks
    (2, 64, [64, 33], (None, None)),  # a bucket below one block
    (2, 1536, [1536], (None, None)),  # the half-octave rung: blocks of 512
    (2, 1536, [1100, 0], (None, None)),
    (4, 1024, [1023, 514], (None, None)),  # queries in 512s, keys in one step of 1,024
], ids=["one-row", "g3-mid-blocks", "padded-row", "below-a-block", "1536-full",
        "1536-ragged-padded-row", "1024-g2"])
def test_kernel_with_a_shared_rotary_key_matches_the_latent_xla_form(H, T, t_reals, blocks):
    """A key wider than its value, the rotary part once a row, keys and values
    with the heads first: what ``ops.latent_attention.latent_attention_prefill``
    computes."""
    if T == 1536:
        assert (_block(T, BLOCK_Q), _block(T, BLOCK_K)) == (512, 512)
    got, want = _latent_both(H, T, t_reals, blocks)
    _check(got, want, T, t_reals, blocks[0], 2e-5)


@pytest.mark.parametrize("H,T,t_reals,blocks", [
    (3, 256, [256, 200, 1], (128, 64)),
    (2, 256, [97, 0], (64, 128)),
    (2, 64, [64, 33], (None, None)),
], ids=["g3-mid-blocks", "padded-row", "below-a-block"])
def test_kernel_with_a_shared_rotary_key_takes_flat_keys_and_values_too(H, T, t_reals, blocks):
    """The same with keys and values ``[G, T, H, d]``, as projections with the
    heads fused leave them (``scripts/time_prefill_attention.py`` times both)."""
    got, want = _latent_both(H, T, t_reals, blocks, heads_first=False)
    _check(got, want, T, t_reals, blocks[0], 2e-5)


def test_kernel_takes_keys_wider_than_values_without_a_shared_operand():
    """The other way to feed it the latent shapes (``scripts/
    time_prefill_attention.py --concat-keys`` times it): keys of 256 lanes a
    head, values of 128."""
    t_reals = [256, 130]
    got, want = _latent_both(2, 256, t_reals, (128, 128), concat=True)
    _check(got, want, 256, t_reals, 128, 2e-5)


def test_latent_kernel_in_bfloat16_is_within_its_rounding():
    t_reals = [256, 100]
    got, want = _latent_both(2, 256, t_reals, (128, 128), dtype=jnp.bfloat16)
    _check(got, want, 256, t_reals, 128, 2e-2)


def test_kernel_refuses_heads_it_cannot_slice():
    q = jnp.zeros((1, 64, 4, 64), jnp.float32)
    kv = jnp.zeros((1, 64, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="128-lane"):
        flash_attention_prefill(q, kv, kv, jnp.asarray([64], jnp.int32), 0.125, interpret=True)


# --------------------------------------------------------------------------
# through the models and the engine

CFG = ModelConfig(vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
                  num_heads=4, num_kv_heads=2, head_dim=D, rope_theta=10000.0,
                  max_position_embeddings=2048, eos_token_ids=(0,), bos_token_id=1,
                  dtype="float32")


def test_forward_prefill_batched_under_the_kernel_matches_xla():
    """``models/llama.forward_prefill_batched`` with ``no_ctx``: the same
    logits and the same pages under either form, a padded row in the group."""
    module = get_model(CFG.arch)
    params = module.init_params(CFG, jax.random.PRNGKey(0))
    inv_freq = jnp.asarray(rope_frequencies(CFG.head_dim, CFG.rope_theta, CFG.rope_scaling))
    G, T, P, ps, mp = 4, 64, 33, 16, 8
    t_reals = jnp.asarray([64, 17, 40, 0], jnp.int32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(2, CFG.vocab_size, (G, T)), jnp.int32)
    tables = jnp.asarray(1 + np.arange(G * mp).reshape(G, mp), jnp.int32)
    kc = jnp.zeros((CFG.num_layers, P, ps, CFG.num_kv_heads * CFG.head_dim), jnp.float32)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        out[impl] = module.forward_prefill_batched(
            params, CFG, inv_freq, tokens, jnp.zeros(G, jnp.int32), t_reals, kc, kc, tables,
            no_ctx=True, attn_impl=impl)
    (lx, kx, vx), (lp, kp, vp) = out["xla"], out["pallas_interpret"]
    np.testing.assert_allclose(np.asarray(lp[:3]), np.asarray(lx[:3]), rtol=2e-4, atol=2e-4)
    # page 0 takes the padded tokens' rows, which nothing reads
    np.testing.assert_allclose(np.asarray(kp[:, 1:]), np.asarray(kx[:, 1:]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vp[:, 1:]), np.asarray(vx[:, 1:]), atol=1e-5)


def test_hybrid_forward_prefill_batched_under_the_kernel_matches_xla():
    """``models/olmo_hybrid.forward_prefill_batched`` with ``no_ctx``: its
    full-attention layers under either form, the same logits, pages and
    recurrent state, a padded row in the group."""
    cfg = dataclasses.replace(tiny_olmo_hybrid_config(), num_heads=2, num_kv_heads=2,
                              head_dim=D, dtype="float32")
    module = get_model(cfg.arch)
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    G, T, P, ps, mp = 4, 64, 17, 16, 4
    t_reals = jnp.asarray([64, 0, 23, 50], jnp.int32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(2, cfg.vocab_size, (G, T)), jnp.int32)
    tables = jnp.asarray(1 + np.arange(G * mp).reshape(G, mp), jnp.int32)
    kc = jnp.zeros((cfg.num_cache_layers, P, ps, cfg.num_kv_heads * D), jnp.float32)
    s_shape, c_shape = module.state_shapes(cfg, G + 1)
    sp, cp = jnp.zeros(s_shape, jnp.float32), jnp.zeros(c_shape, jnp.float32)
    slots = jnp.asarray([1, 0, 2, 3], jnp.int32)
    out = {impl: module.forward_prefill_batched(
        params, cfg, jnp.zeros(D // 2), tokens, jnp.zeros(G, jnp.int32), t_reals, kc, kc,
        tables, sp, cp, slots, no_ctx=True, attn_impl=impl)
        for impl in ("xla", "pallas_interpret")}
    (lx, *pools_x), (lp, *pools_p) = out["xla"], out["pallas_interpret"]
    real = np.asarray(t_reals) > 0  # the padded row's logits are nobody's
    np.testing.assert_allclose(np.asarray(lp)[real], np.asarray(lx)[real], rtol=2e-4, atol=2e-4)
    # pages and slots from 1 on: page 0 and slot 0 take the padded rows'
    for x, k in zip(pools_x, pools_p):
        np.testing.assert_allclose(np.asarray(k[:, 1:]), np.asarray(x[:, 1:]), atol=2e-5)


def _engine(monkeypatch=None) -> Engine:
    cfg = EngineConfig(
        model=CFG,
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(max_batch_size=4, max_seq_len=256, max_prefill_tokens=256,
                                  prefill_token_buckets=(32, 64, 128, 256),
                                  decode_batch_buckets=(4,)),
        dtype="float32", attention_impl="xla")
    engine = Engine(cfg, tokenizer=MockTokenizer())
    if monkeypatch is not None:
        # the CPU has no Mosaic: the rule's kernel answer, interpreted
        monkeypatch.setattr(
            engine.runner, "_grouped_prefill_impl_for",
            lambda G, T, no_ctx: "pallas_interpret" if no_ctx else "xla")
    return engine


def _serve(engine: Engine, prompts) -> dict:
    done = {}
    for i, ids in enumerate(prompts):
        engine.submit(ids, SamplingParams(temperature=0.0, max_new_tokens=4, ignore_eos=True),
                      rid=f"r{i}")
    for _ in range(64):
        for out in engine.step():
            done.setdefault(out.rid, []).extend(out.new_token_ids)
        if len(done) == len(prompts) and not engine.scheduler.has_work():
            break
    return done


def test_engine_serves_the_same_under_the_kernel(monkeypatch):
    """Three cold prompts admitted together: the same tokens from the first
    on, the same pages, and the launches counted under ``pallas_prefill``."""
    prompts = [list(range(5, 5 + n)) for n in (100, 33, 64)]
    xla, kernel = _engine(), _engine(monkeypatch)
    want, got = _serve(xla, prompts), _serve(kernel, prompts)
    assert got == want and all(len(t) == 4 for t in got.values())
    lx = xla.loads()["attention"]["launches"]
    lk = kernel.loads()["attention"]["launches"]
    assert lx["pallas_prefill"] == 0 and lx["xla"] > 0
    assert lk["pallas_prefill"] >= 1
    # every page but the garbage page: the prompts' rows and the decoded ones
    for a, b in ((xla.runner.k_cache, kernel.runner.k_cache),
                 (xla.runner.v_cache, kernel.runner.v_cache)):
        np.testing.assert_allclose(np.asarray(b[:, 1:]), np.asarray(a[:, 1:]), atol=1e-5)
