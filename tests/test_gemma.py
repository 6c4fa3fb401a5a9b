"""Gemma-2 family support: gelu MLP, (1+w) RMSNorm, scaled embeddings,
post-attention/post-ffn norms, attention/final logit softcaps, custom query
scale.  Reference parity target: the Gemma-2 models the reference routes to
its engines (SURVEY §0 model families)."""

import numpy as np
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import ModelConfig, tiny_gemma2_config, tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer


def test_hf_config_parses_gemma2():
    cfg = ModelConfig.from_hf_config({
        "architectures": ["Gemma2ForCausalLM"],
        "vocab_size": 256000, "hidden_size": 2304, "intermediate_size": 9216,
        "num_hidden_layers": 26, "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 256,
        "query_pre_attn_scalar": 256, "sliding_window": 4096,
        "attn_logit_softcapping": 50.0, "final_logit_softcapping": 30.0,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    })
    assert cfg.activation == "gelu_tanh"
    assert cfg.rms_unit_offset and cfg.embed_scale and cfg.post_norms
    assert cfg.attn_logit_softcap == 50.0
    assert cfg.final_logit_softcap == 30.0
    assert cfg.query_scale == pytest.approx(1.0 / 16.0)
    assert cfg.sliding_window == 4096
    assert cfg.tie_word_embeddings is True
    # llama configs keep llama semantics
    base = tiny_test_config()
    assert base.activation == "silu" and not base.post_norms


def test_unit_offset_norm():
    import jax.numpy as jnp

    from smg_tpu.ops.norms import rms_norm

    x = jnp.asarray([[1.0, 2.0, 3.0]])
    w = jnp.asarray([0.5, 0.5, 0.5])
    plain = rms_norm(x, w, 1e-6)
    offset = rms_norm(x, w, 1e-6, unit_offset=True)
    np.testing.assert_allclose(np.asarray(offset), np.asarray(plain) * 3.0,
                               rtol=1e-5)
    # zero weight + unit offset = identity scale
    ident = rms_norm(x, jnp.zeros(3), 1e-6, unit_offset=True)
    norm_only = rms_norm(x, jnp.ones(3), 1e-6)
    np.testing.assert_allclose(np.asarray(ident), np.asarray(norm_only),
                               rtol=1e-6)


def test_attention_softcap_bounds_scores():
    import jax
    import jax.numpy as jnp

    from smg_tpu.ops.attention import attention_prefill

    T, K, G, D = 4, 2, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (T, K * G, D)) * 100
    k = jax.random.normal(jax.random.PRNGKey(1), (T, K, D)) * 100
    v = jax.random.normal(jax.random.PRNGKey(2), (T, K, D))
    pos = jnp.arange(T)
    out_plain = attention_prefill(q, k, v, pos, jnp.int32(T), 1.0)
    out_cap = attention_prefill(q, k, v, pos, jnp.int32(T), 1.0, softcap=5.0)
    # with huge logits the uncapped softmax saturates to one-hot; the capped
    # one cannot — outputs must differ
    assert not np.allclose(np.asarray(out_plain), np.asarray(out_cap), atol=1e-3)
    # softcap=None is exactly the plain path
    out_none = attention_prefill(q, k, v, pos, jnp.int32(T), 1.0, softcap=None)
    np.testing.assert_array_equal(np.asarray(out_plain), np.asarray(out_none))


def _gemma_engine() -> Engine:
    return Engine(EngineConfig(
        model=tiny_gemma2_config(),
        cache=CacheConfig(page_size=16, num_pages=128, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=4, max_seq_len=256, max_prefill_tokens=32,
            prefill_token_buckets=(16, 32), decode_batch_buckets=(2, 4),
        ),
        dtype="float32", model_id="tiny-gemma2",
    ), tokenizer=MockTokenizer())


def test_gemma2_generates_and_differs_from_llama():
    """Tiny Gemma-2 engine: deterministic generation; the family knobs
    measurably change the computation vs a same-seed llama config."""
    import threading

    def gen(eng, prompt, n=8):
        done = threading.Event()
        acc = []

        def cb(out):
            acc.extend(out.new_token_ids)
            if out.finished:
                done.set()

        eng.submit(prompt, SamplingParams(temperature=0.0, max_new_tokens=n,
                                          ignore_eos=True), on_output=cb)
        for _ in range(300):
            eng.step()
            if done.is_set():
                return list(acc)
        raise TimeoutError

    g = _gemma_engine()
    try:
        prompt = list(range(5, 25))
        a = gen(g, prompt)
        b = gen(g, prompt)
        assert a == b and len(a) == 8
        # chunked prefill path too
        long_prompt = [(i * 3) % 90 + 7 for i in range(50)]
        c = gen(g, long_prompt)
        assert len(c) == 8
        # post-norm params exist and loaded shapes match
        assert "post_attn_norm" in g.runner.params["layers"]
        assert "post_mlp_norm" in g.runner.params["layers"]
        # off the TPU the rule answers XLA at every shape (on one the decode
        # kernel, which has the softcap and the window, takes them all:
        # tests/test_tpu_compile.py::TestDispatchRule)
        assert g.runner._prefill_impl_for(64, 8) == "xla"
        for shape in [(64, 512), (16, 128), (16, 256)]:
            assert g.runner._attn_impl_for(*shape) == "xla"
    finally:
        g.stop()


def test_final_softcap_bounds_logits():
    import jax
    import jax.numpy as jnp

    from smg_tpu.models import llama

    cfg = tiny_gemma2_config()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (3, cfg.hidden_size)) * 50
    logits = llama.unembed(params, cfg, h)
    assert float(jnp.max(jnp.abs(logits))) <= cfg.final_logit_softcap + 1e-3


def test_sliding_window_validation():
    """Serving beyond the window is now supported (real per-layer masks);
    what stays rejected is ring/sp composition with windows or softcaps."""
    from smg_tpu.config import validate_engine_config
    from smg_tpu.engine.config import ParallelConfig

    def cfg(par):
        return EngineConfig(
            model=tiny_gemma2_config(),
            parallel=par,
            cache=CacheConfig(page_size=16, num_pages=64, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=2, max_seq_len=8192, max_prefill_tokens=32,
                prefill_token_buckets=(32,), decode_batch_buckets=(2,),
            ),
            dtype="float32",
        )

    # long max_seq_len over a windowed model: fine now
    assert not [i for i in validate_engine_config(cfg(ParallelConfig()))
                if "sliding" in i.message or "window" in i.message]
    issues = validate_engine_config(cfg(ParallelConfig(sp=2)))
    assert any("ring attention" in i.message for i in issues)


def test_gemma_weight_mapping_keys():
    from smg_tpu.models.weights import _hf_key_map

    m = _hf_key_map(tiny_gemma2_config(), 4)
    assert m[("layers", "mlp_norm")].endswith("pre_feedforward_layernorm.weight")
    assert m[("layers", "post_attn_norm")].endswith("post_attention_layernorm.weight")
    assert m[("layers", "post_mlp_norm")].endswith("post_feedforward_layernorm.weight")
    # llama mapping unchanged
    lm = _hf_key_map(tiny_test_config(), 4)
    assert lm[("layers", "mlp_norm")].endswith("post_attention_layernorm.weight")
    assert ("layers", "post_attn_norm") not in lm


def test_sliding_window_attention_masks():
    """Window masks vs a dense reference: only the last `window` keys (incl.
    self) attend; window<=0 means global."""
    import jax
    import jax.numpy as jnp

    from smg_tpu.ops.attention import attention_decode_cached, attention_prefill

    T, K, G, D = 8, 2, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (T, K * G, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (T, K, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (T, K, D))
    pos = jnp.arange(T)

    def dense_ref(window):
        qf = np.asarray(q, np.float64).reshape(T, K, G, D)
        kf, vf = np.asarray(k, np.float64), np.asarray(v, np.float64)
        scores = np.einsum("tkgd,skd->tkgs", qf, kf)
        j = np.arange(T)
        mask = j[None, :] <= np.arange(T)[:, None]
        if window:
            mask &= j[None, :] > np.arange(T)[:, None] - window
        scores = np.where(mask[:, None, None, :], scores, -1e30)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        return np.einsum("tkgs,skd->tkgd", p, vf).reshape(T, K * G, D)

    for w in (3, 5, None):
        got = attention_prefill(
            q, k, v, pos, jnp.int32(T), 1.0,
            window=None if w is None else jnp.int32(w),
        )
        np.testing.assert_allclose(np.asarray(got), dense_ref(w),
                                   rtol=1e-4, atol=1e-5)
    # window == 0 (traced "global") equals no window
    g0 = attention_prefill(q, k, v, pos, jnp.int32(T), 1.0, window=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(g0), dense_ref(None), rtol=1e-5)
    # the decode rows: lane t holds tokens 0..t-1 in the (shared) page and
    # token t in its side buffer, as a decode column does
    kc = jnp.zeros((1, 2, T, K * D)).at[0, 1].set(k.reshape(T, K * D))
    vc = jnp.zeros((1, 2, T, K * D)).at[0, 1].set(v.reshape(T, K * D))
    for w in (3, 5, 0):
        got = attention_decode_cached(
            q, kc, vc, k.reshape(T, 1, K * D), v.reshape(T, 1, K * D), jnp.int32(1),
            jnp.int32(0), jnp.ones((T, 1), jnp.int32), pos, 1.0, window=jnp.int32(w))
        np.testing.assert_allclose(np.asarray(got), dense_ref(w), rtol=1e-4, atol=1e-5)


def test_layer_window_alternation():
    import jax.numpy as jnp

    from smg_tpu.models.llama import _layer_window

    cfg = tiny_gemma2_config()  # pattern 2, window 4096
    w = [int(_layer_window(cfg, jnp.int32(l))) for l in range(4)]
    assert w == [4096, 0, 4096, 0]  # even sliding, odd global
    assert _layer_window(tiny_test_config(), jnp.int32(0)) is None


def test_sliding_window_serving_beyond_window():
    """Contexts LONGER than the window now serve (the v1 restriction is
    gone): outputs deterministic, and the windowed model differs from the
    same weights with the window disabled (locality is real)."""
    import dataclasses
    import threading

    def eng_for(window):
        model = dataclasses.replace(
            tiny_gemma2_config(), sliding_window=window,
            attn_logit_softcap=None, final_logit_softcap=None,
        )
        return Engine(EngineConfig(
            model=model,
            cache=CacheConfig(page_size=16, num_pages=128, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=2, max_seq_len=256, max_prefill_tokens=64,
                prefill_token_buckets=(32, 64), decode_batch_buckets=(2,),
            ),
            dtype="float32", model_id="tiny-sw",
        ), tokenizer=MockTokenizer())

    def gen(eng, prompt, n=6):
        done = threading.Event()
        acc = []

        def cb(out):
            acc.extend(out.new_token_ids)
            if out.finished:
                done.set()

        eng.submit(prompt, SamplingParams(temperature=0.0, max_new_tokens=n,
                                          ignore_eos=True), on_output=cb)
        for _ in range(300):
            eng.step()
            if done.is_set():
                return list(acc)
        raise TimeoutError

    prompt = [(i * 7) % 90 + 5 for i in range(100)]  # 100 > window 32
    win = eng_for(32)
    glob = eng_for(None)
    try:
        a = gen(win, prompt)
        b = gen(win, prompt)
        assert a == b and len(a) == 6
        c = gen(glob, prompt)
        # beyond-window context: locality must change the computation
        assert a != c
        # within-window prompt: window >= context behaves globally
        short = prompt[:20]
        np.testing.assert_array_equal(gen(win, short), gen(glob, short))
    finally:
        win.stop()
        glob.stop()


def test_train_embed_window_bounds():
    """train/embed paths bound contexts to the window at trace time (their
    shared layer body has no per-layer alternation)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from smg_tpu.models import llama
    from smg_tpu.ops.rope import rope_frequencies

    cfg = tiny_gemma2_config()  # window 4096: tiny T is fine
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    inv = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, None))
    out = llama.forward_embed(params, cfg, inv, jnp.ones((1, 8), jnp.int32),
                              jnp.asarray([8]))
    assert np.isfinite(np.asarray(out)).all()

    # training path bounds real lengths
    small = dataclasses.replace(cfg, sliding_window=4)
    with pytest.raises(ValueError, match="sliding_window"):
        llama.forward_train(params, small, inv,
                            jnp.ones((1, 8), jnp.int32))


def test_mistral_every_layer_window():
    import jax.numpy as jnp

    from smg_tpu.models.llama import _layer_window

    cfg = ModelConfig.from_hf_config({
        "architectures": ["MistralForCausalLM"],
        "vocab_size": 32000, "hidden_size": 4096, "intermediate_size": 14336,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 8, "sliding_window": 4096,
    })
    assert cfg.sliding_window == 4096
    assert cfg.sliding_window_pattern == 0  # every layer windowed
    assert cfg.activation == "silu"  # llama semantics otherwise
    for l in range(4):
        assert int(_layer_window(cfg, jnp.int32(l))) == 4096


def test_pp_rejects_alternating_windows():
    from smg_tpu.config import validate_engine_config
    from smg_tpu.engine.config import ParallelConfig

    cfg = EngineConfig(
        model=tiny_gemma2_config(),
        parallel=ParallelConfig(pp=2),
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=2, max_seq_len=128, max_prefill_tokens=32,
            prefill_token_buckets=(32,), decode_batch_buckets=(2,),
        ),
        dtype="float32",
    )
    issues = validate_engine_config(cfg)
    assert any("alternation" in i.message for i in issues)


def test_qwen3_qk_norm():
    """Qwen3 parses + applies per-head q/k RMSNorm (real Qwen3 checkpoints
    would silently be wrong without it)."""
    import dataclasses
    import threading

    import jax.numpy as jnp

    cfg = ModelConfig.from_hf_config({
        "architectures": ["Qwen3ForCausalLM"],
        "vocab_size": 1000, "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 16,
    })
    assert cfg.qk_norm is True
    cfg2 = ModelConfig.from_hf_config({
        "architectures": ["Qwen2ForCausalLM"],
        "vocab_size": 1000, "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 2,
    })
    assert cfg2.qk_norm is False
    moe = ModelConfig.from_hf_config({
        "architectures": ["Qwen3MoeForCausalLM"],
        "vocab_size": 1000, "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 8,
        "num_key_value_heads": 2, "num_experts": 4,
        "num_experts_per_tok": 2, "moe_intermediate_size": 64,
    })
    assert moe.qk_norm is True and moe.arch == "qwen_moe"

    from smg_tpu.models.weights import _hf_key_map

    m = _hf_key_map(dataclasses.replace(tiny_test_config(), qk_norm=True), 4)
    assert m[("layers", "q_norm")].endswith("self_attn.q_norm.weight")
    assert m[("layers", "k_norm")].endswith("self_attn.k_norm.weight")

    def gen(qk):
        eng = Engine(EngineConfig(
            model=dataclasses.replace(tiny_test_config(), qk_norm=qk),
            cache=CacheConfig(page_size=16, num_pages=64, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=2, max_seq_len=128, max_prefill_tokens=32,
                prefill_token_buckets=(32,), decode_batch_buckets=(2,),
            ),
            dtype="float32", model_id="tiny-q3",
        ), tokenizer=MockTokenizer())
        try:
            assert ("q_norm" in eng.runner.params["layers"]) == qk
            done = threading.Event()
            acc = []

            def cb(out):
                acc.extend(out.new_token_ids)
                if out.finished:
                    done.set()

            eng.submit(list(range(5, 25)),
                       SamplingParams(temperature=0.0, max_new_tokens=6,
                                      ignore_eos=True), on_output=cb)
            for _ in range(200):
                eng.step()
                if done.is_set():
                    return list(acc)
            raise TimeoutError
        finally:
            eng.stop()

    a, b = gen(True), gen(False)
    assert len(a) == 6 and len(b) == 6

    # logits-level oracle: the SAME weights with/without the q/k norm must
    # produce different prefill logits (rms rescaling changes attention)
    import jax

    from smg_tpu.models import llama
    from smg_tpu.ops.rope import rope_frequencies

    qcfg = dataclasses.replace(tiny_test_config(), qk_norm=True)
    params = llama.init_params(qcfg, jax.random.PRNGKey(0))
    inv = jnp.asarray(rope_frequencies(qcfg.head_dim, qcfg.rope_theta, None))
    kc = jnp.zeros((qcfg.num_layers, 8, 16,
                    qcfg.num_kv_heads * qcfg.head_dim), jnp.float32)
    toks = jnp.arange(5, 17, dtype=jnp.int32)
    pt = jnp.arange(1, 3, dtype=jnp.int32)
    lo_q, _, _ = llama.forward_prefill(
        params, qcfg, inv, toks, jnp.int32(0), jnp.int32(12),
        kc, jnp.zeros_like(kc), pt)
    # same params sans the norm application (identity weights exist either way)
    plain_cfg = dataclasses.replace(qcfg, qk_norm=False)
    lo_p, _, _ = llama.forward_prefill(
        params, plain_cfg, inv, toks, jnp.int32(0), jnp.int32(12),
        jnp.zeros_like(kc), jnp.zeros_like(kc), pt)
    assert not np.allclose(np.asarray(lo_q), np.asarray(lo_p), atol=1e-4)
