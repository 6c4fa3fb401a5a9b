"""``models/olmo_hybrid.py`` and ``ops/linear_attention.py`` on the CPU at tiny
widths with seeded weights, against the one plain reference there is: the
architecture file ``benchmark/architectures/olmo_hybrid.py``, loaded by path as
``benchmark/tests`` load it (token-by-token recurrence, float32)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import get_model
from smg_tpu.models.config import ModelConfig, tiny_olmo_hybrid_config
from smg_tpu.ops import linear_attention as la
from smg_tpu.ops.pallas import linattn_decode as kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_arch():
    path = os.path.join(ROOT, "benchmark", "architectures", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("arch_olmo_hybrid", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def hf_of(cfg: ModelConfig) -> dict:
    return {
        "model_type": "olmo_hybrid", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "rms_norm_eps": cfg.rms_norm_eps,
        "layer_types": list(cfg.layer_types), "linear_num_key_heads": cfg.linear_num_heads,
        "linear_num_value_heads": cfg.linear_num_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim,
        "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
        "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval,
        "rope_parameters": {"rope_theta": None}, "tie_word_embeddings": False,
        "hidden_act": "silu", "attention_bias": False, "max_position_embeddings": 2048,
        "eos_token_id": 0, "bos_token_id": 1,
    }


ARCH = load_arch()
CFG = tiny_olmo_hybrid_config()
MODULE = get_model(CFG.arch)
PS, PAGES, MP, SLOTS = 16, 40, 16, 4


@pytest.fixture(scope="module")
def world():
    params = MODULE.init_params(CFG, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    n, n_dec = 100, 4
    toks = rng.integers(2, CFG.vocab_size, size=n + n_dec).astype(np.int32)
    ref = ARCH.logits(params, hf_of(CFG), toks, list(range(n - 1, n + n_dec)))
    return {"params": params, "toks": toks, "n": n, "n_dec": n_dec, "ref": ref}


def empty():
    kc = jnp.zeros((CFG.num_cache_layers, PAGES, PS, CFG.num_kv_heads * CFG.head_dim), jnp.float32)
    s_shape, c_shape = MODULE.state_shapes(CFG, SLOTS)
    return kc, kc, jnp.zeros(s_shape, jnp.float32), jnp.zeros(c_shape, jnp.float32)


INV = jnp.zeros(CFG.head_dim // 2)
TABLE = jnp.arange(1, MP + 1, dtype=jnp.int32)


def err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


def prefill(params, impl, chunk, lo, n, state, slot, T):
    padded = np.zeros(T, np.int32)
    padded[:n] = chunk
    fn = jax.jit(lambda *a: MODULE.forward_prefill(params, CFG, INV, *a, attn_impl=impl))
    return fn(jnp.asarray(padded), jnp.int32(lo), jnp.int32(n), *state[:2], TABLE, *state[2:],
              jnp.int32(slot))


def test_from_hf_config_reads_the_published_keys_and_picks_the_module():
    cfg = ModelConfig.from_hf_config(hf_of(CFG), dtype="float32")
    assert cfg == dataclasses.replace(CFG, eos_token_ids=(0,))
    assert cfg.arch == "olmo_hybrid" and cfg.recurrent and cfg.num_cache_layers == 2
    assert get_model(cfg.arch).__name__.endswith("olmo_hybrid")
    # a Llama config still goes where it went
    assert ModelConfig.from_hf_config({"architectures": ["LlamaForCausalLM"], "vocab_size": 8,
                                       "hidden_size": 8, "num_hidden_layers": 1,
                                       "num_attention_heads": 1}).arch == "llama"


@pytest.mark.parametrize("change,needle", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"linear_num_key_heads": 2}, "grouped value heads"),
    ({"layer_types": ["linear_attention", "full_attention", "full_attention"],
      "num_hidden_layers": 3}, "one period"),
    ({"layer_types": ["linear_attention"] * 6}, "both kinds"),
    ({"rope_parameters": {"rope_theta": None, "rope_type": "yarn"}}, "rope_parameters"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**hf_of(CFG), **change})


def test_registry_error_lists_what_is_registered():
    with pytest.raises(KeyError) as e:
        get_model("no_such_arch")
    assert "olmo_hybrid" in str(e.value) and "llama" in str(e.value)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_full_forward_matches_the_reference(world, impl):
    out = prefill(world["params"], impl, world["toks"][:100], 0, 100, empty(), 2, 128)
    assert err(out[0], world["ref"][0]) < 1e-3


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_chunks_then_decode_through_the_slots_matches_one_full_forward(world, impl):
    """Logits, not tokens: a chunk behind a live state, then four decode
    columns in a batch whose other rows are padding, against the reference's
    rows; the slot of another sequence and the garbage slot are left bit for
    bit (the padded rows and the lanes that do not run write nothing)."""
    p, toks, n = world["params"], world["toks"], world["n"]
    kc, vc, sp, cp = empty()
    sp, cp = sp + 7.0, cp + 3.0  # a slot holds whatever its last owner left
    _, kc, vc, sp, cp = prefill(p, impl, toks[:37], 0, 37, (kc, vc, sp, cp), 2, 64)
    lo, kc, vc, sp, cp = prefill(p, impl, toks[37:n], 37, n - 37, (kc, vc, sp, cp), 2, 64)
    assert err(lo, world["ref"][0]) < 1e-3
    assert bool(jnp.all(sp[:, 1] == 7.0)) and bool(jnp.all(cp[:, 1] == 3.0))
    B, N = 4, 4
    hk = jnp.zeros((CFG.num_cache_layers, B, N, CFG.num_kv_heads * CFG.head_dim), jnp.float32)
    hv = hk
    tabs = np.zeros((B, MP), np.int32)
    tabs[1] = np.asarray(TABLE)
    entry = np.full(B, MP * PS, np.int32)
    entry[1] = n
    slots, runs = np.array([0, 2, 0, 0], np.int32), np.array([False, True, False, False])
    step = jax.jit(lambda *a: MODULE.forward_decode_horizon(
        p, CFG, INV, *a, attn_impl=impl, linattn_impl=impl))
    sp0, cp0 = sp, cp
    for j in range(world["n_dec"]):
        cur = np.zeros(B, np.int32)
        cur[1] = toks[n + j]
        lg, hk, hv, sp, cp = step(jnp.asarray(cur), jnp.asarray(entry + j), jnp.asarray(entry),
                                  jnp.int32(j), kc, vc, jnp.asarray(tabs), hk, hv, sp, cp,
                                  jnp.asarray(slots), jnp.asarray(runs))
        assert err(lg[1], world["ref"][1 + j]) < 1e-3
    for pool, pool0 in ((sp, sp0), (cp, cp0)):
        assert bool(jnp.all(pool[:, 0] == pool0[:, 0])) and bool(jnp.all(pool[:, 1] == pool0[:, 1]))
        assert not bool(jnp.all(pool[:, 2] == pool0[:, 2]))


def test_a_masked_column_leaves_a_live_slot_bit_for_bit(world):
    p, toks = world["params"], world["toks"]
    _, kc, vc, sp, cp = prefill(p, "xla", toks[:50], 0, 50, empty(), 1, 64)
    B, N = 4, 2
    hk = jnp.zeros((CFG.num_cache_layers, B, N, CFG.num_kv_heads * CFG.head_dim), jnp.float32)
    tabs = np.tile(np.asarray(TABLE), (B, 1))
    out = MODULE.forward_decode_horizon(
        p, CFG, INV, jnp.full(B, 5, jnp.int32), jnp.full(B, 50, jnp.int32),
        jnp.full(B, 50, jnp.int32), jnp.int32(0), kc, vc, jnp.asarray(tabs), hk, hk, sp, cp,
        jnp.array([1, 0, 0, 0], jnp.int32), jnp.zeros(B, bool))  # the slot is named, the lane does not run
    assert bool(jnp.all(out[3] == sp)) and bool(jnp.all(out[4] == cp))


def test_padded_rows_of_a_group_leave_state_alone_and_a_fresh_row_starts_from_zero(world):
    p, toks = world["params"], world["toks"]
    G, T = 4, 64
    tokens = np.zeros((G, T), np.int32)
    tokens[0, :40], tokens[1, :25] = toks[:40], toks[40:65]
    t_reals = np.array([40, 25, 0, 0], np.int32)
    tabs = np.zeros((G, MP), np.int32)
    tabs[0, :3], tabs[1, :2] = (1, 2, 3), (4, 5)
    kc, vc, sp, cp = empty()
    dirty = (kc, vc, sp + 5.0, cp + 2.0)
    slots = jnp.array([1, 3, 0, 0], jnp.int32)
    fn = jax.jit(lambda *a: MODULE.forward_prefill_batched(p, CFG, INV, *a, no_ctx=True))
    args = (jnp.asarray(tokens), jnp.zeros(G, jnp.int32), jnp.asarray(t_reals))
    lg_d, _, _, sp_d, cp_d = fn(*args, *dirty[:2], jnp.asarray(tabs), *dirty[2:], slots)
    lg_c, _, _, sp_c, cp_c = fn(*args, kc, vc, jnp.asarray(tabs), sp, cp, slots)
    # what the slots held before does not reach a sequence that starts here
    assert bool(jnp.all(lg_d[:2] == lg_c[:2]))
    assert bool(jnp.all(sp_d[:, 1] == sp_c[:, 1])) and bool(jnp.all(sp_d[:, 3] == sp_c[:, 3]))
    # the slot nobody named keeps what it held; each row matches the reference
    assert bool(jnp.all(sp_d[:, 2] == 5.0)) and bool(jnp.all(cp_d[:, 2] == 2.0))
    hf = hf_of(CFG)
    assert err(lg_c[0], ARCH.logits(p, hf, toks[:40], [39])[0]) < 1e-3
    assert err(lg_c[1], ARCH.logits(p, hf, toks[40:65], [24])[0]) < 1e-3


def recurrence(q, k, v, g, beta, S0):
    """Token by token in float64 (``S`` as [G, H, dk, dv])."""
    S, out = S0.astype(np.float64), []
    for t in range(q.shape[1]):
        S = S * np.exp(g[:, t])[..., None, None]
        u = (v[:, t] - np.einsum("ghkv,ghk->ghv", S, k[:, t])) * beta[:, t][..., None]
        S = S + k[:, t][..., None] * u[..., None, :]
        out.append(np.einsum("ghkv,ghk->ghv", S, q[:, t]))
    return np.stack(out, 1), S


def row_at_a_time_inverse(A):
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., C, C] by forward
    substitution one row at a time (row i needs rows < i): what the chunked
    form ran until PR 52, 64 dependent steps a layer, and the reference its
    sub-block inverse is held to."""
    C = A.shape[-1]

    def row(i, T):
        a = jax.lax.dynamic_slice_in_dim(A, i, 1, axis=-2)
        new = -a - jnp.einsum("...ij,...jk->...ik", a, T, precision="highest")
        return jax.lax.dynamic_update_slice_in_dim(T, new, i, axis=-2)

    return jax.lax.fori_loop(0, C, row, jnp.zeros_like(A)) + jnp.eye(C, dtype=A.dtype)


# 48 is three sub-blocks and 8 is half of one: both run as one block
@pytest.mark.parametrize("C", [64, 48, 32, 16, 8])
def test_the_sub_block_inverse_is_the_row_at_a_time_one(C):
    """A chunk's system as the rule makes it: ``b_i (k_i . k_j)`` times the
    decay from j to i below the diagonal, ``b`` up to 2."""
    rng = np.random.default_rng(C)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    k = unit(rng.normal(size=(2, 3, C, 8)))
    gc = np.cumsum(-0.3 * np.abs(rng.normal(size=(2, 3, C))), axis=-1)
    beta = 2 / (1 + np.exp(-rng.normal(size=(2, 3, C, 1))))
    A = np.tril(beta * (k @ np.swapaxes(k, -1, -2)) * np.exp(gc[..., :, None] - gc[..., None, :]),
                -1).astype(np.float32)
    got = np.asarray(jax.jit(la._unit_lower_inverse)(A))
    exact = np.linalg.inv(np.eye(C) + A.astype(np.float64))
    scale = np.abs(exact).max()
    assert np.abs(got - np.asarray(row_at_a_time_inverse(jnp.asarray(A)))).max() < 2e-6 * scale
    assert np.abs(got - exact).max() < 2e-6 * scale
    assert np.array_equal(np.triu(got, 1), np.zeros_like(got))


# (192, 64): three whole chunks of four sub-blocks, the state carried between
# them; (100, 64): sub-blocks with padding; (48, 64): a chunk of 48, one block
@pytest.mark.parametrize("T,chunk", [(128, 64), (128, 32), (100, 64), (50, 7), (16, 64),
                                     (192, 64), (48, 64)])
def test_chunked_form_is_the_recurrence(T, chunk):
    rng = np.random.default_rng(T + chunk)
    G, H, dk, dv = 2, 3, 8, 16
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(rng.normal(size=(G, T, H, dk))) / np.sqrt(dk)).astype(np.float32)
    k = unit(rng.normal(size=(G, T, H, dk))).astype(np.float32)
    v = rng.normal(size=(G, T, H, dv)).astype(np.float32)
    g = (-0.3 * np.abs(rng.normal(size=(G, T, H)))).astype(np.float32)
    beta = (2 / (1 + np.exp(-rng.normal(size=(G, T, H))))).astype(np.float32)
    S0 = rng.normal(size=(G, H, dk, dv)).astype(np.float32)
    o, S = jax.jit(la.gated_delta_chunked, static_argnames="chunk")(q, k, v, g, beta, S0, chunk=chunk)
    o_ref, S_ref = recurrence(q, k, v, g, beta, S0)
    assert np.abs(o - o_ref).max() < 1e-5 and np.abs(S - S_ref).max() < 1e-5


def test_padded_tokens_of_a_chunk_write_nothing():
    rng = np.random.default_rng(3)
    G, T, H, dk, dv, real = 1, 64, 2, 8, 16, 41
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (unit(rng.normal(size=(G, T, H, dk))).astype(np.float32) for _ in range(2))
    v = rng.normal(size=(G, T, H, dv)).astype(np.float32)
    g = (-0.3 * np.abs(rng.normal(size=(G, T, H)))).astype(np.float32)
    beta = np.ones((G, T, H), np.float32)
    g[:, real:], beta[:, real:] = 0.0, 0.0
    S0 = np.zeros((G, H, dk, dv), np.float32)
    _, S = la.gated_delta_chunked(q, k, v, g, beta, S0)
    _, S_ref = recurrence(q[:, :real], k[:, :real], v[:, :real], g[:, :real], beta[:, :real], S0)
    assert np.abs(S - S_ref).max() < 1e-5


def test_convolution_tail_holds_the_last_real_inputs():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 16, 8)).astype(np.float32)
    tail = rng.normal(size=(2, 3, 8)).astype(np.float32)
    w = rng.normal(size=(4, 8)).astype(np.float32)
    y, new = la.causal_conv(x, tail, w, jnp.array([16, 5]))
    full = np.concatenate([tail, x], axis=1)
    want = sum(full[:, i:i + 16] * w[i] for i in range(4))
    assert np.allclose(y, want / (1 + np.exp(-want)), atol=1e-5)
    assert np.array_equal(new[0], x[0, 13:16]) and np.array_equal(new[1], x[1, 2:5])
    # one token at a time gives the same outputs and the same tail
    t = jnp.asarray(tail)
    for j in range(5):
        yj, t = la.conv_token(x[:, j], t, w)
        assert np.allclose(yj, y[:, j], atol=1e-5)
    assert np.array_equal(t[1], new[1])


def test_decode_kernel_in_interpret_mode_is_its_xla_form():
    rng = np.random.default_rng(5)
    H, dk, dv, B = 4, 16, 64, 3
    assert kernel.supported(H, dk, dv) and kernel.heads_per_block(30, 96, 192) == 10
    assert not kernel.supported(3, 16, 24)  # 72 lanes: no whole tile
    pool = rng.normal(size=(3, 4, dk, H * dv)).astype(np.float32)
    q = rng.normal(size=(B, H, dk)).astype(np.float32)
    k = rng.normal(size=(B, H, dk)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(B, H, dv)).astype(np.float32)
    alpha = rng.uniform(0.5, 1, size=(B, H)).astype(np.float32)
    beta = rng.uniform(0, 2, size=(B, H)).astype(np.float32)
    alpha[1], beta[1] = 1.0, 0.0  # a lane that does not run
    slots = np.array([2, 3, 1], np.int32)
    o1, p1 = la.gated_delta_step(jnp.asarray(pool), 1, slots, q, k, v, alpha, beta)
    o2, p2 = kernel.linattn_decode(jnp.asarray(pool), 1, slots, q, k, v, alpha, beta,
                                   interpret=True)
    assert np.abs(o1 - o2).max() < 1e-5 and np.abs(p1 - p2).max() < 1e-5
    for p in (np.asarray(p1), np.asarray(p2)):
        assert np.array_equal(p[1, 3], pool[1, 3])  # alpha 1, beta 0: bit for bit
        assert np.array_equal(p[0], pool[0]) and np.array_equal(p[1, 0], pool[1, 0])
        assert not np.array_equal(p[1, 2], pool[1, 2])


def test_decode_kernel_compiles_for_a_v5e_at_the_published_widths_in_place():
    """Mosaic and XLA:TPU compile for a topology they do not have: the kernel
    at 16 lanes of 30 heads (96, 192) over a pool of 72 slots, inside a loop as
    the layer scan carries it, with the pool aliased (no second copy)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        devs = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - no libtpu in this installation
        pytest.skip(f"no TPU topology without a chip: {e}")
    sh = SingleDeviceSharding(devs[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    B, H, dk, dv, f32 = 16, 30, 96, 192, jnp.float32

    def three_layers(pool, slots, q, k, v, a, b):
        def body(l, c):
            pool, acc = c
            o, pool = kernel.linattn_decode(pool, l, slots, q, k, v, a, b)
            return pool, acc + o
        return jax.lax.fori_loop(0, 3, body, (pool, jnp.zeros((B, H, dv), f32)))

    pool = S((12, 73, dk, H * dv), f32)
    compiled = jax.jit(three_layers, donate_argnums=0).lower(
        pool, S((B,), jnp.int32), S((B, H, dk), f32), S((B, H, dk), f32), S((B, H, dv), f32),
        S((B, H), f32), S((B, H), f32)).compile()
    mem = compiled.memory_analysis()
    pool_bytes = 12 * 73 * dk * H * dv * 4
    assert mem.alias_size_in_bytes >= pool_bytes          # updated where it lies
    assert mem.temp_size_in_bytes < pool_bytes // 20      # and not copied beside it
    assert mem.argument_size_in_bytes < pool_bytes * 1.01  # 96 x 5760: no tile padding
