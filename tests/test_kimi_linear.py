"""``models/kimi_linear.py`` (Kimi Delta Attention in the state slots beside a
latent cache, unrotated latent attention, sigmoid-routed experts on a chip's
share) on the CPU in float32, held to the plain reference
``benchmark/architectures/kimi_linear.py`` or to the recurrence: the serving
forwards through slots and latent pages, the per-channel chunked form against
the position-by-position recurrence, the decode kernel against its XLA form, the
share test of the model-configs guide, the loader, the cache plan, and the
engine through the scheduler."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from smg_tpu.models import get_model
from smg_tpu.models import kimi_linear as M
from smg_tpu.models.config import ModelConfig, tiny_kimi_linear_config
from smg_tpu.ops import linear_attention as LA
from smg_tpu.ops.latent_attention import entry_lanes
from smg_tpu.ops.pallas import linattn_decode as kernel
from smg_tpu.ops.rope import rope_frequencies

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark"))
import catalog  # noqa: E402

ARCH = catalog.architecture("kimi_linear")
PS, PAGES, MP, SLOTS = 16, 40, 16, 4
#: float32 against float32: the served path's own error is rounding; what a
#: fault must pass is a hundred times that
SOUND, BROKEN = 1e-4, 1e-2


def hf_of(cfg: ModelConfig) -> dict:
    """What the reference reads of a configuration."""
    kinds = cfg.layer_types
    return {"model_type": "kimi_linear", "hidden_size": cfg.hidden_size,
            "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads, "intermediate_size": cfg.intermediate_size,
            "linear_attn_config": {
                "kda_layers": [l + 1 for l, k in enumerate(kinds) if k == "kda"],
                "full_attn_layers": [l + 1 for l, k in enumerate(kinds) if k != "kda"],
                "head_dim": cfg.linear_key_head_dim, "num_heads": cfg.linear_num_heads,
                "short_conv_kernel_size": cfg.linear_conv_kernel_dim},
            "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_experts": cfg.held_experts[1], "router_num_experts": cfg.num_experts,
            "routed_expert_offset": cfg.held_experts[0],
            "num_experts_per_token": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "moe_renormalize": cfg.norm_topk_prob, "rms_norm_eps": cfg.rms_norm_eps}


def err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


class World:
    """A configuration, its random weights and the serving forwards over a
    fresh latent cache and fresh pools."""

    def __init__(self, cfg, key=0):
        self.cfg = cfg
        self.params = M.init_params(cfg, jax.random.PRNGKey(key))
        self.table = jnp.arange(1, MP + 1, dtype=jnp.int32)
        self.inv_freq = jnp.asarray(rope_frequencies(cfg.rope_dim, 10000.0, None))

    def empty(self):
        cfg = self.cfg
        W = entry_lanes(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        kc = jnp.zeros((cfg.num_cache_layers, PAGES, PS, W), jnp.float32)
        s_shape, c_shape = M.state_shapes(cfg, SLOTS)
        return (kc, jnp.zeros((cfg.num_cache_layers, 0, PS, 0), jnp.float32),
                jnp.zeros(s_shape, jnp.float32), jnp.zeros(c_shape, jnp.float32))

    def reference(self, toks, rows, cfg=None, params=None):
        return ARCH.logits(params or self.params, hf_of(cfg or self.cfg), toks, rows)

    def prefill(self, impl, chunk, lo, state, slot, T=64):
        padded = np.zeros(T, np.int32)
        padded[: len(chunk)] = chunk
        fn = jax.jit(lambda *a: M.forward_prefill(self.params, self.cfg, self.inv_freq, *a,
                                                  attn_impl=impl, moe_impl=impl))
        return fn(jnp.asarray(padded), jnp.int32(lo), jnp.int32(len(chunk)), *state[:2],
                  self.table, *state[2:], jnp.int32(slot))

    def decode(self, impl, state, tokens, entry, slots, columns, cfg=None):
        """``columns`` decode columns of one frame; the logits of each."""
        B, cfg = len(slots), cfg or self.cfg
        kc, vc, sp, cp = state
        side = jnp.zeros((cfg.num_cache_layers, B, columns, kc.shape[-1]), jnp.float32)
        tables = jnp.stack([self.table if s else jnp.zeros_like(self.table) for s in slots])
        fn = jax.jit(lambda *a: M.forward_decode_horizon(
            self.params, cfg, self.inv_freq, *a, attn_impl=impl, kda_impl=impl, moe_impl=impl))
        slots, entry = jnp.asarray(slots, jnp.int32), jnp.asarray(entry, jnp.int32)
        out = []
        for j in range(columns):
            logits, side, sp, cp, counts = fn(
                jnp.asarray(tokens[j], jnp.int32), entry + j, entry, jnp.int32(j), kc, vc,
                tables, side, sp, cp, slots, slots > 0)
            out.append((logits, counts))
        return out, (kc, vc, sp, cp)


@pytest.fixture(scope="module")
def world():
    w = World(tiny_kimi_linear_config(layers=12))  # period 0 written out, 1 and 2 one scan
    rng = np.random.default_rng(0)
    w.n, w.n_dec = 100, 4
    w.toks = rng.integers(2, w.cfg.vocab_size, size=w.n + w.n_dec).astype(np.int32)
    w.ref = w.reference(w.toks, list(range(w.n + w.n_dec)))
    return w


# --------------------------------------------------------------------------
# the forwards against the reference


@pytest.mark.parametrize("kinds,dense,scan", [
    ("kkkf" * 3, 1, (1, 2)), ("kkkf" * 2, 1, None), ("kkkfkkkfkkf", 1, None),
    ("kfkfkfkf", 0, (0, 3)), ("f", 0, None), ("kkfkkfkkf", 4, None)])
def test_the_dense_forward_is_the_reference_for_any_stack_of_periods(kinds, dense, scan):
    types = tuple("kda" if c == "k" else "full_attention" for c in kinds)
    cfg = tiny_kimi_linear_config(layers=len(kinds), layer_types=types,
                                  first_k_dense_replace=dense)
    assert M.layout(cfg)["scan"] == scan
    w = World(cfg)
    toks = np.random.default_rng(1).integers(2, 512, size=(1, 45)).astype(np.int32)
    got = M.forward_train(w.params, cfg, w.inv_freq, jnp.asarray(toks))[0]
    assert err(got, w.reference(toks[0], list(range(45)))) < SOUND


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("cut", [37, 64])
def test_two_chunks_then_decode_through_slots_and_latent_pages_is_one_full_forward(
        world, impl, cut):
    w = world
    logits, *state = w.prefill(impl, w.toks[:cut], 0, w.empty(), 2, T=64)
    assert err(logits, w.ref[cut - 1]) < SOUND
    logits, *state = w.prefill(impl, w.toks[cut:w.n], cut, state, 2, T=64)
    assert err(logits, w.ref[w.n - 1]) < SOUND
    assert state[1].size == 0  # no V buffer went through
    cols, after = w.decode(impl, state, [[t, 0] for t in w.toks[w.n:]], [w.n, MP * PS],
                           [2, 0], w.n_dec)
    for j, (logits, _) in enumerate(cols):
        assert err(logits[0], w.ref[w.n + j]) < SOUND
    # the padded lane named the garbage slot: it and every other slot are as they were
    for pool_after, pool in zip(after[2:], state[2:]):
        assert bool((pool_after[:, [0, 1, 3]] == pool[:, [0, 1, 3]]).all())
        assert not bool((pool_after[:, 2] == pool[:, 2]).all())


def test_a_grouped_prefill_is_its_rows_solo_and_a_padded_row_writes_the_garbage_slot(world):
    w = world
    rows = [w.toks[:50], w.toks[50:71]]
    tokens = np.zeros((4, 64), np.int32)
    for g, r in enumerate(rows):
        tokens[g, : len(r)] = r
    kc, vc, sp, cp = w.empty()
    tables = jnp.stack([w.table, w.table + MP, jnp.zeros_like(w.table), jnp.zeros_like(w.table)])
    logits, kc, vc, sp, cp = M.forward_prefill_batched(
        w.params, w.cfg, w.inv_freq, jnp.asarray(tokens), jnp.zeros(4, jnp.int32),
        jnp.asarray([50, 21, 0, 0], jnp.int32), kc, vc, tables, sp, cp,
        jnp.asarray([1, 3, 0, 0], jnp.int32), no_ctx=True)
    for g, r in enumerate(rows):
        assert err(logits[g], w.reference(r, [len(r) - 1])[0]) < SOUND
    assert not bool(sp[:, 0].any()) and not bool(sp[:, 2].any()) and bool(sp[:, 3].any())


def test_a_lane_on_the_garbage_slot_does_not_run_and_picks_no_expert(world):
    w = world
    _, *state = w.prefill("xla", w.toks[:40], 0, w.empty(), 1)
    (_, one), _ = w.decode("xla", state, [[w.toks[40], 7, 9]], [40, MP * PS, MP * PS],
                           [1, 0, 0], 1)[0][0], None
    experts = w.cfg.num_layers - w.cfg.first_k_dense_replace
    assert int(one[0]) == experts * w.cfg.num_experts_per_tok  # the picks of one lane


# --------------------------------------------------------------------------
# the recurrence: the chunked form, the step and the kernel


def recurrence(q, k, v, g, beta, S0):
    """Position by position: ``Diag(a_t)``, then the delta update."""
    S, out = S0, []
    for t in range(q.shape[1]):
        S = jnp.exp(g[:, t])[..., None] * S
        write = beta[:, t][..., None] * (v[:, t] - jnp.einsum(
            "ghkv,ghk->ghv", S, k[:, t], precision="highest"))
        S = S + k[:, t][..., :, None] * write[..., None, :]
        out.append(jnp.einsum("ghkv,ghk->ghv", S, q[:, t], precision="highest"))
    return jnp.stack(out, 1), S


def drawn(G, T, H=3, dk=8, dv=16, seed=0, strength=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (l2(jax.random.normal(ks[0], (G, T, H, dk))) * dk ** -0.5,
            l2(jax.random.normal(ks[1], (G, T, H, dk))), jax.random.normal(ks[2], (G, T, H, dv)),
            -strength * jax.random.uniform(ks[3], (G, T, H, dk)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (G, T, H))),
            jax.random.normal(ks[5], (G, H, dk, dv)))


@pytest.mark.parametrize("T,chunk,strength", [
    (37, 8, 1.0), (64, 16, 1.0), (5, 8, 1.0), (130, 64, 0.1),
    # a channel falls by more than e^-88 inside a chunk: by up to e^-640 here
    # (and inside one 16-row sub-block of a chunk of 64: by up to e^-128)
    (64, 16, 40.0), (70, 64, 8.0),
    # three and four whole chunks of four sub-blocks, the state carried between
    # them; a chunk of 48 is three sub-blocks and runs as one block
    (192, 64, 1.0), (256, 64, 8.0), (48, 64, 1.0)])
def test_the_per_channel_chunked_form_is_the_recurrence_from_a_carried_state(T, chunk, strength):
    q, k, v, g, beta, S0 = drawn(2, T, strength=strength)
    o, S = LA.kda_chunked(q, k, v, g, beta, S0, chunk=chunk)
    o2, S2 = recurrence(q, k, v, g, beta, S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    assert float(jnp.abs(o - o2).max()) < 2e-5 and float(jnp.abs(S - S2).max()) < 2e-5


@pytest.mark.parametrize("T,chunk", [(130, 64), (48, 64)])
def test_every_exponent_the_per_channel_form_takes_is_at_most_zero(monkeypatch, T, chunk):
    """What keeps ``kda_chunked`` inside float32 at any decay: the sub-blocks'
    factors about their first row (``exp(G_i - G_ref)``, ``exp(G_ref - G_j)``)
    are of differences ``<= 0`` as the diagonal blocks' weights are, so nothing
    overflows; a factor ``exp(-G_j)`` would pass e^88 at this strength."""
    args = drawn(2, T, strength=8.0)
    exponents, exp = [], jnp.exp

    def checked(x):
        jax.debug.callback(lambda m: exponents.append(float(np.max(m))), jnp.max(x))
        return exp(x)

    monkeypatch.setattr(jnp, "exp", checked)
    o, _ = LA.kda_chunked(*args, chunk=chunk)
    jax.block_until_ready(o)
    jax.effects_barrier()
    assert len(exponents) >= 5 and max(exponents) <= 0.0


def test_with_one_decay_a_head_it_is_the_gated_delta_rule_as_it_stands():
    q, k, v, g, beta, S0 = drawn(2, 50)
    gh = g[..., 0]
    o, S = LA.kda_chunked(q, k, v, jnp.broadcast_to(gh[..., None], g.shape), beta, S0, chunk=16)
    o2, S2 = LA.gated_delta_chunked(q, k, v, gh, beta, S0, chunk=16)
    assert float(jnp.abs(o - o2).max()) < 1e-5 and float(jnp.abs(S - S2).max()) < 1e-5


def test_padded_positions_of_a_chunk_write_nothing_and_decay_nothing():
    q, k, v, g, beta, S0 = drawn(1, 24)
    real = jnp.arange(24) < 13
    o, S = LA.kda_chunked(q, k, v, jnp.where(real[None, :, None, None], g, 0.0),
                          jnp.where(real[None, :, None], beta, 0.0), S0, chunk=8)
    o2, S2 = recurrence(q[:, :13], k[:, :13], v[:, :13], g[:, :13], beta[:, :13], S0)
    assert float(jnp.abs(o[:, :13] - o2).max()) < 1e-5 and float(jnp.abs(S - S2).max()) < 1e-5


@pytest.mark.parametrize("shape", [(4, 16, 32), (2, 8, 64), (8, 16, 16), (1, 128, 128)])
def test_the_decode_kernel_interpreted_is_its_xla_form_and_touches_its_lanes_slots_alone(shape):
    H, dk, dv = shape
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    B, S = 3, 6
    pool = jax.random.normal(ks[0], (2, S, dk, H * dv))
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (l2(jax.random.normal(ks[i], (B, H, dk))) for i in (1, 2))
    v = jax.random.normal(ks[3], (B, H, dv))
    alpha = jax.random.uniform(ks[4], (B, H, dk)).at[1].set(1.0)  # lane 1 does not run
    beta = jax.random.uniform(ks[5], (B, H)).at[1].set(0.0)
    slots = jnp.asarray([3, 0, 5])
    o, new = kernel.kda_decode(pool, 1, slots, q, k, v, alpha, beta, interpret=True)
    o2, new2 = LA.kda_step(pool, 1, slots, q, k, v, alpha, beta)
    assert float(jnp.abs(o - o2).max()) < 1e-4 and float(jnp.abs(new - new2).max()) < 1e-5
    for got in (new, new2):  # the garbage slot, the other slots, the other layer: bit for bit
        assert bool((got[1, [0, 1, 2, 4]] == pool[1, [0, 1, 2, 4]]).all())
        assert bool((got[0] == pool[0]).all())
        assert not bool((got[1, 3] == pool[1, 3]).all())


def test_the_kernel_fits_the_published_shape_and_says_where_it_does_not():
    assert kernel.heads_per_block(32, 128, 128) == 16  # a block of 1 MiB, two a lane and layer
    assert kernel.supported(32, 128, 128)
    assert not kernel.supported(4, 16, 16) and not kernel.supported(4, 12, 32)
    assert M.decode_step(tiny_kimi_linear_config()) == {
        "name": "kda_decode", "arg": "kda_impl", "layers": "KDA", "kernel_fits": True}
    with pytest.raises(ValueError, match="use the XLA form"):
        kernel.kda_decode(jnp.zeros((1, 2, 16, 64)), 0, jnp.zeros(1, jnp.int32),
                          jnp.zeros((1, 4, 16)), jnp.zeros((1, 4, 16)), jnp.zeros((1, 4, 16)),
                          jnp.ones((1, 4, 16)), jnp.zeros((1, 4)), interpret=True)


# --------------------------------------------------------------------------
# the experts: a chip's share


def moe_out(w: World, cfg, h):
    layer = jax.tree.map(lambda x: x[0], w.params["moe"])
    got, counts = M.moe_layer(h, layer, w.params["experts"], 0, cfg, jnp.ones(h.shape[:-1], bool),
                              "xla")
    return got - h, counts


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of eight shares (offset 0, 1/8, ..., 7/8 of the toy
    router's experts) plus the shared expert counted once are the uncut layer."""
    whole = tiny_kimi_linear_config()
    w = World(whole)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden_size)) * 0.02
    want, counts = moe_out(w, whole, h)
    layer = jax.tree.map(lambda x: x[0], w.params["moe"])
    shared = M.shared_expert(layer, M._norm(h, layer["norm"], whole), whole)
    total, held_picks = shared.astype(jnp.float32), 0
    X = whole.num_experts
    for s in range(8):
        first, n = s * X // 8, X // 8
        cfg = dataclasses.replace(whole, experts_held=(first, n))
        share = World(cfg)
        share.params = {**w.params, "experts": jax.tree.map(
            lambda x: x[:, first:first + n], w.params["experts"])}
        got, c = moe_out(share, cfg, h)
        total = total + (got - shared)
        held_picks += int(c[1])
    assert float(jnp.abs(total - want).max()) < 1e-5 * float(jnp.abs(want).max()) + 1e-7
    assert held_picks == int(counts[1]) == 24 * whole.num_experts_per_tok


def test_the_routers_read_lanes_that_only_the_embedding_writes():
    cfg = tiny_kimi_linear_config()
    p = M.init_params(cfg, jax.random.PRNGKey(0))
    RL = M.route_lanes(cfg.hidden_size)
    for out in (p["kda"]["wo"], p["mla"]["wo"], p["dense"]["w_down"], p["moe"]["ws_down"],
                p["experts"]["w_down"]):
        assert not bool(out[..., -RL:].any())
    RR = M.router_lanes(cfg.hidden_size)
    assert RR <= RL and not bool(p["moe"]["router"][:, :-RR].any())
    assert bool((p["moe"]["router"][:, -RR:] != 0).all())
    with pytest.raises(ValueError, match="random_weights names"):
        M.drawing(dataclasses.replace(cfg, random_init=(("loudness", 2.0),)))
    # the decays spread over (0, 1) channel by channel under the drawing a cell sets
    wide = dataclasses.replace(cfg, random_init=(("dt_max", 0.5), ("dt_min", 0.02)))
    k = M.init_params(wide, jax.random.PRNGKey(0))["kda"]
    a = jnp.exp(-jnp.exp(k["A_log"])[..., None] * jax.nn.softplus(
        k["dt_bias"].reshape(*k["A_log"].shape, -1)))
    assert float(a.min()) < 0.05 and float(a.max()) > 0.9
    assert float(jnp.std(a, axis=-1).mean()) > 0.15  # inside a head, not only between heads


# --------------------------------------------------------------------------
# the latent attention: unrotated


def test_unrotated_absorbed_decode_is_the_expanded_reference_and_not_the_rotated_form(world):
    w = world
    _, *state = w.prefill("xla", w.toks[:64], 0, w.empty(), 1)
    _, *state = w.prefill("xla", w.toks[64:w.n], 64, state, 1)
    (still, _), = w.decode("xla", state, [[w.toks[w.n]]], [w.n], [1], 1)[0]
    assert err(still[0], w.ref[w.n]) < SOUND
    rotated = dataclasses.replace(w.cfg, rope_theta=10000.0)
    (turned, _), = w.decode("xla", state, [[w.toks[w.n]]], [w.n], [1], 1, cfg=rotated)[0]
    assert err(turned[0], w.ref[w.n]) > BROKEN  # so the benchmark's control can fail


# --------------------------------------------------------------------------
# the loader


def catalog_row() -> dict:
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(path):
        for line in open(path):
            row = json.loads(line)
            if row["name"] == "Kimi-Linear-48B-A3B-Instruct":
                return dict(row["config"])
    # a checkout without the guide: the benchmark's configuration is the row
    # with four keys cut, and says what the source has for them
    cell = catalog.Cell(catalog.load_benchmark(), "kimi-linear-48b-a3b.reason")
    own = ("router_num_experts", "routed_expert_offset", "random_weights")
    published = {k: v for k, v in cell.config["published"].items() if not k.endswith("_note")}
    return {**{k: v for k, v in cell.hf_config.items() if k not in own}, **published}


def test_from_hf_config_reads_the_rows_own_keys_and_picks_the_module():
    cfg = ModelConfig.from_hf_config(catalog_row())
    assert cfg.arch == "kimi_linear" and get_model(cfg.arch) is M
    assert cfg.recurrent and cfg.latent_cache and not cfg.window_cache
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (27, 2304, 163840)
    kinds = cfg.layer_types
    assert kinds.count("kda") == 20 and kinds.count("full_attention") == 7 == cfg.num_cache_layers
    assert [l + 1 for l, k in enumerate(kinds) if k == "full_attention"] == [4, 8, 12, 16, 20, 24, 27]
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (32, 128, 128, 4)
    assert (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.q_lora_rank, cfg.head_dim) == (32, 128, 64, 128, 512, 0, 192)
    assert cfg.rope_theta == 0.0  # rope_theta 10000 is in the row and read by nothing
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok, cfg.n_shared_experts,
            cfg.moe_intermediate_size, cfg.intermediate_size, cfg.first_k_dense_replace) == (
        256, (0, 256), 8, 1, 1024, 9216, 1)
    assert cfg.moe_scoring == "sigmoid" and cfg.norm_topk_prob and cfg.moe_select_bias
    assert cfg.routed_scaling_factor == 2.446 and cfg.max_position_embeddings == 1048576
    # 27 layers: six whole periods, the last of two KDA layers; five are one scan
    plan = M.layout(cfg)
    assert plan["periods"][0] == (0, 3) and plan["periods"][-1] == (24, 2) and plan["scan"] == (1, 5)
    # the published count: 49.1 B in all
    total = ARCH.param_count(catalog_row())["total"]
    assert 49.0e9 < total < 49.2e9
    # one chip's share: 32 experts held of a router of 256
    cut = ModelConfig.from_hf_config({**catalog_row(), "num_experts": 32,
                                      "router_num_experts": 256, "routed_expert_offset": 64})
    assert cut.num_experts == 256 and cut.held_experts == (64, 32)


@pytest.mark.parametrize("change,needle", [
    ({"num_expert_group": 4, "topk_group": 2}, "group limit"),
    ({"mla_use_nope": False}, "mla_use_nope false"),
    ({"q_lora_rank": 1536}, "q_lora_rank 1536"),
    ({"linear_attn_config": {"kda_layers": [1, 2, 3], "full_attn_layers": [3, 4],
                             "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4},
      "num_hidden_layers": 4}, r"in both \[3\]"),
    ({"linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [4],
                             "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4},
      "num_hidden_layers": 4}, r"in neither \[3\]"),
    ({"linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                             "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4},
      "num_hidden_layers": 4}, "must end on a full_attention"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"moe_layer_freq": 2}, "moe_layer_freq 2"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"num_key_value_heads": 8}, "one latent for all heads"),
    ({"num_experts": 32, "router_num_experts": 256, "routed_expert_offset": 240}, "are not among"),
    ({"some_new_key": 1}, "does not consume"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrong(change, needle):
    with pytest.raises(ValueError, match=needle):
        ModelConfig.from_hf_config({**catalog_row(), **change})


def test_the_llama_loader_refuses_the_file_when_the_model_type_is_not_known():
    with pytest.raises(ValueError, match="kv_lora_rank"):
        ModelConfig.from_hf_config({**catalog_row(), "model_type": "kimi_linear_v2"})


# --------------------------------------------------------------------------
# the cache plan and the engine: ``RecurrentModelRunner`` with latent pages


def test_a_model_with_state_and_latent_pages_plans_both():
    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import create_kv_buffers, plan_recurrent_cache

    cut = {**catalog_row(), "num_hidden_layers": 12, "num_experts": 32, "router_num_experts": 256,
           "vocab_size": 20480}
    cut["linear_attn_config"] = {**cut["linear_attn_config"],
                                 "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
                                 "full_attn_layers": [4, 8, 12]}
    cfg = ModelConfig.from_hf_config(cut)
    limit, in_use = 16 * 10**9, int(6.4e9)
    work = M.prefill_workspace_bytes(cfg, 4096, "bfloat16")
    spec, state = plan_recurrent_cache(cfg, CacheConfig(), 72, M.state_shapes, limit, in_use, work)
    assert state.slot_bytes == 9 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)  # 19.54 MB
    assert spec.latent_lanes == 640 and spec.lanes == 640 and spec.num_layers == 3
    assert spec.bytes_per_page == 16 * 3 * 1280  # one buffer, no V
    want = (int(limit * 0.9) - in_use - 73 * state.slot_bytes - work) // spec.bytes_per_page
    assert spec.num_pages == want and 30_000 < spec.num_pages < 200_000
    small = dataclasses.replace(spec, num_pages=8)
    k, v = create_kv_buffers(small)
    assert k.shape == (3, 8, 16, 640) and v.size == 0
    # and a model with state beside K and V pages plans what it planned
    from smg_tpu.models import olmo_hybrid
    from smg_tpu.models.config import tiny_olmo_hybrid_config

    spec, _ = plan_recurrent_cache(tiny_olmo_hybrid_config(), CacheConfig(), 4,
                                   olmo_hybrid.state_shapes)
    assert spec.latent_lanes == 0 and spec.v_shape == spec.shape


def make_engine(num_pages=128, max_batch=8, max_seq_len=256, horizon=4, overlap=True,
                held=(4, 4), model=None, **sched_kw):
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.tokenizer import MockTokenizer

    return Engine(EngineConfig(
        model=model or tiny_kimi_linear_config(held=held), dtype="float32",
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


def test_the_engine_serves_it_through_slots_latent_pages_and_the_one_decode_frame(engine):
    from smg_tpu.engine.flight_recorder import MOE_STEP_RECORD_KEYS, STEP_RECORD_KEYS
    from smg_tpu.engine.recurrent_runner import RecurrentModelRunner
    from tests.test_recurrent_engine import greedy, prompts, run_all

    assert type(engine.runner) is RecurrentModelRunner
    runner = engine.runner
    assert runner.spec.num_layers == 2 and runner.spec.latent_lanes == 128
    assert runner.v_cache.size == 0 and runner.s_pool.shape[0] == 6
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
    # state slots and a latent cache with no V buffer in one engine
    assert loads["state_slots_total"] == 8 + 8 and loads["state_slots_in_use"] == 0
    # the tail's 6 rows of 128 take a whole float32 tile of 8
    assert loads["state_slot_bytes"] == 6 * (16 * 128 * 4 + 8 * 128 * 4)
    assert loads["kda_decode"] == "xla" and "linattn_decode" not in loads
    assert loads["latent_cache"]["entry_bytes_published"] == (64 + 16) * 4
    assert loads["latent_cache"]["entry_bytes_laid_out"] == 128 * 4
    assert "no V buffer" in loads["latent_cache"]["layout"]
    info = loads["moe"]
    assert (info["experts"], info["experts_held"], info["top_k"], info["impl"]) == (16, 4, 4, "xla")
    assert 0 < info["picks_held"] < info["picks"] and info["picks"] % 4 == 0
    ring = engine.scheduler.flight.snapshot("test")["ring"]
    decoded = [r for r in ring if "moe_picks_held" in r]
    assert decoded and all(STEP_RECORD_KEYS <= set(r) <= STEP_RECORD_KEYS | MOE_STEP_RECORD_KEYS
                           for r in ring)
    assert sum(r["moe_picks_held"] for r in decoded) == info["picks_held"]
    assert all(r["state_lanes"] > 0 for r in decoded)


def test_a_single_cold_row_pads_to_an_octave_and_another_modules_to_its_finest_rung(engine):
    """``OCTAVE_RUNGS_ONLY``: the one program of the ladder that XLA:TPU refuses
    at the published widths (one row of 1,536) is never asked for; a module
    without it keeps the rung (PR 42)."""
    from smg_tpu.models.config import tiny_olmo_hybrid_config

    wide = dict(max_seq_len=4096, max_prefill_tokens=4096,
                prefill_token_buckets=(64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096))

    def rung(model, n):
        from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
        from smg_tpu.engine.recurrent_runner import RecurrentModelRunner

        runner = RecurrentModelRunner(EngineConfig(
            model=model, dtype="float32",
            cache=CacheConfig(page_size=16, num_pages=16, auto_size=False, dtype="float32"),
            scheduler=SchedulerConfig(**wide)))
        return runner._prefill_rung([([0] * n, 0, None)])

    kimi, olmo = tiny_kimi_linear_config(), tiny_olmo_hybrid_config()
    assert [rung(kimi, n) for n in (1025, 1536, 2049, 3072)] == [2048, 2048, 4096, 4096]
    assert [rung(olmo, n) for n in (1025, 1536, 2049, 3072)] == [1536, 1536, 3072, 3072]
    assert rung(kimi, 1000) == rung(olmo, 1000) == 1024


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


def test_a_discarded_lookahead_runs_no_column_and_leaves_state_and_pages_as_they_were():
    """A stop token the host cannot foresee, with a lookahead in flight: the
    frame chained on the one that met it runs no column on the device
    (``frame_clean``), so the surviving lane's state and latent pages hold
    exactly its accepted tokens, and the discarded frame's picks are in no
    count."""
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
    # every accepted decode token is a lane-column that routed in seven layers
    assert loads["moe"]["picks"] == 7 * 4 * (len(cut) - 1 + 20 - 1)


def test_what_the_module_does_not_serve_is_refused_at_start():
    from smg_tpu.config.validation import ConfigError
    from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.weights import load_params
    from tests.test_recurrent_engine import greedy

    model = tiny_kimi_linear_config()
    cache = CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32")
    with pytest.raises(ConfigError, match="has no verify block"):
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
    with pytest.raises(ValueError, match="recurrent state is not in"):
        eng.runner.export_pages([1])
    with pytest.raises(ValueError, match="recurrent state"):
        eng.scheduler.prefill_only([1, 2, 3], greedy(1))
    assert set(M.SERVING_LIMITS) == {"speculative", "lora", "embeddings", "mesh",
                                     "kv_transfer", "checkpoint"}


def test_the_presets_are_registered_for_serve():
    from smg_tpu.models.config import PRESETS

    cfg = PRESETS["tiny-kimi-linear"]()
    assert cfg.arch == "kimi_linear" and cfg.recurrent and cfg.latent_cache
    assert cfg.num_cache_layers == 2
    whole = PRESETS["kimi-linear-48b-a3b"]()
    assert whole == ModelConfig.from_hf_config(catalog_row())
