"""The Llama-family block, which every configuration without an
``architecture`` key is: pre-norm attention with grouped KV heads and
rotate-half rope, SwiGLU MLP, and the two published departures the
configuration itself switches: Qwen3's per-head RMSNorm on q and k before
rope, and tied or untied output embeddings.  The program serves it from
``smg_tpu/models/llama.py``.

What an architecture file gives, and nothing else (README, "An architecture"):
``logits``, the plain reference; ``impls`` and ``drive``, the serving forward
as ``reference.check_engine`` drives it; and the four cost functions the
``kernels.*`` readers divide by.
"""

from __future__ import annotations

import math
from functools import partial

VOCAB_BLOCK = 16384


# --------------------------------------------------------------------------
# the plain reference: straightforward ``jax.numpy`` in float32, no kernels,
# no cache, no batching tricks, matrix multiplications at ``highest``
# precision.  It reads the engine's own parameters and upcasts them one layer
# at a time (4 B parameters in float32 do not fit beside the engine); under a
# mesh the slices stay sharded as the engine sharded them.


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    """Rotate-half rope.  x [T, H, D], pos [T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(h, w, *, heads, kv_heads, eps, theta, qk_norm):
    """One decoder layer over one sequence.  h [T, E], float32."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    pos = jnp.arange(T)
    x = _rms(h, w["attn_norm"], eps)
    q = jnp.einsum("te,ehd->thd", x, w["wq"])
    k = jnp.einsum("te,ekd->tkd", x, w["wk"])
    v = jnp.einsum("te,ekd->tkd", x, w["wv"])
    if qk_norm:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    h = h + jnp.einsum("thd,hde->te", a, w["wo"])
    x = _rms(h, w["mlp_norm"], eps)
    gate = jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])
    return h + gate @ w["w_down"]


def logits(params, hf: dict, tokens, rows):
    """Reference logits [len(rows), V] (numpy float32) of one sequence of
    token ids at the positions ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if hf.get("rope_scaling") or hf.get("sliding_window"):
        raise NotImplementedError("the reference has no rope scaling and no window")
    f32 = jnp.float32
    heads = hf["num_attention_heads"]
    kw = dict(heads=heads, kv_heads=hf.get("num_key_value_heads", heads),
              eps=hf.get("rms_norm_eps", 1e-5), theta=float(hf.get("rope_theta", 10000.0)),
              qk_norm="qwen3" in hf["architectures"][0].lower())
    layer = jax.jit(partial(_layer, **kw))
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        n_layers = params["layers"]["wq"].shape[0]
        for l in range(n_layers):
            h = layer(h, {k: v[l].astype(f32) for k, v in params["layers"].items()})
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), kw["eps"])
        tied = "lm_head" not in params
        table = params["embed"] if tied else params["lm_head"]
        vocab = table.shape[0] if tied else table.shape[1]
        out = []
        for lo in range(0, vocab, VOCAB_BLOCK):
            blk = (table[lo:lo + VOCAB_BLOCK].astype(f32).T if tied
                   else table[:, lo:lo + VOCAB_BLOCK].astype(f32))
            out.append(np.asarray(h @ blk))
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------------------------
# the drive of the serving forward


def impls(runner, rehearsal: bool) -> list:
    """The attention implementations the runner's dispatch rule can pick."""
    cfg = runner.model_cfg
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal and runner.mesh is None and (cfg.num_kv_heads * cfg.head_dim) % 128 == 0:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one attention
    implementation.  The state is the runner's own layout: the two paged
    caches ``[layers, pages, page_size, kv_heads x head_dim]`` and, while a
    frame of ``horizon`` decode columns runs, its side buffers ``[layers,
    lanes, horizon, kv_heads x head_dim]`` (decode reads the caches and
    writes the side buffers only).  Nothing here is kept per sequence, so
    ``seq`` is ignored."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import jax

        cfg, module, inv_freq = runner.model_cfg, runner.module, runner.inv_freq
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        self._prefill = jax.jit(lambda p, *a, impl=impl: module.forward_prefill(
            p, cfg, inv_freq, *a, attn_impl=impl))
        # under a mesh that splits the cache's lanes the engine traces decode
        # with the per-head products: the check traces what the engine serves
        self._decode = jax.jit(lambda p, *a, impl=impl: module.forward_decode_horizon(
            p, cfg, inv_freq, *a, attn_impl=impl,
            kv_lanes_sharded=runner.kv_lanes_sharded))

    def _zeros(self, *lead):
        import jax.numpy as jnp

        cfg, spec = self.runner.model_cfg, self.runner.spec
        kc = jnp.zeros((cfg.num_layers, *lead, cfg.num_kv_heads * cfg.head_dim),
                       jnp.dtype(spec.dtype))
        return kc, jnp.zeros_like(kc)

    def empty(self, pages: int):
        """A fresh pool of ``pages`` pages (page 0 is the garbage page)."""
        return {"cache": self._zeros(pages, self.runner.spec.page_size), "side": None}

    def prefill(self, state, seq, chunk, lo, n, table):
        """``n`` real tokens of the padded ``chunk`` at positions ``lo``..
        behind the prefix the cache already holds; logits after the last
        real token."""
        import jax.numpy as jnp

        out, kc, vc = self._prefill(
            self.runner.params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            *state["cache"], jnp.asarray(table))
        return out, {**state, "cache": (kc, vc)}

    def decode(self, state, tokens, positions, entry, column, page_tables):
        """Column ``column`` of a frame that entered at ``entry`` tokens a
        lane; column 0 starts the frame with empty side buffers.  Logits
        ``[lanes, V]``."""
        import jax.numpy as jnp

        side = self._zeros(self.lanes, self.horizon) if column == 0 else state["side"]
        out, hk, hv = self._decode(
            self.runner.params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(entry),
            jnp.int32(column), *state["cache"], jnp.asarray(page_tables), *side)
        return out, {**state, "side": (hk, hv)}


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.
# These are the least a launch could do, not what the program does: padding,
# recomputed tiles and gathered-but-unused cache slots do not count, so a
# share of the roofline built on them cannot pass 100%.


def param_count(hf: dict) -> dict:
    """Parameters by role, from the model's config.json."""
    E, F, L = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"]
    H = hf["num_attention_heads"]
    K = hf.get("num_key_value_heads", H)
    D = hf.get("head_dim") or E // H
    V = hf["vocab_size"]
    layer = E * H * D + 2 * E * K * D + H * D * E + 3 * E * F
    embed = V * E
    head = 0 if hf.get("tie_word_embeddings") else V * E
    return {"layers": L * layer, "embed": embed, "lm_head": head,
            # what one token's forward multiplies through: every layer and
            # the output head (the input embedding is a gather)
            "matmul": L * layer + V * E,
            "total": L * layer + embed + head}


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    H = hf["num_attention_heads"]
    K = hf.get("num_key_value_heads", H)
    D = hf.get("head_dim") or hf["hidden_size"] // H
    return 2 * hf["num_hidden_layers"] * K * D * dtype_bytes


def attention_layers(hf: dict) -> int:
    """Layers that hold keys and values: each runs the decode attention kernel
    once a column, which is how a trace counts the columns run."""
    return hf["num_hidden_layers"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns (one token for every live
    lane each): every matmul parameter is read once a column and the live
    lanes' cached keys and values once a column, spread over the chips.
    ``lane_tokens`` is the sum over the columns of the live context tokens."""
    p = param_count(hf)
    weight_bytes = p["matmul"] * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    matmul parameter and token, plus attention's 4 * heads * head_dim FLOPs
    for every (query, key) pair of the causal triangle (``attn_pairs``,
    summed over the requests), in every layer."""
    H = hf["num_attention_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    p = param_count(hf)
    # the output head runs for one position of each request, not for all:
    # leave it out, the share errs low by under a percent
    flops = 2.0 * p["layers"] * new_tokens + 4.0 * H * D * hf["num_hidden_layers"] * attn_pairs
    return flops / (chips * peak["flops_per_s"])
