"""Olmo-Hybrid (``model_type: olmo_hybrid``): periods of gated-delta
linear-attention layers followed by one full-attention layer, norms placed as
OLMo 2/3 place them (``h = h + RMSNorm(sublayer(h))``), no rotary embedding in
the full layers unless the configuration gives a ``rope_theta``.  The program
serves it from ``smg_tpu/models/olmo_hybrid.py``, whose docstring has the
equations; this file is the one plain reference of them.

What an architecture file gives, and nothing else (README, "An architecture"):
``logits``, the plain reference (here with **the recurrence token by token**:
no chunked form, no kernel, no cache, no batching); ``impls`` and ``drive``,
the serving forward as ``reference.check_engine`` drives it, with the
per-sequence state slots next to the pages and controls of their own; the four
cost functions the ``kernels.*`` readers divide by; and ``linattn_state_bytes``
for this architecture's own reader.
"""

from __future__ import annotations

import math

VOCAB_BLOCK = 16384


# --------------------------------------------------------------------------
# the plain reference: ``jax.numpy`` in float32, matrix multiplications at
# ``highest`` precision, one sequence, one token at a time through the
# recurrence.  It reads the engine's own parameters a layer at a time.


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _mlp(h, w, eps):
    import jax

    y = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return h + _rms(y, w["mlp_post_norm"], eps)


def _linear_layer(h, w, *, heads, dk, dv, eps, neg_eigval):
    """One gated-delta layer over one sequence.  h [T, E], float32."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    K = w["conv"].shape[0]
    qkv = jnp.concatenate([jnp.einsum("te,ehd->thd", h, w[n]).reshape(T, -1)
                           for n in ("wq", "wk", "wv")], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(padded[i:i + T] * w["conv"][i] for i in range(K)))
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
    q, k, v = (x.reshape(T, heads, -1) for x in (q, k, v))
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    beta = jax.nn.sigmoid(h @ w["w_b"]) * (2.0 if neg_eigval else 1.0)  # [T, H]
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(h @ w["w_a"] + w["dt_bias"]))

    def token(S, x):  # S [H, dv, dk]
        q_t, k_t, v_t, a_t, b_t = x
        S = S * a_t[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hvk,hk->hv", S, k_t))
        S = S + u[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    gate = jax.nn.silu(jnp.einsum("te,ehd->thd", h, w["wg"]))
    y = jnp.einsum("thd,hde->te", _rms(o, w["o_norm"], eps) * gate, w["wo"])
    return _mlp(h + _rms(y, w["attn_post_norm"], eps), w, eps)


def _rope(x, pos, theta):
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _full_layer(h, w, *, eps, theta):
    """One full-attention layer over one sequence.  h [T, E], float32."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    pos = jnp.arange(T)
    q = jnp.einsum("te,ehd->thd", h, w["wq"])
    k = jnp.einsum("te,ekd->tkd", h, w["wk"])
    v = jnp.einsum("te,ekd->tkd", h, w["wv"])
    q = _rms(q.reshape(T, -1), w["q_norm"], eps).reshape(q.shape)
    k = _rms(k.reshape(T, -1), w["k_norm"], eps).reshape(k.shape)
    if theta:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    y = jnp.einsum("thd,hde->te", a, w["wo"])
    return _mlp(h + _rms(y, w["attn_post_norm"], eps), w, eps)


def _shape(hf: dict) -> dict:
    types = list(hf["layer_types"])
    n = types.index("full_attention")
    return {"linear_per_period": n, "periods": len(types) // (n + 1),
            "heads": hf["linear_num_value_heads"], "dk": hf["linear_key_head_dim"],
            "dv": hf["linear_value_head_dim"]}


def logits(params, hf: dict, tokens, rows):
    """Reference logits [len(rows), V] (numpy float32) of one sequence of
    token ids at the positions ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    f32 = jnp.float32
    sh = _shape(hf)
    eps = hf.get("rms_norm_eps", 1e-6)
    theta = (hf.get("rope_parameters") or {}).get("rope_theta")
    linear = jax.jit(partial(_linear_layer, heads=sh["heads"], dk=sh["dk"], dv=sh["dv"],
                             eps=eps, neg_eigval=bool(hf.get("linear_allow_neg_eigval"))))
    full = jax.jit(partial(_full_layer, eps=eps, theta=float(theta) if theta else None))
    up = lambda tree, *idx: {k: v[idx].astype(f32) for k, v in tree.items()}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for p in range(sh["periods"]):
            for i in range(sh["linear_per_period"]):
                h = linear(h, up(params["periods"]["lin"], p, i))
            h = full(h, up(params["periods"]["full"], p))
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), eps)
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
    """The implementations the runner's dispatch can pick: of the full layers'
    attention as for any model, and each with the form of the linear layers'
    decode step the runner serves (``linattn_impl``; its XLA form on the CPU,
    where the rehearsal also interprets the kernel)."""
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one
    implementation.  The state is the runner's own layout: the two paged
    caches of the full layers ``[periods, pages, page_size, kv_heads x
    head_dim]``, the two state pools (``[linear layers, slots, dk, heads x
    dv]`` float32 and the convolution's ``[linear layers, slots, 3 x
    channels]``), and while a frame runs the side buffers.  Sequence ``s``
    holds slot ``s + 1``; slot 0 is the garbage slot the padded rows name.
    Nothing is donated: a decode returns new pools and leaves the state it
    was given as it was."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import jax

        cfg, module, inv_freq = runner.model_cfg, runner.module, runner.inv_freq
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        if impl == "xla":
            lin = "xla"
        elif impl == "pallas":
            lin = runner.linattn_impl
        else:
            lin = "pallas_interpret" if runner.linattn_kernel_fits else "xla"
        self._prefill = jax.jit(lambda p, *a: module.forward_prefill(
            p, cfg, inv_freq, *a, attn_impl=impl))
        self._decode = jax.jit(lambda p, *a: module.forward_decode_horizon(
            p, cfg, inv_freq, *a, attn_impl=impl, linattn_impl=lin))

    def _zeros(self, *lead):
        import jax.numpy as jnp

        cfg, spec = self.runner.model_cfg, self.runner.spec
        kc = jnp.zeros((cfg.num_cache_layers, *lead, cfg.num_kv_heads * cfg.head_dim),
                       jnp.dtype(spec.dtype))
        return kc, jnp.zeros_like(kc)

    def empty(self, pages: int):
        """A fresh pool of ``pages`` pages (page 0 is the garbage page) and
        of one slot for each lane (slot 0 is the garbage slot)."""
        import jax.numpy as jnp

        s_shape, c_shape = self.runner.module.state_shapes(self.runner.model_cfg, self.lanes + 1)
        return {"cache": self._zeros(pages, self.runner.spec.page_size), "side": None,
                "slots": (jnp.zeros(s_shape, jnp.float32),
                          jnp.zeros(c_shape, self.runner.c_pool.dtype))}

    def prefill(self, state, seq, chunk, lo, n, table):
        """``n`` real tokens of the padded ``chunk`` at positions ``lo``..
        of sequence ``seq``, behind the prefix its pages and its slot hold;
        logits after the last real token."""
        import jax.numpy as jnp

        out, kc, vc, sp, cp = self._prefill(
            self.runner.params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            *state["cache"], jnp.asarray(table), *state["slots"], jnp.int32(seq + 1))
        return out, {**state, "cache": (kc, vc), "slots": (sp, cp)}

    def decode(self, state, tokens, positions, entry, column, page_tables):
        """Column ``column`` of a frame that entered at ``entry`` tokens a
        lane; column 0 starts the frame with empty side buffers.  Row ``s``
        of the sequences reads and writes slot ``s + 1`` (a control's state
        names others under ``decode_slots``); padded rows sit past the table
        and name slot 0.  Logits ``[lanes, V]``."""
        import jax.numpy as jnp
        import numpy as np

        side = self._zeros(self.lanes, self.horizon) if column == 0 else state["side"]
        live = np.asarray(entry) < page_tables.shape[1] * self.runner.spec.page_size
        slots = state.get("decode_slots")
        if slots is None:
            slots = np.where(live, np.arange(self.lanes) + 1, 0)
        out, hk, hv, sp, cp = self._decode(
            self.runner.params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(entry),
            jnp.int32(column), *state["cache"], jnp.asarray(page_tables), *side,
            *state["slots"], jnp.asarray(slots, jnp.int32), jnp.asarray(live))
        return out, {**state, "side": (hk, hv), "slots": (sp, cp)}

    def controls(self, state) -> dict:
        """Two broken states, each of which must miss the tolerance as the
        wrong page does: sequence 0 decoding from sequence 1's slot, and
        sequence 0 decoding from a zeroed slot."""
        import jax.numpy as jnp
        import numpy as np

        swapped = np.zeros(self.lanes, np.int32)
        swapped[:2] = (2, 1)
        sp, cp = state["slots"]
        return {
            "other_sequences_slot": {**state, "decode_slots": swapped},
            "zeroed_slot": {**state, "slots": (sp.at[:, 1].set(0.0), cp.at[:, 1].set(0))},
        }


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    E, F, V = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    H = hf["num_attention_heads"]
    K = hf.get("num_key_value_heads") or H
    D = hf.get("head_dim") or E // H
    sh = _shape(hf)
    mlp = 3 * E * F
    lin_proj = E * sh["heads"] * (2 * sh["dk"] + 3 * sh["dv"]) + 2 * E * sh["heads"]
    return {**sh, "E": E, "V": V, "H": H, "K": K, "D": D,
            "linear": lin_proj + mlp, "full": 2 * E * H * D + 2 * E * K * D + mlp,
            "n_linear": sh["periods"] * sh["linear_per_period"]}


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms, the convolution's taps
    and the per-head gates are under a tenth of a percent and left out)."""
    w = _widths(hf)
    layers = w["n_linear"] * w["linear"] + w["periods"] * w["full"]
    embed = w["V"] * w["E"]
    head = 0 if hf.get("tie_word_embeddings") else embed
    return {"layers": layers, "embed": embed, "lm_head": head,
            "matmul": layers + w["V"] * w["E"], "total": layers + embed + head}


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """Only the full-attention layers hold keys and values."""
    w = _widths(hf)
    return 2 * w["periods"] * w["K"] * w["D"] * dtype_bytes


def linattn_state_bytes(hf: dict) -> int:
    """Bytes of recurrent state one sequence holds in one linear-attention
    layer: ``heads x dv x dk`` float32.  A decode column reads and writes it
    once for every live lane and layer."""
    w = _widths(hf)
    return w["heads"] * w["dv"] * w["dk"] * 4


def linear_layers(hf: dict) -> int:
    return _widths(hf)["n_linear"]


def attention_layers(hf: dict) -> int:
    """The full-attention layers, one a period: each runs the decode attention
    kernel once a column, which is how a trace counts the columns run."""
    return _widths(hf)["periods"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: every matmul parameter read
    once a column and the live lanes' cached keys and values (full layers
    only) once a column.  The recurrent state is **not** counted: this
    function is given no lane count (PERF.md 7.16), so the share built on it
    errs low; ``kernels.linattn_decode_roofline_share`` counts the state."""
    p = param_count(hf)
    weight_bytes = p["matmul"] * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    layer parameter and token; in the full layers attention's ``4 x heads x
    head_dim`` FLOPs for every (query, key) pair of the causal triangle; in
    the linear layers the recurrence's own products, which the chunked form
    cannot go under: reading the state for the output and writing the update
    into it, ``4 x heads x dk x dv`` FLOPs a token (the intra-chunk products
    are an implementation's choice and are not counted)."""
    w = _widths(hf)
    p = param_count(hf)
    flops = (2.0 * p["layers"] * new_tokens
             + 4.0 * w["H"] * w["D"] * w["periods"] * attn_pairs
             + 4.0 * w["heads"] * w["dk"] * w["dv"] * w["n_linear"] * new_tokens)
    return flops / (chips * peak["flops_per_s"])
