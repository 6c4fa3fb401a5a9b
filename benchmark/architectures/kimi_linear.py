"""Kimi-Linear (``model_type: kimi_linear``; Kimi-Linear-48B-A3B-Instruct): periods
of Kimi Delta Attention layers (a delta rule whose decay is a number a key
channel) closed by one latent attention layer whose shared key is not rotated,
a dense SwiGLU MLP in the first layer and sigmoid-routed experts beside one
shared expert in every other.  The program serves it from
``smg_tpu/models/kimi_linear.py``, whose docstring has the equations; this file
is the one plain reference of them.

What an architecture file gives, and nothing else (README, "An architecture"):
``logits``, the plain reference (here with **the recurrence written as the
recurrence**: one position after another, ``Diag(a_t)`` then the delta update,
no chunks, no WY form, no kernel; **latent attention expanded**: keys and values
rebuilt from ``c`` for every position, no absorption, no cache; **the experts as
a loop over the held range**, one at a time over all tokens; the vocabulary in
blocks); ``impls`` and ``drive``, the serving forward as
``reference.check_engine`` drives it, with the per-sequence state slots next to
the latent pages and controls of their own; the four cost functions the
``kernels.*`` readers divide by; and for this architecture's own readers
``kda_layers``, ``kda_lane_bytes``, ``kda_decode_min_seconds`` (the lanes that
ran are the reader's to give, the argument the contract's
``decode_min_seconds`` lacks), ``latent_entry_bytes`` and ``expert_bytes``.

**Departures from the published equations**: none known.  What the catalog
row's keys do not fix is read as ``benchmark/configs/kimi-linear-48b-a3b.json``
lists under ``assumed``.  The reference is given the chip's share as the
program is: ``num_experts`` experts from ``routed_expert_offset`` on, of the
``router_num_experts`` the router scores; a pick elsewhere adds nothing.
"""

from __future__ import annotations

import math

VOCAB_BLOCK = 16384
L2_EPS = 1e-6


# --------------------------------------------------------------------------
# the plain reference: ``jax.numpy`` in float32, matrix multiplications at
# ``highest`` precision, one sequence.  It reads the engine's own parameters a
# layer at a time.


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _kda_layer(h, w, *, heads, dk, dv, eps):
    """One KDA layer over one sequence from zero state.  h [T, E]."""
    import jax
    import jax.numpy as jnp

    T, H = h.shape[0], heads
    u = _rms(h, w["norm"], eps)
    qkv = u @ w["w_qkv"]
    K = w["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(padded[i:i + T] * w["conv"][i] for i in range(K)))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q, k, v = q.reshape(T, H, dk), k.reshape(T, H, dk), v.reshape(T, H, dv)
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    q, k = l2(q) / math.sqrt(dk), l2(k)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (u @ w["w_f1"]) @ w["w_f2"] + w["dt_bias"]).reshape(T, H, dk)
    a = jnp.exp(g)  # [T, H, dk]: a number a key channel
    beta = jax.nn.sigmoid(u @ w["w_b"])  # [T, H]

    def position(S, xs):  # S [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, :, None] * S  # Diag(a_t) first
        write = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * write[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, a, beta))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w["o_norm"]
    o = o * jax.nn.sigmoid(((u @ w["w_g1"]) @ w["w_g2"]).reshape(T, H, dv))
    return h + o.reshape(T, -1) @ w["wo"]


def _latent_layer(h, w, *, heads, dn, dr, eps):
    """One latent attention layer over one sequence, expanded and unrotated."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    pos = jnp.arange(T)
    u = _rms(h, w["norm"], eps)
    q_n = (u @ w["w_q_nope"].T).reshape(T, heads, dn)  # stored [out, in]
    q_r = jnp.einsum("te,dhe->thd", u, w["w_q_pe"])
    c = _rms(u @ w["w_dkv"], w["kv_norm"], eps)
    k_r = u @ w["w_dk_pe"]  # the key all heads share, as it comes
    k_n = jnp.einsum("tc,hcd->thd", c, w["w_uk"])
    v = jnp.einsum("tc,hcd->thd", c, w["w_uv"])
    s = (jnp.einsum("thd,shd->hts", q_n, k_n) + jnp.einsum("thd,sd->hts", q_r, k_r)) \
        / math.sqrt(dn + dr)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return h + out.reshape(T, -1) @ w["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _dense_layer(h, w, *, eps):
    return h + _swiglu(_rms(h, w["norm"], eps), w["w_gate"], w["w_up"], w["w_down"])


def _route(u, w, *, top_k, scale, renorm):
    """The picks [T, k] and their weights: float32 sigmoid over all outputs,
    the largest of score plus bias, weighed by the scores alone."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(u @ w["router"])
    _, picked = jax.lax.top_k(scores + w["select_bias"][None, :], top_k)
    weight = jnp.take_along_axis(scores, picked, axis=-1)
    if renorm:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return picked, weight * scale


def _expert(u, on, w_gate, w_up, w_down):
    import jax.numpy as jnp

    f32 = jnp.float32
    return on * _swiglu(u, w_gate.astype(f32), w_up.astype(f32), w_down.astype(f32))


def _moe_layer(h, w, experts, l: int, expert, *, first, top_k, scale, renorm, eps):
    """One expert layer over one sequence: the held experts one at a time
    over all tokens; the shared expert on every token, unweighted."""
    import jax.numpy as jnp

    u = _rms(h, w["norm"], eps)
    picked, weight = _route(u, w, top_k=top_k, scale=scale, renorm=renorm)
    out = _swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"])
    for e in range(experts["w_up"].shape[1]):
        on = jnp.sum(jnp.where(picked == first + e, weight, 0.0), axis=-1, keepdims=True)
        out = out + expert(u, on, *(experts[n][l, e] for n in ("w_gate", "w_up", "w_down")))
    return h + out


def _shape(hf: dict) -> dict:
    lin = hf["linear_attn_config"]
    layers = hf["num_hidden_layers"]
    kda = set(lin["kda_layers"])  # both lists count from 1
    if kda | set(lin["full_attn_layers"]) != set(range(1, layers + 1)) \
            or kda & set(lin["full_attn_layers"]):
        raise ValueError("kimi_linear reference: the two layer lists do not name every layer once")
    held = hf["num_experts"]
    return {"kinds": ["kda" if l in kda else "latent" for l in range(1, layers + 1)],
            "E": hf["hidden_size"], "V": hf["vocab_size"], "H": hf["num_attention_heads"],
            "dn": hf["qk_nope_head_dim"], "dr": hf["qk_rope_head_dim"], "dv": hf["v_head_dim"],
            "rkv": hf["kv_lora_rank"], "Hl": lin["num_heads"], "dk": lin["head_dim"],
            "taps": lin["short_conv_kernel_size"], "dense": hf.get("first_k_dense_replace", 0),
            "F": hf["intermediate_size"], "Fm": hf["moe_intermediate_size"],
            "X": hf.get("router_num_experts", held), "held": held,
            "first": hf.get("routed_expert_offset", 0), "top_k": hf["num_experts_per_token"],
            "scale": float(hf.get("routed_scaling_factor", 1.0)),
            "renorm": bool(hf.get("moe_renormalize", True)), "eps": hf.get("rms_norm_eps", 1e-5)}


def logits(params, hf: dict, tokens, rows):
    """Reference logits [len(rows), V] (numpy float32) of one sequence of
    token ids at the positions ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    f32 = jnp.float32
    sh = _shape(hf)
    eps = sh["eps"]
    # the row gives one ``head_dim`` for keys and values; a toy configuration
    # with values of another width says so in its weights
    kda = jax.jit(partial(_kda_layer, heads=sh["Hl"], dk=sh["dk"],
                          dv=params["kda"]["o_norm"].shape[-1], eps=eps))
    latent = jax.jit(partial(_latent_layer, heads=sh["H"], dn=sh["dn"], dr=sh["dr"], eps=eps))
    dense = jax.jit(partial(_dense_layer, eps=eps))
    expert = jax.jit(_expert)
    up = lambda tree, i: {k: v[i].astype(f32) for k, v in tree.items()}
    seen = {"kda": 0, "latent": 0}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for l, kind in enumerate(sh["kinds"]):
            i = seen[kind]
            seen[kind] += 1
            h = kda(h, up(params["kda"], i)) if kind == "kda" else latent(h, up(params["mla"], i))
            if l < sh["dense"]:
                h = dense(h, up(params["dense"], l))
            else:
                h = _moe_layer(h, up(params["moe"], l - sh["dense"]), params["experts"],
                               l - sh["dense"], expert, first=sh["first"], top_k=sh["top_k"],
                               scale=sh["scale"], renorm=sh["renorm"], eps=eps)
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), eps)
        head = params["lm_head"]
        out = [np.asarray(h @ head[:, lo:lo + VOCAB_BLOCK].astype(f32))
               for lo in range(0, head.shape[1], VOCAB_BLOCK)]
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------------------------
# the drive of the serving forward


def _rounded(x, mantissa_bits: int):
    """Float32 ``x`` rounded to nearest-even on the grid of ``mantissa_bits``
    explicit mantissa bits (7: bfloat16's), by whole-number arithmetic on the
    bits: inside one compiled program XLA for the TPU keeps the excess
    precision of a pair of conversions and rounds nothing
    (``architectures/nemotron_h._rounded``)."""
    import jax
    import jax.numpy as jnp

    drop = 23 - mantissa_bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(u & jnp.uint32((0xFFFFFFFF >> drop) << drop), jnp.float32)


def impls(runner, rehearsal: bool) -> list:
    """The implementations the runner's dispatch can pick: of the latent
    attention as for any model, each with a form of the KDA decode step and
    with the experts' grouped products the runner serves (``Drive``; on the
    CPU XLA's, and the rehearsal also interprets the kernels)."""
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one
    implementation.  The state is the runner's own layout: one latent cache
    ``[latent layers, pages, page_size, entry lanes]`` (the ``v_cache`` of zero
    size is made on the way in), the two state pools (``[KDA layers, slots, dk,
    heads x dv]`` float32 and the convolution's ``[KDA layers, slots, 3 x
    channels]``), and while a frame runs the one side buffer.  Sequence ``s``
    holds slot ``s + 1``; slot 0 is the garbage slot the padded rows name.  A
    control may put a broken ``decode`` program, other ``params`` or other
    ``decode_slots`` into the state, which the next step then runs with.
    Nothing is donated.  ``rounded_state_reading``: how far the first decode
    column's logits of sequence 0 move, in the row's own standard deviations,
    when the state it starts from is rounded to bfloat16 (a reading, not a
    control: ``PERF.md``, Findings, PR 50)."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import importlib

        import jax

        cfg, inv_freq = runner.model_cfg, runner.inv_freq
        # the module itself: a control serves it with one of its layers wrapped
        self.module = module = importlib.import_module("smg_tpu.models.kimi_linear")
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        self.tables = {}  # sequence -> the page table it was prefilled through
        # the experts' grouped products are the runner's own under both of its
        # attentions (XLA's ragged product over 32 experts a layer holds
        # gigabytes of temporaries beside a chip that is full); the KDA step
        # runs as its XLA form under "xla", the kernel's specification, and as
        # the runner serves it under "pallas"
        if impl == "xla":
            kda, moe = "xla", runner.moe_impl
        elif impl == "pallas":
            kda, moe = runner.state_impl, runner.moe_impl
        else:
            kda = moe = "pallas_interpret"
        self._prefill = jax.jit(lambda p, *a: module.forward_prefill(
            p, cfg, inv_freq, *a, attn_impl=impl, moe_impl=moe))
        self._column_under = lambda under: lambda p, *a: module.forward_decode_horizon(
            p, under, inv_freq, *a, attn_impl=impl, kda_impl=kda, moe_impl=moe)
        self._decode = jax.jit(self._column_under(cfg))
        self.rounded_state_reading = None

    def _zeros(self, *lead):
        import jax.numpy as jnp

        spec = self.runner.spec
        return jnp.zeros((spec.num_layers, *lead, spec.lanes), jnp.dtype(spec.dtype))

    def _no_v(self):
        import jax.numpy as jnp

        spec = self.runner.spec
        return jnp.zeros((spec.num_layers, 0, spec.page_size, 0), jnp.dtype(spec.dtype))

    def empty(self, pages: int):
        """A fresh pool of ``pages`` pages (page 0 is the garbage page) and
        of one slot for each lane (slot 0 is the garbage slot)."""
        import jax.numpy as jnp

        s_shape, c_shape = self.module.state_shapes(self.runner.model_cfg, self.lanes + 1)
        return {"cache": self._zeros(pages, self.runner.spec.page_size), "side": None,
                "slots": (jnp.zeros(s_shape, self.runner.s_pool.dtype),
                          jnp.zeros(c_shape, self.runner.c_pool.dtype))}

    def prefill(self, state, seq, chunk, lo, n, table):
        """``n`` real tokens of the padded ``chunk`` at positions ``lo``..
        of sequence ``seq``, behind the prefix its pages and its slot hold;
        logits after the last real token."""
        import jax.numpy as jnp

        self.tables[seq] = table
        out, cache, _v, sp, cp = self._prefill(
            self.runner.params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            state["cache"], self._no_v(), jnp.asarray(table), *state["slots"],
            jnp.int32(seq + 1))
        return out, {**state, "cache": cache, "slots": (sp, cp)}

    def _column(self, state, tokens, positions, entry, column, page_tables, side, slots, live):
        import jax.numpy as jnp

        decode, params = state.get("decode", self._decode), state.get("params", self.runner.params)
        return decode(
            params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(entry),
            jnp.int32(column), state["cache"], self._no_v(), jnp.asarray(page_tables), side,
            *state["slots"], jnp.asarray(slots, jnp.int32), jnp.asarray(live))

    def decode(self, state, tokens, positions, entry, column, page_tables):
        """Column ``column`` of a frame that entered at ``entry`` tokens a
        lane; column 0 starts the frame with an empty side buffer.  Row ``s``
        of the sequences reads and writes slot ``s + 1`` (a control's state
        names others under ``decode_slots``); padded rows sit past the table
        and name slot 0.  Logits ``[lanes, V]``."""
        import numpy as np

        side = self._zeros(self.lanes, self.horizon) if column == 0 else state["side"]
        live = np.asarray(entry) < page_tables.shape[1] * self.runner.spec.page_size
        slots = state.get("decode_slots")
        if slots is None:
            slots = np.where(live, np.arange(self.lanes) + 1, 0)
        args = (tokens, positions, entry, column, page_tables, side, slots, live)
        out, side, sp, cp, _counts = self._column(state, *args)
        if column == 0 and self.rounded_state_reading is None \
                and not state.keys() - {"cache", "side", "slots"}:
            # the sound state once more, held no finer than bfloat16
            coarse = {**state, "slots": (_rounded(state["slots"][0], 7), state["slots"][1])}
            row, other = np.asarray(out[0], np.float32), np.asarray(
                self._column(coarse, *args)[0][0], np.float32)
            self.rounded_state_reading = float(np.max(np.abs(other - row)) / np.std(row))
            print(f"bench: kimi_linear: the state rounded to bfloat16 moves the first decode "
                  f"row by {self.rounded_state_reading:.5f} of its deviation (a reading, no "
                  f"control)", flush=True)
        return out, {**state, "side": side, "slots": (sp, cp)}

    def _decode_with(self, wrap, under=None):
        """The decode program of another model: under the configuration
        ``under``, or traced with the module's ``kda_layer`` wrapped."""
        import jax

        M, column = self.module, self._column_under(under or self.runner.model_cfg)
        if wrap is None:
            return jax.jit(column)
        real = M.kda_layer

        def broken(*a):
            M.kda_layer = wrap(real)
            try:
                return column(*a)
            finally:
                M.kda_layer = real

        return jax.jit(broken)

    def controls(self, state) -> dict:
        """Broken states, each of which must miss the tolerance as the wrong
        page does.  Two break what sequence 0 holds beside its pages: it
        decodes from sequence 1's slot, and from its own slot with the
        convolution's last three inputs zeroed.  Two serve another model over
        the sound state: **the decay averaged over each head's channels** (the
        gated delta rule this model is not: every channel of a head forgets at
        the head's mean log-decay), and **the shared key rotated at its
        position** (the latent attention the repository had: the cached keys of
        sequence 0 and the column's own query and key turned by their
        positions, ``rope_theta`` as the row has it).  Two break the expert
        layers: the held experts give nothing (their selection bias at -1e4: no
        token is sent to an expert this chip holds), and the selection bias
        dropped (the picks by the scores alone)."""
        import dataclasses

        import jax.numpy as jnp
        import numpy as np

        from smg_tpu.ops.rope import apply_rope

        cfg = self.runner.model_cfg
        swapped = np.zeros(self.lanes, np.int32)
        swapped[:2] = (2, 1)
        sp, cp = state["slots"]
        params = self.runner.params
        first, count = cfg.held_experts
        bias = params["moe"]["select_bias"]
        with_bias = lambda b: {**params, "moe": {**params["moe"], "select_bias": b}}

        def averaged(kda_layer):
            def layer(h, w, c, mix):
                mean = lambda g: jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
                return kda_layer(h, w, c, lambda qkv, g, beta: mix(qkv, mean(g), beta))
            return layer

        # sequence 0's cached keys turned at their positions: page ``i`` of its
        # table holds positions ``i * page_size ..``
        rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        ps = self.runner.spec.page_size
        mine = jnp.asarray(self.tables[0])
        pos = (jnp.arange(mine.shape[0])[:, None] * ps + jnp.arange(ps)[None, :])
        keys = state["cache"][:, mine, :, rkv:rkv + dr]  # [L, pages, ps, dr]
        turned = apply_rope(keys[..., None, :].astype(jnp.float32),
                            jnp.broadcast_to(pos, keys.shape[:-1]),
                            self.runner.inv_freq)[..., 0, :]
        rotated = dataclasses.replace(cfg, rope_theta=float(cfg.rope_theta or 10000.0))
        return {
            "other_sequences_slot": {**state, "decode_slots": swapped},
            "conv_tail_zeroed": {**state, "slots": (sp, cp.at[:, 1].set(0))},
            "decay_averaged_over_channels": {**state, "decode": self._decode_with(averaged)},
            "shared_key_rotated": {
                **state, "decode": self._decode_with(None, rotated),
                "cache": state["cache"].at[:, mine, :, rkv:rkv + dr].set(
                    turned.astype(state["cache"].dtype))},
            "held_experts_give_nothing": {
                **state, "params": with_bias(bias.at[..., first:first + count].set(-1e4))},
            "selection_bias_dropped": {**state, "params": with_bias(jnp.zeros_like(bias))},
        }


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    s = _shape(hf)
    r = s["dk"]  # the low rank of the decay and of the output gate
    d_in = s["Hl"] * s["dk"]
    kinds = s["kinds"]
    layers = len(kinds)
    return {**s, "d_in": d_in, "conv": 3 * d_in, "layers": layers,
            "n_kda": kinds.count("kda"), "n_latent": kinds.count("latent"),
            "kda": s["E"] * 3 * d_in + 2 * (s["E"] * r + r * d_in) + s["E"] * s["Hl"] + d_in * s["E"],
            "latent": (s["E"] * s["H"] * (s["dn"] + s["dr"]) + s["E"] * (s["rkv"] + s["dr"])
                       + s["rkv"] * s["H"] * (s["dn"] + s["dv"]) + s["H"] * s["dv"] * s["E"]),
            "dense_mlp": 3 * s["E"] * s["F"], "expert": 3 * s["E"] * s["Fm"],
            "router": s["E"] * s["X"], "expert_layers": layers - s["dense"],
            "vocab": s["V"] * s["E"]}


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms, the convolution's taps
    and the per-head and per-channel vectors are under a tenth of a percent and
    left out).  ``always``: what every token passes whatever the routing (both
    mixers, the dense MLP, the routers, the shared experts); ``routed``: the
    held routed experts."""
    w = _widths(hf)
    always = (w["n_kda"] * w["kda"] + w["n_latent"] * w["latent"] + w["dense"] * w["dense_mlp"]
              + w["expert_layers"] * (w["router"] + w["expert"]))
    routed = w["expert_layers"] * w["held"] * w["expert"]
    head = 0 if hf.get("tie_word_embeddings") else w["vocab"]
    return {"always": always, "routed": routed, "layers": always + routed,
            "embed": w["vocab"], "lm_head": head, "matmul": always + routed + w["vocab"],
            "total": always + routed + w["vocab"] + head}


def latent_entry_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes one token leaves in the cache in one latent layer, as published:
    ``kv_lora_rank + qk_rope_head_dim`` numbers (the program lays them out on
    whole 128-lane tiles and reports both, ``loads()["latent_cache"]``)."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * dtype_bytes


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """Only the latent layers hold anything that grows with the context."""
    return _widths(hf)["n_latent"] * latent_entry_bytes(hf, dtype_bytes)


def mla_decode_flops_per_token(hf: dict) -> int:
    """FLOPs of absorbed decode attention for one cached token of one lane in
    one latent layer: every head's score over the entry and its weighted sum
    of the latent."""
    w = _widths(hf)
    return 2 * w["H"] * ((w["rkv"] + w["dr"]) + w["rkv"])


def kda_layers(hf: dict) -> int:
    return _widths(hf)["n_kda"]


def kda_lane_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Least bytes one KDA layer's decode step moves for one lane that runs:
    the state read and written (``heads x dk x dv`` float32 each way), the
    convolution's tail read and written, and the lane's rows of ``q``, ``k``,
    ``v`` (float32, out of the convolution), of the decay (a number a key
    channel) and of ``beta``."""
    w = _widths(hf)
    state = w["Hl"] * w["dk"] * w["dk"] * 4
    tail = (w["taps"] - 1) * w["conv"] * dtype_bytes
    return 2 * state + 2 * tail + 4 * (w["conv"] + w["d_in"] + w["Hl"])


def kda_decode_min_seconds(hf: dict, lane_columns: float, chips: int, peak: dict,
                           dtype_bytes: int = 2) -> float:
    """Least time of the KDA layers' decode steps for ``lane_columns`` lanes x
    columns that ran, every layer once each."""
    return (lane_columns * kda_layers(hf) * kda_lane_bytes(hf, dtype_bytes)
            / (chips * peak["bytes_per_s"]))


def expert_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return _widths(hf)["expert"] * dtype_bytes


def expert_flops_per_row(hf: dict) -> int:
    """FLOPs of one routed expert for one row."""
    return 2 * _widths(hf)["expert"]


def attention_layers(hf: dict) -> int:
    """The latent layers: each runs the decode attention kernel once a column,
    which is how a trace counts the columns run."""
    return _widths(hf)["n_latent"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: what every column reads
    whatever the routing and the lanes (both mixers' weights, the dense MLP,
    routers, shared experts, the head's slice) once a column, and the live
    lanes' latent entries of the latent layers.  **Neither the routed experts
    nor the recurrent state is counted**: this function is given neither the
    experts hit nor the lanes that ran (PERF.md 7.16), so the share built on it
    errs low by much (1.3 of a 64-lane column's 8.3 GB are counted);
    ``kernels.kda_moe_decode_roofline_share`` counts the experts hit and
    ``kernels.kda_decode_roofline_share`` the state of the lanes that ran."""
    p = param_count(hf)
    weight_bytes = (p["always"] + p["lm_head"]) * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


# the chunk ``ops.linear_attention.kda_chunked`` runs the recurrence in
KDA_CHUNK = 64


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    parameter every token passes whatever the routing; expanded latent
    attention's ``2 x heads x (dn + dr + dv)`` FLOPs for every (query, key)
    pair of the causal triangle; in the KDA layers the chunked form's
    operations at the chunk ``C`` the program uses, a head: a token's share of
    the chunk's two decayed products (``2 x 2 dk C / 2``: keys with keys and
    queries with keys, half a square each), of the triangular solve and its two
    products (``C^2 / 3 + 2 C (dk + dv) / 2``), of the output inside the chunk
    (``2 dv C / 2``), and its reading of and writing into the carried state
    (``6 dk dv``).  **The routed experts are left out** (no argument says how
    many rows were routed here), so the share errs low."""
    w = _widths(hf)
    p = param_count(hf)
    C, dk = KDA_CHUNK, w["dk"]
    kda = w["Hl"] * (2 * dk * C + C * C / 3 + 2 * C * dk + dk * C + 6 * dk * dk)
    flops = (2.0 * p["always"] * new_tokens
             + 2.0 * w["H"] * (w["dn"] + w["dr"] + w["dv"]) * w["n_latent"] * attn_pairs
             + kda * w["n_kda"] * new_tokens)
    return flops / (chips * peak["flops_per_s"])
