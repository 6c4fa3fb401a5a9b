"""Nemotron-H (``model_type: nemotron_h``; Nemotron 3 Super 120B-A12B): one mixer
or one feed-forward part a layer after a pre-norm, in the order of
``hybrid_override_pattern``: ``M`` a Mamba-2 state-space mixer, ``E`` routed
experts that work in a latent (ungated, squared ReLU) beside one shared expert
on the model's own width, ``*`` attention with no rotary embedding.  The
program serves it from ``smg_tpu/models/nemotron_h.py``, whose docstring has
the equations; this file is the one plain reference of them.

What an architecture file gives, and nothing else (README, "An architecture"):
``logits``, the plain reference (here with **the recurrence position by
position**: no chunks, no kernel, no cache, no batching; **the experts as a
loop over the held range**, one at a time over all tokens; the vocabulary in
blocks); ``impls`` and ``drive``, the serving forward as
``reference.check_engine`` drives it, with the per-sequence state slots next to
the pages, controls of their own and **the state held to the precision the
configuration states** (``STATE_COARSE_LIMIT``); the four cost functions the ``kernels.*``
readers divide by; and for this architecture's own readers ``ssm_layers``,
``ssm_lane_bytes`` (the lanes that ran are the reader's to give, the argument
the contract's ``decode_min_seconds`` lacks) and ``expert_bytes``.

**Departures from the published equations**: none known.  What the catalog
row's keys do not fix is read as ``benchmark/configs/nemotron-3-super-120b-a12b.json``
lists under ``assumed``.  The reference is given the chip's share as the
program is: ``n_routed_experts`` experts from ``routed_expert_offset`` on, of
the ``router_num_experts`` the router scores; a pick elsewhere adds nothing.
"""

from __future__ import annotations

import math

VOCAB_BLOCK = 16384
LETTERS = {"M": "mamba", "E": "moe", "*": "attn"}


# --------------------------------------------------------------------------
# the plain reference: ``jax.numpy`` in float32, matrix multiplications at
# ``highest`` precision, one sequence.  It reads the engine's own parameters a
# layer at a time.


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def _mamba_layer(h, w, *, heads, head_dim, state, groups, eps):
    """One Mamba-2 layer over one sequence from zero state.  h [T, E]."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    H, P, N, R = heads, head_dim, state, groups
    u = _rms(h, w["norm"], eps)
    z, xbc = u @ w["w_z"], u @ w["w_xbc"]
    dt = jax.nn.softplus(u @ w["w_dt"] + w["dt_bias"])  # [T, H]
    K = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(padded[i:i + T] * w["conv_w"][i] for i in range(K)) + w["conv_b"])
    x, B, C = jnp.split(xbc, [H * P, H * P + R * N], axis=-1)
    x, B, C = x.reshape(T, H, P), B.reshape(T, R, N), C.reshape(T, R, N)
    a = jnp.exp(-dt * jnp.exp(w["A_log"]))  # [T, H]
    of_head = jnp.arange(H) // (H // R)  # the group a head reads

    def position(S, xs):  # S [H, P, N]
        x_t, B_t, C_t, a_t, dt_t = xs
        S = a_t[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[of_head][:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t[of_head]) + w["D"][:, None] * x_t

    _, y = jax.lax.scan(position, jnp.zeros((H, P, N), jnp.float32), (x, B, C, a, dt))
    y = (y.reshape(T, -1) * jax.nn.silu(z)).reshape(T, R, -1)  # the gate first
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)  # then the norm a group
    return h + (y.reshape(T, -1) * w["gate_norm"]) @ w["w_out"]


def _attention_layer(h, w, *, head_dim, eps):
    """One attention layer over one sequence, no rotary embedding.  h [T, E]."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    pos = jnp.arange(T)
    u = _rms(h, w["norm"], eps)
    q, k, v = ((u @ w[n].T).reshape(T, -1, head_dim) for n in ("wq", "wk", "wv"))  # stored [out, in]
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return h + a.reshape(T, -1) @ w["wo"]


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


def _expert(c, on, w_up, w_down):
    import jax.numpy as jnp

    f32 = jnp.float32
    return on * (_relu2(c @ w_up.astype(f32)) @ w_down.astype(f32))


def _moe_layer(h, w, experts, l: int, expert, *, first, top_k, scale, renorm, eps):
    """One expert layer over one sequence: the held experts one at a time
    over all tokens, in the latent; the shared expert on the uncut input."""
    import jax.numpy as jnp

    u = _rms(h, w["norm"], eps)
    picked, weight = _route(u, w, top_k=top_k, scale=scale, renorm=renorm)
    c = u @ w["w_dl"]
    m = jnp.zeros_like(c)
    for e in range(experts["w_up"].shape[1]):
        on = jnp.sum(jnp.where(picked == first + e, weight, 0.0), axis=-1, keepdims=True)
        m = m + expert(c, on, experts["w_up"][l, e], experts["w_down"][l, e])
    return h + m @ w["w_ul"] + _relu2(u @ w["ws_up"]) @ w["ws_down"]


def _shape(hf: dict) -> dict:
    pattern = hf["hybrid_override_pattern"]
    strange = sorted(set(pattern) - set(LETTERS))
    if strange:
        raise ValueError(f"nemotron_h reference: pattern letters {strange} name no layer here")
    kinds = [LETTERS[c] for c in pattern]
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    held = hf["n_routed_experts"]
    return {"kinds": kinds, "n": {k: kinds.count(k) for k in LETTERS.values()},
            "E": E, "V": hf["vocab_size"], "H": H, "K": hf.get("num_key_value_heads") or H,
            "D": hf.get("head_dim") or E // H,
            "Hm": hf["mamba_num_heads"], "P": hf["mamba_head_dim"], "N": hf["ssm_state_size"],
            "R": hf["n_groups"], "taps": hf["conv_kernel"],
            "Z": hf["moe_latent_size"], "F": hf["moe_intermediate_size"],
            "Fs": hf["moe_shared_expert_intermediate_size"],
            "X": hf.get("router_num_experts", held), "held": held,
            "first": hf.get("routed_expert_offset", 0), "top_k": hf["num_experts_per_tok"],
            "scale": float(hf.get("routed_scaling_factor", 1.0)),
            "renorm": bool(hf.get("norm_topk_prob", True)), "eps": hf.get("norm_eps", 1e-5)}


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
    mamba = jax.jit(partial(_mamba_layer, heads=sh["Hm"], head_dim=sh["P"], state=sh["N"],
                            groups=sh["R"], eps=eps))
    attn = jax.jit(partial(_attention_layer, head_dim=sh["D"], eps=eps))
    expert = jax.jit(_expert)
    up = lambda tree, i: {k: v[i].astype(f32) for k, v in tree.items()}
    seen = dict.fromkeys(sh["n"], 0)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for kind in sh["kinds"]:
            i = seen[kind]
            seen[kind] += 1
            if kind == "mamba":
                h = mamba(h, up(params["mamba"], i))
            elif kind == "attn":
                h = attn(h, up(params["attn"], i))
            else:
                h = _moe_layer(h, up(params["moe"], i), params["experts"], i, expert,
                               first=sh["first"], top_k=sh["top_k"], scale=sh["scale"],
                               renorm=sh["renorm"], eps=eps)
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), eps)
        head = params["lm_head"]
        out = [np.asarray(h @ head[:, lo:lo + VOCAB_BLOCK].astype(f32))
               for lo in range(0, head.shape[1], VOCAB_BLOCK)]
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------------------------
# the drive of the serving forward


# The configuration states a float32 recurrent state (``assumed`` (f)), and the
# comparison of logits cannot hold the program to it: a state rounded to
# bfloat16 moves every element by at most 2^-9 of itself and the readout ``S C``
# is linear in it, so the logits move by thousandths of a row's deviation where
# ``reference.LOGIT_TOLERANCE`` allows 0.30 (on a v5e at the published widths
# within 0.01 of the sound row in 104 readings; PERF.md, Findings, PR 47).  Nor
# can a comparison against the reference's own float32 state: the inputs of the
# recurrence pass bfloat16 activations, which alone put the served state some
# 2e-3 of its size from the reference's, more than the rounding does (1e-3).
# What tells the two apart by four orders of magnitude is what they can hold:
# **the share of a slot's nonzero elements that bfloat16 holds exactly** is
# 2^-16 for a float32 state and 1 for one kept in bfloat16, whether the pool is
# that dtype or the step rounds.  The drive reads it off every state a decode
# column is handed (``Drive.coarse_shares``), and a lane whose share is over
# this limit decodes from an empty slot in its place (no state, no convolution
# tail: a state coarser than the configuration states is no state), the size of
# error a wrong slot gives: a program that kept the state in bfloat16 would fail every
# decode row, and the control ``state_in_bfloat16`` must fail as the others do.
STATE_COARSE_LIMIT = 0.5


def _rounded(x, mantissa_bits: int):
    """Float32 ``x`` rounded to nearest-even on the grid of ``mantissa_bits``
    explicit mantissa bits (7: bfloat16's), by whole-number arithmetic on the
    bits.  Not a pair of conversions: inside one compiled program XLA for the
    TPU keeps the excess precision of ``x.astype(bfloat16).astype(float32)``
    and rounds nothing (the first chip call of the review read every float32
    state as on bfloat16's grid, and float8 weights as not moved at all)."""
    import jax
    import jax.numpy as jnp

    drop = 23 - mantissa_bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(u & jnp.uint32((0xFFFFFFFF >> drop) << drop), jnp.float32)


def _coarse_share(s_pool, slots):
    """For each of ``slots`` [B]: the share of the slot's nonzero elements,
    over all state-space layers, that bfloat16 holds exactly (the low sixteen
    bits of the float32 are zero)."""
    import jax
    import jax.numpy as jnp

    s = s_pool[:, slots].astype(jnp.float32)  # [layers, B, N, H x P]
    there = s != 0
    low = jax.lax.bitcast_convert_type(s, jnp.uint32) & jnp.uint32(0xFFFF)
    count = lambda m: jnp.sum(m, axis=(0, 2, 3), dtype=jnp.float32)
    return count(there & (low == 0)) / jnp.maximum(count(there), 1.0)


def impls(runner, rehearsal: bool) -> list:
    """The implementations the runner's dispatch can pick: of the attention
    layer as for any model, each with a form of the state-space decode step
    and with the experts' grouped products the runner serves (``Drive``; on
    the CPU XLA's, and the rehearsal also interprets the kernels)."""
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one
    implementation.  The state is the runner's own layout: the two paged
    caches of the attention layers ``[attention layers, pages, page_size,
    kv_heads x head_dim]``, the two state pools (``[state-space layers, slots,
    state, heads x head_dim]`` float32 and the convolution's ``[state-space
    layers, slots, 3 x channels]``), and while a frame runs the side buffers.
    Sequence ``s`` holds slot ``s + 1``; slot 0 is the garbage slot the padded
    rows name.  Nothing is donated: a decode returns new pools and leaves the
    state it was given as it was.  ``coarse_shares`` keeps, a decode column,
    the live lanes' shares of state elements that bfloat16 holds exactly
    (``STATE_COARSE_LIMIT``), for whoever wants the reading."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import jax

        cfg, module, inv_freq = runner.model_cfg, runner.module, runner.inv_freq
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        # the experts' grouped products are the runner's own under both of its
        # attentions: XLA's ragged product over 128 experts a layer takes 5.5 GB
        # of temporaries at the published widths, beside a chip that is full,
        # and no program the runner launches on a TPU holds it.  The
        # state-space step runs as its XLA form under "xla", the kernel's
        # specification, and as the runner serves it under "pallas"
        if impl == "xla":
            ssm, moe = "xla", runner.moe_impl
        elif impl == "pallas":
            ssm, moe = runner.state_impl, runner.moe_impl
        else:
            ssm = moe = "pallas_interpret"
        self._prefill = jax.jit(lambda p, *a: module.forward_prefill(
            p, cfg, inv_freq, *a, attn_impl=impl, moe_impl=moe))
        self._decode = jax.jit(lambda p, *a: module.forward_decode_horizon(
            p, cfg, inv_freq, *a, attn_impl=impl, ssm_impl=ssm, moe_impl=moe))
        self._coarse = jax.jit(_coarse_share)
        self.coarse_shares: list = []

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
                "slots": (jnp.zeros(s_shape, self.runner.s_pool.dtype),
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
        names others under ``decode_slots``, or other parameters under
        ``params``); padded rows sit past the table and name slot 0.  A live
        lane whose state is held no finer than bfloat16 decodes from an empty
        slot, no state and no tail (``STATE_COARSE_LIMIT``).  Logits
        ``[lanes, V]``."""
        import jax.numpy as jnp
        import numpy as np

        side = self._zeros(self.lanes, self.horizon) if column == 0 else state["side"]
        live = np.asarray(entry) < page_tables.shape[1] * self.runner.spec.page_size
        slots = state.get("decode_slots")
        if slots is None:
            slots = np.where(live, np.arange(self.lanes) + 1, 0)
        sp, cp = state["slots"]
        coarse = np.where(live, np.asarray(self._coarse(sp, jnp.asarray(slots, jnp.int32))), 0.0)
        self.coarse_shares.append([float(c) for c in coarse[live]])
        if (coarse > STATE_COARSE_LIMIT).any():
            keep = jnp.asarray(coarse <= STATE_COARSE_LIMIT)
            at = jnp.asarray(slots, jnp.int32)
            sp = sp.at[:, at].multiply(keep.astype(sp.dtype)[None, :, None, None])
            cp = cp.at[:, at].multiply(keep.astype(cp.dtype)[None, :, None])
        out, hk, hv, sp, cp, _counts = self._decode(
            state.get("params", self.runner.params), jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(entry), jnp.int32(column), *state["cache"],
            jnp.asarray(page_tables), *side, sp, cp, jnp.asarray(slots, jnp.int32),
            jnp.asarray(live))
        return out, {**state, "side": (hk, hv), "slots": (sp, cp)}

    def controls(self, state) -> dict:
        """Broken states, each of which must miss the tolerance as the wrong
        page does.  Two break what sequence 0 holds beside its pages: it
        decodes from sequence 1's slot, and from its own slot with the
        convolution's last three inputs zeroed.  Two break the expert layers
        of the step over the sound state: the held experts give nothing (no
        token is sent to an expert this chip holds, their selection bias at
        -1e4: what a grouped product that returned zeros would leave), and the
        selection bias dropped (the picks by the scores alone).  One breaks the
        precision the configuration states: the state pool rounded to bfloat16
        and back, which the logits alone would not hear and the drive's hold on
        the state does (``STATE_COARSE_LIMIT``)."""
        import jax.numpy as jnp
        import numpy as np

        swapped = np.zeros(self.lanes, np.int32)
        swapped[:2] = (2, 1)
        sp, cp = state["slots"]
        params = self.runner.params
        first, count = self.runner.model_cfg.held_experts
        bias = params["moe"]["select_bias"]
        with_bias = lambda b: {**params, "moe": {**params["moe"], "select_bias": b}}
        return {
            "other_sequences_slot": {**state, "decode_slots": swapped},
            "conv_tail_zeroed": {**state, "slots": (sp, cp.at[:, 1].set(0))},
            "held_experts_give_nothing": {
                **state, "params": with_bias(bias.at[..., first:first + count].set(-1e4))},
            "selection_bias_dropped": {**state, "params": with_bias(jnp.zeros_like(bias))},
            "state_in_bfloat16": {**state, "slots": (_rounded(sp, 7).astype(sp.dtype), cp)},
        }


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    s = _shape(hf)
    d_inner = s["Hm"] * s["P"]
    conv = d_inner + 2 * s["R"] * s["N"]
    return {**s, "d_inner": d_inner, "conv": conv,
            "mamba": s["E"] * (d_inner + conv + s["Hm"]) + d_inner * s["E"],
            "attn": 2 * s["E"] * s["H"] * s["D"] + 2 * s["E"] * s["K"] * s["D"],
            "expert": 2 * s["Z"] * s["F"],
            "moe_always": (s["E"] * s["X"] + 2 * s["E"] * s["Z"] + 2 * s["E"] * s["Fs"]),
            "vocab": s["V"] * s["E"]}


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms, the convolution's taps
    and the per-head vectors are under a tenth of a percent and left out).
    ``always``: what every token passes whatever the routing; ``routed``: the
    held routed experts."""
    w = _widths(hf)
    n = w["n"]
    always = n["mamba"] * w["mamba"] + n["attn"] * w["attn"] + n["moe"] * w["moe_always"]
    routed = n["moe"] * w["held"] * w["expert"]
    head = 0 if hf.get("tie_word_embeddings") else w["vocab"]
    return {"always": always, "routed": routed, "layers": always + routed,
            "embed": w["vocab"], "lm_head": head, "matmul": always + routed + w["vocab"],
            "total": always + routed + w["vocab"] + head}


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """Only the attention layers hold keys and values."""
    w = _widths(hf)
    return 2 * w["n"]["attn"] * w["K"] * w["D"] * dtype_bytes


def ssm_layers(hf: dict) -> int:
    return _widths(hf)["n"]["mamba"]


def ssm_lane_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Least bytes one state-space layer's decode step moves for one lane that
    runs: the state read and written (``heads x head_dim x state`` float32
    each way), the convolution's tail read and written, and the lane's row of
    ``x``, ``B``, ``C`` (float32, out of the convolution) and ``dt``."""
    w = _widths(hf)
    state = w["Hm"] * w["P"] * w["N"] * 4
    tail = (w["taps"] - 1) * w["conv"] * dtype_bytes
    return 2 * state + 2 * tail + 4 * (w["conv"] + w["Hm"])


def ssm_decode_min_seconds(hf: dict, lane_columns: float, chips: int, peak: dict,
                           dtype_bytes: int = 2) -> float:
    """Least time of the state-space layers' decode steps for ``lane_columns``
    lanes x columns that ran, every layer once each."""
    return (lane_columns * ssm_layers(hf) * ssm_lane_bytes(hf, dtype_bytes)
            / (chips * peak["bytes_per_s"]))


def expert_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one routed expert's two matrices."""
    return _widths(hf)["expert"] * dtype_bytes


def expert_flops_per_row(hf: dict) -> int:
    """FLOPs of one routed expert for one row."""
    return 2 * _widths(hf)["expert"]


def attention_layers(hf: dict) -> int:
    """The ``*`` layers: each runs the decode attention kernel once a column,
    which is how a trace counts the columns run."""
    return _widths(hf)["n"]["attn"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: what every column reads
    whatever the routing and the lanes (the mixers' and the attention's
    weights, routers, latent projections, shared experts, the head's slice)
    once a column, and the live lanes' keys and values of the attention
    layers.  **Neither the routed experts nor the recurrent state is
    counted**: this function is given neither the experts hit nor the lanes
    that ran (PERF.md 7.16), so the share built on it errs low by much;
    ``kernels.latent_moe_decode_roofline_share`` counts the experts hit and
    ``kernels.ssm_decode_roofline_share`` the state of the lanes that ran."""
    p = param_count(hf)
    weight_bytes = (p["always"] + p["lm_head"]) * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    parameter every token passes whatever the routing; attention's ``4 x
    heads x head_dim`` FLOPs for every (query, key) pair of the causal
    triangle; in the state-space layers the scan's operations at the model's
    chunk ``L``: a token's share of the chunk's masked ``C B^T`` product (``2 N
    L / 2`` a group), of its weighted sum of the chunk's inputs (``2 P L / 2``
    a head), and its reading of and writing into the carried state (``4 P N``
    a head).  **The routed experts are left out** (no argument says how many
    rows were routed here), so the share errs low."""
    w = _widths(hf)
    p = param_count(hf)
    L = hf.get("chunk_size", 128)
    scan = w["R"] * w["N"] * L + w["Hm"] * w["P"] * L + 4.0 * w["Hm"] * w["P"] * w["N"]
    flops = (2.0 * p["always"] * new_tokens
             + 4.0 * w["H"] * w["D"] * w["n"]["attn"] * attn_pairs
             + scan * w["n"]["mamba"] * new_tokens)
    return flops / (chips * peak["flops_per_s"])
