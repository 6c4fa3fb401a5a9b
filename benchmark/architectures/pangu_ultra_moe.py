"""openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``): latent attention,
routed experts beside a shared one, four norms a layer.  The program serves it
from ``smg_tpu/models/pangu_moe.py``, whose docstring has the equations; this
file is the one plain reference of them, and it imports nothing of
``smg_tpu/models``.

What an architecture file gives (README, "An architecture"): ``logits``, the
plain reference (one sequence, keys and values of every head rebuilt from the
latent, no cache, no kernel, no batching, every routed expert a plain matrix
product over all tokens and a mask); ``impls`` and ``drive``, the serving
forward as ``reference.check_engine`` drives it, with three controls of its own;
the cost functions the ``kernels.*`` readers divide by; and for this
architecture's own readers ``latent_entry_bytes``, ``mla_decode_flops_per_token``,
``expert_bytes`` and ``expert_flops_per_row``.

**The chip's share.**  The configuration holds ``n_routed_experts`` of the
router's ``router_num_experts`` experts (the range from ``routed_expert_offset``)
and a slice of the vocabulary.  The reference is given the same share: it
routes over the router's whole width and adds what the held experts give;
what the absent ones would add is left out, here and in the program alike.
"""

from __future__ import annotations

import math

VOCAB_BLOCK = 16384
MLP_BLOCK = 4096  # hidden columns of an MLP multiplied at a time


# --------------------------------------------------------------------------
# the plain reference: ``jax.numpy`` in float32, matrix multiplications at
# ``highest`` precision.  It reads the engine's own parameters a layer at a
# time.


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of ``x`` [T, ..., d] at positions ``pos`` [T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _attention(x, w, *, dn, rkv, eps, theta):
    """Latent attention over one sequence, expanded.  ``x`` [T, E], float32;
    ``w(name)`` gives a matrix of this layer."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    pos = jnp.arange(T)
    # how the program stores the projections (``models/pangu_moe.init_params``):
    # W_uq as its two parts, the no-rope part [H * dn, rq] and the rotary part
    # [dr, H, rq]; W_dkv as [E, rkv] beside [E, dr]; W_uk, W_uv as [H, rkv, d]
    c_q = _rms(x @ w("w_dq"), w("q_norm"), eps)
    q_nope = (c_q @ w("w_uq_nope").T).reshape(T, -1, dn)
    q_pe = _rope(jnp.einsum("tr,dhr->thd", c_q, w("w_uq_pe")), pos, theta)
    c, k_pe = _rms(x @ w("w_dkv"), w("kv_norm"), eps), _rope(x @ w("w_dk_pe"), pos, theta)
    assert c.shape[-1] == rkv
    k_nope = jnp.einsum("sc,hcd->shd", c, w("w_uk"))
    v = jnp.einsum("sc,hcd->shd", c, w("w_uv"))
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope) + jnp.einsum("thd,sd->hts", q_pe, k_pe))
    s = s / math.sqrt(dn + q_pe.shape[-1])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, -1) @ w("wo")


def _swiglu(x, w, names, *at):
    """``W_down(silu(W_gate x) * W_up x)`` of the matrices ``names`` (at the
    index ``at`` of their stack), the hidden width in blocks: a float32 copy
    of an 18,432-wide MLP is 1.7 GB, and the reference runs beside the
    engine's weights and cache."""
    import jax

    gate, up, down = names
    y = 0.0
    F = w.width(gate, *at)
    for lo in range(0, F, MLP_BLOCK):
        cols = slice(lo, lo + MLP_BLOCK)
        hidden = jax.nn.silu(x @ w(gate, *at, slice(None), cols)) * (x @ w(up, *at, slice(None), cols))
        y = y + hidden @ w(down, *at, cols)
    return y


def _routed(x, w, *, top_k, scoring, norm_topk, scale, first):
    """``sum_i w_i E_i(x)`` over each token's picks on the held experts
    (``first ..``).  ``x`` [T, E].  One expert at a time over all tokens."""
    import jax
    import jax.numpy as jnp

    logits = x @ w("router")
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    top, picked = jax.lax.top_k(scores, top_k)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * scale
    y = jnp.zeros_like(x)
    for e in range(w.held):
        weight = jnp.sum(jnp.where(picked == first + e, top, 0.0), axis=-1, keepdims=True)
        y = y + weight * _swiglu(x, w, ("w_gate", "w_up", "w_down"), e)
    return y


def _layer(h, w, *, dense, shape):
    eps = shape["eps"]
    a = _attention(_rms(h, w("attn_norm"), eps), w, dn=shape["dn"], rkv=shape["rkv"],
                   eps=eps, theta=shape["theta"])
    h = h + _rms(a, w("post_attn_norm"), eps)
    x = _rms(h, w("mlp_norm"), eps)
    if dense:
        m = _swiglu(x, w, ("w_gate", "w_up", "w_down"))
    else:
        m = (_routed(x, w, top_k=shape["top_k"], scoring=shape["scoring"],
                     norm_topk=shape["norm_topk"], scale=shape["scale"], first=shape["first"])
             + _swiglu(x, w, ("ws_gate", "ws_up", "ws_down")))
    return h + _rms(m, w("post_mlp_norm"), eps)


def _shape(hf: dict) -> dict:
    return {"dn": hf["qk_nope_head_dim"], "rkv": hf["kv_lora_rank"],
            "eps": hf.get("rms_norm_eps", 1e-5), "theta": float(hf["rope_theta"]),
            "top_k": hf["num_experts_per_tok"], "scoring": hf.get("scoring_func", "sigmoid"),
            "norm_topk": bool(hf.get("norm_topk_prob", True)),
            "scale": float(hf.get("routed_scaling_factor", 1.0)),
            "first": hf.get("routed_expert_offset", 0)}


class _Weights:
    """One layer of the engine's own parameters, a matrix (or a block of one)
    at a time in float32: the float32 copy lives as long as its product."""

    def __init__(self, stack: dict, layer: int, routed: bool):
        self.stack, self.layer = stack, layer
        self.held = stack["w_gate"].shape[1] if routed else 0

    def __call__(self, name, *at):
        import jax.numpy as jnp

        return self.stack[name][(self.layer, *at)].astype(jnp.float32)

    def width(self, name, *at) -> int:
        return self.stack[name].shape[1 + len(at) + 1]


def logits(params, hf: dict, tokens, rows):
    """Reference logits [len(rows), V] (numpy float32) of one sequence of
    token ids at the positions ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f32 = jnp.float32
    shape = _shape(hf)
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for name, dense in (("dense", True), ("moe", False)):
            for i in range(params[name]["wo"].shape[0]):
                h = _layer(h, _Weights(params[name], i, not dense), dense=dense, shape=shape)
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), shape["eps"])
        table, out = params["lm_head"], []
        for lo in range(0, table.shape[1], VOCAB_BLOCK):
            out.append(np.asarray(h @ table[:, lo:lo + VOCAB_BLOCK].astype(f32)))
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------------------------
# the drive of the serving forward


def impls(runner, rehearsal: bool) -> list:
    """The implementations the runner's dispatch can pick: XLA attention with
    XLA's ragged product for the experts, and the two kernels (interpreted in
    the rehearsal)."""
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one
    implementation (of the decode attention and of the experts' grouped
    products alike).  The state is the runner's own layout: one latent cache
    ``[layers, pages, page_size, entry lanes]``, a ``v_cache`` of zero size,
    and while a frame runs the one side buffer; a control may put a broken
    ``decode`` program with its ``params`` into it, which the next step then
    runs.  Nothing is donated."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import importlib

        import jax

        cfg, inv_freq = runner.model_cfg, runner.inv_freq
        # the module itself: the runner's own handle has its choice of the
        # experts' products bound, and the drive makes that choice
        module = importlib.import_module("smg_tpu.models.pangu_moe")
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        self.tables = {}  # sequence -> the page table it was prefilled through
        self._prefill = jax.jit(lambda p, *a: module.forward_prefill(
            p, cfg, inv_freq, *a, moe_impl=impl))
        self._decode_under = lambda under: jax.jit(lambda p, *a: module.forward_decode_horizon(
            p, under, inv_freq, *a, attn_impl=impl, moe_impl=impl))
        self._decode = self._decode_under(cfg)

    def _zeros(self, *lead):
        import jax.numpy as jnp

        spec = self.runner.spec
        return jnp.zeros((spec.num_layers, *lead, spec.lanes), jnp.dtype(spec.dtype))

    def empty(self, pages: int):
        return {"cache": self._zeros(pages, self.runner.spec.page_size), "side": None}

    def prefill(self, state, seq, chunk, lo, n, table):
        import jax.numpy as jnp

        self.tables[seq] = table
        out, cache, _v = self._prefill(
            self.runner.params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            state["cache"], self._zeros(0, self.runner.spec.page_size), jnp.asarray(table))
        return out, {**state, "cache": cache}

    def decode(self, state, tokens, positions, entry, column, page_tables):
        import jax.numpy as jnp
        import numpy as np

        side = self._zeros(self.lanes, self.horizon) if column == 0 else state["side"]
        live = np.asarray(entry) < page_tables.shape[1] * self.runner.spec.page_size
        decode, params = state.get("decode", (self._decode, self.runner.params))
        out, side, _counts = decode(
            params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(entry),
            jnp.int32(column), state["cache"], jnp.asarray(page_tables), side, jnp.asarray(live))
        return out, {**state, "side": side}

    def controls(self, state) -> dict:
        """Three broken states, each of which must miss the tolerance as the
        wrong page does.  Two break the cache of sequence 0: the rotary lanes
        of its pages zeroed (every latent is right, the keys' positions are
        gone), and the latent lanes of its second page taken from the second
        page of sequence 1 (the rotary keys are right).  The first breaks
        every page and not one: one page's rotary lanes only re-weigh 16 keys
        of 700 and moved the logits by 0.16 to 0.35 sigma on the chip, and
        weights under which 16 keys' positions weigh more make the attention
        so peaked that the serving path's own error passes the tolerance
        (PERF.md, Findings, PR 34).

        The third breaks the routed experts and leaves the cache alone: the
        step runs as a program whose router is as wide as the experts held
        (the fault of reading ``n_routed_experts``, the experts held, for the
        router's width: the router's columns of the held range and no others),
        so each of a token's picks lands on a held expert, through dispatch,
        the grouped products and combine, where a token of the reference
        sends a pick here in one layer of two.  A routed path that gives
        nothing, or the same whatever the routing, makes this control read
        what that fault reads on the sound row, under the tolerance.  A fault that keeps the routing and drops
        or swaps what the held experts give cannot serve as a control: the
        harness reads a control on one row, and a row whose 32 picks all fall
        on experts held elsewhere (one in eight at 16 of 256) reads the same
        with and without them."""
        import dataclasses

        cfg = self.runner.model_cfg
        rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        cache = state["cache"]
        mine = self.tables[0]
        own, other = int(mine[1]), int(self.tables[1][1])
        first, count = cfg.held_experts
        params = self.runner.params
        narrow = dataclasses.replace(cfg, num_experts=count, experts_held=(0, count))
        cut = {**params["moe"], "router": params["moe"]["router"][..., first:first + count]}
        return {
            "rotary_lanes_zeroed": {
                **state, "cache": cache.at[:, mine, :, rkv:rkv + dr].set(0)},
            "latent_of_other_sequence": {
                **state, "cache": cache.at[:, own, :, :rkv].set(cache[:, other, :, :rkv])},
            "router_cut_to_held": {
                **state, "decode": (self._decode_under(narrow), {**params, "moe": cut})},
        }


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rq, rkv = hf["q_lora_rank"], hf["kv_lora_rank"]
    attention = E * rq + rq * H * (dn + dr) + E * (rkv + dr) + rkv * H * (dn + dv) + H * dv * E
    expert = 3 * E * hf["moe_intermediate_size"]
    dense_layers = hf.get("first_k_dense_replace", 0)
    return {"E": E, "H": H, "dn": dn, "dr": dr, "dv": dv, "rkv": rkv,
            "attention": attention, "expert": expert,
            "dense_mlp": 3 * E * hf["intermediate_size"],
            "router": E * hf.get("router_num_experts", hf["n_routed_experts"]),
            "shared": hf.get("n_shared_experts", 0) * expert,
            "held": hf["n_routed_experts"], "layers": hf["num_hidden_layers"],
            "dense_layers": dense_layers,
            "expert_layers": hf["num_hidden_layers"] - dense_layers,
            "vocab": hf["vocab_size"] * E}


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms are under a hundredth of
    a percent and left out).  ``always``: what every token passes whatever the
    routing (attention, the dense MLPs, the shared experts, the routers);
    ``routed``: the held routed experts."""
    w = _widths(hf)
    always = (w["layers"] * w["attention"] + w["dense_layers"] * w["dense_mlp"]
              + w["expert_layers"] * (w["shared"] + w["router"]))
    routed = w["expert_layers"] * w["held"] * w["expert"]
    head = 0 if hf.get("tie_word_embeddings") else w["vocab"]
    return {"always": always, "routed": routed, "layers": always + routed,
            "embed": w["vocab"], "lm_head": head, "matmul": always + routed + w["vocab"],
            "total": always + routed + w["vocab"] + head}


def latent_entry_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes one token leaves in the cache in one layer, as published:
    ``kv_lora_rank + qk_rope_head_dim`` numbers (the program lays them out on
    whole 128-lane tiles and reports both, ``loads()["latent_cache"]``)."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * dtype_bytes


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    return hf["num_hidden_layers"] * latent_entry_bytes(hf, dtype_bytes)


def mla_decode_flops_per_token(hf: dict) -> int:
    """FLOPs of absorbed decode attention for one cached token of one lane in
    one layer: every head's score over the entry and its weighted sum of the
    latent."""
    w = _widths(hf)
    return 2 * w["H"] * ((w["rkv"] + w["dr"]) + w["rkv"])


def expert_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return _widths(hf)["expert"] * dtype_bytes


def expert_flops_per_row(hf: dict) -> int:
    """FLOPs of one routed expert for one row."""
    return 2 * _widths(hf)["expert"]


def attention_layers(hf: dict) -> int:
    """Every layer runs the decode attention kernel once a column."""
    return hf["num_hidden_layers"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: what every column reads
    whatever the routing (attention and dense weights, shared experts,
    routers, the head's slice) once a column, and the live lanes' latent
    entries.  **The routed experts are not counted**: this function is given
    neither the experts hit nor the rows routed here, so the share built on
    it errs low by much (3.5 of a 64-lane column's 8.8 GB are counted);
    ``kernels.moe_decode_roofline_share`` counts the experts hit."""
    p = param_count(hf)
    weight_bytes = (p["always"] + p["lm_head"]) * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    parameter every token passes whatever the routing, and expanded
    attention's ``2 x heads x (dn + dr + dv)`` FLOPs for every (query, key)
    pair of the causal triangle.  **The routed experts are left out** (no
    argument says how many rows were routed here), so the share errs low by
    about a twentieth at the benchmark's cut (a token's expected 0.5 rows a
    layer: 94 M of the 1.70 B parameters it passes)."""
    w = _widths(hf)
    p = param_count(hf)
    flops = (2.0 * p["always"] * new_tokens
             + 2.0 * w["H"] * (w["dn"] + w["dr"] + w["dv"]) * w["layers"] * attn_pairs)
    return flops / (chips * peak["flops_per_s"])
