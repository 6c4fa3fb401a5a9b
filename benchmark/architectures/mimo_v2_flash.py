"""MiMo-V2-Flash (``model_type: mimo_v2_flash``): sliding-window layers with a
sink beside full-attention layers, keys wider than values, rotary over part of
a head, routed experts picked by a biased score.  The program serves it from
``smg_tpu/models/mimo.py``, whose docstring has the equations; this file is
the one plain reference of them, and it imports nothing of ``smg_tpu/models``.

What an architecture file gives (README, "An architecture"): ``logits``, the
plain reference (one sequence, every layer over the whole sequence with a
mask, no cache, no kernel, no batching, every routed expert a plain matrix
product over all tokens and a mask); ``impls`` and ``drive``, the serving
forward as ``reference.check_engine`` drives it, with four controls of its
own; the cost functions the ``kernels.*`` readers divide by; and for this
architecture's own readers ``window_entry_bytes``, ``window_layers``,
``window``, ``expert_bytes`` and ``expert_flops_per_row``.

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


def _rope(x, pos, theta, n):
    """Rotate-half rotary embedding over the first ``n`` lanes of ``x``
    [T, heads, d] at positions ``pos`` [T]; the other lanes pass."""
    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n))
    ang = (pos.astype(jnp.float32)[:, None] * inv[None, :])[:, None, :]
    a, b = x[..., : n // 2], x[..., n // 2: n]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang), x[..., n:]], axis=-1)


def _attention(x, w, *, window, shape):
    """One layer's attention over one sequence.  ``x`` [T, E], float32;
    ``window`` 0 for a full layer."""
    import jax.numpy as jnp

    T = x.shape[0]
    D, Dv, n_rot = shape["D"], shape["Dv"], shape["rotary"]
    pos = jnp.arange(T)
    theta = shape["swa_theta"] if window else shape["theta"]
    # the program stores the three input projections [out, in]
    # (``models/mimo.init_params`` says why)
    q = _rope((x @ w("wq").T).reshape(T, -1, D), pos, theta, n_rot)
    k = _rope((x @ w("wk").T).reshape(T, -1, D), pos, theta, n_rot)
    v = shape["value_scale"] * (x @ w("wv").T).reshape(T, -1, Dv)
    H, G = q.shape[1], k.shape[1]
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(D)
    seen = pos[:, None] >= pos[None, :]
    if window:
        seen = seen & (pos[None, :] > pos[:, None] - window)
    s = jnp.where(seen[None], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    if window and shape["sink"]:
        b = w("sink")[:, None, None]  # [H, 1, 1]: mass in the denominator, no value
        m = jnp.maximum(m, b)
        e = jnp.exp(s - m)
        p = e / (e.sum(axis=-1, keepdims=True) + jnp.exp(b - m))
    else:
        e = jnp.exp(s - m)
        p = e / e.sum(axis=-1, keepdims=True)
    return jnp.einsum("hts,shd->thd", p, v).reshape(T, -1) @ w("wo")


def _swiglu(x, w, *at):
    """``W_down(silu(W_gate x) * W_up x)`` (of the expert ``at``), the hidden
    width in blocks: a float32 copy of a 16,384-wide MLP is 0.8 GB, and the
    reference runs beside the engine's weights and cache."""
    import jax

    y = 0.0
    for lo in range(0, w.width("w_gate", *at), MLP_BLOCK):
        cols = slice(lo, lo + MLP_BLOCK)
        hidden = (jax.nn.silu(x @ w("w_gate", *at, slice(None), cols))
                  * (x @ w("w_up", *at, slice(None), cols)))
        y = y + hidden @ w("w_down", *at, cols)
    return y


def _routed(x, w, *, shape):
    """``sum_e w_e E_e(x)`` over each token's picks on the held experts.  The
    picks are the largest of ``sigmoid + bias``, the weights the sigmoids
    over their sum.  One expert at a time over all tokens."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(x @ w("router"))
    _, picked = jax.lax.top_k(scores + w("select_bias")[None, :], shape["top_k"])
    top = jnp.take_along_axis(scores, picked, axis=-1)
    if shape["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(w.held):
        weight = jnp.sum(jnp.where(picked == shape["first"] + e, top, 0.0), axis=-1,
                         keepdims=True)
        y = y + weight * _swiglu(x, w, e)
    return y


def _layer(h, w, *, window, routed, shape):
    eps = shape["eps"]
    h = h + _attention(_rms(h, w("attn_norm"), eps), w, window=window, shape=shape)
    x = _rms(h, w("mlp_norm"), eps)
    return h + (_routed(x, w, shape=shape) if routed else _swiglu(x, w))


def _shape(hf: dict) -> dict:
    return {"D": hf["head_dim"], "Dv": hf["v_head_dim"],
            "rotary": int(hf["head_dim"] * hf.get("partial_rotary_factor", 1.0)),
            "theta": float(hf["rope_theta"]), "swa_theta": float(hf["swa_rope_theta"]),
            "window": hf["sliding_window"], "sink": bool(hf.get("add_swa_attention_sink_bias")),
            "value_scale": float(hf.get("attention_value_scale") or 1.0),
            "eps": hf.get("layernorm_epsilon", 1e-5), "top_k": hf["num_experts_per_tok"],
            "norm_topk": bool(hf.get("norm_topk_prob", True)),
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


def _kinds(hf: dict) -> list:
    """(window layer?, routed experts?) of every layer, in order."""
    return [(bool(p), bool(m)) for p, m in zip(hf["hybrid_layer_pattern"], hf["moe_layer_freq"])]


def _stack_name(window: bool, routed: bool) -> str:
    """How the program names the parameter stack of a kind of layer."""
    return ("window" if window else "full") + ("_moe" if routed else "_dense")


def logits(params, hf: dict, tokens, rows):
    """Reference logits [len(rows), V] (numpy float32) of one sequence of
    token ids at the positions ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f32 = jnp.float32
    shape = _shape(hf)
    seen: dict = {}
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(tokens)].astype(f32)
        for window, routed in _kinds(hf):
            name = _stack_name(window, routed)
            i = seen.get(name, 0)
            seen[name] = i + 1
            h = _layer(h, _Weights(params[name], i, routed),
                       window=shape["window"] if window else 0, routed=routed, shape=shape)
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), shape["eps"])
        table, out = params["lm_head"], []
        for lo in range(0, table.shape[1], VOCAB_BLOCK):
            out.append(np.asarray(h @ table[:, lo:lo + VOCAB_BLOCK].astype(f32)))
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------------------------
# the drive of the serving forward


def impls(runner, rehearsal: bool) -> list:
    """The implementations the runner's dispatch can pick: XLA attention with
    XLA's ragged product for the experts, and the kernels (the paged decode
    kernel, the ring kernel and the experts' grouped product; interpreted in
    the rehearsal)."""
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one
    implementation of both decode attentions and of the experts' grouped
    products.  The state is the runner's own layout: the full layers' pages, K
    and V of their own widths; the window layers' rings, one slot a lane and
    the garbage slot 0 (sequence ``s`` holds slot ``s + 1``); and while a
    frame runs its four side buffers.  A control may put a broken ``decode``
    program with its parameters into it, or other slots for the lanes, which
    the next step then runs.  Nothing is donated."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import importlib

        import jax

        cfg, inv_freq = runner.model_cfg, runner.inv_freq
        # the module itself: the runner's own handle has its choice of the
        # experts' products bound, and the drive makes that choice
        self.module = module = importlib.import_module("smg_tpu.models.mimo")
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        self._prefill = jax.jit(lambda p, *a: module.forward_prefill(
            p, cfg, inv_freq, *a, moe_impl=impl))
        self._decode_under = lambda under: jax.jit(lambda p, *a: module.forward_decode_horizon(
            p, under, inv_freq, *a, attn_impl=impl, moe_impl=impl))
        self._decode = self._decode_under(cfg)

    def empty(self, pages: int):
        import jax.numpy as jnp

        spec, window = self.runner.spec, self.runner.state_spec
        dtype = jnp.dtype(spec.dtype)
        slots = lambda shape: jnp.zeros((shape[0], self.lanes + 1, *shape[2:]), dtype)
        return {"cache": (jnp.zeros((spec.num_layers, pages, *spec.shape[2:]), dtype),
                          jnp.zeros((spec.num_layers, pages, *spec.v_shape[2:]), dtype)),
                "rings": (slots(window.k_shape), slots(window.v_shape)), "side": None}

    def prefill(self, state, seq, chunk, lo, n, table):
        import jax.numpy as jnp

        out, kc, vc, rk, rv = self._prefill(
            self.runner.params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            *state["cache"], jnp.asarray(table), *state["rings"], jnp.int32(seq + 1))
        return out, {**state, "cache": (kc, vc), "rings": (rk, rv)}

    def decode(self, state, tokens, positions, entry, column, page_tables):
        import jax.numpy as jnp
        import numpy as np

        spec = self.runner.spec
        side = (self.module.side_buffers(self.runner.model_cfg, self.lanes, self.horizon,
                                         jnp.dtype(spec.dtype))
                if column == 0 else state["side"])
        live = np.asarray(entry) < page_tables.shape[1] * spec.page_size
        slots = state.get("decode_slots")
        if slots is None:
            slots = np.where(live, np.arange(self.lanes) + 1, 0)
        decode, params = state.get("decode", (self._decode, self.runner.params))
        out, side, _counts = decode(
            params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(entry),
            jnp.int32(column), *state["cache"], jnp.asarray(page_tables), *state["rings"],
            jnp.asarray(slots, jnp.int32), side, jnp.asarray(live))
        return out, {**state, "side": side}

    def controls(self, state) -> dict:
        """Four broken states, each of which must miss the tolerance as the
        wrong page does.  None touches the pages.  Three break what the
        window layers alone hold and compute.  The window ignored: the step
        runs as a program whose window is wider than any context, so a window
        layer's query meets every entry of its ring (the 16 or so beyond the
        window among them, at the positions a ring of this size still holds).
        The sink left out: the same program with
        ``add_swa_attention_sink_bias`` off, a plain softmax.  One sequence's
        ring given to the other: lane 0 decodes from sequence 1's slot and
        lane 1 from sequence 0's.

        The fourth breaks the routed experts and leaves attention alone
        (``architectures/pangu_ultra_moe.py`` has the same control, and PR
        34's findings why it has this form): the step runs as a program whose
        router is as wide as the experts held (the fault of reading
        ``n_routed_experts``, the experts held, for the router's width: the
        router's columns and the selection bias of the held range and no
        others), so each of a token's picks lands on a held expert, through
        the biased selection, dispatch, the grouped products and combine,
        where a token of the reference sends a pick here in one layer of two.
        A routed path that gives nothing, or the same whatever the routing,
        makes this control read what that fault reads on the sound row, under
        the tolerance."""
        import dataclasses

        import numpy as np

        cfg, params = self.runner.model_cfg, self.runner.params
        under = lambda broken: (self._decode_under(broken), params)
        swapped = np.zeros(self.lanes, np.int32)
        swapped[:2] = (2, 1)
        first, count = cfg.held_experts
        narrow = dataclasses.replace(cfg, num_experts=count, experts_held=(0, count))
        cut = {kind: ({**stack, "router": stack["router"][..., first:first + count],
                       "select_bias": stack["select_bias"][..., first:first + count]}
                      if "router" in stack else stack)
               for kind, stack in params.items() if isinstance(stack, dict)}
        return {
            "window_ignored": {**state, "decode": under(
                dataclasses.replace(cfg, sliding_window=2**20))},
            "sink_left_out": {**state, "decode": under(
                dataclasses.replace(cfg, swa_sink_bias=False))},
            "other_sequences_ring": {**state, "decode_slots": swapped},
            "router_cut_to_held": {**state, "decode": (self._decode_under(narrow),
                                                       {**params, **cut})},
        }


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    D, Dv = hf["head_dim"], hf["v_head_dim"]
    G, Gw = hf["num_key_value_heads"], hf["swa_num_key_value_heads"]
    kinds = _kinds(hf)
    attention = lambda g: E * H * D + E * g * D + E * g * Dv + H * Dv * E
    return {"E": E, "H": H, "D": D, "Dv": Dv, "G": G, "Gw": Gw,
            "full_attention": attention(G), "window_attention": attention(Gw),
            "expert": 3 * E * hf["moe_intermediate_size"],
            "dense_mlp": 3 * E * hf["intermediate_size"],
            "router": E * hf.get("router_num_experts", hf["n_routed_experts"]),
            "held": hf["n_routed_experts"],
            "full_layers": sum(not w for w, _ in kinds),
            "window_layers": sum(w for w, _ in kinds),
            "dense_layers": sum(not m for _, m in kinds),
            "expert_layers": sum(m for _, m in kinds),
            "vocab": hf["vocab_size"] * E}


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms, sinks and selection
    biases are under a thousandth of a percent and left out).  ``always``:
    what every token passes whatever the routing (attention, the dense MLPs,
    the routers); ``routed``: the held routed experts."""
    w = _widths(hf)
    always = (w["full_layers"] * w["full_attention"] + w["window_layers"] * w["window_attention"]
              + w["dense_layers"] * w["dense_mlp"] + w["expert_layers"] * w["router"])
    routed = w["expert_layers"] * w["held"] * w["expert"]
    head = 0 if hf.get("tie_word_embeddings") else w["vocab"]
    return {"always": always, "routed": routed, "layers": always + routed,
            "embed": w["vocab"], "lm_head": head, "matmul": always + routed + w["vocab"],
            "total": always + routed + w["vocab"] + head}


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """What a token adds to what its sequence holds: keys and values in the
    full layers' pages.  The window layers' rings do not grow."""
    w = _widths(hf)
    return w["full_layers"] * w["G"] * (w["D"] + w["Dv"]) * dtype_bytes


def window_entry_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one entry of a window layer's ring: the keys and values of
    one token."""
    w = _widths(hf)
    return w["Gw"] * (w["D"] + w["Dv"]) * dtype_bytes


def window_layers(hf: dict) -> int:
    return _widths(hf)["window_layers"]


def window(hf: dict) -> int:
    return hf["sliding_window"]


def expert_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return _widths(hf)["expert"] * dtype_bytes


def expert_flops_per_row(hf: dict) -> int:
    """FLOPs of one routed expert for one row."""
    return 2 * _widths(hf)["expert"]


def attention_layers(hf: dict) -> int:
    """The full-attention layers: each runs the paged decode kernel
    (``smg.attn.decode``) once a column, which is how a trace counts the
    columns run.  The window layers run a kernel of another name."""
    return _widths(hf)["full_layers"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: what every column reads
    whatever the routing (attention and dense weights, routers, the head's
    slice) once a column, and the live lanes' keys and values in the full
    layers.  **Neither the routed experts nor the window layers' rings are
    counted**: this function is given neither the experts hit nor the lanes,
    so the share built on it errs low by much (at the benchmark's cut about
    2.6 of a 64-lane column's 7.0 GB are counted);
    ``kernels.moe_decode_roofline_share`` counts the experts hit and
    ``kernels.swa_decode_roofline_share`` the rings."""
    p = param_count(hf)
    weight_bytes = (p["always"] + p["lm_head"]) * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    parameter every token passes whatever the routing, and in the full layers
    attention's ``2 x heads x (head_dim + v_head_dim)`` FLOPs for every
    (query, key) pair of the causal triangle.  **The routed experts and the
    window layers' attention are left out** (no argument says how many rows
    were routed here, and ``attn_pairs`` counts the triangle, not the band),
    so the share errs low: at the benchmark's cut a token's expected 3 rows
    are 75 M of the 1,010 M parameters it passes, and its window attention
    26 MFLOP beside 2 GFLOP."""
    w = _widths(hf)
    p = param_count(hf)
    flops = (2.0 * p["always"] * new_tokens
             + 2.0 * w["H"] * (w["D"] + w["Dv"]) * w["full_layers"] * attn_pairs)
    return flops / (chips * peak["flops_per_s"])
