"""LongCat-Flash (``model_type: longcat_flash``): two latent-attention sublayers
a layer, two dense MLPs, and the routed experts as a shortcut from behind the
first attention to the layer's end, with identity experts among the router's
outputs.  The program serves it from ``smg_tpu/models/longcat_flash.py``,
whose docstring has the equations; this file is the one plain reference of
them, in the published order, and it imports nothing of ``smg_tpu/models``.

What an architecture file gives (README, "An architecture"): ``logits``, the
plain reference (one sequence, keys and values of every head rebuilt from the
latent, no cache, no kernel, no batching, every routed expert a plain matrix
product over all tokens and a mask, the identity experts one weight a token);
``impls`` and ``drive``, the serving forward as ``reference.check_engine``
drives it, with controls of its own; the cost functions the ``kernels.*``
readers divide by; and for the latent and expert readers ``latent_entry_bytes``,
``mla_decode_flops_per_token``, ``expert_bytes`` and ``expert_flops_per_row``.

**The chip's share.**  The configuration holds ``n_routed_experts`` of the
router's ``router_num_experts`` real experts (the range from
``routed_expert_offset``) and a slice of the vocabulary; the router's outputs
are the real experts and ``zero_expert_num`` identity experts behind them.
The reference is given the same share: it routes over the router's whole
width, adds what the held experts give and the identity picks' ``w_i x``
(every chip computes that alike for its own tokens); what the absent experts
would add is left out, here and in the program alike.

**What ``correct`` rests on.**  Both kinds of result of the branch are in the
sound rows at full voice: the program's random routers read lanes of the
stream that only the embedding writes (``models/longcat_flash.init_params``),
so this file's float32 routing and the program's bfloat16 routing pick the
same outputs, and the experts are drawn loud enough that held experts that
give nothing, or an identity term that is dropped, miss the tolerance (the
controls ``held_experts_give_nothing`` and ``identity_term_dropped`` say by how
much: 0.7-2.6 and 1.7-3.0 of a row's deviation on the chip where rounding
reads 0.05-0.08; PERF.md, Findings, PR 43).

**What the reference reads of the program's storage** (its parameters are the
engine's own): ``W_uq`` and ``W_dkv`` in the parts ``models/pangu_moe.py``
stores, and the rotary rows of both de-interleaved; the reference puts them
back in the published order and turns the published pairs ``(2i, 2i + 1)``.
"""

from __future__ import annotations

import math

VOCAB_BLOCK = 16384
MLP_BLOCK = 4096  # hidden columns of an MLP multiplied at a time


# --------------------------------------------------------------------------
# the plain reference: ``jax.numpy`` in float32, matrix multiplications at
# ``highest`` precision.  It reads the engine's own parameters a matrix at a
# time.


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _published_order(x):
    """Lanes stored de-interleaved (``2i`` at ``i``, ``2i + 1`` at ``d/2 + i``)
    back in the published order."""
    import jax.numpy as jnp

    d = x.shape[-1]
    return jnp.stack([x[..., : d // 2], x[..., d // 2:]], axis=-1).reshape(*x.shape[:-1], d)


def _rope(x, pos, theta):
    """Interleaved rotary embedding (the DeepSeek-V3 family's pairing: lanes
    ``2i`` and ``2i + 1`` turn together) of ``x`` [T, ..., d] at ``pos`` [T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1).reshape(x.shape)


def _attention(x, w, shape):
    """Latent attention over one sequence, expanded.  ``x`` [T, E], float32;
    ``w(name)`` gives a matrix of this sublayer."""
    import jax
    import jax.numpy as jnp

    T, dn, eps, theta = x.shape[0], shape["dn"], shape["eps"], shape["theta"]
    pos = jnp.arange(T)
    c_q = shape["s_q"] * _rms(x @ w("w_dq"), w("q_norm"), eps)
    q_nope = (c_q @ w("w_uq_nope").T).reshape(T, -1, dn)
    q_pe = _rope(_published_order(jnp.einsum("tr,dhr->thd", c_q, w("w_uq_pe"))), pos, theta)
    c = shape["s_kv"] * _rms(x @ w("w_dkv"), w("kv_norm"), eps)
    k_pe = _rope(_published_order(x @ w("w_dk_pe")), pos, theta)  # one key, not scaled
    k_nope = jnp.einsum("sc,hcd->shd", c, w("w_uk"))
    v = jnp.einsum("sc,hcd->shd", c, w("w_uv"))
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope) + jnp.einsum("thd,sd->hts", q_pe, k_pe))
    s = s / math.sqrt(dn + q_pe.shape[-1])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, -1) @ w("wo")


def _swiglu(x, gate, up, down):
    """``W_down(silu(W_gate x) * W_up x)``, the hidden width in blocks: a
    float32 copy of a 12,288-wide MLP is 0.9 GB, and the reference runs beside
    the engine's weights and cache.  ``gate``, ``up``, ``down`` give a block of
    columns (of rows, for ``down``) in float32."""
    import jax

    y = 0.0
    for lo in range(0, gate.width, MLP_BLOCK):
        cols = slice(lo, lo + MLP_BLOCK)
        y = y + (jax.nn.silu(x @ gate(cols)) * (x @ up(cols))) @ down(cols)
    return y


def _branch(x, layer, experts, l: int, shape):
    """``MoE(x)`` on this chip: ``sum w_i E_i(x)`` over each token's picks on
    the held experts, and ``(sum of w_i over its picks on identity experts)
    x``.  ``x`` [T, E].  One expert at a time over all tokens."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    scores = jax.nn.softmax(x @ layer["router"][l].astype(f32), axis=-1)
    _, picked = jax.lax.top_k(scores + layer["select_bias"][l].astype(f32)[None, :],
                              shape["top_k"])
    weight = shape["scale"] * jnp.take_along_axis(scores, picked, axis=-1)  # not renormalised
    y = jnp.sum(jnp.where(picked >= shape["real"], weight, 0.0), axis=-1, keepdims=True) * x
    for e in range(experts["w_gate"].shape[1]):
        on = jnp.sum(jnp.where(picked == shape["first"] + e, weight, 0.0), axis=-1, keepdims=True)
        y = y + on * _swiglu(x, *(_Columns(experts[k], (l, e), k == "w_down")
                                  for k in ("w_gate", "w_up", "w_down")))
    return y


class _Columns:
    """A block of a matrix's columns (rows, for an output projection) in
    float32: the float32 copy lives as long as its product."""

    def __init__(self, stack, at: tuple, rows: bool = False):
        self.stack, self.at, self.rows = stack, at, rows
        self.width = stack.shape[len(at) + (0 if rows else 1)]

    def __call__(self, block):
        import jax.numpy as jnp

        m = self.stack[self.at]
        return (m[block] if self.rows else m[:, block]).astype(jnp.float32)


def _layer(h, layer, experts, l: int, shape):
    """The double block, line by line as published."""
    import jax.numpy as jnp

    f32, eps = jnp.float32, shape["eps"]
    first, second = layer["sub"]
    w = lambda sub: (lambda name: sub[name][l].astype(f32))
    mlp = lambda sub: (_Columns(sub[k], (l,), k == "w_down") for k in ("w_gate", "w_up", "w_down"))
    u = h + _attention(_rms(h, w(first)("attn_norm"), eps), w(first), shape)
    x0 = _rms(u, w(first)("mlp_norm"), eps)
    m = _branch(x0, layer, experts, l, shape)
    v = u + _swiglu(x0, *mlp(first))
    ww = v + _attention(_rms(v, w(second)("attn_norm"), eps), w(second), shape)
    z = ww + _swiglu(_rms(ww, w(second)("mlp_norm"), eps), *mlp(second))
    return z + m


def _shape(hf: dict) -> dict:
    E = hf["hidden_size"]
    return {"dn": hf["qk_nope_head_dim"], "eps": hf.get("rms_norm_eps", 1e-5),
            "theta": float(hf["rope_theta"]), "top_k": hf["moe_topk"],
            "scale": float(hf.get("routed_scaling_factor", 1.0)),
            "s_q": math.sqrt(E / hf["q_lora_rank"]) if hf.get("mla_scale_q_lora") else 1.0,
            "s_kv": math.sqrt(E / hf["kv_lora_rank"]) if hf.get("mla_scale_kv_lora") else 1.0,
            "real": hf.get("router_num_experts", hf["n_routed_experts"]),
            "first": hf.get("routed_expert_offset", 0)}


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
        for l in range(params["layers"]["router"].shape[0]):
            h = _layer(h, params["layers"], params["experts"], l, shape)
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
    ``[cache layers, pages, page_size, entry lanes]`` (two cache layers a
    layer of the model), a ``v_cache`` of zero size, and while a frame runs
    the one side buffer; a control may put a broken ``decode`` program with
    its ``params`` into it, which the next step then runs.  Nothing is
    donated."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import importlib

        import jax

        cfg, inv_freq = runner.model_cfg, runner.inv_freq
        # the module itself: the runner's own handle has its choice of the
        # experts' products bound, and the drive makes that choice
        module = importlib.import_module("smg_tpu.models.longcat_flash")
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        self.tables = {}  # sequence -> the page table it was prefilled through
        self._prefill_under = lambda under: jax.jit(lambda p, *a: module.forward_prefill(
            p, under, inv_freq, *a, moe_impl=impl))
        self._decode_under = lambda under: jax.jit(lambda p, *a: module.forward_decode_horizon(
            p, under, inv_freq, *a, attn_impl=impl, moe_impl=impl))
        self._prefill, self._decode = self._prefill_under(cfg), self._decode_under(cfg)

    def _zeros(self, *lead):
        import jax.numpy as jnp

        spec = self.runner.spec
        return jnp.zeros((spec.num_layers, *lead, spec.lanes), jnp.dtype(spec.dtype))

    def empty(self, pages: int):
        return {"cache": self._zeros(pages, self.runner.spec.page_size), "side": None, "fed": ()}

    def prefill(self, state, seq, chunk, lo, n, table, program=None):
        """``program``: a broken ``(prefill, params)`` of a control that
        prefills the sequences again."""
        import jax.numpy as jnp

        self.tables[seq] = table
        prefill, params = program or (self._prefill, self.runner.params)
        out, cache, _v = prefill(
            params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            state["cache"], self._zeros(0, self.runner.spec.page_size), jnp.asarray(table))
        return out, {**state, "cache": cache, "fed": (*state["fed"], (seq, chunk, lo, n, table))}

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

    def _unweighted(self):
        """The decode program, traced while ``ops/moe.identity_picks`` is one
        that weighs every identity pick 1."""
        import importlib

        import jax.numpy as jnp

        moe = importlib.import_module("smg_tpu.ops.moe")
        decode = self._decode_under(self.runner.model_cfg)  # its own trace, at its first call

        def unweighted(x, routing, first):
            on = routing.experts >= first
            return (jnp.sum(on, axis=-1, keepdims=True) * x.astype(jnp.float32),
                    jnp.sum(on).astype(jnp.int32))

        def program(*args):
            sound, moe.identity_picks = moe.identity_picks, unweighted
            try:
                return decode(*args)
            finally:
                moe.identity_picks = sound

        return program

    def controls(self, state) -> dict:
        """Nine broken states, each of which must miss the tolerance as the
        wrong page does.

        Two break the cache of sequence 0 as ``architectures/pangu_ultra_moe.py``
        does, for its reasons: the rotary lanes of its pages zeroed, and the
        latent lanes of its second page taken from sequence 1's.  One swaps
        what the two sublayers of every layer left: cache layers ``2l`` and
        ``2l + 1`` exchanged (a runner that numbers the cache by the model's
        layers, or the sublayers the other way round, reads that).

        Two are the branch's two kinds of result, each gone **in what the
        sequences hold and in the step alike**: the sequences are prefilled
        again by the broken program, because a row sends one pick in four
        layers to the 16 outputs of 768 this chip holds and one row in three
        sends none, so what its context lost has to show.  *The held experts
        give nothing* (``architectures/exaone_moe.py``'s control): no token is
        ever sent to an expert this chip holds (their selection bias at
        -1e4), so the grouped products add nothing anywhere, which is what a
        product that returned zeros would leave; through the bias it takes no
        second copy of the experts' projections.  *The identity term dropped*:
        the program of a configuration without identity experts over the same
        router, to which a pick past the real experts is one on an expert
        held elsewhere and adds nothing.

        Four run the step as a broken program over the sound cache.  An
        identity pick unweighted: the step traced with ``ops/moe.identity_picks``
        adding the token itself for every identity pick, not ``w_i`` times it.
        Each of the two scales left out (``mla_scale_q_lora``,
        ``mla_scale_kv_lora`` read as false; the second over a cache whose
        latents are what such a program would have left, the sound ones
        divided by the scale).  And the router cut to the experts held
        (``architectures/pangu_ultra_moe.py``'s fault of reading the experts
        held for the router's width: no identity outputs either), so that all
        12 picks of the row land on held experts.

        What makes the branch audible at 16 held of 768: the program's random
        routers read lanes of the stream that only the embedding writes
        (``models/longcat_flash.init_params``), so this file's float32 routing
        and the program's bfloat16 routing pick the same outputs, no sound row
        differs by a whole pick, and the experts can be drawn as loud as the
        comparison needs (PERF.md, Findings, PR 43)."""
        import dataclasses

        cfg = self.runner.model_cfg
        rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        cache = state["cache"]
        mine = self.tables[0]
        own, other = int(mine[1]), int(self.tables[1][1])
        first, count = cfg.held_experts
        params = self.runner.params
        layers = params["layers"]
        under = lambda **changed: (self._decode_under(dataclasses.replace(cfg, **changed)), params)
        narrow = dataclasses.replace(cfg, num_experts=count, zero_experts=0,
                                     experts_held=(0, count))
        cut = {**layers, "router": layers["router"][..., first:first + count],
               "select_bias": layers["select_bias"][..., first:first + count]}
        unpicked = {**params, "layers": {**layers, "select_bias": layers["select_bias"]
                                         .at[..., first:first + count].set(-1e4)}}
        blind = dataclasses.replace(cfg, zero_experts=0)

        def again(prefill, decode, broken):
            """The sequences prefilled by a broken program, and its step."""
            fresh = self.empty(cache.shape[1])
            for fed in state["fed"]:
                _, fresh = self.prefill(fresh, *fed, program=(prefill, broken))
            return {**fresh, "decode": (decode, broken)}

        pairs = cache.reshape(-1, 2, *cache.shape[1:])
        return {
            "rotary_lanes_zeroed": {
                **state, "cache": cache.at[:, mine, :, rkv:rkv + dr].set(0)},
            "latent_of_other_sequence": {
                **state, "cache": cache.at[:, own, :, :rkv].set(cache[:, other, :, :rkv])},
            "sublayer_caches_swapped": {
                **state, "cache": pairs[:, ::-1].reshape(cache.shape)},
            "held_experts_give_nothing": again(self._prefill, self._decode, unpicked),
            "identity_term_dropped": again(self._prefill_under(blind),
                                           self._decode_under(blind), params),
            "identity_picks_unweighted": {**state, "decode": (self._unweighted(), params)},
            "q_scale_left_out": {**state, "decode": under(mla_q_scale=1.0)},
            "kv_scale_left_out": {
                **state, "decode": under(mla_kv_scale=1.0),
                "cache": cache.at[..., :rkv].multiply(1.0 / cfg.mla_kv_scale)},
            "router_cut_to_held": {
                **state, "decode": (self._decode_under(narrow), {**params, "layers": cut})},
        }


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rq, rkv = hf["q_lora_rank"], hf["kv_lora_rank"]
    attention = E * rq + rq * H * (dn + dr) + E * (rkv + dr) + rkv * H * (dn + dv) + H * dv * E
    real = hf.get("router_num_experts", hf["n_routed_experts"])
    return {"E": E, "H": H, "dn": dn, "dr": dr, "dv": dv, "rkv": rkv,
            "attention": attention, "expert": 3 * E * hf["expert_ffn_hidden_size"],
            "dense_mlp": 3 * E * hf["ffn_hidden_size"],
            "router": E * (real + hf.get("zero_expert_num", 0)),
            "held": hf["n_routed_experts"], "layers": hf["num_layers"],
            "vocab": hf["vocab_size"] * E}


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms and the selection bias are
    under a hundredth of a percent and left out).  ``always``: what every token
    passes whatever the routing (two attentions, two dense MLPs and the router
    a layer); ``routed``: the held routed experts.  The identity experts have
    no parameters."""
    w = _widths(hf)
    always = w["layers"] * (2 * w["attention"] + 2 * w["dense_mlp"] + w["router"])
    routed = w["layers"] * w["held"] * w["expert"]
    return {"always": always, "routed": routed, "layers": always + routed,
            "embed": w["vocab"], "lm_head": w["vocab"], "matmul": always + routed + w["vocab"],
            "total": always + routed + 2 * w["vocab"]}


def latent_entry_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes one token leaves in the cache in one cache layer, as published:
    ``kv_lora_rank + qk_rope_head_dim`` numbers (the program lays them out on
    whole 128-lane tiles and reports both, ``loads()["latent_cache"]``)."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * dtype_bytes


def attention_layers(hf: dict) -> int:
    """Cache layers: every layer runs the decode attention kernel twice a
    column, once a sublayer."""
    return 2 * hf["num_layers"]


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    return attention_layers(hf) * latent_entry_bytes(hf, dtype_bytes)


def mla_decode_flops_per_token(hf: dict) -> int:
    """FLOPs of absorbed decode attention for one cached token of one lane in
    one cache layer: every head's score over the entry and its weighted sum
    of the latent."""
    w = _widths(hf)
    return 2 * w["H"] * ((w["rkv"] + w["dr"]) + w["rkv"])


def expert_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one real routed expert's three matrices (an identity expert
    has none)."""
    return _widths(hf)["expert"] * dtype_bytes


def expert_flops_per_row(hf: dict) -> int:
    """FLOPs of one real routed expert for one row (an identity pick is one
    multiply-add a lane of the hidden vector, counted as nothing)."""
    return 2 * _widths(hf)["expert"]


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: what every column reads
    whatever the routing (the attentions, the dense MLPs, the routers, the
    head's slice) once a column, and the live lanes' latent entries in every
    cache layer.  **The routed experts are not counted** (this function is
    given neither the experts hit nor the rows routed here, and an identity
    pick reads nothing), so the share built on it errs low;
    ``kernels.scmoe_experts_decode_roofline_share`` counts the experts hit."""
    p = param_count(hf)
    weight_bytes = (p["always"] + p["lm_head"]) * dtype_bytes * columns
    kv = kv_bytes_per_token(hf, dtype_bytes) * lane_tokens
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    parameter every token passes whatever the routing, and expanded
    attention's ``2 x heads x (dn + dr + dv)`` FLOPs for every (query, key)
    pair of the causal triangle in every attention sublayer.  **The routed
    experts are left out** (no argument says how many rows were routed here;
    a token's expected 0.25 rows a layer are 9 M of the 648 M parameters it
    passes there), and an identity pick computes nothing."""
    w = _widths(hf)
    p = param_count(hf)
    flops = (2.0 * p["always"] * new_tokens
             + 2.0 * w["H"] * (w["dn"] + w["dr"] + w["dv"]) * attention_layers(hf) * attn_pairs)
    return flops / (chips * peak["flops_per_s"])
