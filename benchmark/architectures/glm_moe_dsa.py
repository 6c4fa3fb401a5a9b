"""GLM-5.2 (``model_type: glm_moe_dsa``): latent attention that reads a learned
selection of the cache (an indexer with a key cache of its own, one selection
shared by the layers behind it), routed experts beside a shared one, pre-norm
block.  The program serves it from ``smg_tpu/models/glm_moe_dsa.py``, whose
docstring has the equations; this file is the one plain reference of them, in
the published order, and it imports nothing of ``smg_tpu/models``.

What an architecture file gives (README, "An architecture"): ``logits``, the
plain reference (one sequence, keys and values of every head rebuilt from the
latent, no cache, no kernel, no batching, the selection by a plain stable sort
of every row of float32 scores, every routed expert a plain matrix product over
all tokens and a mask; queries in blocks of ``QUERY_BLOCK`` rows so that a
16,000-token sequence fits beside the weights); ``impls`` and ``drive``, the
serving forward as ``reference.check_engine`` drives it, with controls of its
own; the four costs; and for the cell's readers ``attention_layers``,
``index_layers``, ``latent_entry_bytes``, ``index_key_bytes``,
``mla_decode_flops_per_token``, ``index_decode_flops_per_token``,
``expert_bytes`` and ``expert_flops_per_row``.

**The chip's share.**  The configuration holds ``n_routed_experts`` of the
router's ``router_num_experts`` experts (the range from ``routed_expert_offset``)
and a slice of the vocabulary.  The reference is given the same share: it
routes over the router's whole width and adds what the held experts give and
the shared expert; what the absent ones would add is left out, here and in the
program alike.

**What the reference reads of the program's storage** (its parameters are the
engine's own): ``W_uq`` and ``W_dkv`` in the parts ``models/pangu_moe.py``
stores; the rotary rows of both, the first ``qk_rope_head_dim`` lanes of the
indexer's ``W^I_q`` and ``W^I_k`` and of the index key's norm weight and bias
de-interleaved: the reference puts them back in the published order and turns
the published pairs ``(2i, 2i + 1)``; the indexers' weights as one stack over
the ``full`` layers.
"""

from __future__ import annotations

import math

VOCAB_BLOCK = 16384
MLP_BLOCK = 4096  # hidden columns of an MLP multiplied at a time
QUERY_BLOCK = 256  # rows whose scores (by head, over the whole sequence) live together


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
    """Interleaved rotary embedding (lanes ``2i`` and ``2i + 1`` turn
    together) of ``x`` [T, ..., d] at ``pos`` [T]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), d // 2)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1).reshape(x.shape)


def _turned(x, pos, dr, theta):
    """``x`` [T, ..., D] with its first ``dr`` lanes (stored de-interleaved)
    in the published order and rotated."""
    import jax.numpy as jnp

    return jnp.concatenate([_rope(_published_order(x[..., :dr]), pos, theta), x[..., dr:]], axis=-1)


def select(scores, k: int, recent: bool = False):
    """``S_t`` of every row of ``scores`` [n, T] (float32; row ``i`` is the
    query at ``rows[i]``, given as ``-inf`` past it), as a mask: the ``min(t +
    1, k)`` largest, equal scores to the lower position, by a stable sort.
    ``recent`` (a control's): the ``k`` nearest positions instead."""
    import jax.numpy as jnp

    n, T = scores.shape
    seen = scores > -jnp.inf
    if recent:
        last = jnp.sum(seen, axis=-1, keepdims=True)  # t + 1
        return seen & (jnp.arange(T)[None, :] >= last - k)
    order = jnp.argsort(-scores, axis=-1, stable=True)  # descending, ties by position
    rank = jnp.zeros((n, T), jnp.int32).at[jnp.arange(n)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (n, T)))
    return seen & (rank < k)


def index_scores(x, c_q, ix, shape, lo: int, hi: int, keys):
    """``I[t, s]`` [hi - lo, T] of the queries ``lo .. hi`` against the index
    keys ``keys`` [T, D], ``-inf`` where ``s > t``."""
    import jax
    import jax.numpy as jnp

    T = keys.shape[0]
    pos = jnp.arange(lo, hi)
    q = _turned(jnp.einsum("tr,jrd->tjd", c_q[lo:hi], ix("wq")), pos, shape["dr"], shape["theta"])
    w = (x[lo:hi] @ ix("ww")) * (shape["J"] * shape["D"]) ** -0.5
    scores = jnp.einsum("tjs,tj->ts", jax.nn.relu(jnp.einsum("tjd,sd->tjs", q, keys)), w)
    return jnp.where(pos[:, None] >= jnp.arange(T)[None, :], scores, -jnp.inf)


def index_keys(x, ix, shape):
    """``k^I`` [T, D] of the normed tokens ``x`` [T, E]."""
    import jax.numpy as jnp

    k = x @ ix("wk")
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k / jnp.sqrt(jnp.mean(k * k, axis=-1, keepdims=True) + shape["eps"])
    return _turned(k * ix("k_norm") + ix("k_bias"), jnp.arange(x.shape[0]), shape["dr"],
                   shape["theta"])


def _attention(x, w, shape, ix, chosen, recent: bool):
    """Latent attention over one sequence under the selection.  ``x`` [T, E],
    float32; ``w(name)`` gives a matrix of this layer, ``ix(name)`` one of its
    indexer (None: a ``shared`` layer, which reads ``chosen`` [T, T], the mask
    of the ``full`` layer before it).  Returns the layer's output and the
    selection it read."""
    import jax
    import jax.numpy as jnp

    T, dn, dr, eps, theta = x.shape[0], shape["dn"], shape["dr"], shape["eps"], shape["theta"]
    pos = jnp.arange(T)
    c_q = _rms(x @ w("w_dq"), w("q_norm"), eps)
    c = _rms(x @ w("w_dkv"), w("kv_norm"), eps)
    k_pe = _rope(_published_order(x @ w("w_dk_pe")), pos, theta)
    k_nope = jnp.einsum("sc,hcd->shd", c, w("w_uk"))
    v = jnp.einsum("sc,hcd->shd", c, w("w_uv"))
    keys = index_keys(x, ix, shape) if ix is not None else None
    out, masks = [], []
    block = max(32, min(QUERY_BLOCK, QUERY_BLOCK * 8192 // T))  # [heads, block, T] in float32
    for lo in range(0, T, block):
        hi = min(lo + block, T)
        if ix is not None:
            mask = select(index_scores(x, c_q, ix, shape, lo, hi, keys), shape["topk"], recent)
        else:
            mask = chosen[lo:hi]
        masks.append(mask)
        q_nope = (c_q[lo:hi] @ w("w_uq_nope").T).reshape(hi - lo, -1, dn)
        q_pe = _rope(_published_order(jnp.einsum("tr,dhr->thd", c_q[lo:hi], w("w_uq_pe"))),
                     pos[lo:hi], theta)
        s = (jnp.einsum("thd,shd->hts", q_nope, k_nope) + jnp.einsum("thd,sd->hts", q_pe, k_pe))
        s = jnp.where(mask[None], s / math.sqrt(dn + dr), -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v).reshape(hi - lo, -1))
    return jnp.concatenate(out) @ w("wo"), jnp.concatenate(masks)


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


def _swiglu(x, gate, up, down):
    """``W_down(silu(W_gate x) * W_up x)``, the hidden width in blocks."""
    import jax

    y = 0.0
    for lo in range(0, gate.width, MLP_BLOCK):
        cols = slice(lo, lo + MLP_BLOCK)
        y = y + (jax.nn.silu(x @ gate(cols)) * (x @ up(cols))) @ down(cols)
    return y


def _mlp(stack, at: tuple, names):
    return (_Columns(stack[k], at, k == names[2]) for k in names)


def _routed(x, layer, experts, i: int, shape):
    """``sum_i w_i E_i(x)`` over each token's picks on the held experts: the
    ``top_k`` largest of ``sigmoid + bias``, weighed by the sigmoid alone,
    renormalised, times the scaling factor.  One expert at a time."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    scores = jax.nn.sigmoid(x @ layer["router"][i].astype(f32))
    _, picked = jax.lax.top_k(scores + layer["select_bias"][i].astype(f32)[None, :],
                              shape["top_k"])
    top = jnp.take_along_axis(scores, picked, axis=-1)
    if shape["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * shape["scale"]
    y = jnp.zeros_like(x)
    for e in range(experts["w_gate"].shape[1]):
        on = jnp.sum(jnp.where(picked == shape["first"] + e, top, 0.0), axis=-1, keepdims=True)
        y = y + on * _swiglu(x, *_mlp(experts, (i, e), ("w_gate", "w_up", "w_down")))
    return y


def _shape(hf: dict) -> dict:
    return {"dn": hf["qk_nope_head_dim"], "dr": hf["qk_rope_head_dim"],
            "eps": hf.get("rms_norm_eps", 1e-5),
            "theta": float(hf["rope_parameters"]["rope_theta"]),
            "topk": hf["index_topk"], "J": hf["index_n_heads"], "D": hf["index_head_dim"],
            "top_k": hf["num_experts_per_tok"],
            "norm_topk": bool(hf.get("norm_topk_prob", True)),
            "scale": float(hf.get("routed_scaling_factor", 1.0)),
            "first": hf.get("routed_expert_offset", 0)}


def hidden(params, hf: dict, tokens, recent: bool = False):
    """The stream behind the last layer, [T, E] float32 (``recent``: under the
    control's selection, the nearest ``index_topk`` positions)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    shape = _shape(hf)
    kinds = hf["indexer_types"]
    dense_layers = params["dense"]["wo"].shape[0]
    h = params["embed"][jnp.asarray(tokens)].astype(f32)
    chosen, place = None, -1
    for l, kind in enumerate(kinds):
        name, i = ("dense", l) if l < dense_layers else ("moe", l - dense_layers)
        w = lambda key, name=name, i=i: params[name][key][i].astype(f32)
        ix = None
        if kind == "full":
            place += 1
            ix = lambda key, place=place: params["indexer"][key][place].astype(f32)
        a, chosen = _attention(_rms(h, w("attn_norm"), shape["eps"]), w, shape, ix, chosen, recent)
        h = h + a
        x = _rms(h, w("mlp_norm"), shape["eps"])
        if name == "dense":
            h = h + _swiglu(x, *_mlp(params["dense"], (i,), ("w_gate", "w_up", "w_down")))
        else:
            h = h + (_routed(x, params["moe"], params["experts"], i, shape)
                     + _swiglu(x, *_mlp(params["moe"], (i,), ("ws_gate", "ws_up", "ws_down"))))
    return h


def logits(params, hf: dict, tokens, rows, recent: bool = False):
    """Reference logits [len(rows), V] (numpy float32) of one sequence of
    token ids at the positions ``rows``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        h = hidden(params, hf, tokens, recent)
        h = _rms(h[jnp.asarray(rows)], params["final_norm"].astype(f32), _shape(hf)["eps"])
        table, out = params["lm_head"], []
        for lo in range(0, table.shape[1], VOCAB_BLOCK):
            out.append(np.asarray(h @ table[:, lo:lo + VOCAB_BLOCK].astype(f32)))
    return np.concatenate(out, axis=-1)


# --------------------------------------------------------------------------
# the drive of the serving forward


def impls(runner, rehearsal: bool) -> list:
    """The implementations the runner's dispatch can pick: XLA's ragged product
    for the experts, and the kernel (interpreted in the rehearsal).  The
    attention over the selection is XLA's under both."""
    out = ["xla"]
    if runner.attn_impl != "xla":
        out.append("pallas")
    elif rehearsal:
        out.append("pallas_interpret")
    return out


class Drive:
    """``forward_prefill`` and ``forward_decode_horizon`` under one
    implementation.  The state is the runner's own layout: the latent cache
    ``[layers, pages, page_size, entry lanes]``, the index keys ``[full layers,
    pages, page_size, index_head_dim]`` on the same pages, and while a frame
    runs the two side buffers; a control may put a broken ``decode`` program
    with its ``params`` into it, which the next step then runs.  Nothing is
    donated."""

    def __init__(self, runner, impl: str, lanes: int, horizon: int):
        import importlib

        import jax

        cfg, inv_freq = runner.model_cfg, runner.inv_freq
        # the module itself: the runner's own handle has its choice of the
        # experts' products bound, and the drive makes that choice
        self.module = module = importlib.import_module("smg_tpu.models.glm_moe_dsa")
        self.runner, self.lanes, self.horizon = runner, lanes, horizon
        self.tables, self.held = {}, {}  # sequence -> its page table, the tokens it holds
        self._prefill = jax.jit(lambda p, *a: module.forward_prefill(
            p, cfg, inv_freq, *a, moe_impl=impl))
        self._decode_under = lambda under: jax.jit(lambda p, *a: module.forward_decode_horizon(
            p, under, inv_freq, *a, attn_impl=impl, moe_impl=impl))
        self._decode = self._decode_under(cfg)

    def _zeros(self, index: bool, *lead):
        import jax.numpy as jnp

        spec = self.runner.spec
        layers, lanes = ((spec.index_layers, spec.index_lanes) if index
                         else (spec.num_layers, spec.lanes))
        return jnp.zeros((layers, *lead, lanes), jnp.dtype(spec.dtype))

    def empty(self, pages: int):
        ps = self.runner.spec.page_size
        return {"cache": self._zeros(False, pages, ps), "keys": self._zeros(True, pages, ps),
                "side": None}

    def prefill(self, state, seq, chunk, lo, n, table):
        import jax.numpy as jnp

        self.tables[seq], self.held[seq] = table, lo + n
        out, cache, keys = self._prefill(
            self.runner.params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(n),
            state["cache"], state["keys"], jnp.asarray(table))
        return out, {**state, "cache": cache, "keys": keys}

    def decode(self, state, tokens, positions, entry, column, page_tables):
        import jax.numpy as jnp
        import numpy as np

        side = state["side"]
        if column == 0:
            side = (self._zeros(False, self.lanes, self.horizon),
                    self._zeros(True, self.lanes, self.horizon))
        live = np.asarray(entry) < page_tables.shape[1] * self.runner.spec.page_size
        decode, params = state.get("decode", (self._decode, self.runner.params))
        out, side, _counts = decode(
            params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(entry),
            jnp.int32(column), (state["cache"], state["keys"]), jnp.asarray(page_tables), side,
            jnp.asarray(live))
        return out, {**state, "side": side}

    def _nearest(self):
        """The decode program, traced while the module's ``select_decode`` is
        ``nearest_places``."""
        module = self.module
        decode = self._decode_under(self.runner.model_cfg)  # its own trace, at its first call

        def program(*args):
            sound, module.select_decode = module.select_decode, nearest_places
            try:
                return decode(*args)
            finally:
                module.select_decode = sound

        return program

    def controls(self, state) -> dict:
        """Broken states, each of which must miss the tolerance as the wrong
        page does.  Two are ``architectures/pangu_ultra_moe.py``'s, for its
        reasons: the rotary lanes of sequence 0's pages zeroed, and the router
        cut to the experts held.  (Its third, one page's latent lanes from the
        other sequence with the rotary keys right, is the shared wrong page at
        less than its strength, and under weights whose heads share a part of
        their scores one page of 44 is heard by whether it holds a token they
        all weigh: on a v5e it read 0.30-1.56 over six seeds where the shared
        control read 0.45-1.14, so it is not offered: PERF.md, Findings,
        PR 55.)  Two are the selector's, **offered only where both prefilled
        sequences pass ``index_topk``** (below it every cached token is
        selected whatever the index keys say, and both read what the sound
        state reads): the index keys of sequence 0's second page taken from
        sequence 1's (the entries are right; the sixteen tokens are scored by
        another sequence's keys, so those the attention weighs fall out of the
        selection and others come in), and the step run as a program that
        selects the nearest ``index_topk`` tokens whatever the indexer
        scored."""
        import dataclasses

        cfg = self.runner.model_cfg
        rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        cache, keys = state["cache"], state["keys"]
        mine = self.tables[0]
        own, other = int(mine[1]), int(self.tables[1][1])
        first, count = cfg.held_experts
        params = self.runner.params
        narrow = dataclasses.replace(cfg, num_experts=count, experts_held=(0, count))
        cut = {**params["moe"], "router": params["moe"]["router"][..., first:first + count],
               "select_bias": params["moe"]["select_bias"][..., first:first + count]}
        out = {
            "rotary_lanes_zeroed": {
                **state, "cache": cache.at[:, mine, :, rkv:rkv + dr].set(0)},
            "router_cut_to_held": {
                **state, "decode": (self._decode_under(narrow), {**params, "moe": cut})},
        }
        if min(self.held.values()) > cfg.index_topk:
            out["index_keys_of_other_sequence"] = {
                **state, "keys": keys.at[:, own].set(keys[:, other])}
            out["nearest_selected"] = {**state, "decode": (self._nearest(), params)}
        return out


def nearest_places(q, w, keys, side_keys, entry_positions, n_extra, k):
    """A control's stand-in for ``ops/sparse_attention.select_decode``: the
    nearest ``k`` places, whatever the indexer scored."""
    import jax
    import jax.numpy as jnp

    S, N = keys.shape[1], side_keys.shape[1]
    place = jnp.arange(S + N)[None, :]
    valid = jnp.where(place < S, place < entry_positions[:, None], place - S < n_extra)
    # a place's position: the pages hold what lies below the entry
    pos = jnp.where(place < S, place, entry_positions[:, None] + place - S)
    top, ids = jax.lax.top_k(jnp.where(valid, pos, -1), min(k, S + N))
    return ids.astype(jnp.int32), top >= 0


drive = Drive


# --------------------------------------------------------------------------
# costs: operations and bytes the algorithm needs, computed from shapes.


def _widths(hf: dict) -> dict:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rq, rkv = hf["q_lora_rank"], hf["kv_lora_rank"]
    J, D = hf["index_n_heads"], hf["index_head_dim"]
    attention = E * rq + rq * H * (dn + dr) + E * (rkv + dr) + rkv * H * (dn + dv) + H * dv * E
    expert = 3 * E * hf["moe_intermediate_size"]
    dense_layers = hf.get("first_k_dense_replace", 0)
    return {"E": E, "H": H, "dn": dn, "dr": dr, "dv": dv, "rkv": rkv, "J": J, "D": D,
            "attention": attention, "indexer": rq * J * D + E * D + E * J, "expert": expert,
            "dense_mlp": 3 * E * hf["intermediate_size"],
            "router": E * hf.get("router_num_experts", hf["n_routed_experts"]),
            "shared": hf.get("n_shared_experts", 0) * expert,
            "held": hf["n_routed_experts"], "layers": hf["num_hidden_layers"],
            "dense_layers": dense_layers,
            "expert_layers": hf["num_hidden_layers"] - dense_layers,
            "vocab": hf["vocab_size"] * E}


def attention_layers(hf: dict) -> int:
    """Every layer attends over a selection once a column."""
    return hf["num_hidden_layers"]


def index_layers(hf: dict) -> int:
    """Layers with an indexer: each scores a lane's whole context once a
    column and keeps one index key a token."""
    return sum(1 for t in hf["indexer_types"] if t == "full")


def param_count(hf: dict) -> dict:
    """Parameters by role (matmul weights; the norms, the index keys' norm and
    the selection bias are under a hundredth of a percent and left out).
    ``always``: what every token passes whatever the routing (attention, the
    indexers, the dense MLPs, the shared experts, the routers); ``routed``:
    the held routed experts."""
    w = _widths(hf)
    always = (w["layers"] * w["attention"] + index_layers(hf) * w["indexer"]
              + w["dense_layers"] * w["dense_mlp"]
              + w["expert_layers"] * (w["shared"] + w["router"]))
    routed = w["expert_layers"] * w["held"] * w["expert"]
    head = 0 if hf.get("tie_word_embeddings") else w["vocab"]
    return {"always": always, "routed": routed, "layers": always + routed,
            "embed": w["vocab"], "lm_head": head, "matmul": always + routed + w["vocab"],
            "total": always + routed + w["vocab"] + head}


def latent_entry_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes one token leaves in the latent cache in one layer, as published:
    ``kv_lora_rank + qk_rope_head_dim`` numbers."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * dtype_bytes


def index_key_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes one token leaves in the index-key cache in one layer with an
    indexer: ``index_head_dim`` numbers."""
    return hf["index_head_dim"] * dtype_bytes


def kv_bytes_per_token(hf: dict, dtype_bytes: int = 2) -> int:
    """What a token holds in both caches over all layers."""
    return (attention_layers(hf) * latent_entry_bytes(hf, dtype_bytes)
            + index_layers(hf) * index_key_bytes(hf, dtype_bytes))


def mla_decode_flops_per_token(hf: dict) -> int:
    """FLOPs of absorbed decode attention for one selected token of one lane
    in one layer: every head's score over the entry and its weighted sum of
    the latent."""
    w = _widths(hf)
    return 2 * w["H"] * ((w["rkv"] + w["dr"]) + w["rkv"])


def index_decode_flops_per_token(hf: dict) -> int:
    """FLOPs of the indexer for one cached token of one lane in one layer with
    an indexer: every index head's product with the key, rectified and
    weighed (one multiply-add a head)."""
    w = _widths(hf)
    return 2 * w["J"] * w["D"] + 2 * w["J"]


def expert_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one routed expert's three matrices."""
    return _widths(hf)["expert"] * dtype_bytes


def expert_flops_per_row(hf: dict) -> int:
    """FLOPs of one routed expert for one row."""
    return 2 * _widths(hf)["expert"]


def selected(context: int, hf: dict) -> int:
    """Cached tokens a query behind ``context`` tokens attends."""
    return min(context, hf["index_topk"])


def decode_min_seconds(hf: dict, columns: float, lane_tokens: float, chips: int,
                       peak: dict, dtype_bytes: int = 2) -> float:
    """Least time for ``columns`` decode columns: what every column reads
    whatever the routing (attention and indexer weights, the dense MLPs, the
    shared experts, the routers, the head's slice) once a column, every live
    lane-token's index key in the layers with an indexer, and the selected
    entries.  **It errs low twice**: the routed experts are not counted (this
    function is given neither the experts hit nor the rows routed here), and
    it is given the lanes' contexts as one sum, from which ``min(context,
    index_topk)`` of each lane cannot be had: the entries are counted as
    ``lane_tokens x index_topk / max_position_embeddings``, which no mix of
    lanes reads less than.  ``kernels.dsa_attn_decode_roofline_share`` and
    ``kernels.dsa_moe_decode_roofline_share`` take each lane's context and the
    experts hit."""
    p = param_count(hf)
    weight_bytes = (p["always"] + p["lm_head"]) * dtype_bytes * columns
    least_share = min(1.0, hf["index_topk"] / hf["max_position_embeddings"])
    kv = lane_tokens * (index_layers(hf) * index_key_bytes(hf, dtype_bytes)
                        + attention_layers(hf) * latent_entry_bytes(hf, dtype_bytes) * least_share)
    return (weight_bytes + kv) / (chips * peak["bytes_per_s"])


def prefill_min_seconds(hf: dict, new_tokens: float, attn_pairs: float, chips: int,
                        peak: dict) -> float:
    """Least time to prefill ``new_tokens`` prompt tokens: 2 FLOPs for every
    parameter every token passes whatever the routing, the indexers' products
    for every (query, key) pair of the causal triangle in the layers that have
    one, and expanded attention's ``2 x heads x (dn + dr + dv)`` FLOPs for the
    pairs a selection keeps.  **It errs low**: the routed experts are left out
    (no argument says how many rows were routed here), and of the triangle's
    pairs the selection keeps ``min(t + 1, index_topk)`` a row, which a sum
    over rows does not give: they are counted as ``attn_pairs x index_topk /
    max_position_embeddings``, which no mix of rows keeps fewer than."""
    w = _widths(hf)
    p = param_count(hf)
    least_share = min(1.0, hf["index_topk"] / hf["max_position_embeddings"])
    flops = (2.0 * p["always"] * new_tokens
             + index_decode_flops_per_token(hf) * index_layers(hf) * attn_pairs
             + 2.0 * w["H"] * (w["dn"] + w["dr"] + w["dv"]) * attention_layers(hf)
             * attn_pairs * least_share)
    return flops / (chips * peak["flops_per_s"])
