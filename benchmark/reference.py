"""The plain reference and the comparison that decides ``correct``.

``dense_logits`` is the architecture's forward pass in straightforward
``jax.numpy`` and float32: no kernels, no cache, no batching tricks, matrix
multiplications at ``highest`` precision.  It follows the published
description of the Llama-family block the two configurations share
(pre-norm attention with grouped KV heads and rotate-half rope, SwiGLU MLP),
with the two published departures switched by the configuration itself:
Qwen3's per-head RMSNorm on q and k before rope, and tied or untied output
embeddings.  It reads the engine's own parameters and upcasts them one layer
at a time (4 B parameters in float32 do not fit beside the engine); under a
mesh the slices stay sharded as the engine sharded them.

``check_engine`` drives the serving forward (paged cache, chunked prefill
behind a live prefix, decode through the horizon side buffers) with the
attention implementations the engine's dispatch rule can pick, and compares
logits.  The layout of the test is ``chip_smoke.py`` phase b's; the
arithmetic of the reference is this file's own.
"""

from __future__ import annotations

import math
from functools import partial

# Largest |logit - reference| allowed, in units of the reference row's
# standard deviation.  The serving path rounds activations and the cache to
# bfloat16 in every layer: at Qwen3-1.7B's 28 layers that alone measured 0.114
# to 0.150 on a v5e, under XLA attention and under the Pallas kernels alike,
# while one wrong page of a sequence's 44 (the control below) measured 1.39 to
# 1.41 (PR 24's chip runs; chip_smoke.py has 0.095-0.119 and 1.2 at 16 layers).
# 0.30 is twice the first and under a quarter of the second.
LOGIT_TOLERANCE = 0.30

VOCAB_BLOCK = 16384


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


def dense_logits(params, hf: dict, tokens, rows):
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


def check_engine(engine, hf: dict, seed: int, rehearsal: bool) -> dict:
    """Serving-path logits against ``dense_logits`` for two seeded sequences
    whose pages interleave in one shuffled pool: each is prefilled in two
    chunks (the second behind a live prefix), then both decode together
    beside six padded rows for four steps.  The control decodes one step
    through a table with one wrong page and must miss the tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    runner = engine.runner
    cfg, module, params = runner.model_cfg, runner.module, runner.params
    inv_freq = runner.inv_freq
    lens, splits, T = ((88, 40), (48, 16), 64) if rehearsal else ((700, 330), (380, 130), 512)
    n_dec, ps, mp, B = 4, runner.spec.page_size, 64, 8
    impls = ["xla"]
    if runner.attn_impl != "xla":
        impls.append("pallas")
    elif rehearsal and runner.mesh is None and (cfg.num_kv_heads * cfg.head_dim) % 128 == 0:
        impls.append("pallas_interpret")
    rng = np.random.default_rng(seed)
    toks = [rng.integers(2, cfg.vocab_size, size=n + n_dec).astype(np.int32) for n in lens]
    ref = [dense_logits(params, hf, t, list(range(n - 1, n + n_dec)))
           for t, n in zip(toks, lens)]  # row j: logits after token n-1+j

    P = 2 * mp + 1  # page 0 is the garbage page
    perm = rng.permutation(np.arange(1, P)).astype(np.int32)
    tables = [perm[0::2], perm[1::2]]
    KD = cfg.num_kv_heads * cfg.head_dim
    dtype = jnp.dtype(runner.spec.dtype)

    def err(logits, want) -> float:
        diff = np.max(np.abs(np.asarray(logits, np.float32) - want))
        return float(diff / np.std(want))

    def prefill_both(prefill):
        kc = jnp.zeros((cfg.num_layers, P, ps, KD), dtype)
        vc = jnp.zeros_like(kc)
        errs = {}
        for s, (n, split) in enumerate(zip(lens, splits)):
            for lo, hi in ((0, split), (split, n)):
                chunk = np.zeros(T, np.int32)
                chunk[: hi - lo] = toks[s][lo:hi]
                logits, kc, vc = prefill(
                    params, jnp.asarray(chunk), jnp.int32(lo), jnp.int32(hi - lo),
                    kc, vc, jnp.asarray(tables[s]))
            errs[f"prefill[{s}]"] = err(logits, ref[s][0])
        return kc, vc, errs

    def decode_both(decode, kc, vc, tabs, steps):
        page_tables = np.zeros((B, mp), np.int32)
        entry = np.full(B, mp * ps, np.int32)  # padded rows sit past the table
        for s in range(2):
            page_tables[s], entry[s] = tabs[s], lens[s]
        hk = jnp.zeros((cfg.num_layers, B, n_dec, KD), dtype)
        hv = jnp.zeros_like(hk)
        errs = {}
        for j in range(steps):
            cur = np.zeros(B, np.int32)
            for s in range(2):
                cur[s] = toks[s][lens[s] + j]
            logits, hk, hv = decode(
                params, jnp.asarray(cur), jnp.asarray(entry + j), jnp.asarray(entry),
                jnp.int32(j), kc, vc, jnp.asarray(page_tables), hk, hv)
            for s in range(2):
                errs[f"decode[{s}]+{j}"] = err(logits[s], ref[s][1 + j])
        return errs

    wrong = [tables[0].copy(), tables[1]]
    wrong[0][1] = tables[1][1]
    errors, control = {}, {}
    for impl in impls:
        prefill = jax.jit(lambda p, *a, impl=impl: module.forward_prefill(
            p, cfg, inv_freq, *a, attn_impl=impl))
        decode = jax.jit(lambda p, *a, impl=impl: module.forward_decode_horizon(
            p, cfg, inv_freq, *a, attn_impl=impl))
        kc, vc, errors[impl] = prefill_both(prefill)
        errors[impl].update(decode_both(decode, kc, vc, tables, n_dec))
        control[impl] = decode_both(decode, kc, vc, wrong, 1)["decode[0]+0"]
        del kc, vc
    worst = max(e for per in errors.values() for e in per.values())
    ok = (all(np.isfinite(e) and e <= LOGIT_TOLERANCE
              for per in errors.values() for e in per.values())
          and all(e > LOGIT_TOLERANCE for e in control.values()))
    return {"ok": bool(ok), "tolerance": LOGIT_TOLERANCE, "worst": worst,
            "errors": errors, "control_errors": control}
