"""Are two trees the same programs?  For a change that must move no number.

    python scripts/same_programs.py dump --root /path/to/tree --out a.npz
    python scripts/same_programs.py dump --root . --out b.npz
    python scripts/same_programs.py compare a.npz b.npz

``dump`` imports ``smg_tpu`` from ``--root`` and runs, on the CPU in float32
with seeded weights, (A) every forward of ``models/llama.py`` under ``jit``
with the static flags the runner passes, on tiny Llama, the Gemma-style
preset, a ``qk_norm`` config and an M-RoPE config, under XLA attention and
under the interpreted kernels, and (B) every program family a runner
registers (``prefill``, ``prefill_extend``, ``prefill_batched`` cold and
warm, ``decode_multi``, ``decode_spec``, ``embed``) through the runner's own
host API, for the same configs (decode frames with penalties, a stop token
met inside the frame, a vocabulary mask, an M-RoPE offset and a LoRA bank
among them, and tiny Llama once more under ``tp=2`` on forced host devices:
the Llama frame alone carries ``in_shardings``, ``out_shardings`` and
``shard_hint``), and for ``tiny-olmo-hybrid``, ``tiny-pangu-moe``,
``tiny-mimo``, ``tiny-longcat-flash``, ``tiny-exaone-moe``, ``tiny-nemotron-h``
and ``tiny-kimi-linear`` through ``Engine`` (all but the first where the tree
has them; the last one plain,
self-drafting, and self-drafting with weights whose drafts are right, so
that the verify frame's accept branch runs), a sampled group, a sampled
stream and a stream with penalties and a stop token among them, and (C) the routed-expert layer itself under XLA's ragged product and under
the interpreted grouped-product kernel: on the CPU an engine's expert layers
are XLA's, and the served ones on a TPU the kernel's.
It keeps every output (logits, caches, tokens, logprobs) and each compiled
program's ``cost_analysis()`` FLOPs and bytes.  ``compare`` wants the outputs
bit-equal and the costs equal, and names what is not.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # two host devices, for the mesh form of the Llama programs
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2").strip()


def _cost(compiled) -> dict:
    c = compiled.cost_analysis()
    c = c[0] if isinstance(c, (list, tuple)) else c
    return {"flops": float(c.get("flops", -1.0)),
            "bytes": float(c.get("bytes accessed", -1.0))}


def _configs():
    from smg_tpu.models.config import (
        tiny_gemma2_config, tiny_test_config, tiny_vlm_mrope_config)

    tiny = tiny_test_config()
    return {
        "tiny": tiny,
        "gemma": dataclasses.replace(tiny_gemma2_config(), sliding_window=24,
                                     sliding_window_pattern=2),
        "qk_norm": dataclasses.replace(tiny, qk_norm=True, tie_word_embeddings=True),
        "mrope": dataclasses.replace(tiny_vlm_mrope_config(), vision=None),
    }


def _forwards(name, cfg, impl, out, costs):
    """(A): the module's forwards, one jit each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.models import llama
    from smg_tpu.ops.rope import rope_frequencies

    if impl != "xla":  # the kernels want whole 128-lane tiles
        cfg = dataclasses.replace(cfg, num_kv_heads=8)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    # norm weights off their identity, so that a misplaced norm shows
    params["layers"] = {
        k: (v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape, v.dtype)
            if k.endswith("norm") else v)
        for i, (k, v) in enumerate(sorted(params["layers"].items()))}
    inv = jnp.asarray(rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling))
    KD = cfg.num_kv_heads * cfg.head_dim
    L, ps, P = cfg.num_layers, 16, 32
    zeros = lambda: jnp.zeros((L, P, ps, KD), jnp.float32)
    mrope = bool(cfg.mrope_section)
    rng = np.random.default_rng(0)
    toks = lambda *s: jnp.asarray(rng.integers(3, cfg.vocab_size - 20, s), jnp.int32)

    def run(tag, fn, *args):
        # smglint: disable-next=RETRACE one jit a program, each run once
        jitted = jax.jit(fn)
        res = jitted(*args)
        costs[f"A/{name}/{impl}/{tag}"] = _cost(jitted.lower(*args).compile())
        for i, r in enumerate(jax.tree.leaves(res)):
            out[f"A/{name}/{impl}/{tag}/{i}"] = np.asarray(r)
        return res

    n_ad = 3
    bank = {f"w{w}_{ab}": 0.05 * jax.random.normal(
        jax.random.PRNGKey(40 + j),
        (L, n_ad, *shape), jnp.float32)
        for j, (w, ab, shape) in enumerate([
            ("q", "a", (cfg.hidden_size, 4)), ("q", "b", (4, cfg.num_heads * cfg.head_dim)),
            ("k", "a", (cfg.hidden_size, 4)), ("k", "b", (4, KD)),
            ("v", "a", (cfg.hidden_size, 4)), ("v", "b", (4, KD)),
            ("o", "a", (cfg.num_heads * cfg.head_dim, 4)), ("o", "b", (4, cfg.hidden_size))])}
    gate1 = jax.nn.one_hot(1, n_ad)

    # solo prefill: cold, then a chunk behind it, then with a LoRA bank
    pt = jnp.array([1, 2, 3, 0], jnp.int32)
    seq = toks(40)
    rp = (jnp.stack([jnp.arange(32)] * 3) + jnp.array([[0], [1], [2]])) if mrope else None
    kw = dict(attn_impl=impl)
    _, kc, vc = run("prefill_cold", lambda kc, vc: llama.forward_prefill(
        params, cfg, inv, seq[:32], jnp.int32(0), jnp.int32(27), kc, vc, pt,
        rope_pos=rp, **kw), zeros(), zeros())
    run("prefill_extend", lambda kc, vc: llama.forward_prefill(
        params, cfg, inv, seq[24:40], jnp.int32(27), jnp.int32(13), kc, vc, pt,
        rope_pos=None if rp is None else rp[:, :16] + 27, **kw), kc, vc)
    run("prefill_all_logits", lambda kc, vc: llama.forward_prefill(
        params, cfg, inv, seq[:32], jnp.int32(0), jnp.int32(27), kc, vc, pt,
        all_logits=True, **kw), zeros(), zeros())
    run("prefill_lora", lambda kc, vc: llama.forward_prefill(
        params, cfg, inv, seq[:32], jnp.int32(0), jnp.int32(27), kc, vc, pt,
        lora=bank, lora_gates=gate1, **kw), zeros(), zeros())
    emb = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (32, cfg.hidden_size))
    run("prefill_embeds", lambda kc, vc: llama.forward_prefill(
        params, cfg, inv, seq[:32], jnp.int32(0), jnp.int32(27), kc, vc, pt,
        input_embeds=emb, embeds_mask=jnp.arange(32) % 3 == 0, **kw), zeros(), zeros())

    if impl == "xla":
        # grouped prefill has no kernel: cold, then rows behind their prefixes
        pts = jnp.array([[1, 2, 3, 0], [4, 5, 6, 7], [0, 0, 0, 0], [8, 9, 0, 0]], jnp.int32)
        g = toks(4, 32)
        tr = jnp.array([32, 19, 0, 7], jnp.int32)
        grp = None if rp is None else jnp.broadcast_to(rp[None], (4, 3, 32))
        _, kc, vc = run("batched_cold", lambda kc, vc: llama.forward_prefill_batched(
            params, cfg, inv, g, jnp.zeros(4, jnp.int32), tr, kc, vc, pts, no_ctx=True,
            rope_pos=grp), zeros(), zeros())
        g2 = toks(4, 16)
        _, kc, vc = run("batched_warm", lambda kc, vc: llama.forward_prefill_batched(
            params, cfg, inv, g2, tr, jnp.array([9, 16, 0, 3], jnp.int32), kc, vc, pts,
            rope_pos=None if grp is None else grp[:, :, :16] + tr[:, None, None]), kc, vc)
        run("batched_lora", lambda kc, vc: llama.forward_prefill_batched(
            params, cfg, inv, g, jnp.zeros(4, jnp.int32), tr, kc, vc, pts, no_ctx=True,
            lora=bank, lora_gates=jax.nn.one_hot(jnp.array([0, 1, 2, 0]), n_ad)),
            zeros(), zeros())
        entry = jnp.array([41, 35, 0, 10], jnp.int32)
        # the verify block over what the grouped prefills left
        run("verify", lambda kc, vc: llama.forward_verify_block(
            params, cfg, inv, toks(4, 4), entry, kc, vc, pts,
            rope_delta=jnp.array([2, 0, 0, 1]) if mrope else None), kc, vc)
        run("embed", lambda t, n: llama.forward_embed(params, cfg, inv, t, n),
            g, jnp.array([32, 19, 1, 7], jnp.int32))
        run("train", lambda t: llama.forward_train(params, cfg, inv, t), g[:, :20])
    else:
        pts = jnp.array([[1, 2, 3, 0], [4, 5, 6, 7], [0, 0, 0, 0], [8, 9, 0, 0]], jnp.int32)
        entry = jnp.array([40, 0, 0, 0], jnp.int32)
        _, kc, vc = run("prefill_kc", lambda kc, vc: llama.forward_prefill(
            params, cfg, inv, seq, jnp.int32(0), jnp.int32(40), kc, vc, pt, **kw),
            zeros(), zeros())

    # two columns of a horizon of 4 over the frozen cache, plain and with LoRA
    for tag, extra in (("decode", {}), ("decode_lora", dict(
            lora=bank, lora_gates=jax.nn.one_hot(jnp.array([1, 0, 2, 0]), n_ad)))):
        hk = jnp.zeros((L, 4, 4, KD), jnp.float32)
        hv = jnp.zeros_like(hk)
        cur = toks(4)
        for j in range(2):
            lo, hk, hv = run(f"{tag}{j}", lambda cur, hk, hv, j=j: llama.forward_decode_horizon(
                params, cfg, inv, cur, entry + j, entry, jnp.int32(j), kc, vc, pts, hk, hv,
                rope_delta=jnp.array([2, 0, 0, 1]) if mrope else None, **kw, **extra),
                cur, hk, hv)
            cur = jnp.argmax(lo, -1).astype(jnp.int32)


def _engine_config(cfg, impl, tp=1, **kw):
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.parallel.mesh import ParallelConfig

    return EngineConfig(
        parallel=ParallelConfig(tp=tp),
        model=cfg,
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=4, max_seq_len=128, max_prefill_tokens=64,
            prefill_token_buckets=(32, 64), decode_batch_buckets=(4,), **kw),
        dtype="float32", attention_impl=impl,
    )


def _program_costs(runner, prefix, costs):
    for key, rec in runner._programs._records.items():
        if rec.last_specs is not None:
            costs[f"{prefix}/{key!r}"] = _cost(rec.fn.lower(*rec.last_specs).compile())


def _runner_programs(name, cfg, impl, out, costs, tp=1):
    """(B): the programs a ``ModelRunner`` registers, through its host API."""
    import numpy as np

    from smg_tpu.engine.runner import ModelRunner
    from smg_tpu.models.lora import empty_adapter

    if impl != "xla":
        cfg = dataclasses.replace(cfg, num_kv_heads=8)
    r = ModelRunner(_engine_config(cfg, impl, tp=tp))
    r._programs.arm()
    pre = f"B/{name}/{impl}" + (f"/tp{tp}" if tp > 1 else "")
    mpz = r.max_pages_per_seq
    rng = np.random.default_rng(1)
    ids = lambda n: [int(t) for t in rng.integers(3, cfg.vocab_size - 20, n)]
    row = lambda *pages: np.array(list(pages) + [0] * (mpz - len(pages)), np.int32)
    s4 = (np.zeros(4, np.float32), np.full(4, -1, np.int32), np.ones(4, np.float32),
          np.zeros(4, np.float32))

    out[f"{pre}/prefill"] = np.array(r.prefill(ids(20), 0, row(1, 2), 0.0, -1, 1.0, 0.0))
    r.prefill_extend(ids(32), 0, row(3, 4, 5))
    out[f"{pre}/prefill_after_extend"] = np.array(
        r.prefill(ids(9), 32, row(3, 4, 5), 0.0, -1, 1.0, 0.0))
    cold = [(ids(30), 0, row(6, 7)), (ids(11), 0, row(8)), (ids(17), 0, row(9, 10))]
    s3 = tuple(x[:3] for x in s4)
    out[f"{pre}/batched_cold"] = np.stack(r.prefill_batched(cold, *s3))
    warm = [(ids(5), 20, row(1, 2)), (ids(13), 17, row(9, 10)), (ids(2), 30, row(6, 7))]
    out[f"{pre}/batched_warm"] = np.stack(r.prefill_batched(warm, *s3))
    # a group that samples: the key's fold shows in its tokens
    hot = (np.full(3, 0.8, np.float32), np.array([-1, 20, -1], np.int32),
           np.array([1.0, 1.0, 0.9], np.float32), np.array([0.0, 0.0, 0.02], np.float32))
    drawn = [(ids(21), 0, row(11, 12)), (ids(7), 0, row(13)), (ids(32), 0, row(14, 15))]
    out[f"{pre}/batched_sampled"] = np.stack(r.prefill_batched(drawn, *hot))
    tables = np.stack([row(1, 2), row(9, 10), row(6, 7), row()])
    pos = np.array([25, 30, 32, 0], np.int32)
    cur = np.array(ids(4), np.int32)
    for n in (1, 4):
        t, lp = r.decode_multi(cur, pos, tables, *s4, num_steps=n)
        out[f"{pre}/decode_multi{n}"] = np.stack([t.astype(np.float64), lp])
        pos = pos + n
    # the frame's optional arms: penalties with a stop token and a limit met
    # inside the frame, an M-RoPE offset, a vocabulary mask, a LoRA bank
    def frame(tag, t, lp):
        out[f"{pre}/decode_{tag}"] = np.stack([t.astype(np.float64), lp])

    for slot in range(4):
        r.sync_slot_penalty_state(slot, ids(12), ids(3))
    pen = (np.arange(4, dtype=np.int32), np.array([0.5, 0.0, 0.3, 0.0], np.float32),
           np.array([0.0, 0.4, 0.2, 0.0], np.float32), np.array([1.3, 1.0, 1.1, 1.0], np.float32))
    warm_s4 = (np.array([0.0, 0.7, 0.9, 0.0], np.float32), s4[1], s4[2], s4[3])
    t, lp = r.decode_multi(cur, pos, tables, *warm_s4, num_steps=4, pen=pen)
    frame("pen", t, lp)
    # lane 0 ends on its second token; nothing ends on the first
    stop = (np.stack([[int(t[0, 1]), -1], [-1, -1], [-1, -1], [-1, -1]]).astype(np.int32),
            np.array([2**30, 2**30, pos[2] + 4, 2**30], np.int32),
            np.array([True, True, True, False]))
    for slot in range(4):
        r.sync_slot_penalty_state(slot, ids(12), ids(3))
    t, lp = r.decode_multi(cur, pos, tables, *warm_s4, num_steps=4, pen=pen, stop_state=stop)
    frame("pen_stop", t, lp)
    out[f"{pre}/counts_buf"] = np.asarray(r._counts_buf)
    t, lp = r.decode_multi(cur, pos, tables, *s4, num_steps=3, max_steps=4, stop_state=stop)
    frame("stop", t, lp)
    t, lp = r.decode_multi(cur, pos, tables, *s4, num_steps=4,
                           rope_delta=np.array([2, 0, 5, 0], np.int32))
    frame("mrope", t, lp)
    mask = np.arange(cfg.vocab_size)[None, :] % np.arange(2, 6)[:, None] == 0
    t, lp = r.decode_multi(cur, pos, tables, *warm_s4, num_steps=1, mask=mask)
    frame("mask", t, lp)
    if name == "tiny":
        rng_l = np.random.default_rng(2)
        w = {k: rng_l.normal(0, 0.5, v.shape).astype(np.float32)
             for k, v in empty_adapter(cfg, 4).items()}
        r.load_lora("a", w)
        t, lp = r.decode_multi(cur, pos, tables, *s4, num_steps=4, pen=pen,
                               lora_idx=np.array([1, 0, 1, 0], np.int32))
        frame("lora_pen", t, lp)
    if hasattr(r, "decode_spec_async") and impl == "xla":
        block = np.array([ids(4) for _ in range(4)], np.int32)
        em, ne, lp = r.decode_spec_async(block, np.array([3, 1, 0, 0], np.int32), pos,
                                         tables, *s4)
        out[f"{pre}/decode_spec"] = np.concatenate(
            [np.asarray(em, np.float64), np.asarray(lp), np.asarray(ne, np.float64)[:, None]], 1)
        out[f"{pre}/embed"] = r.embed([ids(12), ids(31), ids(3)])
    out[f"{pre}/k_cache"] = np.asarray(r.k_cache)
    _program_costs(r, pre, costs)


def _engine_programs(name, cfg, impl, out, costs, params=None, **sched):
    """(B) for a model whose runner keeps state per sequence: through
    ``Engine``, greedy, one prompt long enough to be cut into chunks."""
    import jax
    import numpy as np

    from smg_tpu.engine.engine import Engine
    from smg_tpu.protocols.sampling import SamplingParams

    config = _engine_config(cfg, impl, decode_horizon=4, **sched)
    if params is not None:
        params = params(config.model)
    eng = Engine(config, params=params)
    eng.runner._programs.arm()
    pre = f"B/{name}/{impl}"
    sp = SamplingParams(temperature=0.0, max_new_tokens=9, ignore_eos=True)
    for i, n in enumerate((20, 100, 45)):
        res = eng.generate(prompt_ids=list(range(5 + i, 5 + i + n)), sampling=sp)
        out[f"{pre}/generate{n}"] = np.asarray(res.token_ids)
    hot = SamplingParams(temperature=0.8, top_p=0.9, max_new_tokens=9, ignore_eos=True)
    res = eng.generate(prompt_ids=list(range(9, 40)), sampling=hot)
    out[f"{pre}/generate_sampled"] = np.asarray(res.token_ids)
    # penalties, and a stop token and a limit that fall inside a frame
    base = eng.generate(prompt_ids=list(range(30, 52)), sampling=sp).token_ids
    pen = SamplingParams(temperature=0.0, max_new_tokens=7, repetition_penalty=1.3,
                         frequency_penalty=0.4, presence_penalty=0.2,
                         stop_token_ids=[int(base[5])])
    res = eng.generate(prompt_ids=list(range(30, 52)), sampling=pen)
    out[f"{pre}/generate_pen_stop"] = np.asarray(res.token_ids)
    hot_pen = SamplingParams(temperature=0.7, top_k=40, max_new_tokens=10,
                             frequency_penalty=0.5, ignore_eos=True)
    res = eng.generate(prompt_ids=list(range(60, 95)), sampling=hot_pen)
    out[f"{pre}/generate_sampled_pen"] = np.asarray(res.token_ids)
    out[f"{pre}/k_cache"] = np.asarray(eng.runner.k_cache)
    for what in ("s_pool", "c_pool", "draft_buf", "frame_counts", "frame_clean"):
        if getattr(eng.runner, what, None) is not None:
            out[f"{pre}/{what}"] = np.asarray(getattr(eng.runner, what))
    if getattr(eng.runner, "frame_tail", None) is not None:
        # the verify frame launched last: emitted, last, positions, [drafted, accepted]
        for i, x in enumerate(jax.tree.leaves(eng.runner.frame_tail)):
            out[f"{pre}/frame_tail/{i}"] = np.asarray(x)
        out[f"{pre}/mtp"] = np.array([eng.loads()["mtp"][k] for k in
                                      ("drafted", "accepted", "columns", "tokens")])
    _program_costs(eng.runner, pre, costs)


def _expert_layer_forms(out, costs):
    """(C): ``ops.moe.expert_layer`` in one pass and in several, a form each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.ops import moe

    E, F, held, top_k = 128, 256, (4, 8), 4
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    router = jax.random.normal(keys[0], (E, 16))
    w_gate, w_up = (0.1 * jax.random.normal(k, (2, held[1], E, F)) for k in keys[1:3])
    w_down = 0.1 * jax.random.normal(keys[3], (2, held[1], F, E))
    for T in (8, 1024):  # 32 pairs at once; 4,096 pairs in passes of 2,048 rows
        x = jax.random.normal(keys[4], (T, E))
        for impl in ("xla", "pallas_interpret"):
            def layer(x, w_gate, w_up, w_down):
                picks = moe.route(x, router, top_k=top_k, scoring="sigmoid",
                                  norm_topk=True, scale=1.0)
                return moe.expert_layer(x, picks, w_gate, w_up, w_down, held, impl,
                                        layer=jnp.int32(1))

            # smglint: disable-next=RETRACE one jit a program, each run once
            jitted = jax.jit(layer)
            args = (x, w_gate, w_up, w_down)
            costs[f"C/expert_layer/{impl}/T{T}"] = _cost(jitted.lower(*args).compile())
            for i, r in enumerate(jax.tree.leaves(jitted(*args))):
                out[f"C/expert_layer/{impl}/T{T}/{i}"] = np.asarray(r)


def dump(root: str, path: str) -> None:
    sys.path.insert(0, os.path.abspath(root))
    import numpy as np

    from smg_tpu.models.config import tiny_olmo_hybrid_config

    out: dict = {}
    costs: dict = {}
    for name, cfg in _configs().items():
        for impl in ("xla", "pallas_interpret"):
            _forwards(name, cfg, impl, out, costs)
            if name != "mrope":  # the runner takes M-RoPE ids with a vision tower only
                _runner_programs(name, cfg, impl, out, costs)
        _engine_programs(name, cfg, "xla", out, costs)
    _runner_programs("tiny", _configs()["tiny"], "xla", out, costs, tp=2)
    for impl in ("xla", "pallas_interpret"):
        _engine_programs("olmo_hybrid", tiny_olmo_hybrid_config(), impl, out, costs)
    # the recurrent runner's two other modules, where the tree has them: state
    # slots with routed counts beside K and V pages, and beside latent pages
    for name, preset in (("nemotron_h", "tiny_nemotron_h_config"),
                         ("kimi_linear", "tiny_kimi_linear_config")):
        from smg_tpu.models import config as presets

        if hasattr(presets, preset):
            for impl in ("xla", "pallas_interpret"):
                _engine_programs(name, getattr(presets, preset)(held=(4, 4)), impl, out, costs)
    try:
        from smg_tpu.models.config import tiny_pangu_moe_config
    except ImportError:  # a tree from before the latent model
        tiny_pangu_moe_config = None
    if tiny_pangu_moe_config is not None:
        for impl in ("xla", "pallas_interpret"):
            _engine_programs("pangu_moe", tiny_pangu_moe_config(held=(4, 8)), impl, out, costs)
        _expert_layer_forms(out, costs)
    try:
        from smg_tpu.models.config import tiny_mimo_config
    except ImportError:  # a tree from before the window model
        tiny_mimo_config = None
    if tiny_mimo_config is not None:
        for impl in ("xla", "pallas_interpret"):
            _engine_programs("mimo", tiny_mimo_config(held=(4, 8)), impl, out, costs)
    try:
        from smg_tpu.models.config import tiny_longcat_flash_config
    except ImportError:  # a tree from before the model with two attention sublayers
        tiny_longcat_flash_config = None
    if tiny_longcat_flash_config is not None:
        for impl in ("xla", "pallas_interpret"):
            _engine_programs("longcat_flash", tiny_longcat_flash_config(held=(4, 12)), impl,
                             out, costs)
    try:
        from smg_tpu.models.config import tiny_exaone_moe_config
    except ImportError:  # a tree from before the self-drafting model
        tiny_exaone_moe_config = None
    if tiny_exaone_moe_config is not None:
        from smg_tpu.models import exaone_moe

        def right(model):  # every draft is the model's own next token
            import jax

            return exaone_moe.params_whose_drafts_are_right(
                exaone_moe.init_params(model, jax.random.PRNGKey(0)))

        cfg = tiny_exaone_moe_config(held=(4, 8))
        for impl in ("xla", "pallas_interpret"):
            _engine_programs("exaone_moe", cfg, impl, out, costs)
            _engine_programs("exaone_moe_drafting", cfg, impl, out, costs, speculative=True)
        _engine_programs("exaone_moe_drafts_right", tiny_exaone_moe_config(), "xla", out,
                         costs, params=right, speculative=True)
    np.savez_compressed(path, __costs__=np.array(json.dumps(costs)), **out)
    print(f"{len(out)} outputs, {len(costs)} compiled programs -> {path}")


def compare(a: str, b: str) -> int:
    import numpy as np

    A, B = np.load(a), np.load(b)
    ca, cb = json.loads(str(A["__costs__"])), json.loads(str(B["__costs__"]))
    bad = []
    for k in sorted((set(A.files) | set(B.files)) - {"__costs__"}):
        if k not in A.files or k not in B.files:
            bad.append(f"output only on one side: {k}")
        elif A[k].shape != B[k].shape or not np.array_equal(A[k], B[k], equal_nan=True):
            d = (float(np.max(np.abs(A[k].astype(np.float64) - B[k])))
                 if A[k].shape == B[k].shape else "shape")
            bad.append(f"output differs: {k} (max abs {d})")
    for k in sorted(set(ca) | set(cb)):
        if ca.get(k) != cb.get(k):
            bad.append(f"cost differs: {k}: {ca.get(k)} != {cb.get(k)}")
    n_out = len(set(A.files) & set(B.files)) - 1
    print(f"{n_out} outputs and {len(set(ca) & set(cb))} programs compared; "
          f"{len(bad)} differ")
    for line in bad:
        print("  " + line)
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("dump")
    d.add_argument("--root", default=".")
    d.add_argument("--out", required=True)
    c = sub.add_parser("compare")
    c.add_argument("a")
    c.add_argument("b")
    args = ap.parse_args()
    if args.cmd == "dump":
        dump(args.root, args.out)
        return 0
    return compare(args.a, args.b)


if __name__ == "__main__":
    sys.exit(main())
