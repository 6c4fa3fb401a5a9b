#!/usr/bin/env python3
"""Check and time the ``glm-5.2`` configuration standalone, on the chip this
process holds, **at lengths past ``index_topk``**, which the benchmark's shared
comparison (``benchmark/reference.check_engine``: 700 and 380 tokens) never
reaches:

    python3 scripts/time_glm_dsa.py --check 4096,9216,16384 [--seed 0]
    python3 scripts/time_glm_dsa.py --flips 16384
    python3 scripts/time_glm_dsa.py --time 4096,8192,16384

Draws the weights ``serve`` would draw for ``benchmark/configs/glm-5.2.json``
(``models/glm_moe_dsa.init_params``, published widths) and a cache of one
sequence's pages.

``--check N,...``: for each length one seeded sequence through the serving path
(``forward_prefill`` in 4,096-token chunks, each behind the live prefix, then
decode frames of 8 columns through both caches, one live lane beside seven
padded) against the plain float32 reference
(``benchmark/architectures/glm_moe_dsa.logits``, in blocks) at the prompt's
last row and every decode row: the errors in units of the reference row's
deviation, as the harness reads them, against ``TOLERANCE``.  Then three
controls on the first decode row, each of which must miss it: the latent
entries of one page wrong, the index keys of that page wrong (both pages past
2,048 tokens: the page of the token that layer 0's attention weighs most for
that row, found from the embeddings alone; the wrong content is another page's
of the same sequence), and the step run as a program that selects the nearest
2,048 tokens whatever the indexer scored.

``--harness SEED,...``: the benchmark's own comparison for those seeds
(``benchmark/reference.check_engine``: 700 and 380 tokens, below ``index_topk``,
so every cached token is selected: the shared wrong-page control and the
drive's) over these weights, without an engine: how loud one wrong page of 44
is in the dense regime under a drawing.

``--flips N``: how many query rows in a hundred pick otherwise with bfloat16
products than with float32 ones, and how many picks such a row flips: layer 0's
indexer (its input is the embeddings alone) over one sequence of N tokens, the
last 128 rows.

``--draw NAME=VALUE,...`` overrides constants of the module's random weights
for the run (``SHARED_SCORE_STD=1.5``): how the module's values were set.

``--time N,...``: after one warm-up each, on the host clock round
``block_until_ready``, at 32 lanes behind N tokens each: the index scores and
``lax.top_k`` of a column (``select_decode``), the same selection as the exact
threshold's mask (``select_mask``), the places' cache slots (``selected_slots``), the gather of
the selected entries out of a buffer several layers deep, the attention over
the gathered block, and beside them the dense latent kernel over
the same pages (``latent_attention_decode_cached``), which reads every entry.

Prints one JSON line a reading.  Refuses to run without a TPU: a CPU time is
not a device time.  ``--rehearsal`` runs the same code at the configuration's
rehearsal widths on the CPU (lengths divided by 64) and prints no times.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

#: largest |logit - reference| in units of the reference row's deviation: the
#: harness's own (``benchmark/reference.LOGIT_TOLERANCE``).  What it has to
#: part at these lengths: the serving path's bfloat16 (activations, both caches
#: and the index scores' products rounded in every layer, picks at the
#: selection's edge flipped by it) below it, one wrong page and a wrong
#: selection above.
TOLERANCE = 0.30
PS, CHUNK, LANES, HORIZON = 16, 4096, 8, 8
LAYERS = 3  # cache layers of the timing's buffer


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def rel_err(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.std(want))


class Served:
    """The serving forwards over one sequence's pages."""

    def __init__(self, cfg, params, pages: int, nearest_places):
        import jax
        import jax.numpy as jnp

        from smg_tpu.models import glm_moe_dsa as M
        from smg_tpu.ops.rope import rope_frequencies

        self.cfg, self.params, self.M, self.pages = cfg, params, M, pages
        self.nearest_places = nearest_places
        self.inv = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None))
        dtype = jnp.dtype(cfg.dtype)
        self.W, self.D = M.cache_lanes(cfg), cfg.index_head_dim
        self.zeros = lambda layers, *lead, lanes: jnp.zeros((layers, *lead, lanes), dtype)
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        self.prefill = jax.jit(lambda p, *a: M.forward_prefill(p, cfg, self.inv, *a, moe_impl=impl),
                               donate_argnums=(4, 5))
        self.program = lambda: jax.jit(lambda p, *a: M.forward_decode_horizon(
            p, cfg, self.inv, *a, attn_impl=impl, moe_impl=impl))
        self.decode = self.program()
        self.table = jnp.arange(1, pages + 1, dtype=jnp.int32)

    def caches(self):
        cfg = self.cfg
        return (self.zeros(cfg.num_cache_layers, self.pages + 1, PS, lanes=self.W),
                self.zeros(cfg.num_index_layers, self.pages + 1, PS, lanes=self.D))

    def run_prefill(self, toks, n, chunk):
        import jax.numpy as jnp
        import numpy as np

        kc, vc = self.caches()
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            part = np.zeros(chunk, np.int32)
            part[: hi - lo] = toks[lo:hi]
            logits, kc, vc = self.prefill(self.params, jnp.asarray(part), jnp.int32(lo),
                                          jnp.int32(hi - lo), kc, vc, self.table)
        return logits, kc, vc

    def run_decode(self, kc, vc, toks, n, columns, decode=None):
        """``columns`` decode rows behind ``n`` cached tokens, in one frame."""
        import jax.numpy as jnp
        import numpy as np

        cfg = self.cfg
        side = (self.zeros(cfg.num_cache_layers, LANES, HORIZON, lanes=self.W),
                self.zeros(cfg.num_index_layers, LANES, HORIZON, lanes=self.D))
        tables = np.zeros((LANES, self.pages), np.int32)
        tables[0] = np.asarray(self.table)
        entry = np.full(LANES, self.pages * PS, np.int32)
        entry[0] = n
        out = []
        for j in range(columns):
            cur = np.zeros(LANES, np.int32)
            cur[0] = toks[n + j]
            logits, side, _ = (decode or self.decode)(
                self.params, jnp.asarray(cur), jnp.asarray(entry + j), jnp.asarray(entry),
                jnp.int32(j), (kc, vc), jnp.asarray(tables), side,
                jnp.asarray(entry < self.pages * PS))
            out.append(np.asarray(logits[0], np.float32))
        return out

    def nearest(self):
        """The decode program traced while ``select_decode`` chooses the
        nearest ``index_topk`` places (the architecture file's control)."""
        M, decode = self.M, self.program()

        def program(*args):
            sound, M.select_decode = M.select_decode, self.nearest_places
            try:
                return decode(*args)
            finally:
                M.select_decode = sound

        return program


def layer0(arch, params, hf, toks):
    """Layer 0's normed input and the reference's pieces of it, float32: the
    embeddings are all it reads."""
    import jax.numpy as jnp

    f32 = jnp.float32
    shape = arch._shape(hf)
    w = lambda key: params["dense"][key][0].astype(f32)
    ix = lambda key: params["indexer"][key][0].astype(f32)
    x = arch._rms(params["embed"][jnp.asarray(toks)].astype(f32), w("attn_norm"), shape["eps"])
    return x, w, ix, shape


def most_weighed_page(arch, params, hf, toks, row: int, topk: int) -> "tuple[int, float]":
    """The page (past ``topk`` tokens, not the nearest four) of the token that
    layer 0's attention, under its selection, weighs most for ``row``, and that
    weight averaged over the heads."""
    import math

    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x, w, ix, shape = layer0(arch, params, hf, toks[: row + 1])
        T, dn, dr, theta = row + 1, shape["dn"], shape["dr"], shape["theta"]
        pos = jnp.arange(T)
        c_q = arch._rms(x[row:] @ w("w_dq"), w("q_norm"), shape["eps"])
        c = arch._rms(x @ w("w_dkv"), w("kv_norm"), shape["eps"])
        k_pe = arch._rope(arch._published_order(x @ w("w_dk_pe")), pos, theta)
        q_nope = (c_q @ w("w_uq_nope").T).reshape(1, -1, dn)
        q_pe = arch._rope(arch._published_order(jnp.einsum("tr,dhr->thd", c_q, w("w_uq_pe"))),
                          pos[row:], theta)
        s = (jnp.einsum("thd,hcd,sc->hts", q_nope, w("w_uk"), c)
             + jnp.einsum("thd,sd->hts", q_pe, k_pe))[:, 0] / math.sqrt(dn + dr)
        keys = arch.index_keys(x, ix, shape)
        c_q_all = jnp.zeros((T, c_q.shape[-1]), jnp.float32).at[row].set(c_q[0])
        mask = arch.select(arch.index_scores(x, c_q_all, ix, shape, row, row + 1, keys), topk)[0]
        p = jnp.mean(jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1), axis=0)
        far = (pos >= topk) & (pos < row - 4 * PS)
        at = int(jnp.argmax(jnp.where(far, p, -1.0)))
    return at // PS, float(p[at])


def check(cfg, hf, arch, params, lengths, seed: int, chunk: int, topk: int) -> bool:
    import numpy as np

    ok = True
    for n in lengths:
        rng = np.random.default_rng(seed * 1000003 + n)
        toks = rng.integers(2, hf["vocab_size"], size=n + HORIZON).astype(np.int32)
        pages = -(-(n + HORIZON) // PS)
        served = Served(cfg, params, pages, arch.nearest_places)
        t = time.monotonic()  # the reference first: it wants the room the caches take
        ref = arch.logits(params, hf, toks, list(range(n - 1, n + HORIZON)))
        t_ref = time.monotonic() - t
        t = time.monotonic()
        logits, kc, vc = served.run_prefill(toks, n, chunk)
        rows = [np.asarray(logits, np.float32)] + served.run_decode(kc, vc, toks, n, HORIZON)
        t_served = time.monotonic() - t
        errors = [rel_err(r, ref[j]) for j, r in enumerate(rows)]
        page, weight = most_weighed_page(arch, params, hf, toks, n, topk)
        other = 5 if page != 5 else 6  # another page of the same sequence
        rkv = cfg.kv_lora_rank
        control = {
            "latent_page_wrong": served.run_decode(
                kc.at[:, page + 1, :, :rkv].set(kc[:, other + 1, :, :rkv]), vc, toks, n, 1)[0],
            "index_key_page_wrong": served.run_decode(
                kc, vc.at[:, page + 1].set(vc[:, other + 1]), toks, n, 1)[0],
            "nearest_selected": served.run_decode(kc, vc, toks, n, 1, served.nearest())[0],
        }
        control = {k: rel_err(v, ref[1]) for k, v in control.items()}
        good = max(errors) <= TOLERANCE and min(control.values()) > TOLERANCE
        ok = ok and good
        emit(check=n, seed=seed, chunk=chunk, index_topk=topk, tolerance=TOLERANCE, ok=good,
             worst=max(errors), errors=errors, control=control,
             wrong_page={"page": page, "first_token": page * PS,
                         "layer0_weight_of_its_token": weight},
             served_s=t_served, reference_s=t_ref)
        del served, kc, vc
    return ok


def harness(cfg, cell, params, seeds, rehearsal: bool) -> None:
    """``reference.check_engine`` over these weights: what it reads of a
    runner is its parameters, the cache's spec and the rotary frequencies."""
    import types

    import jax
    import jax.numpy as jnp
    import reference

    from smg_tpu.engine.config import CacheConfig
    from smg_tpu.engine.kv_cache import plan_latent_cache
    from smg_tpu.ops.rope import rope_frequencies

    spec = plan_latent_cache(cfg, CacheConfig(dtype=cfg.dtype, auto_size=False, num_pages=256))
    runner = types.SimpleNamespace(
        params=params, spec=spec, model_cfg=cfg,
        inv_freq=jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None)),
        attn_impl="auto" if jax.default_backend() == "tpu" else "xla")
    for seed in seeds:
        c = reference.check_engine(types.SimpleNamespace(runner=runner), cell, seed, rehearsal)
        emit(harness=seed, ok=c["ok"], worst=c["worst"],
             control={k: round(v, 3) for k, v in c["control_errors"].items()})


def flips(cfg, hf, arch, params, n: int, seed: int, topk: int) -> None:
    """bfloat16 products against float32 ones in layer 0's indexer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from smg_tpu.models import glm_moe_dsa as M
    from smg_tpu.ops import sparse_attention as sparse
    from smg_tpu.ops.rope import rope_frequencies

    rng = np.random.default_rng(seed + 17)
    toks = rng.integers(2, hf["vocab_size"], size=n).astype(np.int32)
    rows = list(range(n - 128, n))
    with jax.default_matmul_precision("highest"):
        x, w, ix, shape = layer0(arch, params, hf, toks)
        c_q = arch._rms(x @ w("w_dq"), w("q_norm"), shape["eps"])
        keys = arch.index_keys(x, ix, shape)
        want = np.asarray(arch.select(arch.index_scores(x, c_q, ix, shape, rows[0], n, keys), topk))
    dtype = jnp.dtype(cfg.dtype)
    inv = jnp.asarray(rope_frequencies(cfg.rope_dim, cfg.rope_theta, None))
    mine = lambda name: params["indexer"][name][0]
    layer = jax.tree.map(lambda a: a[0], params["dense"])
    pos = jnp.arange(n)

    @jax.jit
    def program(tokens):
        xs = M._norm(params["embed"][tokens].astype(dtype), layer["attn_norm"], cfg)
        _, _, _, cq = M.pangu_moe._latent_qkv(layer, cfg, xs, pos, inv)
        k = M.index_key(mine, cfg, xs, pos, inv)
        q, wt = M.index_query(mine, cfg, xs[rows[0]:], cq[rows[0]:], pos[rows[0]:], inv)
        return sparse.select_prefill(q[None], wt[None], k[None], pos[None, rows[0]:],
                                     jnp.asarray([n]), topk)[0]

    got = np.asarray(program(jnp.asarray(toks)))
    differ = (got != want).sum(-1) // 2
    emit(flips=n, index_topk=topk, rows=len(rows), rows_that_flip=int((differ > 0).sum()),
         rows_that_flip_in_100=100.0 * float((differ > 0).mean()),
         picks_flipped_mean_of_those=float(differ[differ > 0].mean()) if differ.any() else 0.0,
         picks_flipped_max=int(differ.max()))


def timed(fn, *args, reps: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def timing(cfg, lengths, report_times: bool, topk: int, lanes: int = 32) -> None:
    import jax
    import jax.numpy as jnp

    from smg_tpu.models import glm_moe_dsa as M
    from smg_tpu.ops import sparse_attention as sparse
    from smg_tpu.ops.latent_attention import value_lanes

    dtype = jnp.dtype(cfg.dtype)
    B, J, D, H, W, N = lanes, cfg.index_n_heads, cfg.index_head_dim, cfg.num_heads, \
        M.cache_lanes(cfg), HORIZON
    key = jax.random.PRNGKey(0)
    kernel = jax.default_backend() == "tpu"
    for n in lengths:
        mp = -(-(n + N) // PS)
        P = B * mp + 1
        ks = jax.random.split(key, 6)
        # several layers deep, read at a traced layer, as a frame reads the cache
        cache = jnp.tile(jax.random.normal(ks[0], (1, P, PS, W), jnp.float32).astype(dtype),
                         (LAYERS, 1, 1, 1))
        keys = jax.random.normal(ks[1], (1, P, PS, D), jnp.float32).astype(dtype)
        tables = (1 + jnp.arange(B * mp, dtype=jnp.int32)).reshape(B, mp)
        entry = jnp.full((B,), n, jnp.int32)
        q = jax.random.normal(ks[2], (B, J, D), jnp.float32).astype(dtype)
        w = jnp.abs(jax.random.normal(ks[3], (B, J), jnp.float32))
        qa = jax.random.normal(ks[4], (B, H, W), jnp.float32).astype(dtype)
        side = jnp.zeros((B, N, W), dtype)
        side_keys = jnp.zeros((B, N, D), dtype)
        S = mp * PS
        latent = value_lanes(cfg.kv_lora_rank)
        layer = jnp.int32(LAYERS - 1)
        # the buffers go in as arguments: a closure's array is a constant of the program
        ctx = lambda keys: keys[0, tables].reshape(B, S, D)
        select = jax.jit(lambda keys: sparse.select_decode(q, w, ctx(keys), side_keys, entry, 1,
                                                           topk))
        scores = jax.jit(lambda keys: sparse.index_scores(q[:, None], w[:, None], ctx(keys))[:, 0])
        mask = jax.jit(lambda s: sparse.select_mask(
            s, jnp.arange(S)[None, :] < entry[:, None], topk))
        ids, chosen = select(keys)
        where = jax.jit(lambda: sparse.selected_slots(tables, ids, chosen, PS, N))
        slots, paged, fresh = where()
        gather = jax.jit(lambda cache, l: sparse.gather_selected(cache, l, slots))
        block = gather(cache, layer)
        attend = jax.jit(lambda block: sparse.attend_selected(qa, block, side, paged, fresh,
                                                              1 / 16, latent))
        out = {"select_decode_ms": timed(select, keys), "index_scores_ms": timed(scores, keys),
               "select_mask_ms": timed(mask, scores(keys)), "selected_slots_ms": timed(where),
               "gather_selected_ms": timed(gather, cache, layer),
               "attend_selected_ms": timed(attend, block)}
        if kernel:
            from smg_tpu.ops.pallas.decode_attention import latent_attention_decode_cached

            dense = jax.jit(lambda cache, l: latent_attention_decode_cached(
                qa, cache, side, 1, l, tables, entry, latent=latent, scale=1 / 16))
            out["dense_latent_kernel_ms"] = timed(dense, cache, layer)
        del cache, keys, block
        emit(time=n, lanes=B, index_topk=topk, one_layer=True,
             **({k: v * 1e3 for k, v in out.items()} if report_times else
                {k: "not measured" for k in out}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", default="")
    ap.add_argument("--flips", type=int, default=0)
    ap.add_argument("--harness", default="", metavar="SEED,...")
    ap.add_argument("--time", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--draw", default="", metavar="NAME=VALUE,...",
                    help="constants of models/glm_moe_dsa's random weights, overridden for "
                         "this run: how the module's values were set")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if not args.rehearsal and jax.default_backend() != "tpu":
        print("time_glm_dsa: no TPU; a CPU time is not a device time", file=sys.stderr)
        return 3
    import catalog
    from smg_tpu.models import glm_moe_dsa as M
    from smg_tpu.models.config import ModelConfig

    cell = catalog.Cell(catalog.load_benchmark(), "glm-5.2.longdoc", rehearsal=args.rehearsal)
    hf, arch = cell.hf_config, cell.architecture
    cfg = ModelConfig.from_hf_config(hf, dtype="float32" if args.rehearsal else "bfloat16")
    shrink = 64 if args.rehearsal else 1
    for item in filter(None, args.draw.split(",")):
        name, _, value = item.partition("=")
        assert isinstance(getattr(M, name), float), name
        setattr(M, name, float(value))
    lengths = lambda text: [int(x) // shrink for x in text.split(",") if x]
    dev = jax.devices()[0]
    emit(device={"platform": dev.platform, "kind": dev.device_kind}, rehearsal=args.rehearsal,
         index_topk=cfg.index_topk, layers=cfg.num_layers, draw=args.draw)
    ok = True
    if args.check or args.flips or args.harness:
        params = jax.jit(lambda k: M.init_params(cfg, k))(jax.random.PRNGKey(args.seed))
        if args.harness:
            harness(cfg, cell, params, [int(x) for x in args.harness.split(",")], args.rehearsal)
        if args.check:
            ok = check(cfg, hf, arch, params, lengths(args.check), args.seed, CHUNK // shrink,
                       cfg.index_topk)
        if args.flips:
            flips(cfg, hf, arch, params, args.flips // shrink, args.seed, cfg.index_topk)
        del params
    if args.time:
        timing(cfg, lengths(args.time), not args.rehearsal, cfg.index_topk,
               lanes=4 if args.rehearsal else 32)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
