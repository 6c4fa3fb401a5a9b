"""A model with a latent cache and routed experts through the engine on the
CPU (``--decode-horizon 8``): the scheduler's pages, radix cache, preemption
and overlapped schedule are untouched and hold for a latent page as for K and
V; the expert layers' counters; the step record; what the engine refuses.
Every stream is held to the greedy tokens of the plain reference
(``benchmark/architectures/pangu_ultra_moe.py``)."""

import numpy as np
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.flight_recorder import (
    MOE_STEP_RECORD_KEYS,
    SCHEMA_VERSION,
    STEP_RECORD_KEYS,
)
from smg_tpu.models.config import tiny_pangu_moe_config, tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer
from tests.test_pangu_moe import ARCH, hf_of

HELD = (4, 8)  # experts 4..11 of the router's 16


def make_engine(num_pages=128, max_batch=8, max_seq_len=256, horizon=8, overlap=True,
                model=None, **kw) -> Engine:
    sched = {k: kw.pop(k) for k in list(kw) if k in ("watermark_pages", "speculative")}
    cfg = EngineConfig(
        model=model or tiny_pangu_moe_config(held=HELD),
        cache=CacheConfig(page_size=16, num_pages=num_pages, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=max_batch, max_seq_len=max_seq_len, max_prefill_tokens=64,
            prefill_token_buckets=(16, 32, 64), decode_batch_buckets=(4, 8),
            decode_horizon=horizon, overlap_schedule=overlap, **sched),
        dtype="float32", **kw)
    return Engine(cfg, tokenizer=MockTokenizer())


def greedy(n, **kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=n, ignore_eos=True, **kw)


def reference_tokens(engine, prompt, n) -> list:
    hf, toks = hf_of(engine.config.model), list(prompt)
    for _ in range(n):
        row = ARCH.logits(engine.runner.params, hf, np.asarray(toks, np.int32), [len(toks) - 1])
        toks.append(int(np.argmax(row[0])))
    return toks[len(prompt):]


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 500, size=n).tolist() for n in lengths]


def run_all(engine, jobs, steps=3000) -> dict:
    out = {i: [] for i in range(len(jobs))}
    done = set()

    def sink(i):
        def on(o):
            out[i].extend(o.new_token_ids)
            if o.finished:
                done.add(i)
        return on

    for i, (p, sp) in enumerate(jobs):
        engine.submit(p, sp, on_output=sink(i))
    for _ in range(steps):
        engine.step()
        if len(done) == len(jobs):
            break
    assert len(done) == len(jobs), engine.loads()
    return out


@pytest.fixture(scope="module")
def engine():
    return make_engine()


def test_streams_are_the_references_through_chunks_groups_and_frames(engine):
    (short, long_, a, b, c) = prompts(1, 40, 150, 20, 70, 33)
    r = engine.generate(prompt_ids=short, sampling=greedy(10))
    assert r.token_ids == reference_tokens(engine, short, 10)
    # 150 tokens over a 64-token budget: two continuing chunks and a final one
    r = engine.generate(prompt_ids=long_, sampling=greedy(9))
    assert r.token_ids == reference_tokens(engine, long_, 9)
    out = run_all(engine, [(a, greedy(12)), (b, greedy(5)), (c, greedy(17))])
    for i, (p, n) in enumerate(((a, 12), (b, 5), (c, 17))):
        assert out[i] == reference_tokens(engine, p, n)
    loads = engine.loads()
    assert loads["lookahead_kept"] > 0 and loads["audit"]["clean"]
    assert "state_slots_total" not in loads and "leaked_state_slots" not in loads["audit"]


@pytest.mark.parametrize("lengths, parts", [
    ([10, 12, 9, 40], [[3], [0, 1, 2]]),  # 1 x 64, then 4 x 16: the group whole pads to 4 x 64
    ([16, 16, 16, 16, 16], [[0, 1, 2, 3], [4]]),
    ([30, 31], [[0, 1]]),  # 2 x 32 is the budget
    ([64, 5], [[0], [1]]),
    ([20, 9, 33, 17, 50], [[2], [4], [0, 3], [1]]),  # a part holds one token bucket
])
def test_a_group_is_split_so_that_no_part_pads_past_the_steps_budget(engine, lengths, parts):
    sched = engine.config.scheduler  # a budget of 64 tokens, buckets 16, 32, 64
    got = engine.runner._split_group(lengths)
    assert got == parts
    for rows in got:
        G = 1 << (len(rows) - 1).bit_length()
        assert G * sched.prefill_bucket(max(lengths[i] for i in rows)) <= sched.max_prefill_tokens


def test_the_cache_is_one_buffer_and_loads_say_how_it_is_laid_out(engine):
    r = engine.runner
    assert r.k_cache.shape == (3, 128, 16, 128) and r.v_cache.size == 0
    assert r.spec.bytes_per_page == 3 * 16 * 128 * 4
    loads = engine.loads()
    assert loads["latent_cache"]["entry_bytes_published"] == (96 + 16) * 4
    assert loads["latent_cache"]["entry_bytes_laid_out"] == 128 * 4
    assert "no V buffer" in loads["latent_cache"]["layout"]
    assert loads["attention"]["form"].startswith("latent")
    assert loads["mesh"]["kv_bytes_per_device"] == r.k_cache.nbytes
    assert not r.widest_table_only  # XLA attention on the CPU keeps its table buckets


def test_a_runner_with_one_decode_program_a_bucket_gets_the_widest_table(monkeypatch):
    """Where the decode kernel reads each lane's own pages the scheduler asks
    for one table width; ``Scheduler._mp_bucket`` itself stays a function of
    the table's width alone (the benchmark's ``warm.reachable`` and its test
    call it on a bare namespace)."""
    import types

    from smg_tpu.engine.latent_runner import LatentModelRunner
    from smg_tpu.engine.scheduler import Scheduler

    assert Scheduler._mp_bucket(types.SimpleNamespace(mp=512), 9) == 16
    assert [make_engine().scheduler._mp_bucket(n) for n in (1, 9, 99)] == [8, 16, 16]
    monkeypatch.setattr(LatentModelRunner, "widest_table_only", True)
    sched = make_engine().scheduler
    assert [sched._mp_bucket(n) for n in (1, 9, 99)] == [sched.mp] * 3 == [16] * 3


@pytest.mark.parametrize("model", ["pangu", "longcat"])
@pytest.mark.parametrize("lengths", [(40,), (30, 27)], ids=["a-group-of-1", "a-group-of-2"])
def test_cold_groups_serve_the_same_tokens_through_the_prefill_kernel(model, lengths,
                                                                      monkeypatch):
    """Heads of the published widths (128 + 64 lanes of key, 128 of value), the
    cold grouped prefill's attention through the online-softmax kernel
    (interpreted: the CPU has no Mosaic) and through XLA's form: the same
    tokens from the first on through ``Engine.submit`` and ``step``, the same
    entries in the pages, and the launches counted under ``pallas_prefill``."""
    import dataclasses

    from smg_tpu.models.config import tiny_longcat_flash_config

    tiny = tiny_pangu_moe_config(held=HELD) if model == "pangu" else tiny_longcat_flash_config(
        held=(6, 6))
    cfg = dataclasses.replace(tiny, num_heads=2, qk_nope_head_dim=128, qk_rope_head_dim=64,
                              v_head_dim=128)
    jobs = [(p, greedy(6)) for p in prompts(21, *lengths)]
    xla, kernel = make_engine(model=cfg), make_engine(model=cfg)
    assert kernel.runner._grouped_prefill_impl_for(2, 64, True) == "xla"  # off the TPU
    monkeypatch.setattr(kernel.runner, "_grouped_prefill_impl_for",
                        lambda G, T, no_ctx: "pallas_interpret" if no_ctx else "xla")
    want, got = run_all(xla, jobs), run_all(kernel, jobs)
    assert got == want and all(len(t) == 6 for t in got.values())
    lx, lk = (e.loads()["attention"]["launches"] for e in (xla, kernel))
    assert lx["pallas_prefill"] == 0 and lx["xla"] > 0
    assert lk["pallas_prefill"] == 1  # the one cold group; decode stays XLA's here
    shape = f"{len(lengths)}x{32 if len(lengths) > 1 else 64}"
    assert kernel.loads()["prefill_padding"]["launches"] == {shape: 1}
    np.testing.assert_allclose(np.asarray(kernel.runner.k_cache[:, 1:]),
                               np.asarray(xla.runner.k_cache[:, 1:]), atol=1e-5)


def test_a_radix_hit_on_a_latent_prefix_is_reused(engine):
    (p,) = prompts(3, 80)
    first = engine.generate(prompt_ids=p, sampling=greedy(8))
    hits = engine.loads()["radix_hit_pages"]
    again = engine.generate(prompt_ids=p, sampling=greedy(8))  # its pages are cached now
    assert again.token_ids == first.token_ids == reference_tokens(engine, p, 8)
    # the rotary key sits in the page rotated at its absolute position: a
    # cached prefix is reusable as cached K and V are
    assert again.cached_tokens >= 64 and engine.loads()["radix_hit_pages"] > hits
    longer = p + prompts(33, 21)[0]
    assert engine.generate(prompt_ids=longer, sampling=greedy(6)).token_ids \
        == reference_tokens(engine, longer, 6)


def test_the_radix_cache_counts_the_pages_no_request_holds():
    from smg_tpu.engine.radix_cache import RadixCache

    radix = RadixCache(4)
    a, b = list(range(100, 112)), list(range(100, 108)) + [7, 7, 7, 7]  # 2 pages shared
    radix.insert(a, [1, 2, 3])
    radix.insert(b, [1, 2, 4])
    assert (radix.num_cached_pages, radix.num_unpinned_pages) == (4, 4)
    _, leaf_a = radix.match_prefix(a)
    _, leaf_b = radix.match_prefix(b)
    radix.lock(leaf_a)
    assert radix.num_unpinned_pages == 1  # the pin holds the whole path
    radix.lock(leaf_b)
    assert radix.num_unpinned_pages == 0
    assert radix.lock_stats()["locked_nodes"] == 4 - radix.num_unpinned_pages
    radix.unlock(leaf_a)
    assert radix.num_unpinned_pages == 1 and radix.evict(4) == [3]
    radix.unlock(leaf_b)
    assert radix.num_unpinned_pages == radix.num_cached_pages == 3
    assert sorted(radix.evict(8)) == [1, 2, 4] and radix.num_unpinned_pages == 0


def _evict_by_walk(radix, n_pages):
    """``RadixCache.evict`` as it was: every unpinned leaf of a walk of the
    whole tree, oldest first, each freed with the chain above it."""
    leaves = sorted((n for n in radix._iter_nodes() if n.is_leaf and n.refcount == 0),
                    key=lambda n: n.last_access)
    freed = []
    for node in leaves:
        while node is not radix.root and node.is_leaf and node.refcount == 0 \
                and len(freed) < n_pages:
            del node.parent.children[node.key]
            freed.append(node.page)
            radix._size -= 1
            node = node.parent
    return freed


@pytest.mark.parametrize("seed", range(6))
def test_eviction_from_the_heap_frees_what_a_walk_of_the_tree_freed(seed):
    """The same inserts, matches, pins and evictions on two trees, one evicted
    by the heap of leaves and one by the walk it replaced: the same pages in
    the same order, whatever was pinned, extended or touched in between."""
    from smg_tpu.engine.radix_cache import RadixCache

    rng = np.random.default_rng(seed)
    heap, walk = RadixCache(4), RadixCache(4)
    stems = [rng.integers(2, 50, size=8).tolist() for _ in range(5)]
    page, held = 1, []
    for _ in range(400):
        op = rng.integers(4)
        if op == 0:  # a prompt behind one of a few shared stems
            toks = stems[rng.integers(5)][: 4 * rng.integers(3)] \
                + rng.integers(2, 50, size=4 * rng.integers(1, 6)).tolist()
            pages = list(range(page, page + len(toks) // 4))
            page += len(pages)
            assert heap.insert(toks, pages) == walk.insert(toks, pages)
            if rng.integers(3) == 0:
                nodes = [r.match_prefix(toks)[1] for r in (heap, walk)]
                for r, n in zip((heap, walk), nodes):
                    r.lock(n)
                held.append(nodes)
        elif op == 1 and held:
            for r, n in zip((heap, walk), held.pop(rng.integers(len(held)))):
                r.unlock(n)
        elif op == 2:  # a probe that touches a path and pins nothing
            toks = stems[rng.integers(5)] + [1] * 4
            assert heap.match_prefix(toks)[0] == walk.match_prefix(toks)[0]
        else:
            n = int(rng.integers(1, 7))
            assert heap.evict(n) == _evict_by_walk(walk, n)
        assert (heap.num_cached_pages, heap.num_unpinned_pages) \
            == (walk.num_cached_pages, walk.num_unpinned_pages)
    while held:
        for r, n in zip((heap, walk), held.pop()):
            r.unlock(n)
    assert heap.evict(10**6) == _evict_by_walk(walk, 10**6) and heap.num_cached_pages == 0
    assert len(heap._lru) <= 4 * 0 + 1024


@pytest.mark.parametrize("family", ["latent", "llama"])
def test_a_pool_filled_by_finished_prompts_does_not_shorten_the_frames(family):
    """64 tokens of pool beyond what the lanes hold, all of it in the radix
    cache: on the free pool alone a frame would run one column; the unpinned
    pages are a frame's to count on under every runner (the Llama path had
    its exception until PR 49), so it runs its eight, preempts nobody, and
    the streams are the one-column schedule's."""
    jobs = [(p, greedy(40)) for p in prompts(5, 30, 45, 28, 50)]
    model = tiny_test_config() if family == "llama" else None
    streams = {}
    for horizon in (8, 1):
        e = make_engine(num_pages=40, watermark_pages=1, model=model, horizon=horizon)
        for p in prompts(6, 64, 64, 64, 64, 64, 64):  # fill the cache with finished prompts
            e.generate(prompt_ids=p, sampling=greedy(2))
        assert e.scheduler.pool.free_count < 16 < e.scheduler.radix.num_unpinned_pages
        before = dict(e.loads()["decode_launches"])
        streams[horizon] = run_all(e, jobs)
        after = e.loads()["decode_launches"]
        assert after["page_headroom"] == before["page_headroom"]
        assert e.loads()["preemptions"] == 0 and e.loads()["audit"]["clean"]
        if horizon == 8:
            assert after["full"] > before["full"]
    assert streams[8] == streams[1]


def test_overlapped_and_synchronous_schedules_give_the_same_tokens():
    ps = prompts(5, 30, 61, 17, 44)
    jobs = [(p, SamplingParams(temperature=0.8, top_k=20, max_new_tokens=n, ignore_eos=True))
            for p, n in zip(ps, (19, 9, 26, 13))]
    streams = [run_all(make_engine(overlap=o), jobs) for o in (True, False)]
    assert streams[0] == streams[1]
    one = run_all(make_engine(horizon=1), jobs)
    assert one == streams[0]  # K=8 frames are K=1 steps, byte for byte


@pytest.mark.parametrize("horizon", [1, 8])
def test_a_frame_launched_behind_a_grouped_prefill_leaves_the_streams_alone(horizon):
    """Requests that arrive while others decode, one of them a group that
    this runner prefills in two launches (two token buckets): the step's
    decode frame goes out before the first tokens are fetched, and the
    streams are the synchronous schedule's at temperature 0 and 0.8."""
    from tests.test_overlap import staged_streams

    ps = prompts(9, 30, 22, 41, 19, 40, 27)
    jobs = [(f"j{i}", p, SamplingParams(temperature=t, top_k=20, max_new_tokens=n,
                                        ignore_eos=True))
            for i, (p, t, n) in enumerate(zip(
                ps, (0.8, 0.0, 0.8, 0.8, 0.0, 0.8), (21, 17, 12, 15, 9, 11)))]
    at = [0, 0, 3, 6, 6, 11]
    engs = [make_engine(overlap=o, horizon=horizon) for o in (True, False)]
    assert len(engs[0].runner._split_group([19, 40])) == 2
    streams = [staged_streams(e, jobs, at) for e in engs]
    assert streams[0] == streams[1]
    loads = engs[0].loads()
    assert loads["prefill_chained_launches"] >= 4
    assert not any(loads["prefill_sync_launches"].values())
    assert loads["wasted_decode_tokens"] == 0 and loads["audit"]["clean"]
    assert engs[1].loads()["prefill_chained_launches"] == 0


def test_a_preempted_request_comes_out_as_an_undisturbed_one():
    eng = make_engine(num_pages=12, max_batch=4, max_seq_len=128, watermark_pages=1)
    ps = prompts(4, 30, 33, 36)
    out = run_all(eng, [(p, greedy(40)) for p in ps])
    loads = eng.loads()
    assert loads["preemptions"] > 0 and loads["audit"]["clean"]
    for i, p in enumerate(ps):
        assert out[i] == reference_tokens(eng, p, 40)


def test_the_moe_counters_are_the_routing_computed_by_hand():
    """One request alone, K = 8: every decode token after the first passes two
    expert layers with four picks each; the picks on held experts are read off
    the reference's own router."""
    import jax
    import jax.numpy as jnp

    eng = make_engine()
    (p,) = prompts(6, 25)
    n = 17
    r = eng.generate(prompt_ids=p, sampling=greedy(n))
    moe = eng.loads()["moe"]
    assert (moe["experts"], moe["experts_held"], moe["top_k"]) == (16, 8, 4)
    assert moe["picks"] == (n - 1) * 2 * 4  # the first token comes out of the prefill
    # by hand: hidden states of the decoded positions from the reference's layers
    cfg, params = eng.config.model, eng.runner.params
    toks = np.asarray(p + r.token_ids, np.int32)
    shape = ARCH._shape(hf_of(cfg))
    held = hit = 0
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks[:-1])].astype(jnp.float32)
        h = ARCH._layer(h, ARCH._Weights(params["dense"], 0, False), dense=True, shape=shape)
        for i in range(2):
            w = ARCH._Weights(params["moe"], i, True)
            a = ARCH._attention(ARCH._rms(h, w("attn_norm"), shape["eps"]), w, dn=shape["dn"],
                                rkv=shape["rkv"], eps=shape["eps"], theta=shape["theta"])
            mid = h + ARCH._rms(a, w("post_attn_norm"), shape["eps"])
            x = ARCH._rms(mid, w("mlp_norm"), shape["eps"])
            picked = np.asarray(jax.lax.top_k(jax.nn.sigmoid(x @ w("router")), 4)[1])[len(p):]
            mine = (picked >= HELD[0]) & (picked < HELD[0] + HELD[1])
            held += int(mine.sum())
            hit += sum(len(set(row[m])) for row, m in zip(picked, mine))
            h = ARCH._layer(h, w, dense=False, shape=shape)
    assert (moe["picks_held"], moe["experts_hit"]) == (held, hit)
    assert 1 <= moe["rows_max"] <= 4
    count = lambda h: eng.metrics.moe_picks.labels(held=h)._value.get()
    assert (count("true"), count("false")) == (held, moe["picks"] - held)


def test_the_step_record_carries_columns_run_and_the_frames_counts():
    eng = make_engine()
    (p,) = prompts(7, 20)
    eng.generate(prompt_ids=p, sampling=greedy(12))  # 11 decoded: a frame of 8, a frame of 3
    dump = eng.dump_flight("manual")
    assert dump["schema_version"] == SCHEMA_VERSION == 11
    ring = dump["ring"]
    assert all(STEP_RECORD_KEYS <= set(r) <= STEP_RECORD_KEYS | MOE_STEP_RECORD_KEYS
               for r in ring)
    decodes = [r for r in ring if r["kind"] == "decode"]
    assert [r["columns_run"] for r in decodes] == [8, 3]
    assert all(r["columns_run"] == min(r["horizon"], r["decode_tokens"]) for r in decodes)
    for r in decodes:  # two expert layers, one lane, top 4
        assert 0 <= r["moe_experts_hit"] <= r["moe_picks_held"] <= r["columns_run"] * 8
    moe = eng.loads()["moe"]
    assert sum(r["moe_picks_held"] for r in decodes) == moe["picks_held"]
    assert all("moe_picks_held" not in r and r["columns_run"] == 0
               for r in ring if r["kind"] != "decode")


def test_a_llama_engine_writes_columns_run_and_no_moe_keys():
    eng = make_engine(model=tiny_test_config())
    eng.generate(prompt_ids=[5, 6, 7, 8], sampling=greedy(6))
    ring = eng.dump_flight("manual")["ring"]
    assert all(set(r) == STEP_RECORD_KEYS for r in ring)
    assert [r["columns_run"] for r in ring if r["kind"] == "decode"] == [5]
    loads = eng.loads()
    assert "moe" not in loads and "latent_cache" not in loads


def test_what_the_model_cannot_do_is_refused_with_a_sentence():
    from smg_tpu.config.validation import ConfigError
    from smg_tpu.models.pangu_moe import SERVING_LIMITS

    with pytest.raises(ConfigError, match="no verify block"):
        make_engine(speculative=True)
    with pytest.raises(ConfigError, match="no verify block"):
        make_engine(draft_model=tiny_test_config())
    with pytest.raises(ConfigError, match="runs on one device"):
        make_engine(parallel=ParallelConfig(tp=2))
    eng = make_engine()
    for call, key in ((lambda: eng.runner.load_lora("a", {}), "lora"),
                      (lambda: eng.runner.embed([[1, 2, 3]]), "embeddings"),
                      (lambda: eng.runner.export_pages([1]), "kv_transfer"),
                      (lambda: eng.runner._decode_spec_fn(4, 8, 4), "speculative")):
        with pytest.raises(ValueError) as e:
            call()
        assert str(e.value) == SERVING_LIMITS[key]
    assert eng.runner.supports_kv_transfer is False
    from smg_tpu.models.weights import load_params

    cfg = eng.config.replace(model_path="/nonexistent")
    with pytest.raises(ValueError) as e:
        load_params(cfg)
    assert str(e.value) == SERVING_LIMITS["checkpoint"]
    assert set(SERVING_LIMITS) == {"speculative", "lora", "embeddings", "mesh", "kv_transfer",
                                   "checkpoint"}
