"""A model with window layers beside full ones through the engine on the CPU
(``--decode-horizon 8``): a slot of rings a sequence beside its pages, bound
at admission and freed with them; a radix match declined for want of the
window entries at its end; a preemption that prefills again; frames thrown
away by an abort, a stop token and a stop string's rollback, which cost
nothing here; the two kinds of cache and the expert layers in ``loads()``.
Every stream is held to the greedy tokens of the plain reference
(``benchmark/architectures/mimo_v2_flash.py``)."""

import numpy as np
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.flight_recorder import MOE_STEP_RECORD_KEYS, SCHEMA_VERSION, STEP_RECORD_KEYS
from smg_tpu.models.config import (
    tiny_mimo_config,
    tiny_olmo_hybrid_config,
    tiny_pangu_moe_config,
    tiny_test_config,
)
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer
from tests.test_mimo import ARCH, HELD, hf_of

WINDOW_KEYS = {"kv_groups", "window_prefix_hits_declined", "window_recomputed_tokens"}


def make_engine(num_pages=128, max_batch=8, max_seq_len=256, horizon=8, overlap=True,
                model=None, params=None, **kw) -> Engine:
    sched = {k: kw.pop(k) for k in list(kw) if k in ("watermark_pages", "speculative")}
    cfg = EngineConfig(
        model=model or tiny_mimo_config(held=HELD),
        cache=CacheConfig(page_size=16, num_pages=num_pages, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=max_batch, max_seq_len=max_seq_len, max_prefill_tokens=64,
            prefill_token_buckets=(16, 32, 64), decode_batch_buckets=(4, 8),
            decode_horizon=horizon, overlap_schedule=overlap, **sched),
        dtype="float32", **kw)
    return Engine(cfg, tokenizer=MockTokenizer(), params=params)


def greedy(n, **kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=n, ignore_eos=True, **kw)


def reference_tokens(engine, prompt, got) -> list:
    """The reference's greedy token after every prefix of ``prompt + got``
    from the prompt on: ``got`` itself, if the engine decoded as the reference
    does (one forward of the whole sequence; attention is causal)."""
    toks = np.asarray(list(prompt) + list(got), np.int32)
    rows = ARCH.logits(engine.runner.params, hf_of(engine.config.model), toks,
                       list(range(len(prompt) - 1, len(toks) - 1)))
    return [int(t) for t in np.argmax(rows, axis=-1)]


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
    """Prompts under and over a step's budget (64: the long ones continue
    behind a live prefix longer than the window), outputs past the ring's
    wrap (32 entries), five lanes at once."""
    ps = prompts(0, 5, 40, 100, 150, 23)
    lengths = (30, 44, 30, 52, 9)
    out = run_all(engine, [(p, greedy(n)) for p, n in zip(ps, lengths)])
    for i, (p, n) in enumerate(zip(ps, lengths)):
        assert len(out[i]) == n and out[i] == reference_tokens(engine, p, out[i]), i
    loads = engine.loads()
    assert loads["audit"]["clean"] and loads["audit"]["leaked_window_slots"] == 0
    assert loads["kv_groups"]["window"]["slots_in_use"] == 0
    assert loads["window_recomputed_tokens"] == 0 and loads["preemptions"] == 0


def test_what_a_sequence_holds_for_the_window_layers_does_not_grow_with_its_context():
    short, long = make_engine(max_seq_len=256), make_engine(max_seq_len=1024, num_pages=256)
    a, b = (e.loads()["kv_groups"] for e in (short, long))
    assert a["window"] == b["window"]
    assert a["window"] == {"layers": 3, "window": 8, "ring_tokens": 32,
                           "slot_bytes": 3 * 32 * (8 * 64 + 8 * 32) * 4,
                           "slots_total": 8 + 8, "slots_in_use": 0}
    assert a["global"] == {"layers": 2, "bytes_per_token": 2 * (4 * 64 + 4 * 32) * 4,
                           "pages_total": 127, "pages_in_use": 0}
    assert short.runner.s_pool.shape == (3, 17, 32, 512)
    assert short.runner.c_pool.shape == (3, 17, 32, 256)
    assert short.runner.k_cache.shape == (2, 128, 16, 256)
    assert short.runner.v_cache.shape == (2, 128, 16, 128)
    forms = short.loads()["attention"]["decode_forms"]
    assert "smg.attn.decode" in forms["full"] and "smg.attn.window_decode" in forms["window"]


def test_a_reused_slot_needs_no_clearing(engine):
    """One lane at a time: every request gets the slot the last one freed,
    with the last one's entries still in its rings."""
    for p in prompts(2, 30, 45, 30):
        got = engine.generate(prompt_ids=p, sampling=greedy(12)).token_ids
        assert got == reference_tokens(engine, p, got)
    assert engine.loads()["kv_groups"]["window"]["slots_in_use"] == 0


def test_a_radix_match_without_the_window_entries_prefills_from_the_first_token(engine):
    (p,) = prompts(3, 80)
    before = engine.loads()["window_prefix_hits_declined"]
    first = engine.generate(prompt_ids=p, sampling=greedy(8))
    again = engine.generate(prompt_ids=p, sampling=greedy(8))  # its pages are cached now
    assert again.token_ids == first.token_ids == reference_tokens(engine, p, first.token_ids)
    assert again.cached_tokens == 0
    assert engine.loads()["window_prefix_hits_declined"] == before + 1


def test_a_preempted_request_comes_out_as_an_undisturbed_one():
    eng = make_engine(num_pages=12, max_batch=4, max_seq_len=128, watermark_pages=1)
    ps = prompts(4, 30, 33, 36)
    out = run_all(eng, [(p, greedy(40)) for p in ps])
    loads = eng.loads()
    assert loads["preemptions"] > 0 and loads["window_recomputed_tokens"] > 0
    for i, p in enumerate(ps):
        assert out[i] == reference_tokens(eng, p, out[i])
    assert loads["audit"]["clean"] and loads["kv_groups"]["window"]["slots_in_use"] == 0


def test_the_leak_audit_sees_a_leaked_slot():
    eng = make_engine()
    assert eng.loads()["audit"]["clean"]
    leaked = eng.scheduler.state_pool.alloc()  # bound to no sequence
    audit = eng.loads()["audit"]
    assert audit["leaked_window_slots"] == 1 and not audit["clean"]
    assert "leaked_state_slots" not in audit
    eng.scheduler.state_pool.free(leaked)
    assert eng.loads()["audit"]["clean"]


def test_overlapped_and_synchronous_schedules_give_the_same_tokens():
    ps = prompts(5, 25, 31, 90, 40)
    lengths = (6, 8, 29, 21)  # ends mid-frame and on a frame's last column
    outs = []
    for overlap in (True, False):
        eng = make_engine(overlap=overlap)
        outs.append(run_all(eng, [(p, greedy(n)) for p, n in zip(ps, lengths)]))
        loads = eng.loads()
        assert loads["window_recomputed_tokens"] == 0 and loads["preemptions"] == 0
    assert outs[0] == outs[1]


@pytest.mark.parametrize("horizon", [1, 8])
def test_a_frame_launched_behind_a_grouped_prefill_leaves_the_streams_alone(horizon):
    """Requests that arrive while others decode: the step's decode frame goes
    out before the first tokens are fetched, stop ids or not (a frame thrown
    away costs nothing here, so nothing waits as a recurrent model's does),
    and the streams are the synchronous schedule's at temperature 0 and 0.8."""
    from tests.test_overlap import staged_streams

    ps = prompts(9, 30, 22, 41, 19, 20, 18, 27)
    jobs = [(f"j{i}", p, SamplingParams(temperature=t, top_k=20, max_new_tokens=n,
                                        stop_token_ids=[3] if i % 2 else []))
            for i, (p, t, n) in enumerate(zip(
                ps, (0.8, 0.0, 0.8, 0.8, 0.0, 0.8, 0.0), (21, 17, 12, 15, 9, 11, 14)))]
    at = [0, 0, 3, 6, 6, 6, 11]
    engs = [make_engine(overlap=o, horizon=horizon) for o in (True, False)]
    streams = [staged_streams(e, jobs, at) for e in engs]
    assert streams[0] == streams[1]
    loads = engs[0].loads()
    assert loads["prefill_chained_launches"] >= 4
    assert loads["prefill_sync_launches"]["recurrent_stop_ids"] == 0
    assert loads["window_recomputed_tokens"] == 0
    assert loads["audit"]["clean"] and engs[1].loads()["prefill_chained_launches"] == 0


def test_a_stop_token_throws_a_lookahead_away_and_nobody_prefills_again():
    eng = make_engine()
    (p, q) = prompts(6, 30, 44)
    probe = run_all(make_engine(overlap=False), [(p, greedy(24))])[0]
    # a token first seen in the second frame, so that a lookahead is in flight
    at = next(k for k in range(9, 24) if probe[k] not in probe[:k])
    stop, cut = probe[at], probe[: at + 1]
    out = run_all(eng, [(p, greedy(24, stop_token_ids=[stop])), (q, greedy(28))])
    assert out[0] == cut and out[1] == reference_tokens(eng, q, out[1])
    loads = eng.loads()
    assert loads["lookahead_discarded"] > 0
    assert loads["window_recomputed_tokens"] == 0 and loads["preemptions"] == 0


def test_an_abort_with_a_frame_and_its_lookahead_in_flight_costs_no_recompute():
    eng = make_engine()
    (p, q) = prompts(7, 30, 44)
    got = {"p": [], "q": []}
    done = set()
    rid_p = eng.submit(p, greedy(60), on_output=lambda o: got["p"].extend(o.new_token_ids))

    def on_q(o):
        got["q"].extend(o.new_token_ids)
        if o.finished:
            done.add("q")

    eng.submit(q, greedy(40), on_output=on_q)
    for _ in range(4):
        eng.step()
    assert eng.scheduler.inflight is not None
    assert eng.abort(rid_p)
    for _ in range(500):
        eng.step()
        if done:
            break
    assert len(got["q"]) == 40 and got["q"] == reference_tokens(eng, q, got["q"])
    loads = eng.loads()
    assert loads["window_recomputed_tokens"] == 0 and loads["preemptions"] == 0
    assert loads["audit"]["clean"] and loads["kv_groups"]["window"]["slots_in_use"] == 0


def test_a_stop_strings_rollback_mid_lookahead_matches_the_synchronous_schedule():
    """The stop string is found after the step returned, with the next frame
    in flight: the request's trailing tokens are rolled back and the frame is
    thrown away; the lane beside it decodes on from rings that were written
    past what was accepted, token for token as without overlap."""
    from tests.test_overlap import run_streams

    (p, q) = prompts(8, 30, 24)
    probe = run_streams(make_engine(overlap=False), [("p", p, greedy(8))])["p"][0]
    jobs = [("r0", p, greedy(16, stop=[f"w{probe[2]}"])), ("r1", q, greedy(30))]
    engs = [make_engine(overlap=o) for o in (True, False)]
    streams = [run_streams(e, jobs) for e in engs]
    assert streams[0] == streams[1]
    assert streams[0]["r0"][2] == "stop"
    toks = streams[0]["r1"][0]
    assert len(toks) == 30 and toks == reference_tokens(engs[0], q, toks)
    assert engs[0].loads()["window_recomputed_tokens"] == 0


def test_the_counters_are_a_count_by_hand():
    """One request alone: every decode column routes one token in each of the
    four expert layers; the pages and the slot it holds while it runs."""
    eng = make_engine()
    (p,) = prompts(10, 37)
    seen = []
    done = []
    eng.submit(p, greedy(20), on_output=lambda o: done.append(o.finished))
    for _ in range(200):
        eng.step()
        groups = eng.loads()["kv_groups"]
        seen.append((groups["global"]["pages_in_use"], groups["window"]["slots_in_use"]))
        if done and done[-1]:
            break
    assert max(s for _p, s in seen) == 1 and seen[-1][1] == 0
    assert max(pages for pages, _s in seen) >= -(-(37 + 19) // 16)  # what 56 tokens fill
    loads = eng.loads()
    moe = loads["moe"]
    assert (moe["experts"], moe["experts_held"], moe["top_k"], moe["impl"]) == (16, 8, 4, "xla")
    columns = 19  # the first token comes from the prefill
    assert moe["picks"] == columns * 4 * 4  # top 4 in each of 4 expert layers
    assert 0 < moe["picks_held"] < moe["picks"] and 0 < moe["experts_hit"] <= moe["picks_held"]
    assert moe["rows_max"] <= 4
    steps = [s for s in eng.dump_flight("manual")["ring"] if s["kind"] == "decode"]
    assert sum(s["moe_picks_held"] for s in steps) == moe["picks_held"]
    assert sum(s["columns_run"] for s in steps) == columns
    assert all(s["state_lanes"] == 1 for s in steps)


def test_the_step_record_has_no_new_field():
    eng = make_engine()
    eng.generate(prompt_ids=prompts(11, 20)[0], sampling=greedy(10))
    dump = eng.dump_flight("manual")
    assert dump["schema_version"] == SCHEMA_VERSION == 11
    assert all(STEP_RECORD_KEYS <= set(s) <= STEP_RECORD_KEYS | MOE_STEP_RECORD_KEYS
               for s in dump["ring"])


@pytest.mark.parametrize("model", [tiny_test_config, tiny_olmo_hybrid_config,
                                   tiny_pangu_moe_config])
def test_the_other_engines_have_none_of_the_new_keys(model):
    eng = make_engine(model=model())
    loads = eng.loads()
    assert not WINDOW_KEYS & set(loads)
    assert "decode_forms" not in loads["attention"]
    assert "leaked_window_slots" not in loads["audit"]
    assert not hasattr(eng.runner, "window_info")


def test_the_gauges_follow_the_slots():
    eng = make_engine()
    (p,) = prompts(12, 20)
    eng.submit(p, greedy(30), on_output=lambda o: None)
    for _ in range(3):
        eng.step()
    m = eng.metrics
    assert m.window_slots_total._value.get() == 16
    assert m.window_slots_in_use._value.get() == 1
    while eng.scheduler.has_work():
        eng.step()
    assert m.window_slots_in_use._value.get() == 0


@pytest.mark.parametrize("limit, how, needle", [
    ("speculative", lambda m, c: Engine(EngineConfig(
        model=m, cache=c, dtype="float32", scheduler=SchedulerConfig(speculative=True))),
     "multi-token-prediction"),
    ("mesh", lambda m, c: Engine(EngineConfig(
        model=m, cache=c, dtype="float32", parallel=ParallelConfig(tp=2))), "one device"),
    ("checkpoint", lambda m, c: __import__("smg_tpu.models.weights", fromlist=["x"]).load_params(
        EngineConfig(model=m, model_path="/nonexistent", dtype="float32")), "key map"),
    ("lora", lambda m, c: make_engine().runner.load_lora("a", {}), "LoRA"),
    ("embeddings", lambda m, c: make_engine().embed([[1, 2, 3]]), "embedding"),
    ("kv_transfer", lambda m, c: make_engine().runner.export_pages([1]), "not in the pages"),
])
def test_what_the_model_cannot_do_is_refused_with_a_sentence(limit, how, needle):
    from smg_tpu.models import mimo

    assert needle in mimo.SERVING_LIMITS[limit]
    model = tiny_mimo_config()
    cache = CacheConfig(page_size=16, num_pages=64, auto_size=False, dtype="float32")
    with pytest.raises(ValueError, match=needle):
        how(model, cache)


def test_a_sequence_cannot_be_handed_to_another_engine():
    eng = make_engine()
    assert not eng.runner.supports_kv_transfer
    with pytest.raises(ValueError, match="not in the pages"):
        eng.scheduler.prefill_only([1, 2, 3], greedy(1))


# --------------------------------------------------------------------------
# the model's own next-token module as a drafter (``SelfDraftingRunner``)

from smg_tpu.models import exaone_moe as X  # noqa: E402
from smg_tpu.models.config import tiny_exaone_moe_config  # noqa: E402


def exaone(**kw) -> Engine:
    return make_engine(model=tiny_exaone_moe_config(held=HELD), **kw)


def right_drafts():
    import jax

    cfg = tiny_exaone_moe_config(held=HELD)
    return cfg, X.params_whose_drafts_are_right(X.init_params(cfg, jax.random.PRNGKey(3)))


JOBS = ((5, 30), (40, 44), (100, 30), (150, 52), (23, 9))  # prompts over a chunk (64), a window


@pytest.fixture(scope="module")
def one_row():
    """The streams with speculation off: one row a column, the module not run."""
    e = exaone()
    ps = prompts(0, *(p for p, _ in JOBS))
    out = run_all(e, [(p, greedy(n)) for p, (_, n) in zip(ps, JOBS)])
    assert type(e.runner).__name__ == "WindowModelRunner" and "mtp" not in e.loads()
    return ps, out


@pytest.mark.parametrize("overlap", [True, False])
def test_tokens_are_the_same_with_the_module_drafting_and_without(one_row, overlap):
    """Prompts that cross the window (8), a chunked prefill (over 64) and the
    frames' ends (8 columns), five lanes at once, with the lookahead and
    without: at temperature 0 a draft decides only whether it is taken."""
    ps, want = one_row
    e = exaone(speculative=True, overlap=overlap)
    assert type(e.runner).__name__ == "SelfDraftingRunner"
    got = run_all(e, [(p, greedy(n)) for p, (_, n) in zip(ps, JOBS)])
    assert got == want
    loads = e.loads()
    mtp = loads["mtp"]
    assert mtp["columns"] > 0 and mtp["tokens"] == mtp["columns"] + mtp["accepted"]
    assert mtp["tokens"] == sum(n - 1 for _, n in JOBS)  # the first token is the prefill's
    assert mtp["drafted"] >= mtp["accepted"] and mtp["accepted"] <= 2  # random weights
    assert loads["audit"]["clean"] and loads["audit"]["leaked_window_slots"] == 0
    assert loads["window_recomputed_tokens"] == 0
    assert loads["kv_groups"]["global"]["layers"] == 2  # the full layer's and the module's
    assert loads["kv_groups"]["window"]["ring_tokens"] == 48  # the window, two frames of 16


@pytest.mark.parametrize("overlap", [True, False])
def test_with_drafts_that_are_right_every_column_gives_two_tokens(overlap):
    """Weights under which the module's every draft is the model's own next
    token: a lane advances by two a column, a lane one short of its limit by
    one, and the text is the one-row run's."""
    cfg, params = right_drafts()
    ps = prompts(1, 12, 70, 33)
    lengths = (33, 18, 2)  # 32 = 16 columns of two; 17 = 8 of two and one of one; 1 of one
    plain = run_all(make_engine(model=cfg, params=params, overlap=overlap),
                    [(p, greedy(n)) for p, n in zip(ps, lengths)])
    e = make_engine(model=cfg, params=params, speculative=True, overlap=overlap)
    for i, (p, n) in enumerate(zip(ps, lengths)):  # one at a time: the counts are a lane's
        before = dict(e.loads()["mtp"])
        got = run_all(e, [(p, greedy(n))])[0]
        assert got == plain[i] and len(got) == n
        after = e.loads()["mtp"]
        tokens, columns = (after[k] - before[k] for k in ("tokens", "columns"))
        assert tokens == n - 1 and columns == n // 2, (i, tokens, columns)
        assert after["accepted"] - before["accepted"] == tokens - columns
    assert e.loads()["audit"]["clean"]


def test_a_sampling_lane_runs_one_row_beside_lanes_that_draft():
    cfg, params = right_drafts()
    e = make_engine(model=cfg, params=params, speculative=True)
    ps = prompts(2, 20, 20)
    warm = SamplingParams(temperature=0.8, max_new_tokens=21, ignore_eos=True)
    out = run_all(e, [(ps[0], warm), (ps[1], greedy(21))])
    assert len(out[0]) == len(out[1]) == 21
    mtp = e.loads()["mtp"]
    # the greedy lane's 20 tokens took 10 columns; the sampling lane's 20 took 20, tried none
    assert mtp["accepted"] == 10 and mtp["tokens"] == 40
    assert mtp["drafted"] <= 20  # only the greedy lane's columns try a draft


def test_aborts_mid_frame_leave_no_page_slot_or_draft_behind():
    cfg, params = right_drafts()
    e = make_engine(model=cfg, params=params, speculative=True)
    ps = prompts(3, 30, 50, 9)
    rids, seen = [], {i: 0 for i in range(3)}
    for i, p in enumerate(ps):
        rids.append(e.submit(p, greedy(200), on_output=lambda o, i=i: seen.__setitem__(
            i, seen[i] + len(o.new_token_ids))))
    for step in range(40):
        e.step()
        if step == 6:
            e.abort(rids[1])
        if step == 9:
            e.abort(rids[0])
    e.abort(rids[2])
    for _ in range(4):
        e.step()
    loads = e.loads()
    assert seen[1] > 0 and loads["audit"]["clean"], loads["audit"]
    assert loads["audit"]["leaked_window_slots"] == 0 and loads["num_running"] == 0
    assert loads["kv_groups"]["window"]["slots_in_use"] == 0
    # and the engine serves on: a discarded frame's draft is only a wrong guess
    plain = run_all(make_engine(model=cfg, params=params), [(ps[2], greedy(12))])
    assert run_all(e, [(ps[2], greedy(12))]) == plain


def test_a_verify_frames_step_record_counts_tokens_beside_columns():
    cfg, params = right_drafts()
    e = make_engine(model=cfg, params=params, speculative=True)
    run_all(e, [(prompts(4, 16)[0], greedy(17))])
    dump = e.dump_flight("manual")
    assert dump["schema_version"] == SCHEMA_VERSION == 11
    frames = [s for s in dump["ring"] if s["columns_run"]]
    assert frames and all(set(s) >= STEP_RECORD_KEYS for s in frames)
    assert sum(s["decode_tokens"] for s in frames) == 16
    assert sum(s["columns_run"] for s in frames) == 8 == sum(s["spec_accepted"] for s in frames)
    assert all(s["decode_tokens"] - s["spec_accepted"] == s["columns_run"] for s in frames)
    m = e.metrics
    assert m.spec_accepted.labels(tier="mtp")._value.get() == 8
    assert m.spec_drafted.labels(tier="mtp")._value.get() >= 8
