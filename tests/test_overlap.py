"""Overlap-pipeline parity: the one-step-lookahead scheduler must produce
token streams BYTE-IDENTICAL to the synchronous path in every scenario —
greedy, seeded sampling, stop-string rollback mid-lookahead, abort of an
in-flight request, the pipelined speculative schedule, and structured-output
forced sync.  Each test runs the same workload through a fresh engine with
``overlap_schedule`` on and off (fresh engines so the sampling-key counter
starts identically) and compares full per-request streams."""

import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.models.config import tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer


def make_engine(overlap: bool, num_pages=128, max_batch=8, max_seq_len=256,
                **sched_kw) -> Engine:
    cfg = EngineConfig(
        model=tiny_test_config(),
        cache=CacheConfig(page_size=16, num_pages=num_pages, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=max_batch,
            max_seq_len=max_seq_len,
            max_prefill_tokens=64,
            prefill_token_buckets=(16, 32, 64),
            decode_batch_buckets=(4, 8),
            overlap_schedule=overlap,
            **sched_kw,
        ),
        dtype="float32",
    )
    return Engine(cfg, tokenizer=MockTokenizer())


def run_streams(engine: Engine, jobs: list) -> dict:
    """Submit ``jobs`` = [(rid, prompt_ids, sampling)] concurrently, drive
    the step loop inline to completion, and return the full stream per rid:
    (token_ids, text, finish_reason, matched_stop, logprobs)."""
    chunks: dict[str, list] = {rid: [] for rid, _, _ in jobs}
    done: set[str] = set()

    def cb(out):
        chunks[out.rid].append(out)
        if out.finished:
            done.add(out.rid)

    for rid, prompt, sampling in jobs:
        engine.submit(prompt, sampling, rid=rid, on_output=cb)
    for _ in range(5000):
        if len(done) == len(jobs):
            # drain the pipeline (a kept lookahead may still be in flight)
            while engine.scheduler.has_work():
                engine.step()
            break
        engine.step()
    else:
        raise TimeoutError(f"jobs stuck: {engine.loads()}")
    return streams_of(chunks)


def streams_of(chunks: dict) -> dict:
    """The full stream per rid of the outputs collected per rid."""
    out = {}
    for rid, cs in chunks.items():
        toks = [t for c in cs for t in c.new_token_ids]
        text = "".join(c.text_delta for c in cs)
        lps = [round(x, 4) for c in cs for x in c.logprobs]
        out[rid] = (toks, text, cs[-1].finish_reason, cs[-1].matched_stop, lps)
    return out


def assert_parity(jobs, **engine_kw):
    a = run_streams(make_engine(True, **engine_kw), jobs)
    b = run_streams(make_engine(False, **engine_kw), jobs)
    assert a == b, f"overlap diverged from sync:\n{a}\nvs\n{b}"
    return a


def greedy(max_new=8, **kw) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=max_new,
                          ignore_eos=True, **kw)


def test_greedy_parity_concurrent_batch():
    jobs = [
        (f"g{i}", list(range(5 + i, 25 + 3 * i)), greedy(6 + 2 * i))
        for i in range(4)
    ]
    assert_parity(jobs)


def test_greedy_parity_with_horizon():
    jobs = [(f"h{i}", list(range(10 + i, 40 + i)), greedy(13)) for i in range(3)]
    assert_parity(jobs, decode_horizon=4)


def test_seeded_sampling_parity():
    jobs = [
        ("s0", list(range(40, 80)),
         SamplingParams(temperature=0.9, top_k=40, top_p=0.95,
                        max_new_tokens=12, ignore_eos=True)),
        ("s1", list(range(90, 120)),
         SamplingParams(temperature=0.7, min_p=0.05, max_new_tokens=10,
                        ignore_eos=True)),
        ("s2", list(range(130, 150)),
         SamplingParams(temperature=1.1, frequency_penalty=0.4,
                        presence_penalty=0.2, max_new_tokens=9,
                        ignore_eos=True)),
    ]
    assert_parity(jobs)


def test_eos_and_stop_token_parity():
    # natural EOS finishes (ignore_eos off) and stop_token_ids both cut the
    # stream mid-flight, which is exactly what invalidates a lookahead
    probe = run_streams(
        make_engine(False), [("p", list(range(5, 15)), greedy(6))]
    )["p"][0]
    stop_tok = probe[3]
    jobs = [
        ("e0", list(range(5, 15)),
         SamplingParams(temperature=0.0, max_new_tokens=32)),
        ("e1", list(range(5, 15)),
         SamplingParams(temperature=0.0, max_new_tokens=32, ignore_eos=True,
                        stop_token_ids=[stop_tok])),
    ]
    res = assert_parity(jobs)
    assert res["e1"][2] == "stop" and res["e1"][3] == stop_tok


def test_stop_string_rollback_mid_lookahead():
    # the stop string is found at the ENGINE layer after the scheduler step
    # returned, with the next lookahead frame already in flight: the engine
    # rolls back trailing tokens and finishes the request, and the kept
    # frame must be discarded without corrupting any other stream
    probe = run_streams(
        make_engine(False), [("p", list(range(60, 90)), greedy(8))]
    )["p"][0]
    stop_word = f"w{probe[2]}"
    jobs = [
        ("r0", list(range(60, 90)),
         SamplingParams(temperature=0.0, max_new_tokens=16, ignore_eos=True,
                        stop=[stop_word])),
        ("r1", list(range(7, 31)), greedy(14)),  # rides alongside, unaffected
    ]
    res = assert_parity(jobs)
    assert res["r0"][2] == "stop" and res["r0"][3] == stop_word
    assert not res["r0"][1].endswith(stop_word)


def test_stop_string_rollback_with_horizon():
    probe = run_streams(
        make_engine(False, decode_horizon=4),
        [("p", list(range(60, 90)), greedy(8))],
    )["p"][0]
    stop_word = f"w{probe[2]}"
    jobs = [
        ("r0", list(range(60, 90)),
         SamplingParams(temperature=0.0, max_new_tokens=16, ignore_eos=True,
                        stop=[stop_word])),
        ("r1", list(range(7, 31)), greedy(14)),
    ]
    res = assert_parity(jobs, decode_horizon=4)
    assert res["r0"][2] == "stop"


def test_abort_of_inflight_request():
    eng = make_engine(True)
    got: dict[str, list] = {"a": [], "b": []}
    eng.submit(list(range(5, 25)), greedy(64), rid="a",
               on_output=lambda o: got["a"].append(o))
    eng.submit(list(range(30, 55)), greedy(10), rid="b",
               on_output=lambda o: got["b"].append(o))
    for _ in range(3):
        eng.step()
    assert eng.abort("a")
    for _ in range(200):
        if got["b"] and got["b"][-1].finished:
            break
        eng.step()
    assert got["b"][-1].finished and got["b"][-1].finish_reason == "length"
    # the aborted request's lanes went stale with its frame in flight; the
    # survivor's stream must equal a run where "a" never existed past abort
    while eng.scheduler.has_work():
        eng.step()
    assert eng.scheduler.inflight is None
    assert all(s is None for s in eng.scheduler.slots)
    # no page leak: everything not held by the radix cache is back in the pool
    sched = eng.scheduler
    held = sched.radix.num_cached_pages if sched.radix else 0
    assert sched.pool.free_count + held == eng.runner.spec.num_pages - 1


def test_speculative_pipelines_with_parity():
    # spec no longer forces sync: the batched verify frame stays in flight
    # across steps (drafting/detokenize overlap the device pass), and the
    # overlap-on stream must still be byte-identical to overlap-off
    rep = [5, 6, 7, 8] * 8
    jobs = [("sp", rep, greedy(16))]
    res = assert_parity(jobs, speculative=True, spec_max_draft=6)
    eng = make_engine(True, speculative=True, spec_max_draft=6)
    streams = run_streams(eng, jobs)
    assert streams == res
    assert eng.scheduler.num_lookahead_kept > 0  # the spec pipeline engaged
    assert eng.scheduler.inflight is None  # drained clean
    assert eng.scheduler.num_spec_drafted > 0  # spec really ran
    assert eng.scheduler.num_spec_accepted > 0  # repetitive prompt accepts


def test_structured_output_forces_sync():
    # grammar-masked requests need a host-derived vocab mask per token
    # (depends on last step's token), so no lookahead may be launched while
    # one is active — but the stream must still match the sync path
    jobs = [
        ("j0", list(range(20, 50)),
         SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True,
                        regex=r"w[0-9 ]*")),
        ("j1", list(range(70, 95)), greedy(6)),
    ]
    res = assert_parity(jobs)
    assert res["j0"][0]  # produced tokens under the grammar
    eng = make_engine(True)
    run_streams(eng, jobs)
    assert eng.scheduler.num_lookahead_kept == 0


def test_lookahead_engages_and_counters_exposed():
    eng = make_engine(True)
    run_streams(eng, [(f"l{i}", list(range(5 + i, 30 + i)), greedy(16))
                      for i in range(3)])
    loads = eng.loads()
    assert loads["lookahead_kept"] > 0
    assert "lookahead_discarded" in loads
    # sync engines never engage the pipeline
    eng2 = make_engine(False)
    run_streams(eng2, [("x", list(range(5, 30)), greedy(8))])
    assert eng2.loads()["lookahead_kept"] == 0


def test_overlap_metrics_recorded():
    from prometheus_client import generate_latest

    eng = make_engine(True)
    probe = run_streams(eng, [("m", list(range(5, 30)), greedy(12))])
    # a stop-token finish is UNPREDICTED at lookahead-launch time (unlike a
    # length finish, which suppresses the launch), so it forces a discard
    stop_tok = probe["m"][0][4]
    run_streams(eng, [
        ("d", list(range(5, 30)),
         SamplingParams(temperature=0.0, max_new_tokens=32, ignore_eos=True,
                        stop_token_ids=[stop_tok])),
        ("d2", list(range(31, 55)), greedy(20)),
    ])
    text = generate_latest(eng.metrics.registry).decode()
    assert 'smg_engine_lookahead_launches_total{outcome="kept"}' in text
    assert 'smg_engine_lookahead_launches_total{outcome="discarded"}' in text
    assert "smg_engine_deferred_fetch_seconds" in text
    assert 'smg_engine_step_phase_seconds_total{phase="launch_dispatch"}' in text
    assert 'smg_engine_step_phase_seconds_total{phase="consume_fetch"}' in text
    assert eng.scheduler.num_lookahead_discarded > 0


def test_submission_behind_kept_lookahead():
    # submit a second request while the first's lookahead frame is in
    # flight: sync admits before decoding, so the kept frame must be
    # discarded and the combined batch must match the sync schedule
    def run(overlap):
        eng = make_engine(overlap)
        got: dict[str, list] = {"a": [], "b": []}
        eng.submit(list(range(5, 25)), greedy(20), rid="a",
                   on_output=lambda o: got["a"].append(o))
        for _ in range(4):
            eng.step()
        eng.submit(list(range(40, 70)), greedy(12), rid="b",
                   on_output=lambda o: got["b"].append(o))
        for _ in range(300):
            if all(v and v[-1].finished for v in got.values()):
                break
            eng.step()
        while eng.scheduler.has_work():
            eng.step()
        return {
            rid: [t for o in v for t in o.new_token_ids]
            for rid, v in got.items()
        }

    assert run(True) == run(False)


def test_preemption_under_page_pressure_parity():
    # tight page pool: growth forces eviction/preemption, which the
    # lookahead capacity precheck must route through the sync path
    jobs = [(f"p{i}", list(range(5 + 17 * i, 37 + 17 * i)), greedy(24))
            for i in range(4)]
    a = run_streams(make_engine(True, num_pages=24, max_batch=4), jobs)
    b = run_streams(make_engine(False, num_pages=24, max_batch=4), jobs)
    assert a == b


def test_flush_cache_with_stale_inflight_frame():
    eng = make_engine(True)
    run_streams(eng, [("f", list(range(5, 30)), greedy(6))])
    # pipeline drained by run_streams; force a frame then finish everything
    assert eng.flush_cache()
    r = eng.generate(prompt_ids=list(range(5, 30)), sampling=greedy(6))
    assert len(r.token_ids) == 6


def test_engine_stop_drops_inflight():
    eng = make_engine(True)
    eng.start()
    eng.submit(list(range(5, 25)), greedy(64), rid="s")
    import time

    time.sleep(0.3)  # let the loop launch frames
    eng.stop()
    assert eng.scheduler.inflight is None


@pytest.mark.slow  # subsumed by the temp-0.8 variant below (key-sensitive)
def test_chunked_prefill_parity_greedy():
    # a multi-chunk prompt admits under the per-step budget (64) while a
    # short one decodes: the resumable-prefill steps are fold-free, so the
    # pipeline keeps lookahead frames across them — streams must still be
    # byte-identical to the sync path
    jobs = [
        ("long", list(range(5, 155)), greedy(8)),
        ("c0", list(range(200, 230)), greedy(14)),
    ]
    assert_parity(jobs)


def test_chunked_prefill_parity_sampled():
    # temp 0.8: any key-fold ordering slip between the chunked prefill and
    # the chained decode launches flips the sampled streams
    jobs = [
        ("long", list(range(5, 185)),
         SamplingParams(temperature=0.8, top_k=40, max_new_tokens=10,
                        ignore_eos=True)),
        ("c0", list(range(200, 240)),
         SamplingParams(temperature=0.8, max_new_tokens=12, ignore_eos=True)),
        ("c1", list(range(250, 275)), greedy(9)),
    ]
    assert_parity(jobs, decode_horizon=2)


@pytest.mark.slow  # legacy-policy variant; budgeted-vs-legacy parity also
# rides tests/test_chunked_prefill.py in tier-1
def test_chunked_prefill_parity_legacy_policy():
    # the legacy drain-the-queue policy must keep its own overlap/sync parity
    jobs = [
        ("long", list(range(5, 155)), greedy(8)),
        ("c0", list(range(200, 230)),
         SamplingParams(temperature=0.9, max_new_tokens=8, ignore_eos=True)),
    ]
    assert_parity(jobs, prefill_mix_policy="throughput")


def test_admission_on_slot_freed_by_inflight_finish_parity():
    # max_batch 2: request "b" waits for a slot that only frees when "a"
    # finishes INSIDE the in-flight frame.  Sync admits "b" the same step
    # the slot frees; the overlap prefill phase must therefore run with
    # post-consume capacity (regression: admission ran pre-consume, saw the
    # free slot one step late, and shifted the sampling-key fold order)
    jobs = [
        ("a", list(range(5, 15)),
         SamplingParams(temperature=0.8, max_new_tokens=3, ignore_eos=True)),
        ("c", list(range(30, 50)),
         SamplingParams(temperature=0.8, max_new_tokens=20, ignore_eos=True)),
        ("b", list(range(60, 85)),
         SamplingParams(temperature=0.8, max_new_tokens=8, ignore_eos=True)),
    ]
    assert_parity(jobs, max_batch=2)


def test_admission_on_pages_freed_by_inflight_finish_parity():
    # same shape under PAGE pressure: "b" back-pressures on pages released
    # by "a"'s in-frame finish
    jobs = [
        ("a", list(range(5, 40)),
         SamplingParams(temperature=0.8, max_new_tokens=4, ignore_eos=True)),
        ("c", list(range(50, 80)),
         SamplingParams(temperature=0.8, max_new_tokens=16, ignore_eos=True)),
        ("b", list(range(100, 140)),
         SamplingParams(temperature=0.8, max_new_tokens=6, ignore_eos=True)),
    ]
    assert_parity(jobs, num_pages=16, max_batch=4, max_seq_len=128)


def test_lookahead_survives_admission_over_budget():
    # historically ANY waiting request forced the pipeline sync (kept
    # required an empty queue).  Now: a long prompt mid-resumable-prefill
    # consumes the whole per-step budget, a second prompt waits over budget
    # — and the running lane's lookahead frames stay KEPT through both.
    from smg_tpu.engine.request import RequestStatus

    eng = make_engine(True)
    got: list = []
    eng.submit(list(range(5, 30)), greedy(48), rid="a",
               on_output=lambda o: got.append(o))
    for _ in range(3):  # admit + prime the pipeline
        eng.step()
    eng.submit(list(range(40, 220)), greedy(4), rid="long")  # 3 chunks @ 64
    eng.submit(list(range(300, 330)), greedy(4), rid="w")  # over budget
    sched = eng.scheduler
    kept_while_waiting = 0
    saw_prefilling = False
    for _ in range(4):
        kept0 = sched.num_lookahead_kept
        eng.step()
        lr = sched.requests.get("long")
        if lr is not None and lr.status is RequestStatus.PREFILLING:
            saw_prefilling = True
            assert 0 < lr.prefill_pos < 180
        if sched.num_lookahead_kept > kept0 and sched.waiting:
            kept_while_waiting += 1
    assert saw_prefilling
    assert kept_while_waiting > 0  # the pipeline rode across the admission
    while sched.has_work():
        eng.step()
    assert not sched.requests  # everyone drained to completion


@pytest.mark.slow
@pytest.mark.parametrize("horizon", [1, 2, 4])
def test_exhaustive_parity_sweep(horizon):
    """Randomized stress parity: mixed greedy/sampled/stop/penalty workloads
    at several horizons, staggered finish lengths so lookahead frames get
    invalidated at many different points."""
    import random

    rng = random.Random(horizon)
    jobs = []
    for i in range(6):
        prompt = [rng.randrange(5, 500) for _ in range(rng.randrange(8, 60))]
        if i % 3 == 0:
            sp = greedy(rng.randrange(3, 20))
        elif i % 3 == 1:
            sp = SamplingParams(temperature=0.8, top_k=50,
                                max_new_tokens=rng.randrange(3, 20),
                                ignore_eos=True)
        else:
            sp = SamplingParams(temperature=0.0,
                                max_new_tokens=rng.randrange(6, 24),
                                frequency_penalty=0.3, ignore_eos=True)
        jobs.append((f"x{i}", prompt, sp))
    assert_parity(jobs, decode_horizon=horizon)


# ---- a step that prefills launches its decode frame BEHIND the prefill ----
#
# The step's decode frame is dispatched before the grouped prefill's first
# tokens are fetched (``Scheduler._launch_behind_prefill``): same lanes,
# positions, tables and keys as the synchronous order, the promoted lanes'
# input tokens taken from the prefill's output on the device.  Streams must
# not move, and every case in which the host needs the token first must be
# seen to take the old order.


def staged_streams(engine: Engine, jobs: list, at: list) -> dict:
    """``run_streams`` with job ``i`` submitted before step ``at[i]`` (a
    request that arrives while others decode); (tokens, finish reason,
    logprobs) by rid."""
    chunks: dict[str, list] = {rid: [] for rid, _, _ in jobs}
    done: set[str] = set()

    def cb(out):
        chunks[out.rid].append(out)
        if out.finished:
            done.add(out.rid)

    due = sorted(zip(at, jobs), key=lambda t: t[0])
    for step in range(5000):
        while due and due[0][0] <= step:
            rid, prompt, sampling = due.pop(0)[1]
            engine.submit(prompt, sampling, rid=rid, on_output=cb)
        if len(done) == len(jobs):
            break
        engine.step()
    else:
        raise TimeoutError(f"jobs stuck: {engine.loads()}")
    while engine.scheduler.has_work():
        engine.step()
    return {
        rid: ([t for c in chunks[rid] for t in c.new_token_ids],
              chunks[rid][-1].finish_reason,
              [round(x, 4) for c in chunks[rid] for x in c.logprobs])
        for rid, _, _ in jobs
    }


def staged_parity(jobs, at, make=make_engine, **engine_kw):
    """Streams of the staged jobs under the overlapped schedule, held to the
    synchronous schedule's; also the overlapped engine."""
    eng = make(True, **engine_kw)
    a = staged_streams(eng, jobs, at)
    b = staged_streams(make(False, **engine_kw), jobs, at)
    assert a == b, f"overlap diverged from sync:\n{a}\nvs\n{b}"
    return a, eng


def sampled(temp, max_new, **kw) -> SamplingParams:
    return SamplingParams(temperature=temp, max_new_tokens=max_new,
                          ignore_eos=True, **kw)


@pytest.mark.parametrize("temp", [0.0, 0.8])
@pytest.mark.parametrize("horizon", [1, 8])
def test_a_frame_behind_a_grouped_prefill_leaves_the_streams_alone(horizon, temp):
    # admissions mid-stream, alone and two at once, beside lanes that decode
    jobs = [(f"j{i}", list(range(5 + 11 * i, 25 + 14 * i)), sampled(temp, 10 + 3 * i))
            for i in range(6)]
    _res, eng = staged_parity(jobs, [0, 0, 3, 5, 5, 11], decode_horizon=horizon)
    loads = eng.loads()
    assert loads["prefill_chained_launches"] >= 4
    assert not any(loads["prefill_sync_launches"].values())
    assert loads["wasted_decode_tokens"] == 0
    assert eng.scheduler.inflight is None and eng.scheduler._pending_group is None


FALLBACKS = {
    "penalties": dict(frequency_penalty=0.4, presence_penalty=0.2),
    "stop_strings": dict(stop=["never in this text"]),
    "token_filter": dict(regex=r"w[0-9 ]*"),
    "first_token_ends": dict(max_new_tokens=1),
    "recurrent_stop_ids": dict(ignore_eos=False),
}


@pytest.mark.parametrize("reason", list(FALLBACKS))
def test_where_the_host_needs_the_first_token_the_fetch_comes_first(reason):
    make, kw = make_engine, {}
    if reason == "recurrent_stop_ids":
        # a frame that ran and is discarded costs a recurrent model its
        # lanes' state, so a first token that a stop id may end waits
        from tests.test_recurrent_engine import make_engine as make_recurrent

        def make(overlap, **kw):
            return make_recurrent(overlap=overlap, **kw)
        assert make(True).scheduler.config.model.eos_token_ids
    late = SamplingParams(**{"temperature": 0.8, "max_new_tokens": 7,
                             "ignore_eos": True, **FALLBACKS[reason]})
    jobs = [("a", list(range(5, 27)), sampled(0.8, 16)),
            ("b", list(range(40, 58)), sampled(0.0, 14)),
            ("late", list(range(70, 95)), late),
            ("last", list(range(100, 120)), sampled(0.8, 6))]
    _res, eng = staged_parity(jobs, [0, 0, 3, 9], make=make, **kw)
    loads = eng.loads()
    assert loads["prefill_sync_launches"][reason] == 1
    assert sum(loads["prefill_sync_launches"].values()) == 1
    assert loads["prefill_chained_launches"] == 2  # "a" with "b", and "last"


def test_of_two_sampling_prefills_in_a_step_the_frame_chains_on_the_last():
    # groups of two: four arrivals in one step prefill as two groups, and the
    # first group's tokens are fetched before the second is dispatched; a
    # prompt over the step's budget ends in a solo prefill, which fetches
    jobs = [("a", list(range(5, 27)), sampled(0.8, 30))]
    jobs += [(f"g{i}", list(range(40 + 13 * i, 52 + 13 * i)), sampled(0.8, 8 + i))
             for i in range(4)]
    jobs += [("long", list(range(100, 200)), sampled(0.8, 6))]
    _res, eng = staged_parity(jobs, [0, 3, 3, 3, 3, 12], max_prefill_group=2)
    loads = eng.loads()
    assert loads["prefill_sync_launches"]["earlier_group"] == 1
    assert loads["prefill_sync_launches"]["solo"] == 1
    assert sum(loads["prefill_sync_launches"].values()) == 2
    assert loads["prefill_chained_launches"] == 2  # "a", and the second pair


def test_a_first_token_that_ends_its_request_throws_the_chained_frame_away():
    prompt = list(range(60, 90))
    first = run_streams(make_engine(False), [("p", prompt, greedy(2))])["p"][0][0]
    ends = SamplingParams(temperature=0.0, max_new_tokens=8, ignore_eos=True,
                          stop_token_ids=[first])
    jobs = [("a", list(range(5, 27)), sampled(0.8, 24)),
            ("b", list(range(40, 58)), sampled(0.8, 20)),
            ("e", prompt, ends),
            ("c", list(range(100, 120)), sampled(0.8, 9))]
    res, eng = staged_parity(jobs, [0, 0, 3, 6], decode_horizon=4)
    assert res["e"][:2] == ([first], "stop")
    assert eng.loads()["prefill_chained_launches"] == 3
    # the step itself: the frame held "e", so it goes before anything else
    # folds, its folds go back, and what the device ran of it is waste
    eng = make_engine(True, decode_horizon=4)
    out: dict = {}
    eng.submit(jobs[0][1], jobs[0][2], rid="a")
    for _ in range(3):
        eng.step()
    eng.submit(prompt, ends, rid="e", on_output=lambda o: out.setdefault("e", o))
    sched, wasted = eng.scheduler, eng.scheduler.num_wasted_decode_tokens
    eng.step()
    assert out["e"].finished and out["e"].finish_reason == "stop"
    assert sched.num_wasted_decode_tokens - wasted == 2 * 4  # lanes x K
    frame = sched.inflight
    assert [r.rid for _s, r, _e in frame.lanes] == ["a"] and not frame.lookahead
    assert eng.runner._step == frame.rng_mark + frame.folds
    assert eng.scheduler.flight.snapshot()["ring"][-1]["overlap"] == "sync"
    eng.stop()


def test_a_step_that_chains_is_steady_state_guard_clean():
    """The merge of the host's column with the prefill's tokens uploads
    explicitly and compiles with the engine's warm-up, so a step that
    prefills and launches behind the prefill passes the guard that the
    steady state passes."""
    from smg_tpu.analysis.runtime_guards import steady_state_guard

    eng = make_engine(True, decode_horizon=8)
    eng.warmup()
    merges = eng.runner._merge._cache_size()
    assert merges == 2 * 4  # decode buckets x padded group sizes
    for i in range(2):
        eng.submit([(7 * i + j) % 90 + 5 for j in range(16)], greedy(90), rid=f"r{i}")
    for _ in range(4):
        eng.step()
    eng.submit(list(range(100, 116)), greedy(40), rid="warm")  # the shapes below
    for _ in range(3):
        eng.step()
    chained = eng.loads()["prefill_chained_launches"]
    with steady_state_guard() as cc:
        eng.submit(list(range(120, 136)), greedy(40), rid="guarded")
        for _ in range(3):
            eng.step()
    assert cc.count == 0
    assert eng.loads()["prefill_chained_launches"] == chained + 1
    while eng.scheduler.has_work():
        eng.step()
    assert eng.runner._merge._cache_size() == merges  # traffic compiled none


def test_chained_is_an_outcome_of_the_step_record_and_the_metrics():
    from prometheus_client import generate_latest

    from smg_tpu.engine.flight_recorder import (
        OVERLAP_OUTCOMES, PREFILL_SYNC_REASONS,
    )

    eng = make_engine(True)
    jobs = [(f"j{i}", list(range(5 + 11 * i, 30 + 11 * i)), greedy(12)) for i in range(3)]
    staged_streams(eng, jobs, [0, 3, 6])
    ring = eng.dump_flight()["ring"]
    outcomes = {r["overlap"] for r in ring}
    assert "chained" in outcomes and outcomes <= set(OVERLAP_OUTCOMES)
    # a chained step prefilled, and launched a decode frame of its own
    assert all(r["prefill_tokens"] and r["horizon_reason"]
               for r in ring if r["overlap"] == "chained")
    text = generate_latest(eng.metrics.registry).decode()
    assert 'smg_engine_lookahead_launches_total{outcome="chained"} 3.0' in text
    loads = eng.loads()
    assert loads["prefill_chained_launches"] == 3
    assert tuple(loads["prefill_sync_launches"]) == PREFILL_SYNC_REASONS
    # the synchronous schedule passes no such pipeline and counts neither
    eng2 = make_engine(False)
    staged_streams(eng2, jobs, [0, 3, 6])
    loads = eng2.loads()
    assert loads["prefill_chained_launches"] == 0
    assert not any(loads["prefill_sync_launches"].values())
