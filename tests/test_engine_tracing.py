"""The engine's own measurement (ISSUE 26): ``smg.*`` spans on the
profiler's clock, the profiler's start and stop outside the engine lock, the
``submit_t`` stamp and the lock-wait counters, ``horizon_reason`` on the step
ring and its counter, and ``loads()["programs"]`` counting unarmed.  And the
step account that the same spans keep (ISSUE 39): where a step's host seconds
went and when the chip had nothing queued, on the step record, in
``loads()["step_phases"]`` and the two counters, and the slow steps kept
whole.  CPU, seconds each."""

import glob
import os
import threading
import time

import jax
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.flight_recorder import (
    HORIZON_REASONS,
    MOE_STEP_RECORD_KEYS,
    PHASE_RECORD_KEYS,
    SCHEMA_VERSION,
    SLOW_STEP_S,
    SLOW_STEPS_KEPT,
    STEP_RECORD_KEYS,
)
from smg_tpu.engine.spans import PHASES, SPAN_NAMES, STARVED_PHASES, StepAccount
from smg_tpu.models.config import (
    tiny_mimo_config,
    tiny_olmo_hybrid_config,
    tiny_pangu_moe_config,
    tiny_test_config,
)
from smg_tpu.protocols.sampling import SamplingParams


def make_engine(model=None, **sched_kw) -> Engine:
    sched = dict(
        max_batch_size=4, max_seq_len=128, max_prefill_tokens=32,
        prefill_token_buckets=(16, 32), decode_batch_buckets=(4,),
        decode_horizon=4,
    )
    sched.update(sched_kw)
    return Engine(EngineConfig(
        model=model or tiny_test_config(),
        cache=CacheConfig(page_size=16, num_pages=128, auto_size=False, dtype="float32"),
        scheduler=SchedulerConfig(**sched), dtype="float32", model_id="tiny-tracing",
    ))


def greedy(n: int) -> SamplingParams:
    return SamplingParams(temperature=0.0, max_new_tokens=n, ignore_eos=True)


def run_all(eng: Engine, prompts: list, n: int = 12, timeout: float = 120.0) -> None:
    """Submit every prompt at once and wait until all have ended (the engine's
    loop must be running)."""
    left = threading.Semaphore(0)
    for p in prompts:
        eng.submit(p, greedy(n), on_output=lambda o: o.finished and left.release())
    for _ in prompts:
        assert left.acquire(timeout=timeout)


@pytest.fixture
def fake_profiler(monkeypatch):
    """``jax.profiler`` start/stop replaced by stand-ins: ``stop_trace``
    records the thread it ran on and blocks until ``release`` is set, as a
    chip's seconds of trace writing do."""
    state = {"release": threading.Event(), "entered": threading.Event(), "thread": None}
    state["release"].set()

    def stop_trace():
        state["thread"] = threading.current_thread().name
        state["entered"].set()
        assert state["release"].wait(60)

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    return state


def host_spans(trace_dir: str) -> dict:
    """``{line: [(name, start_ns, end_ns, attributes)]}`` of the ``smg.*``
    host spans."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):  # one line a thread, all named alike
            for e in line.events:
                if e.name.startswith("smg."):
                    out.setdefault((plane.name, i), []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced_spans(tmp_path_factory) -> dict:
    """``host_spans`` of one real trace of a tiny engine under traffic."""
    tmp_path = tmp_path_factory.mktemp("trace")
    eng = make_engine(max_batch_size=2)
    eng.generate(prompt_ids=[5, 6, 7], sampling=greedy(6))  # compile outside the trace
    eng.start()
    try:
        eng.start_profile(str(tmp_path))
        # 48 tokens each: with two lanes the third prompt has to wait for a
        # finish however late this thread gets to submit it (at 12 tokens a
        # loaded machine let the first two end before the third arrived, and
        # no launch ran K=1)
        run_all(eng, [[3 + i, 4, 5, 6] for i in range(5)], n=48)
        eng.stop_profile()
    finally:
        eng.stop()
    return host_spans(str(tmp_path))


def test_trace_holds_every_span_nested_in_the_step(traced_spans):
    by_line = traced_spans
    seen = {n for evs in by_line.values() for n, *_ in evs}
    assert seen == set(SPAN_NAMES)
    inner = {"smg.step.consume", "smg.step.admit", "smg.step.launch", "smg.step.postprocess"}
    checked = 0
    for evs in by_line.values():
        steps = [(s, e) for n, s, e, _a in evs if n == "smg.step"]
        for n, s, e, _a in evs:
            if n in inner:
                assert any(s0 <= s and e <= e0 for s0, e0 in steps), n
                checked += 1
            elif n == "smg.step.callbacks":  # after the step released the lock
                assert not any(s0 < s < e0 for s0, e0 in steps)
    assert checked >= 8
    launches = [a for evs in by_line.values() for n, _s, _e, a in evs
                if n == "smg.step.launch"]
    launches = [a for a in launches if a]  # a launch that found no capacity names nothing
    assert all(set(a) == {"K", "lanes", "lookahead"} for a in launches), launches
    assert {a["K"] for a in launches} == {1, 4}  # K=1 while a request waits
    assert {a["lookahead"] for a in launches} == {0, 1}
    # the submitting thread is not the step thread
    line_of = lambda name: {k for k, evs in by_line.items() if any(n == name for n, *_ in evs)}
    assert not line_of("smg.submit") & line_of("smg.step")


def test_submit_and_steps_go_on_while_stop_profile_writes(fake_profiler):
    eng = make_engine()
    eng.generate(prompt_ids=[5, 6, 7], sampling=greedy(4))
    eng.start()
    try:
        eng.start_profile("/nonexistent")
        fake_profiler["release"].clear()
        stopper = threading.Thread(target=eng.stop_profile)
        stopper.start()
        assert fake_profiler["entered"].wait(10)  # stop_trace() is "writing"
        serial0 = eng.scheduler.flight.step_serial
        t = time.monotonic()
        done = threading.Event()
        eng.submit([9, 8, 7], greedy(8), on_output=lambda o: o.finished and done.set())
        assert time.monotonic() - t < 1.0  # the submit did not wait for the write
        assert done.wait(60)  # and steps went on to the request's end
        assert eng.scheduler.flight.step_serial > serial0
        assert stopper.is_alive()
        with pytest.raises(RuntimeError, match="already running"):
            eng.start_profile("/nonexistent")  # still being written
        assert eng.loads()["num_running"] == 0  # loads() is not held up either
        fake_profiler["release"].set()
        stopper.join(10)
        assert not stopper.is_alive() and not eng._profiling
        with pytest.raises(RuntimeError, match="not running"):
            eng.stop_profile()
        eng.start_profile("/nonexistent")  # and the profiler starts again
        eng.stop_profile()
    finally:
        fake_profiler["release"].set()
        eng.stop()


def test_num_steps_auto_stop_ends_the_trace_off_the_step_thread(fake_profiler):
    eng = make_engine()
    eng.start_profile("/nonexistent", num_steps=2)
    fake_profiler["entered"].clear()
    eng.generate(prompt_ids=[5, 6, 7], sampling=greedy(8))  # steps on this thread
    assert fake_profiler["entered"].wait(10)
    assert fake_profiler["thread"] == "smg-profiler-stop"
    assert fake_profiler["thread"] != threading.current_thread().name
    deadline = time.monotonic() + 10
    while eng._profiling and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not eng._profiling and eng._profile_steps_left is None
    eng.start_profile("/nonexistent", num_steps=1000)  # an explicit stop wins the race
    eng.stop_profile()
    assert eng._profile_steps_left is None


def test_timeline_stamps_run_in_order_and_the_lock_wait_is_counted():
    eng = make_engine()
    eng.start()
    try:
        run_all(eng, [[5, 6, 7, 8 + i] for i in range(6)])
    finally:
        eng.stop()
    finished = eng.dump_flight("test")["timelines"]["finished"]
    assert len(finished) == 6
    for tl in finished:
        assert tl["submit_t"] <= tl["queued_t"] <= tl["admitted_t"] <= tl["first_token_t"]
    loads = eng.loads()
    assert loads["submits"] == 6
    waited = sum(tl["queued_t"] - tl["submit_t"] for tl in finished)
    assert 0.0 <= loads["submit_lock_wait_seconds"] <= waited + 1e-3
    value = lambda c: sum(s.value for m in c.collect() for s in m.samples
                          if s.name.endswith("_total"))
    assert value(eng.metrics.submits) == 6
    assert value(eng.metrics.submit_lock_wait) == pytest.approx(
        loads["submit_lock_wait_seconds"])


def test_the_step_hands_the_lock_to_a_submission_that_waits_for_it():
    """What ``step()`` does before its prefill phase, with the engine lock
    held as ``step()`` holds it: a submission that waits for the lock is
    queued when ``_let_submitters_in`` returns, the lock is this thread's
    again, and the wait is counted as the submission's lock wait."""
    eng = make_engine()
    with eng._lock:
        eng._let_submitters_in()  # nobody waits: returns at once
        submitter = threading.Thread(target=eng.submit, args=([5, 6, 7], greedy(4)))
        submitter.start()
        deadline = time.monotonic() + 10
        while not eng._submitters_waiting() and time.monotonic() < deadline:
            time.sleep(0.001)
        assert eng._submitters_waiting() and not eng.scheduler.waiting
        held = time.monotonic()
        while not eng.scheduler.waiting and time.monotonic() < deadline:
            eng._let_submitters_in()  # bounded by SUBMIT_YIELD_S a call
        assert len(eng.scheduler.waiting) == 1 and not eng._submitters_waiting()
        assert time.monotonic() - held < 1.0
        # the lock is held again: another thread cannot take it
        took = []
        prober = threading.Thread(target=lambda: took.append(eng._lock.acquire(timeout=0.05)))
        prober.start()
        prober.join(10)
        assert took == [False]
    submitter.join(10)
    assert not submitter.is_alive()
    assert eng.loads()["submits"] == 1 and eng.loads()["submit_lock_wait_seconds"] > 0.0
    while eng.scheduler.has_work():
        eng.step()


@pytest.mark.parametrize("case,reason", [
    ("alone", "full"), ("queued", "pending_admission"), ("stop_string", "forced_lane"),
    ("no_megastep", "cap"),
])
def test_horizon_reason_on_the_ring_and_the_counter(case, reason):
    from smg_tpu.tokenizer import MockTokenizer

    eng = make_engine(max_batch_size=1, **({"decode_horizon": 1} if case == "no_megastep" else {}))
    sampling = greedy(12)
    if case == "stop_string":
        eng.tokenizer = MockTokenizer()
        sampling = SamplingParams(temperature=0.0, max_new_tokens=12, ignore_eos=True,
                                  stop=["never-in-the-output"])
    eng.submit([5, 6, 7], sampling)
    if case == "queued":  # one slot: the second request waits while the first decodes
        eng.submit([7, 6, 5], greedy(12))
    while eng.scheduler.has_work():
        eng.step()
    ring = eng.dump_flight("test")["ring"]
    assert all(set(r) == STEP_RECORD_KEYS for r in ring)
    reasons = [r["horizon_reason"] for r in ring]
    assert set(reasons) <= set(HORIZON_REASONS) | {""}
    assert reason in reasons
    # a step that consumed a decode frame and launched the next names a reason
    assert all(r["horizon_reason"] for r in ring[:-1] if r["horizon"] and r["running"])
    launches = eng.loads()["decode_launches"]
    assert set(launches) == set(HORIZON_REASONS)
    assert launches[reason] == reasons.count(reason) > 0
    assert sum(launches.values()) == sum(1 for r in reasons if r)
    if case == "queued":  # K=1 while the queue holds a request, the full K once it is empty
        assert launches["full"] > 0
        by_reason = {s.labels["horizon_reason"]: s.value
                     for m in eng.metrics.decode_launches.collect() for s in m.samples
                     if s.name.endswith("_total")}
        assert by_reason == {k: float(v) for k, v in launches.items() if v}


def test_adaptive_and_page_headroom_reasons():
    eng = make_engine(adaptive_horizon=True, decode_horizon=1, decode_horizon_max=8)
    eng.submit([5, 6, 7], greedy(3))  # fewer tokens left than the cap: K shrinks
    while eng.scheduler.has_work():
        eng.step()
    assert eng.loads()["decode_launches"]["adaptive"] > 0
    eng2 = make_engine(decode_horizon=8)
    eng2.submit(list(range(3, 23)), greedy(11))  # 20 tokens in two pages, 31 at the end
    eng2.step()  # prefill, and a first launch at K=8: 28 tokens, still two pages
    pool = eng2.scheduler.pool
    held = pool.alloc(pool.free_count)  # nothing free: 28 + 8 would need a third page
    while eng2.scheduler.has_work():
        eng2.step()
    pool.free(held)
    launches = eng2.loads()["decode_launches"]
    assert launches["page_headroom"] > 0 and launches["full"] > 0
    assert eng2.loads()["preemptions"] == 0


def test_programs_count_launches_and_compiles_unarmed():
    eng = make_engine()
    before = eng.loads()["programs"]
    assert before["armed"] is False and before["recompiles"] == 0
    eng.generate(prompt_ids=[5, 6, 7], sampling=greedy(10))
    mid = eng.loads()["programs"]
    assert mid["armed"] is False
    assert mid["compiles"] > before["compiles"]  # the programs' first compiles
    launched = [p for p in mid["programs"] if p["launches"]]
    assert launched and any(p["launches"] > 1 for p in launched)
    assert mid["recompiles"] == 0
    eng.generate(prompt_ids=[9, 6, 7], sampling=greedy(10))  # the same shapes again
    after = eng.loads()["programs"]
    assert after["compiles"] == mid["compiles"] and after["recompiles"] == 0
    assert sum(p["launches"] for p in after["programs"]) > sum(
        p["launches"] for p in mid["programs"])
    # a program that compiles on a launch after its first has been retraced
    key = ("test", "retraced")
    launch = eng.runner._programs.wrap(key, jax.jit(lambda x: x + 1))
    launch(jax.numpy.zeros(3))
    launch(jax.numpy.zeros(3))
    assert eng.loads()["programs"]["recompiles"] == 0
    launch(jax.numpy.zeros(4))
    assert eng.loads()["programs"]["recompiles"] == 1
    eng.runner._programs.forget([key])


# ---- the step account (ISSUE 39) ----

SUB_SPANS = {
    "smg.step.consume.fetch": "smg.step.consume", "smg.step.admit.pack": "smg.step.admit",
    "smg.step.admit.dispatch": "smg.step.admit", "smg.step.launch.dispatch": "smg.step.launch",
}


def test_the_four_sub_spans_are_named_and_nest_in_their_parents(traced_spans):
    assert set(SUB_SPANS) <= set(SPAN_NAMES) and len(SPAN_NAMES) == 12
    counted = dict.fromkeys(SUB_SPANS, 0)
    for evs in traced_spans.values():
        for n, s, e, _a in evs:
            if n in SUB_SPANS:
                parents = [(s0, e0) for n0, s0, e0, _ in evs if n0 == SUB_SPANS[n]]
                assert any(s0 <= s and e <= e0 for s0, e0 in parents), n
                counted[n] += 1
    assert all(counted.values()), counted
    # every launch dispatches once, and every consume fetches once
    names = [n for evs in traced_spans.values() for n, *_ in evs]
    assert names.count("smg.step.consume.fetch") == names.count("smg.step.consume")
    assert 0 < names.count("smg.step.launch.dispatch") <= names.count("smg.step.launch")


def drive(eng: Engine, prompts: list, n: int = 12, **sampling) -> list:
    """Submit and step on this thread until nothing is left; the ring."""
    for p in prompts:
        eng.submit(p, SamplingParams(temperature=0.0, max_new_tokens=n, ignore_eos=True,
                                     **sampling))
    while eng.scheduler.has_work():
        eng.step()
    return eng.dump_flight("test")["ring"]


@pytest.mark.parametrize("family", ["llama", "recurrent", "latent", "window"])
def test_schema_9_step_records_carry_the_account_in_every_runner(family):
    model = {"llama": tiny_test_config,
             "recurrent": tiny_olmo_hybrid_config,
             "latent": lambda: tiny_pangu_moe_config(held=(4, 8)),
             "window": lambda: tiny_mimo_config(held=(4, 8))}[family]()
    eng = make_engine(model=model)
    ring = drive(eng, [[5, 6, 7, 8 + i] for i in range(3)], n=8)
    dump = eng.dump_flight("test")
    assert dump["schema_version"] == SCHEMA_VERSION == 11
    assert PHASE_RECORD_KEYS <= STEP_RECORD_KEYS and len(PHASE_RECORD_KEYS) == 11
    moe = MOE_STEP_RECORD_KEYS if family in ("latent", "window") else frozenset()
    assert all(STEP_RECORD_KEYS <= set(r) <= STEP_RECORD_KEYS | moe for r in ring)
    assert all(isinstance(r[k], float) for r in ring for k in PHASE_RECORD_KEYS)
    assert sum(r["admit_dispatch_s"] for r in ring) > 0  # the runner's own spans fed it
    assert sum(r["dispatch_s"] - r["admit_dispatch_s"] for r in ring) > 0
    assert dump["slow_steps"] == eng.loads()["slow_steps"]["steps"]


def check_account(ring: list, sums: dict) -> None:
    """What holds of every step record's account, and of its sums."""
    eps = 1e-9
    for r in ring:
        assert r["consume_s"] + r["admit_s"] + r["launch_s"] <= r["step_s"] + eps
        assert 0.0 <= r["fetch_wait_s"] <= r["consume_s"] + eps
        assert r["admit_pack_s"] + r["admit_dispatch_s"] <= r["admit_s"] + eps
        assert r["admit_dispatch_s"] <= r["dispatch_s"] + eps
        assert r["dispatch_s"] - r["admit_dispatch_s"] <= r["launch_s"] + eps
        assert 0.0 <= r["starved_s"] <= r["step_s"] + r["gap_s"] + eps
        parts = r["starved_consume_s"] + r["starved_admit_s"] + r["starved_launch_s"]
        assert 0.0 <= parts <= r["starved_s"] + eps
        assert r["starved_consume_s"] <= r["consume_s"] and r["starved_admit_s"] <= r["admit_s"]
        assert r["starved_launch_s"] <= r["launch_s"] and r["gap_s"] >= 0.0
    sec, starved = sums["seconds"], sums["starved_seconds"]
    assert set(sec) == set(PHASES) | {"gap", "step"} and set(starved) == set(STARVED_PHASES)
    assert sums["steps"] == ring[-1]["serial"]
    for sub, parent in (("consume_fetch", "consume"), ("admit_pack", "admit"),
                        ("admit_dispatch", "admit"), ("launch_dispatch", "launch")):
        assert 0.0 < sec[sub] <= sec[parent]
    if len(ring) == sums["steps"]:  # the ring holds every step: the sums are the records'
        for key, rec in (("consume", "consume_s"), ("consume_fetch", "fetch_wait_s"),
                         ("admit", "admit_s"), ("launch", "launch_s"), ("gap", "gap_s"),
                         ("step", "step_s"), ("admit_pack", "admit_pack_s")):
            assert sec[key] == pytest.approx(sum(r[rec] for r in ring))
        assert sum(starved.values()) == pytest.approx(sum(r["starved_s"] for r in ring))


@pytest.mark.parametrize("schedule", ["overlap", "sync", "spec", "spec_sync"])
def test_the_account_adds_up_under_every_schedule(schedule):
    kw = {"overlap": {}, "sync": {"overlap_schedule": False},
          "spec": {"speculative": True, "spec_max_draft": 4},
          "spec_sync": {"speculative": True, "spec_max_draft": 4,
                        "overlap_schedule": False}}[schedule]
    eng = make_engine(**kw)
    # a prompt over the step's budget of 32 (KV-only chunks, then a solo last
    # one), short ones that group, and repeats for the n-gram drafts
    prompts = [[5, 6, 7] * 14, [5, 6, 7, 8], [9, 8, 7, 9, 8, 7, 9, 8], [3, 4, 3, 4, 3, 4]]
    ring = drive(eng, prompts, n=16)
    check_account(ring, eng.loads()["step_phases"])
    assert {r["kind"] for r in ring} >= {"decode"}
    if schedule.startswith("spec"):
        assert sum(r["spec_drafted"] for r in ring) > 0  # verify frames were among them
    text = {s.labels["phase"]: s.value for m in eng.metrics.step_phase_seconds.collect()
            for s in m.samples if s.name.endswith("_total")}
    assert text == pytest.approx(
        {k: v for k, v in eng.loads()["step_phases"]["seconds"].items() if k != "step"})
    starved = {s.labels["phase"]: s.value for m in eng.metrics.chip_starved_seconds.collect()
               for s in m.samples if s.name.endswith("_total")}
    assert starved == pytest.approx(eng.loads()["step_phases"]["starved_seconds"])


class Script:
    """A clock that reads what the test wrote next."""

    def __init__(self, *times):
        self.times = list(times)

    def __call__(self) -> float:
        return self.times.pop(0)


def test_a_lookahead_in_flight_starves_nothing_and_a_cold_launch_does():
    """The account alone, driven as the overlapped step drives it, on a
    scripted clock: the fake runner is the test."""
    clock = Script()
    a = StepAccount(clock=clock)

    def span(name, t0, t1, proves=None, inside=lambda: None):
        clock.times += [t0]
        with a.span(name, proves=proves):
            inside()
            clock.times += [t1]

    # step 1, from idle: a prefill and the frame behind it (launches 1 and 2)
    clock.times += [10.0]
    a.begin_step()
    span("smg.step.admit", 10.0, 10.5,
         inside=lambda: span("smg.step.admit.dispatch", 10.1, 10.4))
    span("smg.step.launch", 10.5, 10.8,
         inside=lambda: span("smg.step.launch.dispatch", 10.6, 10.7))
    clock.times += [10.85, 10.9]
    a.fetched(1)  # the prefill's first tokens: launch 2 is still out
    rec = a.end_step(has_work=True)
    assert rec["gap_s"] == 0.0  # nothing was waiting before
    # an idle chip starves from the step's start to the first dispatch's return
    assert rec["starved_s"] == pytest.approx(0.4) == pytest.approx(rec["starved_admit_s"])

    # step 2: a lookahead (launch 3) goes out before frame 2 is fetched
    clock.times += [11.0]
    a.begin_step()
    span("smg.step.launch", 11.0, 11.2,
         inside=lambda: span("smg.step.launch.dispatch", 11.05, 11.15))
    span("smg.step.consume", 11.2, 11.6,
         inside=lambda: span("smg.step.consume.fetch", 11.2, 11.5, proves=2))
    span("smg.step.admit", 11.6, 11.7)
    clock.times += [11.8]
    rec = a.end_step(has_work=True)
    assert rec["gap_s"] == pytest.approx(0.1)
    assert rec["starved_s"] == 0.0 and rec["fetch_wait_s"] == pytest.approx(0.3)

    # step 3: frame 3 met a finish, nothing is behind it; a cold launch follows
    clock.times += [12.0]
    a.begin_step()
    span("smg.step.consume", 12.0, 12.5,
         inside=lambda: span("smg.step.consume.fetch", 12.0, 12.3, proves=3))
    span("smg.step.admit", 12.6, 12.8)
    span("smg.step.launch", 13.0, 13.5,
         inside=lambda: span("smg.step.launch.dispatch", 13.2, 13.4))
    clock.times += [13.6]
    rec = a.end_step(has_work=True)
    assert rec["starved_consume_s"] == pytest.approx(0.2)  # 12.3 to 12.5
    assert rec["starved_admit_s"] == pytest.approx(0.2)
    assert rec["starved_launch_s"] == pytest.approx(0.4)  # 13.0 to the dispatch's return
    assert rec["starved_s"] == pytest.approx(13.4 - 12.3)  # the fetch's return to the dispatch's
    assert rec["launch_s"] == pytest.approx(0.5) and rec["dispatch_s"] == pytest.approx(0.2)

    # step 4 ends with nothing left: the next step has no gap, and the empty
    # chip between them counts for nothing
    clock.times += [13.7]
    a.begin_step()
    span("smg.step.consume", 13.7, 13.9,
         inside=lambda: span("smg.step.consume.fetch", 13.7, 13.8, proves=4))
    clock.times += [14.0]
    rec = a.end_step(has_work=False)
    assert rec["starved_s"] == pytest.approx(0.2)
    clock.times += [20.0, 20.5]
    a.begin_step()
    rec = a.end_step(has_work=False)
    assert rec["gap_s"] == 0.0 and rec["starved_s"] == pytest.approx(0.5)
    assert a.sums()["steps"] == 5 and not clock.times


def ticking(eng: Engine, tick: float) -> None:
    """Every reading of the account's clock is ``tick`` seconds after the
    last: what a step records is then a count of the account's own readings."""
    state = {"t": 0.0}

    def clock() -> float:
        state["t"] += tick
        return state["t"]

    eng.scheduler.account.clock = clock


def test_on_the_engine_a_kept_lookahead_starves_nothing_and_a_finish_leaves_the_chip_empty():
    eng = make_engine(max_batch_size=2)
    ticking(eng, 1.0)
    # two lanes; the first ends by its length 8 tokens before the second
    eng.submit([5, 6, 7, 8], greedy(9))
    eng.submit([9, 8, 7, 6], greedy(17))
    while eng.scheduler.has_work():
        eng.step()
    ring = eng.dump_flight("test")["ring"]
    kept = [r for r in ring if r["overlap"] == "kept"]
    assert kept and all(r["starved_s"] == 0.0 for r in kept)
    # no lookahead goes out where a lane is certain to end inside the frame:
    # the fetch then leaves nothing behind it, and the cold launch that
    # follows closes the empty interval inside ``smg.step.launch``
    cold = [r for r in ring if r["overlap"] == "sync" and r["horizon"] and r["horizon_reason"]]
    assert cold
    for r in cold:
        # readings between the fetch's return and the dispatch's: the end of
        # consume, admit's two, launch's start, the dispatch's two
        assert r["starved_s"] == 6.0
        assert (r["starved_consume_s"], r["starved_admit_s"], r["starved_launch_s"]) == (1.0, 1.0, 2.0)
    check_account(ring, eng.loads()["step_phases"])


def test_gap_is_zero_after_an_idle_engine_and_counted_between_busy_steps():
    eng = make_engine()
    first = drive(eng, [[5, 6, 7, 8]], n=8)
    assert first[0]["gap_s"] == 0.0 and all(r["gap_s"] > 0.0 for r in first[1:])
    time.sleep(0.05)  # idle: no step left work behind
    ring = drive(eng, [[9, 8, 7, 6]], n=8)
    second = ring[len(first):]
    assert second[0]["gap_s"] == 0.0 and all(r["gap_s"] > 0.0 for r in second[1:])
    # the idle stretch is nobody's: neither a gap nor starved seconds
    assert second[0]["starved_s"] <= second[0]["step_s"] + 1e-9
    assert eng.loads()["step_phases"]["seconds"]["gap"] == pytest.approx(
        sum(r["gap_s"] for r in ring))


def test_slow_steps_are_kept_whole_and_the_ninth_pushes_out_the_first():
    eng = make_engine()
    drive(eng, [[5, 6, 7, 8]], n=6)
    before = eng.loads()["slow_steps"]  # a compile may have passed a second
    assert all(s["step_s"] + s["gap_s"] > SLOW_STEP_S for s in before["steps"])
    assert before["threshold_s"] == SLOW_STEP_S == 1.0 and SLOW_STEPS_KEPT == 8
    last = eng.scheduler.flight.step_serial
    ticking(eng, 0.6)  # two readings of the clock are over SLOW_STEP_S: every step is slow
    made = [r for r in drive(eng, [[9, 8, 7, 6]], n=40) if r["serial"] > last]
    assert len(made) >= 9 and all(r["step_s"] > SLOW_STEP_S for r in made)
    slow = eng.loads()["slow_steps"]
    assert slow["count"] == before["count"] + len(made)
    assert slow["steps"] == made[-8:]  # whole records, the newest eight
    assert made[-9] not in slow["steps"]
    assert all(set(s) == STEP_RECORD_KEYS for s in slow["steps"])
