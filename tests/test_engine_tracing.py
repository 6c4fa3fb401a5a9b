"""The engine's own measurement (ISSUE 26): ``smg.*`` spans on the
profiler's clock, the profiler's start and stop outside the engine lock, the
``submit_t`` stamp and the lock-wait counters, ``horizon_reason`` on the step
ring and its counter, and ``loads()["programs"]`` counting unarmed.  CPU,
seconds each."""

import glob
import os
import threading
import time

import jax
import pytest

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.flight_recorder import HORIZON_REASONS, STEP_RECORD_KEYS
from smg_tpu.engine.spans import SPAN_NAMES
from smg_tpu.models.config import tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams


def make_engine(**sched_kw) -> Engine:
    sched = dict(
        max_batch_size=4, max_seq_len=128, max_prefill_tokens=32,
        prefill_token_buckets=(16, 32), decode_batch_buckets=(4,),
        decode_horizon=4,
    )
    sched.update(sched_kw)
    return Engine(EngineConfig(
        model=tiny_test_config(),
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


def test_trace_holds_every_span_nested_in_the_step(tmp_path):
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
    by_line = host_spans(str(tmp_path))
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
