"""Reliability e2e: engine failure isolation (poison-step quarantine,
per-request deadlines, admission backpressure, graceful drain, step
watchdog) plus the original worker-death and drain-before-remove gateway
scenarios (reference: tier-2 reliability tests, model_gateway/tests/ + the
--drain-settle-secs removal semantics, main.rs:550-556).

Every failure scenario is driven through the shipped fault points in
``smg_tpu/faults.py`` — no monkeypatching of internals — so the code paths
exercised are exactly the production ones."""

import asyncio
import json
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.request import QueueFullError
from smg_tpu.faults import FAULTS, InjectedFault
from smg_tpu.gateway.server import AppContext, build_app
from smg_tpu.gateway.worker_client import InProcWorkerClient, WorkerQueueFullError
from smg_tpu.gateway.workers import CircuitBreaker, Worker
from smg_tpu.models.config import tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No armed fault may outlive its test."""
    yield
    FAULTS.clear()


def make_engine(watchdog_secs: float = 0.0, **sched_kw) -> Engine:
    sched = dict(
        max_batch_size=4, max_seq_len=128, max_prefill_tokens=64,
        prefill_token_buckets=(16, 32, 64), decode_batch_buckets=(4,),
    )
    sched.update(sched_kw)
    return Engine(
        EngineConfig(
            model=tiny_test_config(),
            cache=CacheConfig(page_size=16, num_pages=128, auto_size=False, dtype="float32"),
            scheduler=SchedulerConfig(**sched),
            dtype="float32",
            model_id="tiny-test",
            step_watchdog_secs=watchdog_secs,
        )
    )


def _collector(outs: dict, rid: str):
    def cb(out):
        outs.setdefault(rid, []).append(out)
    return cb


def _drive(eng: Engine, outs: dict, rids: list, max_steps: int = 300) -> None:
    """Step the engine inline until every rid has a terminal output."""
    for _ in range(max_steps):
        eng.step()
        if all(
            rid in outs and any(o.finished for o in outs[rid]) for rid in rids
        ):
            return
    raise AssertionError(f"requests never finished: {outs}")


def _tokens(outs: dict, rid: str) -> list:
    return [t for c in outs[rid] for t in c.new_token_ids]


def assert_engine_clean(eng: Engine) -> None:
    """Zero leaked pages, radix locks, or decode lanes after all finishes.

    Asserts BOTH through the public quiescence audit (``Engine.audit()`` —
    what operators and the loadgen harness read via ``loads()``) and by
    independent internal walk, so a bug in the audit itself cannot hide a
    leak from this suite."""
    audit = eng.audit()
    assert audit["quiescent"] and audit["clean"], audit
    assert audit["leaked_pages"] == 0, audit
    assert audit["radix_locked_nodes"] == 0 == audit["radix_lock_refcounts"], audit
    assert audit["pending_callbacks"] == 0, audit
    sch = eng.scheduler
    assert sch.requests == {}, f"leaked requests: {list(sch.requests)}"
    assert all(s is None for s in sch.slots), "leaked decode lane"
    assert sch.inflight is None, "leaked in-flight frame"
    # page 0 is the reserved garbage page: free + radix-cached must cover
    # every allocatable page
    cached = sch.radix.num_cached_pages if sch.radix else 0
    assert sch.pool.free_count + cached == sch.runner.spec.num_pages - 1, (
        sch.pool.free_count, cached
    )
    # no radix node may stay pinned once every request released
    if sch.radix is not None:
        stack = [sch.radix.root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                assert child.refcount == 0, "leaked radix lock"
                stack.append(child)


class DyingClient(InProcWorkerClient):
    """Streams a couple of chunks then dies (simulated worker crash)."""

    def __init__(self, engine, die_after_chunks: int = 2):
        super().__init__(engine)
        self.die_after = die_after_chunks
        self.dead = False

    async def generate(self, req):
        n = 0
        async for chunk in super().generate(req):
            yield chunk
            n += 1
            if n >= self.die_after:
                self.dead = True
                raise ConnectionError("worker process died mid-stream")

    async def health(self) -> bool:
        return not self.dead and await super().health()


class SlowClient(InProcWorkerClient):
    """Adds per-chunk latency so requests stay in flight during a drain."""

    def __init__(self, engine, delay: float = 0.08):
        super().__init__(engine)
        self.delay = delay

    async def generate(self, req):
        async for chunk in super().generate(req):
            await asyncio.sleep(self.delay)
            yield chunk


def _gateway(workers):
    loop = asyncio.new_event_loop()
    ctx = AppContext(policy="round_robin")
    ctx.tokenizers.register("tiny-test", MockTokenizer(), default=True)

    async def _setup():
        for w in workers:
            ctx.registry.add(w)
        tc = TestClient(TestServer(build_app(ctx)))
        await tc.start_server()
        return tc

    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()

    def run(coro, timeout=180):  # generous: first-compiles under CI load
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=timeout)

    tc = run(_setup())
    return loop, ctx, tc, run


def test_worker_dies_mid_stream_clean_error_and_heal():
    """Worker dies mid-SSE: the client sees streamed tokens, then ONE clean
    terminal error frame (no hang, no truncated garbage); the breaker opens
    and later requests route around the dead worker."""
    eng_a, eng_b = make_engine(), make_engine()
    dying = DyingClient(eng_a, die_after_chunks=1)
    w0 = Worker(worker_id="w0", client=dying, model_id="tiny-test")
    w0.circuit = CircuitBreaker(failure_threshold=1, cooldown_secs=300.0)
    w1 = Worker(worker_id="w1", client=InProcWorkerClient(eng_b), model_id="tiny-test")
    loop, ctx, tc, run = _gateway([w0, w1])
    try:
        async def stream_until_dead():
            # round_robin may pick w1 first; loop until the dying worker is hit
            for _ in range(4):
                r = await tc.post("/v1/chat/completions", json={
                    "model": "tiny-test",
                    "messages": [{"role": "user", "content": "w5 w6"}],
                    "max_tokens": 8, "temperature": 0, "ignore_eos": True,
                    "stream": True,
                })
                text = await r.text()
                if dying.dead:
                    return text
            return None

        raw = run(stream_until_dead())
        assert raw is not None, "dying worker was never selected"
        frames = [l[6:] for l in raw.splitlines() if l.startswith("data: ")]
        parsed = [json.loads(f) for f in frames if f != "[DONE]"]
        # streamed at least one real token chunk, then a terminal error frame
        assert any("choices" in p for p in parsed), frames
        assert "error" in parsed[-1], frames[-3:]
        assert w0.circuit.state.value == "open"
        assert w0.total_failures >= 1

        async def after():
            results = []
            for _ in range(4):
                r = await tc.post("/v1/chat/completions", json={
                    "model": "tiny-test",
                    "messages": [{"role": "user", "content": "w9"}],
                    "max_tokens": 3, "temperature": 0, "ignore_eos": True,
                })
                results.append(r.status)
            return results

        # registry heals: every subsequent request routes around w0
        assert run(after()) == [200, 200, 200, 200]
        assert w1.total_requests >= 4
    finally:
        run(tc.close())
        loop.call_soon_threadsafe(loop.stop)
        eng_a.stop(); eng_b.stop()


def test_drain_before_remove():
    """DELETE /workers/{id}?drain=N lets in-flight streams finish: the
    draining worker takes no new requests, the live stream completes
    cleanly, and removal reports drained=true."""
    eng_a, eng_b = make_engine(), make_engine()
    slow = SlowClient(eng_a, delay=0.06)
    w0 = Worker(worker_id="w0", client=slow, model_id="tiny-test")
    w1 = Worker(worker_id="w1", client=InProcWorkerClient(eng_b), model_id="tiny-test")
    loop, ctx, tc, run = _gateway([w0, w1])
    try:
        async def go():
            # Prewarm both engines first (pin selection via draining) so the
            # drain window below measures scheduling, not first-compile time —
            # under full-suite CPU load compiles can take minutes and the
            # 600×0.05s engagement poll would time out (r3 flake).
            for warm, other in ((w0, w1), (w1, w0)):
                other.draining = True
                r = await tc.post("/v1/chat/completions", json={
                    "model": "tiny-test",
                    "messages": [{"role": "user", "content": "w1 w2"}],
                    "max_tokens": 2, "temperature": 0, "ignore_eos": True,
                })
                assert r.status == 200
                other.draining = False
            w0.total_requests = w1.total_requests = 0

            # occupy w0 with a slow stream — pin selection by draining w1
            # for the setup call (deterministic; the old round_robin hunt
            # raced with selection state left by earlier tests)
            w1.draining = True
            stream_task = asyncio.ensure_future(tc.post("/v1/chat/completions", json={
                "model": "tiny-test",
                "messages": [{"role": "user", "content": "w5 w6"}],
                "max_tokens": 10, "temperature": 0, "ignore_eos": True,
                "stream": True,
            }))
            for _ in range(600):  # first-compile under CI load can be slow
                if w0.load > 0:
                    break
                await asyncio.sleep(0.05)
            w1.draining = False
            assert w0.load > 0, "slow worker never engaged"

            # remove with drain while the stream is live
            del_task = asyncio.ensure_future(
                tc.delete("/workers/w0", params={"drain": "10"})
            )
            await asyncio.sleep(0.1)
            assert w0.draining
            # new requests during the drain land on w1 only
            r = await tc.post("/v1/chat/completions", json={
                "model": "tiny-test",
                "messages": [{"role": "user", "content": "w7"}],
                "max_tokens": 2, "temperature": 0, "ignore_eos": True,
            })
            assert r.status == 200
            assert w1.total_requests >= 1

            resp = await stream_task
            raw = await resp.text()
            del_resp = await del_task
            del_body = await del_resp.json()
            return raw, del_body

        raw, del_body = run(go(), timeout=420)
        frames = [l for l in raw.splitlines() if l.startswith("data: ")]
        assert frames[-1] == "data: [DONE]"  # the in-flight stream finished
        assert len([f for f in frames if "choices" in f]) >= 10
        assert del_body["removed"] == "w0"
        assert del_body["drained"] is True
        assert ctx.registry.get("w0") is None
    finally:
        run(tc.close())
        loop.call_soon_threadsafe(loop.stop)
        eng_a.stop(); eng_b.stop()

# ---- poison-step quarantine (fault-driven, engine-level) ----


def test_poison_prefill_quarantine_survivors_byte_identical():
    """ISSUE acceptance: 3 concurrent streams + 1 deterministically-failing
    request.  The poisoned request gets exactly ONE terminal error output,
    the other 3 complete with token streams byte-identical to the same run
    without the fault, and the engine ends with zero leaked pages, radix
    locks, or decode lanes."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True)
    prompts = {f"ok-{i}": [5 + i, 6 + i, 7 + i] for i in range(3)}

    def run(poison: bool) -> tuple[dict, Engine]:
        eng = make_engine()
        outs: dict = {}
        rids = list(prompts)
        for rid, ids in prompts.items():
            eng.submit(ids, sp, rid=rid, on_output=_collector(outs, rid))
        if poison:
            FAULTS.arm("engine.prefill", match="poison")
            eng.submit([9, 10, 11], sp, rid="poison",
                       on_output=_collector(outs, "poison"))
            rids.append("poison")
        _drive(eng, outs, rids)
        FAULTS.clear()
        return outs, eng

    poisoned, eng_p = run(poison=True)
    clean, eng_c = run(poison=False)

    # exactly one terminal error chunk for the culprit, nothing streamed
    assert len(poisoned["poison"]) == 1
    assert poisoned["poison"][0].finished
    assert poisoned["poison"][0].finish_reason == "error"
    assert poisoned["poison"][0].new_token_ids == []
    # survivors: full streams, byte-identical to the fault-free run
    for rid in prompts:
        assert _tokens(poisoned, rid) == _tokens(clean, rid)
        assert len(_tokens(poisoned, rid)) == 6
    assert_engine_clean(eng_p)
    assert_engine_clean(eng_c)
    assert eng_p.scheduler.num_quarantined == 1
    assert eng_p.healthy  # quarantine contained the failure


def test_poison_mid_prefill_chunk_quarantined():
    """A resumable (non-final) chunk that raises quarantines only its own
    request; the budget keeps metering other admissions normally."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=4, ignore_eos=True)
    # prompt longer than the per-step budget -> chunked, resumable prefill
    eng = make_engine(max_prefill_tokens=16)
    outs: dict = {}
    long_prompt = [(3 * i) % 200 + 5 for i in range(40)]
    FAULTS.arm("engine.prefill", mode="after", n=1, match="longpoison")
    eng.submit(long_prompt, sp, rid="longpoison",
               on_output=_collector(outs, "longpoison"))
    eng.submit([5, 6, 7], sp, rid="short", on_output=_collector(outs, "short"))
    _drive(eng, outs, ["longpoison", "short"])
    assert outs["longpoison"][-1].finish_reason == "error"
    assert len(_tokens(outs, "short")) == 4
    assert_engine_clean(eng)


def test_decode_step_blame_newest_lane():
    """A decode-batch failure blames the most-recently-admitted lane: it is
    quarantined, surviving lanes retry within the same step and stream
    byte-identically to a fault-free run."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True)

    def run(fault: bool) -> tuple[dict, Engine]:
        eng = make_engine()
        outs: dict = {}
        for i in range(3):
            eng.submit([5 + i, 6 + i, 7 + i], sp, rid=f"r{i}",
                       on_output=_collector(outs, f"r{i}"))
        eng.step()  # admit + prefill all three
        assert all(
            eng.scheduler.requests[f"r{i}"].status.value == "running"
            for i in range(3)
        )
        if fault:
            FAULTS.arm("engine.decode_step", mode="once")
        _drive(eng, outs, [f"r{i}" for i in range(3)])
        FAULTS.clear()
        return outs, eng

    faulted, eng_f = run(fault=True)
    clean, _eng_c = run(fault=False)
    # r2 has the highest admission serial -> blamed
    assert faulted["r2"][-1].finish_reason == "error"
    for rid in ("r0", "r1"):
        assert _tokens(faulted, rid) == _tokens(clean, rid)
        assert len(_tokens(faulted, rid)) == 6
    assert_engine_clean(eng_f)
    assert eng_f.scheduler.num_quarantined == 1
    assert eng_f.scheduler.consec_step_failures == 0  # clean steps resumed


def _late_joiner(overlap: bool, break_it) -> tuple[dict, Engine]:
    """Two sampled lanes, then a third request beside them, admitted in a
    step that ``break_it(engine)`` has prepared a fault for."""
    eng = make_engine(overlap_schedule=overlap, decode_horizon=4)
    outs: dict = {}
    sp = SamplingParams(temperature=0.8, max_new_tokens=14, ignore_eos=True)
    for i in range(2):
        eng.submit([5 + i, 6 + i, 7 + i], sp, rid=f"r{i}",
                   on_output=_collector(outs, f"r{i}"))
    for _ in range(3):
        eng.step()
    break_it(eng)
    eng.submit([40, 41, 42, 43], sp, rid="late", on_output=_collector(outs, "late"))
    _drive(eng, outs, ["r0", "r1", "late"])
    FAULTS.clear()
    return outs, eng


def test_a_grouped_prefill_that_fails_at_the_fetch_gives_the_chained_frame_back():
    """A grouped prefill that fails on the device surfaces where its first
    tokens are fetched, which under the overlapped schedule is after the
    step's decode frame went out behind it: the frame is discarded and its
    folds rewound before the members retry solo, so the retry refolds the
    keys it always did and every stream is the synchronous schedule's under
    the same failure."""
    def fetch_fails_once(eng):
        real, calls = eng.runner.fetch_first_tokens, []

        def fetch(parts, g_real):
            calls.append(g_real)
            if len(calls) == 1:
                raise RuntimeError("device lost the launch")
            return real(parts, g_real)
        eng.runner.fetch_first_tokens = fetch

    got, eng = _late_joiner(True, fetch_fails_once)
    want, eng_s = _late_joiner(False, fetch_fails_once)
    for rid in ("r0", "r1", "late"):
        assert _tokens(got, rid) == _tokens(want, rid)
        assert len(_tokens(got, rid)) == 14 and got[rid][-1].finish_reason == "length"
    for e in (eng, eng_s):
        assert e.scheduler.num_step_failures == 1 and e.scheduler.num_quarantined == 0
        assert_engine_clean(e)
    loads = eng.loads()
    assert loads["prefill_chained_launches"] == 2  # the first pair, and "late"
    assert loads["wasted_decode_tokens"] == 3 * 4  # the frame behind "late": lanes x K
    assert loads["prefill_sync_launches"]["solo"] == 0  # the retry is not a launch of the phase


def test_a_launch_behind_a_prefill_that_raises_still_accepts_the_first_tokens():
    """The decode launch dispatched behind a grouped prefill fails before it
    is out: the first tokens are accepted all the same, so the recovery
    blames the newest lane and retries the others exactly as the
    synchronous schedule does."""
    def launch_with_late_fails(_eng):
        FAULTS.arm("engine.decode_step", mode="once", match="late")

    got, eng = _late_joiner(True, launch_with_late_fails)
    want, eng_s = _late_joiner(False, launch_with_late_fails)
    assert got["late"][-1].finish_reason == "error" == want["late"][-1].finish_reason
    for rid in ("r0", "r1", "late"):
        assert _tokens(got, rid) == _tokens(want, rid)
    assert len(_tokens(got, "late")) == 1 and len(_tokens(got, "r0")) == 14
    for e in (eng, eng_s):
        assert e.scheduler.num_quarantined == 1
        assert_engine_clean(e)


def test_decode_poison_batch_condemned_and_unhealthy():
    """A decode fault that survives the single-lane eviction retry condemns
    the whole batch (every lane gets a terminal error), and N consecutive
    failed steps flip the engine unhealthy for loads()/health()."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, ignore_eos=True)
    eng = make_engine()
    outs: dict = {}
    FAULTS.arm("engine.decode_step")  # always
    for i in range(2):
        eng.submit([5 + i, 6 + i, 7 + i], sp, rid=f"r{i}",
                   on_output=_collector(outs, f"r{i}"))
    _drive(eng, outs, ["r0", "r1"], max_steps=10)
    assert all(outs[r][-1].finish_reason == "error" for r in ("r0", "r1"))
    assert_engine_clean(eng)
    # rack up consecutive decode failures past the health threshold
    assert eng.healthy
    for i in range(eng.config.max_consecutive_step_failures + 1):
        eng.submit([5, 6, 7 + i], sp, rid=f"y{i}",
                   on_output=_collector(outs, f"y{i}"))
        eng.step()
    assert not eng.healthy
    assert eng.loads()["healthy"] is False
    FAULTS.clear()
    # recovery: clean steps reset the consecutive counter
    eng.submit([5, 6, 99], sp, rid="fresh", on_output=_collector(outs, "fresh"))
    _drive(eng, outs, ["fresh"])
    assert eng.healthy


# ---- per-request deadlines ----


def test_deadline_expiry_waiting_vs_running():
    """WAITING requests past deadline expire in queue; RUNNING lanes are
    aborted mid-generation — both with terminal finish_reason='timeout'."""
    eng = make_engine(max_batch_size=1)
    outs: dict = {}
    eng.submit([5, 6, 7],
               SamplingParams(temperature=0.0, max_new_tokens=64, ignore_eos=True),
               rid="run", on_output=_collector(outs, "run"), timeout_secs=0.25)
    eng.step()  # admit + prefill the running lane
    assert eng.scheduler.requests["run"].status.value == "running"
    # slot-blocked: stays WAITING until its deadline passes
    eng.submit([8, 9, 10], SamplingParams(max_new_tokens=4), rid="wait",
               on_output=_collector(outs, "wait"), timeout_secs=0.05)
    time.sleep(0.3)
    _drive(eng, outs, ["run", "wait"], max_steps=5)
    assert outs["wait"][-1].finish_reason == "timeout"
    assert outs["wait"][-1].new_token_ids == []
    assert outs["run"][-1].finish_reason == "timeout"
    assert_engine_clean(eng)
    sch = eng.scheduler
    assert sch.num_deadline_waiting == 1
    assert sch.num_deadline_running == 1
    loads = eng.loads()
    assert loads["deadline_expirations_waiting"] == 1
    assert loads["deadline_expirations_running"] == 1


def test_generate_timeout_is_a_finish_not_a_raise():
    """Satellite: Engine.generate's wait is parameterized and rides the
    deadline plumbing — sync callers get a 'timeout' finish instead of a
    raised TimeoutError with an orphaned abort."""
    eng = make_engine()
    res = eng.generate(
        prompt_ids=[5, 6, 7],
        sampling=SamplingParams(temperature=0.0, max_new_tokens=10_000,
                                ignore_eos=True),
        timeout_secs=0.2,
    )
    assert res.finish_reason == "timeout"
    assert_engine_clean(eng)


# ---- admission backpressure ----


def test_bounded_queue_rejects_at_submit():
    eng = make_engine(max_queued_requests=1)
    sp = SamplingParams(max_new_tokens=4)
    eng.submit([1, 2, 3], sp, rid="a")  # fills the (unstarted) queue
    with pytest.raises(QueueFullError):
        eng.submit([1, 2, 4], sp, rid="b")
    assert eng.scheduler.num_queue_rejections == 1
    assert eng.loads()["queue_rejections"] == 1


def test_bounded_queue_token_cap():
    eng = make_engine(max_queued_tokens=8)
    sp = SamplingParams(max_new_tokens=4)
    eng.submit([1, 2, 3, 4, 5], sp, rid="a")
    with pytest.raises(QueueFullError):
        eng.submit([1, 2, 3, 4, 5], sp, rid="b")


# ---- step watchdog ----


def test_watchdog_stall_detection_and_recovery():
    """A wedged device fetch (injected hang) flips the engine unhealthy via
    the watchdog thread; progress resuming clears the stall and the request
    still completes."""
    eng = make_engine(watchdog_secs=0.3)
    eng.start()
    try:
        # warm the compile caches first so the injected hang dominates
        eng.generate(prompt_ids=[5, 6, 7],
                     sampling=SamplingParams(temperature=0.0, max_new_tokens=4,
                                             ignore_eos=True))
        stalls_before = eng.num_watchdog_stalls
        FAULTS.arm("engine.device_fetch", mode="once", action="hang", delay=2.0)
        outs: dict = {}
        eng.submit([8, 9, 10],
                   SamplingParams(temperature=0.0, max_new_tokens=4,
                                  ignore_eos=True),
                   rid="w", on_output=_collector(outs, "w"))
        saw_unhealthy = False
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if not eng.healthy:
                saw_unhealthy = True
            if outs.get("w") and outs["w"][-1].finished:
                break
            time.sleep(0.02)
        assert saw_unhealthy, "watchdog never flagged the stall"
        assert eng.num_watchdog_stalls > stalls_before
        assert outs["w"][-1].finished
        deadline = time.monotonic() + 10
        while not eng.healthy and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.healthy, "stall never cleared after progress resumed"
    finally:
        eng.stop()


# ---- graceful drain ----


def test_drain_on_stop():
    """engine.stop(drain=True): admission stops, queued requests get a
    terminal abort (clients see an end, never a hang), running lanes finish
    their streams completely."""
    eng = make_engine(max_batch_size=1)
    eng.start()
    outs: dict = {}
    eng.submit([5, 6, 7],
               SamplingParams(temperature=0.0, max_new_tokens=30, ignore_eos=True),
               rid="run", on_output=_collector(outs, "run"))
    eng.submit([8, 9, 10], SamplingParams(max_new_tokens=4), rid="wait",
               on_output=_collector(outs, "wait"))
    deadline = time.monotonic() + 120
    while "run" not in outs and time.monotonic() < deadline:
        time.sleep(0.01)  # the running lane engaged
    eng.stop(drain=True, timeout=120)
    assert outs["wait"][-1].finished
    assert outs["wait"][-1].finish_reason == "abort"
    assert outs["run"][-1].finished
    assert outs["run"][-1].finish_reason in ("length", "stop")
    assert len(_tokens(outs, "run")) == 30
    assert_engine_clean(eng)


# ---- queue-full through the gateway (retry-other-worker / 429) ----


def _frozen_full_worker(worker_id: str) -> tuple:
    """A worker whose engine queue is full and whose loop is stopped, so
    every generate hits admission backpressure deterministically."""
    eng = make_engine(max_queued_requests=1)
    client = InProcWorkerClient(eng)
    eng.stop()  # freeze the loop: the queued filler never drains
    eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4), rid="filler")
    return eng, Worker(worker_id=worker_id, client=client, model_id="tiny-test")


def test_queue_full_routes_to_other_worker_then_429():
    """Engine backpressure surfaces as retry-another-worker: requests
    succeed on the healthy worker, the full worker's breaker stays closed
    (load is not fault) — and with no capacity anywhere the front door
    answers 429."""
    eng_a, w0 = _frozen_full_worker("w0")
    eng_b = make_engine()
    w1 = Worker(worker_id="w1", client=InProcWorkerClient(eng_b),
                model_id="tiny-test")
    loop, ctx, tc, run = _gateway([w0, w1])
    try:
        async def go():
            statuses = []
            for _ in range(4):
                r = await tc.post("/v1/chat/completions", json={
                    "model": "tiny-test",
                    "messages": [{"role": "user", "content": "w5 w6"}],
                    "max_tokens": 2, "temperature": 0, "ignore_eos": True,
                })
                statuses.append(r.status)
            return statuses

        assert run(go()) == [200, 200, 200, 200]
        # backpressure is not failure: the full worker's breaker never moved
        assert w0.circuit.state.value == "closed"
        assert w0.total_failures == 0
        assert eng_a.scheduler.num_queue_rejections >= 1

        # all capacity gone: only the full worker remains -> 429 retry-later
        ctx.registry.remove("w1")

        async def go429():
            r = await tc.post("/v1/chat/completions", json={
                "model": "tiny-test",
                "messages": [{"role": "user", "content": "w9"}],
                "max_tokens": 2, "temperature": 0, "ignore_eos": True,
            })
            return r.status, await r.json()

        status, body = run(go429())
        assert status == 429, body
        assert "capacity" in body["error"]["message"]
    finally:
        run(tc.close())
        loop.call_soon_threadsafe(loop.stop)
        eng_a.stop()
        eng_b.stop()


# ---- satellite: circuit breaker half-open probe gating ----


def test_half_open_admits_single_probe():
    """HALF_OPEN admits ONE in-flight probe, not the whole backed-up queue
    (half-open flood).  allow() stays read-only (health endpoints / policy
    filters must not starve real probes); the slot is claimed at dispatch
    (begin_probe via the load guard), freed by the probe's outcome, and
    self-heals if the outcome never lands."""
    cb = CircuitBreaker(failure_threshold=1, success_threshold=1,
                        cooldown_secs=0.05)
    cb.record_failure()
    assert cb.state.value == "open"
    assert not cb.allow()
    time.sleep(0.06)
    assert cb.state.value == "half_open"
    assert cb.allow() is True       # probe slot free
    assert cb.allow() is True       # read-only: no consumption
    cb.begin_probe()                # a request dispatched: slot claimed
    assert cb.allow() is False      # flood gated
    assert cb.allow() is False
    cb.record_success()             # probe succeeded -> closed
    assert cb.state.value == "closed"
    assert cb.allow() is True

    # a probe whose outcome never lands (client vanished) must not wedge
    # the breaker: the stale slot expires after the cooldown
    cb2 = CircuitBreaker(failure_threshold=1, success_threshold=1,
                         cooldown_secs=0.05)
    cb2.record_failure()
    time.sleep(0.06)
    cb2.begin_probe()
    assert cb2.allow() is False
    time.sleep(0.06)
    assert cb2.allow() is True

    # a failed probe re-opens the circuit
    cb3 = CircuitBreaker(failure_threshold=1, success_threshold=1,
                         cooldown_secs=0.05)
    cb3.record_failure()
    time.sleep(0.06)
    cb3.begin_probe()
    cb3.record_failure()
    assert cb3.state.value == "open"
    assert not cb3.allow()


def test_half_open_gates_through_worker_guard():
    """End to end through Worker: the first half-open dispatch claims the
    probe, concurrent selection sees the worker unavailable until the probe
    reports."""
    eng = make_engine()
    w = Worker(worker_id="wp", client=InProcWorkerClient(eng), model_id="m")
    w.circuit = CircuitBreaker(failure_threshold=1, success_threshold=1,
                               cooldown_secs=0.05)
    w.circuit.record_failure()
    assert not w.is_available()
    time.sleep(0.06)
    assert w.is_available()
    guard = w.acquire()             # the probe dispatch
    assert not w.is_available()     # flood gated while the probe flies
    guard.release(success=True)
    assert w.is_available()         # closed again (threshold 1)
    assert w.circuit.state.value == "closed"
    eng.stop()


def test_total_failures_incremented_under_lock():
    """Satellite: Worker.total_failures increments under the worker lock —
    concurrent guard releases must never lose counts."""
    eng = make_engine()
    w = Worker(worker_id="wx", client=InProcWorkerClient(eng), model_id="tiny-test")
    N = 32
    barrier = threading.Barrier(N)

    def one():
        guard = w.acquire()
        barrier.wait()
        guard.release(success=False)

    threads = [threading.Thread(target=one) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert w.total_failures == N
    assert w.load == 0
    eng.stop()


# ---- satellite: HealthMonitor state cleanup on worker removal ----


def test_health_monitor_cleans_up_removed_workers():
    from prometheus_client import CollectorRegistry

    from smg_tpu.gateway.health import HealthConfig, HealthMonitor
    from smg_tpu.gateway.observability import Metrics
    from smg_tpu.gateway.worker_client import WorkerClient
    from smg_tpu.gateway.workers import WorkerRegistry

    class StubClient(WorkerClient):
        async def health(self):
            return True

    registry = WorkerRegistry()
    metrics = Metrics(registry=CollectorRegistry())
    monitor = HealthMonitor(registry, HealthConfig(), metrics)
    w = Worker(worker_id="gone", client=StubClient(), model_id="m")
    registry.add(w)
    asyncio.run(monitor.check_all())
    assert "gone" in monitor._succs
    assert ("gone",) in monitor.metrics.worker_healthy._metrics

    registry.remove("gone")
    assert "gone" not in monitor._succs
    assert "gone" not in monitor._fails
    assert ("gone",) not in monitor.metrics.worker_healthy._metrics
    assert ("gone",) not in monitor.metrics.worker_load._metrics


# ---- satellite: per-chunk stream idle timeout (rpc client) ----


def test_stream_idle_timeout_treats_silence_as_failure():
    from smg_tpu.rpc.client import StreamIdleTimeout, iter_with_idle_timeout

    class FakeCall:
        """Async iterator: one chunk, then silence forever."""

        def __init__(self):
            self.cancelled = False
            self._sent = False

        def __aiter__(self):
            return self

        async def __anext__(self):
            if not self._sent:
                self._sent = True
                return "chunk-1"
            await asyncio.sleep(3600)  # wedged worker: no further chunks

        def cancel(self):
            self.cancelled = True

    async def go():
        call = FakeCall()
        got = []
        with pytest.raises(StreamIdleTimeout):
            async for chunk in iter_with_idle_timeout(call, 0.05, "w:1"):
                got.append(chunk)
        return call, got

    call, got = asyncio.run(go())
    assert got == ["chunk-1"]  # progress before the stall was delivered
    assert call.cancelled      # the wedged call was torn down

    async def clean():
        class Done:
            def __init__(self):
                self.n = 0

            def __aiter__(self):
                return self

            async def __anext__(self):
                self.n += 1
                if self.n > 3:
                    raise StopAsyncIteration
                return self.n

            def cancel(self):
                pass

        return [c async for c in iter_with_idle_timeout(Done(), 0.5, "w:1")]

    assert asyncio.run(clean()) == [1, 2, 3]


# ---- review-fix regressions ----


def test_submit_during_drain_rejected_not_hung():
    """A submit landing after stop(drain=True) must get a retryable
    rejection, never sit in a queue no admission loop will touch."""
    eng = make_engine()
    eng.start()
    eng.stop(drain=True, timeout=10)
    with pytest.raises(QueueFullError):
        eng.submit([1, 2, 3], SamplingParams(max_new_tokens=2), rid="late")


def test_consecutive_prefill_failures_flip_unhealthy():
    """A worker failing EVERY prefill must eventually report unhealthy —
    quarantined steps complete, but they are not clean steps."""
    eng = make_engine()
    outs: dict = {}
    FAULTS.arm("engine.prefill")  # always
    for i in range(eng.config.max_consecutive_step_failures + 1):
        eng.submit([5, 6, 7 + i], SamplingParams(max_new_tokens=2),
                   rid=f"p{i}", on_output=_collector(outs, f"p{i}"))
        eng.step()
        assert outs[f"p{i}"][-1].finish_reason == "error"
    assert not eng.healthy
    FAULTS.clear()
    # one genuinely clean step (with real work) resets the streak
    eng.submit([5, 6, 99], SamplingParams(temperature=0.0, max_new_tokens=2,
                                          ignore_eos=True),
               rid="ok", on_output=_collector(outs, "ok"))
    _drive(eng, outs, ["ok"])
    assert eng.healthy


def test_exhausted_grpc_budget_is_not_unlimited():
    """timeout_secs=0.0 (budget burned by retries) must round to a tiny
    positive deadline on the wire, not the proto's 0=no-deadline sentinel."""
    from smg_tpu.rpc import scheduler_pb2 as pb

    # the client-side clamp: None -> 0 (no deadline), 0.0 -> epsilon
    assert (0.0 if None is None else max(None, 1e-3)) == 0.0
    msg = pb.GenerateRequestProto(rid="x", timeout_secs=max(0.0, 1e-3))
    assert pb.GenerateRequestProto.FromString(
        msg.SerializeToString()
    ).timeout_secs > 0.0
    # and the engine treats an epsilon deadline as expire-now, not run-forever
    eng = make_engine()
    outs: dict = {}
    eng.submit([5, 6, 7], SamplingParams(max_new_tokens=1000, ignore_eos=True),
               rid="spent", on_output=_collector(outs, "spent"),
               timeout_secs=0.001)
    time.sleep(0.01)
    _drive(eng, outs, ["spent"], max_steps=5)
    assert outs["spent"][-1].finish_reason == "timeout"


def test_first_chunk_timeout_separate_from_idle_bound():
    """Queue wait + prefill (time to FIRST chunk) must not trip the
    inter-chunk idle bound — only the longer wedge backstop applies there."""
    from smg_tpu.rpc.client import StreamIdleTimeout, iter_with_idle_timeout

    class SlowStart:
        """First chunk after a delay LONGER than the idle bound, then a
        quick second chunk, then silence."""

        def __init__(self):
            self.cancelled = False
            self.n = 0

        def __aiter__(self):
            return self

        async def __anext__(self):
            self.n += 1
            if self.n == 1:
                await asyncio.sleep(0.15)  # busy worker: > idle, < backstop
                return "first"
            if self.n == 2:
                return "second"
            await asyncio.sleep(3600)  # wedged mid-stream

        def cancel(self):
            self.cancelled = True

    async def go():
        call = SlowStart()
        got = []
        with pytest.raises(StreamIdleTimeout):
            async for c in iter_with_idle_timeout(
                call, 0.05, "w:1", first_chunk_timeout_secs=1.0
            ):
                got.append(c)
        return call, got

    call, got = asyncio.run(go())
    assert got == ["first", "second"]  # slow start survived the idle bound
    assert call.cancelled              # mid-stream silence did not


def test_reliability_locks_inversion_free_under_sentinel():
    """Lock-order sentinel over the failure-isolation hot paths: engine
    RLock + flight-recorder lock + quarantine dump path, exercised by a
    poison-decode quarantine with survivors, record ZERO order inversions.
    (scripts/ci_checks.sh additionally runs this whole suite with
    SMG_LOCK_SENTINEL=1, which fails any test at the acquisition closing an
    inversion cycle.)"""
    from smg_tpu.analysis.runtime_guards import lock_order_sentinel

    with lock_order_sentinel() as s:
        eng = make_engine()  # locks created inside the armed block
        outs: dict = {}
        rids = []
        for i in range(3):
            rid = f"sent-{i}"
            rids.append(rid)
            eng.submit(
                [(5 * i + j) % 90 + 5 for j in range(16)],
                SamplingParams(temperature=0.0, max_new_tokens=12,
                               ignore_eos=True),
                rid=rid, on_output=_collector(outs, rid),
            )
        # poison one decode step mid-flight: quarantine + flight-recorder
        # dump runs with the engine lock held (the nesting under test)
        FAULTS.arm("engine.decode_step", mode="once")
        _drive(eng, outs, rids)
        quarantined = [
            r for r in rids
            if any(o.finish_reason == "error" for o in outs[r])
        ]
        assert len(quarantined) == 1  # blame fell on exactly one lane
        eng.stop(drain=True, timeout=5.0)
        assert_engine_clean(eng)
    assert s.inversions == [], s.format_inversions()
