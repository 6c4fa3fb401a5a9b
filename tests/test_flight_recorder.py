"""Flight recorder + SLO accounting: the engine's step-level black box
(`engine/flight_recorder.py`), its auto-dump triggers (quarantine / watchdog
stall / health flip / drain — driven through `smg_tpu/faults.py`, zero
monkeypatching), the DumpFlight RPC / `GET /debug/flight/{worker}` fetch
path, the gateway SLO tracker behind `/debug/slo`, and the TTFT
retry-attribution fix (failover latency must be visible in
`smg_time_to_first_token_seconds`)."""

import asyncio
import json
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
from smg_tpu.engine.engine import Engine
from smg_tpu.engine.flight_recorder import (
    OVERLAP_OUTCOMES,
    SCHEMA_VERSION,
    STEP_RECORD_KEYS,
    FlightRecorder,
)
from smg_tpu.faults import FAULTS
from smg_tpu.gateway.observability import Metrics
from smg_tpu.gateway.server import AppContext, build_app
from smg_tpu.gateway.worker_client import (
    InProcWorkerClient,
    WorkerClient,
    WorkerGenerateRequest,
    WorkerQueueFullError,
    WorkerStreamChunk,
)
from smg_tpu.gateway.workers import Worker, WorkerRegistry
from smg_tpu.models.config import tiny_test_config
from smg_tpu.protocols.sampling import SamplingParams
from smg_tpu.tokenizer import MockTokenizer


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    FAULTS.clear()


def make_engine(watchdog_secs: float = 0.0, *, flight_kw: dict | None = None,
                **sched_kw) -> Engine:
    sched = dict(
        max_batch_size=4, max_seq_len=128, max_prefill_tokens=32,
        prefill_token_buckets=(16, 32, 64), decode_batch_buckets=(4,),
    )
    sched.update(sched_kw)
    return Engine(
        EngineConfig(
            model=tiny_test_config(),
            cache=CacheConfig(page_size=16, num_pages=128, auto_size=False,
                              dtype="float32"),
            scheduler=SchedulerConfig(**sched),
            dtype="float32",
            model_id="tiny-flight",
            step_watchdog_secs=watchdog_secs,
            # tests assert on immediate dump sequences; the production
            # default (5s) would suppress the second trigger
            flight_dump_min_interval_secs=0.0,
            **(flight_kw or {}),
        )
    )


def _collector(outs: dict, rid: str):
    def cb(out):
        outs.setdefault(rid, []).append(out)
    return cb


def _drive(eng: Engine, outs: dict, rids: list, max_steps: int = 400) -> None:
    for _ in range(max_steps):
        eng.step()
        if all(rid in outs and any(o.finished for o in outs[rid]) for rid in rids):
            return
    raise AssertionError(f"requests never finished: {list(outs)}")


# ---- ring buffer + timelines (engine-local, inline stepping) ----


def test_ring_buffer_bound_holds_under_churn():
    """The step ring and finished-timeline ring stay at their configured
    bounds no matter how many steps/requests churn through."""
    eng = make_engine(flight_kw=dict(flight_ring_size=16, flight_timeline_keep=8))
    fl = eng.scheduler.flight
    outs: dict = {}
    for batch in range(4):
        rids = [f"r{batch}-{i}" for i in range(4)]
        for rid in rids:
            eng.submit([5 + batch, 6, 7, 8], SamplingParams(
                temperature=0.0, max_new_tokens=6, ignore_eos=True),
                rid=rid, on_output=_collector(outs, rid))
        _drive(eng, outs, rids)
    snap = fl.snapshot()
    assert len(snap["ring"]) == 16  # full and bounded
    assert fl.step_serial > 16  # far more steps happened than the ring holds
    serials = [r["serial"] for r in snap["ring"]]
    assert serials == sorted(serials) and serials[-1] == fl.step_serial
    assert len(snap["timelines"]["finished"]) == 8  # 16 finished, 8 kept
    assert snap["timelines"]["live"] == []
    eng.stop()


def test_timeline_completeness_chunked_prefill_overlap():
    """Under chunked prefill (budget 32, 80-token prompt) with the overlap
    pipeline on, the timeline still reads queued -> admitted -> every
    prefill chunk (final last) -> first token -> finish, with TTFT/ITL/e2e
    computed."""
    eng = make_engine()  # overlap_schedule defaults on
    outs: dict = {}
    # a running stream so the long admission interleaves with decode
    eng.submit([9, 9, 9], SamplingParams(
        temperature=0.0, max_new_tokens=24, ignore_eos=True),
        rid="bg", on_output=_collector(outs, "bg"))
    for _ in range(4):
        eng.step()
    eng.submit(list(range(5, 85)), SamplingParams(
        temperature=0.0, max_new_tokens=4, ignore_eos=True),
        rid="long", on_output=_collector(outs, "long"))
    _drive(eng, outs, ["bg", "long"])
    dump = eng.dump_flight()
    tl = {t["rid"]: t for t in dump["timelines"]["finished"]}["long"]
    kinds = [e["kind"] for e in tl["events"]]
    assert kinds[0] == "queued" and kinds[1] == "admitted"
    chunks = [e for e in tl["events"] if e["kind"] == "prefill_chunk"]
    # 80 tokens / 32-token budget -> 3 chunks, only the last final
    assert len(chunks) == 3
    assert [c["final"] for c in chunks] == [False, False, True]
    assert sum(c["n"] for c in chunks) == 80
    assert kinds.index("first_token") > kinds.index("admitted")
    assert kinds[-1] == "finish" and tl["finish_reason"] == "length"
    assert tl["ttft_s"] > 0 and tl["e2e_s"] >= tl["ttft_s"]
    assert tl["output_tokens"] == 4 and tl["prompt_tokens"] == 80
    assert tl["itl"]["count"] == 3  # 4 tokens -> 3 gaps
    # overlap outcomes recorded in the ring
    outcomes = {r["overlap"] for r in dump["ring"]}
    assert outcomes & {"kept", "sync", "discarded"} and outcomes <= set(OVERLAP_OUTCOMES)
    eng.stop()


def test_dump_schema_stable():
    """The dump key sets are a contract: top level, step records, and
    timeline dicts.  Extending them is fine — update this test AND bump
    SCHEMA_VERSION when a key is renamed/removed."""
    eng = make_engine()
    eng.generate(prompt_ids=[5, 6, 7], sampling=SamplingParams(
        temperature=0.0, max_new_tokens=3, ignore_eos=True))
    dump = eng.dump_flight("manual")
    assert dump["schema_version"] == SCHEMA_VERSION
    assert {
        "schema_version", "reason", "ts_unix", "t_mono", "last_step_serial",
        "ring", "timelines", "auto_dumps", "engine",
    } <= set(dump)
    assert dump["reason"] == "manual"
    for rec in dump["ring"]:
        assert set(rec) == STEP_RECORD_KEYS
        assert rec["overlap"] in OVERLAP_OUTCOMES
    # the one request's prefill had its step's decode frame launched behind it
    assert dump["ring"][0]["overlap"] == "chained"
    assert OVERLAP_OUTCOMES == ("sync", "kept", "discarded", "chained")
    tl = dump["timelines"]["finished"][0]
    assert {
        "rid", "trace_id", "meta", "submit_t", "queued_t", "admitted_t",
        "first_token_t", "finish_t", "finish_reason", "finish_message", "deadline_t", "ttft_s",
        "e2e_s", "prompt_tokens", "cached_tokens", "output_tokens", "itl",
        "events",
    } == set(tl)
    assert {"count", "mean_s", "p50_s", "p95_s", "max_s"} == set(tl["itl"])
    assert tl["meta"]["temperature"] == 0.0
    json.dumps(dump)  # JSON-able end to end
    eng.stop()


# ---- auto-dump triggers (driven through faults.py) ----


def test_dump_on_quarantine_contains_failing_step_and_culprit():
    """A fault-injected poison decode step auto-dumps; the dump's ring
    contains the failing step (fault flags set) and its timelines identify
    the quarantined request (acceptance criterion, engine-local half)."""
    FAULTS.arm_from_env("engine.decode_step=once")  # the SMG_FAULTS grammar
    eng = make_engine()
    outs: dict = {}
    for rid in ("a", "b"):
        eng.submit([5, 6, 7], SamplingParams(
            temperature=0.0, max_new_tokens=4, ignore_eos=True),
            rid=rid, on_output=_collector(outs, rid))
    _drive(eng, outs, ["a", "b"])
    fl = eng.scheduler.flight
    assert [d["reason"] for d in fl.dumps] == ["quarantine"]
    dump = fl.dumps[0]
    faulted = [r for r in dump["ring"] if "decode" in r["faults"]]
    assert faulted, "dump ring lost the failing step"
    quarantined = [
        t for t in dump["timelines"]["finished"]
        if any(e["kind"] == "quarantine" for e in t["events"])
    ]
    assert len(quarantined) == 1
    assert quarantined[0]["finish_reason"] == "error"
    # the blamed rid really is the one that saw finish_reason=error
    errored = [r for r in outs if outs[r][-1].finish_reason == "error"]
    assert [quarantined[0]["rid"]] == errored
    eng.stop()


def test_health_flip_dump_on_consecutive_failures():
    """Crossing max_consecutive_step_failures dumps reason=health_flip.
    One prefill-quarantine per step keeps the failure streak unbroken (a
    batch condemn resolves in a single step and never reaches the
    threshold)."""
    FAULTS.arm("engine.prefill", mode="always")
    eng = make_engine()
    outs: dict = {}
    for i in range(4):
        eng.submit([5 + i, 6, 7], SamplingParams(
            temperature=0.0, max_new_tokens=8, ignore_eos=True),
            rid=f"r{i}", on_output=_collector(outs, f"r{i}"))
        eng.step()  # each step fails (and quarantines) one prefill
        if not eng.healthy:
            break
    assert not eng.healthy
    reasons = [d["reason"] for d in eng.scheduler.flight.dumps]
    assert "health_flip" in reasons
    FAULTS.clear()
    eng.stop()


def test_dump_on_watchdog_stall():
    """A wedged device fetch (injected hang) makes the watchdog dump the
    black box — lock-free, while the step thread still holds the engine
    lock — and the dump is fetchable via dump_flight at the same moment."""
    eng = make_engine(watchdog_secs=0.3)
    eng.start()
    try:
        eng.generate(prompt_ids=[5, 6, 7], sampling=SamplingParams(
            temperature=0.0, max_new_tokens=4, ignore_eos=True))  # warm
        FAULTS.arm("engine.device_fetch", mode="once", action="hang", delay=2.0)
        outs: dict = {}
        eng.submit([8, 9, 10], SamplingParams(
            temperature=0.0, max_new_tokens=4, ignore_eos=True),
            rid="w", on_output=_collector(outs, "w"))
        deadline = time.monotonic() + 30
        dumped = False
        while time.monotonic() < deadline:
            if any(d["reason"] == "watchdog_stall"
                   for d in eng.scheduler.flight.dumps):
                dumped = True
                # postmortem fetch works mid-stall (no engine lock taken)
                snap = eng.dump_flight("probe")
                assert snap["last_auto_dump"]["reason"] == "watchdog_stall"
                break
            time.sleep(0.02)
        assert dumped, "watchdog stall never produced a flight dump"
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if outs.get("w") and outs["w"][-1].finished:
                break
            time.sleep(0.02)
        assert outs["w"][-1].finished
    finally:
        eng.stop()


def test_dump_on_drain():
    eng = make_engine()
    eng.start()
    eng.generate(prompt_ids=[5, 6], sampling=SamplingParams(
        temperature=0.0, max_new_tokens=2, ignore_eos=True))
    eng.stop(drain=True, timeout=5.0)
    assert "drain" in [d["reason"] for d in eng.scheduler.flight.dumps]


def test_failing_dump_degrades_to_log_not_failure():
    """An armed flight.dump fault breaks the dump path; the quarantine it
    was reporting still completes cleanly and the engine keeps serving."""
    FAULTS.arm("flight.dump")
    FAULTS.arm("engine.decode_step", mode="once")
    eng = make_engine()
    outs: dict = {}
    eng.submit([5, 6, 7], SamplingParams(
        temperature=0.0, max_new_tokens=4, ignore_eos=True),
        rid="a", on_output=_collector(outs, "a"))
    _drive(eng, outs, ["a"])
    assert outs["a"][-1].finish_reason == "error"  # quarantine still landed
    assert len(eng.scheduler.flight.dumps) == 0  # dump failed, engine fine
    FAULTS.clear()
    r = eng.generate(prompt_ids=[8, 9], sampling=SamplingParams(
        temperature=0.0, max_new_tokens=2, ignore_eos=True))
    assert len(r.token_ids) == 2
    eng.stop()


def test_auto_dump_rate_limit_is_per_reason():
    fl = FlightRecorder(dump_min_interval_secs=60.0)
    assert fl.auto_dump("quarantine") is True
    assert fl.auto_dump("quarantine") is False  # throttled
    assert fl.auto_dump("drain") is True  # different reason passes
    assert fl.num_dump_suppressed == 1
    assert [d["reason"] for d in fl.dumps] == ["quarantine", "drain"]


def test_recorder_off_engine_still_works():
    eng = make_engine(flight_kw=dict(flight_recorder=False))
    assert eng.scheduler.flight is None
    r = eng.generate(prompt_ids=[5, 6, 7], sampling=SamplingParams(
        temperature=0.0, max_new_tokens=3, ignore_eos=True))
    assert len(r.token_ids) == 3
    assert eng.dump_flight()["error"] == "flight recorder disabled"
    eng.stop()


def test_dump_dir_writes_reason_tagged_files(tmp_path):
    eng = make_engine(flight_kw=dict(flight_dump_dir=str(tmp_path)))
    FAULTS.arm("engine.decode_step", mode="once")
    outs: dict = {}
    eng.submit([5, 6, 7], SamplingParams(
        temperature=0.0, max_new_tokens=4, ignore_eos=True),
        rid="a", on_output=_collector(outs, "a"))
    _drive(eng, outs, ["a"])
    files = list(tmp_path.glob("flight-*-quarantine.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["schema_version"] == SCHEMA_VERSION
    assert on_disk["reason"] == "quarantine"
    eng.stop()


# ---- RPC + gateway fetch path (acceptance criterion, end to end) ----


def test_flight_dump_fetchable_end_to_end_over_rpc():
    """SMG_FAULTS=engine.decode_step poisons one decode step; the auto-dump
    is then fetched through the FULL path: gateway HTTP
    GET /debug/flight/{worker} -> GrpcWorkerClient.DumpFlight -> worker
    servicer -> Engine.dump_flight."""
    from smg_tpu.rpc.client import GrpcWorkerClient
    from smg_tpu.rpc.server import serve_worker_async

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()

    def run(coro, timeout=180):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=timeout)

    engine = make_engine()
    engine.start()

    async def _setup():
        server = await serve_worker_async(engine, port=0, host="127.0.0.1")
        client = GrpcWorkerClient(f"127.0.0.1:{server._bound_port}")
        ctx = AppContext(policy="round_robin")
        ctx.tokenizers.register("tiny-flight", MockTokenizer(), default=True)
        ctx.registry.add(Worker(worker_id="w0", client=client,
                                model_id="tiny-flight"))
        tc = TestClient(TestServer(build_app(ctx)))
        await tc.start_server()
        return server, client, tc

    server, client, tc = run(_setup())
    try:
        # warm path (compiles), then poison exactly one decode step
        async def warm():
            req = WorkerGenerateRequest(
                rid="warm", input_ids=[5, 6, 7],
                sampling=SamplingParams(temperature=0.0, max_new_tokens=2,
                                        ignore_eos=True))
            async for _ in client.generate(req):
                pass
        run(warm())
        assert FAULTS.arm_from_env("engine.decode_step=once") == 1

        async def poisoned():
            chunks = []
            req = WorkerGenerateRequest(
                rid="poison-me", input_ids=[8, 9, 10],
                sampling=SamplingParams(temperature=0.0, max_new_tokens=4,
                                        ignore_eos=True))
            async for c in client.generate(req):
                chunks.append(c)
            return chunks
        chunks = run(poisoned())
        assert chunks[-1].finish_reason == "error"

        async def fetch():
            r = await tc.get("/debug/flight/w0")
            return r.status, await r.json()
        status, body = run(fetch())
        assert status == 200 and body["worker_id"] == "w0"
        dump = body["dump"]
        assert dump["schema_version"] == SCHEMA_VERSION
        auto = dump["last_auto_dump"]
        assert auto["reason"] == "quarantine"
        assert any("decode" in r["faults"] for r in auto["ring"])
        quarantined = [
            tl for tl in auto["timelines"]["finished"]
            if any(e["kind"] == "quarantine" for e in tl["events"])
        ]
        assert [tl["rid"] for tl in quarantined] == ["poison-me"]

        async def fetch_missing():
            r = await tc.get("/debug/flight/ghost")
            return r.status
        assert run(fetch_missing()) == 404
    finally:
        run(tc.close())
        run(client.close())
        run(server.stop(grace=None))
        loop.call_soon_threadsafe(loop.stop)
        engine.stop()


def test_traceparent_joins_worker_timeline_over_grpc():
    """The gateway's ambient span rides gRPC metadata; the engine-side
    flight timeline records the SAME trace id (satellite: no fresh trace
    root per worker hop)."""
    from smg_tpu.gateway.tracing import OtelTracer, current_span, current_tracer
    from smg_tpu.rpc.client import GrpcWorkerClient
    from smg_tpu.rpc.server import serve_worker_async

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()

    def run(coro, timeout=120):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=timeout)

    engine = make_engine()
    engine.start()
    worker_tracer = OtelTracer("http://collector.invalid")  # never flushed

    async def _setup():
        server = await serve_worker_async(
            engine, port=0, host="127.0.0.1", tracer=worker_tracer
        )
        return server, GrpcWorkerClient(f"127.0.0.1:{server._bound_port}")

    server, client = run(_setup())
    gateway_tracer = OtelTracer("http://collector.invalid")
    span = gateway_tracer.start_span("POST /v1/chat/completions")
    try:
        async def go():
            tok_s = current_span.set(span)
            tok_t = current_tracer.set(gateway_tracer)
            try:
                req = WorkerGenerateRequest(
                    rid="traced", input_ids=[5, 6, 7],
                    sampling=SamplingParams(temperature=0.0, max_new_tokens=2,
                                            ignore_eos=True))
                async for _ in client.generate(req):
                    pass
            finally:
                current_span.reset(tok_s)
                current_tracer.reset(tok_t)
        run(go())
        tl = {
            t["rid"]: t
            for t in engine.dump_flight()["timelines"]["finished"]
        }["traced"]
        assert tl["trace_id"] == span.trace_id
        # worker-side span joined the SAME trace rather than rooting a new one
        worker_spans = [s for s in worker_tracer._buffer
                        if s.name == "worker.generate"]
        assert worker_spans and worker_spans[0].trace_id == span.trace_id
        assert worker_spans[0].parent_span_id == span.span_id
    finally:
        run(client.close())
        run(server.stop(grace=None))
        loop.call_soon_threadsafe(loop.stop)
        engine.stop()


# ---- TTFT retry attribution + SLO tracker (gateway side) ----


class _SharedQueueFullOnce:
    """First generate() across the pool hits queue-full after a delay, so
    whichever worker the policy picks first forces a failover."""

    def __init__(self, delay: float):
        self.delay = delay
        self.lock = threading.Lock()
        self.tripped = False

    def trip(self) -> bool:
        with self.lock:
            if not self.tripped:
                self.tripped = True
                return True
            return False


class _StubWorkerClient(WorkerClient):
    def __init__(self, shared: _SharedQueueFullOnce):
        self.shared = shared

    async def generate(self, req):
        if self.shared.trip():
            await asyncio.sleep(self.shared.delay)
            raise WorkerQueueFullError("induced backpressure")
        yield WorkerStreamChunk(
            rid=req.rid, token_ids=[1], finished=False, prompt_tokens=3,
            output_tokens=1,
        )
        yield WorkerStreamChunk(
            rid=req.rid, token_ids=[2], finished=True, finish_reason="stop",
            prompt_tokens=3, output_tokens=2,
        )

    async def abort(self, rid):
        return True

    async def health(self):
        return True

    async def get_loads(self):
        return {"num_waiting": 0, "num_running": 0, "queued_tokens": 0}


def _hist_sample(metrics_registry, name, suffix, labels):
    for fam in metrics_registry.collect():
        for s in fam.samples:
            if s.name == name + suffix and all(
                s.labels.get(k) == v for k, v in labels.items()
            ):
                return s.value
    return None


def test_ttft_measured_from_first_dispatch_across_queue_full_failover():
    """Satellite: after a WorkerQueueFullError failover, TTFT must span BOTH
    dispatches — the induced 80ms first-worker delay has to show up in
    smg_time_to_first_token_seconds, and exactly one sample is recorded."""
    from smg_tpu.gateway.router import Router
    from smg_tpu.policies import PolicyRegistry, RequestContext
    from smg_tpu.tokenizer.registry import TokenizerRegistry

    shared = _SharedQueueFullOnce(delay=0.08)
    registry = WorkerRegistry()
    registry.add(Worker(worker_id="wa", client=_StubWorkerClient(shared),
                        model_id="m"))
    registry.add(Worker(worker_id="wb", client=_StubWorkerClient(shared),
                        model_id="m"))
    metrics = Metrics()
    router = Router(registry, PolicyRegistry(default="round_robin"),
                    TokenizerRegistry(), metrics=metrics)

    async def go():
        evs = []
        ctx = RequestContext(model_id="m", request_id="t1")
        async for ev in router._execute(
            ctx, [1, 2, 3], SamplingParams(max_new_tokens=4), "t1", None
        ):
            evs.append(ev)
        return evs

    evs = asyncio.run(go())
    assert evs[-1].finished and evs[-1].finish_reason == "stop"
    count = _hist_sample(metrics.registry, "smg_time_to_first_token_seconds",
                         "_count", {"route": "unknown"})
    total = _hist_sample(metrics.registry, "smg_time_to_first_token_seconds",
                         "_sum", {"route": "unknown"})
    assert count == 1.0, "TTFT must be observed exactly once per request"
    assert total >= 0.08, (
        f"TTFT {total}s lost the queue-full failover latency"
    )
    assert shared.tripped
    # the SLO record agrees with the metric: one request, ttft >= failover
    rec = metrics.slo.summary()["recent"][-1]
    assert rec["rid"] == "t1" and rec["ttft_s"] >= 0.08
    assert rec["reason"] == "stop" and rec["output_tokens"] == 2


def test_slo_tracker_deadline_and_goodput():
    m = Metrics()
    # deadline met: fast clean finish
    r1 = m.slo.begin("ok", route="/v1/completions", deadline_secs=5.0)
    r1.first_token(10, 2)
    r1.tokens(3)
    r1.tokens(2)
    r1.finish("stop")
    # deadline missed: engine timeout finish
    r2 = m.slo.begin("late", route="/v1/completions", deadline_secs=5.0)
    r2.first_token(10, 0)
    r2.tokens(1)
    r2.finish("timeout")
    # no deadline: clean finish counts toward goodput, not deadline outcomes
    r3 = m.slo.begin("free", route="/v1/chat/completions")
    r3.first_token(4, 0)
    r3.tokens(4)
    r3.finish("stop")
    # terminal transitions are idempotent
    r3.fail("error")

    s = m.slo.summary()
    assert s["window_requests"] == 3
    assert s["deadline"] == {"with_deadline": 2, "met": 1, "missed": 1}
    assert s["goodput"]["tokens"] == 5 + 4  # ok(5) + free(4), late excluded
    assert s["finish_reasons"] == {"stop": 2, "timeout": 1}
    assert s["ttft"]["p95_s"] >= 0.0 and s["recent"][-1]["rid"] == "free"
    met = _hist_sample(m.registry, "smg_request_deadline_outcomes_total", "",
                       {"outcome": "met"})
    missed = _hist_sample(m.registry, "smg_request_deadline_outcomes_total",
                          "", {"outcome": "missed"})
    good = _hist_sample(m.registry, "smg_goodput_tokens_total", "", {})
    assert (met, missed, good) == (1.0, 1.0, 9.0)


def test_debug_slo_endpoint_over_gateway():
    """/debug/slo reflects requests served through the real dispatch path
    (in-proc engine worker) including ITL observations."""
    eng = make_engine()
    ctx = AppContext(policy="round_robin")
    ctx.tokenizers.register("tiny-flight", MockTokenizer(), default=True)

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()

    def run(coro, timeout=180):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=timeout)

    async def _setup():
        ctx.registry.add(Worker(worker_id="w0", client=InProcWorkerClient(eng),
                                model_id="tiny-flight"))
        tc = TestClient(TestServer(build_app(ctx)))
        await tc.start_server()
        return tc

    tc = run(_setup())
    try:
        async def go():
            r = await tc.post("/v1/chat/completions", json={
                "model": "tiny-flight",
                "messages": [{"role": "user", "content": "w5 w6 w7"}],
                "max_tokens": 6, "temperature": 0, "ignore_eos": True,
            })
            assert r.status == 200
            r2 = await tc.get("/debug/slo")
            return await r2.json()

        s = run(go())
        assert s["window_requests"] == 1
        rec = s["recent"][-1]
        assert rec["route"] == "/v1/chat/completions"
        assert rec["reason"] == "length" and rec["output_tokens"] == 6
        assert rec["ttft_s"] > 0 and rec["deadline_met"] is True
        # engine-side timeline for the same request exists with matching rid
        dump = eng.dump_flight()
        assert any(tl["rid"] == rec["rid"]
                   for tl in dump["timelines"]["finished"])
    finally:
        run(tc.close())
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()
