"""OTel OTLP/HTTP trace export against a local collector double
(reference: observability/otel_trace.rs; VERDICT r3 next-round #8)."""

import asyncio
import threading

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from smg_tpu.gateway.tracing import OtelTracer, Span, parse_traceparent


def test_parse_traceparent():
    tid, sid = parse_traceparent("00-" + "ab" * 16 + "-" + "cd" * 8 + "-01")
    assert tid == "ab" * 16 and sid == "cd" * 8
    assert parse_traceparent(None) is None
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-" + "0" * 32 + "-" + "cd" * 8 + "-01") is None


def test_span_otlp_shape():
    s = Span(name="GET /x", trace_id="ab" * 16)
    s.set("http.request.method", "GET")
    s.set("retries", 2)
    s.set("sampled", True)
    s.end()
    d = s.to_otlp()
    assert d["traceId"] == "ab" * 16 and len(d["spanId"]) == 16
    assert d["status"]["code"] == 1
    attrs = {a["key"]: a["value"] for a in d["attributes"]}
    assert attrs["http.request.method"] == {"stringValue": "GET"}
    assert attrs["retries"] == {"intValue": "2"}
    assert attrs["sampled"] == {"boolValue": True}
    assert int(d["endTimeUnixNano"]) >= int(d["startTimeUnixNano"])


class Collector:
    """OTLP/HTTP collector double."""

    def __init__(self):
        self.batches = []
        self.app = web.Application()
        self.app.router.add_post("/v1/traces", self.handle)

    async def handle(self, request):
        self.batches.append(await request.json())
        return web.json_response({})

    def spans(self):
        out = []
        for b in self.batches:
            for rs in b["resourceSpans"]:
                for ss in rs["scopeSpans"]:
                    out.extend(ss["spans"])
        return out


def test_tracer_batches_and_exports():
    async def go():
        col = Collector()
        runner = web.AppRunner(col.app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]

        tracer = OtelTracer(f"http://127.0.0.1:{port}", "test-svc",
                            flush_interval=0.05)
        await tracer.start()
        parent = tracer.start_span("parent")
        child = tracer.start_span("child", parent=parent)
        child.end()
        parent.end()
        tracer.record(child)
        tracer.record(parent)
        for _ in range(100):
            if tracer.exported >= 2:
                break
            await asyncio.sleep(0.02)
        await tracer.stop()
        await runner.cleanup()

        spans = col.spans()
        assert len(spans) == 2
        by_name = {s["name"]: s for s in spans}
        assert by_name["child"]["traceId"] == by_name["parent"]["traceId"]
        assert by_name["child"]["parentSpanId"] == by_name["parent"]["spanId"]
        res = col.batches[0]["resourceSpans"][0]["resource"]["attributes"]
        assert {"key": "service.name",
                "value": {"stringValue": "test-svc"}} in res

    asyncio.run(go())


def test_export_failure_never_raises():
    async def go():
        tracer = OtelTracer("http://127.0.0.1:9")  # discard-port: refused
        tracer.record(Span(name="x", trace_id="ab" * 16))
        await tracer.flush()  # must swallow the connection error
        assert tracer.dropped == 1
        await tracer.stop()

    asyncio.run(go())


def test_buffer_cap_drops():
    async def go():
        tracer = OtelTracer("http://127.0.0.1:9", max_buffer=3)
        for _ in range(5):
            tracer.record(Span(name="x", trace_id="ab" * 16))
        assert len(tracer._buffer) == 3 and tracer.dropped == 2
        tracer._buffer.clear()
        await tracer.stop()

    asyncio.run(go())


# ---- gateway e2e: spans for real requests, traceparent propagation ----


def test_gateway_emits_request_spans():
    from smg_tpu.engine.config import CacheConfig, EngineConfig, SchedulerConfig
    from smg_tpu.engine.engine import Engine
    from smg_tpu.gateway.server import AppContext, build_app
    from smg_tpu.gateway.worker_client import InProcWorkerClient
    from smg_tpu.gateway.workers import Worker
    from smg_tpu.models.config import tiny_test_config
    from smg_tpu.tokenizer import MockTokenizer

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()

    def run(coro, timeout=300):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout=timeout)

    eng = Engine(EngineConfig(
        model=tiny_test_config(),
        cache=CacheConfig(page_size=16, num_pages=64, auto_size=False,
                          dtype="float32"),
        scheduler=SchedulerConfig(
            max_batch_size=2, max_seq_len=128, max_prefill_tokens=32,
            prefill_token_buckets=(32,), decode_batch_buckets=(2,),
        ),
        dtype="float32", model_id="tiny-otel",
    ), tokenizer=MockTokenizer())

    col = Collector()

    async def _setup():
        runner = web.AppRunner(col.app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        ctx = AppContext(policy="round_robin",
                         otel_endpoint=f"http://127.0.0.1:{port}")
        ctx.tracer.flush_interval = 0.05
        ctx.tokenizers.register("tiny-otel", MockTokenizer(), default=True)
        ctx.registry.add(Worker(worker_id="w0", client=InProcWorkerClient(eng),
                                model_id="tiny-otel"))
        tc = TestClient(TestServer(build_app(ctx)))
        await tc.start_server()
        return runner, ctx, tc

    runner, ctx, tc = run(_setup())
    try:
        upstream = "00-" + "12" * 16 + "-" + "34" * 8 + "-01"

        async def go():
            r = await tc.post("/v1/chat/completions", json={
                "model": "tiny-otel",
                "messages": [{"role": "user", "content": "w5"}],
                "max_tokens": 2, "temperature": 0, "ignore_eos": True,
            }, headers={"traceparent": upstream})
            assert r.status == 200
            # the response carries OUR span in traceparent, same trace id
            tp = r.headers.get("traceparent")
            assert tp is not None and tp.split("-")[1] == "12" * 16
            # the stage spans end before the request span does and can go
            # out a batch earlier: wait for the request span itself
            for _ in range(250):
                if any(s["name"] == "POST /v1/chat/completions" for s in col.spans()):
                    return
                await asyncio.sleep(0.02)
            raise TimeoutError(
                f"request span never exported: {[s['name'] for s in col.spans()]}")

        run(go())
        spans = col.spans()
        chat = [s for s in spans if s["name"] == "POST /v1/chat/completions"]
        assert chat, [s["name"] for s in spans]
        s = chat[0]
        assert s["traceId"] == "12" * 16
        assert s["parentSpanId"] == "34" * 8
        attrs = {a["key"]: a["value"] for a in s["attributes"]}
        assert attrs["http.response.status_code"] == {"intValue": "200"}
        assert attrs["request.id"]["stringValue"].startswith("req-")
    finally:
        run(tc.close())
        run(runner.cleanup())
        loop.call_soon_threadsafe(loop.stop)
        eng.stop()
