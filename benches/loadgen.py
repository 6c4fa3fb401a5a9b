#!/usr/bin/env python
"""Heavy-traffic open-loop loadgen + SLO enforcement harness (ROADMAP item 5).

Drives a REAL in-proc gateway + N-worker stack (aiohttp app over real
sockets, ``InProcWorkerClient`` engines on the CPU backend) with an
open-loop arrival process replaying a mixed scenario matrix, then asserts
the repo's whole observability contract as hard pass/fail:

- scenario matrix: short chat (bursty arrivals), long-context prefill
  (chunked-prefill budget), JSON-constrained decode, tool-call loops,
  streaming with mid-stream client disconnects, deadline'd requests (every
  request rides ``--request-timeout-secs``), and Zipf multi-turn sessions
  (the PR 9 routing-probe trace, ``_zipf_trace``);
- open-loop arrivals: Poisson (exponential gaps) or bursty, from a seeded
  RNG threaded through ``LoadgenConfig`` — a given (seed, matrix) emits the
  identical request schedule every run;
- epilogue (the asserted invariants):
  * every installed SLO verdict passes (``GET /debug/slo/verdicts`` — the
    gateway-side enforcement layer, ``gateway/slo_enforcement.py``),
  * ``/debug/slo`` goodput stays above the spec floor and client
    disconnects are excluded from deadline met/missed (PR 6 semantics),
  * ``/debug/router`` reconciliation shows real prefix hits with
    prediction error in band,
  * a saturation burst produces queue-full 429s WITHOUT breaker penalty
    (every circuit still closed, retry-other-worker observed),
  * drain-under-load: removing the busiest worker mid-stream completes
    every in-flight stream,
  * zero slot/page/radix-lock/callback leaks at quiescence on every engine
    (``Engine.audit()``, incl. the drained worker),
  * an injected SLO violation window flips a verdict to fail and a
    flight-recorder dump is fetched for every worker in that window.

Results print as one JSON line per ``loadgen_*`` scenario/probe using
STEP-COUNT metrics (request/token/429/dump counts — the trustworthy
numbers; ROADMAP documents +-3x wall-clock noise on the bench box), plus a
final ``loadgen_checks`` line; exit code 1 on any failed check.

Usage::

    JAX_PLATFORMS=cpu python benches/loadgen.py --seed 0 --workers 2
    ... --scenarios short_chat,zipf_session --scale 2 --out /tmp/lg.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import time
from dataclasses import dataclass, field

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ALL_SCENARIOS = (
    "short_chat", "long_prefill", "json_mode", "tool_loop",
    "stream_disconnect", "zipf_session", "tp_worker",
)

#: smoke-grade SLO spec: the verdicts must PASS on a healthy stack, so the
#: targets are sized for the WORST tier-1/CI environment, not a quiet box —
#: the bench host swings +-3x with ambient load (ROADMAP) and gateway-side
#: ITL measures event-loop chunk arrival, which stalls whole seconds when
#: the suite runs alongside.  The point is enforcement wiring (a hang, a
#: broken dispatch path, or mass deadline misses still fail); latency
#: regression-hunting belongs to the step-count probes.  The goodput floor
#: is deliberately low: the matrix is disconnect-heavy by design, and
#: tokens streamed to a client that hung up count toward total but never
#: toward goodput (PR 6 semantics).
DEFAULT_SLO_SPECS = [
    {
        "name": "loadgen_smoke",
        "ttft_p95_s": 60.0,
        "itl_p95_s": 10.0,
        "e2e_p95_s": 60.0,
        "goodput_ratio_floor": 0.1,
        "deadline_miss_budget": 0.5,
        "fast_window_s": 120.0,
        "slow_window_s": 600.0,
        "min_requests": 5,
        "hysteresis": 1,
    },
]


def _zipf_trace(rng, n_requests, n_users, system_tokens, turn_tokens,
                vocab_size, max_prompt):
    """Zipf-ish multi-turn chat trace (the PR 9 routing-probe trace): a few
    hot users dominate, and every prompt is a shared system prefix, the
    user's growing history and a fresh turn, which is the workload
    cache-aware routing exists for.  The turn length is deliberately NOT
    page-aligned, so reconciliation sees the engine's page-granular rounding
    as honest small error.  Scaled to the tiny test model: token ids folded
    into the vocab, prompts truncated to the engine's sequence budget."""
    system = [rng.randrange(32000) for _ in range(system_tokens)]
    weights = [1.0 / (rank + 1) for rank in range(n_users)]
    histories: dict[int, list[int]] = {}
    trace = []
    for _ in range(n_requests):
        uid = rng.choices(range(n_users), weights=weights)[0]
        hist = histories.setdefault(uid, list(system))
        hist.extend(rng.randrange(32000) for _ in range(turn_tokens))
        trace.append([t % vocab_size for t in hist[-max_prompt:]])
    return trace


@dataclass
class LoadgenConfig:
    """One reproducible run: thread the seed through EVERYTHING."""

    seed: int = 0
    workers: int = 2
    scale: float = 1.0
    scenarios: tuple = ALL_SCENARIOS
    arrival: str = "poisson"  # poisson | bursty (short_chat is always bursty)
    rate_rps: float = 24.0  # open-loop arrival rate across the matrix
    request_timeout_secs: float = 60.0  # every request's deadline (PR 5/6)
    max_queued_requests: int = 8  # engine bounded queue (backpressure probe)
    slo_specs: list | None = None  # None -> DEFAULT_SLO_SPECS
    probes: bool = True  # violation/backpressure/drain probes + audits
    # band checks for /debug/router reconciliation
    prediction_error_band_tokens: float = 48.0
    # engine shape (tiny CPU model)
    max_batch_size: int = 4
    num_pages: int = 256
    page_size: int = 16
    max_seq_len: int = 192
    model_id: str = "tiny-loadgen"
    # tensor-parallel in-proc worker: with the "tp_worker" scenario enabled,
    # worker 0 runs a tp=tp_mesh sharded engine (needs that many jax
    # devices; loadgen forces an 8-device CPU mesh before jax imports)
    tp_mesh: int = 2


def build_engine(cfg: LoadgenConfig, idx: int):
    from smg_tpu.engine.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from smg_tpu.engine.engine import Engine
    from smg_tpu.models.config import tiny_test_config
    from smg_tpu.tokenizer import MockTokenizer

    parallel = None
    devices = None
    if idx == 0 and "tp_worker" in cfg.scenarios and cfg.tp_mesh > 1:
        # worker 0 is the fleet's tensor-parallel worker: same weights
        # (seed 0), sharded over a tp mesh — the matrix exercises it
        # through the same gateway path as every single-device peer
        import jax

        devs = jax.devices("cpu")
        if len(devs) >= cfg.tp_mesh:
            parallel = ParallelConfig(tp=cfg.tp_mesh)
            devices = devs[: cfg.tp_mesh]
        else:  # no silent caps: say the TP leg degraded to single-device
            print(json.dumps({"bench": "loadgen_tp_worker",
                              "skipped": f"{len(devs)} devices < tp={cfg.tp_mesh}"}))

    model = tiny_test_config()
    return Engine(
        EngineConfig(
            model=model,
            parallel=parallel or ParallelConfig(),
            cache=CacheConfig(page_size=cfg.page_size, num_pages=cfg.num_pages,
                              auto_size=False, dtype="float32"),
            scheduler=SchedulerConfig(
                max_batch_size=cfg.max_batch_size,
                max_seq_len=cfg.max_seq_len,
                max_prefill_tokens=32,
                prefill_token_buckets=(16, 32, 64),
                decode_batch_buckets=(cfg.max_batch_size,),
                max_queued_requests=cfg.max_queued_requests,
            ),
            dtype="float32",
            model_id=cfg.model_id,
            # identical weights on every worker: same model, different worker
            seed=0,
            flight_dump_min_interval_secs=0.0,
        ),
        tokenizer=MockTokenizer(vocab_size=model.vocab_size),
        devices=devices,
    )


def _warm_engines(engines) -> None:
    """Compile every program the matrix needs BEFORE the open-loop clock
    starts (prefill buckets via a chunked prompt, the decode trace, and the
    grammar-constrained K=1 trace) so first-request XLA compiles don't
    masquerade as TTFT violations or pile arrivals into the bounded queue."""
    from smg_tpu.protocols.sampling import SamplingParams

    for eng in engines:
        eng.generate(prompt_ids=list(range(2, 42)),
                     sampling=SamplingParams(temperature=0.0, max_new_tokens=4,
                                             ignore_eos=True))
        eng.generate(prompt_ids=[2, 3, 4],
                     sampling=SamplingParams(temperature=0.0, max_new_tokens=2,
                                             json_schema="{}"))


# ---- request runners (each returns one record dict) ----


async def _chat(tc, scenario, *, content, max_tokens, stream=False, tools=None,
                messages=None):
    body = {
        "model": "tiny-loadgen",
        "messages": messages or [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
        "stream": stream,
    }
    if tools:
        body["tools"] = tools
    rec = {"scenario": scenario, "status": 0, "tokens": 0,
           "rejected": False, "disconnected": False, "error": None}
    try:
        resp = await tc.post("/v1/chat/completions", json=body)
        rec["status"] = resp.status
        if resp.status == 429:
            rec["rejected"] = True
            await resp.release()
            return rec
        if resp.status != 200:
            rec["error"] = f"http {resp.status}"
            await resp.release()
            return rec
        if stream:
            async for _line in resp.content:
                pass
            rec["tokens"] = max_tokens  # temp-0 ignore_eos: runs to budget
        else:
            data = await resp.json()
            rec["tokens"] = data["usage"]["completion_tokens"]
    except Exception as e:  # noqa: BLE001 - harness boundary, recorded
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


async def _completion_ids(tc, scenario, *, input_ids, max_tokens):
    rec = {"scenario": scenario, "status": 0, "tokens": 0,
           "rejected": False, "disconnected": False, "error": None}
    try:
        resp = await tc.post("/v1/completions", json={
            "model": "tiny-loadgen", "prompt": input_ids,
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
        })
        rec["status"] = resp.status
        if resp.status == 429:
            rec["rejected"] = True
            await resp.release()
            return rec
        if resp.status != 200:
            rec["error"] = f"http {resp.status}"
            await resp.release()
            return rec
        data = await resp.json()
        rec["tokens"] = data["usage"]["completion_tokens"]
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


async def _generate(tc, scenario, *, text=None, input_ids=None, max_tokens=4,
                    json_schema=None, stream=False, disconnect_after=None,
                    ignore_eos=True):
    sp = {"max_new_tokens": max_tokens, "temperature": 0,
          "ignore_eos": ignore_eos}
    if json_schema is not None:
        sp["json_schema"] = json_schema
        sp["ignore_eos"] = False  # the grammar decides when to stop
    body = {"sampling_params": sp, "stream": stream}
    if text is not None:
        body["text"] = text
    else:
        body["input_ids"] = input_ids
    rec = {"scenario": scenario, "status": 0, "tokens": 0,
           "rejected": False, "disconnected": False, "error": None}
    try:
        resp = await tc.post("/generate", json=body)
        rec["status"] = resp.status
        if resp.status == 429:
            rec["rejected"] = True
            await resp.release()
            return rec
        if resp.status != 200:
            rec["error"] = f"http {resp.status}"
            await resp.release()
            return rec
        if stream:
            seen = 0
            async for line in resp.content:
                if not line.startswith(b"data:"):
                    continue
                seen += 1
                if disconnect_after is not None and seen >= disconnect_after:
                    # abrupt client disconnect mid-stream: close the
                    # connection with the server still generating
                    resp.close()
                    rec["disconnected"] = True
                    rec["tokens"] = seen  # lower bound; stream was cut
                    return rec
            rec["tokens"] = max_tokens
        else:
            data = await resp.json()
            rec["tokens"] = data["meta_info"]["completion_tokens"]
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


async def _tool_loop(tc, scenario, *, content):
    """Two-turn tool-call loop: ask with tools declared, then continue the
    conversation with the (parsed) assistant turn + a tool result message —
    the tool-parser path runs on both turns."""
    tools = [{
        "type": "function",
        "function": {"name": "lookup", "description": "lookup a word",
                     "parameters": {"type": "object", "properties": {
                         "q": {"type": "string"}}}},
    }]
    first = await _chat(tc, scenario, content=content, max_tokens=6,
                        tools=tools)
    if first["error"] or first["rejected"]:
        return first
    follow = await _chat(
        tc, scenario, content=None, max_tokens=4, tools=tools,
        messages=[
            {"role": "user", "content": content},
            {"role": "assistant", "content": "w12 w13"},
            {"role": "tool", "content": "w99 w98"},
        ],
    )
    follow["tokens"] += first["tokens"]
    return follow


# ---- the matrix ----


def build_matrix(cfg: LoadgenConfig, tc) -> list:
    """[(arrival_offset_s, scenario, coroutine_factory)] — the full seeded
    schedule, built before the clock starts so arrivals are open-loop."""
    rng = random.Random(cfg.seed)
    n = lambda base: max(1, round(base * cfg.scale))  # noqa: E731
    vocab = 512
    entries: list = []

    def poisson_offsets(count, rate):
        t, out = 0.0, []
        for _ in range(count):
            t += rng.expovariate(rate)
            out.append(t)
        return out

    def bursty_offsets(count, burst=3, gap=0.35):
        out, t = [], 0.0
        while len(out) < count:
            out.extend([t] * min(burst, count - len(out)))
            t += gap
        return out

    if "short_chat" in cfg.scenarios:
        count = n(8)
        offs = (bursty_offsets(count) if cfg.arrival in ("poisson", "bursty")
                else poisson_offsets(count, cfg.rate_rps))
        for i, off in enumerate(offs):
            content = " ".join(f"w{rng.randrange(2, vocab)}" for _ in range(6))
            stream = i % 3 == 0
            entries.append((off, "short_chat", lambda c=content, s=stream:
                            _chat(tc, "short_chat", content=c, max_tokens=6,
                                  stream=s)))

    if "long_prefill" in cfg.scenarios:
        for off in poisson_offsets(n(4), cfg.rate_rps / 4):
            ids = [rng.randrange(2, vocab) for _ in range(rng.choice((80, 96, 112)))]
            entries.append((off, "long_prefill", lambda x=ids:
                            _completion_ids(tc, "long_prefill", input_ids=x,
                                            max_tokens=4)))

    if "json_mode" in cfg.scenarios:
        for off in poisson_offsets(n(4), cfg.rate_rps / 3):
            text = " ".join(f"w{rng.randrange(2, vocab)}" for _ in range(5))
            entries.append((off, "json_mode", lambda t=text:
                            _generate(tc, "json_mode", text=t, max_tokens=6,
                                      json_schema="{}")))

    if "tool_loop" in cfg.scenarios:
        for off in poisson_offsets(n(3), cfg.rate_rps / 3):
            content = " ".join(f"w{rng.randrange(2, vocab)}" for _ in range(5))
            entries.append((off, "tool_loop", lambda c=content:
                            _tool_loop(tc, "tool_loop", content=c)))

    if "stream_disconnect" in cfg.scenarios:
        # the generation must outlive the client's close by a wide margin or
        # a fast engine streams to completion into the socket buffer before
        # the disconnect ever lands (max_tokens >> disconnect_after)
        disc_budget = cfg.max_seq_len - 32
        for i, off in enumerate(poisson_offsets(n(4), cfg.rate_rps / 3)):
            ids = [rng.randrange(2, vocab) for _ in range(12)]
            entries.append((off, "stream_disconnect", lambda x=ids, k=2 + i % 3:
                            _generate(tc, "stream_disconnect", input_ids=x,
                                      max_tokens=disc_budget, stream=True,
                                      disconnect_after=k)))

    if "zipf_session" in cfg.scenarios:
        trace = _zipf_trace(
            rng, n_requests=n(12), n_users=max(3, n(4)),
            system_tokens=32, turn_tokens=13, vocab_size=vocab,
            max_prompt=cfg.max_seq_len - 48,
        )
        # session turns must keep their order for prefix reuse to exist:
        # offsets are sorted within the scenario
        offs = sorted(poisson_offsets(len(trace), cfg.rate_rps / 2))
        for off, ids in zip(offs, trace):
            entries.append((off, "zipf_session", lambda x=ids:
                            _completion_ids(tc, "zipf_session", input_ids=x,
                                            max_tokens=2)))

    if "tp_worker" in cfg.scenarios:
        # medium decode runs with shared prefixes: the cache-aware policy
        # concentrates them, so some land on the TP worker (w0) — asserted
        # via its loads()["mesh"] + nonzero decode counters in the epilogue
        base = [rng.randrange(2, vocab) for _ in range(24)]
        for off in poisson_offsets(n(6), cfg.rate_rps / 3):
            ids = base + [rng.randrange(2, vocab) for _ in range(8)]
            entries.append((off, "tp_worker", lambda x=ids:
                            _completion_ids(tc, "tp_worker", input_ids=x,
                                            max_tokens=8)))

    entries.sort(key=lambda e: e[0])
    return entries


async def _dispatch_open_loop(entries) -> list[dict]:
    """Open-loop execution: every request launches at its scheduled offset
    regardless of how many are still in flight (arrivals never backpressure
    on completions — that is the whole point of an open-loop generator)."""
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    tasks = []
    for off, _scenario, factory in entries:
        delay = t0 + off - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(factory()))
    return await asyncio.gather(*tasks)


# ---- the harness ----


async def _run_async(cfg: LoadgenConfig) -> dict:
    from aiohttp.test_utils import TestClient, TestServer

    from smg_tpu.gateway.router import RouterConfig
    from smg_tpu.gateway.server import AppContext, build_app
    from smg_tpu.gateway.worker_client import InProcWorkerClient
    from smg_tpu.gateway.workers import Worker
    from smg_tpu.tokenizer import MockTokenizer

    engines = [build_engine(cfg, i) for i in range(cfg.workers)]
    _warm_engines(engines)

    ctx = AppContext(
        policy="cache_aware",
        policy_kwargs={"page_size": cfg.page_size, "match_threshold": 0.05},
        router_config=RouterConfig(
            request_timeout_secs=cfg.request_timeout_secs
        ),
        request_timeout_secs=cfg.request_timeout_secs,
        slo_specs=cfg.slo_specs if cfg.slo_specs is not None else DEFAULT_SLO_SPECS,
    )
    ctx.tokenizers.register(cfg.model_id, MockTokenizer(), default=True)
    for i, eng in enumerate(engines):
        ctx.registry.add(Worker(
            worker_id=f"w{i}", client=InProcWorkerClient(eng),
            model_id=cfg.model_id, page_size=cfg.page_size,
        ))

    tc = TestClient(TestServer(build_app(ctx)))
    await tc.start_server()

    checks: dict[str, dict] = {}
    results: dict = {"config": {
        "seed": cfg.seed, "workers": cfg.workers, "scale": cfg.scale,
        "scenarios": list(cfg.scenarios), "arrival": cfg.arrival,
    }}

    def check(name: str, ok: bool, **detail) -> None:
        checks[name] = {"ok": bool(ok), **detail}

    try:
        # ---- phase 1: the mixed matrix, open loop ----
        entries = build_matrix(cfg, tc)
        records = await _dispatch_open_loop(entries)

        per_scenario: dict[str, dict] = {}
        for rec in records:
            s = per_scenario.setdefault(rec["scenario"], {
                "requests": 0, "completed": 0, "output_tokens": 0,
                "rejected": 0, "disconnected": 0, "errors": 0,
            })
            s["requests"] += 1
            if rec["error"]:
                s["errors"] += 1
            elif rec["rejected"]:
                s["rejected"] += 1
            elif rec["disconnected"]:
                s["disconnected"] += 1
                s["output_tokens"] += rec["tokens"]
            else:
                s["completed"] += 1
                s["output_tokens"] += rec["tokens"]
        results["scenarios"] = per_scenario

        total = sum(s["requests"] for s in per_scenario.values())
        errors = sum(s["errors"] for s in per_scenario.values())
        rejected = sum(s["rejected"] for s in per_scenario.values())
        disconnects = sum(s["disconnected"] for s in per_scenario.values())
        check("matrix_complete",
              errors == 0 and rejected <= max(1, int(0.1 * total)),
              requests=total, errors=errors, rejected=rejected,
              disconnected=disconnects)

        if "tp_worker" in cfg.scenarios:
            # the TP leg: worker 0 must actually be sharded (unless devices
            # were short — then build_engine already reported the skip) and
            # must have served decode traffic through the shared gateway
            mesh = engines[0].loads(include_audit=False)["mesh"]
            w0_decode = engines[0].scheduler.num_decode_tokens
            results["tp_worker"] = {"mesh": mesh, "decode_tokens": w0_decode}
            if engines[0].runner.mesh is not None:
                check("tp_worker_sharded",
                      mesh["devices"] == cfg.tp_mesh and w0_decode > 0,
                      mesh=mesh, decode_tokens=w0_decode)

        # give voluntary-abort bookkeeping a moment to settle before judging
        await asyncio.sleep(0.3)

        # ---- phase 2: SLO verdicts + /debug/slo contract ----
        r = await tc.get("/debug/slo/verdicts")
        verdicts = await r.json()
        results["verdicts"] = verdicts
        check("slo_verdicts_pass",
              r.status == 200 and verdicts["specs"] >= 1 and verdicts["all_pass"],
              verdicts=[(v["slo"], v["verdict"]) for v in verdicts["verdicts"]],
              breaches={
                  v["slo"]: {w: {
                      "breaches": win["breaches"],
                      "burn_rate": win["burn_rate"],
                      "ttft_p95_s": win["ttft_p95_s"],
                      "itl_p95_s": win["itl_p95_s"],
                      "e2e_p95_s": win["e2e_p95_s"],
                      "goodput_ratio": win["goodput_ratio"],
                      "miss_fraction": win["miss_fraction"],
                  } for w, win in v["windows"].items() if win["violating"]}
                  for v in verdicts["verdicts"] if v["verdict"] != "pass"
              })

        # ?recent=256 returns the WHOLE ring: the voluntary count below must
        # tile against full-ring counters, not the default last-32 slice
        r = await tc.get("/debug/slo", params={"recent": "256"})
        slo = await r.json()
        results["slo_summary"] = {k: slo[k] for k in
                                  ("window_requests", "deadline", "goodput",
                                   "finish_reasons")}
        floor = next((s.get("goodput_ratio_floor") for s in
                      (cfg.slo_specs or DEFAULT_SLO_SPECS)
                      if isinstance(s, dict) and s.get("goodput_ratio_floor")),
                     0.5)
        check("goodput_above_floor", slo["goodput"]["ratio"] >= floor,
              ratio=slo["goodput"]["ratio"], floor=floor)
        # disconnect exclusion (PR 6 semantics): voluntary endings appear in
        # the ring but NEVER as deadline met/missed — every non-voluntary
        # record carries the global deadline, so the counts must tile
        voluntary = sum(1 for rec in slo["recent"] if rec["voluntary"])
        check("disconnects_excluded_from_deadline",
              disconnects > 0 and voluntary >= disconnects
              and slo["deadline"]["with_deadline"]
              == slo["window_requests"] - voluntary
              and slo["deadline"]["missed"] <= rejected,
              voluntary_records=voluntary, client_disconnects=disconnects,
              deadline=slo["deadline"])

        # ---- phase 3: routing observability in band ----
        r = await tc.get("/debug/router")
        router_dbg = await r.json()
        recon = router_dbg.get("reconciliation", {})
        count = sum(v.get("count", 0) for v in recon.values())
        abs_err = sum(v.get("abs_error_sum", 0.0) for v in recon.values())
        mean_err = abs_err / count if count else float("inf")
        loads = {}
        for w in ctx.registry.list():
            loads[w.worker_id] = await w.client.get_loads()
        cached = sum(l.get("cached_prompt_tokens", 0) for l in loads.values())
        computed = sum(l.get("computed_prompt_tokens", 0) for l in loads.values())
        hit_rate = cached / (cached + computed) if (cached + computed) else 0.0
        results["router"] = {
            "reconciled": count,
            "mean_abs_prediction_error_tokens": round(mean_err, 2),
            "prefix_hit_rate": round(hit_rate, 4),
        }
        check("router_prediction_in_band",
              count > 0 and mean_err <= cfg.prediction_error_band_tokens,
              **results["router"])
        check("prefix_reuse_observed", cached > 0, cached_prompt_tokens=cached)

        if cfg.probes:
            # ---- phase 4: injected SLO violation window -> verdict fail ->
            # flight-recorder dump fetched for every worker ----
            ctx.metrics.slo_enforcer.install([{
                "name": "injected_tight_ttft", "ttft_p95_s": 1e-9,
                "fast_window_s": 120.0, "slow_window_s": 600.0,
                "min_requests": 1, "hysteresis": 1,
            }])
            r = await tc.get("/debug/slo/verdicts")
            vio = await r.json()
            injected = next(v for v in vio["verdicts"]
                            if v["slo"] == "injected_tight_ttft")
            dumps = 0
            for w in ctx.registry.list():
                fr = await tc.get(f"/debug/flight/{w.worker_id}",
                                  params={"reason": "slo_violation"})
                body = await fr.json()
                if fr.status == 200 and "schema_version" in body["dump"]:
                    dumps += 1
            ctx.metrics.slo_enforcer.remove("injected_tight_ttft")
            results["violation_probe"] = {
                "verdict": injected["verdict"],
                "breaches": injected["windows"]["fast"]["breaches"],
                "flight_dumps_fetched": dumps,
            }
            check("violation_window_dumps",
                  injected["verdict"] == "fail" and dumps == cfg.workers,
                  **results["violation_probe"])

            # ---- phase 5: saturation burst -> 429s without breaker penalty ----
            # sized to outrun drainage: total in-system capacity is
            # workers * (max_batch + max_queued) lanes, the burst is ~3x
            # that, and each lane holds its slot for a 24-token decode
            burst_n = 3 * cfg.workers * (cfg.max_batch_size
                                         + cfg.max_queued_requests)
            burst = await asyncio.gather(*(
                _generate(tc, "burst", input_ids=[2 + (i % 60), 3, 4, 5],
                          max_tokens=24)
                for i in range(burst_n)
            ))
            n429 = sum(1 for b in burst if b["rejected"])
            nerr = sum(1 for b in burst if b["error"])
            breakers = {w.worker_id: w.circuit.state.value
                        for w in ctx.registry.list()}
            results["backpressure"] = {
                "burst": burst_n, "rejected_429": n429, "errors": nerr,
                "breakers": breakers,
            }
            check("backpressure_429_no_breaker_penalty",
                  n429 > 0 and nerr == 0
                  and all(s == "closed" for s in breakers.values()),
                  **results["backpressure"])

            # ---- phase 6: drain-under-load ----
            streams = [asyncio.create_task(
                _generate(tc, "drain_stream", input_ids=[7 + i, 8, 9],
                          max_tokens=24, stream=True))
                for i in range(3 * cfg.workers)]
            await asyncio.sleep(0.25)
            busiest = max(ctx.registry.list(), key=lambda w: w.load)
            victim_id = busiest.worker_id
            dr = await tc.delete(f"/workers/{victim_id}",
                                 params={"drain": "20"})
            drain_body = await dr.json()
            stream_recs = await asyncio.gather(*streams)
            stream_errors = sum(1 for s in stream_recs
                                if s["error"] or s["rejected"])
            wl = await tc.get("/workers")
            remaining = [w["worker_id"] for w in (await wl.json())["workers"]]
            results["drain"] = {
                "victim": victim_id, "status": dr.status,
                "drained": drain_body.get("drained"),
                "streams": len(stream_recs), "stream_errors": stream_errors,
                "remaining_workers": remaining,
            }
            check("drain_under_load",
                  dr.status == 200 and stream_errors == 0
                  and victim_id not in remaining,
                  **results["drain"])

        # ---- phase 7: zero-leak quiescence audit on EVERY engine ----
        audits = {}
        deadline = time.monotonic() + 15.0
        while True:
            audits = {f"w{i}": eng.audit() for i, eng in enumerate(engines)}
            if all(a["quiescent"] and a["clean"] for a in audits.values()):
                break
            if time.monotonic() > deadline:
                break
            await asyncio.sleep(0.1)
        # the registered workers also answer through the public surface
        surf = await tc.get("/scheduler")
        surf_body = await surf.json()
        surfaced = {
            wid: loads.get("audit", {}).get("clean")
            for wid, loads in surf_body.get("engine", {}).items()
        }
        results["audit"] = {"engines": audits, "surfaced_clean": surfaced}
        check("zero_leak_quiescence",
              all(a["quiescent"] and a["clean"] and a["leaked_pages"] == 0
                  and a["radix_lock_refcounts"] == 0
                  for a in audits.values())
              and all(v is True for v in surfaced.values()),
              leaked={k: a["leaked_pages"] for k, a in audits.items()},
              locks={k: a["radix_lock_refcounts"] for k, a in audits.items()},
              surfaced=surfaced)
    finally:
        await tc.close()
        for eng in engines:
            try:
                eng.stop()
            except Exception:  # noqa: BLE001 - teardown must not mask results
                pass

    results["checks"] = checks
    results["ok"] = all(c["ok"] for c in checks.values())
    return results


def run(cfg: LoadgenConfig) -> dict:
    """Synchronous entry point (the tier-1 smoke test imports this)."""
    return asyncio.run(_run_async(cfg))


def emit(results: dict) -> None:
    """One JSON line per scenario/probe — the BENCH-embeddable records."""
    for name, s in results.get("scenarios", {}).items():
        print(json.dumps({"bench": f"loadgen_{name}", **s}))
    for key in ("router", "backpressure", "drain", "violation_probe"):
        if key in results:
            print(json.dumps({"bench": f"loadgen_{key}", **results[key]}))
    if "slo_summary" in results:
        print(json.dumps({"bench": "loadgen_slo",
                          **results["slo_summary"],
                          "all_pass": results.get("verdicts", {}).get("all_pass")}))
    print(json.dumps({
        "bench": "loadgen_checks",
        "ok": results.get("ok", False),
        "failed": [k for k, c in results.get("checks", {}).items()
                   if not c["ok"]],
    }))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="request-count multiplier on the matrix")
    ap.add_argument("--scenarios", default=",".join(ALL_SCENARIOS),
                    help=f"comma list from: {', '.join(ALL_SCENARIOS)}")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty"])
    ap.add_argument("--rate-rps", type=float, default=24.0)
    ap.add_argument("--slo-spec", default=None,
                    help="JSON spec file (default: built-in smoke spec)")
    ap.add_argument("--no-probes", action="store_true",
                    help="matrix + verdicts only (skip violation/"
                         "backpressure/drain probes)")
    ap.add_argument("--out", default=None, help="write full results JSON here")
    args = ap.parse_args(argv)

    scenarios = tuple(s.strip() for s in args.scenarios.split(",") if s.strip())
    unknown = set(scenarios) - set(ALL_SCENARIOS)
    if unknown:
        ap.error(f"unknown scenario(s): {sorted(unknown)}")
    slo_specs = None
    if args.slo_spec:
        from smg_tpu.gateway.slo_enforcement import load_slo_specs

        slo_specs = [s.__dict__ for s in load_slo_specs(args.slo_spec)]
    if "tp_worker" in scenarios and "jax" not in sys.modules:
        # the TP worker needs a multi-device CPU backend; the flag must land
        # before jax initializes (no-op when the env already forces one)
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    cfg = LoadgenConfig(
        seed=args.seed, workers=args.workers, scale=args.scale,
        scenarios=scenarios, arrival=args.arrival, rate_rps=args.rate_rps,
        slo_specs=slo_specs, probes=not args.no_probes,
    )
    results = run(cfg)
    emit(results)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0 if results["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
