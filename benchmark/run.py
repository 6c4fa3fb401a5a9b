#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|2>

The process holds the cell's chips and does what ``smg-tpu serve`` does, by
the program's own code: ``gateway.launch._run_gateway`` builds the engine,
warms it, registers the tokenizer and the in-process worker client, builds
the app and binds a port.  The cell's configuration reaches it as a model
preset registered from ``benchmark/configs/<config>.json`` (``serve`` has
presets for Llama only and ``--model-path`` wants safetensors), so weights
are random, made on the device by the engine from its own seed, and the
tokenizer is the vocab-matched mock.  The load generator is a child process
(``loadgen.py``) that never imports JAX and talks HTTP to the port.

Set-up (all of it counted in ``setup_s``): import, weights, cache, every
program the cell's traffic can reach (``warm.py``), the program's own
warm-up, a slice of the cell's traffic from another seed, and the
correctness check (``reference.py``).  Then the window.  ``--trace 2`` is a
``--trace 0`` run to the moment the window has closed and its numbers are
taken; only then, in the same process and engine, the window's traffic is
replayed with its last seconds traced, for the per-layer metrics
(``trace_phase``).

The last line of standard output is the result, one JSON object.  Without a
TPU the run prints no result and exits 3; ``--rehearsal`` is the only way
onto the CPU (tiny widths, kernels interpreted, ``"rehearsal": true``).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import catalog  # noqa: E402
from client_reduce import MISSED_MS, latencies, percentile, request_ok  # noqa: E402


def log(msg: str) -> None:
    print(f"bench[{time.monotonic() - T_START:7.1f}s]: {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# client-side reduction


#: how much of the replayed window's end a ``--trace 2`` run traces: the
#: profiler's write grows with the launches it holds, and the whole run has
#: 360 s (PERF.md, Findings, PR 32)
TRACE_SECONDS = 6.0
#: ``--trace 2``: traffic goes on this long after the traced stretch, so that
#: steps and submits meet the profiler while it writes its trace
TRACE_TAIL_SECONDS = 4.0
#: what the flight recorder keeps, so that one read after the window and one
#: after the replay hold every step and finished request of each: a window of
#: ``eval`` is about 1,080 steps and 510 requests where ``serve`` keeps 256 and 64
FLIGHT_RING_STEPS = 4096
FLIGHT_TIMELINES = 2048


def end_to_end(result: dict) -> dict:
    """The end-to-end metrics of one window, from the client's records."""
    reqs = result["requests"]
    t0, t1 = result["t0"], result["t0"] + result["seconds"]
    ttft, tpot = latencies(reqs)
    # a rate is taken over all the work of the window: every token that
    # reached the client inside it, whether or not its request ended there
    tokens_in_window = sum(r["tokens_in_window"] for r in reqs)
    tokens_completed = sum(r["output_tokens"] for r in reqs
                           if request_ok(r) and r["done"] <= t1)
    failed = sum(1 for r in reqs if not request_ok(r))
    late = [(r["sent"] - r["due"]) * 1e3 for r in reqs if r["sent"] is not None]
    good_ttft = [v for v in ttft if v is not None]
    good_tpot = [v for v in tpot if v is not None]
    return {
        "attempted": len(reqs), "failed": failed,
        "metrics": {
            "ttft_p95_ms": min(percentile(ttft, 0.95), MISSED_MS),
            "tpot_p95_ms": min(percentile(tpot, 0.95), MISSED_MS),
            "output_tok_per_s": tokens_in_window / (t1 - t0),
        },
        "detail": {
            "ttft_p50_ms": statistics.median(good_ttft) if good_ttft else None,
            "tpot_p50_ms": statistics.median(good_tpot) if good_tpot else None,
            "samples": len(good_ttft),
            "completed_request_tok_per_s": tokens_completed / (t1 - t0),
            "generator_late_p50_ms": statistics.median(late) if late else None,
            "generator_late_max_ms": max(late) if late else None,
            "prompt_tokens": sum(r["prompt_tokens"] for r in reqs),
            "cached_tokens": sum(r["cached_tokens"] for r in reqs),
            "output_tokens": sum(r["output_tokens"] for r in reqs),
            "backlog_mid": backlog(reqs, t0 + result["seconds"] / 2),
            "backlog_end": backlog(reqs, t1),
            # a chain that has sent its whole pool starts over with other words
            # (loadgen.run_chain): how many did, so a ceiling shows before it binds
            "chains_started_over": len({r["chain"] for r in reqs if r.get("lap", 0) > 0}),
            "live_kv_tokens_peak": live_tokens_peak(reqs),
            "errors": sorted({r["error"] or f"finish={r['finish']}" for r in reqs
                              if not request_ok(r)})[:5],
        },
    }


def backlog(reqs: list, t: float) -> int:
    """Requests due and unfinished at time ``t``."""
    return sum(1 for r in reqs if r["due"] <= t and (r["done"] is None or r["done"] > t))


def live_tokens_peak(reqs: list) -> int:
    """The most tokens the requests in flight held in the KV cache at once
    (prompt plus the output streamed so far), read at every send: what the
    traffic fills of the pool that ``memory_peak_bytes`` reserves."""
    def held(r, t):
        if r["sent"] is None or r["done"] is None or not r["sent"] <= t < r["done"]:
            return 0
        if r["first"] is None or t < r["first"]:
            return r["prompt_tokens"]
        part = min((t - r["first"]) / max(r["last"] - r["first"], 1e-9), 1.0)
        return r["prompt_tokens"] + int(part * r["output_tokens"])

    return max((sum(held(r, q["sent"]) for r in reqs) for q in reqs
                if q["sent"] is not None), default=0)


# --------------------------------------------------------------------------
# the server side


class CompileWatch:
    """Counts XLA compilations (``jax.monitoring``) and keeps the names JAX
    logs for them, with the time of each, so that a run can say what
    compiled inside its window."""

    def __init__(self):
        import logging

        import jax.monitoring

        self.count = 0
        self.names: list = []  # (monotonic, message)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        watch = self

        class Handler(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling"):
                    watch.names.append((time.monotonic(), msg[:160]))

        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            lg = logging.getLogger(name)
            lg.setLevel(logging.DEBUG)
            lg.addHandler(Handler())
            lg.propagate = False

    def _on_event(self, name: str, *_a, **_kw) -> None:
        if "backend_compile" in name:
            self.count += 1

    def since(self, t: float) -> list:
        return [msg for at, msg in self.names if at >= t]


class PauseWatch:
    """The interpreter's garbage collections in this process, timed
    (``gc.callbacks``): how many of each generation a span of time held and
    the longest with its generation.  A run that reads some percent low has
    every lane stalled for seconds at one point of its window (PERF.md,
    Findings, PR 32); a full collection over a heap that holds every traced
    program is the first suspect, and this is its witness on the detail line."""

    def __init__(self):
        import gc

        self.pauses: list = []  # (start, seconds, generation)
        self._start = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._start = now
        else:
            self.pauses.append((self._start, now - self._start, info["generation"]))

    def between(self, lo: float, hi: float) -> dict:
        inside = [p for p in self.pauses if lo <= p[0] <= hi]
        worst = max(inside, key=lambda p: p[1], default=None)
        return {"collections": [sum(1 for p in inside if p[2] == g) for g in (0, 1, 2)],
                "total_s": sum(p[1] for p in inside),
                "longest": worst and {"at_s": worst[0] - lo, "seconds": worst[1],
                                      "generation": worst[2]}}


def longest_steps(steps: list, lo: float, hi: float, n: int = 3) -> list:
    """The ``n`` longest steps the flight recorder stamped between ``lo`` and
    ``hi``: when (the stamp is the step's end), how long, how much of it the
    step waited for the device, and what it did.  A stall shows here as one
    step of seconds, and ``fetch_wait_s`` says on which side of the fetch."""
    inside = sorted((s for s in steps if lo <= s["t"] <= hi), key=lambda s: -s["step_s"])[:n]
    return [{"at_s": s["t"] - lo, "step_s": s["step_s"], "fetch_wait_s": s.get("fetch_wait_s"),
             "kind": s["kind"], "prefill_tokens": s.get("prefill_tokens")} for s in inside]


class Probe:
    """What a traced run reads from inside the server process: the engine's
    submit and first-output stamps, the flight recorder's step ring and
    timelines, and host spans for the profiler."""

    def __init__(self, engine):
        self.engine = engine
        self.stamps: dict = {}  # rid -> [submit_t, first_output_t]
        self.steps: dict = {}  # serial -> step record
        self.timelines: dict = {}  # rid -> finished timeline
        self.lost_steps = 0
        self._last_serial = None

    def install(self) -> None:
        import jax.profiler

        engine, stamps = self.engine, self.stamps
        submit, step = engine.submit, engine.step

        def stamped_submit(input_ids, sampling, rid=None, on_output=None, **kw):
            rec = stamps.setdefault(rid, [time.monotonic(), None])

            def first_stamped(out):
                if rec[1] is None and out.new_token_ids:
                    rec[1] = time.monotonic()
                return on_output(out)

            with jax.profiler.TraceAnnotation("bench.engine_submit"):
                return submit(input_ids, sampling, rid=rid,
                              on_output=first_stamped if on_output else None, **kw)

        def spanned_step(*a, **kw):
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                return step(*a, **kw)

        engine.submit, engine.step = stamped_submit, spanned_step

    def poll(self) -> None:
        flight = self.engine.scheduler.flight
        if flight is None:
            return
        snap = flight.snapshot("bench")
        ring = snap["ring"]
        if ring and self._last_serial is not None and ring[0]["serial"] > self._last_serial + 1:
            self.lost_steps += ring[0]["serial"] - self._last_serial - 1
        for rec in ring:
            self.steps[rec["serial"]] = rec
        if ring:
            self._last_serial = ring[-1]["serial"]
        for tl in snap["timelines"]["finished"]:
            tl.pop("events", None)
            self.timelines[tl["rid"]] = tl


#: ``loads()`` counters whose rise over a window goes on the detail line
COUNTERS = ("radix_evicted_pages", "preemptions", "radix_hit_pages", "radix_miss_pages",
            "lookahead_kept", "lookahead_discarded", "wasted_decode_tokens")


async def run_loadgen(plan: dict, out_dir: str, tag: str) -> dict:
    plan_path = os.path.join(out_dir, f"{tag}.plan.json")
    res_path = os.path.join(out_dir, f"{tag}.result.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    if os.path.exists(res_path):
        os.remove(res_path)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "loadgen.py"), plan_path, res_path, env=env)
    try:
        rc = await proc.wait()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    if rc != 0 or not os.path.exists(res_path):
        raise RuntimeError(f"load generator ({tag}) exited {rc}")
    with open(res_path) as f:
        result = json.load(f)
    os.remove(plan_path)
    os.remove(res_path)
    return result


async def wait_quiet(engine, timeout: float = 60.0) -> dict:
    deadline = time.monotonic() + timeout
    while True:
        loads = await asyncio.to_thread(engine.loads)
        if loads["audit"]["quiescent"] or time.monotonic() > deadline:
            return loads
        await asyncio.sleep(0.1)


def keep_timelines(engine, n: int) -> None:
    """Has the flight recorder keep the last ``n`` finished timelines.
    ``serve`` has a flag for the step ring (``--flight-ring-size``) and none
    yet for ``EngineConfig.flight_timeline_keep`` (PERF.md, Open questions), so
    the harness widens the recorder's own deque, before any request exists.  A
    recorder without one keeps what it keeps: ``rests_on.window_timelines`` on
    the detail line says how many were read."""
    flight = engine.scheduler.flight
    kept = getattr(flight, "_finished", None)
    if isinstance(kept, deque) and (kept.maxlen or 0) < n:
        flight._finished = deque(kept, maxlen=n)


def memory_peak(engine) -> int:
    peak = 0
    for d in engine.runner.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))))
    return peak


def program_keys(loads: dict) -> set:
    return {p["key"] for p in loads["programs"]["programs"]}


async def trace_phase(args, cell: catalog.Cell, engine, probe: Probe, base: dict,
                      out_dir: str, watch: CompileWatch, trace_dir: str, *,
                      closed_window: dict) -> dict:
    """The second half of a ``--trace 2`` run.  ``closed_window`` is the
    measured window's result: it and the numbers taken from it exist before
    anything here starts, and nothing here touches them.

    In order: one read of the flight recorder (its ring and finished timelines
    still hold the window, which no probe watched); one start and stop of the
    profiler whose trace is thrown away, so that the cost of the first start
    falls into no number; the probe; a second load generator that replays the
    window's own chains with other words (the window's prompts are in the
    radix cache); the profiler through the engine's own ``start_profile``
    over the replay's last ``TRACE_SECONDS``; the stop, with traffic still
    running, and how long steps and submits stalled meanwhile; the drain; the
    second read of the recorder (its ring holds the replay: nothing polls it
    while the trace is on).

    The replay is as long as the window because the arrangement of the
    requests is part of the work: traced right after the callers' ramp, the
    batch held young requests behind narrow page tables and a decode column
    took half the time it takes at the window's end (PERF.md, Findings, PR 26)."""
    import shutil

    await asyncio.to_thread(probe.poll)
    window_steps = sorted(probe.steps.values(), key=lambda r: r["serial"])
    window_timelines = list(probe.timelines.values())

    scrap = os.path.join(out_dir, "trace_scrap")
    await asyncio.to_thread(engine.start_profile, scrap)
    await asyncio.to_thread(engine.stop_profile)
    shutil.rmtree(scrap, ignore_errors=True)
    shutil.rmtree(trace_dir, ignore_errors=True)

    probe.install()
    loads0 = await wait_quiet(engine)
    compiles0 = watch.count
    traced = min(TRACE_SECONDS, 0.3 * args.seconds)
    lead = float(args.seconds) - traced
    seconds = float(args.seconds) + TRACE_TAIL_SECONDS
    t0 = time.monotonic() + 0.3
    plan = {**base, "tag": "t", "seconds": seconds, "t0": t0,
            "drain_s": min(base["drain_s"], 30.0),
            "chains": cell.chains(args.seed ^ 0x7ACE, seconds)}
    load_task = asyncio.create_task(run_loadgen(plan, out_dir, "trace"))
    await asyncio.sleep(max(t0 + lead - time.monotonic(), 0))
    ts = time.monotonic()
    await asyncio.to_thread(engine.start_profile, trace_dir)
    started = time.monotonic()
    await asyncio.sleep(traced)
    stopping = time.monotonic()
    await asyncio.to_thread(engine.stop_profile)
    te = time.monotonic()
    result = await load_task
    loads1 = await wait_quiet(engine)
    await asyncio.to_thread(probe.poll)
    reqs = result["requests"]
    steps = sorted(probe.steps.values(), key=lambda r: r["serial"])
    timelines = list(probe.timelines.values())

    def gaps(lo, hi):  # between the stamps of consecutive steps, in seconds
        ts_ = [s["t"] for s in steps if lo <= s["t"] <= hi]
        return [b - a for a, b in zip(ts_, ts_[1:])]

    def lock_waits(lo, hi):
        return [tl["queued_t"] - tl["submit_t"] for tl in timelines
                if lo <= tl["submit_t"] <= hi]

    def engine_tok_per_s(lo, hi, recs, tls):
        """Tokens the engine accepted between ``lo`` and ``hi``: the decode
        tokens of the step records stamped there and one first token for
        every timeline whose first token fell there, a second."""
        n = sum(s["decode_tokens"] for s in recs if lo <= s["t"] <= hi)
        n += sum(1 for tl in tls if tl["first_token_t"] is not None
                 and lo <= tl["first_token_t"] <= hi)
        return n / (hi - lo)

    in_trace = [s for s in steps if ts <= s["t"] <= stopping]  # the readers' trace_window
    w1 = closed_window["t0"] + closed_window["seconds"]
    w0 = w1 - (stopping - started)  # the stretch of the window that the trace replays
    return {
        "window_steps": window_steps, "window_timelines": window_timelines,
        "attempted": len(reqs), "failed": sum(1 for r in reqs if not request_ok(r)),
        "compiles": watch.count - compiles0,
        "new_programs": sorted(program_keys(loads1) - program_keys(loads0)),
        "recompiles": loads1["programs"]["recompiles"] - loads0["programs"]["recompiles"],
        "counters": {k: loads1[k] - loads0[k] for k in COUNTERS},
        "decode_launches": {r: n - loads0["decode_launches"][r]
                            for r, n in loads1["decode_launches"].items()},
        "traced_steps": {
            "records": len(in_trace),
            # columns asked; main() puts ``columns_run_sum`` beside it from the trace
            "horizon_sum": sum(s["horizon"] for s in in_trace),
            "decode_tokens": sum(s["decode_tokens"] for s in in_trace),
            "early_exits": sum(s["early_exits"] for s in in_trace)},
        "seconds": {"lead": lead, "start_profile": started - ts,
                    "traced": stopping - started, "stop_profile": te - stopping},
        # what tracing costs while it is on (the same stretch of the same
        # arrangement, untraced in the window and traced in the replay), and
        # what the stop holds up
        "engine_tok_per_s": {
            "window_same_stretch": engine_tok_per_s(w0, w1, window_steps, window_timelines),
            "traced": engine_tok_per_s(started, stopping, steps, timelines)},
        "step_gap_s": {"traced_max": max(gaps(started, stopping), default=None),
                       "stop_profile_max": max(gaps(stopping, te), default=None)},
        "submit_lock_wait_s": {"traced_max": max(lock_waits(started, stopping), default=None),
                               "stop_profile_max": max(lock_waits(stopping, te), default=None)},
        "window_longest_steps": longest_steps(window_steps, closed_window["t0"], w1),
        "rests_on": {"window_requests": len(closed_window["requests"]),
                     "window_steps": len(window_steps),
                     "window_ring_reaches_back": bool(
                         window_steps and window_steps[0]["t"] <= closed_window["t0"]),
                     "window_timelines": len(window_timelines),
                     "trace_phase_requests": len(reqs), "steps_lost": probe.lost_steps},
        "ctx": {
            # steps go on while the profiler writes: the traced window ends
            # where the stop was asked for, not where it returned
            "requests": reqs, "window": (result["t0"], ts), "trace_window": (ts, stopping),
            "loads_before": loads0, "loads_after": loads1, "stamps": probe.stamps,
            "steps": steps, "timelines": timelines, "lost_steps": probe.lost_steps,
        },
    }


async def serve_and_measure(args, bench: dict, cell: catalog.Cell, out_dir: str) -> dict:
    import jax

    import reference
    import warm
    from smg_tpu.cli import build_parser
    from smg_tpu.config.validation import raise_on_errors, validate_cli_args
    from smg_tpu.gateway import launch
    from smg_tpu.models.config import PRESETS, ModelConfig
    from smg_tpu.utils import get_logger
    from smg_tpu.utils.logging import configure

    marks = {"imports": time.monotonic() - T_START}
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    want = "cpu" if args.rehearsal else "tpu"
    if device["platform"] != want or len(devs) < cell.chips:
        print(f"bench: the cell needs {cell.chips} {want} device(s); JAX found {device}. "
              "No result.", file=sys.stderr)
        raise SystemExit(3)
    if not args.rehearsal:
        import peaks

        peaks.peaks_for(device["kind"])  # a device without published peaks is an error

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    preset = cell.entry["config"]
    argv = ["serve", "--model-preset", preset, *cell.serve_args,
            "--flight-ring-size", str(FLIGHT_RING_STEPS),
            "--host", "127.0.0.1", "--port", str(port)]
    sargs = build_parser().parse_args(argv)
    configure(level=sargs.log_level, json_logs=None)
    raise_on_errors(validate_cli_args(sargs), logger=get_logger("config"))
    hf = cell.hf_config
    PRESETS[preset] = lambda: ModelConfig.from_hf_config(hf, dtype=sargs.dtype)

    holder: dict = {}
    build = launch.build_engine_from_args

    def build_and_keep(a):
        t = time.monotonic()
        engine = build(a)
        keep_timelines(engine, FLIGHT_TIMELINES)
        marks["weights_and_cache"] = time.monotonic() - t
        t = time.monotonic()
        holder["warmed"] = warm.warm_shapes(
            engine, cell.chains(args.seed, float(args.seconds)), log)
        marks["warm_shapes"] = time.monotonic() - t
        holder["engine"] = engine
        holder["t_built"] = time.monotonic()
        return engine

    launch.build_engine_from_args = build_and_keep
    watch = CompileWatch()
    pauses = PauseWatch()
    gateway = asyncio.create_task(launch._run_gateway(sargs))
    try:
        # _run_gateway blocks the loop while it builds and warms the engine
        while True:
            await asyncio.sleep(0.05)
            if gateway.done():
                raise RuntimeError(f"the gateway ended during start-up: {gateway.result()}")
            try:
                _r, w = await asyncio.open_connection("127.0.0.1", port)
                w.close()
                break
            except OSError:
                continue
        engine = holder["engine"]
        marks["program_warmup"] = time.monotonic() - holder["t_built"]
        log(f"listening on {port}; device {device}; mesh {engine.runner.mesh_info()}")
        sched = engine.config.scheduler
        vocab = engine.config.model.vocab_size
        base = {"url": f"http://127.0.0.1:{port}", "vocab": vocab,
                "drain_s": float(cell.traffic.get("drain_s", 60))}

        # a slice of the cell's own traffic from another seed: the gateway,
        # the tokenizer, the uploads; and the proof that warm.py missed nothing
        t = time.monotonic()
        before_slice = program_keys(await wait_quiet(engine))
        slice_s = min(float(args.seconds), 3.0)
        res = await run_loadgen(
            {**base, "tag": "slice", "seconds": slice_s,
             "chains": cell.chains(args.seed ^ 0x5EED5, slice_s)}, out_dir, "slice")
        loads = await wait_quiet(engine)
        grew = sorted(program_keys(loads) - before_slice)
        if grew:
            log(f"the traffic slice reached {len(grew)} program(s) the warm-up had "
                f"not: {grew}")
        holder["slice_grew"] = grew
        log(f"slice: {len(res['requests'])} requests, "
            f"{sum(1 for r in res['requests'] if not request_ok(r))} failed")
        # no flush_cache here: on a chip whose cache was auto-sized it allocates
        # the new buffers before it frees the old and dies (PERF.md, Open
        # questions).  The slice's prompts come from another seed, so the
        # pages it leaves behind match nothing in the window and are evictable.
        marks["traffic_slice"] = time.monotonic() - t

        t = time.monotonic()
        check = await asyncio.to_thread(
            reference.check_engine, engine, cell, args.seed, args.rehearsal)
        marks["correctness_check"] = time.monotonic() - t
        log(f"logits vs float32 reference: {json.dumps(check)}")

        probe = Probe(engine)
        trace_dir = os.path.join(out_dir, "trace")
        loads0 = await wait_quiet(engine)
        compiles0 = watch.count

        # ---- the window ----
        t0 = time.monotonic() + 0.3
        setup_s = t0 - T_START
        plan = {**base, "tag": "w", "seconds": float(args.seconds), "t0": t0,
                "chains": cell.chains(args.seed, float(args.seconds))}
        load_task = asyncio.create_task(run_loadgen(plan, out_dir, "window"))
        result = await load_task
        loads1 = await wait_quiet(engine)
        compiles1 = watch.count
        peak = memory_peak(engine)
        e2e = end_to_end(result)
        phase = None
        if args.trace == 2:
            # the window is closed and its numbers are taken: from here on
            # nothing can move them
            phase = await trace_phase(args, cell, engine, probe, base, out_dir, watch,
                                      trace_dir, closed_window=result)
            peak = max(peak, memory_peak(engine))
    finally:
        if not gateway.done():
            os.kill(os.getpid(), signal.SIGTERM)  # the program's own way down
            try:
                await asyncio.wait_for(gateway, 60)
            except asyncio.TimeoutError:
                gateway.cancel()
        launch.build_engine_from_args = build

    new_programs = sorted(program_keys(loads1) - program_keys(loads0))
    verdict = {
        "logits": check["ok"],
        "requests": e2e["failed"] == 0 and e2e["attempted"] > 0,
        "step_failures": loads1["step_failures"] == loads0["step_failures"],
        "quarantined": loads1["quarantined_requests"] == loads0["quarantined_requests"],
        "audit_clean": bool(loads1["audit"]["quiescent"] and loads1["audit"]["clean"]),
        "no_compile_in_window": (not new_programs and compiles1 == compiles0
                                 and loads1["programs"]["recompiles"]
                                 == loads0["programs"]["recompiles"]),
    }
    e2e["metrics"]["setup_s"] = setup_s
    ctx = {
        "cell": cell.name, "hf": hf, "costs": cell.architecture, "chips": cell.chips,
        "device": device,
        "rehearsal": args.rehearsal, "requests": result["requests"],
        "window": (result["t0"], result["t0"] + result["seconds"]),
        "loads_before": loads0, "loads_after": loads1,
        # the window runs unwatched: no stamps, and the recorder is read after it
        "stamps": {}, "steps": [], "timelines": [], "lost_steps": 0,
        "trace_window": None, "trace": None, "kv_dtype_bytes":
            2 if engine.config.cache.dtype == "bfloat16" else 4,
    }
    if phase is not None:
        # the trace phase's context: everything of ``ctx`` that does not
        # belong to the window, and the phase's own traffic, stamps and trace
        ctx = {**ctx, "steps": phase.pop("window_steps"),
               "timelines": phase.pop("window_timelines")}
        phase["ctx"] = {**ctx, **phase["ctx"]}
    return {"e2e": e2e, "verdict": verdict, "check": check, "marks": marks, "ctx": ctx,
            "trace_phase": phase, "device": device, "memory_peak_bytes": peak, "new_programs": new_programs,
            "compiles_in_window": compiles1 - compiles0,
            "compiled_in_window": watch.since(t0), "warmed": holder["warmed"],
            "gc_in_window": pauses.between(t0, t0 + float(args.seconds)),
            "slice_grew": holder["slice_grew"], "trace_dir": trace_dir,
            "attention": loads1["attention"], "mesh": loads1["mesh"],
            "counters": {k: loads1[k] - loads0[k] for k in COUNTERS},
            "total_pages": loads1["total_pages"]}


def per_layer(bench: dict, cell: str, *ctxs: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something, in
    the first of ``ctxs`` that gives it something to read (``--trace 2``: the
    closed window's context, then the trace phase's)."""
    out = {}
    for m in catalog.metrics_for(bench, cell, "per_layer"):
        reader = catalog.layer_metric_reader(m["name"])
        if reader is None:
            continue
        value = next((v for c in ctxs if (v := reader.read(c)) is not None), None)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 2), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny widths on the CPU; proves nothing about a chip")
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"),
                    help="directory for the run's file")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=NUMBER",
                    help="override one number of the traffic file (for the rate "
                         "sweep that defines a cell; the result says so)")
    ap.add_argument("--list", action="store_true", help="list what the harness sees")
    args = ap.parse_args()
    try:
        bench = catalog.load_benchmark()
        if args.list:
            print(json.dumps(catalog.listing(), indent=1))
            return 0
        if not args.workload:
            ap.error("--workload is required")
        cell = catalog.Cell(bench, args.workload, rehearsal=args.rehearsal)
    except catalog.CatalogError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "smg_tpu")):
        print("bench: no smg_tpu package beside benchmark/: nothing to measure",
              file=sys.stderr)
        return 2
    for item in args.set:
        key, _, val = item.partition("=")
        if key not in cell.traffic or not isinstance(cell.traffic[key], (int, float)):
            ap.error(f"--set {item}: the traffic file has no number {key!r}")
        cell.traffic[key] = float(val)
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".bench_cache", "jax"))
    if args.rehearsal:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR")  # a rehearsal caches nothing
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4").strip()
    import jax

    # every program goes to the persistent cache, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    out_dir = os.path.join(args.out, args.workload, f"seed{args.seed}-trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)

    run = asyncio.run(serve_and_measure(args, bench, cell, out_dir))
    e2e, ctx = run["e2e"], run.pop("ctx")
    # the context that holds the trace is the trace phase's, after the window
    tctx = run["trace_phase"].pop("ctx") if run["trace_phase"] else ctx
    device = dict(run["device"], memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": all(run["verdict"].values()), "attempted": e2e["attempted"],
            "failed": e2e["failed"]}
    units = {m["name"]: m["unit"] for m in catalog.metrics_for(bench, cell.name, "end_to_end")}
    e2e_metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in e2e["metrics"].items() if k in units}
    report = {"workload": cell.name, "seed": args.seed, "seconds": args.seconds,
              "trace": args.trace, "overrides": args.set, **run, "traffic": cell.traffic}
    if args.trace:
        import shutil

        import trace_reduce

        path = trace_reduce.find_xplane(run["trace_dir"])
        if path is None:
            print("bench: the profiler wrote no trace", file=sys.stderr)
            return 4
        tctx["trace"] = trace = trace_reduce.load_xplane(path)
        with open(os.path.join(out_dir, "trace_cut.json"), "w") as f:
            json.dump(trace_reduce.cut(trace, 0.25), f)  # a quarter second, to look at
        b = trace_reduce.busy(trace)
        if not b["busy_s"] or b["window_s"] <= 0:
            print("bench: no operation ran on the device in the traced window",
                  file=sys.stderr)
            return 4
        device["busy_s"] = sum(b["busy_s"].values()) / len(b["busy_s"])
        device["window_s"] = b["window_s"]
        # both kinds of metric side by side; idle gaps by the program's spans
        line["metrics"] = {**e2e_metrics, **per_layer(bench, cell.name, ctx, tctx)}
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace, span_prefix="smg.")}
        # columns the device ran in the traced launches, beside the columns the
        # frames asked for (``horizon_sum``): what both decode readers divide by
        run["trace_phase"]["traced_steps"]["columns_run_sum"] = (
            catalog.layer_metric_reader("_common").columns_run(tctx))
        steps = [s for s in ctx["steps"] if s["horizon"] > 0
                 and ctx["window"][0] <= s["t"] <= ctx["window"][1]]
        report["probe"] = {
            "steps_read": len(tctx["steps"]), "steps_lost": tctx["lost_steps"],
            "timelines_read": len(tctx["timelines"]),
            "step_ms_by_kind": {
                kind: [len(v), statistics.median(v) * 1e3, percentile(v, 0.95) * 1e3]
                for kind in ("decode", "mixed", "prefill", "idle")
                if (v := [s["step_s"] for s in ctx["steps"] if s["kind"] == kind
                          and ctx["window"][0] <= s["t"] <= ctx["window"][1]])},
            "decode_launches_at_k1_share":
                (sum(1 for s in steps if s["horizon"] == 1) / len(steps)) if steps else None,
            "programs": {d: {k: v[:2] for k, v in per.items()}
                         for d, per in trace_reduce.program_times(trace).items()},
        }
        log(f"probe: {json.dumps(report['probe'])[:3000]}")
        shutil.rmtree(run["trace_dir"], ignore_errors=True)
    else:
        line["metrics"] = e2e_metrics
    line["device"] = device
    if args.rehearsal:
        line["rehearsal"] = True
    if args.set:
        line["overrides"] = args.set
    report["line"] = line
    # the samples behind the percentiles, and the engine's own counters
    report["requests"] = [{k: q[k] for k in ("id", "due", "sent", "first", "last", "done",
                                             "prompt_tokens", "cached_tokens", "output_tokens",
                                             "tokens_in_window", "finish", "error")}
                          for q in ctx["requests"]]
    report["loads_before"], report["loads_after"] = ctx["loads_before"], ctx["loads_after"]
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print("bench: detail " + json.dumps(
        {"cell": cell.name, "seed": args.seed, "verdict": run["verdict"],
         "setup": {k: round(v, 2) for k, v in run["marks"].items()},
         **e2e["detail"], "e2e": e2e["metrics"], "attention": run["attention"], "counters": run["counters"],
         "total_pages": run["total_pages"], "check_worst": run["check"]["worst"],
         "control": run["check"]["control_errors"], "new_programs": run["new_programs"],
         "compiles_in_window": run["compiles_in_window"], "gc_in_window": run["gc_in_window"],
         "compiled_in_window": run["compiled_in_window"],
         "programs_warmed": len(run["warmed"]),
         **({"trace_phase": run["trace_phase"]} if run["trace_phase"] else {})}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
