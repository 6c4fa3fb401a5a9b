"""``--trace 2`` and the readers that came with it (ISSUE 26): the three
readers on hand-made contexts, ``per_layer`` asking the closed window first
and the trace phase second, and the order of a ``--trace 2`` run against a
stub engine: window closed, numbers taken, and only then the probe and the
profiler.  CPU, seconds:  python3 -m pytest benchmark/tests -q
"""

import argparse
import asyncio
import inspect
import json
import os
import time

import pytest

import catalog
import run
from conftest import ROOT

NEW = ("scheduler.submit_lock_wait_ms", "scheduler.admit_to_first_token_ms",
       "scheduler.k1_pending_share")


def timeline(rid, submit, queued, admitted, first):
    tl = {"rid": rid, "queued_t": queued, "admitted_t": admitted, "first_token_t": first}
    if submit is not None:
        tl["submit_t"] = submit
    return tl


def step(serial, t, reason=None, **kw):
    rec = {"serial": serial, "t": t, "kind": "decode", "horizon": 8, "running": 3,
           "decode_tokens": 24, **kw}
    if reason is not None:
        rec["horizon_reason"] = reason
    return rec


def read(name, ctx):
    return catalog.layer_metric_reader(name).read(ctx)


def test_new_metrics_are_entries_with_readers_and_the_switch_is_declared():
    bench = catalog.load_benchmark()
    assert bench["trace_in_run"] is True
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert sorted(NEW, key=names.index) == list(NEW)  # there, in order, wherever later ones went
    for name in NEW:
        m, meta = by_name[name], catalog.layer_metric_reader(name).META
        assert (m["layer"], m["moves"], m["unit"]) == ("scheduler", "output_tok_per_s",
                                                      meta["unit"])
        assert meta["source"].startswith(m["source"]) and m["better"] == "lower"
        assert "workloads" not in m


def test_lock_wait_and_admit_to_first_token_read_the_timelines_of_the_window():
    ctx = {"window": (100.0, 200.0), "timelines": [
        timeline("a", 110.00, 110.20, 110.50, 110.90),   # waits .2, then .4 to the token
        timeline("b", 120.00, 120.10, 120.40, 120.60),   # .1 and .2
        timeline("c", 130.00, 130.40, 130.45, 131.45),   # .4 and 1.0
        timeline("early", 90.0, 99.0, 99.5, 105.0),      # queued before the window
        timeline("unadmitted", 150.0, 150.3, None, None),
    ]}
    assert read(NEW[0], ctx) == pytest.approx(250.0)  # median of 200, 100, 400, 300
    assert read(NEW[1], ctx) == pytest.approx(400.0)  # median of 400, 200, 1000
    # a program without the stamp (the parent): nothing to read, nothing raised
    old = {"window": ctx["window"],
           "timelines": [timeline("a", None, 110.2, 110.5, 110.9)]}
    assert read(NEW[0], old) is None
    assert read(NEW[1], old) == pytest.approx(400.0)
    assert read(NEW[0], {"window": (0, 1), "timelines": []}) is None
    assert read(NEW[1], {"window": (0, 1), "timelines": []}) is None


def test_k1_pending_share_counts_launches_by_reason():
    steps = [step(1, 10.0, "full"), step(2, 11.0, "pending_admission"),
             step(3, 12.0, ""),  # a step that launched nothing
             step(4, 13.0, "full"), step(5, 14.0, "forced_lane"),
             step(6, 99.0, "pending_admission")]  # outside the window
    assert read(NEW[2], {"window": (9.0, 20.0), "steps": steps}) == pytest.approx(25.0)
    assert read(NEW[2], {"window": (9.0, 10.5), "steps": steps}) == 0.0
    # the parent's records have no such field: nothing to read
    assert read(NEW[2], {"window": (9.0, 20.0), "steps": [step(1, 10.0), step(2, 11.0)]}) is None
    assert read(NEW[2], {"window": (9.0, 20.0), "steps": []}) is None


def test_per_layer_asks_the_closed_window_first_and_the_trace_phase_second(tmp_path, monkeypatch):
    readers = {"a.window": lambda c: c.get("a"), "b.phase": lambda c: c.get("b"),
               "c.none": lambda c: None}
    bench = {"per_layer": [{"name": n, "unit": "ms"} for n in readers]}
    monkeypatch.setattr(catalog, "layer_metric_reader",
                        lambda name: argparse.Namespace(read=readers[name]))
    window, phase = {"a": 1.0}, {"a": 9.0, "b": 2.0}
    assert run.per_layer(bench, "cell", window, phase) == {
        "a.window": {"value": 1.0, "unit": "ms"}, "b.phase": {"value": 2.0, "unit": "ms"}}
    assert run.per_layer(bench, "cell", window) == {"a.window": {"value": 1.0, "unit": "ms"}}
    window["a"] = 0.0  # a reading of zero is a reading
    assert run.per_layer(bench, "cell", window, phase)["a.window"]["value"] == 0.0


# ---- the order of a --trace 2 run ----

class StubFlight:
    def __init__(self, log):
        self.log = log
        self.serial = 0

    def snapshot(self, _reason):
        self.log.append("flight.snapshot")
        self.serial += 1
        now = time.monotonic()
        return {"ring": [{"serial": self.serial, "t": now, "decode_tokens": 8, "horizon": 8,
                          "early_exits": 0, "horizon_reason": "full"}],
                "timelines": {"finished": [{"rid": f"r{self.serial}", "submit_t": now - 0.3,
                                            "queued_t": now - 0.2, "admitted_t": now - 0.1,
                                            "first_token_t": now, "events": []}]}}


class StubEngine:
    def __init__(self, log):
        self.log = log
        self.scheduler = argparse.Namespace(flight=StubFlight(log))

    def submit(self, *a, **kw):
        return "rid"

    def step(self):
        return []

    def start_profile(self, d):
        self.log.append(f"start_profile:{os.path.basename(d)}")

    def stop_profile(self):
        self.log.append("stop_profile")

    def loads(self):
        return {"audit": {"quiescent": True, "clean": True},
                "programs": {"recompiles": 0, "programs": [{"key": "k"}]},
                "decode_launches": {"full": 3}, **dict.fromkeys(run.COUNTERS, 0)}


def test_trace_phase_starts_probe_and_profiler_only_after_the_window(tmp_path, monkeypatch):
    log: list = []
    engine = StubEngine(log)
    probe = run.Probe(engine)
    install = probe.install
    monkeypatch.setattr(probe, "install", lambda: (log.append("probe.install"), install())[1])
    monkeypatch.setattr(run, "TRACE_SECONDS", 0.2)
    monkeypatch.setattr(run, "TRACE_TAIL_SECONDS", 0.1)
    plans = []

    async def loadgen(plan, _out_dir, tag):
        log.append(f"loadgen:{tag}")
        plans.append(plan)
        await asyncio.sleep(plan["t0"] + plan["seconds"] - time.monotonic())
        log.append(f"loadgen:{tag}:done")
        return {"t0": plan["t0"], "seconds": plan["seconds"], "requests": []}

    monkeypatch.setattr(run, "run_loadgen", loadgen)
    bench = catalog.load_benchmark()
    cell = catalog.Cell(bench, "qwen3-1.7b.eval", rehearsal=True)
    args = argparse.Namespace(seed=2**31 + 11, trace=2, seconds=0.5)
    watch = argparse.Namespace(count=0)
    closed = {"t0": time.monotonic() - 2.0, "seconds": 2.0, "requests": []}
    phase = asyncio.run(run.trace_phase(
        args, cell, engine, probe, {"url": "x", "vocab": 512, "drain_s": 60.0},
        str(tmp_path), watch, str(tmp_path / "trace"), closed_window=closed))
    # one read of the recorder for the closed window, a scrap start and stop,
    # then the probe, then traffic, then the real trace, stopped while traffic runs
    order = [e for e in log if e != "flight.snapshot"]
    assert order[:6] == ["start_profile:trace_scrap", "stop_profile", "probe.install",
                         "loadgen:trace", "start_profile:trace", "stop_profile"]
    assert order.index("stop_profile", 2) < order.index("loadgen:trace:done")
    assert log[0] == "flight.snapshot"  # before anything of the profiler
    # and once more after the replay: nothing polls the recorder while the trace is on
    assert log.count("flight.snapshot") == 2 and log[-1] == "flight.snapshot"
    assert plans[0]["chains"] != cell.chains(args.seed, plans[0]["seconds"])  # other words
    assert plans[0]["tag"] == "t" and plans[0]["drain_s"] <= 30.0
    assert phase["window_steps"] and phase["window_timelines"]
    ctx = phase["ctx"]
    assert ctx["window"][1] == ctx["trace_window"][0] < ctx["trace_window"][1]
    # the replay is the window's length, traced over its last stretch
    assert plans[0]["seconds"] == pytest.approx(0.5 + 0.1)
    assert ctx["trace_window"][0] - plans[0]["t0"] == pytest.approx(0.5 - 0.15, abs=0.05)
    assert ctx["trace_window"][1] - ctx["trace_window"][0] == pytest.approx(0.15, abs=0.05)
    assert {"requests", "steps", "timelines", "stamps", "loads_before", "loads_after"} <= set(ctx)
    assert phase["compiles"] == 0 and phase["failed"] == 0 and phase["new_programs"] == []
    assert set(phase["rests_on"]) >= {"window_requests", "window_timelines",
                                      "trace_phase_requests"}
    json.dumps({k: v for k, v in phase.items() if k != "ctx"})
    # the engine's own submit and step are wrapped now, and were not before
    assert engine.submit.__name__ == "stamped_submit"


def test_trace_2_is_trace_0_until_the_window_has_closed():
    """The source of ``serve_and_measure``: whatever a traced run does before
    or inside the window does not ask ``args.trace`` (``--trace 1``, which
    traced inside the window, is gone), and the trace phase is entered after
    the window's result, its ``loads()`` and its end-to-end numbers exist."""
    src = inspect.getsource(run.serve_and_measure)
    assert src.count("args.trace") == 1 and "probe.install" not in src
    order = [src.index(needle) for needle in (
        "load_task = asyncio.create_task(run_loadgen(plan", "result = await load_task",
        "loads1 = await wait_quiet(engine)", "e2e = end_to_end(result)",
        "if args.trace == 2:", "await trace_phase(")]
    assert order == sorted(order)
    assert src.count("args.trace == 2") == 1
    phase = inspect.getsource(run.trace_phase)
    assert phase.index("probe.install()") < phase.index("engine.start_profile, trace_dir")
    assert "closed_window" in inspect.signature(run.trace_phase).parameters


def test_the_command_takes_trace_2(capsys):
    import subprocess
    import sys

    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                          "--workload", "qwen3-1.7b.eval", "--trace", "3"],
                         capture_output=True, text=True)
    assert out.returncode == 2 and "choose from 0, 2" in out.stderr
    one = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                          "--workload", "qwen3-1.7b.eval", "--trace", "1"],
                         capture_output=True, text=True)
    assert one.returncode == 2 and "choose from 0, 2" in one.stderr  # retired by PR 32
