"""Checks of the yardstick itself: generators, reductions, cost functions,
and that a cell, a configuration, a traffic mix and a per-layer metric are
added as new files only.  CPU, seconds:  python3 -m pytest benchmark/tests -q
"""

import json
import os
import re
import statistics
import subprocess
import sys

import pytest

import catalog
import trace_reduce
from conftest import BENCH, checkout, untouched

DATA = os.path.join(BENCH, "tests", "data")
costs = catalog.architecture("llama")  # both configurations below are of that file


def lengths(chains, key):
    if key == "prompt":
        return sorted(r["body"][1] + 2 for c in chains for r in c["requests"])
    if key == "prefix":
        return sorted(r["prefix"][1] if r["prefix"] else 0 for c in chains for r in c["requests"])
    return sorted(r["max_tokens"] for c in chains for r in c["requests"])


# ---- generators ----

@pytest.mark.parametrize("mix", ["chat", "doc-qa", "batch", "eval"])
def test_every_seed_replays_one_arrangement_with_other_words(mix):
    params = json.load(open(os.path.join(BENCH, "traffic", mix + ".json")))
    gen = catalog.load_generator(params["generator"])
    a, b, c = (gen.chains(params, s, 30.0) for s in (7, 7, 2**31 + 5))
    assert a == b
    assert a != c  # other words
    shape = lambda ch: [(x["start"], [(r["gap"], r["prefix"] and r["prefix"][1], r["body"][1],
                                       r["max_tokens"]) for r in x["requests"]]) for x in ch]
    assert shape(a) == shape(c)  # the same sizes for the same callers in the same order
    assert "schedule_seed" not in params  # and no file chooses the arrangement


@pytest.mark.parametrize("mix", ["doc-qa", "eval"])
def test_every_round_of_a_closed_loop_covers_the_distribution(mix):
    """Callers get through the first rounds only: each round alone is an even
    sample of the sizes."""
    params = json.load(open(os.path.join(BENCH, "traffic", mix + ".json")))
    gen = catalog.load_generator(params["generator"])
    chains = gen.chains(params, 1, 51.0)
    assert len(chains) == params["clients"]
    assert [c["start"] for c in chains] == [
        i * params["ramp_s"] / params["clients"] for i in range(len(chains))]
    rounds = [[c["requests"][k]["max_tokens"] for c in chains] for k in range(4)]
    means = [statistics.mean(v) for v in rounds]
    assert max(means) - min(means) <= 0.1 * statistics.mean(means)
    whole = [r["max_tokens"] for c in chains for r in c["requests"]]
    assert abs(means[0] - statistics.mean(whole)) <= 0.15 * statistics.mean(whole)


def test_chat_lengths_follow_the_file():
    params = json.load(open(os.path.join(BENCH, "traffic", "chat.json")))
    chains = catalog.load_generator("open_loop").chains(params, 1, 60.0)
    assert len(chains) == round(params["rate_per_s"] * 60)
    p, o = lengths(chains, "prompt"), lengths(chains, "output")
    assert p[0] >= 128 and p[-1] <= 2048 and abs(statistics.median(p) - 384) <= 4
    assert o[0] >= 16 and o[-1] <= 256 and abs(statistics.median(o) - 64) <= 1
    starts = [c["start"] for c in chains]
    assert starts == sorted(starts) and 0 <= starts[0] and starts[-1] < 60.0
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    assert abs(statistics.mean(gaps) - 1 / params["rate_per_s"]) < 0.02 / params["rate_per_s"] * 5


def test_sessions_share_a_document_and_think_between_turns():
    params = json.load(open(os.path.join(BENCH, "traffic", "doc-qa.json")))
    chains = catalog.load_generator("sessions").chains(params, 3, 40.0)
    turns = params["turns"]
    for c in chains:
        reqs = c["requests"]
        assert len(reqs) == turns * params["documents_per_client"]
        assert reqs[0]["gap"] == 0.0 and all(r["gap"] > 0 for r in reqs[1:])
        for d in range(0, len(reqs), turns):
            assert len({tuple(r["prefix"]) for r in reqs[d:d + turns]}) == 1
            assert 2048 <= reqs[d]["prefix"][1] <= 4096
        assert len({tuple(r["prefix"]) for r in reqs}) == params["documents_per_client"]
        assert all(64 <= r["body"][1] + 2 <= 192 and 16 <= r["max_tokens"] <= 64 for r in reqs)


def test_closed_loop_has_one_long_chain_per_client():
    params = json.load(open(os.path.join(BENCH, "traffic", "batch.json")))
    chains = catalog.load_generator("closed_loop").chains(params, 3, 30.0)
    assert len(chains) == params["clients"]
    assert all(c["start"] == 0.0 and len(c["requests"]) == params["pool_per_client"]
               and all(r["gap"] == 0.0 for r in c["requests"]) for c in chains)


#: sha256 of every chain's start and requests at seed 7, as PR 27's generators
#: dealt them: what a chain sends before it starts over may never move
DEALT = {"eval": "b381173d4568bb58e71fb1375eb26449f48f2f353d1522a3cff39eee1ae11153",
         "doc-qa": "0b4a6db479700a7e835b544cabbf0365aea5d2b231e8a08b71b68242cd6b019f",
         "batch": "4abfb704042e655a74306131166268704932a95dc0e34ca505371ebc0dcfb44f"}


@pytest.mark.parametrize("mix", sorted(DEALT))
def test_the_deal_is_what_it_was_and_a_lap_repeats_it_with_other_words(mix):
    import hashlib

    from generators.common import again

    params = json.load(open(os.path.join(BENCH, "traffic", mix + ".json")))
    chains = catalog.load_generator(params["generator"]).chains(params, 7, 51.0)
    dealt = json.dumps([[c["start"], c["requests"]] for c in chains], sort_keys=True)
    assert hashlib.sha256(dealt.encode()).hexdigest() == DEALT[mix]
    assert all(c["starts_over"] for c in chains)
    sizes = lambda r: (r["prefix"] and r["prefix"][1], r["body"][1], r["max_tokens"])
    seen = {tuple(p) for c in chains for r in c["requests"] for p in (r["prefix"], r["body"]) if p}
    for c in chains:
        reqs = c["requests"]
        for lap in (1, 2):
            rep = [again(reqs, i, lap) for i in range(len(reqs))]
            assert [sizes(r) for r in rep] == [sizes(r) for r in reqs]
            assert [r["gap"] for r in rep[1:]] == [r["gap"] for r in reqs[1:]]
            assert rep[0]["gap"] == reqs[-1]["gap"]  # a reader thinks before the next document
            for r, old in zip(rep, reqs):  # other words, other documents: nothing cached
                assert tuple(r["body"]) not in seen and r["body"] != old["body"]
                assert r["prefix"] is None or tuple(r["prefix"]) not in seen
            if reqs[0]["prefix"]:  # the turns of one document still share it
                assert rep[0]["prefix"] == rep[1]["prefix"]
        assert reqs == c["requests"]  # the deal itself is untouched


def test_a_chain_that_passes_its_pool_goes_on_and_an_open_one_ends(monkeypatch):
    import asyncio
    import time

    import loadgen
    from generators.common import request

    async def served(session, plan, rec, spec):
        rec["sent"] = time.monotonic()
        await asyncio.sleep(0.01)
        rec["done"] = time.monotonic()
        rec["spec"] = spec

    monkeypatch.setattr(loadgen, "one_request", served)
    import random
    rng = random.Random(1)
    reqs = [request(rng, 40 + k, 5 + k, gap=0.0) for k in range(3)]

    def run(chain):
        records = []
        plan = {"seconds": 0.25, "tag": "t"}
        asyncio.run(loadgen.run_chain(None, plan, time.monotonic(), 0, chain, records))
        return records

    closed = run({"start": 0.0, "starts_over": True, "requests": reqs})
    assert len(closed) >= 9 and max(r["lap"] for r in closed) >= 2
    assert [r["turn"] for r in closed] == list(range(len(closed)))
    for r in closed:  # every lap sends the dealt sizes in the dealt order
        assert r["spec"]["max_tokens"] == reqs[r["turn"] % 3]["max_tokens"]
        assert (r["spec"]["body"] == reqs[r["turn"] % 3]["body"]) == (r["lap"] == 0)
    assert closed[-1]["due"] < closed[0]["due"] + 0.25  # and stops at the window's end
    opened = run({"start": 0.0, "requests": reqs})
    assert [r["lap"] for r in opened] == [0, 0, 0]
    # a server that refuses everything is asked the pool and one request more
    async def refused(session, plan, rec, spec):
        rec["done"], rec["error"] = time.monotonic(), "HTTP 503"

    monkeypatch.setattr(loadgen, "one_request", refused)
    assert len(run({"start": 0.0, "starts_over": True, "requests": reqs})) == 4


def test_words_are_exact_token_counts():
    from generators.common import words
    from smg_tpu.tokenizer import MockTokenizer

    tok = MockTokenizer(vocab_size=151936)
    text = tok.apply_chat_template([{"role": "user", "content": words(5, 300, 151936)}])
    assert len(tok.encode(text)) == 302
    assert words(5, 300, 151936) == words(5, 300, 151936) != words(6, 300, 151936)


# ---- the reduction from a trace ----

@pytest.fixture(scope="module")
def four():
    return json.load(open(os.path.join(DATA, "four_devices.json")))


def test_busy_union_and_idle_share(four):
    b = trace_reduce.busy(four)
    assert b["window_s"] == pytest.approx(0.0095)
    assert b["busy_s"]["/device:TPU:0"] == pytest.approx(0.009)  # while 8 ms + prefill 1 ms
    assert b["busy_s"]["/device:TPU:3"] == pytest.approx(0.008)  # its while ends at 7 ms
    assert trace_reduce.idle_share(four) == pytest.approx(100 * (1 - 0.008 / 0.0095))


def test_program_time_by_family(four):
    dec = trace_reduce.family_time(four, "decode")
    pre = trace_reduce.family_time(four, "prefill")
    assert dec["launches"] == 1 and dec["seconds"] == pytest.approx(0.008)
    assert pre["launches"] == 1 and pre["durations"] == [pytest.approx(0.001)]


def test_leaves_drop_enclosing_ops(four):
    names = sorted(e[0] for e in trace_reduce.leaves(four["devices"]["/device:TPU:0"]["ops"]))
    assert names == ["all-reduce.7", "fusion.1", "fusion.2", "fusion.9"]


def test_exposed_collective_time_worst_device(four):
    rec = trace_reduce.collective_exposed(four)
    assert rec["collective_s"] == pytest.approx(0.002)
    assert rec["exposed_s"] == pytest.approx(0.002)  # devices 1-3: nothing overlaps it
    assert rec["device"] != "/device:TPU:0"
    only0 = {"devices": {"d": four["devices"]["/device:TPU:0"]}, "host": []}
    assert trace_reduce.collective_exposed(only0)["exposed_s"] == pytest.approx(0.001)


def test_top_ops_and_gap_labels(four):
    ops = dict(trace_reduce.top_ops(four))
    assert "while.1" not in ops and ops["fusion.1"] == pytest.approx(0.002)
    gaps = dict(trace_reduce.idle_gaps(four))
    # device 3 idles [7, 8.5] ms: mostly inside the first engine step's span
    assert gaps["bench.engine_step"] == pytest.approx(0.0015)


def test_recorded_trace_reduces_to_recorded_numbers():
    path = os.path.join(DATA, "recorded_v5e.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    rec = json.load(open(path))
    trace, want = rec["trace"], rec["expected"]
    b = trace_reduce.busy(trace)
    assert b["window_s"] == pytest.approx(want["window_s"])
    assert sum(b["busy_s"].values()) / len(b["busy_s"]) == pytest.approx(want["busy_s"])
    assert trace_reduce.idle_share(trace) == pytest.approx(want["idle_share"])
    assert abs(want["busy_s"] - want["busy_s_rasterized_at_100ns"]) < 1e-5
    dec = trace_reduce.family_time(trace, "decode")
    assert dec["launches"] == want["decode"]["launches"]
    assert dec["seconds"] == pytest.approx(want["decode"]["seconds"])
    assert trace_reduce.family_time(trace, "prefill") is None
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    assert len(trace_reduce.leaves(ops)) == want["leaves"] < len(ops)  # the while loop encloses
    name, secs = trace_reduce.top_ops(trace, 1)[0]
    assert name == want["top_op"][0] and secs == pytest.approx(want["top_op"][1])


# ---- client-side reduction ----

def rec(due, first, last, done, n, ok=True):
    return {"due": due, "sent": due, "first": first, "last": last, "done": done, "error": None,
            "finish": "length" if ok else "abort", "output_tokens": n, "want_output_tokens": n,
            "prompt_tokens": 10, "want_prompt_tokens": 10, "cached_tokens": 0,
            "streamed_tokens": n, "tokens_in_window": min(n, max(0, round(n * (10.0 - first)
                                                                          / max(last - first, 1e-9))))}


def test_end_to_end_counts_failures_as_worst_and_tokens_inside_the_window():
    import run

    reqs = [rec(0.0, 0.1, 1.1, 1.2, 11), rec(1.0, 1.2, 3.2, 3.3, 21),
            rec(9.0, 9.5, 10.5, 10.6, 11), rec(2.0, 2.1, 2.5, 2.6, 5, ok=False)]
    out = run.end_to_end({"t0": 0.0, "seconds": 10.0, "requests": reqs})
    assert out["attempted"] == 4 and out["failed"] == 1
    # every token streamed inside the window counts, also the half of the
    # third request's that arrived before the window closed, and the failure's
    assert out["metrics"]["output_tok_per_s"] == pytest.approx((11 + 21 + 6 + 5) / 10.0)
    assert out["detail"]["completed_request_tok_per_s"] == pytest.approx((11 + 21) / 10.0)
    assert out["metrics"]["ttft_p95_ms"] == run.MISSED_MS  # the failure ranks last
    ctx = {"requests": reqs, "window": (0.0, 10.0)}
    assert catalog.layer_metric_reader("caller.ttft_p95_ms").read(ctx) == run.MISSED_MS
    assert catalog.layer_metric_reader("caller.ttft_p50_ms").read(ctx) == pytest.approx(350.0)
    assert catalog.layer_metric_reader("caller.tpot_p50_ms").read(
        {"requests": reqs[:3], "window": (0.0, 5.0)}) == pytest.approx(100.0)
    assert out["detail"]["ttft_p50_ms"] == pytest.approx(200.0)
    assert out["detail"]["tpot_p50_ms"] == pytest.approx(100.0)
    assert run.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert run.backlog(reqs, 1.1) == 2


# ---- costs ----

@pytest.mark.parametrize("config,params,kv", [
    ("qwen3-1.7b", 1.72e9, 112 * 1024), ("mistral-nemo-12b-tp4", 12.25e9, 160 * 1024)])
def test_parameter_and_kv_counts(config, params, kv):
    hf = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    assert costs.param_count(hf)["total"] == pytest.approx(params, rel=0.005)
    assert costs.kv_bytes_per_token(hf) == kv


def test_least_times_scale_with_chips_and_never_count_padding():
    import peaks

    hf = json.load(open(os.path.join(BENCH, "configs", "qwen3-1.7b.json")))
    pk = peaks.peaks_for("TPU v5 lite")
    one = costs.decode_min_seconds(hf, 100, 100 * 40 * 700, 1, pk)
    assert one == pytest.approx(100 * (1.72e9 * 2 + 40 * 700 * 114688) / 819e9, rel=0.01)
    assert costs.decode_min_seconds(hf, 100, 100 * 40 * 700, 4, pk) == pytest.approx(one / 4)
    pre = costs.prefill_min_seconds(hf, 4096, 4096 * 4097 / 2, 1, pk)
    assert pre == pytest.approx((2 * 1.409e9 * 4096 + 4 * 16 * 128 * 28 * 4096 * 4097 / 2)
                                / 197e12, rel=0.01)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_warm_sets_cover_what_the_lengths_reach():
    import types

    import warm
    from smg_tpu.engine.config import SchedulerConfig
    from smg_tpu.engine.scheduler import Scheduler

    sched = SchedulerConfig(decode_horizon=8)
    mp_bucket = lambda pages: Scheduler._mp_bucket(types.SimpleNamespace(mp=512), pages)

    def reach(mix, seconds=51.0):
        params = json.load(open(os.path.join(BENCH, "traffic", mix + ".json")))
        chains = catalog.load_generator(params["generator"]).chains(params, 5, seconds)
        want = warm.lengths(chains, 16)
        return want, warm.reachable(sched, mp_bucket, want, 16)

    want, chat = reach("chat")
    assert want["tail"] is None and want["concurrency"] == 61
    assert 128 <= want["fresh"][0] <= 140 and 2000 <= want["fresh"][1] <= 2048
    assert (8, 2048, True) in chat["batched"] and (1, 128, True) in chat["batched"]
    assert not any(T > 2048 or not cold for _, T, cold in chat["batched"])
    assert chat["solo"] == [64, 128, 256, 512, 1024, 2048]
    assert (8, 16) in chat["decode"] and (64, 256) in chat["decode"]
    assert not any(w > 256 for _, w in chat["decode"])
    want, ev = reach("eval")
    assert ev["batched"] == chat["batched"] and want["concurrency"] == 16
    assert {B for B, _ in ev["decode"]} == {8, 16}  # sixteen callers never fill a wider batch
    want, doc = reach("doc-qa")
    assert want["tail"][0] == 64 and 192 <= want["tail"][1] <= 208
    assert (1, 4096, True) in doc["batched"] and (2, 4096, True) not in doc["batched"]
    assert (8, 256, False) in doc["batched"] and (1, 1024, False) not in doc["batched"]
    assert {w for _, w in doc["decode"]} == {256, 512}
    assert {B for B, _ in doc["decode"]} == {8}


# ---- BENCHMARK.json against its own files ----

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_is_consistent_with_its_files():
    bench = catalog.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer", "trace_in_run"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        reader = catalog.layer_metric_reader(m["name"])
        assert reader is not None and reader.META["unit"] == m["unit"]
        assert reader.META["layer"] == m["layer"] and reader.META["moves"] == m["moves"]
    for w in bench["workloads"]:
        cell = catalog.Cell(bench, w["name"])
        assert cell.chips == cell.config["chips"] and len(w["why"]) <= 200
        assert cell.chains(1, 5.0)
        assert catalog.metrics_for(bench, w["name"], "per_layer")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(len(bench["workloads"]) // 4, 1)


# ---- driven by data: new things are new files ----

def test_a_cell_config_traffic_generator_and_metric_are_added_as_new_files(tmp_path):
    root, before = checkout(tmp_path)
    b = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(b, "configs", "qwen3-1.7b.json")))
    cfg["num_hidden_layers"] = 4
    json.dump(cfg, open(os.path.join(b, "configs", "new-model.json"), "w"))
    json.dump({"generator": "ramp", "n": 5, "prompt": 100, "out": 7},
              open(os.path.join(b, "traffic", "new-mix.json"), "w"))
    with open(os.path.join(b, "generators", "ramp.py"), "w") as f:
        f.write("import random\nfrom .common import request\n\n"
                "def chains(params, seed, seconds):\n"
                "    rng = random.Random(seed)\n"
                "    return [{'start': i * seconds / params['n'], 'requests': "
                "[request(rng, params['prompt'], params['out'])]} for i in range(params['n'])]\n")
    with open(os.path.join(b, "layer_metrics", "scheduler.new_metric.py"), "w") as f:
        f.write("from _common import median\nMETA = {'layer': 'scheduler', 'unit': 'ms', "
                "'moves': 'output_tok_per_s'}\n\ndef read(ctx):\n    return median(ctx['x'])\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "new-model", "source": "https://example.org/new",
                             "file": "benchmark/configs/new-model.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                               "traffic": "new-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "scheduler.new_metric", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "scheduler",
                               "moves": "output_tok_per_s", "workloads": ["new-model.new-mix"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    # a process of its own in that checkout, as a run is
    script = (
        "import json, sys; sys.path.insert(0, 'benchmark'); import catalog\n"
        "bench = catalog.load_benchmark()\n"
        "cell = catalog.Cell(bench, 'new-model.new-mix')\n"
        "chains = cell.chains(9, 10.0)\n"
        "names = lambda c: [m['name'] for m in catalog.metrics_for(bench, c, 'per_layer')]\n"
        "print(json.dumps({'seen': catalog.listing(), 'layers': cell.hf_config['num_hidden_layers'],\n"
        "  'chains': len(chains), 'max_tokens': chains[0]['requests'][0]['max_tokens'],\n"
        "  'new': names('new-model.new-mix'), 'old': names(bench['workloads'][0]['name']),\n"
        "  'read': catalog.layer_metric_reader('scheduler.new_metric').read({'x': [1, 2, 9]}),\n"
        "  'none': catalog.layer_metric_reader('no.such_metric') is None}))\n")
    out = json.loads(subprocess.run([sys.executable, "-c", script], cwd=root, check=True,
                                    stdout=subprocess.PIPE, text=True).stdout)
    seen = out["seen"]
    assert "new-model.new-mix" in seen["workloads"] and "new-model" in seen["config_files"]
    assert "new-mix" in seen["traffic"] and "ramp" in seen["generators"]
    assert "scheduler.new_metric" in seen["layer_metrics"]
    assert out["layers"] == 4 and out["chains"] == 5 and out["max_tokens"] == 7
    assert "scheduler.new_metric" in out["new"]
    assert "device.collective_exposed_share" not in out["new"]
    assert "scheduler.new_metric" not in out["old"]
    assert out["read"] == 2 and out["none"] is True
    untouched(before)
