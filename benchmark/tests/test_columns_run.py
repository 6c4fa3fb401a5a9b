"""Columns run, not columns asked (PR 32): the count of the decode kernel's
executions inside the decode launches, both decode readers on it, the step
ring's ``columns_run`` where no kernel runs, and nothing where neither is
there; and what the harness asks the flight recorder to keep.  CPU, seconds:
python3 -m pytest benchmark/tests -q
"""

import argparse
import inspect
from collections import deque

import pytest

import catalog
import peaks
import run
import trace_reduce

LAYERS = 2
HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": LAYERS,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512}
KERNEL = "%smg.attn.decode.7 = bf16[16,16,128]{2,1,0} custom-call(...)"


def launch(name, start, columns, col_s=0.001):
    """One launch on the Modules line and, on the Ops line, its ``while`` with
    the kernel once a layer and a matmul once a column inside it."""
    module = [name, start, columns * col_s + 0.0002]
    ops = [["%while.1", start + 0.0001, columns * col_s]]
    for c in range(columns):
        t = start + 0.0001 + c * col_s
        ops += [[KERNEL, t + 0.0001 * l, 0.00005] for l in range(LAYERS)]
        # the consumer of the kernel's result carries the kernel's name as an operand
        ops.append(["%fusion.3 = bf16[16,2048]{1,0} fusion(bf16[16,16,128]{2,1,0} %smg.attn.decode.7)",
                    t + 0.0005, 0.0004])
    return module, ops


def trace_of(*launches):
    return {"devices": {"/device:TPU:0": {"modules": [m for m, _ in launches],
                                          "ops": [o for _, ops in launches for o in ops]}},
            "host": []}


def ctx_of(trace, steps, costs=None):
    return {"trace": trace, "trace_window": (0.0, 1.0), "hf": HF,
            "costs": costs or catalog.architecture("llama"), "chips": 1,
            "device": {"kind": "TPU v5 lite"}, "kv_dtype_bytes": 2, "steps": steps,
            "requests": [{"first": 0.0, "done": 1.0, "prompt_tokens": 100, "output_tokens": 10}]}


def step(t, horizon, **kw):
    return {"kind": "decode", "t": t, "horizon": horizon, "decode_tokens": 16 * horizon, **kw}


def read(name, ctx):
    return catalog.layer_metric_reader(name).read(ctx)


def test_columns_are_the_kernels_runs_inside_decode_launches_over_the_layers():
    # two frames asked for 8 columns each; the first left at a finish after 3
    early, full = launch("jit_multi(11)", 0.10, 3), launch("jit_multi(12)", 0.20, 8)
    # a prefill launch and a stray operation of that name outside any decode launch
    prefill = (["jit_step(5)", 0.30, 0.01], [[KERNEL, 0.301, 0.0001], ["%fusion.9", 0.302, 0.005]])
    none_ran = (["jit_multi(13)", 0.40, 0.0002], [["%copy.1", 0.4001, 0.00005]])  # chained, clean
    trace = trace_of(early, full, prefill, none_ran)
    assert trace_reduce.kernel_columns(trace, LAYERS) == pytest.approx(11.0)
    assert trace_reduce.kernel_columns(trace, 4) == pytest.approx(5.5)  # over its own layers
    # no launch of the family runs the kernel (XLA attention): nothing to count, not 0
    assert trace_reduce.kernel_columns(trace_of(prefill, none_ran), LAYERS) is None
    # averaged over the devices of a mesh, each of which runs every launch
    two = {"devices": {"a": trace["devices"]["/device:TPU:0"],
                       "b": trace["devices"]["/device:TPU:0"]}, "host": []}
    assert trace_reduce.kernel_columns(two, LAYERS) == pytest.approx(11.0)


@pytest.mark.parametrize("ran, asked", [((8, 8), 16), ((3, 8), 16), ((1, 5), 16)])
def test_both_decode_readers_divide_by_columns_run(ran, asked):
    trace = trace_of(launch("jit_multi(1)", 0.1, ran[0]), launch("jit_multi(2)", 0.2, ran[1]))
    ctx = ctx_of(trace, [step(0.15, 8), step(0.25, 8)])
    columns = sum(ran)
    seconds = trace_reduce.family_time(trace, "decode")["seconds"]
    llama = catalog.architecture("llama")
    pk = peaks.peaks_for("TPU v5 lite")
    # what the readers gave until PR 32, from the sum of ``horizon``
    old_step = seconds * 1e3 / asked
    old_share = 100 * llama.decode_min_seconds(HF, asked, asked * 105.0, 1, pk, 2) / seconds
    assert read("runner.decode_step_ms", ctx) == pytest.approx(old_step * asked / columns)
    assert read("kernels.decode_roofline_share", ctx) == pytest.approx(old_share * columns / asked)
    assert catalog.layer_metric_reader("_common").columns_run(ctx) == pytest.approx(columns)


def test_without_the_kernel_the_ring_says_it_and_without_either_nothing_is_read():
    xla = trace_of((["jit_multi(1)", 0.1, 0.02], [["%fusion.3", 0.101, 0.015]]))
    no_kernel = argparse.Namespace(  # an architecture that names no attention layers
        decode_min_seconds=catalog.architecture("llama").decode_min_seconds)
    for costs in (None, no_kernel):
        # the step ring's own count of columns run, where a program records one
        ctx = ctx_of(xla, [step(0.15, 8, columns_run=3), step(0.25, 8, columns_run=8)], costs)
        assert read("runner.decode_step_ms", ctx) == pytest.approx(20.0 / 11)
        assert read("kernels.decode_roofline_share", ctx) > 0
        # an older program's records have ``horizon`` alone: no reading, not the old one
        ctx = ctx_of(xla, [step(0.15, 8), step(0.25, 8, columns_run=8)], costs)
        assert read("runner.decode_step_ms", ctx) is None
        assert read("kernels.decode_roofline_share", ctx) is None
    # steps outside the traced window do not count, and a sum of 0 is nothing to divide by
    ctx = ctx_of(xla, [step(5.0, 8, columns_run=8), step(0.2, 8, columns_run=0)])
    assert read("runner.decode_step_ms", ctx) is None
    assert read("runner.decode_step_ms", {**ctx, "trace": None}) is None


def test_both_architectures_name_their_attention_layers():
    bench = catalog.load_benchmark()
    for name, layers in (("qwen3-1.7b.eval", 28), ("olmo-hybrid-7b.gen", 4)):
        cell = catalog.Cell(bench, name)
        assert cell.architecture.attention_layers(cell.hf_config) == layers


def test_the_recorder_is_asked_to_keep_the_window():
    from smg_tpu.cli import build_parser

    # ``serve`` has the flag for the ring; the harness passes it for every cell
    sargs = build_parser().parse_args(["serve", "--model-preset", "tiny", "--flight-ring-size",
                                       str(run.FLIGHT_RING_STEPS)])
    assert sargs.flight_ring_size == run.FLIGHT_RING_STEPS == 4096
    src = inspect.getsource(run.serve_and_measure)
    assert '"--flight-ring-size", str(FLIGHT_RING_STEPS)' in src
    assert "keep_timelines(engine, FLIGHT_TIMELINES)" in src
    flight = argparse.Namespace(_finished=deque([1, 2, 3], maxlen=64))
    engine = argparse.Namespace(scheduler=argparse.Namespace(flight=flight))
    run.keep_timelines(engine, run.FLIGHT_TIMELINES)
    assert flight._finished.maxlen == 2048 and list(flight._finished) == [1, 2, 3]
    run.keep_timelines(engine, 100)  # never narrows what the program keeps
    assert flight._finished.maxlen == 2048
    # a recorder that keeps its timelines elsewhere is left alone
    other = argparse.Namespace(scheduler=argparse.Namespace(flight=argparse.Namespace()))
    run.keep_timelines(other, 2048)
    assert not hasattr(other.scheduler.flight, "_finished")
    run.keep_timelines(argparse.Namespace(scheduler=argparse.Namespace(flight=None)), 2048)
    assert run.TRACE_SECONDS == 6.0


def test_a_stall_has_two_witnesses_on_the_detail_line():
    import gc

    watch = run.PauseWatch()
    try:
        t0 = run.time.monotonic()
        gc.collect()
        seen = watch.between(t0, run.time.monotonic())
    finally:
        gc.callbacks.remove(watch._on_gc)
    assert seen["collections"][2] == 1 and seen["longest"]["generation"] == 2
    assert 0 <= seen["longest"]["seconds"] <= seen["total_s"]
    assert watch.between(t0 - 10, t0 - 5) == {"collections": [0, 0, 0], "total_s": 0,
                                              "longest": None}
    steps = [{"t": 10.0 + i, "step_s": 0.08, "fetch_wait_s": 0.07, "kind": "decode",
              "prefill_tokens": 0} for i in range(5)]
    steps[2].update(step_s=4.5, fetch_wait_s=0.06)  # seconds on the host's side of the fetch
    worst = run.longest_steps(steps, 9.0, 20.0, n=2)
    assert len(worst) == 2 and worst[0] == {"at_s": 3.0, "step_s": 4.5, "fetch_wait_s": 0.06,
                                            "kind": "decode", "prefill_tokens": 0}
    assert run.longest_steps(steps, 0.0, 5.0) == []
