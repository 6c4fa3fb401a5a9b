"""The four readers of the program's step account (PR 39): sums of one key of
the step records stamped inside the window over the window's length, nothing
where a record lacks the key, and the four entries in ``BENCHMARK.json`` with
a file each.  CPU, under a second:
python3 -m pytest benchmark/tests -q
"""

import os

import pytest

import catalog

READERS = {
    "scheduler.chip_starved_share": "starved_s",
    "scheduler.starved_admit_share": "starved_admit_s",
    "scheduler.starved_launch_share": "starved_launch_s",
    "scheduler.starved_consume_share": "starved_consume_s",
}
WINDOW = (100.0, 110.0)


def step(t, **starved):
    """A step record as schema 9 writes it, as far as the readers look."""
    return {"t": t, "kind": "decode", "step_s": 0.04, "gap_s": 0.002,
            "starved_s": 0.0, "starved_admit_s": 0.0, "starved_launch_s": 0.0,
            "starved_consume_s": 0.0, **starved}


def read(name, steps, window=WINDOW):
    return catalog.layer_metric_reader(name).read({"steps": steps, "window": window})


@pytest.mark.parametrize("name,key", sorted(READERS.items()))
def test_a_reader_sums_its_key_over_the_records_inside_the_window(name, key):
    steps = [step(99.9, **{key: 5.0}),              # stamped before the window
             step(100.0, **{key: 0.25}), step(104.0, **{key: 0.5}),
             step(110.0, **{key: 0.25}),
             step(110.1, **{key: 7.0})]             # and after it
    assert read(name, steps) == pytest.approx(10.0)  # 1.0 s of 10 s
    others = [n for n in READERS if n != name]
    assert [read(n, steps) for n in others] == [0.0] * 3


@pytest.mark.parametrize("name,key", sorted(READERS.items()))
def test_records_without_the_key_give_nothing(name, key):
    old = {k: v for k, v in step(104.0).items() if not k.startswith("starved")}
    assert read(name, [old, old]) is None          # the parent's records
    assert read(name, [step(104.0, **{key: 0.5}), old]) is None  # never half a sum
    assert read(name, []) is None                  # a --trace 0 context
    assert read(name, [step(50.0, **{key: 0.5})]) is None  # nothing in the window


def test_the_whole_is_at_least_its_parts():
    steps = [step(101.0 + i, starved_s=0.3, starved_admit_s=0.15, starved_launch_s=0.05,
                  starved_consume_s=0.02) for i in range(5)]
    whole, *parts = (read(n, steps) for n in READERS)
    assert whole == pytest.approx(15.0) and 0.0 <= sum(parts) <= whole <= 100.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_benchmark_json_names_the_metric_and_it_has_a_file(name):
    bench = catalog.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "output_tok_per_s"}  # no ``workloads``: every cell
    assert all(m in catalog.metrics_for(bench, w["name"], "per_layer")
               for w in bench["workloads"] for m in [entry])
    reader = catalog.layer_metric_reader(name)
    assert reader is not None and os.path.basename(reader.__file__) == name + ".py"
    assert reader.META["layer"] == entry["layer"] and reader.META["unit"] == entry["unit"]
