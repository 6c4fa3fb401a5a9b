"""``runner.prefill_padded_share`` (PR 42): padded tokens that were no row's
own over all the grouped prefills computed, between the two ``loads()``
snapshots of a window; nothing from a program without the counter; its entry
in ``BENCHMARK.json`` names no cells, so every cell reports it.  CPU, under a
second:  python3 -m pytest benchmark/tests -q
"""

import json
import os

import catalog

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "runner.prefill_padded_share"


def pad(real, padded, **launches):
    return {"prefill_padding": {"real_tokens": real, "padded_tokens": padded,
                                "launches": launches, "groups_in_parts": 0}}


def test_it_reads_the_rise_of_the_two_sums_over_the_window():
    read = catalog.layer_metric_reader(NAME).read
    before, after = pad(5000, 8192), pad(5000 + 7000, 8192 + 10000)
    assert read({"loads_before": before, "loads_after": after}) == 30.0
    assert read({"loads_before": after, "loads_after": after}) is None  # no launch in the window


def test_a_program_without_the_counter_gives_nothing():
    read = catalog.layer_metric_reader(NAME).read
    bare = {"prefill_uploads": {"launches": 3, "arrays": 3}}  # the parent's loads()
    assert read({"loads_before": bare, "loads_after": bare}) is None
    assert read({"loads_before": bare, "loads_after": pad(1, 2)}) is None
    assert read({}) is None


def test_the_entry_is_the_last_names_no_cells_and_agrees_with_the_file():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = bench["per_layer"][-1]
    meta = catalog.layer_metric_reader(NAME).META
    assert entry == {"name": NAME, "unit": meta["unit"], "better": "lower",
                     "source": "program_counter", "layer": meta["layer"],
                     "moves": "output_tok_per_s"}
    assert meta["source"].startswith(entry["source"])
    for cell in (w["name"] for w in bench["workloads"]):
        assert NAME in [m["name"] for m in catalog.metrics_for(bench, cell, "per_layer")]
