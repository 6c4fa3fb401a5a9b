"""An architecture is added as one new file and a configuration that names it:
the harness resolves it, ``reference.check_engine`` holds its drive to its
own ``logits`` (the shared verdict: sound is ``ok``; a control inside the
tolerance is not; one row beyond it is not), and both ``kernels.*`` readers
divide by its costs.  Nothing the benchmark has is edited.  CPU, numpy only.
"""

import json
import os
import subprocess
import sys

from conftest import checkout, untouched

# A model nothing like Llama's: the logits after token t are the sum of the
# embeddings of tokens 0..t over sqrt(t + 1), times the embedding table.  Its
# drive keeps the embeddings in a paged pool read through the page tables, as
# a serving path would keep keys and values, and a frame's in a side buffer.
TOY = '''
import numpy as np


def _out(params, rows_sum, count):
    return (rows_sum / np.sqrt(count)) @ params["embed"].T


def logits(params, hf, tokens, rows):
    e = params["embed"][np.asarray(tokens)]
    c = np.cumsum(e, axis=0)
    return np.stack([_out(params, c[t], t + 1) for t in rows]).astype(np.float32)


def impls(runner, rehearsal):
    return ["numpy"]


class Drive:
    def __init__(self, runner, impl, lanes, horizon):
        self.p, self.ps = runner.params, runner.spec.page_size
        self.lanes, self.horizon = lanes, horizon
        self.E = self.p["embed"].shape[1]

    def empty(self, pages):
        return {"pages": np.zeros((pages, self.ps, self.E), np.float32), "side": None,
                "tables": {}}

    def _held(self, pages, table, n):
        pos = np.arange(n)
        return pages[np.asarray(table)[pos // self.ps], pos % self.ps].sum(axis=0)

    def prefill(self, state, seq, chunk, lo, n, table):
        pages = state["pages"].copy()
        pos = lo + np.arange(n)
        pages[np.asarray(table)[pos // self.ps], pos % self.ps] = self.p["embed"][chunk[:n]]
        out = _out(self.p, self._held(pages, table, lo + n), lo + n)
        return out, {**state, "pages": pages, "tables": {**state["tables"], seq: table}}

    def decode(self, state, tokens, positions, entry, column, page_tables):
        side = (np.zeros((self.lanes, self.horizon, self.E), np.float32) if column == 0
                else state["side"].copy())
        V = self.p["embed"].shape[0]
        out = np.zeros((self.lanes, V), np.float32)
        for b in range(self.lanes):
            if entry[b] >= page_tables.shape[1] * self.ps:
                continue  # a padded row
            side[b, column] = self.p["embed"][tokens[b]]
            table = page_tables[b]
            if self.p["mode"] == "blind":  # reads where prefill wrote, whatever it is told
                table = state["tables"][b]
            held = self._held(state["pages"], table, entry[b]) + side[b, :column + 1].sum(axis=0)
            out[b] = _out(self.p, held, positions[b] + 1)
        if self.p["mode"] == "off_row" and column == 2:
            out[1] += 0.5 * out[1].std()
        return out, {**state, "side": side}

    def controls(self, state):
        return {"emptied": {**state, "pages": np.zeros_like(state["pages"])}}


drive = Drive


def param_count(hf):
    n = hf["toy_vocab"] * hf["toy_width"]
    return {"layers": 0, "embed": n, "lm_head": 0, "matmul": n, "total": n}


def kv_bytes_per_token(hf, dtype_bytes=2):
    return hf["toy_width"] * dtype_bytes


def decode_min_seconds(hf, columns, lane_tokens, chips, peak, dtype_bytes=2):
    held = param_count(hf)["matmul"] * dtype_bytes * columns
    return (held + kv_bytes_per_token(hf, dtype_bytes) * lane_tokens) / (
        chips * peak["bytes_per_s"])


def prefill_min_seconds(hf, new_tokens, attn_pairs, chips, peak):
    return 2.0 * hf["toy_width"] * (new_tokens + attn_pairs) / (chips * peak["flops_per_s"])
'''

SCRIPT = '''
import json, sys, types
sys.path.insert(0, "benchmark")
import numpy as np
import catalog, peaks, reference

bench = catalog.load_benchmark()
cell = catalog.Cell(bench, "toy-model.eval")
arch, hf = cell.architecture, cell.hf_config
out = {"seen": catalog.listing()["architectures"], "module": arch.__name__,
       "own_keys_kept_from_the_program": sorted(set(hf) & {"architecture", "reduced", "published"}),
       "old_cell": catalog.Cell(bench, bench["workloads"][0]["name"]).architecture.__name__}

embed = np.random.default_rng(0).standard_normal((hf["vocab_size"], hf["toy_width"]))
for mode in ("sound", "blind", "off_row"):
    runner = types.SimpleNamespace(params={"embed": embed.astype(np.float32), "mode": mode},
                                   spec=types.SimpleNamespace(page_size=16))
    out[mode] = reference.check_engine(types.SimpleNamespace(runner=runner), cell, 11, True)

four = json.load(open("benchmark/tests/data/four_devices.json"))
ctx = {"trace": four, "trace_window": (0.0, 0.0095), "hf": hf, "costs": arch, "chips": 1,
       "device": {"kind": "TPU v5 lite"}, "kv_dtype_bytes": 2,
       # no decode kernel of the program's runs here: the columns run come from the ring
       "steps": [{"kind": "decode", "t": 0.004, "horizon": 8, "columns_run": 4}],
       "requests": [{"first": 0.0, "done": 0.0095, "prompt_tokens": 100, "output_tokens": 10}],
       "timelines": [{"first_token_t": 0.009, "prompt_tokens": 600, "cached_tokens": 100}]}
out["decode_share"] = catalog.layer_metric_reader("kernels.decode_roofline_share").read(ctx)
out["prefill_share"] = catalog.layer_metric_reader("kernels.prefill_roofline_share").read(ctx)
pk = peaks.peaks_for("TPU v5 lite")
out["decode_want"] = 100 * arch.decode_min_seconds(hf, 4, 4 * 105.0, 1, pk, 2) / 0.008
out["prefill_want"] = 100 * arch.prefill_min_seconds(hf, 500, 500 * (100 + 501 / 2.0), 1, pk) / 0.001
try:
    catalog.architecture("llama").param_count(hf)
    out["llama_could_count_it"] = True
except KeyError:
    out["llama_could_count_it"] = False
print(json.dumps(out))
'''


def test_an_architecture_is_one_new_file_and_the_shared_verdict_holds_it(tmp_path):
    root, before = checkout(tmp_path)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "architectures", "toy.py"), "w") as f:
        f.write(TOY)
    json.dump({"architecture": "toy", "vocab_size": 300, "toy_vocab": 300, "toy_width": 24,
               "reduced": ["toy_depth"], "published": {"toy_depth": 32}, "chips": 1,
               "serve_args": []}, open(os.path.join(b, "configs", "toy-model.json"), "w"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "toy-model", "source": "https://example.org/toy",
                             "file": "benchmark/configs/toy-model.json",
                             "reduced": ["toy_depth"], "why": "test"})
    bench["workloads"].append({"name": "toy-model.eval", "config": "toy-model",
                               "traffic": "eval", "chips": 1, "why": "test"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    out = json.loads(subprocess.run([sys.executable, "-c", SCRIPT], cwd=root, check=True,
                                    stdout=subprocess.PIPE, text=True).stdout)
    assert {"llama", "toy"} <= set(out["seen"])  # whatever else later PRs have added
    assert out["module"].endswith("toy") and out["old_cell"].endswith("llama")
    assert out["own_keys_kept_from_the_program"] == []

    tol = out["sound"]["tolerance"]
    sound = out["sound"]
    assert sound["ok"] and sound["worst"] < 1e-3 and list(sound["errors"]) == ["numpy"]
    assert len(sound["errors"]["numpy"]) == 2 + 2 * 4  # two prefills, four steps of two rows
    # the shared control (one wrong page) and the drive's own both miss the tolerance
    assert set(sound["control_errors"]) == {"numpy", "numpy.emptied"}
    assert all(e > tol for e in sound["control_errors"].values())
    # a drive that does not read through the table it is given: every row
    # agrees, and the control agrees too, so the check has not seen the cache
    blind = out["blind"]
    assert blind["worst"] < 1e-3 and blind["control_errors"]["numpy"] < tol and not blind["ok"]
    # one row beyond the tolerance
    off = out["off_row"]
    beyond = [k for k, e in off["errors"]["numpy"].items() if e > tol]
    assert beyond == ["decode[1]+2"] and not off["ok"]
    assert all(e > tol for e in off["control_errors"].values())

    assert out["decode_share"] == out["decode_want"] > 0
    assert out["prefill_share"] == out["prefill_want"] > 0
    assert out["llama_could_count_it"] is False
    untouched(before)
