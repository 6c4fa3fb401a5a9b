"""Finds the benchmark's parts by name.  ``BENCHMARK.json`` at the root of the
checkout lists cells, configurations and metrics; everything that belongs to
one of them is a file of its own under ``benchmark/``:

    configs/<config>.json          the model's config.json plus serve flags
    traffic/<traffic>.json         parameters for one of generators/<kind>.py
    layer_metrics/<metric>.py      META and read(ctx) for one per-layer metric
    architectures/<name>.py        reference, serving drive and costs of one
                                   architecture; a configuration names it with
                                   its ``architecture`` key (``llama`` without)

so a later PR adds a cell, a configuration, a traffic mix, a metric or an
architecture as new files and a new entry, and edits nothing that exists.
Standard library only.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CatalogError(Exception):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise CatalogError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise CatalogError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def _module(path: str, kind: str, name: str):
    if not os.path.isfile(path):
        raise CatalogError(f"no module {path}")
    name = f"benchmark_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files."""

    def __init__(self, bench: dict, name: str, root: str = ROOT, rehearsal: bool = False):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise CatalogError(f"unknown workload {name!r}; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(root, self.config_entry["file"]),
                                 f"configuration {self.entry['config']}")
        self.traffic = _load_json(
            os.path.join(root, "benchmark", "traffic", self.entry["traffic"] + ".json"),
            f"traffic {self.entry['traffic']}")
        if rehearsal:
            self.config = {**self.config, **self.config.get("rehearsal", {})}
            self.traffic = {**self.traffic, **self.traffic.get("rehearsal", {})}
        self.rehearsal = rehearsal
        self.root = root

    @property
    def hf_config(self) -> dict:
        """The model's config.json as the program's ``from_hf_config`` reads it."""
        own = {"assumed", "deployment", "chips", "serve_args", "rehearsal", "status",
               "architecture", "reduced", "published"}
        return {k: v for k, v in self.config.items() if k not in own}

    @functools.cached_property
    def architecture(self):
        """The module ``architectures/<name>.py`` this configuration names."""
        return architecture(self.config.get("architecture", "llama"), self.root)

    @property
    def serve_args(self) -> list[str]:
        return list(self.config["serve_args"])

    def chains(self, seed: int, seconds: float) -> list[dict]:
        """This cell's traffic for one seed (see generators/__init__.py)."""
        return load_generator(self.traffic["generator"], self.root).chains(
            self.traffic, seed, seconds)


def load_generator(kind: str, root: str = ROOT):
    """``benchmark/generators/<kind>.py`` of the checkout at ``root``."""
    import importlib
    import sys

    bench = os.path.join(root, "benchmark")
    if not os.path.isfile(os.path.join(bench, "generators", kind + ".py")):
        raise CatalogError(f"no generator {kind!r} in {bench}/generators")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(f"generators.{kind}")


def architecture(name: str, root: str = ROOT):
    """``benchmark/architectures/<name>.py`` of the checkout at ``root``: the
    plain reference, the drive of the serving forward and the costs of one
    architecture (README, "An architecture")."""
    path = os.path.join(root, "benchmark", "architectures", name + ".py")
    if not os.path.isfile(path):
        raise CatalogError(f"no architecture {name!r}: no file {path}")
    return _module(path, "architecture", name)


def metrics_for(bench: dict, cell: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def layer_metric_reader(name: str, root: str = ROOT):
    """The reader module of one per-layer metric, or None when it has none
    (the metric is then left out of the line)."""
    path = os.path.join(root, "benchmark", "layer_metrics", name + ".py")
    if not os.path.isfile(path):
        return None
    import sys

    if os.path.dirname(path) not in sys.path:
        sys.path.insert(0, os.path.dirname(path))  # the readers share _common.py
    return _module(path, "layer_metric", name)


def listing(root: str = ROOT) -> dict:
    """What the harness can see: for ``run.py --list`` and the tests."""
    bench = load_benchmark(root)
    bdir = os.path.join(root, "benchmark")

    def stems(sub, ext):
        d = os.path.join(bdir, sub)
        return sorted(f[: -len(ext)] for f in os.listdir(d)
                      if f.endswith(ext) and not f.startswith("_")) if os.path.isdir(d) else []

    return {
        "workloads": [w["name"] for w in bench["workloads"]],
        "configs": [c["name"] for c in bench["configs"]],
        "config_files": stems("configs", ".json"),
        "traffic": stems("traffic", ".json"),
        "generators": [g for g in stems("generators", ".py") if g != "common"],
        "architectures": stems("architectures", ".py"),
        "layer_metrics": stems("layer_metrics", ".py"),
        "end_to_end": [m["name"] for m in bench["end_to_end"]],
        "per_layer": [m["name"] for m in bench["per_layer"]],
    }
