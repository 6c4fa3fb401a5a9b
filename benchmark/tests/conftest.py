import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def checkout(tmp_path) -> tuple:
    """A checkout as a run sees one: ``BENCHMARK.json`` and ``benchmark/`` under
    ``tmp_path``.  Returns its root and every file's bytes, for ``untouched``."""
    import shutil

    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    return root, before


def untouched(before: dict) -> None:
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} had to be edited"
