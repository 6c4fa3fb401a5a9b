"""``runner.moe_held_pick_share`` for ``glm-5.2.longdoc``: the token-expert
pairs on experts this process holds over all pairs routed (16 of the router's
256 outputs: 6.25 % under seeded random routers is the cut being what was
served).  This file hands the cell's context to that reader and adds no
arithmetic, until a ``benchmark`` PR appends the cell to that metric's
``workloads`` (ROADMAP T11), which then folds this file in.  Another
architecture gives None."""

from _common import bench_module
from _dsa import is_cell

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() moe.picks_held / moe.picks, by the reader of "
                  "runner.moe_held_pick_share"}


def read(ctx):
    if not is_cell(ctx):
        return None
    reader = bench_module("catalog").layer_metric_reader("runner.moe_held_pick_share")
    return reader.read(ctx)
