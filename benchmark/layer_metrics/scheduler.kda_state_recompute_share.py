"""``scheduler.state_recompute_share`` for ``kimi-linear-48b-a3b.reason``: prompt
tokens prefilled a second time because a sequence lost its KDA state (a
preemption, or a discarded decode frame that had advanced it) over all prompt
tokens prefilled, in percent; 0 is the cell working as meant.  This file hands
the cell's context to that reader and adds no arithmetic, until a ``benchmark``
PR appends the cell to that metric's ``workloads`` (ROADMAP T11), which then
folds this file in.  Another architecture gives None."""

from _common import bench_module

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() state_recomputed_tokens / computed_prompt_tokens, "
                  "by the reader of scheduler.state_recompute_share"}


def read(ctx):
    if ctx["hf"].get("model_type") != "kimi_linear":
        return None
    reader = bench_module("catalog").layer_metric_reader("scheduler.state_recompute_share")
    return reader.read(ctx)
