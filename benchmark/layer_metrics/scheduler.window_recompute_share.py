"""Prompt tokens prefilled a second time because a sequence lost what it held
for its window layers (a preemption: the slot goes with the pages, and no copy
of a ring is kept) over all prompt tokens prefilled, between the loads()
snapshots before and after the window, in percent.  0 is the cell working as
meant: the pool and the slots held every sequence admitted; a reading above 0
says one of them ran short and prompts were prefilled twice.  A program
without window slots has no such counter and gives None."""

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() window_recomputed_tokens / computed_prompt_tokens"}


def read(ctx):
    a, b = ctx["loads_before"], ctx["loads_after"]
    if "window_recomputed_tokens" not in b:
        return None
    again = b["window_recomputed_tokens"] - a.get("window_recomputed_tokens", 0)
    computed = b["computed_prompt_tokens"] - a["computed_prompt_tokens"]
    return 100.0 * again / computed if computed else None
