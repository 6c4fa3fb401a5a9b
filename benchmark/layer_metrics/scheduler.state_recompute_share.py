"""Prompt tokens prefilled a second time because a sequence lost its recurrent
state (a preemption, or a discarded decode frame that had advanced it) over all
prompt tokens prefilled, between the loads() snapshots before and after the
window, in percent.  0 is the cell working as meant.  A program without state
slots has no such counter and gives None."""

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() state_recomputed_tokens / computed_prompt_tokens"}


def read(ctx):
    a, b = ctx["loads_before"], ctx["loads_after"]
    if "state_recomputed_tokens" not in b:
        return None
    again = b["state_recomputed_tokens"] - a.get("state_recomputed_tokens", 0)
    computed = b["computed_prompt_tokens"] - a["computed_prompt_tokens"]
    return 100.0 * again / computed if computed else None
