"""Prompt tokens served from the radix cache over all prompt tokens admitted,
between the loads() snapshots before and after the window, in percent."""

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() cached_prompt_tokens / computed_prompt_tokens"}


def read(ctx):
    a, b = ctx["loads_before"], ctx["loads_after"]
    cached = b["cached_prompt_tokens"] - a["cached_prompt_tokens"]
    computed = b["computed_prompt_tokens"] - a["computed_prompt_tokens"]
    return 100.0 * cached / (cached + computed) if cached + computed else None
