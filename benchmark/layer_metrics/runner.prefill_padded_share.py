"""Tokens the grouped prefills computed that were no row's own, over all they
computed, between the ``loads()`` snapshots before and after the window, in
percent: ``100 x (padded - real) / padded``, where ``padded`` is rows x tokens
of every launch's program, both rounded up (``ModelRunner._group_shape``), and
``real`` the tokens of its rows.  What a launch takes follows its padded
shape, so this is the part of the prefill time that a finer rung, a split
group or packed rows could take back.  A program without the counter (the
parent) gives None."""

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() prefill_padding real_tokens / padded_tokens"}


def read(ctx):
    a = (ctx.get("loads_before") or {}).get("prefill_padding")
    b = (ctx.get("loads_after") or {}).get("prefill_padding")
    if not a or not b:
        return None
    padded = b["padded_tokens"] - a["padded_tokens"]
    real = b["real_tokens"] - a["real_tokens"]
    return 100.0 * (padded - real) / padded if padded else None
