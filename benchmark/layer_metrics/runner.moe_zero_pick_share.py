"""Token-expert pairs that fell on identity experts (the router's outputs that
compute nothing and add the weighted token itself) over all pairs routed,
between the ``loads()`` snapshots before and after the window, in percent.
Identity outputs over the router's width is the cell working as meant (256 of
768: 33.3 % under seeded random routers); a drift says that the router, the
weights or the traffic changed.  A program without the counter (the parent, a
model without identity experts) gives None."""

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() moe.picks_zero / moe.picks"}


def read(ctx):
    a, b = ctx["loads_before"].get("moe"), ctx["loads_after"].get("moe")
    if not a or not b or "picks_zero" not in a or "picks_zero" not in b:
        return None
    picks = b["picks"] - a["picks"]
    return 100.0 * (b["picks_zero"] - a["picks_zero"]) / picks if picks else None
