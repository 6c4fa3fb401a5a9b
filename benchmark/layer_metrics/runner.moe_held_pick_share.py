"""Token-expert pairs that fell on experts this process holds over all pairs
routed, between the ``loads()`` snapshots before and after the window, in
percent.  Held over the router's width is the cell working as meant (16 of
256: 6.25 % under seeded random routers); a drift says that the cut, the
weights or the traffic changed.  A program without the counter gives None."""

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() moe.picks_held / moe.picks"}


def read(ctx):
    a, b = ctx["loads_before"].get("moe"), ctx["loads_after"].get("moe")
    if not a or not b:
        return None
    picks = b["picks"] - a["picks"]
    return 100.0 * (b["picks_held"] - a["picks_held"]) / picks if picks else None
