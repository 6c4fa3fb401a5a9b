"""Decode rows that attended behind more than ``index_topk`` cached tokens over
all decode rows that attended, between the ``loads()`` snapshots before and
after the window, in percent, for ``glm-5.2.longdoc``: the traffic's own
witness that it reaches the mechanism (a row behind fewer tokens selects all
of them, and the model is then dense latent attention at other numbers).  The
decode frames count both on the device, once a live lane and column
(``loads()["moe"]``: ``dsa_rows``, ``dsa_rows_selecting``).  The prefill rows'
share is on the same ``loads()`` under ``dsa`` (``prefill_rows_selecting`` /
``prefill_rows``, the host's count of the rows launched) and in no metric.  A
program without the counters gives None."""

META = {"layer": "runner", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: loads() moe.dsa_rows_selecting / moe.dsa_rows"}


def read(ctx):
    a, b = ctx["loads_before"].get("moe"), ctx["loads_after"].get("moe")
    if not a or not b or "dsa_rows" not in b:
        return None
    rows = b["dsa_rows"] - a["dsa_rows"]
    return 100.0 * (b["dsa_rows_selecting"] - a["dsa_rows_selecting"]) / rows if rows else None
