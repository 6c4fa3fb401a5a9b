"""Median over requests of the client's time to first token (first content
delta minus send) minus the engine's (first output minus submit, stamped by
run.py's wrapper around the in-process worker client's call into the
engine): what the gateway, the tokenizer, HTTP and the hop between the
engine's thread and the event loop add."""

from _common import median

META = {"layer": "gateway", "unit": "ms", "moves": "output_tok_per_s",
        "source": "program_span: client stamps and run.py's engine.submit wrapper"}


def read(ctx):
    added = []
    for r in ctx["requests"]:
        stamp = ctx["stamps"].get(r["id"])
        if r["first"] is None or r["sent"] is None or not stamp or stamp[1] is None:
            continue
        added.append(((r["first"] - r["sent"]) - (stamp[1] - stamp[0])) * 1e3)
    return median(added)
