"""Mean ``running`` over the decode and mixed records of the flight
recorder's step ring inside the window (the occupancy gauge keeps only its
last value)."""

from _common import decode_records

META = {"layer": "scheduler", "unit": "lanes", "moves": "output_tok_per_s",
        "source": "program_counter: flight recorder step ring"}


def read(ctx):
    recs = decode_records(ctx, ctx["window"])
    return sum(s["running"] for s in recs) / len(recs) if recs else None
