"""The 95th percentile over requests of the time per output token after the first, on the client's clock.  Recorded, not
judged: under a closed loop at full load it swings with how requests happen
to fall against the megastep in flight (PERF.md, Findings, PR 24)."""

from _common import caller_latency

META = {"layer": "caller", "unit": "ms", "moves": "output_tok_per_s", "source": "host_clock"}


def read(ctx):
    return caller_latency(ctx, 1, 0.95)
