"""Seconds in which the host knew the chip had nothing queued (``starved_s``
of the step records: inside the step's three phases, between them and in the
gap before the step) over the window, in percent.  The whole-window
counterpart of ``device.idle_share``, which reads the traced 6 s; it errs
low (``_step_account``).  A program whose step records carry no account
gives nothing to read."""

from _step_account import share

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: flight recorder step ring (starved_s)"}


def read(ctx):
    return share(ctx, "starved_s")
