"""The part of ``scheduler.chip_starved_share`` that fell inside a decode
frame's launch (``smg.step.launch``: the horizon, the decode state's build
and the dispatch, up to its return), in percent of the window:
``starved_launch_s`` of the step records, with the bias that
``_step_account`` states.  A program whose step records carry no account
gives nothing to read."""

from _step_account import share

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: flight recorder step ring (starved_launch_s)"}


def read(ctx):
    return share(ctx, "starved_launch_s")
