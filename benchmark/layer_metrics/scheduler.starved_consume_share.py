"""The part of ``scheduler.chip_starved_share`` that fell inside the consume
phase (``smg.step.consume``) after the fetch had returned: the host's
acceptance of a frame's tokens with no launch behind the frame, in percent
of the window: ``starved_consume_s`` of the step records, with the bias that
``_step_account`` states.  A program whose step records carry no account
gives nothing to read."""

from _step_account import share

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: flight recorder step ring (starved_consume_s)"}


def read(ctx):
    return share(ctx, "starved_consume_s")
