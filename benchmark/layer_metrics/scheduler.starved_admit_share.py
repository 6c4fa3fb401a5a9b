"""The part of ``scheduler.chip_starved_share`` that fell inside the prefill
phase (``smg.step.admit``: the queue, the radix match, pages and eviction,
the packing of a prefill's operands and its dispatch), in percent of the
window: ``starved_admit_s`` of the step records, with the bias that
``_step_account`` states.  A program whose step records carry no account
gives nothing to read."""

from _step_account import share

META = {"layer": "scheduler", "unit": "%", "moves": "output_tok_per_s",
        "source": "program_counter: flight recorder step ring (starved_admit_s)"}


def read(ctx):
    return share(ctx, "starved_admit_s")
