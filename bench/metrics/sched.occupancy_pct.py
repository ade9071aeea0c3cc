"""Scheduler (``serve/scheduler.py``): share of slot-steps in the traced
window that decoded a live request, from the program's own counters
``ContinuousScheduler.stats``: slot_steps_live / (slot_steps_live +
slot_steps_masked).  Moves ``output_tok_s``."""


def read(ctx):
    live = ctx.stats1["slot_steps_live"] - ctx.stats0["slot_steps_live"]
    masked = ctx.stats1["slot_steps_masked"] - ctx.stats0["slot_steps_masked"]
    if live + masked == 0:
        return None
    return 100.0 * live / (live + masked)
