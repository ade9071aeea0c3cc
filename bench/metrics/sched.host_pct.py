"""Scheduler (``serve/scheduler.py``): share of the traced window the host
spent in ``ContinuousScheduler.run_segment``'s own work, from the program's
phase counters (seconds of self time in its ``serve.*`` spans): 100 x
delta(host_s_sweep + host_s_admit + host_s_grow + host_s_retire) /
(t1 - t0).  Program dispatch and the device waits are not counted.  None
when the program keeps no such counters.  Moves ``output_tok_s``."""

KEYS = ("host_s_sweep", "host_s_admit", "host_s_grow", "host_s_retire")


def read(ctx):
    if ctx.t1 <= ctx.t0 or not all(k in ctx.stats0 for k in KEYS):
        return None
    spent = sum(ctx.stats1[k] - ctx.stats0[k] for k in KEYS)
    return 100.0 * spent / (ctx.t1 - ctx.t0)
