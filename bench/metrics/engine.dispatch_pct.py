"""Slot programs (``serve/engine.py``): share of the traced window the host
spent dispatching the prefill and segment programs (argument uploads and
the launch, up to the call's return), from the scheduler's phase counters:
100 x delta(dispatch_s_prefill + dispatch_s_segment) / (t1 - t0).  None
when the program keeps no such counters.  Moves ``output_tok_s``."""

KEYS = ("dispatch_s_prefill", "dispatch_s_segment")


def read(ctx):
    if ctx.t1 <= ctx.t0 or not all(k in ctx.stats0 for k in KEYS):
        return None
    spent = sum(ctx.stats1[k] - ctx.stats0[k] for k in KEYS)
    return 100.0 * spent / (ctx.t1 - ctx.t0)
