"""Device (TPU v5e): share of the traced window in which no operation ran
on the device, 1 - (union of device-op intervals) / window, from the
profiler trace.  Moves ``output_tok_s``."""


def read(ctx):
    if ctx.reduced.window_s <= 0 or ctx.reduced.n_ops == 0:
        return None
    return 100.0 * (1.0 - ctx.reduced.busy_s / ctx.reduced.window_s)
