"""Model step, whole (``models/transformer.py``): FLOPs required by every
prompt prefilled and every token decoded in the traced window
(``work.window_flops``, from the request records), over the window's
seconds times the chip's bf16 peak.  Moves ``output_tok_s``."""
from bench import work


def read(ctx):
    pre, dec = work.window_flops(ctx.shapes, ctx.records, ctx.t0, ctx.t1)
    if pre + dec <= 0 or ctx.reduced.window_s <= 0:
        return None
    return 100.0 * (pre + dec) / (ctx.reduced.window_s * ctx.peaks["bf16_flop_s"])
