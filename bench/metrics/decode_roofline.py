"""Slot programs, decode segments (``serve/engine.py`` ``segment``): the
least time the chip could take for the traced window's decode steps
(``work.decode`` per segment: weights once a step in the configuration's
format, KV at each emitted token's actual context) over their device time
in the trace.  Bound by bytes at these batch sizes.  Moves ``tpot_p95_ms``.

``MODULES`` names the XLA modules of the segment programs as the trace shows
them (the jitted function's name)."""
from bench import work

MODULES = ("jit_segment",)


def read(ctx):
    device_s = sum(ctx.reduced.module_s.get(m, 0.0) for m in MODULES)
    if device_s <= 0 or not ctx.launches.segments:
        return None
    need = sum(work.roofline_s(*work.decode(ctx.shapes, steps, contexts), ctx.peaks)
               for steps, contexts in ctx.launches.segments if contexts)
    return 100.0 * need / device_s
