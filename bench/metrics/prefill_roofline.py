"""Slot programs, chunked prefill (``serve/engine.py`` ``prefill_slots``):
the least time the chip could take for the traced window's prefill launches
(``work.prefill`` per launch: real tokens only, logits at each row's last
token, weights in the configuration's format) over their device time in the
trace.  A chunk of 256 or more real tokens is bound by FLOPs, a launch of a
few short rows by bytes.  Moves ``output_tok_s``.

``MODULES`` names the XLA modules of the prefill program as the trace
shows them (the jitted function's name)."""
from bench import work

MODULES = ("jit_prefill_slots",)


def read(ctx):
    device_s = sum(ctx.reduced.module_s.get(m, 0.0) for m in MODULES)
    if device_s <= 0 or not ctx.launches.prefill:
        return None
    need = sum(work.roofline_s(*work.prefill(ctx.shapes, rows), ctx.peaks)
               for rows in ctx.launches.prefill)
    return 100.0 * need / device_s
