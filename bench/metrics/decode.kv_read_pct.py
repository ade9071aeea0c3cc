"""Slot programs (``serve/engine.py``): share of a full ``max_len`` read
that decode attention read in the traced window, from the scheduler's
counters: 100 x delta(decode_kv_blocks_read) / delta(decode_kv_blocks_span).
A decode step (or verify window) reads each slot's KV blocks up to its
length where attention is length-bounded (GQA over an unquantized pool),
one block for a masked slot, and all ``max_len / block_len`` blocks where
it gathers (latent attention, int8 KV); both counts sum over slots, steps
and layers, the read one on the device.  None where the program keeps no
such counters or ran no paged decode step.  Moves ``tpot_p95_ms``."""


def read(ctx):
    if "decode_kv_blocks_span" not in ctx.stats0:
        return None
    span = (ctx.stats1["decode_kv_blocks_span"]
            - ctx.stats0["decode_kv_blocks_span"])
    if span <= 0:
        return None
    read = (ctx.stats1["decode_kv_blocks_read"]
            - ctx.stats0["decode_kv_blocks_read"])
    return 100.0 * read / span
