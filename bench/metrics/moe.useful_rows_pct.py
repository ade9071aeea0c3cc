"""Expert layer (``models/moe.py``): share of the rows the held-expert
layers launched in the traced window that held a real token's assignment
to an expert held here, from the scheduler's counters: 100 x
delta(moe_rows_held) / delta(moe_rows_computed).  A forward over T tokens
launches T x experts-per-token rows a MoE layer (bucket padding, masked
slots and the assignments to experts held elsewhere included); the held
count is reduced on the device.  A dense FFN counts as an expert layer
that holds its one expert.  None where the program keeps no such counters
or launched no such row.  Moves ``output_tok_s``."""


def read(ctx):
    if "moe_rows_computed" not in ctx.stats0:
        return None
    computed = (ctx.stats1["moe_rows_computed"]
                - ctx.stats0["moe_rows_computed"])
    if computed <= 0:
        return None
    held = ctx.stats1["moe_rows_held"] - ctx.stats0["moe_rows_held"]
    return 100.0 * held / computed
