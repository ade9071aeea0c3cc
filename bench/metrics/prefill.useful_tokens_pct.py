"""Slot programs (``serve/engine.py``): share of the prefill tokens
launched in the traced window that were real prompt tokens, from the
scheduler's counters: 100 x delta(prefill_tokens_real) /
delta(prefill_tokens_launched), where a launch counts width x bucket
tokens (dummy rows and bucket padding included).  None when no prefill
launched or the program keeps no such counters.  Moves ``output_tok_s``."""


def read(ctx):
    if "prefill_tokens_launched" not in ctx.stats0:
        return None
    launched = (ctx.stats1["prefill_tokens_launched"]
                - ctx.stats0["prefill_tokens_launched"])
    if launched <= 0:
        return None
    real = ctx.stats1["prefill_tokens_real"] - ctx.stats0["prefill_tokens_real"]
    return 100.0 * real / launched
