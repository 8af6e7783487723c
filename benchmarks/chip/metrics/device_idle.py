"""Share of the traced window in which no operation ran on the device (%):
1 - union of device-op intervals / window. Moves `output_tok_s`."""


def read(ctx):
    lo, hi = ctx.window
    if hi <= lo or not ctx.trace.ops:
        return None
    return (1 - ctx.busy_seconds() / (hi - lo)) * 100
