"""Host time of the engine's transfer per slice it issued (us/slice): the
span of `TentEngine.transfer_sync` over the growth of `slices_issued`.
Moves `ttft_p50_ms`."""


def read(ctx):
    slices = sum(c.slices for c in ctx.calls)
    secs = sum(c.seconds("transfer_sync") + c.seconds("run_until_idle") for c in ctx.calls)
    if slices == 0:
        return None
    return secs / slices * 1e6
