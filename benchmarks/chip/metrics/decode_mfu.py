"""The decode phase's share of the chip's bf16 peak (%): the operations
every decode step needs (`arch.decode_flops` at the positions it filled)
over the host time from each call's first decode step to its last token on
the host. Moves `tpot_p90_ms`."""


def read(ctx):
    flops, secs = 0, 0.0
    for c in ctx.calls:
        if not c.decode_starts:
            continue
        flops += sum(ctx.batch * ctx.arch.decode_flops(ctx.dims, c.prompt_len + i)
                     for i in range(len(c.decode_starts)))
        secs += c.t_done - c.decode_starts[0]
    if secs <= 0 or flops == 0:
        return None
    return flops / (secs * ctx.peaks["bf16_flops_per_s"]) * 100
