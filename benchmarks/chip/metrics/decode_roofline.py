"""The decode program's share of its roofline (%): for every step, the least
time the chip could take, max(operations / peak FLOP/s, bytes / HBM
bandwidth), with the bytes every weight plus the cache positions actually
filled (`arch.decode_bytes`), summed, over the decode program's device time
in the trace. Moves `tpot_p90_ms`."""
PROGRAM = r"^jit_decode_step(\(|$)"


def read(ctx):
    secs, n = ctx.program_seconds(PROGRAM)
    steps = sum(len(c.decode_starts) for c in ctx.calls)
    if n == 0 or secs <= 0 or n != steps:
        return None
    f_peak, b_peak = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    least = 0.0
    for c in ctx.calls:
        for i in range(len(c.decode_starts)):
            pos = c.prompt_len + i
            least += max(ctx.batch * ctx.arch.decode_flops(ctx.dims, pos) / f_peak,
                         ctx.arch.decode_bytes(ctx.dims, pos, ctx.batch) / b_peak)
    return least / secs * 100
