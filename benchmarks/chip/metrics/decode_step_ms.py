"""Device time of the decode program per step (ms), from the trace. Moves
`tpot_p90_ms`."""
PROGRAM = r"^jit_decode_step(\(|$)"


def read(ctx):
    secs, n = ctx.program_seconds(PROGRAM)
    if n == 0:
        return None
    return secs / n * 1e3
