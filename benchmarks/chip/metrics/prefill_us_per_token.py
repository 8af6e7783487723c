"""Device time of the prefill program per prompt token (us/token), from the
trace. Moves `ttft_p90_ms`."""
PROGRAM = r"^jit_prefill(\(|$)"


def read(ctx):
    secs, n = ctx.program_seconds(PROGRAM)
    tokens = sum(ctx.batch * c.prompt_len for c in ctx.calls)
    if n == 0 or tokens == 0:
        return None
    return secs / tokens * 1e6
