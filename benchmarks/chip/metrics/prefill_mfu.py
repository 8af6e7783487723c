"""The prefill calls' share of the chip's bf16 peak (%): the operations the
prompts need (`arch.prefill_flops`, from the configuration's shapes) over
the host spans of the prefill calls, each ending in a synchronised array.
Moves `ttft_p90_ms`."""


def read(ctx):
    flops = sum(ctx.batch * ctx.arch.prefill_flops(ctx.dims, c.prompt_len)
                for c in ctx.calls)
    secs = sum(c.seconds("prefill_jit") for c in ctx.calls)
    if secs <= 0 or flops == 0:
        return None
    return flops / (secs * ctx.peaks["bf16_flops_per_s"]) * 100
