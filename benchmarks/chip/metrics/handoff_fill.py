"""Share of the KV handoff's bytes that the prefill wrote (%), from the
program's spans: the `filled_bytes` attrs of `tent.kv.pack` over its
`bytes` attrs, summed over the traced window's calls. A call ships all
`max_len` positions whatever its prompt, so the share depends on the
prompts of the calls the window holds: over a whole block it is the mix's
mean prompt length over `max_len`, over a shorter window it moves with the
window's share of long prompts. A handoff that shipped only the filled
positions would read 100. None where no `tent.kv.pack` span carries
`bytes` and `filled_bytes` (a program without the attrs). Moves
`ttft_p50_ms`."""
import span_reduce

PACK = "tent.kv.pack"


def read(ctx):
    if not ctx.spans or not any(s[0] == PACK and "bytes" in s[5] and "filled_bytes" in s[5]
                                for s in ctx.spans):
        return None
    shipped = span_reduce.attr_sum(ctx.spans, PACK, "bytes")
    if shipped == 0:
        return None
    return span_reduce.attr_sum(ctx.spans, PACK, "filled_bytes") / shipped * 100
