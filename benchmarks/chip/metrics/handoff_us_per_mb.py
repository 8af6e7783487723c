"""Host time of the KV handoff per MB it ships (us/MB), from the
program's spans: `tent.kv.pack`, `tent.kv.segments`, `tent.kv.spray`,
`tent.kv.read` and `tent.kv.unpack`, over the MB (1e6 B) that the `bytes`
attrs of `tent.kv.pack` sum to. Every call of a cell ships all `max_len`
positions, the same bytes, so the reading does not depend on which calls
the traced window holds; it compares the handoff's cost per byte across
KV widths. None where no `tent.kv.pack` span carries `bytes` (a program
without the attr). Moves `ttft_p50_ms`."""
import span_reduce

PACK = "tent.kv.pack"
HANDOFF = (PACK, "tent.kv.segments", "tent.kv.spray", "tent.kv.read", "tent.kv.unpack")


def read(ctx):
    if not ctx.spans or not any(s[0] == PACK and "bytes" in s[5] for s in ctx.spans):
        return None
    shipped = span_reduce.attr_sum(ctx.spans, PACK, "bytes")
    if shipped == 0:
        return None
    return span_reduce.seconds(ctx.spans, HANDOFF) * 1e6 / (shipped / 1e6)
