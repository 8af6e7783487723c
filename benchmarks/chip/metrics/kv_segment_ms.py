"""Host time per call of the handoff's segment work (ms), from the
program's spans: `tent.kv.segments` (both segments registered and the
cache's bytes written into the source) plus `tent.kv.read` (the bytes read
back from the decode segment), over the calls (`tent.generate`). Moves
`ttft_p50_ms`."""
import span_reduce


def read(ctx):
    if not ctx.spans:
        return None
    n = span_reduce.calls(ctx.spans)
    secs = span_reduce.seconds(ctx.spans, ("tent.kv.segments", "tent.kv.read"))
    if n == 0 or secs <= 0:
        return None
    return secs / n * 1e3
