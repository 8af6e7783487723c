"""Host time of the engine's own loop per slice it issued (us/slice), from
the program's spans: `tent.engine.transfer` less the waves and drains
inside it (`tent.engine.wave`, `tent.engine.drain`), over its
`slices_issued` attrs. Moves `ttft_p50_ms`."""
import span_reduce

TRANSFER = "tent.engine.transfer"


def read(ctx):
    if not ctx.spans:
        return None
    slices = span_reduce.attr_sum(ctx.spans, TRANSFER, "slices_issued")
    if slices == 0:
        return None
    secs = span_reduce.self_seconds(ctx.spans, TRANSFER,
                                    ("tent.engine.wave", "tent.engine.drain"))
    return secs / slices * 1e6
