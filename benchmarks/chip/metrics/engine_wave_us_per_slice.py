"""Host time of the engine's waves per slice they issued (us/slice), from
the program's spans: `tent.engine.wave` over its `slices` attrs. Moves
`ttft_p50_ms`."""
import span_reduce

WAVE = "tent.engine.wave"


def read(ctx):
    if not ctx.spans:
        return None
    slices = span_reduce.attr_sum(ctx.spans, WAVE, "slices")
    if slices == 0:
        return None
    return span_reduce.seconds(ctx.spans, (WAVE,)) / slices * 1e6
