"""Host time per call of the engine's batched drains (ms), from the
program's spans: `tent.engine.drain` (where the slices' bytes are copied)
less the waves issued inside it (`tent.engine.wave`), over the calls
(`tent.generate`). Moves `ttft_p50_ms`."""
import span_reduce


def read(ctx):
    if not ctx.spans:
        return None
    n = span_reduce.calls(ctx.spans)
    secs = span_reduce.self_seconds(ctx.spans, "tent.engine.drain", ("tent.engine.wave",))
    if n == 0 or secs <= 0:
        return None
    return secs / n * 1e3
