"""Host time per call of the KV handoff's copies (ms): `tree_to_bytes`
(device to host) plus `bytes_to_tree` (host to device, ending in a
synchronised array). Moves `ttft_p50_ms`."""


def read(ctx):
    if not ctx.calls:
        return None
    secs = sum(c.seconds("tree_to_bytes") + c.seconds("bytes_to_tree") for c in ctx.calls)
    return secs / len(ctx.calls) * 1e3
