from .model import (
    abstract_params,
    cache_filled_bytes,
    cache_shapes,
    decode_step,
    encode,
    forward,
    init_cache,
    init_params,
    loss_fn,
    param_shapes,
    prefill,
    prefill_forward,
    prefill_path,
    prefill_replay,
)

__all__ = [
    "abstract_params", "cache_filled_bytes", "cache_shapes", "decode_step", "encode",
    "forward", "init_cache", "init_params", "loss_fn", "param_shapes", "prefill",
    "prefill_forward", "prefill_path", "prefill_replay",
]
