"""Real-compute prefill/decode disaggregation over TENT.

A PrefillWorker runs the real JAX model on the prompt and produces a decode
cache; the cache bytes are shipped to the DecodeWorker's node through one
declarative TENT batch (this is the PD-disaggregation elephant flow); the
DecodeWorker then generates tokens with the real model. Numerically
identical to monolithic generation, by construction and by test; the
examples and tests run it at smoke scale, `chip_smoke.py` at a published
config on a TPU.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core import Location, MemoryKind, TentEngine
from ..models import decode_step, prefill

# Compiled once per (config, shapes); the weights are arguments, never
# constants baked into the program.
prefill_jit = jax.jit(prefill, static_argnums=(0, 3))
decode_step_jit = jax.jit(decode_step, static_argnums=0)


def tree_to_bytes(tree: Any) -> Tuple[np.ndarray, List[Tuple[tuple, str]]]:
    leaves = jax.tree_util.tree_leaves(tree)
    metas = [(l.shape, str(l.dtype)) for l in leaves]
    blobs = [np.ascontiguousarray(np.asarray(l)).view(np.uint8).reshape(-1) for l in leaves]
    return (np.concatenate(blobs) if blobs else np.zeros(0, np.uint8)), metas


def bytes_to_tree(data: np.ndarray, like: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out = []
    off = 0
    for l in leaves:
        nbytes = np.dtype(l.dtype).itemsize * int(np.prod(l.shape)) if l.ndim else np.dtype(l.dtype).itemsize
        arr = data[off : off + nbytes].view(np.dtype(l.dtype) if l.dtype != jnp.bfloat16 else jnp.bfloat16)
        out.append(jnp.asarray(arr.reshape(l.shape)))
        off += nbytes
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass
class DisaggResult:
    tokens: np.ndarray  # (B, n_new)
    kv_transfer_seconds: float
    kv_bytes: int
    kv_segment_id: int  # decode-side segment the cache bytes arrived in


class DisaggregatedServer:
    """Prefill on one node's GPUs, decode on another's, KV over TENT."""

    def __init__(self, engine: TentEngine, cfg: ModelConfig, params: Any,
                 *, prefill_node: int = 0, decode_node: int = 1):
        self.engine = engine
        self.cfg = cfg
        self.params = params
        self.prefill_node = prefill_node
        self.decode_node = decode_node
        spec = engine.topology.spec
        self._loc_p = Location(node=prefill_node, kind=MemoryKind.DEVICE_HBM, device=0,
                               numa=spec.node.gpu_numa(0))
        self._loc_d = Location(node=decode_node, kind=MemoryKind.DEVICE_HBM, device=0,
                               numa=spec.node.gpu_numa(0))

    def ship_kv_async(self, data: np.ndarray, on_done=None) -> Tuple[Any, int]:
        """Declarative KV-handoff intent: post the prefill->decode elephant
        flow as one async TENT batch and return (dst_segment, batch_id)
        immediately — the decode side is woken by the completion callback
        instead of the prefill side blocking on the wire. The closed-loop
        serving simulator and `generate(async_handoff=True)` both ride this.
        """
        nbytes = max(data.size, 1)
        src = self.engine.register_segment(self._loc_p, nbytes, name="kv-src")
        dst = self.engine.register_segment(self._loc_d, nbytes, name="kv-dst")
        src.write(0, data)
        batch = self.engine.allocate_batch()
        self.engine.submit_transfer(
            batch, [(src.segment_id, 0, dst.segment_id, 0, nbytes)])
        if on_done is not None:
            self.engine.on_batch_done(batch, on_done)
        return dst, batch

    def generate(self, prompt: jax.Array, n_new: int, max_len: int,
                 enc_frames: jax.Array | None = None,
                 *, async_handoff: bool = False) -> DisaggResult:
        # ---- prefill pool ----
        last_logits, cache = prefill_jit(self.cfg, self.params, prompt, max_len,
                                         enc_frames=enc_frames)
        # ---- ship the cache through TENT ----
        data, _ = tree_to_bytes(cache)
        t0 = self.engine.fabric.now
        if async_handoff:
            # intent mode: the batch is posted and the decode worker starts
            # when the completion callback lands (here: drain the fabric —
            # the real decode numerics need the full cache)
            done = {}
            dst, _ = self.ship_kv_async(
                data, lambda res: done.setdefault("res", res))
            self.engine.run_until_idle()
            res = done["res"]
        else:
            src = self.engine.register_segment(self._loc_p, max(data.size, 1), name="kv-src")
            dst = self.engine.register_segment(self._loc_d, max(data.size, 1), name="kv-dst")
            src.write(0, data)
            res = self.engine.transfer_sync(src.segment_id, 0, dst.segment_id, 0, max(data.size, 1))
        assert res.ok, res.error
        secs = self.engine.fabric.now - t0
        cache = bytes_to_tree(dst.read(0, data.size), cache)
        # ---- decode pool ----
        return DisaggResult(
            tokens=_greedy_decode(self.cfg, self.params, cache, last_logits,
                                  prompt.shape[1], n_new),
            kv_transfer_seconds=secs,
            kv_bytes=int(data.size),
            kv_segment_id=dst.segment_id,
        )


def _greedy_decode(cfg: ModelConfig, params: Any, cache: Any, last_logits: jax.Array,
                   start: int, n_new: int) -> np.ndarray:
    """`n_new` greedy tokens: the argmax of the prefill logits, then one
    decode step per further token from position `start`."""
    tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
    out = [np.asarray(tok)]
    for i in range(n_new - 1):
        logits, cache = decode_step_jit(cfg, params, cache, tok, jnp.int32(start + i))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok))
    return np.concatenate(out, axis=1)


def monolithic_generate(cfg: ModelConfig, params: Any, prompt: jax.Array, n_new: int,
                        max_len: int, enc_frames: jax.Array | None = None) -> np.ndarray:
    last_logits, cache = prefill_jit(cfg, params, prompt, max_len, enc_frames=enc_frames)
    return _greedy_decode(cfg, params, cache, last_logits, prompt.shape[1], n_new)
