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
from ..models import cache_filled_bytes, decode_step, prefill, prefill_path
from ..obs.spans import span

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
        self._spans = None  # repro.obs.spans.HostSpans; None = off

    def attach_spans(self, rec) -> None:
        """Attach a `repro.obs.spans.HostSpans` (None detaches) to this
        server and its engine; `generate` then records the spans its
        docstring lists."""
        self._spans = rec
        self.engine.attach_spans(rec)

    def _kv_segments(self, data: np.ndarray):
        """Register the handoff's source and destination segments and
        write the cache bytes into the source."""
        nbytes = max(data.size, 1)
        src = self.engine.register_segment(self._loc_p, nbytes, name="kv-src")
        dst = self.engine.register_segment(self._loc_d, nbytes, name="kv-dst")
        src.write(0, data)
        return src, dst, nbytes

    def ship_kv_async(self, data: np.ndarray, on_done=None) -> Tuple[Any, int]:
        """Declarative KV-handoff intent: post the prefill->decode elephant
        flow as one async TENT batch and return (dst_segment, batch_id)
        immediately — the decode side is woken by the completion callback
        instead of the prefill side blocking on the wire. The closed-loop
        serving simulator and `generate(async_handoff=True)` both ride this.
        """
        src, dst, nbytes = self._kv_segments(data)
        batch = self.engine.allocate_batch()
        self.engine.submit_transfer(
            batch, [(src.segment_id, 0, dst.segment_id, 0, nbytes)])
        if on_done is not None:
            self.engine.on_batch_done(batch, on_done)
        return dst, batch

    def generate(self, prompt: jax.Array, n_new: int, max_len: int,
                 enc_frames: jax.Array | None = None,
                 *, async_handoff: bool = False) -> DisaggResult:
        """Prefill, ship the cache through TENT, decode `n_new` tokens.

        With a `HostSpans` attached (`attach_spans`) one call records
        `tent.generate` (attrs `call`, `batch`, `prompt_len`, `n_new`) and,
        in order, its children:

        - `tent.prefill` (attr `path`, `prefill_path(cfg)`: which prefill
          served the call): the dispatch of the prefill program.
          Asynchronous: the device may still run it when the span closes,
          and the wait then lands in `tent.kv.pack`.
        - `tent.kv.pack` (attrs `bytes`, the size of `tree_to_bytes`'s
          output, which is `DisaggResult.kv_bytes`, and `filled_bytes`,
          the part of those bytes that the prefill wrote, from the
          cache's shapes by `cache_filled_bytes`): `tree_to_bytes`, the
          device-to-host copy of the cache, all `max_len` positions of it.
        - `tent.kv.segments`: both segments registered and the bytes
          written into the source (with `async_handoff`, all of
          `ship_kv_async`, which also posts the batch's first wave).
        - `tent.kv.spray`: `transfer_sync` (or `run_until_idle`), holding
          the engine's `tent.engine.transfer`.
        - `tent.kv.read`: the bytes read back from the decode segment.
        - `tent.kv.unpack`: `bytes_to_tree`. Asynchronous: it returns once
          the host-to-device copies are enqueued.
        - `tent.decode`: the greedy decode, with `tent.decode.step` and
          `tent.decode.fetch` per step (see `_greedy_decode`).
        """
        sp = self._spans
        with span(sp, "tent.generate", new_call=True, batch=int(prompt.shape[0]),
                  prompt_len=int(prompt.shape[1]), n_new=int(n_new)):
            # ---- prefill pool ----
            with span(sp, "tent.prefill", path=prefill_path(self.cfg)):
                last_logits, cache = prefill_jit(self.cfg, self.params, prompt, max_len,
                                                 enc_frames=enc_frames)
            # ---- ship the cache through TENT ----
            with span(sp, "tent.kv.pack") as pack:
                data, _ = tree_to_bytes(cache)
                if pack is not None:
                    pack["bytes"] = int(data.size)
                    pack["filled_bytes"] = cache_filled_bytes(cache, prompt.shape[1])
            t0 = self.engine.fabric.now
            if async_handoff:
                # intent mode: the batch is posted and the decode worker
                # starts when the completion callback lands (here: drain the
                # fabric — the real decode numerics need the full cache)
                done = {}
                with span(sp, "tent.kv.segments"):
                    dst, _ = self.ship_kv_async(
                        data, lambda res: done.setdefault("res", res))
                with span(sp, "tent.kv.spray"):
                    self.engine.run_until_idle()
                res = done["res"]
            else:
                with span(sp, "tent.kv.segments"):
                    src, dst, nbytes = self._kv_segments(data)
                with span(sp, "tent.kv.spray"):
                    res = self.engine.transfer_sync(src.segment_id, 0, dst.segment_id,
                                                    0, nbytes)
            assert res.ok, res.error
            secs = self.engine.fabric.now - t0
            with span(sp, "tent.kv.read"):
                shipped = dst.read(0, data.size)
            with span(sp, "tent.kv.unpack"):
                cache = bytes_to_tree(shipped, cache)
            # ---- decode pool ----
            with span(sp, "tent.decode"):
                tokens = _greedy_decode(self.cfg, self.params, cache, last_logits,
                                        prompt.shape[1], n_new, spans=sp)
        return DisaggResult(
            tokens=tokens,
            kv_transfer_seconds=secs,
            kv_bytes=int(data.size),
            kv_segment_id=dst.segment_id,
        )


def _greedy_decode(cfg: ModelConfig, params: Any, cache: Any, last_logits: jax.Array,
                   start: int, n_new: int, *, spans=None) -> np.ndarray:
    """`n_new` greedy tokens: the argmax of the prefill logits, then one
    decode step per further token from position `start`. With `spans`, each
    step records `tent.decode.step` (the dispatch of the decode program and
    the argmax, asynchronous) and `tent.decode.fetch` (the token copied to
    the host, which waits for the device to finish the step)."""
    tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
    out = [np.asarray(tok)]
    for i in range(n_new - 1):
        with span(spans, "tent.decode.step"):
            logits, cache = decode_step_jit(cfg, params, cache, tok, jnp.int32(start + i))
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        with span(spans, "tent.decode.fetch"):
            out.append(np.asarray(tok))
    return np.concatenate(out, axis=1)


def monolithic_generate(cfg: ModelConfig, params: Any, prompt: jax.Array, n_new: int,
                        max_len: int, enc_frames: jax.Array | None = None) -> np.ndarray:
    last_logits, cache = prefill_jit(cfg, params, prompt, max_len, enc_frames=enc_frames)
    return _greedy_decode(cfg, params, cache, last_logits, prompt.shape[1], n_new)
