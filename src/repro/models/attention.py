"""Grouped-query attention: full/sliding-window prefill and cached decode.

Pure-jnp paths (XLA) are the default — they are what the multi-pod dry-run
lowers. When `cfg.use_pallas` is set, the prefill path dispatches to the
Pallas flash-attention kernel (TPU target, validated in interpret mode).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B,S,H,Hd], k: [B,T,K,Hd] -> scores [B,K,G,S,T] with H = K*G."""
    B, S, H, Hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, Hd)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k) / (Hd ** 0.5)


def _gqa_combine(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: [B,K,G,S,T], v: [B,T,K,Hd] -> [B,S,H,Hd]."""
    B, K, G, S, T = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, K * G, v.shape[-1])


def _expand_kv(k: jax.Array, G: int) -> jax.Array:
    """(B, T, K, Hd) -> (B, T, K*G, Hd). A broadcast XLA fuses into the dot;
    it puts attention in plain-MHA form so the *combined* head dim shards
    over the model mesh axis even when kv_heads < mesh (GQA/MQA)."""
    if G == 1:
        return k
    B, T, K, Hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, T, K, G, Hd)).reshape(B, T, K * G, Hd)


def attend_full(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    """Self-attention over equal-length q/k (train & prefill).

    window > 0 applies sliding-window masking (each query sees the last
    `window` keys, inclusive).
    """
    from ..sharding.ctx import constrain

    B, S, H, Hd = q.shape
    T = k.shape[1]
    G = H // k.shape[2]
    k = constrain(_expand_kv(k, G), "bshd")
    v = constrain(_expand_kv(v, G), "bshd")
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / (Hd ** 0.5)
    scores = constrain(scores, "bhst")
    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    probs = constrain(probs, "bhst")
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def attend_cached(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid: jax.Array,
) -> jax.Array:
    """Single-step decode: q [B,1,H,Hd] against a (possibly ring-buffer)
    KV cache [B,W,K,Hd]; `valid` [W] or [B,W] marks live slots."""
    scores = _gqa_scores(q, k_cache).astype(jnp.float32)  # [B,K,G,1,W]
    if valid.ndim == 1:
        vmask = valid[None, None, None, None, :]
    else:
        vmask = valid[:, None, None, None, :]
    scores = jnp.where(vmask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_combine(probs, v_cache)


def attend_cross(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Bidirectional cross-attention (decoder -> encoder memory)."""
    scores = _gqa_scores(q, k).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_combine(probs, v)


CHUNKED_THRESHOLD = 2048
CHUNK_Q = 512


def attend_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = CHUNK_Q,
) -> jax.Array:
    """Flash-style q-chunked attention in pure jnp: scores materialize only
    per (chunk x S) block, and each chunk is rematerialized in the backward
    pass, so peak memory is O(B*H*chunk*S) instead of O(B*H*S^2). This is
    the XLA path the dry-run lowers; on real TPUs `use_pallas` swaps in the
    Pallas kernel with the same math."""
    B, S, H, Hd = q.shape
    assert S % chunk == 0, f"seq {S} % chunk {chunk}"
    nc = S // chunk

    from ..sharding.ctx import constrain

    # Sliding-window locality: a q-chunk at offset o only sees keys in
    # [o - window + 1, o + chunk), so slice k/v to a window-aligned span
    # instead of attending across all S keys (16x waste for 2k windows on
    # 32k sequences — see EXPERIMENTS.md §Perf, hymba prefill iteration).
    span = S
    if window > 0:
        span = min(S, chunk + window)
        span = ((span + chunk - 1) // chunk) * chunk  # keep spans aligned

    @jax.checkpoint
    def block(q_blk, offset):
        q_blk = constrain(q_blk, "bshd")
        if span < S:
            start = jnp.clip(offset + chunk - span, 0, S - span)
            k_blk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            out = attend_full(
                k=k_blk, v=v_blk, q=q_blk, causal=causal, window=window,
                q_offset=offset - start,
            )
        else:
            out = attend_full(q_blk, k, v, causal=causal, window=window, q_offset=offset)
        return constrain(out, "bshd")

    qb = q.reshape(B, nc, chunk, H, Hd).transpose(1, 0, 2, 3, 4)

    def body(_, inp):
        q_blk, i = inp
        return None, block(q_blk, i * chunk)

    _, out = jax.lax.scan(body, None, (qb, jnp.arange(nc)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Hd)


# The serving prefill's budget for one layer's float32 score block. At B=16
# and 14 heads a whole 2048-token block would be 3.76 GB, on a 16 GB chip.
PREFILL_SCORE_BYTES = 1 << 30


def prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int = 0,
    use_pallas: bool = False,
    score_bytes: Optional[int] = None,
) -> jax.Array:
    """Causal self-attention over a prompt. With `score_bytes` None the
    rule goes by length: query chunks of CHUNK_Q past CHUNKED_THRESHOLD
    where S divides into them (training and the dry-run). Otherwise by
    bytes, for every S: the whole float32 score block (B*H*S*S*4) where it
    fits `score_bytes`, else the largest power-of-two chunk up to CHUNK_Q
    whose block (B*H*chunk*S_padded*4) fits, the prompt padded at its end
    to whole chunks. Causal masking keeps the padded keys from every real
    query, and the padded queries are sliced off."""
    if use_pallas:
        from repro.kernels.flash_attention.ops import flash_attention

        return flash_attention(q, k, v, causal=True, window=window)
    B, S, H, _ = q.shape
    if score_bytes is None:
        if S > CHUNKED_THRESHOLD and S % CHUNK_Q == 0:
            return attend_chunked(q, k, v, causal=True, window=window)
        return attend_full(q, k, v, causal=True, window=window)
    if B * H * S * S * 4 <= score_bytes:
        return attend_full(q, k, v, causal=True, window=window)
    chunk = CHUNK_Q
    while chunk > 1 and B * H * chunk * (-(-S // chunk) * chunk) * 4 > score_bytes:
        chunk //= 2
    if S % chunk:
        pad = ((0, 0), (0, -S % chunk), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    return attend_chunked(q, k, v, causal=True, window=window, chunk=chunk)[:, :S]


def cache_update(
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    *,
    window: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Write one step's K/V at `pos` (ring-buffer when window>0). Returns
    (k_cache, v_cache, valid-slot mask [W])."""
    W = k_cache.shape[1]
    slot = (pos % W if window > 0 else pos).astype(jnp.int32)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    valid = jnp.arange(W) <= pos  # before wrap; after wrap every slot is live
    valid = jnp.where(pos >= W, jnp.ones((W,), bool), valid)
    return k_cache, v_cache, valid
