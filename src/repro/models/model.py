"""Composable model definition covering all six assigned families.

One functional implementation parameterized by ModelConfig:
  dense / vlm      -> GQA attention + SwiGLU FFN decoder
  moe              -> GQA attention + top-k expert FFN (sorted dispatch)
  ssm              -> Mamba2 SSD mixer blocks (attention-free)
  hybrid           -> parallel attention + SSM heads per layer + FFN
  audio (enc-dec)  -> bidirectional encoder over frame embeddings + causal
                      decoder with cross-attention

Layers are stacked [L, ...] and applied with `jax.lax.scan`, keeping HLO
size depth-independent (88- and 94-layer configs compile quickly even on a
512-device dry-run mesh). Entry points:

  init_params / param_shapes      parameters (concrete / abstract)
  forward                         causal LM forward (train & prefill)
  loss_fn                         token CE + MoE aux losses
  init_cache / cache_shapes       decode caches (concrete / abstract)
  cache_filled_bytes              the bytes of a cache that a prefill wrote
  decode_step                     single-token serve step
  prefill                         serving prefill: last logits + decode cache
                                  (one parallel causal pass where
                                  `prefill_path` says it is exact, else the
                                  replay)
  prefill_replay                  the reference prefill: decode_step scanned
                                  over the prompt
  prefill_forward                 the parallel pass's own cache layout (what
                                  the dry-run lowers for prefill shapes)
  encode                          audio encoder (enc-dec only)
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn_lib
from . import moe as moe_lib
from . import ssm as ssm_lib
from .attention import (
    attend_cached,
    attend_cross,
    cache_update,
    prefill_attention,
)
from .common import apply_rope, cross_entropy, dense_init, embed_init, rms_norm, rope_angles
from ..sharding.ctx import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def _attn_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    D, H, K, Hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    s = {
        "wq": (D, H * Hd),
        "wk": (D, K * Hd),
        "wv": (D, K * Hd),
        "wo": (H * Hd, D),
    }
    if cfg.qkv_bias:
        s.update({"bq": (H * Hd,), "bk": (K * Hd,), "bv": (K * Hd,)})
    return s


def _layer_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    D = cfg.d_model
    s: Dict[str, tuple] = {"ln1": (D,)}
    if cfg.arch_type == "ssm":
        s.update(ssm_lib.mixer_param_shapes(cfg))
        return s
    s.update(_attn_shapes(cfg))
    if cfg.hybrid:
        s.update(ssm_lib.mixer_param_shapes(cfg))
    s["ln2"] = (D,)
    if cfg.num_experts > 0:
        s.update(moe_lib.moe_param_shapes(cfg))
    else:
        s.update({"w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff), "w_down": (cfg.d_ff, D)})
    if cfg.is_encdec:
        s.update({"lnx": (D,)})
        s.update({f"x{k}": v for k, v in _attn_shapes(cfg).items() if not k.startswith("b")})
    return s


def _encoder_layer_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    D = cfg.d_model
    s: Dict[str, tuple] = {"ln1": (D,), "ln2": (D,)}
    s.update(_attn_shapes(cfg))
    s.update({"w_gate": (D, cfg.d_ff), "w_up": (D, cfg.d_ff), "w_down": (cfg.d_ff, D)})
    return s


def param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    out: Dict[str, Any] = {
        "embed": (V, D),
        "final_norm": (D,),
        "layers": {k: (L,) + v for k, v in _layer_shapes(cfg).items()},
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = (D, V)
    if cfg.is_encdec:
        Le = cfg.encoder_layers
        out["encoder"] = {
            "layers": {k: (Le,) + v for k, v in _encoder_layer_shapes(cfg).items()},
            "final_norm": (D,),
        }
    return out


def _init_from_shapes(shapes: Dict[str, Any], key: jax.Array, dtype) -> Params:
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))

    def mk(shape: tuple, k: jax.Array) -> jax.Array:
        if len(shape) == 1:
            return jnp.zeros(shape, dtype)
        fan_in = shape[-2]
        return dense_init(k, fan_in, shape, dtype)

    return jax.tree_util.tree_unflatten(treedef, [mk(s, k) for s, k in zip(leaves, keys)])


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    shapes = param_shapes(cfg)
    k_embed, k_rest, k_special = jax.random.split(key, 3)
    params = _init_from_shapes(shapes, k_rest, dtype)
    params["embed"] = embed_init(k_embed, shapes["embed"], dtype)
    lp = params["layers"]
    L = cfg.num_layers
    # norm weights -> ones; biases -> zeros
    for name in ("ln1", "ln2", "lnx"):
        if name in lp:
            lp[name] = jnp.ones_like(lp[name])
    for name in ("bq", "bk", "bv"):
        if name in lp:
            lp[name] = jnp.zeros_like(lp[name])
    params["final_norm"] = jnp.ones_like(params["final_norm"])
    if cfg.is_encdec:
        enc = params["encoder"]
        enc["final_norm"] = jnp.ones_like(enc["final_norm"])
        for name in ("ln1", "ln2"):
            enc["layers"][name] = jnp.ones_like(enc["layers"][name])
    # SSM special initializations (Mamba2 defaults)
    if "ssm_A_log" in lp:
        nh = cfg.ssm_nheads
        a0 = jnp.tile(jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32))[None], (L, 1))
        lp["ssm_A_log"] = a0.astype(dtype)
        lp["ssm_D"] = jnp.ones((L, nh), dtype=dtype)
        lp["ssm_dt_bias"] = jnp.full((L, nh), -2.0, dtype=dtype)  # softplus ~ 0.12
        lp["ssm_norm"] = jnp.ones_like(lp["ssm_norm"])
        lp["ssm_conv_w"] = (
            jax.random.normal(k_special, lp["ssm_conv_w"].shape, jnp.float32) * 0.1
        ).astype(dtype)
        lp["ssm_conv_b"] = jnp.zeros_like(lp["ssm_conv_b"])
    return params


def abstract_params(cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """ShapeDtypeStruct pytree (no allocation) — dry-run currency."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, dtype),
        param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# Forward (train & prefill)
# ---------------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, lp, h, positions, prefix=""):
    B, S, _ = h.shape
    H, K, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", h, lp[prefix + "wq"])
    k = jnp.einsum("bsd,de->bse", h, lp[prefix + "wk"])
    v = jnp.einsum("bsd,de->bse", h, lp[prefix + "wv"])
    if cfg.qkv_bias and prefix == "":
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = constrain(q.reshape(B, S, H, Hd), "bshd")
    k = constrain(k.reshape(B, S, K, Hd), "bshd")
    v = constrain(v.reshape(B, S, K, Hd), "bshd")
    if positions is not None:
        cos, sin = rope_angles(positions, Hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _project_q(cfg: ModelConfig, w, h):
    B, S, _ = h.shape
    H, Hd = cfg.num_heads, cfg.resolved_head_dim
    return jnp.einsum("bsd,de->bse", h, w).reshape(B, S, H, Hd)


def _ring_cache(k: jax.Array, window: int) -> jax.Array:
    """Arrange the last `window` keys/values into decode ring-buffer order:
    absolute position p lands at slot p % window. k: (B, S, K, Hd)."""
    S = k.shape[1]
    if S <= window:
        pad = window - S
        return jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    last = k[:, S - window :]
    slots = (jnp.arange(S - window, S) % window)
    out = jnp.zeros((k.shape[0], window) + k.shape[2:], k.dtype)
    return out.at[:, slots].set(last)


def _decoder_layer_train(cfg: ModelConfig, lp, x, enc_out, positions, collect_cache=False,
                         attend=prefill_attention):
    aux = {}
    cache_out = {}
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.arch_type == "ssm":
        y, st, conv_tail = ssm_lib.mamba2_mixer(cfg, lp, h)
        if collect_cache:
            cache_out = {"ssm_state": st.astype(jnp.float32), "conv_buf": conv_tail}
        return x + y, aux, cache_out
    q, k, v = _project_qkv(cfg, lp, h, positions)
    a = attend(q, k, v, window=cfg.sliding_window, use_pallas=cfg.use_pallas)
    a = constrain(a, "bshd")
    attn = jnp.einsum("bse,ed->bsd", a.reshape(a.shape[0], a.shape[1], -1), lp["wo"])
    if collect_cache:
        if cfg.sliding_window > 0:
            cache_out["k"] = constrain(_ring_cache(k, cfg.sliding_window), "cache_kv")
            cache_out["v"] = constrain(_ring_cache(v, cfg.sliding_window), "cache_kv")
        else:
            # explicit reshard into decode-cache layout here, so the cache's
            # length-sharding can't propagate back into the attention loop
            cache_out["k"] = constrain(k, "cache_kv")
            cache_out["v"] = constrain(v, "cache_kv")
    mixed = attn
    if cfg.hybrid:
        y, st, conv_tail = ssm_lib.mamba2_mixer(cfg, lp, h)
        if collect_cache:
            cache_out["ssm_state"] = st.astype(jnp.float32)
            cache_out["conv_buf"] = conv_tail
        mixed = 0.5 * (attn + y)  # Hymba-style parallel head fusion
    x = x + mixed
    if cfg.is_encdec and enc_out is not None:
        hx = rms_norm(x, lp["lnx"], cfg.norm_eps)
        qx = _project_q(cfg, lp["xwq"], hx)
        kx = jnp.einsum("bsd,de->bse", enc_out, lp["xwk"])
        vx = jnp.einsum("bsd,de->bse", enc_out, lp["xwv"])
        K, Hd = cfg.num_kv_heads, cfg.resolved_head_dim
        kx = kx.reshape(enc_out.shape[0], enc_out.shape[1], K, Hd)
        vx = vx.reshape(enc_out.shape[0], enc_out.shape[1], K, Hd)
        xattn = attend_cross(qx, kx, vx)
        x = x + jnp.einsum("bse,ed->bsd", xattn.reshape(x.shape[0], x.shape[1], -1), lp["xwo"])
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.num_experts > 0:
        T = h2.shape[0] * h2.shape[1]
        # expert-parallel path under a mesh; local sorted dispatch otherwise
        y, moe_aux = moe_lib.moe_ffn_ep(cfg, lp, h2.reshape(T, -1))
        y = y.reshape(h2.shape)
        aux = {k: moe_aux[k] for k in ("lb_loss", "z_loss")}
    else:
        from .common import swiglu

        y = swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
    return constrain(x + y, "bsd"), aux, cache_out


def encode(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over precomputed frame embeddings (B, S, D)."""
    enc = params["encoder"]

    def body(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, lp, h, jnp.arange(h.shape[1]))
        from .attention import attend_full

        a = attend_full(q, k, v, causal=False)
        x = x + jnp.einsum("bse,ed->bsd", a.reshape(x.shape[0], x.shape[1], -1), lp["wo"])
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        from .common import swiglu

        x = x + swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, None

    x, _ = jax.lax.scan(body, frames, enc["layers"])
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    *,
    enc_frames: Optional[jax.Array] = None,
    remat: Optional[bool] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal forward. tokens: (B, S) int32 -> logits (B, S, V) fp32 + aux."""
    B, S = tokens.shape
    x = constrain(params["embed"][tokens], "bsd")
    positions = jnp.arange(S)
    enc_out = None
    if cfg.is_encdec:
        assert enc_frames is not None, "enc-dec arch requires enc_frames"
        enc_out = encode(cfg, params, enc_frames)

    layer = functools.partial(_decoder_layer_train, cfg)
    use_remat = cfg.remat == "full" if remat is None else remat
    if use_remat:
        layer = jax.checkpoint(layer, static_argnums=())

    def body(carry, lp):
        x, lb, zl = carry
        x, aux, _ = layer(lp, x, enc_out, positions)
        lb = lb + aux.get("lb_loss", 0.0)
        zl = zl + aux.get("z_loss", 0.0)
        return (x, lb, zl), None

    zero = jnp.zeros((), jnp.float32)
    (x, lb, zl), _ = jax.lax.scan(body, (x, zero, zero), params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]).astype(jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    logits = constrain(logits, "logits")
    denom = max(cfg.num_layers, 1)
    return logits, {"lb_loss": lb / denom, "z_loss": zl / denom}


def prefill_forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    *,
    enc_frames: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Serving prefill: one parallel pass over the prompt that RETURNS the
    decode cache (per-layer K/V in ring order / SSD states / conv tails).
    This is what the prefill_32k dry-run shape lowers — the cache output is
    the PD-disaggregation elephant flow."""
    B, S = tokens.shape
    x = constrain(params["embed"][tokens], "bsd")
    positions = jnp.arange(S)
    enc_out = None
    if cfg.is_encdec:
        assert enc_frames is not None
        enc_out = encode(cfg, params, enc_frames)
    x, cache = _prefill_layers(cfg, params, x, positions, enc_out, prefill_attention)
    if cfg.is_encdec and enc_out is not None:
        K, Hd = cfg.num_kv_heads, cfg.resolved_head_dim
        lp = params["layers"]
        enc_len = enc_out.shape[1]
        cache["enc_k"] = jnp.einsum("bsd,lde->lbse", enc_out, lp["xwk"]).reshape(
            cfg.num_layers, B, enc_len, K, Hd
        )
        cache["enc_v"] = jnp.einsum("bsd,lde->lbse", enc_out, lp["xwv"]).reshape(
            cfg.num_layers, B, enc_len, K, Hd
        )
    return _last_logits(cfg, params, x), cache


def _prefill_layers(cfg: ModelConfig, params: Params, x: jax.Array, positions, enc_out,
                    attend):
    """The decoder layers over the embedded prompt `x` (B, S, D) in one
    causal pass: the last layer's output and the per-layer caches
    `_decoder_layer_train` collects."""
    def body(x, lp):
        x, _, cache = _decoder_layer_train(cfg, lp, x, enc_out, positions,
                                           collect_cache=True, attend=attend)
        return x, cache

    return jax.lax.scan(body, x, params["layers"])


def _last_logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """The (B, V) float32 logits of the last position of `x` (B, S, D)."""
    x_last = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x_last, params["embed"]).astype(jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x_last, head).astype(jnp.float32)
    logits = constrain(logits, "logits")
    return logits[:, 0]


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]):
    logits, aux = forward(cfg, params, batch["tokens"], enc_frames=batch.get("enc_frames"))
    ce = cross_entropy(logits, batch["targets"])
    loss = ce
    if cfg.num_experts > 0:
        loss = loss + 0.01 * aux["lb_loss"] + 0.001 * aux["z_loss"]
    return loss, {"ce": ce, **aux}


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

def _cache_struct(
    cfg: ModelConfig, batch: int, max_len: int, enc_len: int, dtype=jnp.bfloat16
) -> Dict[str, tuple]:
    L, K, Hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    W = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    s: Dict[str, Any] = {}
    if cfg.arch_type != "ssm":
        s["k"] = ((L, batch, W, K, Hd), dtype)
        s["v"] = ((L, batch, W, K, Hd), dtype)
    if cfg.arch_type == "ssm" or cfg.hybrid:
        di, N = cfg.ssm_d_inner, cfg.ssm_state
        s["ssm_state"] = ((L, batch, cfg.ssm_nheads, cfg.ssm_headdim, N), jnp.float32)
        s["conv_buf"] = ((L, batch, cfg.ssm_conv - 1, di + 2 * N), dtype)
    if cfg.is_encdec:
        s["enc_k"] = ((L, batch, enc_len, K, Hd), dtype)
        s["enc_v"] = ((L, batch, enc_len, K, Hd), dtype)
    return s


def cache_filled_bytes(cache: Dict[str, Any], prompt_len: int) -> int:
    """Bytes of a cache in `_cache_struct`'s layout that a prefill of
    `prompt_len` tokens wrote, from the leaves' shapes and dtypes alone: the
    self-attention K/V (L, B, W, K, Hd) count min(prompt_len, W) of their W
    positions (a sliding-window ring fills whole once the prompt passes
    it); `ssm_state`, `conv_buf` and the encoder's `enc_k`/`enc_v` hold no
    position axis and count whole."""
    total = 0
    for name, leaf in cache.items():
        nbytes = math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
        if name in ("k", "v"):
            W = leaf.shape[2]
            nbytes = nbytes // W * min(prompt_len, W)
        total += nbytes
    return total


def cache_shapes(
    cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0, dtype=jnp.bfloat16
) -> Dict[str, Any]:
    return {
        k: jax.ShapeDtypeStruct(shape, dt)
        for k, (shape, dt) in _cache_struct(cfg, batch, max_len, enc_len, dtype).items()
    }


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0, dtype=jnp.bfloat16
) -> Dict[str, Any]:
    return {
        k: jnp.zeros(shape, dt)
        for k, (shape, dt) in _cache_struct(cfg, batch, max_len, enc_len, dtype).items()
    }


def _decoder_layer_step(cfg: ModelConfig, lp, x, cache_l, pos):
    """One layer, one token. x: (B, 1, D). cache_l: per-layer cache dict."""
    new_cache = {}
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.arch_type == "ssm":
        y, new_buf, new_state = ssm_lib.mamba2_mixer_step(
            cfg, lp, h, cache_l["conv_buf"], cache_l["ssm_state"]
        )
        new_cache["conv_buf"], new_cache["ssm_state"] = new_buf, new_state
        return x + y, new_cache
    positions = jnp.full((1,), pos)
    q, k, v = _project_qkv(cfg, lp, h, positions)
    k_cache, v_cache, valid = cache_update(
        cache_l["k"], cache_l["v"], k, v, pos, window=cfg.sliding_window
    )
    new_cache["k"], new_cache["v"] = k_cache, v_cache
    a = attend_cached(q, k_cache, v_cache, valid)
    attn = jnp.einsum("bse,ed->bsd", a.reshape(x.shape[0], 1, -1), lp["wo"])
    mixed = attn
    if cfg.hybrid:
        y, new_buf, new_state = ssm_lib.mamba2_mixer_step(
            cfg, lp, h, cache_l["conv_buf"], cache_l["ssm_state"]
        )
        new_cache["conv_buf"], new_cache["ssm_state"] = new_buf, new_state
        mixed = 0.5 * (attn + y)
    x = x + mixed
    if cfg.is_encdec:
        hx = rms_norm(x, lp["lnx"], cfg.norm_eps)
        qx = _project_q(cfg, lp["xwq"], hx)
        xa = attend_cross(qx, cache_l["enc_k"], cache_l["enc_v"])
        x = x + jnp.einsum("bse,ed->bsd", xa.reshape(x.shape[0], 1, -1), lp["xwo"])
        new_cache["enc_k"], new_cache["enc_v"] = cache_l["enc_k"], cache_l["enc_v"]
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.num_experts > 0:
        T = h2.shape[0]
        y, _ = moe_lib.moe_ffn_sorted(cfg, lp, h2.reshape(T, -1))
        y = y.reshape(h2.shape)
    else:
        from .common import swiglu

        y = swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x + y, new_cache


def decode_step(
    cfg: ModelConfig,
    params: Params,
    cache: Dict[str, jax.Array],
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # scalar int32 (synchronized batch decode)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    pos = jnp.asarray(pos, jnp.int32)
    x = constrain(params["embed"][token], "bsd")

    def body(x, inp):
        lp, cache_l = inp
        x, new_cache = _decoder_layer_step(cfg, lp, x, cache_l, pos)
        return x, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]).astype(jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    logits = constrain(logits, "logits")
    return logits[:, 0], new_cache


def prefill_path(cfg: ModelConfig) -> str:
    """Which prefill `prefill` runs for `cfg`: "parallel" where one causal
    pass over the prompt computes what the replay computes, rounding order
    aside (attention-only decoders without experts), "replay" elsewhere: an
    SSM's chunked scan is another computation than its step recurrence, an
    expert dispatch with capacity over the whole prompt routes otherwise
    than token by token, and the encoder-decoder keeps the replay."""
    if (cfg.arch_type in ("dense", "vlm") and cfg.num_experts == 0
            and not cfg.hybrid and not cfg.is_encdec):
        return "parallel"
    return "replay"


def prefill(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    max_len: int,
    *,
    enc_frames: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Serving prefill: the (B, V) float32 logits of the prompt's last
    position and a decode cache laid out as `init_cache(cfg, B, max_len)`
    lays it out. Where `prefill_path(cfg)` is "parallel", one causal pass
    over the prompt whose K/V fill the cache's first S positions (zeros
    beyond, as the replay leaves them); elsewhere `prefill_replay`."""
    if prefill_path(cfg) == "replay":
        return prefill_replay(cfg, params, tokens, max_len, enc_frames=enc_frames)
    S = tokens.shape[1]
    W = cache_shapes(cfg, 1, max_len)["k"].shape[2]  # the decode cache's width
    if cfg.sliding_window > 0:
        # the decode ring has W slots and attends to all of them once full,
        # so the replay's window is W where max_len is below the window
        cfg = cfg.with_(sliding_window=W)
    elif S > W:
        raise ValueError(f"prompt of {S} tokens does not fit max_len {max_len}")
    x = constrain(params["embed"][tokens], "bsd")
    attend = functools.partial(prefill_attention, score_bytes=attn_lib.PREFILL_SCORE_BYTES)
    x, cache = _prefill_layers(cfg, params, x, jnp.arange(S), None, attend)
    logits = _last_logits(cfg, params, x)
    dtype = params["embed"].dtype
    # a window's K/V arrive in the ring's order and width; the rest is padded
    pad = ((0, 0), (0, 0), (0, W - cache["k"].shape[2]), (0, 0), (0, 0))
    return logits, {kv: jnp.pad(cache[kv].astype(dtype), pad) for kv in ("k", "v")}


def prefill_replay(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    max_len: int,
    *,
    enc_frames: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The reference prefill: the prompt replayed token by token through
    `decode_step` into an `init_cache` cache. For attention archs this
    populates K/V; for SSM it folds the prompt into the state. `prefill`
    serves through it where `prefill_path` is "replay", and the tests hold
    the parallel pass to it."""
    B, S = tokens.shape
    enc_len = enc_frames.shape[1] if enc_frames is not None else 0
    cache = init_cache(cfg, B, max_len, enc_len, dtype=params["embed"].dtype)
    if cfg.is_encdec and enc_frames is not None:
        enc_out = encode(cfg, params, enc_frames)
        K, Hd = cfg.num_kv_heads, cfg.resolved_head_dim
        lp = params["layers"]
        ek = jnp.einsum("bsd,lde->lbse", enc_out, lp["xwk"]).reshape(
            cfg.num_layers, B, enc_len, K, Hd
        )
        ev = jnp.einsum("bsd,lde->lbse", enc_out, lp["xwv"]).reshape(
            cfg.num_layers, B, enc_len, K, Hd
        )
        cache["enc_k"], cache["enc_v"] = ek, ev

    def step(carry, t):
        cache, last = carry
        logits, cache = decode_step(cfg, params, cache, tokens[:, t][:, None], t)
        return (cache, logits), None

    (cache, last_logits), _ = jax.lax.scan(
        step, (cache, jnp.zeros((B, cfg.vocab_size), jnp.float32)), jnp.arange(S)
    )
    return last_logits, cache
