"""Dense decoder (GQA or MHA attention, SwiGLU MLP, RMSNorm, RoPE): the
benchmark's own weights, its plain float32 reference, and the operations
and bytes a step needs.

Nothing here imports the program under test. The weights are laid out as
the program's parameter tree (`embed`, `final_norm`, stacked `layers`,
`lm_head` when untied) so that the same arrays can be served and checked.

The reference follows the published layer equations (Qwen2, arXiv:2407.10671;
DeepSeek LLM, arXiv:2401.02954; both Llama-style):

    h   = x + Wo . attn(RoPE(Wq . n1(x) + bq), RoPE(Wk . n1(x) + bk), Wv . n1(x) + bv)
    out = h + Wd . (silu(Wg . n2(h)) * (Wu . n2(h)))
    n(x) = x / sqrt(mean(x^2) + eps) * w

with causal softmax attention scaled by 1/sqrt(head_dim), key/value heads
shared by `num_attention_heads / num_key_value_heads` query heads, RoPE
over the two halves of each head (inverse frequency theta^(-2i/head_dim)),
and logits = n_final(x) . W_head, where W_head is the transposed embedding
when `tie_word_embeddings`. Everything is computed in float32 at
`Precision.HIGHEST` from the served bfloat16 weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


class Dims(NamedTuple):
    """The sizes of a configuration file that the arithmetic reads."""
    D: int  # hidden_size
    L: int  # num_hidden_layers
    H: int  # num_attention_heads
    K: int  # num_key_value_heads
    Hd: int  # head_dim
    F: int  # intermediate_size
    V: int  # vocab_size
    tied: bool
    qkv_bias: bool
    eps: float
    theta: float


def dims(cfg: Dict[str, Any]) -> Dims:
    H = cfg["num_attention_heads"]
    return Dims(D=cfg["hidden_size"], L=cfg["num_hidden_layers"], H=H,
                K=cfg["num_key_value_heads"],
                Hd=cfg.get("head_dim") or cfg["hidden_size"] // H,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]),
                qkv_bias=bool(cfg.get("qkv_bias", False)),
                eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def param_shapes(d: Dims) -> Dict[str, Any]:
    layer = {
        "ln1": (d.D,), "wq": (d.D, d.H * d.Hd), "wk": (d.D, d.K * d.Hd),
        "wv": (d.D, d.K * d.Hd), "wo": (d.H * d.Hd, d.D), "ln2": (d.D,),
        "w_gate": (d.D, d.F), "w_up": (d.D, d.F), "w_down": (d.F, d.D),
    }
    if d.qkv_bias:
        layer.update({"bq": (d.H * d.Hd,), "bk": (d.K * d.Hd,), "bv": (d.K * d.Hd,)})
    out = {"embed": (d.V, d.D), "final_norm": (d.D,),
           "layers": {k: (d.L,) + v for k, v in layer.items()}}
    if not d.tied:
        out["lm_head"] = (d.D, d.V)
    return out


def _leaf_init(name: str, shape: tuple, key: jax.Array) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "final_norm"):
        return 1.0 + 0.1 * z
    if name in ("bq", "bk", "bv"):
        return 0.02 * z
    if name == "embed":
        return 0.02 * z
    return z * shape[-2] ** -0.5  # fan-in scaling of a (.., in, out) matrix


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


@functools.partial(jax.jit, static_argnums=0)
def _make(d: Dims, key: jax.Array) -> Dict[str, Any]:
    shapes = param_shapes(d)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        leaves.append(_leaf_init(name, shape, jax.random.fold_in(key, i))
                      .astype(jnp.bfloat16))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights in bfloat16, made on the device in one jitted call."""
    return _make(dims(cfg), seed_key(seed))


# ---------------------------------------------------------------------------
# Plain reference
# ---------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (T, heads, Hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(d: Dims, x, lp):
    f = {k: v.astype(jnp.float32) for k, v in lp.items()}
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _norm(x, f["ln1"], d.eps)
    q, k, v = _mm(h, f["wq"]), _mm(h, f["wk"]), _mm(h, f["wv"])
    if d.qkv_bias:
        q, k, v = q + f["bq"], k + f["bk"], v + f["bv"]
    q = _rope(q.reshape(T, d.H, d.Hd), pos, d.theta)
    k = _rope(k.reshape(T, d.K, d.Hd), pos, d.theta)
    v = v.reshape(T, d.K, d.Hd)
    g = d.H // d.K
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,uhd->htu", q, k, precision=HIGHEST) / np.sqrt(d.Hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    a = jnp.einsum("htu,uhd->thd", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)
    x = x + _mm(a.reshape(T, d.H * d.Hd), f["wo"])
    h2 = _norm(x, f["ln2"], d.eps)
    return x + _mm(jax.nn.silu(_mm(h2, f["w_gate"])) * _mm(h2, f["w_up"]), f["w_down"])


def _vocab_chunks(V: int) -> int:
    for n in (16, 8, 4, 2):
        if V % n == 0 and V // n >= 1024:
            return n
    return 1


@functools.partial(jax.jit, static_argnums=0)
def _row_stats(d: Dims, params, tokens, query):
    """One row: at each position, the best logit, its token, and the logit
    of `query[t]`. tokens, query: (T,) int32."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, lp: (_layer(d, x, lp), None), x, params["layers"])
    x = _norm(x, params["final_norm"].astype(jnp.float32), d.eps)
    n = _vocab_chunks(d.V)
    C = d.V // n

    def head_cols(i):  # (D, C) block of the head, columns i*C .. i*C+C
        if d.tied:
            return jax.lax.dynamic_slice_in_dim(params["embed"], i * C, C, axis=0).T
        return jax.lax.dynamic_slice_in_dim(params["lm_head"], i * C, C, axis=1)

    def chunk(carry, i):
        best, arg = carry
        lg = _mm(x, head_cols(i).astype(jnp.float32))  # (T, C)
        m, a = lg.max(-1), lg.argmax(-1).astype(jnp.int32) + i * C
        take = m > best
        return (jnp.where(take, m, best), jnp.where(take, a, arg)), None

    T = x.shape[0]
    init = (jnp.full((T,), -jnp.inf, jnp.float32), jnp.zeros((T,), jnp.int32))
    (best, arg), _ = jax.lax.scan(chunk, init, jnp.arange(n))
    wq = params["embed"][query] if d.tied else params["lm_head"][:, query].T
    qlogit = jnp.sum(x * wq.astype(jnp.float32), axis=-1)
    return best, arg, qlogit


def logit_stats(cfg: Dict[str, Any], params, tokens: np.ndarray, query: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Reference, row by row: best logit, its token, and the logit of
    `query` at every position. tokens, query: (R, T) int32."""
    d = dims(cfg)
    out = [_row_stats(d, params, jnp.asarray(t), jnp.asarray(q))
           for t, q in zip(tokens, query)]
    return tuple(np.stack([np.asarray(o[i]) for o in out]) for i in range(3))


# ---------------------------------------------------------------------------
# Control: the same reference from float8 weights
# ---------------------------------------------------------------------------

def _quantized(path) -> bool:
    return path[-1].key not in ("ln1", "ln2", "final_norm", "bq", "bk", "bv")


@jax.jit
def _fp8_quantize(params):
    """(float8_e4m3fn values, float32 scales) for every matrix, one scale
    per output channel (per row of the embedding), absmax -> 448."""
    def quant(path, w):
        if not _quantized(path):
            return w
        w32 = w.astype(jnp.float32)
        axis = -1 if path[-1].key == "embed" else -2
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / FP8_MAX
        scale = jnp.where(scale > 0, scale, 1.0)
        return (w32 / scale).astype(jnp.float8_e4m3fn), scale
    return jax.tree_util.tree_map_with_path(quant, params)


@jax.jit
def _fp8_dequantize(qparams):
    return jax.tree_util.tree_map(
        lambda x: (x[0].astype(jnp.float32) * x[1]).astype(jnp.bfloat16)
        if isinstance(x, tuple) else x,
        qparams, is_leaf=lambda x: isinstance(x, tuple))


def fp8_params(params):
    """Every matrix rounded to float8 e4m3 and held dequantized in
    bfloat16, whose rounding is far below float8's; norm weights and
    biases as served. Two programs, so that the compiler cannot fold the
    round trip through float8 away (XLA may drop a pair of converts as
    excess precision)."""
    return _fp8_dequantize(_fp8_quantize(params))


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------

def kv_bytes_per_token(d: Dims) -> int:
    """K and V, every layer, bfloat16."""
    return 2 * d.L * d.K * d.Hd * 2


def param_count(d: Dims) -> int:
    per_layer = (d.D * d.H * d.Hd + 2 * d.D * d.K * d.Hd + d.H * d.Hd * d.D
                 + 3 * d.D * d.F + 2 * d.D)
    if d.qkv_bias:
        per_layer += (d.H + 2 * d.K) * d.Hd
    return d.V * d.D * (1 if d.tied else 2) + d.L * per_layer + d.D


def weight_bytes(d: Dims) -> int:
    return 2 * param_count(d)


def _matmul_flops_per_token(d: Dims) -> int:
    return 2 * d.L * (d.D * d.H * d.Hd + 2 * d.D * d.K * d.Hd + d.H * d.Hd * d.D
                      + 3 * d.D * d.F)


def prefill_flops(d: Dims, S: int) -> int:
    """One row of S prompt tokens: every projection for every token, causal
    attention (QK^T and PV over the t+1 keys of token t), and the head at
    the last position only."""
    attn = 4 * d.L * d.H * d.Hd * S * (S + 1) // 2
    return S * _matmul_flops_per_token(d) + attn + 2 * d.D * d.V


def decode_flops(d: Dims, pos: int) -> int:
    """One row, one token at position `pos` (pos + 1 keys)."""
    return (_matmul_flops_per_token(d) + 4 * d.L * d.H * d.Hd * (pos + 1)
            + 2 * d.D * d.V)


def decode_bytes(d: Dims, pos: int, batch: int) -> int:
    """One step of `batch` rows at position `pos`: every weight once, the
    `pos` cached positions read and the new one written, float32 logits."""
    return (weight_bytes(d) + batch * (pos + 1) * kv_bytes_per_token(d)
            + batch * d.V * 4)
