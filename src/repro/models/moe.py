"""Mixture-of-Experts FFN: top-k routing with two dispatch paths.

`moe_ffn_dense` is the readable oracle (computes every expert on every
token, then masks) — used for smoke-scale correctness tests only.

`moe_ffn_sorted` is the production path: sort-based gather/scatter dispatch
into per-expert capacity buckets (Megablocks-style but with static shapes),
so expert FLOPs are proportional to *active* experts, and the expert
dimension shards cleanly over the `model` mesh axis (expert parallelism —
the all-to-all the paper's EP workloads generate comes out of GSPMD here).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.ctx import constrain

def route(
    x: jax.Array, router_w: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (T, D); router_w: (D, E). Returns (weights (T,k), idx (T,k), aux).

    Softmax-then-topk with renormalization; aux carries the load-balance
    loss (Switch-style) and router z-loss.
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    E = router_w.shape[1]
    # load-balance: E * sum_e (fraction of tokens to e) * (mean prob of e)
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)  # (T, E)
    load = one_hot.mean(0)
    importance = probs.mean(0)
    lb_loss = E * jnp.sum(load * importance)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return weights, idx, {"lb_loss": lb_loss, "z_loss": z_loss}


def _expert_ffn(xe: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """xe: (E, C, D); weights: (E, D, F) / (E, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)


def moe_ffn_sorted(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,  # (T, D)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    T, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = max(1, int(T * k / E * cfg.moe_capacity_factor))
    weights, idx, aux = route(x, p["router"], k)

    flat_e = idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    tok = order // k
    # rank of each pair within its expert group
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank = jnp.arange(T * k) - group_start[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)  # overflow -> sentinel

    # gather tokens into (E, C, D) buckets; sentinel row is zeros
    table = jnp.full((E * C + 1,), T, dtype=jnp.int32)
    table = table.at[slot].set(jnp.where(keep, tok, T).astype(jnp.int32))
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    xe = constrain(x_pad[table[: E * C]].reshape(E, C, D), "ecd")

    ye = constrain(_expert_ffn(xe, p["w_gate"], p["w_up"], p["w_down"]), "ecd")  # (E, C, D)

    # scatter back with combine weights (dropped pairs contribute zero)
    ye_flat = jnp.concatenate([ye.reshape(E * C, D), jnp.zeros((1, D), ye.dtype)], axis=0)
    contrib = ye_flat[slot] * keep[:, None]
    w_sorted = weights.reshape(-1)[order].astype(contrib.dtype)
    out = jnp.zeros((T, D), dtype=x.dtype).at[tok].add(contrib * w_sorted[:, None])
    dropped = (~keep).sum()
    aux = dict(aux, dropped=dropped)
    return out, aux


def _bucketize_local(
    x: jax.Array,  # (T, D) local tokens
    idx: jax.Array,  # (T, k) global expert choices
    weights: jax.Array,  # (T, k)
    *,
    e_lo: jax.Array,  # traced: this rank's first expert
    n_local: int,  # static: experts per rank
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort-based bucketing restricted to experts [e_lo, e_lo + n_local).
    Returns (xe (E_loc, C, D), slot, tok, w_sorted) for the scatter-back."""
    T, D = x.shape
    k = idx.shape[1]
    E_loc = n_local
    flat = idx.reshape(-1)
    local = jnp.where((flat >= e_lo) & (flat < e_lo + E_loc), flat - e_lo, E_loc)
    order = jnp.argsort(local, stable=True)
    sorted_e = local[order]
    tok = order // k
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E_loc + 1), side="left")
    rank = jnp.arange(T * k) - group_start[jnp.clip(sorted_e, 0, E_loc)]
    keep = (sorted_e < E_loc) & (rank < capacity)
    slot = jnp.where(keep, sorted_e * capacity + rank, E_loc * capacity)
    table = jnp.full((E_loc * capacity + 1,), T, dtype=jnp.int32)
    table = table.at[slot].set(jnp.where(keep, tok, T).astype(jnp.int32))
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    xe = x_pad[table[: E_loc * capacity]].reshape(E_loc, capacity, D)
    w_sorted = jnp.where(keep, weights.reshape(-1)[order], 0.0)
    return xe, slot, tok, w_sorted


def moe_ffn_ep(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,  # (T, D) globally; rows sharded over the batch axes
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE FFN via shard_map.

    Tokens never leave their data shard: activations are replicated over the
    `model` axis anyway (batch-sharded), so every model-rank routes the same
    local tokens, computes only its E/`model` experts, and one psum over
    `model` combines partial outputs. Expert weights are FSDP-sharded over
    `data` and explicitly all-gathered per layer. Collectives per layer:
    3 weight all-gathers + 1 (T_local, D) psum — versus the global-gather
    dispatch's full-(T, D) all-reduces (see EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P

    from ..sharding.ctx import _cur

    ctx = _cur()
    if ctx is None or not ctx["enabled"] or ctx["model"] is None:
        return moe_ffn_sorted(cfg, p, x)
    mesh = ctx["mesh"]
    b = ctx["batch"]
    baxes = b if isinstance(b, tuple) else ((b,) if b else ())
    msize = mesh.shape["model"]
    E, k = cfg.num_experts, cfg.experts_per_token
    if E % msize != 0:
        return moe_ffn_sorted(cfg, p, x)
    E_loc = E // msize
    T = x.shape[0]
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    if T % bsize != 0:
        return moe_ffn_sorted(cfg, p, x)
    T_loc = T // bsize
    C = max(1, int(T_loc * k / E * cfg.moe_capacity_factor))
    # weight FSDP axis: (E, D, F) sharded (model, data, None); (E, F, D)
    # sharded (model, None, data) per sharding.rules
    d_data = cfg.d_model % mesh.shape.get("data", 1) == 0

    def local_fn(x_l, router, wg, wu, wd):
        if d_data and "data" in mesh.shape and mesh.shape["data"] > 1:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        r = jax.lax.axis_index("model")
        weights, idx, aux = route(x_l, router, k)
        xe, slot, tok, w_sorted = _bucketize_local(
            x_l, idx, weights, e_lo=r * E_loc, n_local=E_loc, capacity=C
        )
        ye = _expert_ffn(xe, wg, wu, wd)  # (E_loc, C, D)
        ye_flat = jnp.concatenate(
            [ye.reshape(E_loc * C, x_l.shape[1]), jnp.zeros((1, x_l.shape[1]), ye.dtype)], axis=0
        )
        contrib = ye_flat[slot] * w_sorted[:, None].astype(ye.dtype)
        partial = jnp.zeros_like(x_l).at[tok].add(contrib)
        out = jax.lax.psum(partial, "model")
        lb = jax.lax.pmean(aux["lb_loss"], baxes) if baxes else aux["lb_loss"]
        zl = jax.lax.pmean(aux["z_loss"], baxes) if baxes else aux["z_loss"]
        return out, lb, zl

    bspec = P(b if b else None, None)
    out, lb, zl = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            bspec,  # x rows over batch axes, replicated over model
            P(None, None),  # router replicated
            P("model", "data" if d_data else None, None),
            P("model", "data" if d_data else None, None),
            P("model", None, "data" if d_data else None),
        ),
        out_specs=(bspec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, {"lb_loss": lb, "z_loss": zl, "dropped": jnp.zeros((), jnp.int32)}


def moe_ffn_dense(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,  # (T, D)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Oracle: every expert computes every token; combine masks select."""
    T, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    weights, idx, aux = route(x, p["router"], k)
    xe = jnp.broadcast_to(x[None], (E, T, D))
    ye = _expert_ffn(xe, p["w_gate"], p["w_up"], p["w_down"])  # (E, T, D)
    combine = jnp.zeros((T, E), dtype=jnp.float32)
    combine = combine.at[jnp.arange(T)[:, None], idx].add(weights)
    out = jnp.einsum("te,etd->td", combine.astype(x.dtype), ye)
    return out, aux


def moe_param_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": (D, E),
        "w_gate": (E, D, F),
        "w_up": (E, D, F),
        "w_down": (E, F, D),
    }
