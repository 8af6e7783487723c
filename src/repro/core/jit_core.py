"""Fused lax.scan simulation core for Monte Carlo fault sweeps.

`_build_sim` compiles a `SprayProgram` into a fully fused model of the spray
closed loop: wave-choose -> busy-chain post -> fault check (+ one masked
retry) -> completion-ordered EWMA drain, all inside one nested `lax.scan`
over fixed-shape rail/slice arrays, with the fabric's deterministic fault
schedule compiled into per-rail window arrays (`Fabric.fault_window_arrays`)
and per-seed jitters applied to fault onset/duration/depth. `vmap` over seed
keys yields whole healing-time/throughput distributions in one dispatch
(`spray_sweep`); `simulate_spray_ref` is the op-for-op numpy twin the
property tests pin the jax path against, bit-exact at float64.

The model is deliberately the *skeleton* of the engine, not the engine: one
plan stage, uniform slice length, one retry attempt, round-granular clock
advancement. Scenarios that need staged hops, backend substitution chains,
or app callbacks keep the full event-driven `ScenarioRunner` path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import numpy as np

from .fabric import FAR_WINDOW

__all__ = [
    "SprayProgram",
    "jax_available",
    "make_draws",
    "simulate_spray_ref",
    "spray_single",
    "spray_sweep",
    "x64",
]


def jax_available() -> bool:
    try:
        import jax  # noqa: F401
    except Exception:  # pragma: no cover - environment without jax
        return False
    return True


def x64():
    """Context manager under which every kernel of this module runs: JAX
    builds float64/int64 arrays inside it, so the jitted paths compute in the
    same precision as their numpy twins. Shared with the parity tests."""
    import jax

    return jax.enable_x64(True)


# ---------------------------------------------------------------------------
# Fused Monte Carlo spray model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SprayProgram:
    """Fixed-shape compilation of one spray scenario: D rails (the resolved
    plan stage's candidate paths), `rounds` waves of `wave` slices of
    `length` bytes each, with the fabric's fault/degradation schedule as
    dense per-rail window arrays (src- and dst-side degradations kept
    separate because the fabric takes the min of the two effective
    bandwidths). Built by `repro.scenarios.sweep.compile_spray_program`;
    consumed by `spray_single` / `spray_sweep` / `simulate_spray_ref`."""

    n_rails: int
    rounds: int
    wave: int
    length: float
    gamma: float
    detect: float  # Fabric.FAIL_DETECT_LATENCY
    jitter: float  # per-transfer service-jitter sigma (Fabric jitter)
    bw_score: np.ndarray  # (D,) local-link nominal bw — Algorithm 1 scoring
    bw_src: np.ndarray  # (D,) source-side nominal bw — service time
    bw_dst: np.ndarray  # (D,) dest-side nominal bw (inf when single-ended)
    penalty: np.ndarray  # (D,) tier penalties
    latency: np.ndarray  # (D,) wire latency added after the busy chain
    beta0: np.ndarray  # (D,) EWMA state priors (telemetry cold start)
    beta1: np.ndarray
    ewma_alpha: np.ndarray
    beta0_alpha: np.ndarray
    fail_start: np.ndarray  # (D, Kf) union of src+dst fail windows
    fail_end: np.ndarray
    degs_start: np.ndarray  # (D, Ks) source-side degradations
    degs_end: np.ndarray
    degs_factor: np.ndarray
    degd_start: np.ndarray  # (D, Kd) dest-side degradations
    degd_end: np.ndarray
    degd_factor: np.ndarray

    def __post_init__(self):
        if not np.isfinite(self.penalty).any():
            raise ValueError("SprayProgram needs >= 1 tier-feasible rail")


def _seed_key(base_seed: int, seed_index: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(base_seed), seed_index)


def _opaque(x):
    """`x` as a value XLA cannot see through. Its simplifier rewrites a
    division by a constant into a multiply by the (inexact) reciprocal,
    which the numpy twin, dividing, cannot reproduce; every constant
    divisor of the fused sim goes through here."""
    import jax
    import jax.numpy as jnp

    return jax.lax.optimization_barrier(jnp.asarray(x, dtype=float))


def _draws_jnp(p: SprayProgram, key):
    """Raw per-seed randomness, all drawn up front so the jax sim and the
    numpy ref consume identical bits: window-jitter uniforms in [-1, 1]
    (fault onset/duration, degradation onset/duration/depth) and the
    per-attempt service-jitter multipliers `1 + |N(0, sigma)|` (the fabric's
    per-transfer jitter law)."""
    import jax
    import jax.numpy as jnp

    kf, ks, kd, kj = jax.random.split(key, 4)
    uf = jax.random.uniform(
        kf, (p.n_rails, p.fail_start.shape[1], 2), minval=-1.0, maxval=1.0)
    us = jax.random.uniform(
        ks, (p.n_rails, p.degs_start.shape[1], 3), minval=-1.0, maxval=1.0)
    ud = jax.random.uniform(
        kd, (p.n_rails, p.degd_start.shape[1], 3), minval=-1.0, maxval=1.0)
    # |N| / (1/sigma), NOT 1 + |N|*sigma: XLA sinks this elementwise chain
    # into the consuming scan and FMA-contracts a+b*c there (single
    # rounding), which the eagerly-materialized `make_draws` copy and the
    # numpy twin cannot reproduce. A division result feeding the add is
    # contraction-proof, so eager and jitted draws stay bit-identical.
    inv_sigma = _opaque(math.inf if p.jitter == 0 else 1.0 / float(p.jitter))
    jm = 1.0 + jnp.abs(
        jax.random.normal(kj, (p.rounds, p.wave, 2))) / inv_sigma
    return uf, us, ud, jm


def make_draws(p: SprayProgram, *, base_seed: int = 0,
               seed_index: int = 0) -> Dict[str, np.ndarray]:
    """Materialized numpy copy of one seed's raw draws — the common input
    feeding both `simulate_spray_ref` and the jax path in parity tests."""
    with x64():
        uf, us, ud, jm = _draws_jnp(p, _seed_key(base_seed, seed_index))
        return {"uf": np.asarray(uf), "us": np.asarray(us),
                "ud": np.asarray(ud), "jm": np.asarray(jm)}


# Window jitter law (shared, op for op, by both backends): onsets scale
# multiplicatively (a window starting at 0 — e.g. a permanent rail derating
# — stays at 0), durations scale multiplicatively (a "forever" horizon
# stays forever), depths scale and clamp into (0, 1]. fj=0 reproduces the
# declared schedule exactly. Every multiply whose result would feed an add
# is routed through a division instead — same FMA-contraction defense as
# the jm draws above (the scale arithmetic gets fused into the jitted sim).


def _inv_fj(fj: float) -> float:
    return math.inf if fj == 0 else 1.0 / float(fj)


def _jitter_windows_np(start, end, u, fj):
    inv = _inv_fj(fj)
    s = np.maximum(0.0, start * (1.0 + u[..., 0] / inv))
    scale1 = 1.0 + u[..., 1] / inv
    e = s + (end - start) / (1.0 / scale1)
    return s, e


def _select_np(scores, rr, gamma):
    s_min = scores.min()
    in_w = scores <= (1.0 + gamma) * s_min
    n_w = int(in_w.sum())
    k = int(rr) % max(n_w, 1)
    order = np.cumsum(in_w.astype(np.int64)) - 1
    match = np.where(in_w & (order == k),
                     np.arange(scores.shape[0]), scores.shape[0])
    return int(match.min())


def simulate_spray_ref(p: SprayProgram, draws: Dict[str, np.ndarray], *,
                       policy: str = "tent",
                       fault_jitter: float = 0.0) -> Tuple[float, ...]:
    """Numpy twin of the fused jax sim, mirrored operation for operation at
    float64 (the parity tests assert exact equality). Returns
    `(throughput, healing_s, bytes_ok, lost, makespan)`."""
    if policy not in ("tent", "round_robin"):
        raise ValueError(f"unsupported sweep policy {policy!r}")
    D, R, W = p.n_rails, p.rounds, p.wave
    L = float(p.length)
    det = float(p.detect)
    gamma = float(p.gamma)
    fj = float(fault_jitter)
    uf, us, ud, jm = draws["uf"], draws["us"], draws["ud"], draws["jm"]
    inv = _inv_fj(fj)
    fs, fe = _jitter_windows_np(p.fail_start, p.fail_end, uf, fj)
    dss, dse = _jitter_windows_np(p.degs_start, p.degs_end, us, fj)
    dsf = np.clip(p.degs_factor * (1.0 + us[..., 2] / inv), 0.01, 1.0)
    dds, dde = _jitter_windows_np(p.degd_start, p.degd_end, ud, fj)
    ddf = np.clip(p.degd_factor * (1.0 + ud[..., 2] / inv), 0.01, 1.0)
    ext = lambda a, fill: np.concatenate(
        [np.asarray(a, dtype=np.float64), [fill]])
    bw_score = ext(p.bw_score, 1.0)
    bw_src = ext(p.bw_src, 1.0)
    bw_dst = ext(p.bw_dst, 1.0)
    pen = ext(p.penalty, np.inf)
    lat = ext(p.latency, 0.0)
    alpha = ext(p.ewma_alpha, 0.0)
    b0a = ext(p.beta0_alpha, 0.0)
    b0 = ext(p.beta0, 0.0)
    b1 = ext(p.beta1, 1.0)
    q = np.zeros(D + 1)
    busy = np.zeros(D + 1)
    rr = 0
    now = 0.0
    arange = np.arange(D + 1)

    def excluded_at(t):
        return np.any((fs + det <= t) & (t < fe + det), axis=1)

    def overlaps(d, s, e):
        return d < D and bool(np.any((fs[d] < e) & (fe[d] > s)))

    def degfac(start, end, fac, d, t):
        out = 1.0
        if d < D:
            for k in range(start.shape[1]):
                if start[d, k] <= t < end[d, k]:
                    out = out * fac[d, k]
        return out

    def effbw(d, t):
        return min(bw_src[d] * degfac(dss, dse, dsf, d, t),
                   bw_dst[d] * degfac(dds, dde, ddf, d, t))

    def choose(rr_, live=None):
        if policy == "tent":
            s = pen * (b0 + b1 * (q + L) / bw_score)
            mask = np.zeros(D + 1, dtype=bool)
            mask[:D] = excl if live is None else ~live
            mask[D] = True
            sx = np.where(mask, np.inf, s)
            se = sx if np.isfinite(sx.min()) else s
            return _select_np(se, rr_, gamma)
        rot = np.where(arange < D,
                       ((arange - rr_) % max(D, 1)).astype(np.float64), np.inf)
        if live is not None:
            rx = np.where(np.concatenate([live, [False]]), rot, np.inf)
            rot = rx if np.isfinite(rx.min()) else rot
        return _select_np(rot, 0, 0.0)

    ends_all = np.zeros((R, W))
    oks_all = np.zeros((R, W), dtype=bool)
    for r in range(R):
        excl = excluded_at(now)
        ds_r = np.zeros(W, dtype=np.int64)
        qat_r = np.zeros(W)
        for w in range(W):
            jm1, jm2 = float(jm[r, w, 0]), float(jm[r, w, 1])
            d1 = choose(rr)
            rr += 1
            q[d1] += L
            qat1 = q[d1]
            start1 = max(now, busy[d1])
            # service = (L * jm) / bw, NOT start + L/bw*jm: a multiply whose
            # result feeds the busy-chain add invites XLA's FMA contraction
            # inside lax.scan (single-rounded a+b*c), which numpy cannot
            # reproduce — a division result is contraction-proof. Mirrored
            # exactly in the jax twin.
            endb1 = start1 + (L * jm1) / effbw(d1, start1)
            f1 = overlaps(d1, start1, endb1)
            if not f1:
                busy[d1] = endb1
            else:
                q[d1] -= L
            t2 = start1 + det
            live = ~excluded_at(t2)
            d2 = choose(rr, live=live)
            if f1:
                rr += 1
                q[d2] += L
                qat2 = q[d2]
                start2 = max(t2, busy[d2])
                endb2 = start2 + (L * jm2) / effbw(d2, start2)
                f2 = overlaps(d2, start2, endb2)
                if not f2:
                    busy[d2] = endb2
                else:
                    q[d2] -= L
                ok = not f2
                d_f, endb_f, qat_f = d2, endb2, qat2
            else:
                ok = True
                d_f, endb_f, qat_f = d1, endb1, qat1
            ds_r[w] = d_f
            ends_all[r, w] = endb_f + lat[d_f]
            oks_all[r, w] = ok
            qat_r[w] = qat_f
        # completion-ordered EWMA drain (failures -> scratch row D)
        key_order = np.where(oks_all[r], ends_all[r], np.inf)
        order = np.argsort(key_order, kind="stable")
        for w in order:
            d = int(ds_r[w]) if oks_all[r, w] else D
            tob = ends_all[r, w] - now
            a = alpha[d]
            x = (qat_r[w] + L) / bw_score[d]
            sample = np.clip((tob - b0[d]) / (x if x > 0 else 1.0), 0.05, 1e4)
            if x > 0:
                b1[d] = (1 - a) * b1[d] + a * sample
            resid = max(0.0, tob - b1[d] * x)
            b0[d] = (1 - b0a[d]) * b0[d] + b0a[d] * resid
            q[d] = max(0.0, q[d] - L)
        if oks_all[r].any():
            now = float(np.max(np.where(oks_all[r], ends_all[r], -np.inf)))
        else:
            now = now + det
    total_ok = int(oks_all.sum())
    bytes_ok = total_ok * L
    makespan = now
    throughput = bytes_ok / max(makespan, 1e-12)
    ends_flat = np.where(oks_all, ends_all, np.inf).ravel()
    onsets = fs.ravel()
    valid = onsets < min(makespan, FAR_WINDOW * 0.5)
    healing = -1.0
    if valid.any():
        heal = np.full(onsets.shape, -np.inf)
        for i, o in enumerate(onsets):
            if valid[i]:
                after = ends_flat[ends_flat >= o]
                heal[i] = (after.min() - o) if after.size else np.inf
        healing = float(heal.max())
    lost = R * W - total_ok
    return (float(throughput), float(healing), float(bytes_ok),
            float(lost), float(makespan))


# -- jax twin ----------------------------------------------------------------

_SIM_CACHE: Dict[tuple, tuple] = {}


def _build_sim(p: SprayProgram, policy: str, fault_jitter: float):
    """One seed-key -> metrics function, closed over the program constants.
    Mirrors `simulate_spray_ref` op for op; every reduction that is
    float-order-sensitive (degradation factor products, the EWMA drain) is
    either statically unrolled or an explicit scan, so CPU results match
    the numpy twin bit for bit under x64."""
    import jax
    import jax.numpy as jnp

    if policy not in ("tent", "round_robin"):
        raise ValueError(f"unsupported sweep policy {policy!r}")
    D, R, W = p.n_rails, p.rounds, p.wave
    L = float(p.length)
    det = float(p.detect)
    gamma = float(p.gamma)
    fj = float(fault_jitter)

    def simulate(key):
        # All program constants materialize at trace time, inside the
        # caller's x64() scope — hoisting them to build time would
        # commit them as float32 and silently demote the whole sim.
        FS = jnp.asarray(p.fail_start, dtype=float)
        FE = jnp.asarray(p.fail_end, dtype=float)
        DSS = jnp.asarray(p.degs_start, dtype=float)
        DSE = jnp.asarray(p.degs_end, dtype=float)
        DSF = jnp.asarray(p.degs_factor, dtype=float)
        DDS = jnp.asarray(p.degd_start, dtype=float)
        DDE = jnp.asarray(p.degd_end, dtype=float)
        DDF = jnp.asarray(p.degd_factor, dtype=float)
        ext = lambda a, fill: jnp.concatenate(
            [jnp.asarray(a, dtype=float), jnp.full((1,), fill)])
        bw_score = ext(p.bw_score, 1.0)
        bw_src = ext(p.bw_src, 1.0)
        bw_dst = ext(p.bw_dst, 1.0)
        pen = ext(p.penalty, jnp.inf)
        lat = ext(p.latency, 0.0)
        alpha = ext(p.ewma_alpha, 0.0)
        b0a = ext(p.beta0_alpha, 0.0)
        b0_init = ext(p.beta0, 0.0)
        b1_init = ext(p.beta1, 1.0)
        bw_score, bw_src, bw_dst = (
            _opaque(bw_score), _opaque(bw_src), _opaque(bw_dst))
        arange = jnp.arange(D + 1)

        def _select(scores, rr_, gamma_):
            s_min = jnp.min(scores)
            in_w = scores <= (1.0 + gamma_) * s_min
            n_w = jnp.sum(in_w)
            k = (rr_ % jnp.maximum(n_w, 1)).astype(jnp.int32)
            order = jnp.cumsum(in_w.astype(jnp.int64)) - 1
            return jnp.min(jnp.where(in_w & (order == k), arange, D + 1))

        uf, us, ud, jm = _draws_jnp(p, key)
        # Mirrors `_jitter_windows_np` op for op, with the same division
        # barriers so XLA cannot FMA-contract the scale arithmetic.
        inv = _opaque(_inv_fj(fj))
        fs = jnp.maximum(0.0, FS * (1.0 + uf[..., 0] / inv))
        fe = fs + (FE - FS) / (1.0 / (1.0 + uf[..., 1] / inv))
        dss = jnp.maximum(0.0, DSS * (1.0 + us[..., 0] / inv))
        dse = dss + (DSE - DSS) / (1.0 / (1.0 + us[..., 1] / inv))
        dsf = jnp.clip(DSF * (1.0 + us[..., 2] / inv), 0.01, 1.0)
        dds = jnp.maximum(0.0, DDS * (1.0 + ud[..., 0] / inv))
        dde = dds + (DDE - DDS) / (1.0 / (1.0 + ud[..., 1] / inv))
        ddf = jnp.clip(DDF * (1.0 + ud[..., 2] / inv), 0.01, 1.0)

        def excluded_at(t):  # (D,) detect-shifted fault visibility
            return jnp.any((fs + det <= t) & (t < fe + det), axis=1)

        def overlaps(d, s, e):  # scratch row D has no windows -> False
            valid = d < D
            dc = jnp.minimum(d, D - 1)
            return valid & jnp.any((fs[dc] < e) & (fe[dc] > s))

        def degfac(start, end, fac, d, t):
            valid = d < D
            dc = jnp.minimum(d, D - 1)
            out = 1.0
            for k in range(start.shape[1]):  # static K: exact multiply order
                active = valid & (start[dc, k] <= t) & (t < end[dc, k])
                out = out * jnp.where(active, fac[dc, k], 1.0)
            return out

        def effbw(d, t):
            return jnp.minimum(
                bw_src[d] * degfac(dss, dse, dsf, d, t),
                bw_dst[d] * degfac(dds, dde, ddf, d, t))

        def choose(q, rr_, excl_e, live=None):
            if policy == "tent":
                s = pen * (b0_ref[0] + b1_ref[0] * (q + L) / bw_score)
                mask = excl_e if live is None else jnp.concatenate(
                    [~live, jnp.ones(1, dtype=bool)])
                sx = jnp.where(mask, jnp.inf, s)
                se = jnp.where(jnp.isinf(jnp.min(sx)), s, sx)
                return _select(se, rr_, gamma)
            rot = jnp.where(arange < D,
                            ((arange - rr_) % max(D, 1)).astype(float),
                            jnp.inf)
            if live is not None:
                rx = jnp.where(jnp.concatenate(
                    [live, jnp.zeros(1, dtype=bool)]), rot, jnp.inf)
                rot = jnp.where(jnp.isinf(jnp.min(rx)), rot, rx)
            return _select(rot, 0, 0.0)

        # b0/b1 are round-constant for scoring (the engine's chooser reads
        # telemetry that only the drain updates); a one-element list lets
        # the nested closures read the current round's vectors.
        b0_ref = [b0_init]
        b1_ref = [b1_init]

        def round_step(carry, jm_r):
            q, b0, b1, busy, rr, now = carry
            b0_ref[0] = b0
            b1_ref[0] = b1
            excl = excluded_at(now)
            excl_e = jnp.concatenate([excl, jnp.ones(1, dtype=bool)])

            def slice_step(c2, jm_w):
                q, busy, rr = c2
                jm1, jm2 = jm_w[0], jm_w[1]
                d1 = choose(q, rr, excl_e)
                rr = rr + 1
                q = q.at[d1].add(L)
                qat1 = q[d1]
                start1 = jnp.maximum(now, busy[d1])
                # (L * jm) / bw: see the numpy twin — keeps XLA from
                # FMA-contracting the busy-chain add inside the scan
                endb1 = start1 + (L * jm1) / effbw(d1, start1)
                f1 = overlaps(d1, start1, endb1)
                busy = busy.at[d1].set(jnp.where(f1, busy[d1], endb1))
                q = q.at[d1].add(jnp.where(f1, -L, 0.0))
                t2 = start1 + det
                live = ~excluded_at(t2)
                d2 = choose(q, rr, excl_e, live=live)
                rr = rr + f1.astype(rr.dtype)
                q = q.at[d2].add(jnp.where(f1, L, 0.0))
                qat2 = q[d2]
                start2 = jnp.maximum(t2, busy[d2])
                endb2 = start2 + (L * jm2) / effbw(d2, start2)
                f2 = overlaps(d2, start2, endb2)
                busy = busy.at[d2].set(
                    jnp.where(f1 & ~f2, endb2, busy[d2]))
                q = q.at[d2].add(jnp.where(f1 & f2, -L, 0.0))
                ok = ~(f1 & f2)
                d_f = jnp.where(f1, d2, d1)
                endb_f = jnp.where(f1, endb2, endb1)
                qat_f = jnp.where(f1, qat2, qat1)
                return (q, busy, rr), (d_f, endb_f + lat[d_f], ok, qat_f)

            (q, busy, rr), (ds, ends, oks, qats) = jax.lax.scan(
                slice_step, (q, busy, rr), jm_r)
            key_order = jnp.where(oks, ends, jnp.inf)
            order = jnp.argsort(key_order, stable=True)

            def drain_step(c3, inp):
                b0_, b1_, q_ = c3
                d, endt, qas, ok = inp
                du = jnp.where(ok, d, D)
                # `one` is a traced, always-1.0 divisor: dividing each EWMA
                # product by it forces a separate IEEE rounding, blocking
                # the backend's mul+add->fma contraction that would break
                # bit-parity with simulate_spray_ref (exact, since
                # x/1.0 == x).
                one = jnp.where(du >= 0, 1.0, 2.0)
                tob = endt - now
                a = alpha[du]
                x = (qas + L) / bw_score[du]
                sample = jnp.clip(
                    (tob - b0_[du]) / jnp.where(x > 0, x, 1.0), 0.05, 1e4)
                b1d = jnp.where(
                    x > 0,
                    ((1 - a) * b1_[du]) / one + (a * sample) / one,
                    b1_[du])
                resid = jnp.maximum(0.0, tob - (b1d * x) / one)
                b0d = ((1 - b0a[du]) * b0_[du]) / one + \
                    (b0a[du] * resid) / one
                return (b0_.at[du].set(b0d), b1_.at[du].set(b1d),
                        q_.at[du].set(jnp.maximum(0.0, q_[du] - L))), None

            (b0, b1, q), _ = jax.lax.scan(
                drain_step, (b0, b1, q),
                (ds[order], ends[order], qats[order], oks[order]))
            any_ok = jnp.any(oks)
            now2 = jnp.where(
                any_ok, jnp.max(jnp.where(oks, ends, -jnp.inf)), now + det)
            return (q, b0, b1, busy, rr, now2), (ends, oks)

        init = (jnp.zeros(D + 1), b0_init, b1_init, jnp.zeros(D + 1),
                jnp.asarray(0, dtype=jnp.int32), jnp.asarray(0.0))
        (q, b0, b1, busy, rr, now), (ends_all, oks_all) = jax.lax.scan(
            round_step, init, jm)
        total_ok = jnp.sum(oks_all)
        bytes_ok = total_ok * L
        makespan = now
        throughput = bytes_ok / jnp.maximum(makespan, 1e-12)
        ends_flat = jnp.where(oks_all, ends_all, jnp.inf).ravel()
        onsets = fs.ravel()
        valid = onsets < jnp.minimum(makespan, FAR_WINDOW * 0.5)

        def heal_one(o):
            after = jnp.min(
                jnp.where(ends_flat >= o, ends_flat, jnp.inf))
            return after - o

        heal = jax.lax.map(heal_one, onsets)
        healing = jnp.where(
            jnp.any(valid),
            jnp.max(jnp.where(valid, heal, -jnp.inf)), -1.0)
        lost = R * W - total_ok
        return (throughput, healing, bytes_ok,
                lost.astype(float), makespan)

    return simulate


def _sim_fns(p: SprayProgram, policy: str, fault_jitter: float):
    import jax

    cache_key = (id(p), policy, float(fault_jitter))
    hit = _SIM_CACHE.get(cache_key)
    if hit is not None and hit[0] is p:
        return hit[1], hit[2]
    simulate = _build_sim(p, policy, fault_jitter)
    single = jax.jit(simulate)
    sweep = jax.jit(jax.vmap(simulate))
    _SIM_CACHE[cache_key] = (p, single, sweep)
    return single, sweep


def spray_single(p: SprayProgram, *, base_seed: int = 0, seed_index: int = 0,
                 policy: str = "tent",
                 fault_jitter: float = 0.0) -> Tuple[float, ...]:
    """One independently-jitted seed:
    `(throughput, healing_s, bytes_ok, lost, makespan)`. Exact-equal to the
    matching lane of `spray_sweep` on the CPU (pinned in
    tests/test_mc_sweep.py); on a TPU, whose float64 is emulated, the two
    programs may round differently (ROADMAP A4)."""
    single, _ = _sim_fns(p, policy, fault_jitter)
    with x64():
        out = single(_seed_key(base_seed, seed_index))
        return tuple(float(np.asarray(v)) for v in out)


def spray_sweep(p: SprayProgram, n_seeds: int, *, base_seed: int = 0,
                policy: str = "tent",
                fault_jitter: float = 0.0) -> Dict[str, np.ndarray]:
    """The vmapped Monte Carlo sweep: `n_seeds` independent fault draws in
    one jit dispatch. Returns per-seed float64 arrays keyed `throughput`,
    `healing_s`, `bytes_ok`, `lost`, `makespan`."""
    import jax.numpy as jnp

    _, sweep = _sim_fns(p, policy, fault_jitter)
    with x64():
        keys = jnp.stack(
            [_seed_key(base_seed, i) for i in range(n_seeds)])
        out = sweep(keys)
        arrs = [np.asarray(v) for v in out]
    return {"throughput": arrs[0], "healing_s": arrs[1],
            "bytes_ok": arrs[2], "lost": arrs[3], "makespan": arrs[4]}
