#!/usr/bin/env python3
"""One-off smoke run of the serving main path on one TPU chip.

Everything runs in this one process, which holds the chip; it starts no
child process. Phases, each of which raises on a failed check:

1. Device gate: JAX's first device must be a TPU. There is no CPU fallback.
2. Serving: `DisaggregatedServer` over a `TentEngine` built from the
   `disagg_prefill_decode` scenario answers three requests with qwen2-0.5b
   at its published config (all 24 layers, full vocabulary, bf16 weights
   drawn from `--seed`). Each request must match `monolithic_generate` bit
   for bit, the decode segment must hold exactly the prefill cache's bytes,
   and the bf16 last-position logits must agree with a float32 run of the
   same weights (tolerances at `REL_L2_MAX` and `TOP1_AGREE_MIN`).
3. Kernels: `kv_pack`/`kv_unpack` compiled for the chip (`interpret=False`)
   round-trip one layer of the real K cache through a shuffled page list.
4. Fused spray sweep: `spray_sweep` over 64 seeds of `single_rail_flap`
   runs in float64 on the chip and must agree with the same sweep on the
   host's CPU backend within a stated tolerance (`SWEEP_REL_TOL`): XLA:TPU
   emulates float64 with pairs of float32s, so the chip and the CPU need
   not round alike. Each lane against that seed's independently jitted
   `spray_single` run on the chip is printed, not required: on the TPU
   the two programs round differently (ROADMAP A4).

The last line of stdout is `{"ok": true, "device": {...}}`. Times, bytes and
memory printed before it are a one-off smoke reading, not a benchmark.

Run from the repository root:  python3 chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

ARCH = "qwen2-0.5b"
BATCH = 4
PROMPT_LENS = (64, 200, 512)
ASYNC_HANDOFF = (False, True, False)
N_NEW = 32
MAX_LEN = 544  # >= 512 + N_NEW, a whole number of 16-token pages
PAGE_TOKENS = 16
SWEEP_SCENARIO = "single_rail_flap"
SWEEP_SEEDS = 64
SWEEP_JITTER = 0.25
SWEEP_FIELDS = ("throughput", "healing_s", "bytes_ok", "lost", "makespan")
SWEEP_COUNTS = ("bytes_ok", "lost")
# The chip's sweep against the same sweep on the CPU, whose program is
# pinned bit-equal to the sequential numpy twin. XLA:TPU emulates float64
# with pairs of float32s, so times need not round alike: 64-seed sweeps of
# this scenario on a v5e stayed within 1.6e-9 of the CPU. A seed on a near
# tie may take another decision there (two chip programs of the same seed
# once disagreed on 2 of 64, throughput 1.6e-2 apart), so up to
# `SWEEP_FLIPS_MAX` seeds may miss the tolerance; the counts must match on
# every seed. A wrongly compiled scan misses on most seeds.
SWEEP_REL_TOL = 1e-6
SWEEP_FLIPS_MAX = 4

# bf16 against float32 on the same weights. bf16 keeps 8 mantissa bits
# (unit roundoff 2^-9); the activations and the KV cache are rounded at each
# of 24 layers, and at 2-8 layers of this width the relative L2 error of the
# last-position logits measured 0.011-0.017, growing slowly with depth. A
# layer that computes the wrong thing moves the logits by a large share of
# their norm, far above this bound.
REL_L2_MAX = 0.05
# Random weights leave some top-1/top-2 logit gaps smaller than the bf16
# error, so a few rows may flip between near-tied tokens; a broken model
# agrees on almost none. Every bf16 argmax must still be in the f32 top 5.
TOP1_AGREE_MIN = 0.75
TOP_K_CONTAINS = 5


class CompileClock:
    """Sums the backend compile time and counts persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_gate() -> jax.Device:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}")
    print(f"device: {devs[0].device_kind} x{len(devs)} "
          f"(platform {devs[0].platform})", flush=True)
    return devs[0]


def logits_agreement(lo: np.ndarray, ref: np.ndarray):
    """Per-row relative L2 error, top-1 agreement, and whether the low-precision
    argmax is among the reference's top `TOP_K_CONTAINS`."""
    rel = np.linalg.norm(lo - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    top1 = lo.argmax(-1) == ref.argmax(-1)
    topk = np.argsort(ref, axis=-1)[:, -TOP_K_CONTAINS:]
    in_topk = (topk == lo.argmax(-1)[:, None]).any(-1)
    return rel, top1, in_topk


def phase_serve(cfg, params, *, batch, prompt_lens, async_handoff, n_new,
                max_len, seed):
    """Serve one request per prompt length through `DisaggregatedServer`;
    returns the prefill cache of the last request."""
    from repro.scenarios import ScenarioRunner, get
    from repro.serving import (DisaggregatedServer, kv_bytes_per_token,
                               monolithic_generate)
    from repro.serving.disagg import prefill_jit, tree_to_bytes

    engine, _ = ScenarioRunner(get("disagg_prefill_decode")).build_engine("tent")
    server = DisaggregatedServer(engine, cfg, params, prefill_node=0, decode_node=1)
    params32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    rels, top1s, in_topks = [], [], []
    cache = None
    for i, (S, use_async) in enumerate(zip(prompt_lens, async_handoff)):
        prompt = jax.random.randint(
            jax.random.PRNGKey(seed + 1 + i), (batch, S), 0, cfg.vocab_size)
        t0 = time.perf_counter()
        res = server.generate(prompt, n_new=n_new, max_len=max_len,
                              async_handoff=use_async)
        t_gen = time.perf_counter() - t0
        ref = monolithic_generate(cfg, params, prompt, n_new=n_new, max_len=max_len)
        if not np.array_equal(res.tokens, ref):
            raise AssertionError(f"request {i}: disaggregated tokens differ from monolithic")
        last_logits, cache = prefill_jit(cfg, params, prompt, max_len)
        sent, _ = tree_to_bytes(cache)
        want = batch * max_len * kv_bytes_per_token(cfg)
        if res.kv_bytes != want or sent.size != want:
            raise AssertionError(
                f"request {i}: handed off {res.kv_bytes} B, prefill cache "
                f"{sent.size} B, expected {want} B")
        received = engine.segments.get(res.kv_segment_id).read(0, res.kv_bytes)
        if not np.array_equal(received, sent):
            raise AssertionError(f"request {i}: decode segment bytes differ from the prefill cache")
        with jax.default_matmul_precision("float32"):
            ref_logits, _ = prefill_jit(cfg, params32, prompt, max_len)
        rel, top1, in_topk = logits_agreement(np.asarray(last_logits),
                                              np.asarray(ref_logits))
        rels.append(rel)
        top1s.append(top1)
        in_topks.append(in_topk)
        print(f"serve[{i}]: B={batch} S={S} new={n_new} async={use_async} "
              f"tokens={res.tokens.size} kv_bytes={res.kv_bytes} "
              f"kv_virtual_s={res.kv_transfer_seconds:.6g} "
              f"generate_host_s={t_gen:.3f} "
              f"logits_rel_l2_max={rel.max():.4g} top1_agree={top1.mean():.3g}",
              flush=True)
    rel, top1, in_topk = (np.concatenate(x) for x in (rels, top1s, in_topks))
    print(f"serve: bf16 vs f32 logits rel_l2 max={rel.max():.4g} "
          f"mean={rel.mean():.4g} (limit {REL_L2_MAX}); top1 agree "
          f"{int(top1.sum())}/{top1.size} (min {TOP1_AGREE_MIN}); "
          f"bf16 argmax in f32 top{TOP_K_CONTAINS}: {int(in_topk.sum())}/{in_topk.size}",
          flush=True)
    if rel.max() > REL_L2_MAX:
        raise AssertionError(f"bf16 logits off the f32 reference: rel L2 {rel.max():.4g}")
    if top1.mean() < TOP1_AGREE_MIN or not in_topk.all():
        raise AssertionError("bf16 argmax disagrees with the f32 reference")
    return cache


def phase_kernels(cache, *, seed):
    """Round-trip layer 0 of the K cache, as 16-token pages, through
    kv_pack/kv_unpack into a fresh pool; exact against the jnp oracles."""
    from repro.kernels.kv_pack import kv_pack, kv_pack_ref, kv_unpack, kv_unpack_ref

    k0 = cache["k"][0]  # (B, W, K, Hd)
    B, W, K, Hd = k0.shape
    pages = k0.reshape(B * W // PAGE_TOKENS, PAGE_TOKENS, K * Hd)
    n = pages.shape[0]
    idx = jnp.asarray(np.random.default_rng(seed).permutation(n), jnp.int32)
    for name, lowered in (
        ("kv_pack", kv_pack.lower(pages, idx, interpret=False)),
        ("kv_unpack", kv_unpack.lower(jnp.zeros_like(pages), pages, idx,
                                      interpret=False)),
    ):
        if "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(f"{name} did not lower to a TPU kernel")
    t0 = time.perf_counter()
    buf = kv_pack(pages, idx, interpret=False)
    pool = kv_unpack(jnp.zeros_like(pages), buf, idx, interpret=False)
    pool.block_until_ready()
    t_k = time.perf_counter() - t0
    if not np.array_equal(np.asarray(buf), np.asarray(kv_pack_ref(pages, idx))):
        raise AssertionError("kv_pack differs from kv_pack_ref")
    ref_pool = kv_unpack_ref(jnp.zeros_like(pages), buf, idx)
    if not np.array_equal(np.asarray(pool), np.asarray(ref_pool)):
        raise AssertionError("kv_unpack differs from kv_unpack_ref")
    if not np.array_equal(np.asarray(pool), np.asarray(pages)):
        raise AssertionError("kv_pack -> kv_unpack did not restore the pages")
    print(f"kernels: kv_pack+kv_unpack {n} pages of {PAGE_TOKENS}x{K * Hd} "
          f"{pages.dtype} ({pages.nbytes} B) exact; first-call host_s={t_k:.3f}",
          flush=True)


def sweep_agreement(got, ref):
    """Per field: seeds bit-equal, seeds within `SWEEP_REL_TOL` of `ref`,
    and the largest relative deviation."""
    out = {}
    for f in SWEEP_FIELDS:
        a, b = np.asarray(got[f]), np.asarray(ref[f])
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.where(a == b, 0.0, np.abs(a - b) / np.abs(b))
        out[f] = (int(np.sum(a == b)), int(np.sum(rel <= SWEEP_REL_TOL)),
                  float(np.max(rel)))
    return out


def format_agreement(agree, n):
    return ", ".join(f"{f} bit_equal={e}/{n} within_tol={c}/{n} max_rel_dev={d:.3g}"
                     for f, (e, c, d) in agree.items())


def sweep_summary(res):
    heal_ms = np.asarray(res["healing_s"]) * 1e3
    return {"healing_p50_ms": float(np.percentile(heal_ms, 50)),
            "healing_p99_ms": float(np.percentile(heal_ms, 99)),
            "throughput_p50": float(np.percentile(res["throughput"], 50))}


def phase_sweep(*, n_seeds, cpu_device):
    """The fused f64 spray sweep on the default device, held to the same
    sweep on `cpu_device` (counts exact, times within `SWEEP_REL_TOL` on all
    but `SWEEP_FLIPS_MAX` seeds); each lane against its independently
    jitted single-seed run on the chip is printed, not required."""
    from repro.core.jit_core import spray_single, spray_sweep
    from repro.scenarios import get
    from repro.scenarios.sweep import compile_spray_program

    spec = get(SWEEP_SCENARIO)
    prog = compile_spray_program(spec)
    for policy in ("tent", "round_robin"):
        kw = dict(base_seed=spec.seed, policy=policy, fault_jitter=SWEEP_JITTER)
        t0 = time.perf_counter()
        chip = spray_sweep(prog, n_seeds, **kw)
        t_sweep = time.perf_counter() - t0
        singles = np.asarray([spray_single(prog, seed_index=i, **kw)
                              for i in range(n_seeds)])
        single = dict(zip(SWEEP_FIELDS, singles.T))
        with jax.default_device(cpu_device):
            cpu = spray_sweep(prog, n_seeds, **kw)
        print(f"sweep {SWEEP_SCENARIO} {policy}: {n_seeds} seeds f64 in one "
              f"dispatch; first-call host_s={t_sweep:.3f}; chip " + " ".join(
                  f"{k}={v!r}" for k, v in sweep_summary(chip).items())
              + "; cpu " + " ".join(
                  f"{k}={v!r}" for k, v in sweep_summary(cpu).items()), flush=True)
        vs_cpu = sweep_agreement(chip, cpu)
        print(f"sweep {policy} chip vs cpu (required): "
              f"{format_agreement(vs_cpu, n_seeds)}", flush=True)
        print(f"sweep {policy} lane vs single-seed program on the chip "
              f"(recorded, not required): "
              f"{format_agreement(sweep_agreement(chip, single), n_seeds)}",
              flush=True)
        for f, (equal, close, _) in vs_cpu.items():
            if f in SWEEP_COUNTS and equal != n_seeds:
                raise AssertionError(
                    f"sweep {policy}: {f} differs from the CPU on "
                    f"{n_seeds - equal} seeds")
            if close < n_seeds - SWEEP_FLIPS_MAX:
                raise AssertionError(
                    f"sweep {policy}: {f} off the CPU by more than "
                    f"{SWEEP_REL_TOL} on {n_seeds - close} seeds")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the weights and the prompts")
    args = ap.parse_args(argv)

    cache_dir, from_env = configure_compile_cache()
    print(f"compile cache: {cache_dir} "
          f"({'from JAX_COMPILATION_CACHE_DIR' if from_env else 'fixed in-repo default'})",
          flush=True)
    clock = CompileClock()
    dev = device_gate()
    t_start = time.perf_counter()

    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name} published config, {cfg.num_layers} layers, "
          f"{n_params} params bf16 from seed {args.seed}", flush=True)

    cache = phase_serve(cfg, params, batch=BATCH, prompt_lens=PROMPT_LENS,
                        async_handoff=ASYNC_HANDOFF, n_new=N_NEW,
                        max_len=MAX_LEN, seed=args.seed)
    phase_kernels(cache, seed=args.seed)
    phase_sweep(n_seeds=SWEEP_SEEDS, cpu_device=jax.devices("cpu")[0])

    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
    print(f"one-off chip smoke, not a benchmark: total_host_s="
          f"{time.perf_counter() - t_start:.1f} compile_s={clock.seconds:.1f} "
          f"compiles={clock.compiles} persistent_cache_hits={clock.cache_hits} "
          f"peak_bytes_in_use={peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
