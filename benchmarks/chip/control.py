#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, for many seeds in one
process: the program's served-token gap (the number a run compares) and
the control's.

The control is the plain reference put in the program's place one
precision below the configuration's: every matrix quantized to float8 e4m3
(per output channel, `arch.fp8_params`). At every position of the same
prompts and served tokens it reads the gap, in the float32 reference's
logits, of the token that the float8 reference puts first.

For each seed the program serves a short window at the cell's own load
(at least `--seconds`, and until a call of the mix's longest prompt has
finished), then the run's own sample of requests is checked. One JSON line
per seed, then a summary line:

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 --seconds 10

The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_harness as H  # noqa: E402
import bench_traffic as traffic  # noqa: E402


def control_gap(arch, config, params, sample, max_len: int) -> float:
    """Widest gap, over the sampled positions, between the reference's best
    logit and its logit of the token the float8 reference puts first."""
    tokens, query, spans = H.reference_inputs(sample, max_len)
    low = arch.fp8_params(params)
    _, first, _ = arch.logit_stats(config, low, tokens, query)
    del low
    best, _, q = arch.logit_stats(config, params, tokens, first)
    return float(max((best[i, a:b] - q[i, a:b]).max() for i, (a, b) in enumerate(spans)))


def serve_until_longest(cell, server, seams, seed: int, seconds: float) -> H.Served:
    """The seed's calls for `seconds`, and on until one of the mix's
    longest prompts has been served."""
    longest = max(traffic.prompt_buckets(cell.mix))
    return H.serve_window(
        cell, server, seams, seed, seconds, keep=set(),
        until=lambda recs: any(r.prompt_len == longest and not r.error for r in recs))


def readings(root: Path, workload: str, seeds, seconds: float, *, platform: str = "tpu"):
    cell = H.load_cell(root, workload)
    H.device_gate(cell.workload["chips"], platform)
    H.configure_cache(root)
    sys.path.insert(0, str(root / "src"))
    import jax

    warm = False
    for seed in seeds:
        params = cell.arch.make_params(cell.config, seed)
        jax.block_until_ready(params)
        server, seams = H.build_server(cell, params)
        if not warm:
            H.warm_up(cell, server, seams, seed)
            warm = True
        try:
            served = serve_until_longest(cell, server, seams, seed, seconds)
        finally:
            seams.restore()
        recs = served.records
        sample = H.sample_requests(recs, cell.mix["batch"], cell.mix["check_tokens"], seed)
        del server, seams
        gc.collect()
        max_len = cell.mix["max_len"]
        got = H.served_gap(cell.arch, cell.config, params, sample, max_len)
        low = control_gap(cell.arch, cell.config, params, sample, max_len)
        row = {"seed": seed, "program_gap": got, "control_gap": low, "calls": len(recs),
               "sampled_requests": len(sample),
               "sampled_tokens": int(sum(r.n_new for r, _ in sample)),
               "longest": int(max(r.prompt_len + r.n_new for r, _ in sample))}
        print(json.dumps(row), flush=True)
        del params
        gc.collect()
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        rows = list(readings(HERE.parents[1], args.workload, seeds, args.seconds))
    except H.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    prog = np.array([r["program_gap"] for r in rows])
    ctrl = np.array([r["control_gap"] for r in rows])
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_gap_max": float(prog.max()),
                      "control_gap_min": float(ctrl.min()),
                      "separation": float(ctrl.min() / prog.max())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
