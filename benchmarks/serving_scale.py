# Serving-scale bench: batched SoA stepper vs per-request loop.
"""Production-stream serving scale benchmark.

One claim rides here: **requests-simulated/sec** of the batched stepper
(whole phases advance per virtual-clock tick over the `RequestTable`,
cold-prefix promotions leave as one cohort batch per tick) against the
per-request event-driven closed loop (every request a chain of fabric
callbacks, every promotion its own batch). Both run the same slowed
production fabric; the floor is ``SCALE_SPEEDUP_FLOOR``x.

All simulated times are virtual; the requests/sec rates are wall-clock and
machine-dependent, which is why the gate is a wide floor and not a pin.

    python -m benchmarks.serving_scale                  # full run
    python -m benchmarks.serving_scale --quick          # CI smoke
    python -m benchmarks.serving_scale --out BENCH_serving_scale.json

The --out document uses the ``tent-scenario-reports/v1`` schema so
``benchmarks.diff old new --fail-on-regression PCT`` tracks the trajectory
with no extra tooling.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

from repro.scenarios import ScenarioRunner, get

SCHEMA = "tent-scenario-reports/v1"
SCENARIO = "serving_production_stream"
# acceptance: the batched SoA stepper simulates >= 10x the requests/sec of
# the per-request event loop
SCALE_SPEEDUP_FLOOR = 10.0
# the per-request arm: enough requests to amortize engine warm-up, few
# enough that the per-request event count stays affordable; concurrency
# matches the legacy closed-loop scenarios (the HiCache GPU pool is sized
# for a handful of concurrent working sets)
ASYNC_CLIENTS, ASYNC_TURNS, ASYNC_CONCURRENCY = 64, 4, 8


def _stream_spec(quick: bool):
    spec = get(SCENARIO)
    if quick:
        spec = dataclasses.replace(
            spec,
            workload=dataclasses.replace(spec.workload, stream_requests=20_000))
    return spec


def bench_batched(quick: bool) -> dict:
    """The batched arm: the library scenario itself (tent policy), timed."""
    spec = _stream_spec(quick)
    t0 = time.perf_counter()
    rep = ScenarioRunner(spec).run_policy("tent")
    wall = time.perf_counter() - t0
    n = int(rep.extra["requests_completed"])
    return {
        "requests": n,
        "wall_seconds": wall,
        "rate": n / wall,
        "throughput": rep.throughput,
        "makespan": rep.makespan,
        "p90_ttft_s": rep.extra["p90_ttft_s"],
        "p99_ttft_s": rep.extra["p99_ttft_s"],
    }


def bench_async(quick: bool) -> dict:
    """The per-request arm: the same slowed fabric and engine knobs, but the
    event-driven closed loop (every request a chain of fabric callbacks,
    HiCache promotions per request)."""
    spec = _stream_spec(quick)
    clients = ASYNC_CLIENTS // 2 if quick else ASYNC_CLIENTS
    spec = dataclasses.replace(
        spec,
        workload=dataclasses.replace(
            spec.workload, stream_requests=0, clients=clients,
            turns=ASYNC_TURNS, concurrency=ASYNC_CONCURRENCY),
        faults=(),  # the async arm is a rate baseline, not an SLO scenario
        expectations=dataclasses.replace(
            spec.expectations, tent_vs_baseline=0.0, ttft_p90_vs_baseline=0.0,
            max_ttft_p99_s=0.0, max_tpot_p99_s=0.0),
    )
    t0 = time.perf_counter()
    rep = ScenarioRunner(spec).run_policy("tent")
    wall = time.perf_counter() - t0
    n = clients * ASYNC_TURNS
    return {
        "requests": n,
        "wall_seconds": wall,
        "rate": n / wall,
        "throughput": rep.throughput,
        "makespan": rep.makespan,
    }


def _policy_report(rate: float, extra: dict) -> dict:
    """Minimal PolicyReport-shaped dict (the keys benchmarks.diff consumes)
    with the requests-simulated/sec rate in the throughput slot."""
    return {
        "policy": extra["mode"],
        "ok": True,
        "throughput": rate,
        "recovery_ms": -1.0,
        "stall_ms": -1.0,
        "extra": extra,
    }


def run(quick: bool = False) -> list:
    batched = bench_batched(quick)
    per_req = bench_async(quick)
    speedup = batched["rate"] / per_req["rate"]
    violations = []
    if speedup < SCALE_SPEEDUP_FLOOR:
        violations.append(
            f"batched stepper simulates {speedup:.1f}x the per-request "
            f"loop's requests/sec (< {SCALE_SPEEDUP_FLOOR:.0f}x floor)")
    docs = [{
        "scenario": "serving_stream_scale",
        "ok": not violations,
        "violations": violations,
        "policies": {
            "batched": _policy_report(
                batched["rate"],
                {"mode": "batched", **batched, "speedup_vs_per_request": speedup}),
            "per_request": _policy_report(
                per_req["rate"], {"mode": "per_request", **per_req}),
        },
        "spec": {"policies": ["batched", "per_request"],
                 "scenario": SCENARIO, "quick": quick},
    }]
    return docs


def render(docs: list) -> None:
    scale = docs[0]["policies"]
    b, p = scale["batched"]["extra"], scale["per_request"]["extra"]
    print(f"\nserving_stream_scale ({'quick' if docs[0]['spec']['quick'] else 'full'})")
    print(f"  batched:     {b['requests']:7d} requests in "
          f"{b['wall_seconds']:6.1f}s wall = {b['rate']:>10,.0f} req/s")
    print(f"  per-request: {p['requests']:7d} requests in "
          f"{p['wall_seconds']:6.1f}s wall = {p['rate']:>10,.0f} req/s")
    print(f"  speedup: {b['speedup_vs_per_request']:.1f}x "
          f"(floor {SCALE_SPEEDUP_FLOOR:.0f}x)")
    for doc in docs:
        for v in doc["violations"]:
            print(f"  VIOLATION: {v}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller stream (CI smoke)")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="write the rates as a tent-scenario-reports/v1 "
                         "document (default: BENCH_serving_scale.json; "
                         "compare runs with benchmarks.diff)")
    args = ap.parse_args(argv)
    docs = run(quick=args.quick)
    render(docs)
    out = args.out or "BENCH_serving_scale.json"
    with open(out, "w") as f:
        json.dump({
            "schema": SCHEMA,
            "generated_unix": round(time.time(), 3),
            "scenarios": len(docs),
            "violated": sum(not d["ok"] for d in docs),
            "reports": docs,
        }, f, indent=2)
        f.write("\n")
    print(f"\nwrote {out}", file=sys.stderr)
    if any(not d["ok"] for d in docs):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
