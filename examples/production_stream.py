"""Production-scale serving stream: 20k Zipf/Poisson requests in seconds.

Builds the request mix with `repro.scenarios.traffic` — the same seeded
generator the `serving_production_stream` scenario, `benchmarks/serving_scale.py`,
and the Monte-Carlo sweep lowering share — inspects its shape, then runs a
scaled-down production stream through the batched SoA stepper and prints its
report.

Run:  PYTHONPATH=src python examples/production_stream.py
"""
import dataclasses
import time

import numpy as np

from repro.scenarios import ScenarioRunner, get
from repro.scenarios.traffic import TrafficSpec, promotion_bytes

# --- the traffic mix, standalone -------------------------------------------
spec = get("serving_production_stream")
wl = spec.workload
traffic = TrafficSpec(
    requests=20_000, arrival_rate=wl.arrival_rate, zipf_alpha=wl.zipf_alpha,
    groups=wl.traffic_groups, input_tokens=wl.input_tokens,
    output_tokens=wl.output_tokens, seed=spec.seed).generate()
promo = promotion_bytes(
    traffic, prefix_frac=wl.prefix_frac,
    kv_bytes_per_token=wl.stream_kv_bytes_per_token, resident_s=wl.resident_s)
counts = np.bincount(traffic.group, minlength=wl.traffic_groups)
cold = int((promo > 0).sum())
print(f"stream: {len(traffic)} requests over {traffic.arrival[-1]:.0f}s, "
      f"{wl.traffic_groups} prefix groups (top group {counts.max()} hits, "
      f"median {int(np.median(counts))})")
print(f"residency model: {cold} cold prefixes promote "
      f"{promo.sum()/1e9:.1f} GB store->GPU; "
      f"{len(traffic) - cold} re-hit GPU-resident KV for free\n")

# --- the same stream through the batched stepper --------------------------
small = dataclasses.replace(
    spec, workload=dataclasses.replace(wl, stream_requests=20_000))
t0 = time.time()
rep = ScenarioRunner(small).run()
wall = time.time() - t0
print(f"{20_000/wall:7.0f} requests-simulated/s, ok={rep.ok}")
for name, pol in rep.policies.items():
    print(f"  {name:12s} {pol.throughput:7.0f} tok/s, "
          f"TTFT P90 {pol.extra['p90_ttft_s']:.2f}s, "
          f"TPOT P99 {pol.extra['p99_tpot_s']*1e3:.1f}ms")
for v in rep.violations:
    print(f"  VIOLATION: {v}")
