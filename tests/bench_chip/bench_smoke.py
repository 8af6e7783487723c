"""A copy of the benchmark at smoke sizes, for runs on the CPU.

`smoke_tree(dst)` copies `BENCHMARK.json` and `benchmarks/chip/` under
`dst`, links the program's `src/` beside them, and shrinks every
configuration (2 layers, narrow widths, a 1,024-token vocabulary, as the
program's own SMOKE configurations are) and every traffic mix (batch 2,
prompts of 8 and 16 tokens, 8-24 new tokens). The CPU gets a row of peaks so
that the per-layer readers run; its numbers are not a device's.
"""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH_REL = Path("benchmarks/chip")

SMOKE_DIMS = {
    "qwen2-0.5b": dict(hidden_size=224, num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=56, intermediate_size=448,
                       vocab_size=1024),
}
SMOKE_MIX = dict(batch=2, max_len=48, block_calls=2, prompt_calls=[[8, 1], [16, 1]],
                 n_new=[8, 24], check_tokens=64)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# Served-token gap limit at these sizes, on the CPU, set the way the chip's
# are: 12 seeds of `control.readings` (about 70 served tokens each) read a
# program gap of at most 0.0098 and a float8 control gap of at least 0.057.
# The tests' windows are timed, so the requests sampled vary with the
# machine's speed: the limit sits more than 2x above the program's largest
# reading.
SMOKE_LIMITS = {"qwen2-0.5b": 0.035}


def smoke_tree(dst: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / BENCH_REL, dst / BENCH_REL,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(REPO / "src")
    bdir = dst / BENCH_REL
    for name, dims in SMOKE_DIMS.items():
        path = bdir / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(dims)
        path.write_text(json.dumps(cfg))
    for path in (bdir / "traffic").glob("*.json"):
        path.write_text(json.dumps(SMOKE_MIX))
    for w in json.loads((dst / "BENCHMARK.json").read_text())["workloads"]:
        (bdir / "checks" / f"{w['name']}.json").write_text(
            json.dumps({"served_gap_max": SMOKE_LIMITS[w["config"]]}))
    peaks = json.loads((bdir / "peaks.json").read_text())
    peaks["devices"]["cpu"] = CPU_PEAKS
    (bdir / "peaks.json").write_text(json.dumps(peaks))
    return dst
