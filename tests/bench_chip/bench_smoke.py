"""A copy of the benchmark at smoke sizes, for runs on the CPU.

`smoke_tree(dst)` copies `BENCHMARK.json` and `benchmarks/chip/` under
`dst`, links the program's `src/` beside them, and shrinks every
configuration and every traffic mix (batch 2, prompts of 8 and 16 tokens,
8-24 new tokens). A configuration's smoke sizes and its CPU check limit
are data, one file per configuration: `smoke/<config>.json` beside this
module, with `dims` (the keys of the configuration file to overwrite),
`served_gap_max` (the served-token gap limit at those sizes on the CPU)
and `readings` (how that limit was set). The CPU gets a row of peaks so
that the per-layer readers run; its numbers are not a device's.
"""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH_REL = Path("benchmarks/chip")
SMOKE_REL = Path("tests/bench_chip/smoke")

SMOKE_MIX = dict(batch=2, max_len=48, block_calls=2, prompt_calls=[[8, 1], [16, 1]],
                 n_new=[8, 24], check_tokens=64)
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_spec(config: str, src: Path = REPO) -> dict:
    """The smoke file of a configuration, as it lies under `src`."""
    path = src / SMOKE_REL / f"{config}.json"
    if not path.is_file():
        raise FileNotFoundError(
            f"no smoke sizes for configuration {config!r}: add {path}, a JSON object "
            "with 'dims' (the configuration file's keys at CPU smoke sizes), "
            "'served_gap_max' (the served-token gap limit at those sizes, set from "
            "control.readings over 12 seeds between the program's largest gap and the "
            "float8 control's smallest) and 'readings' (those readings)")
    return json.loads(path.read_text())


def smoke_tree(dst: Path, src: Path = REPO) -> Path:
    """The benchmark of the repository at `src`, at smoke sizes, under `dst`."""
    bench = json.loads((src / "BENCHMARK.json").read_text())
    specs = {c["name"]: smoke_spec(c["name"], src) for c in bench["configs"]}
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(src / BENCH_REL, dst / BENCH_REL,
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    (dst / "src").symlink_to((src / "src").resolve())
    bdir = dst / BENCH_REL
    for c in bench["configs"]:
        path = dst / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(specs[c["name"]]["dims"])
        path.write_text(json.dumps(cfg))
    for path in (bdir / "traffic").glob("*.json"):
        path.write_text(json.dumps(SMOKE_MIX))
    for w in bench["workloads"]:
        (bdir / "checks" / f"{w['name']}.json").write_text(
            json.dumps({"served_gap_max": specs[w["config"]]["served_gap_max"]}))
    peaks = json.loads((bdir / "peaks.json").read_text())
    peaks["devices"]["cpu"] = CPU_PEAKS
    (bdir / "peaks.json").write_text(json.dumps(peaks))
    return dst
