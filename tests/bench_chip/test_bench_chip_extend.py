"""A later change adds a cell, a configuration, a traffic mix and a
per-layer metric as new files plus new entries in BENCHMARK.json; the
harness finds each by name and no file already there changes."""
import hashlib
import json
import time

import bench_harness
import bench_smoke

READER = '''"""Calls the traced window served (calls). Moves `output_tok_s`."""


def read(ctx):
    return len(ctx.calls) or None
'''


def digests(root):
    return {p: hashlib.sha1(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file() and "__pycache__" not in p.parts
            and ".jax_cache" not in p.parts}


def test_new_cell_config_mix_and_metric_are_found_by_name(tmp_path, jax_cache_restored):
    root = bench_smoke.smoke_tree(tmp_path)
    before = digests(root)
    bdir = root / "benchmarks/chip"
    cfg = json.loads((bdir / "configs/qwen2-0.5b.json").read_text())
    cfg.update(name="qwen2-0.5b-tall", num_hidden_layers=3)
    (bdir / "configs/qwen2-0.5b-tall.json").write_text(json.dumps(cfg))
    mix = dict(bench_smoke.SMOKE_MIX, prompt_calls=[[12, 1], [20, 1]])
    (bdir / "traffic/chat.json").write_text(json.dumps(mix))
    (bdir / "checks/qwen2-0.5b-tall.chat.json").write_text(
        json.dumps({"served_gap_max": bench_smoke.SMOKE_LIMITS["qwen2-0.5b"]}))
    (bdir / "metrics/calls_traced.py").write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen2-0.5b-tall", "source": cfg["source"],
                             "file": "benchmarks/chip/configs/qwen2-0.5b-tall.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "qwen2-0.5b-tall.chat", "config": "qwen2-0.5b-tall",
                               "traffic": "chat", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "calls_traced", "unit": "calls", "better": "higher",
                               "source": "program_counter", "layer": "client",
                               "moves": "output_tok_s", "workloads": ["qwen2-0.5b-tall.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    changed = [p for p, d in digests(root).items() if p in before and before[p] != d]
    assert changed == [root / "BENCHMARK.json"]

    cell = bench_harness.load_cell(root, "qwen2-0.5b-tall.chat")
    assert cell.config["num_hidden_layers"] == 3
    assert bench_harness.traffic.prompt_buckets(cell.mix) == [12, 20]
    out = bench_harness.run(root, "qwen2-0.5b-tall.chat", 5, 0.3, True, time.perf_counter(),
                            platform="cpu")
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["calls_traced"]["value"] >= 1
    assert out["metrics"]["calls_traced"]["unit"] == "calls"
    # an existing cell does not report the new metric
    old = bench_harness.load_cell(root, "qwen2-0.5b.doc-qa")
    assert "calls_traced" not in [m["name"] for m in old.per_layer()]
