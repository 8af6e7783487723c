"""A later change adds a cell, a configuration, a traffic mix and a
per-layer metric as new files plus new entries in BENCHMARK.json; the
harness finds each by name and no file already there changes."""
import hashlib
import json
import shutil
import time
from pathlib import Path

import pytest

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
        json.dumps({"served_gap_max": bench_smoke.smoke_spec("qwen2-0.5b")["served_gap_max"]}))
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


SPAN_READER = '''"""Host time per call of the token fetches (ms), from the program's
spans `tent.decode.fetch`. Moves `tpot_p90_ms`."""
import span_reduce


def read(ctx):
    if not ctx.spans or not span_reduce.calls(ctx.spans):
        return None
    return (span_reduce.seconds(ctx.spans, ("tent.decode.fetch",))
            / span_reduce.calls(ctx.spans) * 1e3)
'''
# An MHA model with an untied head, at smoke sizes; its CPU limit set from
# control.readings over 12 seeds (100-111): program gap at most 0.0296,
# float8 control gap at least 0.139.
MHA_SMOKE = {"dims": dict(hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=4, head_dim=64, intermediate_size=512,
                          vocab_size=1024),
             "served_gap_max": 0.075,
             "readings": "program gap <= 0.0296, float8 control gap >= 0.139 (12 seeds)"}


def repo_copy(dst):
    """The files a checkout of the benchmark holds, the program linked."""
    bench = json.loads((bench_smoke.REPO / "BENCHMARK.json").read_text())
    dst.mkdir()
    shutil.copy(bench_smoke.REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(bench_smoke.REPO / p, dst / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".jax_cache"))
    (dst / "src").symlink_to(bench_smoke.REPO / "src")
    return dst


def test_new_configuration_arrives_as_files_before_the_smoke_tree(tmp_path,
                                                                   jax_cache_restored):
    """A configuration with its smoke file, a mix, a check, a cell and a
    metric reading a program span, all added before `smoke_tree` runs: the
    smoke tree takes them, and the new cell runs correct on the CPU,
    untraced and traced."""
    src = repo_copy(tmp_path / "repo")
    before = digests(src)
    bdir = src / "benchmarks/chip"
    cfg = json.loads((bdir / "configs/qwen2-0.5b.json").read_text())
    cfg.update(name="mha-untied", num_key_value_heads=cfg["num_attention_heads"],
               qkv_bias=False, tie_word_embeddings=False, rope_theta=10000.0)
    (bdir / "configs/mha-untied.json").write_text(json.dumps(cfg))
    (src / "tests/bench_chip/smoke/mha-untied.json").write_text(json.dumps(MHA_SMOKE))
    (bdir / "traffic/turns.json").write_text(json.dumps(dict(
        batch=16, max_len=320, block_calls=20, prompt_calls=[[64, 7], [128, 8], [256, 5]],
        n_new=[16, 64], check_tokens=256)))
    (bdir / "checks/mha-untied.turns.json").write_text(json.dumps({"served_gap_max": 0.3}))
    (bdir / "metrics/decode_fetch_ms.py").write_text(SPAN_READER)
    bench = json.loads((src / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "mha-untied", "source": cfg["source"],
                             "file": "benchmarks/chip/configs/mha-untied.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "mha-untied.turns", "config": "mha-untied",
                               "traffic": "turns", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "decode_fetch_ms", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "model steps",
                               "moves": "tpot_p90_ms", "workloads": ["mha-untied.turns"]})
    (src / "BENCHMARK.json").write_text(json.dumps(bench))
    changed = [p for p, d in digests(src).items() if p in before and before[p] != d]
    assert changed == [src / "BENCHMARK.json"]

    (tmp_path / "smoke").mkdir()
    root = bench_smoke.smoke_tree(tmp_path / "smoke", src)
    cell = bench_harness.load_cell(root, "mha-untied.turns")
    assert cell.config["num_key_value_heads"] == 4 and not cell.config["tie_word_embeddings"]
    assert cell.checks["served_gap_max"] == MHA_SMOKE["served_gap_max"]
    for trace in (False, True):
        out = bench_harness.run(root, "mha-untied.turns", 2**35 + 1, 0.5, trace,
                                time.perf_counter(), platform="cpu")
        assert out["correct"] is True, out["checks"]
        if trace:
            assert set(out["metrics"]) == {"decode_fetch_ms"}
            assert out["metrics"]["decode_fetch_ms"]["value"] > 0
        else:
            assert "ttft_p50_ms" in out["metrics"] and "setup_s" in out["metrics"]


def test_a_configuration_without_smoke_sizes_names_the_missing_file(tmp_path):
    src = repo_copy(tmp_path / "repo")
    bench = json.loads((src / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="no-smoke"))
    (src / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "smoke").mkdir()
    missing = src / "tests/bench_chip/smoke/no-smoke.json"
    with pytest.raises(FileNotFoundError, match=str(missing)) as err:
        bench_smoke.smoke_tree(tmp_path / "smoke", src)
    assert "served_gap_max" in str(err.value) and "dims" in str(err.value)
