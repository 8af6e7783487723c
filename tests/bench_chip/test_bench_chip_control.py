"""The control at a size a test run holds: the reference put in the
program's place in float8 fails the served-token limit that the program
passes, on the same prompts and served tokens."""
import json

import pytest

import bench_smoke
import control

SEEDS = [1, 2, 2**33 + 5]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_smoke.smoke_tree(tmp_path_factory.mktemp("smoke"))


@pytest.mark.parametrize("cell", [w["name"] for w in json.loads(
    (bench_smoke.REPO / "BENCHMARK.json").read_text())["workloads"]])
def test_control_fails_the_limit_the_program_passes(tree, cell, jax_cache_restored):
    limit = json.loads((tree / f"benchmarks/chip/checks/{cell}.json").read_text())["served_gap_max"]
    rows = list(control.readings(tree, cell, SEEDS, 0.0, platform="cpu"))
    assert len(rows) == len(SEEDS)
    for r in rows:
        assert r["sampled_requests"] >= 2 and r["longest"] > bench_smoke.SMOKE_MIX["n_new"][0]
        assert r["program_gap"] <= limit < r["control_gap"], r


def test_float8_control_rounds_every_matrix(tree):
    """The control's weights differ from the served ones by float8's
    rounding (about 2.6% RMS), norms and biases untouched."""
    import jax
    import numpy as np

    import bench_harness

    cell = bench_harness.load_cell(tree, "qwen2-0.5b.doc-qa")
    params = cell.arch.make_params(cell.config, 7)
    low = cell.arch.fp8_params(params)
    f32 = lambda a: np.asarray(a, np.float32)
    for path, w in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = path[-1].key
        got = f32(_at(low, path))
        rel = np.sqrt(np.mean((got - f32(w)) ** 2) / np.mean(f32(w) ** 2))
        if name in ("ln1", "ln2", "final_norm", "bq", "bk", "bv"):
            assert rel == 0, name
        else:
            assert 0.01 < rel < 0.05, (name, rel)


def _at(tree, path):
    for p in path:
        tree = tree[p.key]
    return tree
