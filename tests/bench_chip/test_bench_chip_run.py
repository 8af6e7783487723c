"""Each cell end to end on the CPU at smoke sizes, with the harness's look
for a TPU bypassed by the test: the serving path, the seams, the metrics
and the checks."""
import json
import time

import jax
import numpy as np
import pytest

import bench_harness
import bench_smoke

CELLS = [w["name"] for w in json.loads(
    (bench_smoke.REPO / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**31 + 17  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_smoke.smoke_tree(tmp_path_factory.mktemp("smoke"))


def run(tree, cell, trace):
    return bench_harness.run(tree, cell, SEED, 0.5, trace, time.perf_counter(),
                             platform="cpu")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_with_its_end_to_end_metrics(tree, cell, jax_cache_restored):
    out = run(tree, cell, False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "ttft_p90_ms", "tpot_p90_ms",
                                   "output_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    ttft = out["metrics"]
    assert ttft["ttft_p50_ms"]["value"] <= ttft["ttft_p90_ms"]["value"]
    json.dumps(out)


def test_traced_run_reports_per_layer_metrics(tree, jax_cache_restored):
    out = run(tree, CELLS[0], True)
    assert out["correct"] is True, out["checks"]
    # the CPU trace holds no device plane: the device readers find nothing
    # and leave their metric out, the host-span readers report
    assert set(out["metrics"]) == {"prefill_mfu", "decode_mfu", "handoff_copy_ms",
                                   "spray_us_per_slice"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_makes_the_same_weights(tree, jax_cache_restored):
    """Weights come from the seed alone, made on the device."""
    cell = bench_harness.load_cell(tree, CELLS[0])
    a, b, c = (jax.tree_util.tree_leaves(cell.arch.make_params(cell.config, s))
               for s in (SEED, SEED, SEED + 1))
    same = lambda x, y: np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert all(same(x, y) for x, y in zip(a, b))
    assert not all(same(x, y) for x, y in zip(a, c))
