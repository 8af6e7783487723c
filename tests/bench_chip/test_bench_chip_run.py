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


SEAM_METRICS = {"prefill_mfu", "decode_mfu", "handoff_copy_ms", "spray_us_per_slice"}
SPAN_METRICS = {"kv_segment_ms", "engine_drain_ms", "engine_wave_us_per_slice",
                "engine_loop_us_per_slice"}


def test_traced_run_reports_per_layer_metrics(tree, jax_cache_restored):
    out = run(tree, CELLS[0], True)
    assert out["correct"] is True, out["checks"]
    # the CPU trace holds no device plane: the device readers find nothing
    # and leave their metric out; the readers of the seams and of the
    # program's spans report
    assert set(out["metrics"]) == SEAM_METRICS | SPAN_METRICS
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] == 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.fixture
def attached(monkeypatch):
    """Every server a run builds, and what each was given to record its
    spans with."""
    from repro.serving import DisaggregatedServer

    seen = []
    build = bench_harness.build_server
    attach = DisaggregatedServer.attach_spans

    def build_server(*a, **kw):
        server, seams = build(*a, **kw)
        seen.append((server, []))
        return server, seams

    def attach_spans(self, rec):
        next(given for s, given in seen if s is self).append(rec)
        attach(self, rec)

    monkeypatch.setattr(bench_harness, "build_server", build_server)
    monkeypatch.setattr(DisaggregatedServer, "attach_spans", attach_spans)
    return seen


@pytest.mark.parametrize("trace", [False, True])
def test_spans_are_attached_in_the_traced_run_only(tree, trace, attached,
                                                   jax_cache_restored):
    from repro.obs import HostSpans

    out = run(tree, CELLS[0], trace)
    assert out["correct"] is True, out["checks"]
    [(server, given)] = attached
    assert server._spans is None and server.engine._spans is None
    if trace:
        assert len(given) == 2 and isinstance(given[0], HostSpans) and given[1] is None
        assert given[0].calls == out["attempted"] // bench_smoke.SMOKE_MIX["batch"]
    else:
        assert given == []


def test_a_program_without_spans_runs_traced(tree, monkeypatch, jax_cache_restored):
    """The parent of the change that added `attach_spans` has none: its
    traced run reports what the seams give and leaves the span metrics out."""
    from repro.serving import DisaggregatedServer

    monkeypatch.delattr(DisaggregatedServer, "attach_spans")
    out = run(tree, CELLS[0], True)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == SEAM_METRICS


def test_same_seed_makes_the_same_weights(tree, jax_cache_restored):
    """Weights come from the seed alone, made on the device."""
    cell = bench_harness.load_cell(tree, CELLS[0])
    a, b, c = (jax.tree_util.tree_leaves(cell.arch.make_params(cell.config, s))
               for s in (SEED, SEED, SEED + 1))
    same = lambda x, y: np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert all(same(x, y) for x, y in zip(a, b))
    assert not all(same(x, y) for x, y in zip(a, c))
