"""The per-layer readers of the program's host spans: what each takes from
the spans of one traced window, and that each finds nothing to read in a
run without them."""
from pathlib import Path

import pytest

import bench_harness
import span_reduce

METRICS = Path(__file__).resolve().parents[2] / "benchmarks/chip/metrics"
READERS = ["kv_segment_ms", "engine_drain_ms", "engine_wave_us_per_slice",
           "engine_loop_us_per_slice"]
MS = 1_000_000  # ns


def reader(name):
    return bench_harness.load_module(METRICS / f"{name}.py", f"bench_metric_{name}")


def span(name, t0_ms, t1_ms, **attrs):
    return (name, int(t0_ms * MS), int(t1_ms * MS), -1, 0, attrs)


def two_calls():
    """Two calls of `generate`, as `HostSpans.finished()` lists them (in the
    order they opened). The first call's transfer issues 10 slices in two
    waves, one inside a drain; the second's 6 slices in one wave."""
    return [
        span("tent.generate", 0, 100, call=0),
        span("tent.kv.segments", 10, 30), span("tent.kv.spray", 30, 60),
        span("tent.engine.transfer", 31, 59, slices_issued=10),
        span("tent.engine.wave", 32, 33, slices=8), span("tent.engine.drain", 40, 50, slices=8),
        span("tent.engine.wave", 44, 46, slices=2), span("tent.engine.drain", 52, 55, slices=2),
        span("tent.kv.read", 60, 65), span("tent.decode", 70, 99),
        span("tent.generate", 100, 200, call=1),
        span("tent.kv.segments", 110, 120), span("tent.kv.spray", 120, 140),
        span("tent.engine.transfer", 121, 139, slices_issued=6),
        span("tent.engine.wave", 122, 125, slices=6), span("tent.engine.drain", 130, 134, slices=6),
        span("tent.kv.read", 140, 150),
    ]


def context(spans):
    return bench_harness.LayerContext([], None, (0.0, 1.0), None, None, {}, 2, spans)


def test_span_sums():
    s = two_calls()
    assert span_reduce.calls(s) == 2
    assert span_reduce.seconds(s, ("tent.kv.read",)) == pytest.approx(0.015)
    assert span_reduce.attr_sum(s, "tent.engine.wave", "slices") == 16
    # drains 10 + 3 + 4 ms, less the 2 ms wave inside the first
    assert span_reduce.self_seconds(s, "tent.engine.drain", ("tent.engine.wave",)) == \
        pytest.approx(0.015)
    # transfers 28 + 18 ms, less waves 1 + 3 and drains 10 + 3 + 4 (the
    # wave inside a drain taken off once)
    assert span_reduce.self_seconds(s, "tent.engine.transfer",
                                    ("tent.engine.wave", "tent.engine.drain")) == \
        pytest.approx(0.025)


@pytest.mark.parametrize("name,value", [
    ("kv_segment_ms", (20 + 5 + 10 + 10) / 2),
    ("engine_drain_ms", 15 / 2),
    ("engine_wave_us_per_slice", (1 + 2 + 3) * 1e3 / 16),
    ("engine_loop_us_per_slice", 25 * 1e3 / 16),
])
def test_reader_takes_its_metric_from_the_spans(name, value):
    assert reader(name).read(context(two_calls())) == pytest.approx(value)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("spans", [None, []], ids=["not_attached", "empty"])
def test_reader_finds_nothing_without_spans(name, spans):
    assert reader(name).read(context(spans)) is None
