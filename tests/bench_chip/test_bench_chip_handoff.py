"""The readers of the KV handoff's bytes, `handoff_us_per_mb` and
`handoff_fill`: their values on spans of two calls, written out and
recorded from the program at smoke size, and nothing read where the spans
are missing or `tent.kv.pack` carries no `bytes` (a program without the
attrs)."""
from pathlib import Path

import pytest

import bench_harness

METRICS = Path(__file__).resolve().parents[2] / "benchmarks/chip/metrics"
READERS = ["handoff_us_per_mb", "handoff_fill"]
HANDOFF = ("tent.kv.pack", "tent.kv.segments", "tent.kv.spray", "tent.kv.read",
           "tent.kv.unpack")
MS = 1_000_000  # ns
MB = 1_000_000


def reader(name):
    return bench_harness.load_module(METRICS / f"{name}.py", f"bench_metric_{name}")


def span(name, t0_ms, t1_ms, **attrs):
    return (name, int(t0_ms * MS), int(t1_ms * MS), -1, 0, attrs)


FILLED = ({"bytes": 50 * MB, "filled_bytes": 20 * MB},
          {"bytes": 50 * MB, "filled_bytes": 40 * MB})


def two_calls(pack=FILLED):
    """Two calls of 50 MB each, by default one with a prompt filling 40% of
    the cache and one 80% (`pack`: the attrs of the two `tent.kv.pack`
    spans); the handoff's five spans take 40 ms and 60 ms, with the
    engine's transfer and a decode span around them that do not count."""
    return [
        span("tent.generate", 0, 100, call=0), span("tent.prefill", 0, 5),
        span("tent.kv.pack", 5, 25, **pack[0]), span("tent.kv.segments", 25, 30),
        span("tent.kv.spray", 30, 40), span("tent.engine.transfer", 31, 39, slices_issued=4),
        span("tent.kv.read", 40, 43), span("tent.kv.unpack", 43, 45),
        span("tent.decode", 45, 100),
        span("tent.generate", 100, 200, call=1), span("tent.prefill", 100, 102),
        span("tent.kv.pack", 102, 132, **pack[1]), span("tent.kv.segments", 132, 140),
        span("tent.kv.spray", 140, 155), span("tent.kv.read", 155, 160),
        span("tent.kv.unpack", 160, 162), span("tent.decode", 162, 200),
    ]


def context(spans):
    return bench_harness.LayerContext([], None, (0.0, 1.0), None, None, {}, 2, spans)


@pytest.mark.parametrize("name,value", [
    ("handoff_us_per_mb", (40 + 60) * 1e3 / 100),
    ("handoff_fill", 60.0),
])
def test_reader_takes_its_metric_from_the_pack_attrs(name, value):
    assert reader(name).read(context(two_calls())) == pytest.approx(value)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("spans", [None, [], two_calls(({}, {}))],
                         ids=["not_attached", "empty", "pack_without_bytes"])
def test_reader_finds_nothing_without_the_attrs(name, spans):
    """None, never 0, where the spans are missing or carry no bytes: the
    parent program's case, whose pack span has no attrs."""
    assert reader(name).read(context(spans)) is None


def test_readers_on_spans_recorded_from_the_program():
    """Two calls of the smoke-size server, prompts of 6 and 12 tokens into
    a 24-position cache: the fill is 18 / 48 of the shipped bytes, and the
    cost per MB is the handoff spans' time over those bytes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.core import FabricSpec, TentEngine
    from repro.models import init_params
    from repro.obs import HostSpans
    from repro.serving import DisaggregatedServer

    cfg = get_smoke_config("deepseek-7b")
    server = DisaggregatedServer(TentEngine(FabricSpec()), cfg,
                                 init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    rec = HostSpans()
    server.attach_spans(rec)
    shipped = 0
    for S in (6, 12):
        prompt = jax.random.randint(jax.random.PRNGKey(S), (2, S), 0, cfg.vocab_size)
        shipped += server.generate(prompt, n_new=2, max_len=24).kv_bytes
    spans = rec.finished()
    ctx = context(spans)
    assert reader("handoff_fill").read(ctx) == pytest.approx(100 * 18 / 48)
    secs = sum(s[2] - s[1] for s in spans if s[0] in HANDOFF) * 1e-9
    assert reader("handoff_us_per_mb").read(ctx) == pytest.approx(secs * 1e6 / (shipped / MB))
    assert secs > 0
