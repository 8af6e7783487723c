"""Host spans (`repro.obs.spans`): the recorder's nesting, call ids and
Perfetto export; the span sites of `DisaggregatedServer.generate` and the
engine at smoke size; and the off contract (no clock read, same tokens and
engine accounting with spans attached and detached)."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sanitize import maybe_sanitized
from repro.configs import get_smoke_config
from repro.core import EngineConfig, FabricSpec, TentEngine
from repro.models import init_params
from repro.obs import HostSpans, validate_trace
from repro.obs import spans as spans_mod
from repro.serving import DisaggregatedServer, monolithic_generate

N_NEW = 4
MAX_LEN = 32
CALL_CHILDREN = ["tent.prefill", "tent.kv.pack", "tent.kv.segments", "tent.kv.spray",
                 "tent.kv.read", "tent.kv.unpack", "tent.decode"]


class TestHostSpans:
    def test_nesting_parents_and_calls(self):
        rec = HostSpans()
        rec.open("outside")
        rec.close()
        for _ in range(2):
            with rec.span("tent.generate", new_call=True, batch=2):
                with rec.span("a"):
                    rec.open("a.inner", n=1)
                    rec.close()
                with rec.span("b") as attrs:
                    attrs["late"] = 7
        spans = rec.finished()
        assert [s[0] for s in spans] == ["outside"] + ["tent.generate", "a", "a.inner", "b"] * 2
        names = [s[0] for s in spans]
        for i, (name, t0, t1, parent, call, attrs) in enumerate(spans):
            assert t1 >= t0
            if parent >= 0:
                p = spans[parent]
                assert p[1] <= t0 and t1 <= p[2]
            assert call == (-1 if i == 0 else (i - 1) // 4)
        assert [names[s[3]] if s[3] >= 0 else None for s in spans[1:5]] == [
            None, "tent.generate", "a", "tent.generate"]
        assert spans[1][5] == {"batch": 2, "call": 0}
        assert spans[5][5] == {"batch": 2, "call": 1}
        assert spans[3][5] == {"n": 1} and spans[4][5] == {"late": 7}
        assert rec.calls == 2

    def test_block_closes_spans_left_open_by_an_exception(self):
        rec = HostSpans()
        with pytest.raises(RuntimeError):
            with rec.span("tent.generate", new_call=True):
                rec.open("tent.kv.spray")
                rec.open("tent.engine.transfer")
                raise RuntimeError("stalled")
        with rec.span("tent.generate", new_call=True):
            pass
        spans = rec.finished()
        assert len(spans) == len(rec.spans) == 4
        assert [(s[0], s[3], s[4]) for s in spans] == [
            ("tent.generate", -1, 0), ("tent.kv.spray", 0, 0),
            ("tent.engine.transfer", 1, 0), ("tent.generate", -1, 1)]

    def test_write_trace_is_valid_trace_event_format(self, tmp_path):
        rec = HostSpans()
        with rec.span("tent.generate", new_call=True, prompt_len=16):
            with rec.span("tent.prefill"):
                pass
        path = tmp_path / "spans.json"
        rec.write_trace(path)
        doc = json.loads(path.read_text())
        assert validate_trace(doc) == []
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [e["name"] for e in xs] == ["tent.generate", "tent.prefill"]
        assert xs[0]["args"] == {"call": 0, "parent": -1, "prompt_len": 16}
        assert xs[1]["args"] == {"call": 0, "parent": 0}
        assert xs[0]["ts"] <= xs[1]["ts"]
        assert xs[1]["ts"] + xs[1]["dur"] <= xs[0]["ts"] + xs[0]["dur"] + 1e-3
        assert {e["pid"] for e in doc["traceEvents"]} == {1}

    def test_write_trace_refuses_a_malformed_document(self, tmp_path):
        rec = HostSpans()
        with rec.span("tent.generate", shape=(2, 3)):  # not a JSON scalar
            pass
        with pytest.raises(ValueError, match="malformed"):
            rec.write_trace(tmp_path / "bad.json")


# ---------------------------------------------------------------------------
# The serving path at smoke size
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("qwen2-0.5b").with_(remat="none")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    return cfg, params, prompt


def _server(model):
    cfg, params, _ = model
    # small slices, so the handoff sprays many of them in waves and drains
    eng = TentEngine(FabricSpec(), config=EngineConfig(slice_bytes=1024))
    return DisaggregatedServer(eng, cfg, params)


def _children(spans, parent):
    return [s for s in spans if s[3] == parent]


@pytest.mark.parametrize("async_handoff", [False, True])
def test_generate_span_tree(model, async_handoff):
    """Per call: `tent.generate` with its seven children in order, the
    engine's transfer inside the spray, waves and drains inside it whose
    attrs add up to the transfer's counter growth, and two spans per
    decode step."""
    cfg, params, prompt = model
    server = _server(model)
    rec = HostSpans()
    server.attach_spans(rec)
    eng = server.engine
    issued = []
    for _ in range(2):
        before = eng.slices_issued
        res = server.generate(prompt, n_new=N_NEW, max_len=MAX_LEN,
                              async_handoff=async_handoff)
        issued.append(eng.slices_issued - before)
    np.testing.assert_array_equal(
        res.tokens, monolithic_generate(cfg, params, prompt, N_NEW, MAX_LEN))
    spans = rec.finished()
    assert len(spans) == len(rec.spans)
    roots = _children(spans, -1)
    assert [r[0] for r in roots] == ["tent.generate"] * 2
    for call, root in enumerate(roots):
        assert root[5] == {"call": call, "batch": 2, "prompt_len": 12, "n_new": N_NEW}
        assert all(s[4] == call for s in spans if root[1] <= s[1] and s[2] <= root[2])
        kids = _children(spans, spans.index(root))
        assert [k[0] for k in kids] == CALL_CHILDREN
        (transfer,) = _children(spans, spans.index(kids[3]))
        assert transfer[0] == "tent.engine.transfer"
        t = transfer[5]
        inner = _children(spans, spans.index(transfer))
        waves = [s for s in inner if s[0] == "tent.engine.wave"]
        drains = [s for s in inner if s[0] == "tent.engine.drain"]
        assert len(waves) + len(drains) == len(inner) and drains
        assert len(drains) == t["completion_batches"]
        assert sum(d[5]["slices"] for d in drains) == t["completions_drained"]
        assert sum(d[5]["bytes"] for d in drains) >= t["bytes"] == res.kv_bytes
        # the batch's first wave is posted with the segments in async mode
        first = _children(spans, spans.index(kids[2]))
        assert [s[0] for s in first] == ["tent.engine.wave"] * async_handoff
        assert t["slices_issued"] + sum(s[5]["slices"] for s in first) == issued[call]
        assert t["slices_issued"] == sum(s[5]["slices"] for s in waves)
        assert bool(waves) != async_handoff
        decode = _children(spans, spans.index(kids[6]))
        assert [s[0] for s in decode] == ["tent.decode.step", "tent.decode.fetch"] * (N_NEW - 1)


def test_spans_change_no_result(model):
    """Tokens, handoff bytes and the engine's accounting are the same with
    spans attached and detached."""
    _, _, prompt = model
    runs = {}
    for attached in (False, True):
        server = _server(model)
        if attached:
            server.attach_spans(HostSpans())
        res = server.generate(prompt, n_new=N_NEW, max_len=MAX_LEN)
        eng = server.engine
        runs[attached] = (res.tokens, res.kv_bytes, eng.audit(), eng.slices_issued,
                          eng.waves, eng.completions_drained, eng.completion_batches,
                          eng.fabric.now,
                          eng.segments.get(res.kv_segment_id).read(0, res.kv_bytes))
    off, on = runs[False], runs[True]
    np.testing.assert_array_equal(off[0], on[0])
    np.testing.assert_array_equal(off[-1], on[-1])
    assert off[1:-1] == on[1:-1]


def test_detached_reads_no_clock(model, monkeypatch):
    """Detached, under REPRO_SANITIZE=1, no `repro` module reads a host
    clock (the sanitizer raises) and the span module reads none; attached,
    it reads two per span."""
    _, _, prompt = model
    server = _server(model)
    server.generate(prompt, n_new=N_NEW, max_len=MAX_LEN)  # compiled outside
    reads = []
    monkeypatch.setattr(spans_mod, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: reads.append(1) or len(reads)))
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    with maybe_sanitized():
        server.generate(prompt, n_new=N_NEW, max_len=MAX_LEN)
        assert reads == []
        server.attach_spans(HostSpans())
        server.generate(prompt, n_new=N_NEW, max_len=MAX_LEN)
    assert len(reads) == 2 * len(server._spans.spans)
