"""Observability layer: flight recorder, provenance, metrics, trace export.

The contracts pinned here, in order of importance:

- **Passivity / parity** — attaching a `FlightRecorder` to a scenario run
  never changes the resulting report (the full `PolicyReport.to_dict()`,
  no keys excluded).
- **Determinism** — same spec + seed produces a byte-identical exported
  Chrome trace, run-to-run in one process (dense-id interning hides the
  process-global slice/batch counters).
- **Provenance** — every recorded wave's per-candidate score breakdown
  replays to exactly the choices the engine made (`replay_wave` re-runs
  Algorithm 1 from the recorded inputs and raises on divergence).
- **Healing cross-check** — the flight-recorder timeline re-derives the
  sub-50 ms healing number and it *equals* the report's stall matrix
  (same float ops, exact equality).
- **Uniform counter surface** — all workload kinds route the engine
  counters through one `MetricsRegistry`, so every report's `extra`
  carries the same keys.
- **Docs drift guards** — the scenario README's table stays in sync with
  `SCENARIOS`.
"""
import contextlib
import io
import json
import pathlib
import re

import numpy as np
import pytest

from repro.core import EngineConfig, FabricSpec, TentEngine
from repro.obs import (
    Counter,
    FlightRecorder,
    Histogram,
    MetricsRegistry,
    export_chrome_trace,
    to_json,
    validate_trace,
)
from repro.obs import events as EV
from repro.obs import explain
from repro.obs.explain import (
    healing_timeline,
    print_slice_chain,
    replay_wave,
    slice_chain,
)
from repro.scenarios import SCENARIOS, ScenarioRunner, get
from repro.scenarios.spec import ClusterWorkload
from scenario_sizes import sized

REPO = pathlib.Path(__file__).resolve().parent.parent
ENGINE_COUNTER_KEYS = (
    "slices_issued", "waves", "completions_drained", "completion_batches")


def _run_recorded(name, policy=None, capacity=1 << 18):
    spec = sized(get(name))
    rec = FlightRecorder(capacity=capacity)
    rep = ScenarioRunner(spec).run_policy(
        policy or spec.policies[0], recorder=rec)
    return rec, rep


@pytest.fixture(scope="module")
def incast_flap():
    """multi_engine_incast_flap under tent+diffusion, recorded."""
    return _run_recorded("multi_engine_incast_flap", "tent+diffusion")


@pytest.fixture(scope="module")
def gossip_flap():
    """lossy_gossip_flap under tent+diffusion, recorded."""
    return _run_recorded("lossy_gossip_flap", "tent+diffusion")


@pytest.fixture(scope="module")
def serving_recorded():
    """serving_closed_loop_flap under tent, recorded (request spans)."""
    return _run_recorded("serving_closed_loop_flap", "tent")


class _FakeSlice:
    def __init__(self, slice_id, batch_id, src_offset, length):
        self.slice_id = slice_id
        self.batch_id = batch_id
        self.src_offset = src_offset
        self.length = length


class TestFlightRecorder:
    def test_append_and_order(self):
        rec = FlightRecorder(capacity=8)
        for i in range(5):
            rec.append(EV.POST, float(i), {"i": i})
        assert len(rec) == 5
        assert rec.dropped == 0
        evs = list(rec.events())
        assert [ts for ts, _, _ in evs] == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert [pl["i"] for _, _, pl in evs] == list(range(5))

    def test_ring_wraparound_drops_oldest(self):
        rec = FlightRecorder(capacity=4)
        for i in range(7):
            rec.append(EV.POST, float(i), {"i": i})
        assert len(rec) == 4
        assert rec.dropped == 3
        assert [pl["i"] for _, _, pl in rec.events()] == [3, 4, 5, 6]

    def test_counts_by_kind_name(self):
        rec = FlightRecorder()
        rec.append(EV.WAVE, 0.0, {})
        rec.append(EV.COMPLETE, 1.0, {})
        rec.append(EV.COMPLETE, 2.0, {})
        assert rec.counts() == {"wave": 1, "complete": 2}

    def test_invalid_capacity(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_lazy_interning_first_seen_order(self):
        rec = FlightRecorder()
        a = _FakeSlice(900, 70, 0, 64)
        b = _FakeSlice(905, 70, 64, 64)
        rec.append(EV.WAVE, 0.0, {"slices": [b, a]})
        rec.append(EV.POST, 1.0, {"slice": a})
        # nothing interned until a read happens
        assert rec.n_slices() == 0
        evs = list(rec.events())
        # first-seen order over the event stream: b then a
        assert evs[0][2]["slices"] == [0, 1]
        assert evs[1][2]["slice"] == 1
        assert rec.n_slices() == 2
        assert rec.n_batches() == 1
        assert rec.slice_info(0) == (0, 64, 64)  # b: batch 0, offset 64
        # a second read is idempotent
        assert list(rec.events())[0][2]["slices"] == [0, 1]

    def test_interning_resumes_after_read(self):
        rec = FlightRecorder()
        rec.append(EV.POST, 0.0, {"slice": _FakeSlice(10, 1, 0, 8)})
        list(rec.events())
        rec.append(EV.POST, 1.0, {"slice": _FakeSlice(11, 1, 8, 8)})
        evs = list(rec.events())
        assert [pl["slice"] for _, _, pl in evs] == [0, 1]


class TestMetricsRegistry:
    def test_counter_and_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("retries")
        c.inc()
        c.inc(2)
        box = {"v": 7}
        reg.gauge("waves", lambda: box["v"])
        assert reg.collect() == {"retries": 3.0, "waves": 7.0}
        box["v"] = 9  # gauges are lazy: re-collection sees the new value
        assert reg.collect()["waves"] == 9.0

    def test_counter_is_idempotent_per_name(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")

    def test_registration_order_preserved(self):
        reg = MetricsRegistry()
        reg.gauge("z", lambda: 1)
        reg.counter("a")
        reg.gauge_group(lambda: {"m": 1.0, "b": 2.0})
        assert list(reg.collect()) == ["z", "a", "m", "b"]

    def test_histogram_summary(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft")
        assert reg.collect() == {"ttft_count": 0.0}
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        out = reg.collect()
        assert out["ttft_count"] == 3.0
        assert out["ttft_mean"] == pytest.approx(2.0)
        assert out["ttft_p50"] == pytest.approx(2.0)
        assert h.count == 3

    def test_timestamped_uses_clock(self):
        reg = MetricsRegistry(clock=lambda: 4.25)
        reg.counter("n").inc()
        ts, out = reg.timestamped()
        assert ts == 4.25 and out == {"n": 1.0}

    def test_standalone_primitives(self):
        c = Counter("c")
        c.inc(5)
        assert c.value == 5.0
        h = Histogram("h")
        h.observe(1.0, ts=0.5)
        assert h.count == 1


class TestZeroCostDefaults:
    def test_engine_recorder_off_by_default(self):
        eng = TentEngine(FabricSpec(n_nodes=2), config=EngineConfig(), seed=1)
        assert eng._rec is None
        assert eng.fabric._rec is None
        assert eng.health._rec is None


class TestReportParity:
    """Tracing ON vs OFF must produce byte-identical reports."""

    def test_cluster_report_parity(self, incast_flap):
        _, rep_on = incast_flap
        rep_off = ScenarioRunner(get("multi_engine_incast_flap")).run_policy(
            "tent+diffusion")
        assert rep_on.to_dict() == rep_off.to_dict()

    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_single_engine_report_parity(self, name):
        """Every library scenario's primary policy, recorder on vs off."""
        rec, rep_on = _run_recorded(name)
        spec = sized(get(name))
        rep_off = ScenarioRunner(spec).run_policy(spec.policies[0])
        assert rep_on.to_dict() == rep_off.to_dict()
        assert len(rec) > 0


class TestTraceDeterminism:
    """Same spec + seed => byte-identical exported trace."""

    def test_cluster_trace_bytes(self, incast_flap):
        rec1, _ = incast_flap
        rec2, _ = _run_recorded("multi_engine_incast_flap", "tent+diffusion")
        assert to_json(export_chrome_trace(rec1)) == \
            to_json(export_chrome_trace(rec2))

    def test_single_engine_trace_bytes(self):
        rec1, _ = _run_recorded("uniform_spray")
        rec2, _ = _run_recorded("uniform_spray")
        assert to_json(export_chrome_trace(rec1)) == \
            to_json(export_chrome_trace(rec2))


class TestTraceSchema:
    def test_validates_and_round_trips(self, incast_flap):
        rec, _ = incast_flap
        doc = export_chrome_trace(rec)
        assert validate_trace(doc) == []
        blob = to_json(doc)
        parsed = json.loads(blob)
        assert parsed["displayTimeUnit"] == "ms"
        assert parsed["otherData"]["dropped"] == 0
        evs = parsed["traceEvents"]
        assert len(evs) > 0
        # metadata names every process, spans carry microsecond timestamps
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in evs)
        assert any(e["ph"] == "X" and e["dur"] >= 0 for e in evs)

    def test_serving_request_spans(self, serving_recorded):
        rec, _ = serving_recorded
        phases = [pl for _, k, pl in rec.events() if k == EV.PHASE]
        assert phases, "serving run recorded no request phases"
        kinds = {pl["phase"] for pl in phases}
        assert {"fetch", "prefill", "decode", "request"} <= kinds
        for pl in phases:
            if pl["phase"] == "request":
                assert pl["ttft"] >= 0.0
        doc = export_chrome_trace(rec)
        assert validate_trace(doc) == []
        assert any(e.get("tid") == 5 and e["ph"] == "X"
                   for e in doc["traceEvents"])


class TestDecisionProvenance:
    @pytest.mark.parametrize("fixture", ["incast_flap", "gossip_flap"])
    def test_every_wave_replays_to_recorded_choices(self, fixture, request):
        rec, _ = request.getfixturevalue(fixture)
        waves = [pl for _, k, pl in rec.events() if k == EV.WAVE]
        assert waves, "no waves recorded"
        for pl in waves:
            rows = replay_wave(pl)  # raises ProvenanceError on divergence
            assert len(rows) == len(pl["slices"])
            n_rails = len(pl["inputs"]["queued"])
            for row in rows:
                if not row["infeasible"]:
                    assert len(row["scores"]) == n_rails
                    assert row["chosen"] in row["window"] or row["fallback"]

    def test_replay_detects_tampering(self, incast_flap):
        rec, _ = incast_flap
        pl = next(pl for _, k, pl in rec.events() if k == EV.WAVE
                  if len(pl["slices"]) > 0)
        bad = dict(pl)
        choices = np.array(pl["choices"], copy=True)
        n_rails = len(pl["inputs"]["queued"])
        choices[0] = (int(choices[0]) + 1) % n_rails
        bad["choices"] = choices
        with pytest.raises(explain.ProvenanceError):
            replay_wave(bad)


class TestHealingCrossCheck:
    """Satellite: the sub-50 ms healing claim, re-derived from the flight
    recorder and cross-checked against the stall matrix — exact equality,
    because `healing_timeline` mirrors `ScenarioRunner._stall_ms` float op
    for float op."""

    @pytest.mark.parametrize("fixture", ["incast_flap", "gossip_flap"])
    def test_trace_heal_equals_stall_matrix(self, fixture, request):
        rec, rep = request.getfixturevalue(fixture)
        events = list(rec.events())
        h = healing_timeline(events, exclude_engines=("cache",))
        assert h["heal_ms"] == rep.stall_ms  # exact: same float ops
        assert h["heal_ms"] < 50.0
        assert h["onsets"], "no fault onset in a flap scenario?"
        assert h["first_failure"] is not None
        assert h["last_reroute"] is not None
        assert h["first_failure"] >= h["onsets"][0]

    def test_empty_timeline(self):
        h = healing_timeline([])
        assert h["heal_ms"] == -1.0 and h["onsets"] == []


class TestSliceChains:
    def test_wave_slice_chain_has_causal_steps(self, incast_flap):
        rec, _ = incast_flap
        events = list(rec.events())
        sid = next(pl["slices"][0] for _, k, pl in events if k == EV.WAVE)
        steps = [s for _, s, _ in slice_chain(rec, events, sid)]
        assert "intent" in steps
        assert "wave" in steps
        assert "complete" in steps
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            print_slice_chain(rec, events, sid)
        out = buf.getvalue()
        assert f"slice {sid}" in out
        assert "wave pick" in out and "score" in out

    def test_failed_slice_chain_shows_reroute(self, incast_flap):
        rec, _ = incast_flap
        events = list(rec.events())
        fails = [pl["slice"] for _, k, pl in events if k == EV.FAIL]
        assert fails, "flap scenario recorded no failures"
        steps = [s for _, s, _ in slice_chain(rec, events, fails[0])]
        assert "fail" in steps
        assert "reroute" in steps or "substitute" in steps


class TestExplainCLI:
    def test_main_runs_and_prints_chain(self, tmp_path, capsys):
        trace = tmp_path / "t.json"
        rv = explain.main([
            "--scenario", "uniform_spray", "--slice", "0",
            "--trace-out", str(trace)])
        assert rv == 0
        out = capsys.readouterr().out
        assert "uniform_spray" in out
        assert "slice 0" in out
        assert trace.exists()
        assert validate_trace(json.loads(trace.read_text())) == []


class TestUniformCounterSurface:
    """Satellite: every workload kind reports the engine counters through
    the one MetricsRegistry path."""

    def test_cluster_extra_keys(self, incast_flap):
        _, rep = incast_flap
        for key in ENGINE_COUNTER_KEYS:
            assert key in rep.extra, key
        # the cluster group adds the control-plane keys around them
        for key in ("engines", "diffusion_rounds", "rumors_sent"):
            assert key in rep.extra, key

    @pytest.mark.parametrize("name,policy", [
        ("uniform_spray", "tent"),          # closed loop
        ("hicache_serve", "tent"),          # serve table
    ])
    def test_single_engine_extra_keys(self, name, policy):
        rep = ScenarioRunner(get(name)).run_policy(policy)
        for key in ENGINE_COUNTER_KEYS:
            assert key in rep.extra, (name, key)
        assert rep.extra["slices_issued"] > 0

    def test_serving_extra_keys(self, serving_recorded):
        _, rep = serving_recorded
        for key in ENGINE_COUNTER_KEYS:
            assert key in rep.extra, key


class TestDocsDriftGuards:
    """Satellite: the scenario README's numbers track the library."""

    def test_scenario_table_matches_registry(self):
        text = (REPO / "src/repro/scenarios/README.md").read_text()
        section = text.split("## Named library", 1)[1].split("\n## ", 1)[0]
        rows = re.findall(r"^\| `([a-z0-9_]+)`\s*\|", section, re.M)
        assert len(rows) == len(SCENARIOS), (
            f"scenario README table has {len(rows)} rows, library has "
            f"{len(SCENARIOS)} — update src/repro/scenarios/README.md")
        assert set(rows) == set(SCENARIOS)

    def test_cluster_entry_count_prose(self):
        text = (REPO / "src/repro/scenarios/README.md").read_text()
        m = re.search(r"The (\w+) cluster entries", text)
        assert m, "cluster-entry prose missing from scenario README"
        words = {"two": 2, "three": 3, "four": 4, "five": 5, "six": 6,
                 "seven": 7, "eight": 8, "nine": 9, "ten": 10,
                 "eleven": 11, "twelve": 12}
        actual = sum(1 for s in SCENARIOS.values()
                     if isinstance(s.workload, ClusterWorkload))
        assert words.get(m.group(1)) == actual, (
            f"README says '{m.group(1)}' cluster entries, library has "
            f"{actual}")
