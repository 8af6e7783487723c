"""tentlint tests: per-rule fixture pins, suppression/baseline round-trips,
fingerprint stability, CLI exit codes, the @hot_path marker, and the
REPRO_SANITIZE runtime sanitizer."""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import hot_path, is_hot_path
from repro.analysis.baseline import Baseline, apply_baseline
from repro.analysis.core import Project, run_rules
from repro.analysis.lint import DEFAULT_PATHS, main, run_lint
from repro.analysis.rules import ALL_RULES, RULES_BY_ID, default_rules
from repro.analysis.sanitize import (
    SanitizerError,
    enabled,
    maybe_sanitized,
    sanitized,
)

FIXTURES = Path(__file__).resolve().parent / "fixtures" / "lint"
REPO_ROOT = Path(__file__).resolve().parents[1]


def lint_fixture(name, rules=None):
    """Lint one fixture file as if it were engine source."""
    project = Project(FIXTURES, [FIXTURES / name], src_prefixes=("",),
                      test_markers=("tests/",))
    return run_rules(project, default_rules(rules))


# ---------------------------------------------------------------------------
# per-rule fixture pins: each violation class fails with the right rule id
# ---------------------------------------------------------------------------

class TestRuleFixtures:
    def test_no_wall_clock_flags_bad_fixture(self):
        found = lint_fixture("bad_wall_clock.py", rules=["no-wall-clock"])
        assert len(found) == 4
        assert {f.rule for f in found} == {"no-wall-clock"}
        assert all(f.active for f in found)

    def test_no_wall_clock_passes_clean_fixture(self):
        assert lint_fixture("clean_wall_clock.py",
                            rules=["no-wall-clock"]) == []

    @pytest.mark.parametrize("rel, flagged", [
        ("src/repro/obs/spans.py", 0),  # the host spans' one clock
        ("src/repro/obs/recorder.py", 1),
        ("src/repro/serving/disagg.py", 1),
        ("src/repro/core/engine.py", 1),
    ])
    def test_no_wall_clock_allows_host_spans_only(self, tmp_path, rel, flagged):
        f = tmp_path / rel
        f.parent.mkdir(parents=True)
        f.write_text("import time\n\n\ndef g():\n    return time.perf_counter_ns()\n")
        found = run_rules(Project(tmp_path, [f]), default_rules(["no-wall-clock"]))
        assert len(found) == flagged

    def test_no_global_rng_flags_bad_fixture(self):
        found = lint_fixture("bad_global_rng.py", rules=["no-global-rng"])
        assert {f.rule for f in found} == {"no-global-rng"}
        assert len(found) == 6  # rand, seed, random + id/hash/time seeds
        assert sum("nondeterministic seed" in f.message for f in found) == 3

    def test_no_global_rng_passes_clean_fixture(self):
        assert lint_fixture("clean_global_rng.py",
                            rules=["no-global-rng"]) == []

    def test_fma_hazard_flags_bad_fixture(self):
        found = lint_fixture("bad_fma.py", rules=["fma-hazard"])
        assert {f.rule for f in found} == {"fma-hazard"}
        assert len(found) == 3  # two scan-body products + one jitted blend
        assert not any(f.line > 20 for f in found)  # int product unflagged

    def test_fma_hazard_passes_clean_fixture(self):
        assert lint_fixture("clean_fma.py", rules=["fma-hazard"]) == []

    def test_unordered_iter_flags_bad_fixture(self):
        found = lint_fixture("bad_unordered.py", rules=["unordered-iter"])
        assert {f.rule for f in found} == {"unordered-iter"}
        assert len(found) == 4

    def test_unordered_iter_passes_clean_fixture(self):
        assert lint_fixture("clean_unordered.py",
                            rules=["unordered-iter"]) == []

    def test_hot_path_alloc_flags_bad_fixture(self):
        found = lint_fixture("bad_hotpath.py", rules=["hot-path-alloc"])
        assert {f.rule for f in found} == {"hot-path-alloc"}
        assert len(found) == 4  # lambda, partial, comprehension, nested def

    def test_hot_path_alloc_passes_clean_fixture(self):
        assert lint_fixture("clean_hotpath.py",
                            rules=["hot-path-alloc"]) == []

    def test_twin_drift_mini_project(self):
        root = FIXTURES / "twinproj"
        project = Project(
            root, [root / "kernels.py", root / "tests" / "test_parity.py"],
            src_prefixes=("",), test_markers=("tests/",))
        found = run_rules(project, default_rules(["twin-drift"]))
        assert {f.rule for f in found} == {"twin-drift"}
        by_msg = {f.message.split("`")[1]: f.message for f in found}
        assert set(by_msg) == {"drifted_jnp", "orphan_jnp", "untested_jnp"}
        assert "drifted" in by_msg["drifted_jnp"]  # signature drift
        assert "no numpy twin" in by_msg["orphan_jnp"]
        assert "no parity test" in by_msg["untested_jnp"]


# ---------------------------------------------------------------------------
# suppression pragmas
# ---------------------------------------------------------------------------

class TestSuppression:
    def test_line_and_file_pragmas(self):
        found = lint_fixture("suppressed.py",
                             rules=["no-wall-clock", "no-global-rng"])
        active = [f for f in found if f.active]
        suppressed = [f for f in found if f.suppressed]
        assert len(active) == 1
        assert "perf_counter" in active[0].message
        assert len(suppressed) == 2  # line pragma + disable-file pragma

    def test_pragma_in_string_literal_is_ignored(self, tmp_path):
        f = tmp_path / "strings.py"
        f.write_text(
            's = "# tentlint: disable-file=no-wall-clock"\n'
            "import time\n\n\n"
            "def g():\n    return time.time()\n")
        project = Project(tmp_path, [f], src_prefixes=("",))
        found = run_rules(project, default_rules(["no-wall-clock"]))
        assert len(found) == 1 and found[0].active


# ---------------------------------------------------------------------------
# baseline round-trip + fingerprint stability
# ---------------------------------------------------------------------------

class TestBaseline:
    def _findings(self):
        return lint_fixture("bad_wall_clock.py", rules=["no-wall-clock"])

    def test_round_trip_accepts_then_detects_staleness(self, tmp_path):
        found = self._findings()
        bl = Baseline.from_findings(found)
        path = tmp_path / "baseline.json"
        bl.save(path)
        reloaded = Baseline.load(path)
        assert reloaded.by_fp.keys() == bl.by_fp.keys()

        marked, stale = apply_baseline(found, reloaded)
        assert all(f.baselined for f in marked)
        assert not any(f.active for f in marked)
        assert stale == []

        # against a clean file every entry is stale (debt paid down)
        clean = lint_fixture("clean_wall_clock.py", rules=["no-wall-clock"])
        _, stale = apply_baseline(clean, reloaded)
        assert len(stale) == len(bl.entries)

    def test_reasons_carry_forward(self, tmp_path):
        found = self._findings()
        old = Baseline.from_findings(found)
        for e in old.entries:
            e["reason"] = "justified: " + e["rule"]
        old = Baseline(old.entries)
        new = Baseline.from_findings(found, old)
        assert all(e["reason"].startswith("justified:") for e in new.entries)

    def test_fingerprints_survive_line_drift(self, tmp_path):
        found = self._findings()
        shifted = tmp_path / "bad_wall_clock.py"  # same basename on purpose
        original = (FIXTURES / "bad_wall_clock.py").read_text()
        shifted.write_text("# pushed\n# down\n# by\n# comments\n" + original)
        project = Project(tmp_path, [shifted], src_prefixes=("",))
        drifted = run_rules(project, default_rules(["no-wall-clock"]))
        assert {f.fingerprint for f in drifted} == \
            {f.fingerprint for f in found}
        assert {f.line for f in drifted} != {f.line for f in found}

    def test_bad_version_rejected(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"version": 99, "findings": []}))
        with pytest.raises(ValueError):
            Baseline.load(p)


# ---------------------------------------------------------------------------
# CLI + whole-tree gate
# ---------------------------------------------------------------------------

class TestCli:
    def test_list_rules_exits_zero(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ALL_RULES:
            assert rule.id in out

    def test_unknown_rule_is_usage_error(self):
        assert main(["--rules", "no-such-rule",
                     "--root", str(FIXTURES)]) == 2

    def test_violation_file_fails(self, capsys):
        # no-global-rng applies to every file, so it fires through the CLI
        # even though the fixture sits outside the src/repro prefix
        rc = main([str(FIXTURES / "bad_global_rng.py"),
                   "--root", str(FIXTURES), "--rules", "no-global-rng"])
        assert rc == 1
        assert "[no-global-rng]" in capsys.readouterr().out

    def test_json_report_written(self, tmp_path, capsys):
        out = tmp_path / "report.json"
        rc = main([str(FIXTURES / "bad_global_rng.py"),
                   "--root", str(FIXTURES), "--rules", "no-global-rng",
                   "--json", str(out)])
        assert rc == 1
        report = json.loads(out.read_text())
        assert report["counts"]["active"] == 6
        assert all(f["rule"] == "no-global-rng"
                   for f in report["findings"])

    def test_write_baseline_then_strict_passes(self, tmp_path, capsys):
        bl = tmp_path / "baseline.json"
        bad = str(FIXTURES / "bad_global_rng.py")
        common = [bad, "--root", str(FIXTURES), "--rules", "no-global-rng",
                  "--baseline", str(bl)]
        assert main(common) == 1
        assert main(common + ["--write-baseline"]) == 0
        assert main(common + ["--strict"]) == 0  # all baselined, none stale

    def test_full_tree_is_clean(self, capsys):
        """The acceptance gate, in-process: the committed tree must lint
        clean under every rule with the committed baseline."""
        paths = [REPO_ROOT / p for p in DEFAULT_PATHS
                 if (REPO_ROOT / p).exists()]
        findings, stale, project = run_lint(
            REPO_ROOT, paths,
            baseline_path=REPO_ROOT / "tentlint_baseline.json")
        assert project.errors == []
        assert stale == []
        active = [f for f in findings if f.active]
        assert active == [], "\n".join(
            f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in active)


# ---------------------------------------------------------------------------
# @hot_path marker
# ---------------------------------------------------------------------------

class TestHotPathMarker:
    def test_identity_preserved_and_tagged(self):
        def f(x):
            return x

        tagged = hot_path(f)
        assert tagged is f  # zero-cost: no wrapper frame
        assert is_hot_path(tagged)
        assert not is_hot_path(lambda: None)

    def test_known_hot_paths_are_tagged(self):
        from repro.core.engine import TentEngine
        from repro.core.telemetry import TelemetryStore
        from repro.serving.serve_sim import ServingSimulator

        assert is_hot_path(TentEngine._dispatch)
        assert is_hot_path(TentEngine._on_wire_done_many)
        assert is_hot_path(TelemetryStore.on_complete_many)
        assert is_hot_path(ServingSimulator._run_batched)


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------

def _as_repro_module(stmt: str):
    """Build a zero-arg function whose frame claims to live in a repro.*
    module, so the sanitizer's caller check treats it as engine code."""
    import random
    import time

    ns = {"__name__": "repro.fake.simpath", "time": time, "np": np,
          "random": random}
    exec(f"def f():\n    return {stmt}", ns)
    return ns["f"]


class TestSanitizer:
    def test_enabled_reads_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
        assert not enabled()
        monkeypatch.setenv("REPRO_SANITIZE", "1")
        assert enabled()
        monkeypatch.setenv("REPRO_SANITIZE", "0")
        assert not enabled()

    @pytest.mark.parametrize("stmt", [
        "time.time()", "time.perf_counter()", "np.random.rand(2)",
        "np.random.seed(0)", "random.random()",
    ])
    def test_repro_caller_raises(self, stmt):
        fn = _as_repro_module(stmt)
        with sanitized():
            with pytest.raises(SanitizerError):
                fn()
        fn_name = stmt.split("(")[0]
        assert fn_name  # and the patch is gone afterwards:
        fn()  # outside the context the same call succeeds

    def test_non_repro_caller_passes_through(self):
        import time
        with sanitized():
            assert isinstance(time.time(), float)  # this module isn't repro.*
            assert np.random.default_rng(0).random() >= 0  # always fine

    @pytest.mark.parametrize("module", [
        "repro.training.train_loop", "repro.launch.dryrun", "repro.obs.spans"])
    def test_allowlisted_repro_module_passes(self, module):
        import time
        ns = {"__name__": module, "time": time}
        exec("def f():\n    return time.time()", ns)
        with sanitized():
            assert isinstance(ns["f"](), float)

    def test_reentrant_and_restores(self):
        import time
        orig = time.time
        with sanitized():
            with sanitized():  # inner block must not double-patch
                assert getattr(time.time, "__tentlint_stub__", False)
            assert getattr(time.time, "__tentlint_stub__", False)
        assert time.time is orig

    def test_maybe_sanitized_off_is_noop(self, monkeypatch):
        import time
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
        with maybe_sanitized():
            assert not getattr(time.time, "__tentlint_stub__", False)

    def test_scenario_runs_under_sanitizer(self, monkeypatch):
        """One scenario-library smoke with dynamic enforcement on: the
        whole simulated path must complete without touching the wall clock
        or global RNG, and produce the same report as an unsanitized run."""
        from repro.scenarios import ScenarioRunner, get

        spec = get("single_rail_flap")
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
        plain = ScenarioRunner(spec).run()
        monkeypatch.setenv("REPRO_SANITIZE", "1")
        guarded = ScenarioRunner(spec).run()
        assert guarded.violations == plain.violations
        for pol, rep in plain.policies.items():
            assert guarded.policies[pol] == rep
