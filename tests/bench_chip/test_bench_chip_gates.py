"""A run that cannot measure says why, prints no result and exits non-zero:
no TPU, a seam missing from the program, a device with no peaks, a
checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_harness
import bench_smoke
from repro.serving import disagg

REPO = bench_smoke.REPO
CELL = "qwen2-0.5b.doc-qa"


def run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
                           "--seed", "3", "--seconds", "1", "--trace", "0", *args],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def no_result(res):
    return res.returncode != 0 and '"correct"' not in res.stdout


def test_refuses_the_cpu():
    res = run_py(REPO)
    assert no_result(res)
    assert "needs a TPU" in res.stderr


def test_fails_where_the_checkout_holds_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    assert no_result(run_py(tmp_path))
    # past the device gate too: the program itself is missing
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = ("import sys, time; from pathlib import Path; sys.path.insert(0, 'benchmarks/chip');"
            "import bench_harness as H\n"
            "try:\n H.run(Path('.').resolve(), %r, 3, 1, False, time.perf_counter(), platform='cpu')\n"
            "except H.BenchError as e:\n print(e, file=sys.stderr); sys.exit(2)" % CELL)
    (tmp_path / "benchmarks/chip/peaks.json").write_text(json.dumps(
        {"devices": {"cpu": bench_smoke.CPU_PEAKS}}))
    res = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 2 and "not importable" in res.stderr


def test_unknown_workload(tmp_path):
    with pytest.raises(bench_harness.BenchError, match="no workload"):
        bench_harness.load_cell(REPO, "no-such-cell")


def test_device_gate_refuses_another_platform_and_too_few_chips():
    with pytest.raises(bench_harness.BenchError, match="needs a TPU"):
        bench_harness.device_gate(1)
    with pytest.raises(bench_harness.BenchError, match="asks for 4 chips"):
        bench_harness.device_gate(4, platform="cpu")


def test_device_without_peaks_is_an_error():
    with pytest.raises(bench_harness.BenchError, match="no peaks"):
        bench_harness.peaks_for(REPO, "TPU v9 imaginary")
    assert bench_harness.peaks_for(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12


class FakeEngine:
    slices_issued = 0

    def transfer_sync(self, *a):
        pass

    def run_until_idle(self):
        pass


@pytest.mark.parametrize("seam", bench_harness.MODULE_SEAMS)
def test_missing_seam_is_named(seam, monkeypatch):
    monkeypatch.delattr(disagg, seam)
    with pytest.raises(bench_harness.BenchError, match=seam):
        bench_harness.Seams(disagg, FakeEngine())


def test_missing_engine_seam_is_named():
    class Engine:
        slices_issued = 0

        def run_until_idle(self):
            pass
    with pytest.raises(bench_harness.BenchError, match="engine.transfer_sync"):
        bench_harness.Seams(disagg, Engine())


def test_seams_restore_the_program():
    before = {n: getattr(disagg, n) for n in bench_harness.MODULE_SEAMS}
    eng = FakeEngine()
    seams = bench_harness.Seams(disagg, eng)
    assert all(getattr(disagg, n) is not before[n] for n in before)
    seams.restore()
    assert all(getattr(disagg, n) is before[n] for n in before)
    assert "transfer_sync" not in vars(eng)


def test_a_seam_the_window_never_passed_is_named():
    rec = bench_harness.CallRecord(0, 8, 4, None)
    rec.spans = {"prefill_jit": [(0, 1)], "tree_to_bytes": [(1, 2)],
                 "transfer_sync": [(2, 3)], "bytes_to_tree": [(3, 4)]}
    assert bench_harness.unused_seams([rec]) == ["decode_step_jit"]
