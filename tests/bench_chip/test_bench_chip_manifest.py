"""`BENCHMARK.json` against the contract it is read by: keys, names and
units, bounds, the cells and the files each one names."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
# widths may never be cut: sizes, head and projection dims, ranks, experts
# per token, expansion factors (the vocabulary is a share, not a width)
WIDTH = re.compile(r"(?<!vocab)(_size$|_dim$|_rank$|^num_experts_per_tok$|expand)")


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and not re.search(r"[\n\t]", text)


def reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_command_and_paths():
    cmd, paths = BENCH["command"], BENCH["paths"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in paths) and (ROOT / w).is_file()


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert PATH.match(str(f.relative_to(ROOT))), f


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_directions():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + list(CELLS) + [c["name"] for c in BENCH["configs"]]
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert len(CELLS) == len(BENCH["workloads"])
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%", m


def test_end_to_end_metrics_and_bounds():
    assert 1 <= len(E2E) <= 16 and "setup_s" in E2E
    for m in E2E.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert E2E["setup_s"]["bound"] <= 0.25


def test_per_layer_metrics_move_an_end_to_end_metric_each_of_their_cells_reports():
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in E2E
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS and reports(E2E[m["moves"]], cell), (m["name"], cell)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        reader = ROOT / "benchmarks/chip/metrics" / f"{m['name']}.py"
        assert reader.is_file() and "def read(ctx)" in reader.read_text(), reader
    assert all(len(v) == 1 for v in layers.values())  # one spelling per layer


def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    for cell in CELLS:
        e2e = {n for n, m in E2E.items() if reports(m, cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(reports(m, cell) for m in BENCH["per_layer"])


def test_cells_name_their_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    pairs = set()
    n4 = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        n4 += w["chips"] == 4
        pairs.add((w["config"], w["traffic"]))
        assert (ROOT / "benchmarks/chip/traffic" / f"{w['traffic']}.json").is_file()
        checks = json.loads((ROOT / "benchmarks/chip/checks" / f"{w['name']}.json").read_text())
        assert checks["served_gap_max"] > 0
    assert len(pairs) == len(CELLS) and n4 <= max(1, len(CELLS) // 2)
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert line(entry["source"]) and line(entry["why"])
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(entry["file"]) == 1
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert cfg["published"][key] != cfg[key]
    assert (ROOT / "benchmarks/chip/arch" / f"{cfg['arch']}.py").is_file()
