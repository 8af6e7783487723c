"""The reduction from a profiler trace to busy time, per-program device
time and idle gaps by what the host was doing."""
from pathlib import Path

import pytest

import trace_reduce as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures"


def small_trace():
    """Two programs on one device in a 10 s window; the host makes one call
    with a prefill, a handoff and two decode steps."""
    t = tr.Trace()
    t.ops["/device:TPU:0"] = [(1.0, 2.0), (1.5, 2.5), (3.0, 3.5), (6.0, 6.5), (8.0, 8.25),
                              (9.5, 11.0)]
    t.modules["/device:TPU:0"] = [("jit_prefill(11)", 1.0, 2.5), ("jit_decode_step(12)", 6.0, 6.5),
                                  ("jit_decode_step(12)", 8.0, 8.25),
                                  ("jit_prefill_forward(13)", 3.0, 3.5),
                                  ("jit_decode_step(12)", 9.5, 11.0)]
    t.host = sorted([("bench.window", 0.0, 10.0), ("bench.call", 0.5, 9.0),
                     ("bench.prefill_jit", 0.6, 2.6), ("bench.tree_to_bytes", 2.7, 3.6),
                     ("bench.transfer_sync", 3.7, 5.0), ("bench.bytes_to_tree", 5.1, 5.5),
                     ("bench.decode_step_jit", 5.9, 6.1), ("bench.decode_step_jit", 7.9, 8.0),
                     ("bench.client", 9.0, 9.2)], key=lambda h: (h[1], -h[2]))
    return t


def test_union_merges_overlaps_and_clips():
    assert tr.union([(1, 2), (1.5, 2.5), (3, 4), (0, 0.5)], 0.25, 3.5) == [
        (0.25, 0.5), (1, 2.5), (3, 3.5)]
    assert tr.union([], 0, 1) == []


def test_busy_and_idle_share():
    t = small_trace()
    # 1.0-2.5, 3.0-3.5, 6.0-6.5, 8.0-8.25, 9.5-10 (clipped) = 3.25 s busy
    assert tr.busy_seconds(t, 0.0, 10.0) == pytest.approx(3.25)


def test_busy_is_averaged_over_devices():
    t = small_trace()
    t.ops["/device:TPU:1"] = [(0.0, 10.0)]
    assert tr.busy_seconds(t, 0.0, 10.0) == pytest.approx((3.25 + 10.0) / 2)


def test_program_time_by_name():
    t = small_trace()
    assert tr.module_seconds(t, r"^jit_prefill(\(|$)", 0, 10) == (pytest.approx(1.5), 1)
    secs, n = tr.module_seconds(t, r"^jit_decode_step(\(|$)", 0, 10)
    assert n == 3 and secs == pytest.approx(2.25)  # by start: the last ends past 10
    assert tr.module_seconds(t, r"^jit_decode_step(\(|$)", 0, 9) == (pytest.approx(0.75), 2)
    assert [k for k, _ in tr.top_modules(t, 0, 10)] == [
        "jit_decode_step", "jit_prefill", "jit_prefill_forward"]


def test_idle_gaps_are_labelled_by_the_host_span_they_fall_in():
    t = small_trace()
    gaps = dict(map(tuple, tr.idle_by_host(t, 0.0, 10.0, n=50)))
    # each gap is cut at the host spans' edges: e.g. 3.5-6.0 falls over the
    # end of tree_to_bytes, the spray, the segment read, bytes_to_tree, the
    # host between it and the first decode step, and that step's dispatch
    assert gaps == {
        "outside": pytest.approx(0.8), "after:bench.call": pytest.approx(0.1),
        "bench.prefill_jit": pytest.approx(0.5), "after:bench.prefill_jit": pytest.approx(0.1),
        "bench.tree_to_bytes": pytest.approx(0.4), "after:bench.tree_to_bytes": pytest.approx(0.1),
        "bench.transfer_sync": pytest.approx(1.3), "after:bench.transfer_sync": pytest.approx(0.1),
        "bench.bytes_to_tree": pytest.approx(0.4), "after:bench.bytes_to_tree": pytest.approx(0.4),
        "bench.decode_step_jit": pytest.approx(0.2),
        "after:bench.decode_step_jit": pytest.approx(2.15), "bench.client": pytest.approx(0.2),
    }
    assert sum(gaps.values()) == pytest.approx(10.0 - 3.25)


def test_host_index_labels():
    where = tr.HostIndex(small_trace().host)
    assert where.label(0.55) == "after:bench.call"
    assert where.label(1.0) == "bench.prefill_jit"
    assert where.label(2.65) == "after:bench.prefill_jit"
    assert where.label(9.1) == "bench.client"
    assert where.label(9.5) == "outside"


def test_host_window():
    assert tr.host_window(small_trace()) == (0.0, 10.0)
    with pytest.raises(ValueError):
        tr.host_window(tr.Trace())


def test_recorded_v5e_trace():
    """One call (B=16, an 8-token prompt, 4 new tokens) of qwen2-0.5b at
    its published size, traced on a TPU v5 lite: a prefill, three decode
    steps with their argmax, broadcast and convert programs, and the
    harness's host spans."""
    t = tr.load(FIXTURE / "tiny.xplane.pb")
    assert list(t.ops) == ["/device:TPU:0"]
    lo, hi = tr.host_window(t)
    names = [h[0] for h in t.host]
    assert names.count("bench.decode_step_jit") == 3
    assert names.count("bench.prefill_jit") == names.count("bench.call") == 1
    assert tr.module_seconds(t, r"^jit_prefill(\(|$)", lo, hi)[1] == 1
    secs, n = tr.module_seconds(t, r"^jit_decode_step(\(|$)", lo, hi)
    assert n == 3 and 0.005 < secs < 0.01  # 1.878 ms a step
    busy = tr.busy_seconds(t, lo, hi)
    assert 0 < busy < hi - lo
    # the programs cover the op intervals that lie inside them
    mods = tr.top_modules(t, lo, hi)
    assert mods[0][0] == "jit_prefill" and busy <= sum(v for _, v in mods) + 1e-6
    gaps = tr.idle_by_host(t, lo, hi, n=50)
    assert sum(v for _, v in gaps) == pytest.approx(hi - lo - busy)
    # the device waits on the host's copies and the spray
    idle = dict(map(tuple, gaps))
    assert idle["bench.tree_to_bytes"] > 0.01 and idle["bench.transfer_sync"] > 0.01


def nested_trace():
    """One call with the program's spans around and inside the harness's
    seams, as a traced run records them: the spray holds the seam, which
    holds the engine's transfer, its drain and a wave inside that."""
    t = tr.Trace()
    t.ops["/device:TPU:0"] = [(0.0, 0.05)]
    t.host = sorted([
        ("bench.window", 0.0, 10.0), ("bench.call", 1.0, 9.0), ("tent.generate", 1.1, 8.8),
        ("tent.prefill", 1.2, 2.0), ("bench.prefill_jit", 1.3, 1.9),
        ("tent.kv.pack", 2.0, 3.0), ("bench.tree_to_bytes", 2.1, 2.9),
        ("tent.kv.segments", 3.0, 4.0),
        ("tent.kv.spray", 4.0, 6.0), ("bench.transfer_sync", 4.1, 5.9),
        ("tent.engine.transfer", 4.2, 5.8), ("tent.engine.drain", 4.5, 5.5),
        ("tent.engine.wave", 4.9, 5.1),
        ("tent.kv.read", 6.0, 7.0),
        ("tent.decode", 7.0, 8.5), ("tent.decode.step", 7.1, 7.2),
        ("bench.decode_step_jit", 7.12, 7.18), ("tent.decode.fetch", 7.2, 7.4),
        ("bench.client", 9.2, 9.5)], key=lambda h: (h[1], -h[2]))
    return t


@pytest.mark.parametrize("t,label", [
    (0.5, "outside"), (1.05, "after:bench.call"), (1.15, "tent.generate"),
    (1.25, "tent.prefill"), (1.5, "bench.prefill_jit"), (2.05, "tent.kv.pack"),
    (2.5, "bench.tree_to_bytes"), (3.5, "tent.kv.segments"), (4.05, "tent.kv.spray"),
    (4.15, "bench.transfer_sync"), (4.3, "tent.engine.transfer"),
    (4.6, "tent.engine.drain"), (5.0, "tent.engine.wave"), (5.3, "tent.engine.drain"),
    (5.7, "tent.engine.transfer"), (5.85, "bench.transfer_sync"), (6.5, "tent.kv.read"),
    (7.15, "bench.decode_step_jit"), (7.19, "tent.decode.step"),
    (7.3, "tent.decode.fetch"), (7.5, "tent.decode"), (8.6, "tent.generate"),
    (8.9, "after:tent.generate"), (9.1, "outside"), (9.3, "bench.client"),
    (9.8, "outside")])
def test_nested_spans_label_by_the_innermost(t, label):
    assert tr.HostIndex(nested_trace().host).label(t) == label


def test_after_labels_only_time_no_span_covers():
    t = nested_trace()
    gaps = dict(map(tuple, tr.idle_by_host(t, 0.0, 10.0, n=50)))
    assert sum(gaps.values()) == pytest.approx(10.0 - 0.05)
    after = {k: v for k, v in gaps.items() if k.startswith("after:")}
    # the call's first 0.1 s, before tent.generate, and its last 0.2 s
    assert after == {"after:bench.call": pytest.approx(0.1),
                     "after:tent.generate": pytest.approx(0.2)}
    assert gaps["tent.kv.segments"] == pytest.approx(1.0)
    assert gaps["tent.kv.read"] == pytest.approx(1.0)
    assert gaps["tent.engine.drain"] == pytest.approx(0.8)  # less its wave
    assert gaps["tent.engine.wave"] == pytest.approx(0.2)
    assert gaps["bench.transfer_sync"] == pytest.approx(0.2)
    assert gaps["tent.generate"] == pytest.approx(0.1 + 0.3)
