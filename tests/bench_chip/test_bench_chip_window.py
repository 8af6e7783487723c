"""The window's end-to-end metrics cover whole calls: every call issued
while it is open, the last one served to its end, so the time of every
call moves them."""
import itertools

import numpy as np
import pytest

import bench_harness
import bench_traffic as traffic

BATCH = 4


def served(durations, n_new=(10, 20, 30), ttft=0.5, seconds=2.5):
    """Calls back to back from t=0, the window closing at `seconds`."""
    records, t = [], 0.0
    for i, (d, n) in enumerate(zip(durations, n_new)):
        r = bench_harness.CallRecord(i, 16, n, np.zeros((BATCH, 16), np.int32))
        r.t_issue, r.t_done = t, t + d
        first = t + ttft
        r.decode_starts = list(np.linspace(first, t + d, n, endpoint=False))
        records.append(r)
        t += d
    return bench_harness.Served(records, 0.0, seconds)


def test_the_rate_is_all_tokens_over_the_time_to_the_last_calls_end():
    e2e = bench_harness.end_to_end(served([1.0, 1.0, 2.0]), BATCH, 3.0)
    assert e2e["output_tok_s"] == pytest.approx((10 + 20 + 30) * BATCH / 4.0)
    assert e2e["setup_s"] == 3.0


@pytest.mark.parametrize("slower", [0, 1, 2])
def test_a_slower_call_lowers_the_rate_wherever_it_lies(slower):
    base = bench_harness.end_to_end(served([1.0, 1.0, 2.0]), BATCH, 0.0)
    durations = [1.0, 1.0, 2.0]
    durations[slower] *= 1.1
    slow = bench_harness.end_to_end(served(durations), BATCH, 0.0)
    assert slow["output_tok_s"] < base["output_tok_s"]


def test_the_call_running_at_the_close_counts_in_every_metric():
    # the third call starts at 2.0 s and ends at 6.0 s, well after the close
    e2e = bench_harness.end_to_end(served([1.0, 1.0, 4.0], ttft=0.2), BATCH, 0.0)
    tpot = [(d - 0.2) / (n - 1) for d, n in zip([1.0, 1.0, 4.0], (10, 20, 30))]
    assert e2e["tpot_p90_ms"] == pytest.approx(
        np.percentile(np.repeat(tpot, BATCH), 90) * 1e3)
    assert e2e["output_tok_s"] == pytest.approx(60 * BATCH / 6.0)


def test_a_failed_call_counts_its_time_and_no_tokens():
    s = served([1.0, 1.0, 2.0])
    s.records[1].error = "boom"
    e2e = bench_harness.end_to_end(s, BATCH, 0.0)
    assert e2e["output_tok_s"] == pytest.approx((10 + 30) * BATCH / 4.0)


def test_a_window_with_no_finished_call_cannot_measure():
    s = served([1.0])
    s.records[0].error = "boom"
    with pytest.raises(bench_harness.BenchError, match="finished no request"):
        bench_harness.end_to_end(s, BATCH, 0.0)


def test_the_client_can_start_inside_the_block():
    mix = {"prompt_calls": [[8, 2], [16, 1]], "block_calls": 3, "n_new": [4, 16],
           "max_len": 64, "batch": 1, "check_tokens": 1}
    whole = list(itertools.islice(traffic.calls(mix), 6))
    late = list(itertools.islice(traffic.calls(mix, start=2), 4))
    assert late == whole[2:6]
