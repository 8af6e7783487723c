"""The traffic generator: every block holds the mix's exact shares in one
fixed order whose every prefix keeps them as nearly as whole calls can,
and the seed draws only the token ids."""
import collections
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

import bench_traffic as traffic

MIXES = sorted((Path(__file__).resolve().parents[2] / "benchmarks/chip/traffic").glob("*.json"))


def take(mix, n):
    return list(itertools.islice(traffic.calls(mix), n))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_block_holds_the_exact_mix_in_one_order(path):
    mix = traffic.load_mix(path)
    m = mix["block_calls"]
    want = {int(s): int(n) for s, n in mix["prompt_calls"]}
    calls = take(mix, 3 * m)
    assert [c.index for c in calls] == list(range(3 * m))
    blocks = [[(c.prompt_len, c.n_new) for c in calls[b * m:(b + 1) * m]] for b in range(3)]
    assert blocks[0] == blocks[1] == blocks[2]
    assert collections.Counter(s for s, _ in blocks[0]) == want
    lo, hi = mix["n_new"]
    news = sorted(n for _, n in blocks[0])
    assert news == [round(lo * (hi / lo) ** ((i + 0.5) / m)) for i in range(m)]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_prefix_keeps_the_shares(path):
    mix = traffic.load_mix(path)
    m = mix["block_calls"]
    calls = take(mix, m)
    for k in range(1, m + 1):
        seen = collections.Counter(c.prompt_len for c in calls[:k])
        for s, n in mix["prompt_calls"]:
            assert abs(seen[int(s)] - k * n / m) < 1, (k, s)


def test_output_lengths_spread_low_and_high_early():
    mix = {"prompt_calls": [[8, 4]], "block_calls": 4, "n_new": [16, 256], "max_len": 300,
           "batch": 1, "check_tokens": 1}
    # 16 * 16 ** ((i + 0.5) / 4) for i = 0..3, in van der Corput order of rank
    assert traffic.block_lengths(mix) == ([8, 8, 8, 8], [23, 91, 45, 181])


def test_the_seed_draws_the_token_ids():
    a = traffic.Prompts(2**31 + 5, 4, 1000).next(64)
    assert a.dtype == np.int32 and a.shape == (4, 64)
    assert np.array_equal(a, traffic.Prompts(2**31 + 5, 4, 1000).next(64))
    assert not np.array_equal(a, traffic.Prompts(2**31 + 6, 4, 1000).next(64))
    assert 0 <= a.min() and a.max() < 1000
    warm = traffic.Prompts(2**31 + 5, 4, 1000, stream=traffic.WARMUP).next(64)
    assert not np.array_equal(a, warm)


def test_a_mix_whose_shares_do_not_fill_the_block_is_refused(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"batch": 2, "max_len": 64, "block_calls": 5,
                             "prompt_calls": [[8, 2], [16, 2]], "n_new": [2, 8],
                             "check_tokens": 8}))
    with pytest.raises(ValueError, match="sum to block_calls"):
        traffic.load_mix(p)


def test_the_longest_request_must_fit_the_cache(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"batch": 2, "max_len": 20, "block_calls": 2,
                             "prompt_calls": [[8, 1], [16, 1]], "n_new": [2, 8],
                             "check_tokens": 8}))
    with pytest.raises(ValueError, match="max_len"):
        traffic.load_mix(p)
