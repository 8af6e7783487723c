"""The one traffic generator: reads a mix file (`traffic/<mix>.json`) and
yields the calls a closed-loop client sends.

A mix file holds:
    batch          requests per call (one prompt length and one n_new per
                   call, as `DisaggregatedServer.generate` requires)
    max_len        the cache length every call is served with
    block_calls    calls per block
    prompt_calls   [[prompt_len, calls per block], ...]: the exact share of
                   each prompt length in every block
    n_new          [lo, hi]: output lengths, log-uniform over the range
    check_tokens   served tokens that the output check compares per run
    first_call     (optional, default 0) the place in the block of a run's
                   first call

Every block of `block_calls` calls holds the same prompt lengths and the
same output lengths, stratified over the log-uniform range
(lo * (hi/lo) ** ((i + 1/2) / block_calls)), in one fixed order: prompt
lengths by smooth weighted round robin, so that every prefix of a block
holds the mix's shares as nearly as whole calls can, and output lengths in
van der Corput order over their ranks. A call of the longest prompts can
take longer than a tenth of a window, so a window holds only part of a
block; a seeded order would give each seed a different part, and so
different work. The seed draws the token ids only, uniform over the
vocabulary.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Iterator, List

import numpy as np

# Independent streams of one seed: prompt tokens, warm-up prompts, the
# calls whose handoff is compared, the requests the reference checks.
TOKENS, WARMUP, HANDOFF, CHECK = 2, 3, 4, 5


@dataclass(frozen=True)
class Call:
    index: int
    prompt_len: int
    n_new: int


def load_mix(path: Path) -> Dict[str, Any]:
    mix = json.loads(Path(path).read_text())
    counts = [int(n) for _, n in mix["prompt_calls"]]
    if sum(counts) != mix["block_calls"] or min(counts) < 1:
        raise ValueError(f"{path}: prompt_calls must sum to block_calls "
                         f"({sum(counts)} != {mix['block_calls']})")
    lo, hi = mix["n_new"]
    longest = max(s for s, _ in mix["prompt_calls"]) + hi
    if not 2 <= lo <= hi or longest > mix["max_len"]:
        raise ValueError(f"{path}: need 2 <= n_new lo <= hi and the longest "
                         f"prompt + hi ({longest}) <= max_len ({mix['max_len']})")
    return mix


def block_lengths(mix: Dict[str, Any]) -> tuple[List[int], List[int]]:
    """The prompt lengths and output lengths of one block, in block order."""
    counts = [(int(s), int(n)) for s, n in sorted(mix["prompt_calls"])]
    m = mix["block_calls"]
    current = [0] * len(counts)
    prompts = []
    for _ in range(m):  # smooth weighted round robin
        current = [c + n for c, (_, n) in zip(current, counts)]
        k = max(range(len(counts)), key=lambda j: current[j])
        current[k] -= m
        prompts.append(counts[k][0])
    lo, hi = mix["n_new"]
    news = [int(round(lo * (hi / lo) ** ((i + 0.5) / m))) for i in range(m)]
    rank = sorted(range(m), key=_van_der_corput)
    order = [0] * m
    for r, i in enumerate(rank):
        order[i] = news[r]
    return prompts, order


def _van_der_corput(i: int) -> float:
    v, f = 0.0, 0.5
    while i:
        v += f * (i & 1)
        i >>= 1
        f /= 2
    return v


def prompt_buckets(mix: Dict[str, Any]) -> List[int]:
    return sorted({int(s) for s, _ in mix["prompt_calls"]})


def calls(mix: Dict[str, Any], start: int = 0) -> Iterator[Call]:
    """Endless calls: the block, over and over, from its call `start`."""
    prompts, news = block_lengths(mix)
    m = len(prompts)
    i = start
    while True:
        yield Call(i, prompts[i % m], news[i % m])
        i += 1


class Prompts:
    """Token ids for call after call, uniform over the vocabulary."""

    def __init__(self, seed: int, batch: int, vocab: int, stream: int = TOKENS):
        self._rng = np.random.default_rng([seed, stream])
        self.batch, self.vocab = batch, vocab

    def next(self, prompt_len: int) -> np.ndarray:
        return self._rng.integers(0, self.vocab, (self.batch, prompt_len),
                                  dtype=np.int32)
