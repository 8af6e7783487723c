"""From the program's host spans to seconds, counts and self time.

A span is `repro.obs.HostSpans`'s tuple `(name, t0_ns, t1_ns, parent,
call, attrs)`, and a list of them is in the order they opened, as
`HostSpans.finished()` gives it. Spans of one thread nest: a span that
opens inside another closes inside it. One call of `generate` is one
`tent.generate` span.
"""
from __future__ import annotations

from typing import Collection, Sequence

CALL = "tent.generate"


def calls(spans: Sequence[tuple]) -> int:
    """The calls the spans cover."""
    return sum(1 for s in spans if s[0] == CALL)


def seconds(spans: Sequence[tuple], names: Collection[str]) -> float:
    """Total seconds of the spans named in `names`."""
    return sum(s[2] - s[1] for s in spans if s[0] in names) * 1e-9


def attr_sum(spans: Sequence[tuple], name: str, key: str) -> int:
    """Sum of the attr `key` over the spans named `name`."""
    return sum(s[5].get(key, 0) for s in spans if s[0] == name)


def self_seconds(spans: Sequence[tuple], name: str, inner: Collection[str]) -> float:
    """Total seconds of the spans named `name`, less the time of the spans
    named in `inner` inside them (the outermost of those only, so that one
    nested in another is not taken off twice)."""
    total = 0
    for i, (n, t0, t1, *_) in enumerate(spans):
        if n != name:
            continue
        total += t1 - t0
        covered = t0
        for j in range(i + 1, len(spans)):
            m, s0, s1 = spans[j][:3]
            if s0 >= t1:
                break
            if m in inner and s0 >= covered and s1 <= t1:
                total -= s1 - s0
                covered = s1
    return total * 1e-9
