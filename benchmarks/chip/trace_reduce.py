"""From a profiler trace to device busy time, per-program device time, and
idle gaps labelled by what the host was doing.

`load(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData` into
plain tuples; everything else works on those, so a small recorded trace
checks the arithmetic without a chip.

Device events are those of planes named `/device:<platform>:<n>` (the TPU
planes of a JAX trace). Busy time is the union of the intervals of the
`XLA Ops` line of each device plane (the `XLA Modules` line where a plane
has no op line), averaged over the devices. A program's device time is the
sum of its `XLA Modules` events, found by the module's name. Host spans
are the benchmark's own `jax.profiler.TraceAnnotation`s (names starting
with `bench.`) and the program's (`tent.`, `repro.obs.HostSpans`), on the
same clock as the device events.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]  # (start_s, end_s)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
HOST_PREFIXES = ("bench.", "tent.")
# spans that frame the others and label no time themselves
FRAMES = ("bench.window", "bench.call")


@dataclass
class Trace:
    # per device plane: op intervals, and (module name, start, end) events
    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    modules: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    # host annotations: (name, start, end)
    host: List[Tuple[str, float, float]] = field(default_factory=list)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
            if ops or mods:
                tr.ops[plane.name] = ops or [(s, e) for _, s, e in mods]
                tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        tr.host.append((e.name, e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9))
    tr.host.sort(key=lambda h: (h[1], -h[2]))
    return tr


def union(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_seconds(tr: Trace, lo: float, hi: float) -> float:
    """Union of device-op time in [lo, hi], averaged over the devices."""
    if not tr.ops:
        return 0.0
    per = [sum(e - s for s, e in union(iv, lo, hi)) for iv in tr.ops.values()]
    return sum(per) / len(per)


def module_seconds(tr: Trace, pattern: str, lo: float, hi: float) -> Tuple[float, int]:
    """Device seconds and count of module events whose name matches
    `pattern` (a regular expression) and that start in [lo, hi], averaged
    over the devices."""
    rx = re.compile(pattern)
    if not tr.modules:
        return 0.0, 0
    secs, n = 0.0, 0
    for mods in tr.modules.values():
        for name, s, e in mods:
            if lo <= s < hi and rx.search(name):
                secs += e - s
                n += 1
    k = len(tr.modules)
    return secs / k, n // k


def top_modules(tr: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The programs that took most device time in [lo, hi]."""
    tot: Dict[str, float] = defaultdict(float)
    for mods in tr.modules.values():
        for name, s, e in mods:
            if lo <= s < hi:
                tot[re.sub(r"\(\d+\)$", "", name)] += (e - s) / len(tr.modules)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


class HostIndex:
    """What the host was doing at a time `t`, from the host spans.

    Spans nest (the program's `tent.kv.spray` holds the seam
    `bench.transfer_sync`, which holds `tent.engine.transfer`, which holds
    the engine's waves and drains), and a time is labelled by the innermost
    span around it: the one that opened last. `bench.window` encloses
    everything and `bench.call` one call; neither labels time. Inside a
    call where no other span is open, the time is labelled by the span
    that closed last in that call (`after:<span>`, `after:bench.call`
    before the first); outside every call and span, `outside`."""

    def __init__(self, host: Sequence[Tuple[str, float, float]]):
        edges = sorted({t for _, s, e in host for t in (s, e)})
        # per elementary piece [edges[k], edges[k + 1]): its label
        opens: Dict[float, List[Tuple[float, float, str]]] = defaultdict(list)
        for n, s, e in host:
            if e > s:
                opens[s].append((s, -e, n))
        active: List[Tuple[float, float, str]] = []  # (start, -end, name)
        labels: List[str] = []
        call: Tuple[float, float] = (float("inf"), float("-inf"))
        last = ""  # the labelling span that closed last in the current call
        for t in edges[:-1]:
            closing = [a for a in active
                       if -a[1] == t and a[2] not in FRAMES and a[0] >= call[0]]
            if closing:
                last = min(closing)[2]  # the outermost of those closing together
            active = [a for a in active if -a[1] > t] + opens.get(t, [])
            for s, ne, n in opens.get(t, []):
                if n == "bench.call":
                    call, last = (s, -ne), ""
            inner = max((a for a in active if a[2] not in FRAMES), default=None)
            if inner is not None:
                labels.append(inner[2])
            elif call[0] <= t < call[1]:
                labels.append("after:" + (last or "bench.call"))
            else:
                labels.append("outside")
        self._edges, self._labels = edges, labels

    def label(self, t: float) -> str:
        """The innermost span around `t`; inside a call but in no other
        span, `after:<the span that closed last>`; `outside` where
        nothing is."""
        k = bisect.bisect_right(self._edges, t) - 1
        if k < 0 or k >= len(self._labels):
            return "outside"
        return self._labels[k]

    def split(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        """[lo, hi] cut at every span's edge, each piece labelled."""
        a = bisect.bisect_right(self._edges, lo)
        b = bisect.bisect_left(self._edges, hi)
        cuts = [lo] + self._edges[a:b] + [hi]
        return [(self.label((s + e) / 2), e - s) for s, e in zip(cuts, cuts[1:]) if e > s]


def idle_by_host(tr: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """Idle device time in [lo, hi] (first device), summed by what the host
    was doing meanwhile, largest first."""
    if not tr.ops:
        return []
    busy = union(next(iter(tr.ops.values())), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    where = HostIndex(tr.host)
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        for label, secs in where.split(s, e):
            tot[label] += secs
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_window(tr: Trace, name: str = "bench.window") -> Interval:
    """The traced window, from the benchmark's own span around it."""
    spans = [(s, e) for n, s, e in tr.host if n == name]
    if len(spans) != 1:
        raise ValueError(f"trace holds {len(spans)} {name!r} spans, expected 1")
    return spans[0]
