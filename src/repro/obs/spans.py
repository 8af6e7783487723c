"""Host-clock spans for the real serving path and the engine.

`HostSpans` records nested spans `(name, t0_ns, t1_ns, parent, call,
attrs)` on `time.perf_counter_ns`, in memory, in the order they opened.
`parent` is the index of the enclosing span (-1 at the top) and `call` the
id of the `tent.generate` call that caused the span (-1 outside any call),
so every span of one call shares it. Each span is also opened as a
`jax.profiler.TraceAnnotation` of the same name, so that a profiler trace
holds it on the host plane, on the same clock as the device's events.

Besides the training step timer and the compile-time probe, this is the
only module of `repro` that reads a host clock: the flight recorder
(`recorder.py`) and everything it observes run on the fabric's virtual
clock, and tentlint's `no-wall-clock` rule and the `REPRO_SANITIZE`
sanitizer allow this file by name.

Zero-cost-when-off, the flight recorder's contract: holders keep
`self._spans = None` until `attach_spans` is called, and each site is one
attribute load and one `None` test (in the engine inline, in the serving
path inside `span()`) per call, per decode step, per wave or per drain run,
never per slice. With nothing attached no clock is read and no annotation
is made. Spans sit outside jitted code and add no `block_until_ready`: a
span around an asynchronous dispatch ends when the dispatch returns, not
when the device finishes.

Calls are single-threaded: one recorder serves one thread.
"""
from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .trace import to_json, validate_trace

# (name, t0_ns, t1_ns, parent index, call id, attrs)
Span = Tuple[str, int, int, int, int, Dict[str, Any]]

_OFF = contextlib.nullcontext()


class HostSpans:
    """In-memory recorder of nested host spans (see the module docstring)."""

    __slots__ = ("spans", "calls", "_stack", "_call", "_annotation")

    def __init__(self):
        # imported here: the engine imports `repro.obs` and must not need jax
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        #: finished spans by the order they opened; a slot is None while open
        self.spans: List[Optional[Span]] = []
        #: call ids handed out so far
        self.calls = 0
        # open spans: (index, name, attrs, annotation, enclosing call, t0_ns)
        self._stack: List[tuple] = []
        self._call = -1

    def open(self, name: str, *, new_call: bool = False, **attrs) -> Dict[str, Any]:
        """Open a span inside the innermost open one. `new_call` starts a
        call: the span and everything inside it get the next call id, which
        is also its `call` attr. Returns the span's attrs, which the caller
        may add to until the span closes."""
        prev = self._call
        if new_call:
            self._call = attrs["call"] = self.calls
            self.calls += 1
        ann = self._annotation(name)
        ann.__enter__()
        self._stack.append((len(self.spans), name, attrs, ann, prev,
                            time.perf_counter_ns()))
        self.spans.append(None)
        return attrs

    def close(self) -> None:
        """Close the innermost open span."""
        t1 = time.perf_counter_ns()
        i, name, attrs, ann, prev, t0 = self._stack.pop()
        ann.__exit__(None, None, None)
        parent = self._stack[-1][0] if self._stack else -1
        self.spans[i] = (name, t0, t1, parent, self._call, attrs)
        self._call = prev

    @contextlib.contextmanager
    def span(self, name: str, *, new_call: bool = False,
             **attrs) -> Iterator[Dict[str, Any]]:
        """`open` ... `close` as a block, yielding the span's attrs. On the
        way out it also closes spans opened inside it and left open by an
        exception, so one failed call cannot unbalance the next."""
        depth = len(self._stack)
        attrs = self.open(name, new_call=new_call, **attrs)
        try:
            yield attrs
        finally:
            while len(self._stack) > depth:
                self.close()

    def finished(self) -> List[Span]:
        return [s for s in self.spans if s is not None]

    def write_trace(self, path) -> None:
        """Write the finished spans as a Trace Event Format file (host
        microseconds, one process, one thread) that Perfetto and
        chrome://tracing open; raises ValueError where the document breaks
        the format's invariants (`validate_trace`)."""
        body = [{"ph": "X", "pid": 1, "tid": 1, "ts": t0 / 1e3,
                 "dur": (t1 - t0) / 1e3, "name": name, "cat": "tent",
                 "args": {**attrs, "call": call, "parent": parent}}
                for name, t0, t1, parent, call, attrs in self.finished()]
        meta = [{"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
                 "args": {"name": "host"}},
                {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
                 "args": {"name": "spans"}}]
        doc = {"displayTimeUnit": "ms", "traceEvents": meta + body,
               "otherData": {"generator": "repro.obs.spans",
                             "spans": len(body), "calls": self.calls}}
        problems = validate_trace(doc)
        if problems:
            raise ValueError("host span trace is malformed: " + "; ".join(problems[:5]))
        Path(path).write_text(to_json(doc))


def span(rec: Optional[HostSpans], name: str, **attrs):
    """`rec.span(name, **attrs)`, or a shared no-op block where no recorder
    is attached: the off path reads no clock and makes no annotation."""
    return _OFF if rec is None else rec.span(name, **attrs)
