"""The chip benchmark: one cell of `BENCHMARK.json`, one process, one run.

A run builds what its cell names (a configuration file, a traffic mix, a
file of check limits), makes the weights on the device from `--seed`,
serves the mix through `DisaggregatedServer.generate` over a `TentEngine`
built from the `disagg_prefill_decode` scenario, and times it from outside
the program: the module-level callables that `generate` looks up at call
time, and the engine's transfer entry points, are wrapped ("seams"). A
traced run (`--trace 1`) also records the program's own host spans
(`repro.obs.HostSpans`, where the server has `attach_spans`) for the
per-layer readers; an untraced run attaches nothing.

The client is one closed loop: the next call is issued when the previous
one returns. Every call is one batch of `batch` requests of one prompt
length and one output length. A request's first token is on the host when
the first decode step of its call begins; its last token when `generate`
returns. The window holds the calls issued while it is open; the call
running when it closes is served to its end, and the metrics are taken
over all of them and up to that end.

After the window the run checks what the timed path produced:
- handoff: the decode segment of sampled calls holds the prefill cache's
  bytes exactly;
- engine: every transfer completed whole and the engine's audit shows no
  slice lost or open;
- model: on a sample of finished requests drawn from the seed (the longest
  among them), the widest gap by which a served token's logit lies below
  the best logit of the plain float32 reference, run over the prompt and
  the served tokens.

The last line on stdout is the result object; the numbers compared, with
their limits, are the last lines on stderr and the last key of the result.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import bench_traffic as traffic

BENCH_REL = Path("benchmarks/chip")
MODULE_SEAMS = ("prefill_jit", "tree_to_bytes", "bytes_to_tree", "decode_step_jit")
ENGINE_SEAMS = ("transfer_sync", "run_until_idle")
# seams every call has to pass through (run_until_idle serves only the
# asynchronous handoff, which the benchmark does not ask for)
CALL_SEAMS = ("prefill_jit", "tree_to_bytes", "transfer_sync", "bytes_to_tree",
              "decode_step_jit")
SCENARIO = "disagg_prefill_decode"
# A traced run serves this long at most (and to the end of the call then
# running): the device trace holds up to some 220,000 op events a second
# on a v5e (a 5 s window of qwen2-0.5b decode wrote 1.1 million, 65 MB),
# which the run must read back within its time limit.
TRACE_SECONDS = 16.0
# the window's first call, and one drawn from the next few, keep their
# handoff bytes for the comparison after the window
HANDOFF_CANDIDATES = 4


class BenchError(RuntimeError):
    """A run that cannot measure: it prints no result and exits non-zero."""


# ---------------------------------------------------------------------------
# What the cell names
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    root: Path
    bench: Dict[str, Any]
    workload: Dict[str, Any]
    config: Dict[str, Any]  # the configuration file's contents
    mix: Dict[str, Any]
    checks: Dict[str, Any]
    arch: Any  # the module under arch/ that the configuration names

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["per_layer"] if self._reports(m)]

    def _reports(self, metric: Dict[str, Any]) -> bool:
        return self.name in metric.get("workloads", [self.name])


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    bdir = root / BENCH_REL
    mix = traffic.load_mix(bdir / "traffic" / f"{w['traffic']}.json")
    checks = json.loads((bdir / "checks" / f"{workload}.json").read_text())
    arch = load_module(bdir / "arch" / f"{config['arch']}.py",
                       f"bench_arch_{config['arch']}")
    return Cell(root, bench, w, config, mix, checks, arch)


def program_config(cfg: Dict[str, Any]):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["arch"] != "dense":
        raise BenchError(f"no program mapping for arch {cfg['arch']!r}")
    return ModelConfig(
        name=cfg["name"], arch_type="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        qkv_bias=cfg["qkv_bias"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"],
        source=cfg["source"])


# ---------------------------------------------------------------------------
# Device and compilation
# ---------------------------------------------------------------------------

def device_gate(chips: int, platform: str = "tpu"):
    """The devices of this run; no fallback to another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"needs a {platform.upper()}, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(root: Path, kind: str) -> Dict[str, Any]:
    table = json.loads((root / BENCH_REL / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def configure_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, every program cached however small or quick to compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Backend compilations and persistent-cache hits, as JAX reports them."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> int:
        return self.compiles + self.cache_hits

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# ---------------------------------------------------------------------------
# Seams
# ---------------------------------------------------------------------------

@dataclass
class CallRecord:
    index: int
    prompt_len: int
    n_new: int
    prompt: np.ndarray
    keep_handoff: bool = False
    t_issue: float = math.nan
    t_done: float = math.nan
    decode_starts: List[float] = field(default_factory=list)
    spans: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    slices: int = 0
    transfers: List[Tuple[bool, int, int]] = field(default_factory=list)  # ok, bytes, asked
    segments: List[int] = field(default_factory=list)
    blob: Optional[np.ndarray] = None  # prefill cache bytes, when kept
    kv_segment: int = -1
    tokens: Optional[np.ndarray] = None
    error: str = ""

    def seconds(self, seam: str) -> float:
        return sum(e - s for s, e in self.spans.get(seam, []))

    def token_times(self) -> List[float]:
        """When each output token was on the host."""
        return self.decode_starts[: self.n_new - 1] + [self.t_done]


class Seams:
    """Wraps the callables `generate` looks up at call time and records,
    per call, host spans that end in a synchronised array."""

    def __init__(self, disagg, engine):
        import jax

        self._jax = jax
        self.disagg, self.engine = disagg, engine
        missing = [n for n in MODULE_SEAMS if not callable(getattr(disagg, n, None))]
        missing += [f"engine.{n}" for n in ENGINE_SEAMS
                    if not callable(getattr(engine, n, None))]
        if missing:
            raise BenchError("seams missing from the program: " + ", ".join(missing))
        self.current: Optional[CallRecord] = None
        self._orig = {n: getattr(disagg, n) for n in MODULE_SEAMS}
        for n in MODULE_SEAMS:
            setattr(disagg, n, getattr(self, "_" + n))
        for n in ENGINE_SEAMS:
            orig = getattr(engine, n)
            self._orig["engine." + n] = orig
            setattr(engine, n, getattr(self, "_" + n))

    def original(self, name: str) -> Callable:
        return self._orig[name]

    def restore(self) -> None:
        for n in MODULE_SEAMS:
            setattr(self.disagg, n, self._orig[n])
        for n in ENGINE_SEAMS:
            self.engine.__dict__.pop(n, None)

    def _span(self, name: str, fn: Callable, *a, sync: bool = False, **kw):
        t0 = time.perf_counter()
        with self._jax.profiler.TraceAnnotation("bench." + name):
            out = fn(*a, **kw)
            if sync:
                self._jax.block_until_ready(out)
        t1 = time.perf_counter()
        if self.current is not None:
            self.current.spans.setdefault(name, []).append((t0, t1))
        return out

    def _prefill_jit(self, *a, **kw):
        return self._span("prefill_jit", self._orig["prefill_jit"], *a, sync=True, **kw)

    def _tree_to_bytes(self, tree):
        data, metas = self._span("tree_to_bytes", self._orig["tree_to_bytes"], tree)
        if self.current is not None and self.current.keep_handoff:
            self.current.blob = data
        return data, metas

    def _bytes_to_tree(self, data, like):
        return self._span("bytes_to_tree", self._orig["bytes_to_tree"], data, like,
                          sync=True)

    def _decode_step_jit(self, *a, **kw):
        if self.current is not None:
            self.current.decode_starts.append(time.perf_counter())
        return self._span("decode_step_jit", self._orig["decode_step_jit"], *a, **kw)

    def _transfer(self, name: str, fn: Callable, *a):
        before = self.engine.slices_issued
        res = self._span(name, fn, *a)
        if self.current is not None:
            self.current.slices += self.engine.slices_issued - before
        return res

    def _transfer_sync(self, src, soff, dst, doff, length):
        res = self._transfer("transfer_sync", self._orig["engine.transfer_sync"],
                             src, soff, dst, doff, length)
        if self.current is not None:
            self.current.transfers.append((bool(res.ok), int(res.bytes), int(length)))
            self.current.segments += [src, dst]
        return res

    def _run_until_idle(self):
        return self._transfer("run_until_idle", self._orig["engine.run_until_idle"])


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

@dataclass
class Served:
    records: List[CallRecord]
    t_start: float
    t_end: float


def build_server(cell: Cell, params):
    from repro.scenarios import ScenarioRunner, get
    from repro.serving import DisaggregatedServer
    from repro.serving import disagg

    engine, _ = ScenarioRunner(get(SCENARIO)).build_engine("tent")
    server = DisaggregatedServer(engine, program_config(cell.config), params,
                                 prefill_node=0, decode_node=1)
    return server, Seams(disagg, engine)


def release(engine, rec: CallRecord, *, keep_dst: bool = False) -> None:
    """Deregister the segments a call left behind (`generate` never does)."""
    for sid in rec.segments:
        if not (keep_dst and sid == rec.kv_segment):
            engine.segments.deregister(sid)


def serve_call(server, seams: Seams, rec: CallRecord, max_len: int) -> None:
    import jax

    seams.current = rec
    rec.t_issue = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.call"):
            res = server.generate(rec.prompt, n_new=rec.n_new, max_len=max_len)
        rec.t_done = time.perf_counter()
        rec.tokens = np.asarray(res.tokens)
        rec.kv_segment = res.kv_segment_id
    except Exception:  # a failed request is counted and reported, not fatal
        rec.t_done = time.perf_counter()
        rec.error = traceback.format_exc()
        print(f"call {rec.index} failed:\n{rec.error}", file=sys.stderr, flush=True)
    finally:
        seams.current = None


def warm_up(cell: Cell, server, seams: Seams, seed: int) -> None:
    """Every program the window runs, at every shape it runs it, compiled
    or loaded before it: one call at the shortest prompt length, which runs
    the handoff, the decode step and the small programs around it, and the
    prefill of each longer one compiled without running it (or, where the
    program's prefill is not a jitted function of `generate`'s arguments,
    one call at that length too)."""
    batch, max_len = cell.mix["batch"], cell.mix["max_len"]
    prompts = traffic.Prompts(seed, batch, cell.config["vocab_size"],
                              stream=traffic.WARMUP)
    shortest, *longer = traffic.prompt_buckets(cell.mix)

    def call(s: int) -> None:
        rec = CallRecord(-1, s, 2, prompts.next(s))
        serve_call(server, seams, rec, max_len)
        release(server.engine, rec)
        if rec.error:
            raise BenchError(f"warm-up call at prompt length {s} failed")

    call(shortest)
    for s in longer:
        try:
            seams.original("prefill_jit").lower(
                server.cfg, server.params, prompts.next(s), max_len,
                enc_frames=None).compile()
        except (AttributeError, TypeError):
            call(s)


def serve_window(cell: Cell, server, seams: Seams, seed: int, seconds: float,
                 keep: set, until: Optional[Callable[[List[CallRecord]], bool]] = None
                 ) -> Served:
    """Calls of the seed's traffic, one after another, issued while the
    window is open (and, where `until` is given, until it holds); the call
    running when it closes is served to its end. `keep` holds the places in
    the window of the calls whose handoff bytes are kept."""
    import jax

    calls = traffic.calls(cell.mix, start=cell.mix.get("first_call", 0))
    prompts = traffic.Prompts(seed, cell.mix["batch"], cell.config["vocab_size"])
    records: List[CallRecord] = []
    with jax.profiler.TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        t_end = t_start + seconds
        while time.perf_counter() < t_end or (until is not None and not until(records)):
            with jax.profiler.TraceAnnotation("bench.client"):
                c = next(calls)
                rec = CallRecord(c.index, c.prompt_len, c.n_new,
                                 prompts.next(c.prompt_len),
                                 keep_handoff=len(records) in keep)
            serve_call(server, seams, rec, cell.mix["max_len"])
            with jax.profiler.TraceAnnotation("bench.client"):
                release(server.engine, rec, keep_dst=rec.keep_handoff)
            records.append(rec)
    return Served(records, t_start, t_end)


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------

def end_to_end(served: Served, batch: int, setup_s: float) -> Dict[str, float]:
    """Over all the window's work: every request of every call issued while
    it was open, and the time from its opening to the end of its last call,
    so that a change in the time of any call moves the metrics it is in."""
    ok = [r for r in served.records if not r.error]
    if not ok:
        raise BenchError(f"the {served.t_end - served.t_start:.0f} s window finished "
                         f"no request ({len(served.records)} calls issued)")
    span = max(r.t_done for r in served.records) - served.t_start
    ttft = [r.token_times()[0] - r.t_issue for r in ok]
    tpot = [(r.t_done - r.token_times()[0]) / (r.n_new - 1) for r in ok]
    # every request of a call has its call's times
    ttft_r, tpot_r = np.repeat(ttft, batch), np.repeat(tpot, batch)
    return {
        "ttft_p50_ms": float(np.percentile(ttft_r, 50)) * 1e3,
        "ttft_p90_ms": float(np.percentile(ttft_r, 90)) * 1e3,
        "tpot_p90_ms": float(np.percentile(tpot_r, 90)) * 1e3,
        "output_tok_s": sum(r.n_new for r in ok) * batch / span,
        "setup_s": setup_s,
    }


def call_times(served: Served) -> List[str]:
    """One line per call of the window: its place in the block, its sizes,
    when it was issued, its first token's wait and its length."""
    return [f"call {r.index} prompt {r.prompt_len} n_new {r.n_new} issued "
            f"{r.t_issue - served.t_start:.3f} s ttft "
            f"{(r.token_times()[0] - r.t_issue) * 1e3:.1f} ms took "
            f"{(r.t_done - r.t_issue) * 1e3:.1f} ms" + (" FAILED" if r.error else "")
            for r in served.records]


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def check_handoff(engine, records: List[CallRecord]) -> Tuple[int, int]:
    """(calls compared, bytes that differ) between the prefill cache's
    bytes and the decode segment they were sprayed into."""
    compared, differ = 0, 0
    for r in records:
        if r.blob is None or r.error:
            continue
        got = engine.segments.get(r.kv_segment).read(0, r.blob.size)
        differ += int(np.count_nonzero(got != r.blob))
        compared += 1
        engine.segments.deregister(r.kv_segment)
        r.blob = None
    return compared, differ


def check_engine(engine, records: List[CallRecord]) -> int:
    """Transfers that did not complete whole, plus slices the engine's
    audit shows lost or open."""
    bad = sum(1 for r in records for ok, got, asked in r.transfers
              if not ok or got != asked)
    audit = engine.audit()
    return bad + audit["batches_failed"] + audit["batches_open"] + audit["slices_outstanding"]


def unused_seams(records: List[CallRecord]) -> List[str]:
    ok = [r for r in records if not r.error]
    return [s for s in CALL_SEAMS if any(s not in r.spans for r in ok)]


def sample_requests(records: List[CallRecord], batch: int, check_tokens: int,
                    seed: int) -> List[Tuple[CallRecord, int]]:
    """Finished requests drawn from the seed: a longest one, then others
    until `check_tokens` served tokens are covered."""
    rng = np.random.default_rng([seed, traffic.CHECK])
    done = [r for r in records if not r.error]
    pairs = [(r, b) for r in done for b in range(batch)]
    longest = max(r.prompt_len + r.n_new for r in done)
    first = [p for p in pairs if p[0].prompt_len + p[0].n_new == longest]
    pick = [first[rng.integers(len(first))]]
    covered = pick[0][0].n_new
    for i in rng.permutation(len(pairs)):
        if covered >= check_tokens:
            break
        if pairs[i] not in pick:
            pick.append(pairs[i])
            covered += pairs[i][0].n_new
    return pick


def reference_inputs(sample: List[Tuple[CallRecord, int]], max_len: int):
    """Per request: the prompt and its served tokens as one row, the served
    tokens as the targets of the positions that produced them."""
    R = len(sample)
    tokens = np.zeros((R, max_len), np.int32)
    query = np.zeros((R, max_len), np.int32)
    spans = []
    for i, (r, b) in enumerate(sample):
        S, n = r.prompt_len, r.n_new
        served = r.tokens[b]
        tokens[i, :S] = r.prompt[b]
        tokens[i, S:S + n - 1] = served[:-1]
        query[i, S - 1:S + n - 1] = served
        spans.append((S - 1, S + n - 1))
    return tokens, query, spans


def served_gap(arch, config, params, sample, max_len: int) -> float:
    """Widest gap, over the sampled served tokens, between the reference's
    best logit and its logit of the served token."""
    tokens, query, spans = reference_inputs(sample, max_len)
    best, _, qlogit = arch.logit_stats(config, params, tokens, query)
    return float(max((best[i, a:b] - qlogit[i, a:b]).max()
                     for i, (a, b) in enumerate(spans)))


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------

@dataclass
class LayerContext:
    """What a per-layer metric's reader may read."""
    calls: List[CallRecord]  # finished calls of the traced window
    trace: Any  # trace_reduce.Trace
    window: Tuple[float, float]  # the traced window, on the trace's clock
    arch: Any
    dims: Any
    peaks: Dict[str, Any]
    batch: int
    # the program's finished host spans of the traced window
    # (`repro.obs.HostSpans`: (name, t0_ns, t1_ns, parent, call, attrs) in
    # the order they opened); None where the program has no `attach_spans`
    spans: Optional[List[tuple]] = None

    def program_seconds(self, pattern: str) -> Tuple[float, int]:
        import trace_reduce

        return trace_reduce.module_seconds(self.trace, pattern, *self.window)

    def busy_seconds(self) -> float:
        import trace_reduce

        return trace_reduce.busy_seconds(self.trace, *self.window)


def read_per_layer(cell: Cell, ctx: LayerContext) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer():
        reader = load_module(cell.root / BENCH_REL / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def attach_spans(server):
    """A fresh `repro.obs.HostSpans` attached to the server and its
    engine, where the program has `attach_spans`; None where it has not."""
    if not callable(getattr(server, "attach_spans", None)):
        return None
    from repro.obs import HostSpans

    rec = HostSpans()
    server.attach_spans(rec)
    return rec


def start_trace(tmp: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)


def stop_trace(tmp: Path):
    import jax
    import trace_reduce

    jax.profiler.stop_trace()
    files = sorted(tmp.glob("**/*.xplane.pb"))
    if not files:
        raise BenchError("the profiler wrote no trace")
    return trace_reduce.load(files[-1])


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, *, platform: str = "tpu") -> Dict[str, Any]:
    """One run of one cell; returns the result object. `platform` is the
    only device the run accepts (the CPU only in the benchmark's tests)."""
    cell = load_cell(root, workload)
    devices = device_gate(cell.workload["chips"], platform)
    peaks = peaks_for(root, devices[0].device_kind)
    configure_cache(root)
    sys.path.insert(0, str(root / "src"))
    try:
        import repro.serving.disagg  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the program is not importable from {root / 'src'}: {e}")
    clock = CompileCount()
    try:
        return _run(cell, devices, peaks, clock, seed, seconds, trace, t_process)
    finally:
        clock.close()


def _run(cell: Cell, devices, peaks, clock: CompileCount, seed: int, seconds: float,
         trace: bool, t_process: float) -> Dict[str, Any]:
    import jax

    params = cell.arch.make_params(cell.config, seed)
    jax.block_until_ready(params)
    server, seams = build_server(cell, params)
    try:
        warm_up(cell, server, seams, seed)
        rng = np.random.default_rng([seed, traffic.HANDOFF])
        keep = {0, 1 + int(rng.integers(HANDOFF_CANDIDATES - 1))}
        # what set-up left is kept out of the collections inside the window
        gc.collect()
        gc.freeze()
        tmp = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
        if trace:
            start_trace(tmp)
        compiles_before = clock.total()
        setup_s = time.perf_counter() - t_process
        # the program's own spans in the traced run only: the end-to-end
        # metrics are measured with them off
        spans = attach_spans(server) if trace else None
        try:
            served = serve_window(cell, server, seams, seed,
                                  min(seconds, TRACE_SECONDS) if trace else seconds, keep)
        finally:
            if spans is not None:
                server.attach_spans(None)
            tr = stop_trace(tmp) if trace else None
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)
        window_compiles = clock.total() - compiles_before
    finally:
        seams.restore()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    records = served.records
    missing = unused_seams(records)
    if missing:
        raise BenchError("seams the window never passed through: " + ", ".join(missing))
    batch, max_len = cell.mix["batch"], cell.mix["max_len"]
    dev = devices[0]

    if trace:
        import trace_reduce

        lo, hi = trace_reduce.host_window(tr)
        ctx = LayerContext([r for r in records if not r.error], tr, (lo, hi),
                           cell.arch, cell.arch.dims(cell.config), peaks, batch,
                           spans.finished() if spans is not None else None)
        metrics = read_per_layer(cell, ctx)
        extra_device = {"busy_s": ctx.busy_seconds(), "window_s": hi - lo}
        breakdown = {"device_ops": trace_reduce.top_modules(tr, lo, hi),
                     "idle_gaps": trace_reduce.idle_by_host(tr, lo, hi)}
    else:
        e2e = end_to_end(served, batch, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()}
        extra_device, breakdown = {}, None

    # checks, with the program's state freed before the reference runs
    compared, differ = check_handoff(server.engine, records)
    lost = check_engine(server.engine, records)
    failed = sum(batch for r in records if r.error)
    sample = sample_requests(records, batch, cell.mix["check_tokens"], seed)
    del server, seams
    gc.unfreeze()
    gc.collect()
    gap = served_gap(cell.arch, cell.config, params, sample, max_len)
    for line in call_times(served):
        print(line, file=sys.stderr)
    checks = {
        "served_gap_max": {"value": gap, "limit": cell.checks["served_gap_max"]},
        "handoff_bytes_differ": {"value": differ, "limit": 0},
        "transfers_not_whole": {"value": lost, "limit": 0},
        "requests_failed": {"value": failed, "limit": 0},
    }
    correct = compared >= 1 and all(v["value"] <= v["limit"] for v in checks.values())
    print(f"checked {len(sample)} requests, "
          f"{sum(r.n_new for r, _ in sample)} served tokens, handoff bytes of "
          f"{compared} calls; "
          f"window: {len(records)} calls, {window_compiles} compiles; "
          f"run compiles {clock.compiles}, cache hits {clock.cache_hits}",
          file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    out = {
        "correct": bool(correct),
        "attempted": len(records) * batch,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(mem),
                   **extra_device},
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
