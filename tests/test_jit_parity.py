"""Jitted engine-core regression tier.

PR 8 routes the wave chooser and the completion drain through jitted
fixed-shape kernels (`EngineConfig.jit_core`, `repro.core.jit_core`). Like
the wave and drain vectorizations before it (PRs 4-5), the jitted core must
be a pure *cost* change: with the toggle on, every scenario outcome — byte
counts, makespans, latency percentiles, retries, per-rail byte maps — has
to be bit-identical to the numpy path, because both run the same IEEE
double operations in the same order under `jit_core.x64()`. These tests pin
that end-to-end across the whole scenario library (including the mid-run
fault-window scenarios), force the crossover to both extremes, and pin the
padded kernels against the scalar references with seeded randomized sweeps
that need no optional deps (the hypothesis twins live in
tests/test_properties.py).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import EngineConfig, FabricSpec, TelemetryStore, Topology
from repro.core import jit_core
from repro.core.jit_core import EngineJitCore, _bucket
from repro.core.scheduler import (
    tent_choose_wave,
    tent_choose_wave_padded_jnp,
    tent_on_complete_many_jnp,
)
from repro.scenarios import SCENARIOS, ScenarioRunner, get

pytestmark = pytest.mark.skipif(
    not jit_core.jax_available(), reason="jitted core requires jax")


def _policies(spec) -> dict:
    return ScenarioRunner(spec).run().to_dict()["policies"]


def _with_jit(spec, on=True):
    return dataclasses.replace(
        spec, engine=dataclasses.replace(spec.engine, jit_core=on))


class TestJitCoreBitIdentity:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_reports_identical_across_jit_toggle(self, name):
        """jit_core on vs off over the full scenario library: identical
        kernels modulo execution engine => identical decisions => identical
        fabric event sequence => every report metric matches exactly. The
        fault scenarios exercise the jitted chooser across exclusion
        windows, failure retries, and readmission."""
        spec = get(name)
        assert _policies(_with_jit(spec)) == _policies(spec)

    @pytest.mark.parametrize(
        "name", ["single_rail_flap", "elephant_mice_mix",
                 "degrade_recover_ramp"])
    def test_forced_crossover_parity(self, name, monkeypatch):
        """Crossover pinned to 1: every wave and every completion batch —
        including the single-slice and single-completion ones the adaptive
        crossover would route to numpy — goes through the jitted kernels,
        and the reports still cannot move."""
        monkeypatch.setattr(jit_core, "JIT_MIN", 1)
        monkeypatch.setattr(jit_core, "JIT_MIN_FLOOR", 1)
        monkeypatch.setattr(jit_core, "JIT_MIN_CEIL", 1)
        spec = get(name)
        assert _policies(_with_jit(spec)) == _policies(spec)

    def test_jit_kernels_actually_engage(self, monkeypatch):
        """Guard against the parity suite silently testing numpy-vs-numpy:
        both jitted kernels must actually dispatch. The chooser engages on
        any fat wave; batched completion drains only form on a zero-jitter
        fabric (distinct-timestamp completions drain per-op), so this
        drives an engine directly on one: 64 slices over 8 identical rails
        complete in same-timestamp groups of 8."""
        from repro.core import Fabric, TentEngine, Topology
        from repro.core.types import Location, MemoryKind

        counts = {"waves": 0, "drains": 0}
        orig_choose = EngineJitCore.choose_wave
        orig_drain = EngineJitCore.on_complete_many

        def counting_choose(self, sc, lengths):
            counts["waves"] += 1
            return orig_choose(self, sc, lengths)

        def counting_drain(self, slots, lengths, queued_at, t_obs):
            counts["drains"] += 1
            return orig_drain(self, slots, lengths, queued_at, t_obs)

        monkeypatch.setattr(EngineJitCore, "choose_wave", counting_choose)
        monkeypatch.setattr(EngineJitCore, "on_complete_many", counting_drain)
        monkeypatch.setattr(jit_core, "JIT_MIN", 1)
        monkeypatch.setattr(jit_core, "JIT_MIN_FLOOR", 1)
        monkeypatch.setattr(jit_core, "JIT_MIN_CEIL", 1)
        topo = Topology(FabricSpec())
        eng = TentEngine(
            topology=topo, fabric=Fabric(topo, seed=0, jitter=0.0),
            config=EngineConfig(jit_core=True))
        n = 4 << 20
        src = eng.register_segment(
            Location(node=0, kind=MemoryKind.HOST_DRAM, numa=0), n)
        dst = eng.register_segment(
            Location(node=1, kind=MemoryKind.HOST_DRAM, numa=0), n)
        src.write(0, np.arange(n, dtype=np.uint8))
        res = eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, n)
        assert res.ok
        np.testing.assert_array_equal(
            dst.read(0, n), np.arange(n, dtype=np.uint8))
        assert counts["waves"] > 0 and counts["drains"] > 0


# ---------------------------------------------------------------------------
# Padded kernels vs scalar references: seeded randomized sweeps (no
# optional deps). Padding rows, invalid slices, heavy exclusion — including
# the all-excluded fallback — and repeated slots are all drawn on purpose.
# ---------------------------------------------------------------------------


def _pad_choose_args(rng, n_c, n_s, all_excluded=False):
    q = rng.integers(0, 1 << 28, size=n_c)
    gl = rng.uniform(0.0, 1e7, size=n_c)
    gr = rng.uniform(0.0, 1e7, size=n_c)
    bw = rng.choice([1e9, 25e9, 100e9], size=n_c)
    b0 = rng.uniform(0.0, 1e-3, size=n_c)
    b1 = rng.uniform(0.05, 10.0, size=n_c)
    pen = rng.choice([1.0, 1.0, 1.5, np.inf], size=n_c)
    if all_excluded:
        ex = np.ones(n_c, dtype=bool)
    else:
        ex = rng.random(n_c) < 0.35
    lengths = rng.integers(1, 1 << 20, size=n_s)
    return q, gl, gr, bw, b0, b1, pen, ex, lengths


def _run_padded_choose(args, rr, gamma):
    q, gl, gr, bw, b0, b1, pen, ex, lengths = args
    n_c, n_s = len(q), len(lengths)
    pc, ps = _bucket(n_c), _bucket(n_s)

    def pad(a, n, fill, dtype=np.float64):
        out = np.full(n, fill, dtype=dtype)
        out[: len(a)] = a
        return out

    valid = np.zeros(ps, dtype=bool)
    valid[:n_s] = True
    from repro.core.jit_core import x64

    with x64():
        c, qa, qo, rro = tent_choose_wave_padded_jnp(
            pad(q, pc, 0.0), pad(gl, pc, 0.0), pad(gr, pc, 0.0),
            pad(bw, pc, 1.0), pad(b0, pc, 0.0), pad(b1, pc, 1.0),
            pad(pen, pc, np.inf), pad(ex, pc, True, dtype=bool),
            pad(lengths, ps, 0.0), valid, rr, gamma)
        return (np.asarray(c)[:n_s].astype(np.int64),
                np.asarray(qa)[:n_s].astype(np.int64),
                np.asarray(qo)[:n_c].astype(np.int64), int(rro))


class TestPaddedChooseKernel:
    def test_matches_scalar_reference_randomized(self):
        rng = np.random.default_rng(29)
        for case in range(60):
            n_c = int(rng.integers(1, 11))
            n_s = int(rng.integers(1, 50))
            args = _pad_choose_args(rng, n_c, n_s)
            rr = int(rng.integers(0, 1000))
            gamma = float(rng.choice([0.0, 0.05, 0.2]))
            ref = tent_choose_wave(*args, rr, gamma=gamma)
            got = _run_padded_choose(args, rr, gamma)
            for r, g, label in zip(ref, got,
                                   ("choices", "queued_at", "queued", "rr")):
                assert np.array_equal(np.asarray(r), np.asarray(g)), \
                    f"case {case} {label}: {r} != {g}"

    def test_all_excluded_fallback_matches_scalar(self):
        """Every candidate soft-excluded: both paths must re-score without
        the exclusion mask (spray-anyway beats stalling) and still agree
        bit for bit — including the inf-penalty rails that stay out."""
        rng = np.random.default_rng(31)
        for case in range(20):
            n_c = int(rng.integers(2, 9))
            args = _pad_choose_args(rng, n_c, 8, all_excluded=True)
            ref = tent_choose_wave(*args, 5, gamma=0.05)
            got = _run_padded_choose(args, 5, 0.05)
            assert [np.asarray(r).tolist() for r in ref] == \
                [np.asarray(g).tolist() for g in got], f"case {case}"
            if np.isfinite(args[6]).any():  # some penalty finite
                assert (got[0] >= 0).all()  # fallback really selected rails

    def test_padding_rows_never_selected(self):
        """A padded candidate (penalty inf + excluded) must lose to any real
        rail even under the all-excluded fallback."""
        args = ([100], [0.0], [0.0], [1e9], [0.0], [1.0], [1.0], [True],
                [4096, 4096, 4096])
        choices, queued_at, queued, rr = _run_padded_choose(
            tuple(np.asarray(a, dtype=float) for a in args), 0, 0.05)
        assert (choices == 0).all()
        assert rr == 3 and queued[0] == 100 + 3 * 4096


def _seeded_store(rng, n_links):
    from repro.core.topology import LinkDesc
    from repro.core.types import LinkClass

    store = TelemetryStore()
    for i in range(n_links):
        desc = LinkDesc(link_id=i, node=0, link_class=LinkClass.RDMA,
                        index=i, numa=0,
                        bandwidth=float(rng.choice([25e9, 1e9])),
                        base_latency=5e-6)
        tl = store.ensure(desc)
        tl.queued_bytes = int(rng.integers(0, 1 << 30))
        tl.beta0 = float(rng.uniform(0.0, 1e-2))
        tl.beta1 = float(rng.uniform(0.05, 50.0))
        tl.ewma_service_time = float(rng.uniform(0.0, 1.0))
    return store


class TestPaddedDrainAdapter:
    def test_adapter_bit_equals_store_drain_randomized(self):
        """`EngineJitCore.on_complete_many` (gather -> padded jitted drain
        with scratch-slot batch padding -> scatter) vs the numpy store
        drain, heavy slot repetition included."""

        class _Policy:  # the drain path only touches the store
            _rr = 0
            gamma = 0.05

        rng = np.random.default_rng(47)
        for case in range(40):
            n_links = int(rng.integers(1, 7))
            seed = int(rng.integers(0, 1 << 30))
            a = _seeded_store(np.random.default_rng(seed), n_links)
            b = _seeded_store(np.random.default_rng(seed), n_links)
            m = int(rng.integers(1, 40))
            slots = rng.integers(0, n_links, size=m)
            lengths = rng.integers(0, 1 << 22, size=m)
            queued_at = rng.integers(0, 1 << 24, size=m)
            t_obs = rng.uniform(0.0, 5.0, size=m)
            a.on_complete_many(slots, lengths, queued_at, t_obs)
            EngineJitCore(_Policy(), b).on_complete_many(
                slots, lengths, queued_at, t_obs)
            for name in ("beta0_arr", "beta1_arr", "queued_arr",
                         "ewma_service_arr", "completions_arr"):
                x, y = getattr(a, name)[:a.n], getattr(b, name)[:b.n]
                assert (x == y).all(), f"case {case} {name}: {x} != {y}"

    def test_scratch_row_survives_padding(self):
        """Batch padding scatters into slot n; the write-back must discard
        it and leave rows 0..n-1 governed only by the real batch."""
        a = _seeded_store(np.random.default_rng(9), 3)
        b = _seeded_store(np.random.default_rng(9), 3)

        class _Policy:
            _rr = 0
            gamma = 0.05

        batch = ([0, 2, 2], [4096, 1 << 20, 0], [100, 5000, 0],
                 [0.25, 0.5, 0.75])
        a.on_complete_many(*(np.asarray(c) for c in batch))
        core = EngineJitCore(_Policy(), b)
        core.on_complete_many(*(np.asarray(c) for c in batch))
        assert (a.beta1_arr[:3] == b.beta1_arr[:3]).all()
        assert (a.queued_arr[:3] == b.queued_arr[:3]).all()
        assert core.drains == 1


class TestCrossoverTuner:
    def test_tune_tracks_the_wave_min_shape(self):
        store = _seeded_store(np.random.default_rng(1), 2)

        class _Policy:
            _rr = 0
            gamma = 0.05

        core = EngineJitCore(_Policy(), store)
        assert core.min_batch == jit_core.JIT_MIN
        core.tune(2.0 * jit_core.JIT_MIN)
        assert core.min_batch == jit_core.JIT_MIN_FLOOR
        core.tune(0.5 * jit_core.JIT_MIN)
        assert core.min_batch == jit_core.JIT_MIN_CEIL
        core.tune(1.2 * jit_core.JIT_MIN)
        assert core.min_batch == jit_core.JIT_MIN

    def test_jax_unavailable_falls_back_with_warning(self, monkeypatch):
        """jit_core requested in an environment without jax: the engine
        must warn once and run the numpy path, not crash."""
        monkeypatch.setattr(jit_core, "jax_available", lambda: False)
        spec = _with_jit(get("single_rail_flap"))
        with pytest.warns(RuntimeWarning, match="jax is unavailable"):
            on = _policies(spec)
        assert on == _policies(get("single_rail_flap"))
