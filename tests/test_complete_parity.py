"""Batched-completion-drain regression tier.

PR 4 vectorized the scheduling half of the closed loop; this tier pins the
completion half. The batched drain (`EngineConfig.wave_complete`: fabric
delivers same-timestamp completion runs in one call, telemetry EWMAs update
through `TelemetryStore.on_complete_many`, failure fan-out retries flush
through one batched post) must be a pure *cost* change: every scenario
outcome has to be bit-identical to the per-completion scalar drain. These
tests pin that end-to-end across the whole scenario library, pin the
batched EWMA update against the scalar loop with a no-optional-deps seeded
sweep (the hypothesis twin lives in tests/test_properties.py), and cover
the fabric's drain grouping plus the adaptive WAVE_MIN tuner.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    Fabric,
    FabricSpec,
    TelemetryStore,
    TentEngine,
    Topology,
)
from repro.core.engine import WAVE_MIN, WAVE_MIN_CEIL, WAVE_MIN_FLOOR
from repro.scenarios import SCENARIOS, ScenarioRunner, get

# observables of the drain/dispatch *mechanism* itself — legitimately
# mode-dependent (the scalar drain never forms batches, and the adaptive
# crossover feeds on batch sizes), unlike every data-plane metric
MODE_DEPENDENT_EXTRAS = ("waves", "completion_batches")


def _policies(spec) -> dict:
    doc = ScenarioRunner(spec).run().to_dict()
    for rep in doc["policies"].values():
        for key in MODE_DEPENDENT_EXTRAS:
            rep["extra"].pop(key, None)
    return doc["policies"]


class TestWaveCompleteBitIdentity:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_reports_identical_across_drain_toggle(self, name):
        """wave_complete on vs off over the full scenario library: every
        metric of every policy — byte counts, makespans, latency
        percentiles, retries, exclusions, per-rail byte maps, the
        completions-drained totals — must match exactly (same per-completion
        feedback => same decisions => same fabric event sequence)."""
        spec = get(name)
        on = _policies(spec)
        off = _policies(dataclasses.replace(
            spec,
            engine=dataclasses.replace(spec.engine, wave_complete=False)))
        assert on == off

    @pytest.mark.parametrize(
        "name", ["single_rail_flap", "elephant_mice_mix",
                 "degrade_recover_ramp"])
    def test_pinned_wave_min_keeps_reports_identical(self, name):
        """The crossover is a pure cost knob: pinning it to either extreme
        must not move a single report metric."""
        spec = get(name)
        base = _policies(spec)
        for pin in (1, WAVE_MIN_CEIL * 4):
            pinned = _policies(dataclasses.replace(
                spec, engine=dataclasses.replace(spec.engine, wave_min=pin)))
            assert pinned == base


# ---------------------------------------------------------------------------
# on_complete_many vs looped on_complete: seeded randomized sweep (runs with
# no optional deps so every environment checks the bit-equality; the
# hypothesis twin in tests/test_properties.py explores adversarially)
# ---------------------------------------------------------------------------


def _seeded_store(rng, n_links):
    from repro.core.topology import LinkDesc
    from repro.core.types import LinkClass

    store = TelemetryStore()
    for i in range(n_links):
        desc = LinkDesc(link_id=i, node=0, link_class=LinkClass.RDMA,
                        index=i, numa=0, bandwidth=float(rng.choice([25e9, 1e9])),
                        base_latency=5e-6)
        tl = store.ensure(desc)
        tl.queued_bytes = int(rng.integers(0, 1 << 30))
        tl.beta0 = float(rng.uniform(0.0, 1e-2))
        tl.beta1 = float(rng.uniform(0.05, 50.0))
        tl.ewma_service_time = float(rng.uniform(0.0, 1.0))
    return store


class TestOnCompleteManySweep:
    def test_batched_update_bit_equals_scalar_loop_randomized(self):
        rng = np.random.default_rng(11)
        arrs = ("beta0_arr", "beta1_arr", "queued_arr", "ewma_service_arr",
                "completions_arr")
        for case in range(300):
            n_links = int(rng.integers(1, 7))
            seed = int(rng.integers(0, 1 << 30))
            a = _seeded_store(np.random.default_rng(seed), n_links)
            b = _seeded_store(np.random.default_rng(seed), n_links)
            m = int(rng.integers(1, 40))
            # heavy slot repetition on purpose: EWMA order sensitivity
            slots = rng.integers(0, n_links, size=m)
            lengths = rng.integers(0, 1 << 22, size=m)
            queued_at = rng.integers(0, 1 << 24, size=m)
            t_obs = rng.uniform(0.0, 5.0, size=m)
            for k in range(m):
                a._views[int(slots[k])].on_complete(
                    int(lengths[k]), int(queued_at[k]), float(t_obs[k]))
            b.on_complete_many(slots, lengths, queued_at, t_obs)
            for name in arrs:
                x, y = getattr(a, name)[:a.n], getattr(b, name)[:b.n]
                assert (x == y).all(), f"case {case} {name}: {x} != {y}"

    def test_zero_normalized_load_skips_beta1(self):
        """x == 0 (empty queue, zero-length sample) must leave beta1 alone
        and still apply the beta0/ewma updates — exactly like the scalar
        guard."""
        rng = np.random.default_rng(3)
        a = _seeded_store(np.random.default_rng(5), 2)
        b = _seeded_store(np.random.default_rng(5), 2)
        batch = [(0, 0, 0, 0.25), (1, 4096, 64, 0.5), (0, 0, 0, 0.125)]
        for slot, L, qas, tob in batch:
            a._views[slot].on_complete(L, qas, tob)
        b.on_complete_many(*(np.asarray(col) for col in zip(*batch)))
        assert (a.beta1_arr[:2] == b.beta1_arr[:2]).all()
        assert (a.beta0_arr[:2] == b.beta0_arr[:2]).all()
        assert (a.ewma_service_arr[:2] == b.ewma_service_arr[:2]).all()
        del rng


# ---------------------------------------------------------------------------
# Fabric drain grouping mechanics
# ---------------------------------------------------------------------------


def _quiet_fabric(jitter=0.0):
    return Fabric(Topology(FabricSpec()), seed=0, jitter=jitter)


class TestFabricCompletionBatching:
    def test_same_timestamp_completions_arrive_as_one_batch(self):
        fab = _quiet_fabric()
        topo = fab.topology
        lids = [topo.rdma_nic(0, i).link_id for i in range(4)]
        batches = []

        def cb(*a):  # shared tagged callback object
            raise AssertionError("sink should swallow batched deliveries")

        fab.register_completion_sink(cb, lambda ops, now: batches.append(
            ([op.tag for op in ops], now)))
        # same nbytes on four idle identical links: identical end timestamps
        fab.post_many([(lid, None, 4096, 0.0, 1.0, i)
                       for i, lid in enumerate(lids)], cb)
        fab.run_until_idle()
        assert batches == [([0, 1, 2, 3], batches[0][1])]

    def test_distinct_timestamps_stay_separate_batches(self):
        fab = _quiet_fabric()
        lid = fab.topology.rdma_nic(0, 0).link_id
        batches = []

        def cb(*a):
            raise AssertionError

        fab.register_completion_sink(cb, lambda ops, now: batches.append(
            [op.tag for op in ops]))
        # both ops serialize on one link -> distinct ends -> two batches
        fab.post_many([(lid, None, 4096, 0.0, 1.0, "a"),
                       (lid, None, 4096, 0.0, 1.0, "b")], cb)
        fab.run_until_idle()
        assert batches == [["a"], ["b"]]

    def test_unregistered_callbacks_deliver_per_op(self):
        fab = _quiet_fabric()
        topo = fab.topology
        lids = [topo.rdma_nic(0, i).link_id for i in range(2)]
        got = []
        fab.post_many([(lid, None, 4096, 0.0, 1.0, i)
                       for i, lid in enumerate(lids)],
                      lambda tag, ok, t0, t1, err: got.append((tag, ok)))
        fab.run_until_idle()
        assert got == [(0, True), (1, True)]

    def test_batched_drain_marks_mid_failures(self):
        """An op whose link fails between posting and completion must arrive
        in the batch with failed=True (the engine's batched retry handler
        keys off it)."""
        fab = _quiet_fabric()
        topo = fab.topology
        good = topo.rdma_nic(0, 0).link_id
        bad = topo.rdma_nic(0, 1).link_id
        seen = []

        def cb(*a):
            raise AssertionError

        fab.register_completion_sink(
            cb, lambda ops, now: seen.extend((op.tag, op.failed) for op in ops))
        fab.post_many([(good, None, 4096, 0.0, 1.0, "ok"),
                       (bad, None, 4096, 0.0, 1.0, "dead")], cb)
        # window opens after posting, covering the bad op's whole service
        end = fab.links[bad].busy_until + 1.0
        fab.links[bad].fail_windows.append((0.0, end))
        fab.run_until_idle()
        assert ("ok", False) in seen
        assert ("dead", True) in seen


# ---------------------------------------------------------------------------
# Adaptive WAVE_MIN
# ---------------------------------------------------------------------------


def _host(node, numa=0):
    from repro.core import Location, MemoryKind

    return Location(node=node, kind=MemoryKind.HOST_DRAM, device=numa, numa=numa)


class TestAdaptiveWaveMin:
    def test_burst_lowers_crossover_to_floor(self):
        eng = TentEngine(
            FabricSpec(), config=EngineConfig(max_inflight=4096), seed=3)
        assert eng.wave_min == WAVE_MIN  # neutral until traffic is observed
        src = eng.register_segment(_host(0), 64 << 20, materialize=False)
        dst = eng.register_segment(_host(1), 64 << 20, materialize=False)
        assert eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 64 << 20).ok
        assert eng.wave_min == WAVE_MIN_FLOOR
        assert eng.waves >= 1

    def test_single_slice_trickle_raises_crossover_to_ceiling(self):
        eng = TentEngine(FabricSpec(), seed=3)
        src = eng.register_segment(_host(0), 4096, materialize=False)
        dst = eng.register_segment(_host(1), 4096, materialize=False)
        for _ in range(6):
            assert eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 4096).ok
        assert eng.wave_min == WAVE_MIN_CEIL
        assert eng.waves == 0  # trickle runs must stay on the scalar path

    def test_config_pin_disables_tuning(self):
        eng = TentEngine(
            FabricSpec(),
            config=EngineConfig(max_inflight=4096, wave_min=WAVE_MIN_CEIL),
            seed=3)
        src = eng.register_segment(_host(0), 64 << 20, materialize=False)
        dst = eng.register_segment(_host(1), 64 << 20, materialize=False)
        assert eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 64 << 20).ok
        assert eng.wave_min == WAVE_MIN_CEIL  # pinned, burst notwithstanding

    def test_phantom_transfer_still_bounds_checked(self):
        """Skipping the phantom byte copy in the drain loop must not lose
        bounds validation: out-of-range offsets now fail loudly at submit
        time (for phantom segments the completion-time read/write this
        replaced was the only check)."""
        eng = TentEngine(FabricSpec(), seed=0)
        src = eng.register_segment(_host(0), 1 << 20, materialize=False)
        dst = eng.register_segment(_host(1), 1 << 20, materialize=False)
        with pytest.raises(IndexError, match="out of bounds"):
            eng.transfer_sync(
                src.segment_id, 0, dst.segment_id, 1 << 20, 1 << 20)
        with pytest.raises(IndexError, match="out of bounds"):
            eng.transfer_sync(
                src.segment_id, 1, dst.segment_id, 0, 1 << 20)  # src side too

    def test_drain_batches_counted(self):
        eng = TentEngine(
            FabricSpec(), config=EngineConfig(max_inflight=4096), seed=3)
        src = eng.register_segment(_host(0), 8 << 20, materialize=False)
        dst = eng.register_segment(_host(1), 8 << 20, materialize=False)
        assert eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 8 << 20).ok
        assert eng.completions_drained == eng.slices_issued
        assert 1 <= eng.completion_batches <= eng.completions_drained


# ---------------------------------------------------------------------------
# Dispatch dirty-path regression (scalar substitution failure mid-wave with
# the failed batch's remaining slices spanning later runs)
# ---------------------------------------------------------------------------


class TestDirtyWaveCandidatelessRun:
    def _engine(self, wave: bool, monkeypatch):
        from repro.core import TentError
        from repro.core.types import Location, MemoryKind

        eng = TentEngine(
            FabricSpec(),
            config=EngineConfig(max_inflight=4096, wave=wave,
                                candidate_cache=wave),
            seed=0)
        # A: one intra-node host slice -> scalar run at the head of the wave.
        a_src = eng.register_segment(_host(0), 4096, materialize=False)
        a_dst = eng.register_segment(
            Location(node=0, kind=MemoryKind.HOST_DRAM, device=1, numa=1),
            4096, materialize=False)
        # B: cross-node elephant in the SAME batch, grouped behind A. Its
        # best route's stage gets an empty candidate set, so the run head
        # hits the `not sc.paths` fallback.
        b_src = eng.register_segment(_host(0), 8 << 20, materialize=False)
        b_dst = eng.register_segment(_host(1), 8 << 20, materialize=False)

        real_choose = eng.policy.choose
        monkeypatch.setattr(
            eng.policy, "choose",
            lambda cands, length: (_ for _ in ()).throw(
                TentError("NoEligibleDevice", "forced")) if length == 4096
            else real_choose(cands, length))
        # A (intra-node) cannot substitute -> its failure kills the batch;
        # B (cross-node) still has real fallback transports available
        from repro.core import TransportPlan
        real_sub = TransportPlan.substitute
        monkeypatch.setattr(
            TransportPlan, "substitute",
            lambda self: False if self.src.node == self.dst.node
            else real_sub(self))
        # empty B's rdma candidate set at *dispatch* time only (patching the
        # backend's `paths` would also zero `rank_bandwidth` and delete the
        # route at plan time, never reaching the `not sc.paths` branch)
        from repro.core import engine as engine_mod
        real_build = engine_mod.build_stage_candidates
        monkeypatch.setattr(
            engine_mod, "build_stage_candidates",
            lambda stage, backends, store, **kw: (
                lambda sc: dataclasses.replace(
                    sc, paths=[], cands=[], path_by_link={})
                if stage.backend == "rdma" else sc
            )(real_build(stage, backends, store, **kw)))
        return eng, (a_src, a_dst, b_src, b_dst)

    @pytest.mark.parametrize("wave", [True, False])
    def test_dead_batch_slices_never_reach_substitution(self, wave, monkeypatch):
        """Once a scalar substitution failure kills the batch mid-wave, a
        later run whose stage has no candidates must DROP the dead batch's
        slices — not hand them to the substitution path, which would post
        them on the next-best transport for an already-failed batch. The
        wave dispatcher must match the one-slice loop exactly."""
        eng, (a_src, a_dst, b_src, b_dst) = self._engine(wave, monkeypatch)
        b = eng.allocate_batch()
        eng.submit_transfer(b, [
            (a_src.segment_id, 0, a_dst.segment_id, 0, 4096),
            (b_src.segment_id, 0, b_dst.segment_id, 0, 8 << 20),
        ])
        state, _ = eng.get_transfer_status(b)
        assert state.value == "failed"
        eng.run_until_idle()
        assert eng.slices_issued == 0, \
            "dead batch slices were posted via backend substitution"
        assert eng.backend_substitutions == 0
        assert all(tl.queued_bytes == 0 for _, tl in eng.store.items())
        assert eng.fabric.bytes_by_tenant() == {}
