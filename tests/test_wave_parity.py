"""Wave-scheduler and array-store regression tier.

The vectorized hot path (array-backed `TelemetryStore`, cached
`StageCandidates`, `TentPolicy.choose_wave`, batched fabric posts) must be a
pure *cost* change: every scheduling decision, queue charge, and fabric
event has to be bit-identical to the pre-wave one-slice loop, which stays in
the engine as the scalar fallback (`EngineConfig.wave=False,
candidate_cache=False`). These tests pin that equivalence end-to-end, plus
the struct-of-arrays store's view/dict round-trips through the cluster
hooks (`apply_global` / `clear_global`)."""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    FabricSpec,
    LinkTelemetry,
    TelemetryStore,
    TentEngine,
    Topology,
)
from repro.core.engine import WAVE_MIN
from repro.scenarios import SCENARIOS, ScenarioRunner, get
from scenario_sizes import sized


# ---------------------------------------------------------------------------
# Array-backed store: view round-trips and the cluster write hooks
# ---------------------------------------------------------------------------


def _store_with_links(n=4):
    store = TelemetryStore()
    topo = Topology(FabricSpec())
    return store, [store.ensure(l) for l in topo.links[:n]]


class TestArrayStore:
    def test_view_writes_land_in_arrays_and_back(self):
        store, (tl, *_) = _store_with_links(1)
        tl.queued_bytes = 123
        tl.beta1 = 2.5
        tl.excluded = True
        tl.consecutive_slow = 2
        slot = tl.slot
        assert store.queued_arr[slot] == 123
        assert store.beta1_arr[slot] == 2.5
        assert bool(store.excluded_arr[slot]) is True
        store.queued_arr[slot] = 77
        store.excluded_arr[slot] = False
        assert tl.queued_bytes == 77 and tl.excluded is False
        assert tl.consecutive_slow == 2

    def test_slot_map_stable_across_growth(self):
        """Slots must never move: StageCandidates caches them forever."""
        store = TelemetryStore()
        topo = Topology(FabricSpec(n_nodes=4))  # enough links to force regrowth
        views = [store.ensure(l) for l in topo.links]
        views[0].queued_bytes = 11
        views[3].beta0 = 0.5
        for v in views:  # registration grew the arrays several times
            assert store.slot_of(v.desc.link_id) == v.slot
        assert views[0].queued_bytes == 11
        assert views[3].beta0 == 0.5
        assert store.n == len(topo.links)

    def test_standalone_view_has_private_store(self):
        topo = Topology(FabricSpec())
        a = LinkTelemetry(desc=topo.links[0], beta0=0.1)
        b = LinkTelemetry(desc=topo.links[0], beta0=0.9)
        a.queued_bytes = 5
        assert b.queued_bytes == 0  # no shared arrays between standalone views
        assert a.beta0 == 0.1 and b.beta0 == 0.9

    def test_apply_global_clear_global_round_trip(self):
        store, (tl, *_) = _store_with_links(2)
        store.global_weight = 0.5
        lid = tl.desc.link_id
        store.apply_global({lid: 400})
        assert store.effective_queue(tl) == 0 + 0.5 * 400
        assert store.remote_pressure(lid) == 200.0
        tl.queued_bytes = 100  # array write must compose with the dict view
        assert store.effective_queue(tl) == 100 + 0.5 * 400
        store.clear_global()
        assert store.global_load == {}
        assert store.effective_queue(tl) == 100.0
        assert store.remote_pressure(lid) == 0.0
        # re-apply after clear: a rejoining engine starts clean
        store.apply_global({lid: 64})
        assert store.remote_pressure(lid) == 32.0

    def test_snapshot_reads_queue_array(self):
        store, (a, b, c, _) = _store_with_links(4)
        a.queued_bytes = 10
        c.on_schedule(7)
        store.charge_remote(999, 5)
        snap = store.snapshot()
        assert snap == {a.desc.link_id: 10, c.desc.link_id: 7, 999: 5}
        assert all(isinstance(v, int) for v in snap.values())

    def test_excluded_link_ids_vectorized_scan(self):
        store, views = _store_with_links(4)
        views[1].excluded = True
        views[3].excluded = True
        assert store.excluded_link_ids() == [
            views[1].desc.link_id, views[3].desc.link_id]

    def test_reset_all_vectorized(self):
        store, views = _store_with_links(3)
        for v in views:
            v.beta1 = 9.0
            v.consecutive_slow = 5
        store.reset_all()
        for v in views:
            assert v.beta1 == 1.0
            assert v.consecutive_slow == 0
            assert v.beta0 == v.beta0_prior


# ---------------------------------------------------------------------------
# Kernel parity: seeded randomized sweep (the hypothesis twin of this test
# in tests/test_properties.py explores adversarially; this one runs with no
# optional deps so every environment checks the equivalence)
# ---------------------------------------------------------------------------


class TestKernelParitySweep:
    def test_wave_kernel_replays_scalar_choose_randomized(self):
        from repro.core import Candidate, TentPolicy
        from repro.core.scheduler import tent_choose_wave
        from repro.core.topology import LinkDesc
        from repro.core.types import LinkClass

        rng = np.random.default_rng(7)
        tier_penalty = {1: 1.0, 2: 3.0}
        for case in range(200):
            n = int(rng.integers(2, 9))
            queues = rng.integers(0, 1 << 30, size=n)
            tiers = rng.choice([1, 2], size=n)
            excluded = rng.random(size=n) < 0.25
            beta0s = rng.uniform(0.0, 1e-2, size=n)
            beta1s = rng.uniform(0.05, 50.0, size=n)
            weight = float(rng.choice([0.0, 0.6]))
            lengths = rng.integers(1, 1 << 22, size=int(rng.integers(1, 25)))
            rr0 = int(rng.integers(0, 50))
            gamma = float(rng.choice([0.0, 0.05, 0.3]))

            def build():
                store = TelemetryStore()
                cands = []
                for i in range(n):
                    desc = LinkDesc(link_id=i, node=0, link_class=LinkClass.RDMA,
                                    index=i, numa=0, bandwidth=25e9,
                                    base_latency=5e-6)
                    tl = store.ensure(desc)
                    tl.queued_bytes = int(queues[i])
                    tl.beta0 = beta0s[i]
                    tl.beta1 = beta1s[i]
                    tl.excluded = bool(excluded[i])
                    cands.append(Candidate(tl, int(tiers[i])))
                store.global_weight = weight
                store.global_load = {i: int(queues[(i + 1) % n]) for i in range(n)}
                return store, cands

            store_a, cands_a = build()
            store_b, cands_b = build()
            policy = TentPolicy(gamma=gamma, store=store_a,
                                tier_penalty=dict(tier_penalty))
            policy._rr = rr0
            scalar = [cands_a.index(policy.choose(cands_a, int(L)))
                      for L in lengths]
            choices, queued_at, queued_out, rr_out = tent_choose_wave(
                queues,
                np.asarray([weight * store_b._foreign_load(i) if weight > 0
                            else 0.0 for i in range(n)]),
                np.zeros(n),
                np.full(n, 25e9), beta0s, beta1s,
                np.asarray([tier_penalty[t] for t in tiers]),
                excluded, lengths, rr0, gamma)
            assert list(choices) == scalar, f"case {case}"
            assert rr_out == policy._rr, f"case {case}"
            assert [int(c.telemetry.queued_bytes) for c in cands_a] == \
                [int(v) for v in queued_out], f"case {case}"


# ---------------------------------------------------------------------------
# Wave vs scalar engine: bit-identical scenario outcomes
# ---------------------------------------------------------------------------


def _policies(spec) -> dict:
    doc = ScenarioRunner(spec).run().to_dict()
    for rep in doc["policies"].values():
        # wave count is the one legitimately mode-dependent observable
        rep["extra"].pop("waves", None)
    return doc["policies"]


class TestWaveScalarBitIdentity:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_reports_identical(self, name):
        """Same spec, wave on vs the pre-wave loop, over the whole scenario
        library: every metric — byte counts, makespans, latency
        percentiles, retries, per-rail byte maps — must match exactly (same
        decisions => same fabric event sequence). Covers the
        retry/exclusion interleave (flap), the omega-blend cluster path
        (kv_incast) and every serving and checkpoint workload."""
        spec = sized(get(name))
        wave = _policies(spec)
        scalar = _policies(dataclasses.replace(
            spec,
            engine=dataclasses.replace(
                spec.engine, wave=False, candidate_cache=False)))
        assert wave == scalar


# ---------------------------------------------------------------------------
# Wave dispatch mechanics
# ---------------------------------------------------------------------------


def _host(node, numa=0):
    from repro.core import Location, MemoryKind

    return Location(node=node, kind=MemoryKind.HOST_DRAM, device=numa, numa=numa)


class TestWaveDispatch:
    def test_elephant_burst_uses_waves(self):
        eng = TentEngine(
            FabricSpec(), config=EngineConfig(max_inflight=4096), seed=3)
        src = eng.register_segment(_host(0), 64 << 20, materialize=False)
        dst = eng.register_segment(_host(1), 64 << 20, materialize=False)
        res = eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 64 << 20)
        assert res.ok
        assert eng.waves >= 1
        assert eng.slices_issued >= 512  # decomposed elephant all issued

    def test_small_runs_take_scalar_path(self):
        """Below WAVE_MIN the dispatcher must not pay kernel setup: a
        single-slice transfer schedules without a wave."""
        eng = TentEngine(FabricSpec(), seed=3)
        src = eng.register_segment(_host(0), 4096, materialize=False)
        dst = eng.register_segment(_host(1), 4096, materialize=False)
        res = eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 4096)
        assert res.ok
        assert eng.waves == 0
        assert eng.slices_issued == 1
        assert WAVE_MIN > 1

    def test_stage_cache_shared_across_transfers(self):
        eng = TentEngine(FabricSpec(), seed=3)
        src = eng.register_segment(_host(0), 8 << 20, materialize=False)
        dst = eng.register_segment(_host(1), 8 << 20, materialize=False)
        for _ in range(3):
            res = eng.transfer_sync(src.segment_id, 0, dst.segment_id, 0, 8 << 20)
            assert res.ok
        # one direct rdma stage, cached once, reused by all three transfers
        assert len(eng._stage_cache) == 1
        sc = next(iter(eng._stage_cache.values()))
        assert sc.path_by_link.keys() == {p.local.link_id for p in sc.paths}

    def test_tagged_post_many_delivers_failures(self):
        """Batched posts on a failed link must surface per-op tagged error
        completions (the engine's retry path depends on them)."""
        from repro.core import Fabric

        topo = Topology(FabricSpec())
        fab = Fabric(topo, seed=0)
        lid = topo.rdma_nic(0, 0).link_id
        fab.schedule_failure(lid, at=0.0, recover_at=1.0)
        fab.run_until(0.0)
        got = []
        fab.post_many(
            [(lid, None, 1024, 0.0, 1.0, "a"), (lid, None, 2048, 0.0, 1.0, "b")],
            lambda tag, ok, t0, t1, err: got.append((tag, ok, err)),
        )
        fab.run_until_idle()
        assert got == [("a", False, "LinkFailed"), ("b", False, "LinkFailed")]

    def test_wave_counters_surface_in_reports(self):
        rep = ScenarioRunner(get("uniform_spray")).run_policy("tent")
        assert rep.extra["slices_issued"] > 0
        assert rep.extra["waves"] >= 0

    def test_mid_wave_batch_failure_drops_remaining_runs(self, monkeypatch):
        """If an earlier run of a wave fails its batch (scalar issue with
        substitution exhausted), later runs must drop that batch's slices
        exactly like the one-slice loop's pop-time check — no posts, no
        queue charges for a dead batch."""
        from repro.core import TentError, TransportPlan
        from repro.core.types import Location, MemoryKind

        eng = TentEngine(
            FabricSpec(), config=EngineConfig(max_inflight=4096), seed=0)
        # transfer A: 1 host slice (scalar run); B: 128-slice GPU elephant
        # on a different stage, grouped into the same wave behind A
        a_src = eng.register_segment(_host(0), 4096, materialize=False)
        a_dst = eng.register_segment(_host(1), 4096, materialize=False)
        gpu0 = Location(node=0, kind=MemoryKind.DEVICE_HBM, device=0, numa=0)
        gpu1 = Location(node=0, kind=MemoryKind.DEVICE_HBM, device=5, numa=1)
        b_src = eng.register_segment(gpu0, 8 << 20, materialize=False)
        b_dst = eng.register_segment(gpu1, 8 << 20, materialize=False)

        real_choose = eng.policy.choose
        monkeypatch.setattr(
            eng.policy, "choose",
            lambda cands, length: (_ for _ in ()).throw(
                TentError("NoEligibleDevice", "forced")) if length == 4096
            else real_choose(cands, length))
        monkeypatch.setattr(TransportPlan, "substitute", lambda self: False)

        b = eng.allocate_batch()
        eng.submit_transfer(b, [
            (a_src.segment_id, 0, a_dst.segment_id, 0, 4096),
            (b_src.segment_id, 0, b_dst.segment_id, 0, 8 << 20),
        ])
        state, _ = eng.get_transfer_status(b)
        assert state.value == "failed"
        assert eng.slices_issued == 0  # B's wave never posted
        assert eng.waves == 0
        assert all(tl.queued_bytes == 0 for _, tl in eng.store.items())
