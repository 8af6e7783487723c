"""Property-based tests (hypothesis) on the system's invariants."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional dev dep (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import (
    Candidate,
    FabricSpec,
    Location,
    MemoryKind,
    TentEngine,
    TentPolicy,
    TransferRequest,
    decompose,
    tent_choose_jnp,
    tent_choose_wave,
    tent_choose_wave_jnp,
)
from repro.core.telemetry import LinkTelemetry, TelemetryStore
from repro.core.topology import LinkDesc
from repro.core.types import LinkClass

TIER_PENALTY = {1: 1.0, 2: 3.0}


def _mk_tl(link_id, bw=25e9, queued=0, beta0=0.0, beta1=1.0, excluded=False):
    desc = LinkDesc(link_id=link_id, node=0, link_class=LinkClass.RDMA,
                    index=link_id, numa=0, bandwidth=bw, base_latency=5e-6)
    tl = LinkTelemetry(desc=desc, beta0=beta0, beta0_prior=beta0, beta1=beta1)
    tl.queued_bytes = queued
    tl.excluded = excluded
    return tl


class TestSliceDecomposition:
    @given(
        length=st.integers(1, 1 << 30),
        src_off=st.integers(0, 1 << 20),
        dst_off=st.integers(0, 1 << 20),
        slice_bytes=st.sampled_from([4096, 65536, 1 << 20]),
        max_slices=st.sampled_from([1, 7, 64, 512]),
    )
    @settings(max_examples=200, deadline=None)
    def test_exact_tiling(self, length, src_off, dst_off, slice_bytes, max_slices):
        req = TransferRequest(
            transfer_id=1, src_segment=1, src_offset=src_off,
            dst_segment=2, dst_offset=dst_off, length=length,
        )
        slices = decompose(req, 1, slice_bytes=slice_bytes, max_slices=max_slices)
        # count bound
        assert 1 <= len(slices) <= max_slices
        # exact, ordered, non-overlapping tiling of [0, length)
        cur_src, cur_dst = src_off, dst_off
        for sl in slices:
            assert sl.src_offset == cur_src and sl.dst_offset == cur_dst
            assert sl.length > 0
            # src/dst offset correspondence preserved
            assert sl.src_offset - src_off == sl.dst_offset - dst_off
            cur_src += sl.length
            cur_dst += sl.length
        assert cur_src - src_off == length


class TestSchedulerInvariants:
    @given(
        queues=st.lists(st.integers(0, 1 << 30), min_size=2, max_size=8),
        tiers=st.lists(st.sampled_from([1, 2]), min_size=2, max_size=8),
        length=st.integers(1, 1 << 24),
        gamma=st.floats(0.0, 0.5),
    )
    @settings(max_examples=200, deadline=None)
    def test_choice_is_within_tolerance_window(self, queues, tiers, length, gamma):
        n = min(len(queues), len(tiers))
        cands = [Candidate(_mk_tl(i, queued=queues[i]), tiers[i]) for i in range(n)]
        policy = TentPolicy(gamma=gamma)
        chosen = policy.choose(cands, length)
        # recompute scores as they were at choice time (chosen was charged)
        scores = []
        for c in cands:
            q = c.telemetry.queued_bytes - (length if c is chosen else 0)
            t_hat = c.telemetry.beta0 + c.telemetry.beta1 * (q + length) / c.telemetry.desc.bandwidth
            scores.append({1: 1.0, 2: 3.0}[c.tier] * t_hat)
        s_min = min(scores)
        s_chosen = scores[cands.index(chosen)]
        assert s_chosen <= (1 + gamma) * s_min * (1 + 1e-9)

    @given(
        queues=st.lists(st.integers(0, 1 << 28), min_size=2, max_size=8),
        length=st.integers(1, 1 << 22),
    )
    @settings(max_examples=100, deadline=None)
    def test_queue_accounting_monotonic(self, queues, length):
        cands = [Candidate(_mk_tl(i, queued=q), 1) for i, q in enumerate(queues)]
        policy = TentPolicy()
        before = sum(c.telemetry.queued_bytes for c in cands)
        policy.choose(cands, length)
        after = sum(c.telemetry.queued_bytes for c in cands)
        assert after == before + length  # Algorithm 1 line 11

    @given(
        queues=st.lists(st.integers(0, 1 << 28), min_size=2, max_size=8),
        length=st.integers(1, 1 << 22),
        rr=st.integers(0, 100),
    )
    @settings(max_examples=50, deadline=None)
    def test_jnp_scorer_matches_python(self, queues, length, rr):
        import jax.numpy as jnp

        n = len(queues)
        cands = [Candidate(_mk_tl(i, queued=q), 1) for i, q in enumerate(queues)]
        policy = TentPolicy()
        s_py = policy.scores(cands, length)
        idx = tent_choose_jnp(
            jnp.asarray(queues, jnp.float32), jnp.full((n,), 25e9, jnp.float32),
            jnp.zeros((n,)), jnp.ones((n,)), jnp.ones((n,)), float(length), rr,
        )
        # the jnp choice must land inside the python tolerance window
        s_min = min(s_py)
        assert s_py[int(idx)] <= 1.05 * s_min * (1 + 1e-6)

    @given(
        queues=st.lists(st.integers(0, 1 << 28), min_size=2, max_size=8),
        length=st.integers(1, 1 << 22),
        tier=st.integers(1, 3),
    )
    @settings(max_examples=50, deadline=None)
    def test_jnp_scores_bitexact_vs_policy_scores(self, queues, length, tier):
        """tent_scores_jnp under x64 must reproduce TentPolicy.scores
        bit-exactly (same operation order, same roundings)."""
        import jax.numpy as jnp
        from repro.core.jit_core import x64

        from repro.core.scheduler import tent_scores_jnp
        from repro.core.topology import DEFAULT_TIER_PENALTY

        n = len(queues)
        cands = [Candidate(_mk_tl(i, queued=q), tier) for i, q in enumerate(queues)]
        s_py = TentPolicy().scores(cands, length)
        pen = DEFAULT_TIER_PENALTY[tier]
        with x64():
            s_jnp = tent_scores_jnp(
                jnp.asarray(queues, jnp.float64),
                jnp.full((n,), 25e9, jnp.float64),
                jnp.zeros((n,), jnp.float64), jnp.ones((n,), jnp.float64),
                jnp.full((n,), pen, jnp.float64), float(length),
            )
            np.testing.assert_array_equal(np.asarray(s_jnp), np.asarray(s_py))


def _wave_state(draw_queues, tiers, excluded, beta0s, beta1s, global_load, weight):
    """Build one TelemetryStore + candidate list from hypothesis data. Every
    candidate gets a paired remote link (ids offset by 100) so the remote
    pressure/remote exclusion paths are exercised."""
    n = min(len(draw_queues), len(tiers), len(excluded), len(beta0s), len(beta1s))
    store = TelemetryStore()
    cands = []
    for i in range(n):
        desc = LinkDesc(link_id=i, node=0, link_class=LinkClass.RDMA,
                        index=i, numa=0, bandwidth=25e9, base_latency=5e-6)
        rdesc = LinkDesc(link_id=100 + i, node=1, link_class=LinkClass.RDMA,
                         index=i, numa=0, bandwidth=25e9, base_latency=5e-6)
        tl = store.ensure(desc)
        rtl = store.ensure(rdesc)
        tl.queued_bytes = draw_queues[i]
        tl.beta0 = beta0s[i]
        tl.beta1 = beta1s[i]
        tl.excluded = excluded[i]
        # remote exclusions (failure rumors from peers) knock paths out too
        rtl.excluded = excluded[(i + 1) % len(excluded)] and excluded[i - 1]
        cands.append(Candidate(tl, tiers[i], remote=rtl))
    store.global_weight = weight
    store.global_load = {
        lid % (100 + n): q for lid, q in global_load.items()}
    return store, cands


class TestWaveParity:
    """The scalar chooser and the vectorized wave kernels must pick the
    same rail — bit-identical scores, window membership, round-robin tie
    breaks, and sequential line-11 charges — across randomized telemetry
    states including exclusions and omega-blended global load."""

    @given(
        queues=st.lists(st.integers(0, 1 << 30), min_size=2, max_size=8),
        tiers=st.lists(st.sampled_from([1, 2]), min_size=8, max_size=8),
        excluded=st.lists(st.booleans(), min_size=8, max_size=8),
        beta0s=st.lists(st.floats(0.0, 1e-2), min_size=8, max_size=8),
        beta1s=st.lists(st.floats(0.05, 50.0), min_size=8, max_size=8),
        global_load=st.dictionaries(st.integers(0, 120), st.integers(0, 1 << 28),
                                    max_size=6),
        weight=st.sampled_from([0.0, 0.5, 0.6]),
        lengths=st.lists(st.integers(1, 1 << 22), min_size=1, max_size=24),
        rr0=st.integers(0, 50),
        gamma=st.sampled_from([0.0, 0.05, 0.3]),
    )
    @settings(max_examples=120, deadline=None)
    def test_numpy_wave_kernel_replays_scalar_choose(
            self, queues, tiers, excluded, beta0s, beta1s, global_load,
            weight, lengths, rr0, gamma):
        args = (queues, tiers, excluded, beta0s, beta1s, global_load, weight)
        store_a, cands_a = _wave_state(*args)
        store_b, cands_b = _wave_state(*args)
        n = len(cands_a)

        # scalar replay: one choose() per slice, charging as it goes
        policy = TentPolicy(gamma=gamma, store=store_a,
                            tier_penalty=dict(TIER_PENALTY))
        policy._rr = rr0
        scalar_choices = [
            cands_a.index(policy.choose(cands_a, L)) for L in lengths]

        # vectorized replay over the identical twin state
        choices, queued_at, queued_out, rr_out = tent_choose_wave(
            np.asarray([c.telemetry.queued_bytes for c in cands_b]),
            np.asarray([weight * store_b._foreign_load(c.telemetry.desc.link_id)
                        if weight > 0 else 0.0 for c in cands_b]),
            np.asarray([weight * store_b._foreign_load(c.remote.desc.link_id)
                        if weight > 0 else 0.0 for c in cands_b]),
            np.asarray([c.telemetry.desc.bandwidth for c in cands_b]),
            np.asarray([float(c.telemetry.beta0) for c in cands_b]),
            np.asarray([float(c.telemetry.beta1) for c in cands_b]),
            np.asarray([TIER_PENALTY[c.tier] for c in cands_b]),
            np.asarray([bool(c.telemetry.excluded) or bool(c.remote.excluded)
                        for c in cands_b]),
            np.asarray(lengths), rr0, gamma)

        assert list(choices) == scalar_choices
        assert rr_out == policy._rr
        for i in range(n):  # line-11 charges identical after the wave
            assert queued_out[i] == cands_a[i].telemetry.queued_bytes
        # queued_at_schedule (the EWMA anchor) matches the scalar reads
        replay = [int(q) for q in
                  np.asarray([c.telemetry.queued_bytes for c in cands_b])]
        for k, (c, L) in enumerate(zip(choices, lengths)):
            replay[c] += L
            assert queued_at[k] == replay[c]

    @given(
        queues=st.lists(st.integers(0, 1 << 28), min_size=2, max_size=8),
        tiers=st.lists(st.sampled_from([1, 2]), min_size=8, max_size=8),
        excluded=st.lists(st.booleans(), min_size=8, max_size=8),
        length=st.integers(1, 1 << 22),
        rr=st.integers(0, 100),
        gamma=st.sampled_from([0.0, 0.05, 0.3]),
    )
    @settings(max_examples=60, deadline=None)
    def test_jnp_choose_matches_scalar_incl_exclusions_and_ties(
            self, queues, tiers, excluded, length, rr, gamma):
        """tent_choose_jnp under x64 must land on the exact rail the scalar
        policy picks — including soft-excluded rails, the all-excluded
        fallback, and round-robin selection inside the gamma window."""
        from repro.core.jit_core import x64

        n = min(len(queues), len(tiers))
        cands = [Candidate(_mk_tl(i, queued=queues[i], excluded=excluded[i]),
                           tiers[i]) for i in range(n)]
        policy = TentPolicy(gamma=gamma, tier_penalty=dict(TIER_PENALTY))
        policy._rr = rr
        chosen = policy.choose(cands, length)
        scalar_idx = cands.index(chosen)
        with x64():
            idx = tent_choose_jnp(
                np.asarray(queues[:n], dtype=np.float64),
                np.full(n, 25e9), np.zeros(n), np.ones(n),
                np.asarray([TIER_PENALTY[t] for t in tiers[:n]]),
                float(length), rr, gamma,
                excluded=np.asarray(excluded[:n]))
        assert int(idx) == scalar_idx

    @given(
        queues=st.lists(st.integers(0, 1 << 28), min_size=2, max_size=8),
        excluded=st.lists(st.booleans(), min_size=8, max_size=8),
        lengths=st.lists(st.integers(1, 1 << 22), min_size=1, max_size=12),
        rr0=st.integers(0, 50),
        gamma=st.sampled_from([0.0, 0.05]),
    )
    @settings(max_examples=40, deadline=None)
    def test_jnp_wave_kernel_matches_numpy_kernel(
            self, queues, excluded, lengths, rr0, gamma):
        from repro.core.jit_core import x64

        n = len(queues)
        bw = np.full(n, 25e9)
        b0, b1 = np.zeros(n), np.ones(n)
        pen = np.ones(n)
        ex = np.asarray(excluded[:n])
        zeros = np.zeros(n)
        np_c, np_qas, np_q, np_rr = tent_choose_wave(
            np.asarray(queues), zeros, zeros, bw, b0, b1, pen, ex,
            np.asarray(lengths), rr0, gamma)
        with x64():
            j_c, j_qas, j_q, j_rr = tent_choose_wave_jnp(
                np.asarray(queues, dtype=np.float64), zeros, zeros, bw,
                b0, b1, pen, ex, np.asarray(lengths), rr0, gamma)
            # materialize inside the x64 scope (x64 arrays cannot be
            # unstacked once the flag reverts)
            j_c, j_qas, j_q = np.asarray(j_c), np.asarray(j_qas), np.asarray(j_q)
            j_rr = int(j_rr)
        assert list(np_c) == [int(v) for v in j_c]
        assert list(np_qas) == [int(v) for v in j_qas]
        assert list(np_q) == [int(v) for v in j_q]
        assert j_rr == np_rr


def _paired_stores(n_links, queues, beta0s, beta1s):
    """Two identical stores: one takes the scalar per-completion path, the
    other the batched path; every array must come out bit-equal."""
    out = []
    for _ in range(2):
        store = TelemetryStore()
        for i in range(n_links):
            desc = LinkDesc(link_id=i, node=0, link_class=LinkClass.RDMA,
                            index=i, numa=0, bandwidth=25e9, base_latency=5e-6)
            tl = store.ensure(desc)
            tl.queued_bytes = queues[i % len(queues)]
            tl.beta0 = beta0s[i % len(beta0s)]
            tl.beta1 = beta1s[i % len(beta1s)]
        out.append(store)
    return out


_COMPLETE_ARRS = ("beta0_arr", "beta1_arr", "queued_arr", "ewma_service_arr",
                  "completions_arr", "slow_arr", "failures_arr")


class TestCompleteManyParity:
    """`TelemetryStore.on_complete_many` must be **exactly** (bit-for-bit)
    equal to looping `on_complete` over the batch — including repeated slots
    within one batch, where the per-slot EWMA recurrence is order-sensitive
    and the batched path must replay occurrences sequentially."""

    @given(
        n_links=st.integers(1, 6),
        queues=st.lists(st.integers(0, 1 << 30), min_size=1, max_size=6),
        beta0s=st.lists(st.floats(0.0, 1e-2), min_size=1, max_size=6),
        beta1s=st.lists(st.floats(0.05, 50.0), min_size=1, max_size=6),
        batch=st.lists(
            st.tuples(st.integers(0, 5),           # slot (repeats likely)
                      st.integers(0, 1 << 22),     # length (0 hits x == 0)
                      st.integers(0, 1 << 24),     # queued_at_schedule
                      st.floats(0.0, 10.0)),       # t_obs
            min_size=1, max_size=32),
    )
    @settings(max_examples=150, deadline=None)
    def test_on_complete_many_bit_equals_scalar_loop(
            self, n_links, queues, beta0s, beta1s, batch):
        scalar, batched = _paired_stores(n_links, queues, beta0s, beta1s)
        items = [(slot % n_links, L, qas, tob) for slot, L, qas, tob in batch]
        for slot, L, qas, tob in items:
            scalar._views[slot].on_complete(L, qas, tob)
        batched.on_complete_many(
            np.asarray([i[0] for i in items], dtype=np.int64),
            np.asarray([i[1] for i in items], dtype=np.int64),
            np.asarray([i[2] for i in items], dtype=np.int64),
            np.asarray([i[3] for i in items], dtype=np.float64))
        for name in _COMPLETE_ARRS:
            a = getattr(scalar, name)[:scalar.n]
            b = getattr(batched, name)[:batched.n]
            assert (a == b).all(), f"{name}: {a} != {b}"


class TestEwmaBounded:
    @given(
        obs=st.lists(st.floats(1e-7, 10.0), min_size=1, max_size=50),
        length=st.integers(1, 1 << 24),
    )
    @settings(max_examples=100, deadline=None)
    def test_beta_stays_positive_finite(self, obs, length):
        tl = _mk_tl(0)
        for t_obs in obs:
            tl.on_schedule(length)
            tl.on_complete(length, tl.queued_bytes + length, t_obs)
            assert np.isfinite(tl.beta0) and tl.beta0 >= 0
            assert np.isfinite(tl.beta1) and 0.05 <= tl.beta1 <= 1e4
            assert tl.queued_bytes >= 0
        tl.reset()
        assert tl.beta1 == 1.0 and tl.beta0 == tl.beta0_prior


class TestEndToEndIntegrity:
    @given(
        length=st.integers(1, 4 << 20),
        src_off=st.integers(0, 1 << 16),
        dst_off=st.integers(0, 1 << 16),
        seed=st.integers(0, 2 ** 16),
        policy=st.sampled_from(["tent", "round_robin", "static_best2", "pinned"]),
    )
    @settings(max_examples=20, deadline=None)
    def test_bytes_conserved_any_policy(self, length, src_off, dst_off, seed, policy):
        from repro.core import EngineConfig

        eng = TentEngine(FabricSpec(), config=EngineConfig(policy=policy), seed=seed)
        size = length + max(src_off, dst_off) + 1
        src = eng.register_segment(Location(node=0, kind=MemoryKind.HOST_DRAM), size)
        dst = eng.register_segment(Location(node=1, kind=MemoryKind.HOST_DRAM), size)
        payload = np.random.default_rng(seed).integers(0, 256, length, dtype=np.uint8)
        src.write(src_off, payload)
        res = eng.transfer_sync(src.segment_id, src_off, dst.segment_id, dst_off, length)
        assert res.ok
        np.testing.assert_array_equal(dst.read(dst_off, length), payload)
        # fabric conservation: rdma bytes moved >= payload (retries may add)
        moved = sum(
            l.bytes_completed for l in eng.fabric.links.values()
            if l.desc.node == 0 and l.desc.link_class.value in ("rdma", "tcp")
        )
        assert moved >= length


class TestJitCoreKernelParity:
    """The fused Monte Carlo spray kernel in `repro.core.jit_core` vs its
    sequential numpy twin, over hypothesis-randomized seeds."""

    @given(
        seed_index=st.integers(0, 2 ** 16),
        policy=st.sampled_from(["tent", "round_robin"]),
        fault_jitter=st.sampled_from([0.0, 0.25, 0.5]),
    )
    @settings(max_examples=10, deadline=None)
    def test_fused_sim_matches_numpy_ref(self, seed_index, policy,
                                         fault_jitter):
        """The fused lax.scan spray simulate vs its sequential numpy twin
        on the flap program: one compiled shape, randomized seeds/jitter,
        every scalar output bit-equal."""
        from repro.core import jit_core
        from repro.scenarios import get
        from repro.scenarios.sweep import compile_spray_program

        spec = get("single_rail_flap")
        program = compile_spray_program(spec)
        draws = jit_core.make_draws(program, base_seed=spec.seed,
                                    seed_index=seed_index)
        ref = jit_core.simulate_spray_ref(
            program, draws, policy=policy, fault_jitter=fault_jitter)
        got = jit_core.spray_single(
            program, base_seed=spec.seed, seed_index=seed_index,
            policy=policy, fault_jitter=fault_jitter)
        assert tuple(ref) == tuple(got)


class TestFabricEventOrder:
    """Hypothesis twin of tests/test_fabric_order.py: the fabric's heap
    loop fires `call_at` callbacks in (time, post order), with times behind
    the clock clamped to `now`, across interleaved `run_until` drains."""

    @given(
        rounds=st.lists(
            st.tuples(
                st.lists(st.floats(-0.01, 0.05, allow_nan=False),
                         min_size=0, max_size=8),
                st.floats(0.0, 0.05, allow_nan=False)),
            min_size=1, max_size=20),
        tie_every=st.integers(1, 8),
    )
    @settings(max_examples=100, deadline=None)
    def test_fires_in_time_then_post_order(self, rounds, tie_every):
        from repro.core import Fabric, Topology

        fab = Fabric(Topology(FabricSpec(n_nodes=2)), seed=0)
        fired, pending = [], []
        seq, last = 0, 0.0
        for offsets, advance in rounds:
            for d in offsets:
                # every tie_every-th post reuses the previous timestamp
                t = last if seq % tie_every == 0 and seq else fab.now + d
                last = t
                pending.append((max(t, fab.now), seq))
                fab.call_at(t, lambda k=seq: fired.append(k))
                seq += 1
            horizon = fab.now + advance
            due = sorted(e for e in pending if e[0] <= horizon)
            pending = [e for e in pending if e[0] > horizon]
            before = len(fired)
            fab.run_until(horizon)
            assert fired[before:] == [k for _, k in due]
        before = len(fired)
        fab.run_until_idle()
        assert fired[before:] == [k for _, k in sorted(pending)]
        assert fab.idle
