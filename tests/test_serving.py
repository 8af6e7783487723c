"""Serving-substrate integration tests: HiCache tiering over TENT, the
checkpoint engine, and real-compute disaggregated generation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import EngineConfig, FabricSpec, TentEngine
from repro.models import init_params
from repro.serving import (
    CheckpointEngine,
    DisaggregatedServer,
    HiCache,
    ServeSimConfig,
    ServingSimulator,
    from_table2,
    kv_bytes_per_token,
    make_cpu_pool,
    make_disk_pool,
    make_gpu_pool,
    monolithic_generate,
)
from repro.serving.disagg import prefill_jit, tree_to_bytes
from repro.training import flatten_state


def _hicache(engine, cfg, *, gpu_pages=8, cpu_pages=32, disk_pages=64, page_tokens=16):
    pb = kv_bytes_per_token(cfg) * page_tokens
    return HiCache(
        engine,
        cfg,
        gpu_pool=make_gpu_pool(engine, 0, 0, page_bytes=pb, num_pages=gpu_pages),
        cpu_pool=make_cpu_pool(engine, 1, page_bytes=pb, num_pages=cpu_pages),
        disk_pool=make_disk_pool(engine, 1, page_bytes=pb, num_pages=disk_pages),
        page_tokens=page_tokens,
    )


class TestHiCache:
    def test_insert_then_fetch_hits(self):
        cfg = get_smoke_config("qwen2-0.5b")
        eng = TentEngine(FabricSpec())
        hc = _hicache(eng, cfg)
        tokens = list(range(64))
        hc.insert(tokens)
        res = hc.fetch_prefix(tokens)
        assert res.prefix_tokens == 64
        assert res.promoted_pages == 0  # already on GPU
        assert hc.hits == 1

    def test_eviction_demotes_and_refetch_promotes(self):
        cfg = get_smoke_config("qwen2-0.5b")
        eng = TentEngine(FabricSpec())
        hc = _hicache(eng, cfg, gpu_pages=4)
        # fill beyond GPU capacity: oldest pages demote to CPU tier
        first = list(range(64))  # 4 pages
        hc.insert(first)
        second = list(range(1000, 1064))
        hc.insert(second)
        counts = hc.tier_counts()
        assert counts["gpu"] == 4 and counts["cpu"] + counts["disk"] == 4
        # fetching the first conversation promotes its pages back up
        res = hc.fetch_prefix(first)
        assert res.prefix_tokens == 64
        assert res.promoted_pages > 0
        assert res.transfer_seconds > 0  # promotion really crossed the fabric

    def test_partial_prefix(self):
        cfg = get_smoke_config("qwen2-0.5b")
        eng = TentEngine(FabricSpec())
        hc = _hicache(eng, cfg)
        tokens = list(range(64))
        hc.insert(tokens)
        extended = tokens + list(range(5000, 5032))
        res = hc.fetch_prefix(extended)
        assert res.prefix_tokens == 64  # only the cached prefix

    def test_serving_sim_hicache_beats_baseline(self):
        cfg = get_smoke_config("qwen2-0.5b")
        sim_cfg = ServeSimConfig(clients=4, concurrency=2, turns=5, input_tokens=256,
                                 output_tokens=16)
        perf = from_table2()
        # baseline: no cache
        eng0 = TentEngine(FabricSpec())
        base = ServingSimulator(eng0, perf, hicache=None, sim_cfg=sim_cfg).run()
        # hicache via TENT
        eng1 = TentEngine(FabricSpec())
        hc = _hicache(eng1, cfg, gpu_pages=64, cpu_pages=256, disk_pages=512, page_tokens=64)
        cached = ServingSimulator(eng1, perf, hicache=hc, sim_cfg=sim_cfg).run()
        assert cached.input_throughput > base.input_throughput
        assert cached.round_avg_ttft[5] < base.round_avg_ttft[5]


class TestCheckpointEngine:
    def test_update_moves_real_weights(self):
        cfg = get_smoke_config("qwen2-0.5b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        table = flatten_state(params)
        eng = TentEngine(FabricSpec())
        ce = CheckpointEngine(eng, nodes=2, gpus_per_node=8)
        ce.register_checkpoint(table)
        res = ce.update(verify=True)
        assert res.seconds > 0
        assert res.bytes >= sum(v.nbytes for v in table.values())
        assert res.ranks == 16

    def test_tent_policy_not_slower_than_round_robin(self):
        # elephant-flow checkpoint (256 MB) so slice spraying has room to act
        table = {"w": np.random.default_rng(0).integers(0, 255, 256 << 20, np.uint8)}
        times = {}
        for policy in ("tent", "round_robin"):
            eng = TentEngine(FabricSpec(), config=EngineConfig(policy=policy), seed=3)
            # one rail is degraded — the telemetry-driven engine must route around
            nic = eng.topology.rdma_nic(0, 2)
            eng.fabric.schedule_degradation(nic.link_id, at=0.0, until=1e9, factor=0.15)
            ce = CheckpointEngine(eng, nodes=2, gpus_per_node=8)
            ce.register_checkpoint(table)
            times[policy] = ce.update().seconds
        assert times["tent"] <= times["round_robin"] * 1.02, times


@pytest.mark.slow
class TestDisaggregation:
    @pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-370m", "hymba-1.5b"])
    def test_matches_monolithic(self, arch):
        cfg = get_smoke_config(arch).with_(remat="none")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab_size)
        eng = TentEngine(FabricSpec())
        server = DisaggregatedServer(eng, cfg, params)
        res = server.generate(prompt, n_new=6, max_len=32)
        ref = monolithic_generate(cfg, params, prompt, n_new=6, max_len=32)
        np.testing.assert_array_equal(res.tokens, ref)
        assert res.kv_transfer_seconds > 0
        assert res.kv_bytes > 0
        _, cache = prefill_jit(cfg, params, prompt, 32)
        received = server.engine.segments.get(res.kv_segment_id).read(0, res.kv_bytes)
        np.testing.assert_array_equal(received, tree_to_bytes(cache)[0])
