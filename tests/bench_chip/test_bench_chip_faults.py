"""A run with the timed path broken underneath comes out not correct: once
for each fault a served cell can have. The harness's look for a TPU is
bypassed; everything else is a whole run at smoke sizes on the CPU."""
import time

import jax.numpy as jnp
import pytest

import bench_harness
import bench_smoke
from repro.core import TentEngine
from repro.core.engine import BatchResult
from repro.serving import disagg

CELL = "qwen2-0.5b.doc-qa"
SEED = 2**32 + 99


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return bench_smoke.smoke_tree(tmp_path_factory.mktemp("smoke"))


def stale_state(orig):
    """A decode step that returns the cache it was given."""
    def step(cfg, params, cache, token, pos):
        logits, _ = orig(cfg, params, cache, token, pos)
        return logits, cache
    return step


def half_batch(orig):
    """A prefill that serves the first half of the batch and hands its
    results to the other half too."""
    def prefill(cfg, params, prompt, max_len, **kw):
        h = prompt.shape[0] // 2
        logits, cache = orig(cfg, params, jnp.asarray(prompt)[:h], max_len, **kw)
        twice = lambda a, axis: jnp.concatenate([a, a], axis=axis)
        return twice(logits, 0), {k: twice(v, 1) for k, v in cache.items()}
    return prefill


def altered_token(orig):
    """A decode step whose logits put the next token id first."""
    def step(*a):
        logits, cache = orig(*a)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


def no_exchange(engine, src, soff, dst, doff, length):
    """The handoff reported done, with no byte moved."""
    return BatchResult(batch_id=-1, ok=True, submitted_at=0.0, completed_at=0.0,
                       bytes=length, error="")


@pytest.mark.parametrize("fault", ["stale_state", "half_batch", "no_exchange",
                                   "altered_token"])
def test_broken_path_is_not_correct(tree, fault, monkeypatch, jax_cache_restored):
    if fault == "stale_state":
        monkeypatch.setattr(disagg, "decode_step_jit", stale_state(disagg.decode_step_jit))
    elif fault == "half_batch":
        monkeypatch.setattr(disagg, "prefill_jit", half_batch(disagg.prefill_jit))
    elif fault == "altered_token":
        monkeypatch.setattr(disagg, "decode_step_jit", altered_token(disagg.decode_step_jit))
    else:
        monkeypatch.setattr(TentEngine, "transfer_sync", no_exchange)
    out = bench_harness.run(tree, CELL, SEED, 0.5, False, time.perf_counter(),
                            platform="cpu")
    assert out["correct"] is False, out["checks"]
    checks = out["checks"]
    if fault == "no_exchange":
        assert checks["handoff_bytes_differ"]["value"] > 0
    else:
        assert checks["served_gap_max"]["value"] > checks["served_gap_max"]["limit"]
