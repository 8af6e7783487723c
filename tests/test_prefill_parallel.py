"""The serving prefill: one parallel causal pass for the configurations
`prefill_path` finds it exact for, held to the replay (`prefill_replay`);
which path each architecture takes; the span attr that says so; and the
names and lowerings the chip benchmark and the dry-run rely on."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import EngineConfig, FabricSpec, TentEngine
from repro.models import (
    decode_step,
    forward,
    init_params,
    prefill,
    prefill_forward,
    prefill_path,
    prefill_replay,
)
from repro.models import attention as attn_mod
from repro.models.attention import attend_full, prefill_attention
from repro.obs import HostSpans
from repro.serving import DisaggregatedServer
from repro.serving import disagg

TOL = 2e-3


def _model(arch, **kw):
    cfg = get_smoke_config(arch).with_(remat="none", **kw)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _prompt(cfg, B, S):
    return jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)


def _spy_chunks(monkeypatch):
    """Record (padded q shape, chunk) of every `attend_chunked` call that
    `prefill_attention` makes."""
    calls = []
    real_chunked = attn_mod.attend_chunked

    def spy(q, k, v, **kw):
        calls.append((q.shape, kw["chunk"]))
        return real_chunked(q, k, v, **kw)

    monkeypatch.setattr(attn_mod, "attend_chunked", spy)
    return calls


def _assert_within_budget(calls, budget):
    for (B, Sp, H, _), chunk in calls:
        assert Sp % chunk == 0 and B * H * chunk * Sp * 4 <= budget


@pytest.mark.parametrize("S,max_len,window,chunked", [
    (16, 32, 0, False),  # whole score block
    (512, 544, 0, True),  # over the (shrunk) score budget: query chunks
    (300, 320, 0, True),  # over the budget at a length no chunk divides: padded
    (300, 320, 96, True),  # the same through a ring, W == window
    (24, 32, 8, False),  # sliding window: the ring wraps, W == window
    (24, 16, 32, False),  # a ring narrower than the window: W == max_len
])
def test_parallel_prefill_matches_replay(monkeypatch, S, max_len, window, chunked):
    cfg, params = _model("qwen2-0.5b", sliding_window=window)
    assert prefill_path(cfg) == "parallel"
    chunk_calls = _spy_chunks(monkeypatch)
    budget = 1 << 20
    if chunked:
        monkeypatch.setattr(attn_mod, "PREFILL_SCORE_BYTES", budget)
    tokens = _prompt(cfg, 2, S)
    logits, cache = prefill(cfg, params, tokens, max_len)
    ref_logits, ref_cache = prefill_replay(cfg, params, tokens, max_len)
    assert bool(chunk_calls) == chunked
    _assert_within_budget(chunk_calls, budget)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=TOL, atol=TOL)
    assert set(cache) == set(ref_cache) == {"k", "v"}
    W = min(max_len, window) if window else max_len
    filled = min(S, W)
    for kv in ("k", "v"):
        got, ref = np.asarray(cache[kv]), np.asarray(ref_cache[kv])
        assert got.shape == ref.shape == (cfg.num_layers, 2, W, cfg.num_kv_heads,
                                          cfg.resolved_head_dim)
        assert got.dtype == ref.dtype
        np.testing.assert_allclose(got[:, :, :filled], ref[:, :, :filled], rtol=TOL, atol=TOL)
        assert not got[:, :, filled:].any() and not ref[:, :, filled:].any()
    tok = jnp.argmax(ref_logits, axis=-1)[:, None].astype(jnp.int32)
    step, _ = decode_step(cfg, params, cache, tok, jnp.int32(S))
    ref_step, _ = decode_step(cfg, params, ref_cache, tok, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(step), np.asarray(ref_step), rtol=TOL, atol=TOL)


@pytest.mark.parametrize("S,window,budget", [
    (300, 0, 1 << 16),  # no power-of-two chunk divides 300: padded to 320
    (1000, 0, 1 << 18),
    (1000, 100, 1 << 18),  # a window across chunks
    (517, 0, 1 << 14),  # a prime length
    (2048, 0, 1 << 24),  # aligned: chunks of CHUNK_Q, nothing padded
])
def test_prefill_attention_holds_the_score_budget(monkeypatch, S, window, budget):
    """By bytes, every prompt length attends in chunks whose score block
    fits the budget, and gives what the whole block gives."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 8), jnp.float32)
    k, v = (jax.random.normal(key, (1, S, 2, 8), jnp.float32) for key in ks[1:])
    calls = _spy_chunks(monkeypatch)
    out = prefill_attention(q, k, v, window=window, score_bytes=budget)
    assert len(calls) == 1
    _assert_within_budget(calls, budget)
    if S % attn_mod.CHUNK_Q == 0:
        assert calls == [((1, S, 4, 8), attn_mod.CHUNK_Q)]
    ref = attend_full(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    # within the budget the whole block, as the length rule would give it
    whole = prefill_attention(q, k, v, window=window, score_bytes=4 * 4 * S * S)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(ref))
    assert len(calls) == 1


@pytest.mark.parametrize("arch,path", [
    ("qwen2-0.5b", "parallel"), ("deepseek-7b", "parallel"), ("granite-34b", "parallel"),
    ("chameleon-34b", "parallel"), ("mamba2-370m", "replay"), ("hymba-1.5b", "replay"),
    ("dbrx-132b", "replay"), ("qwen3-moe-235b-a22b", "replay"),
    ("seamless-m4t-medium", "replay"),
])
def test_prefill_path(arch, path):
    assert prefill_path(get_smoke_config(arch)) == path


def test_replay_arch_serves_the_replay_bit_equal():
    cfg, params = _model("mamba2-370m")
    tokens = _prompt(cfg, 2, 16)
    logits, cache = prefill(cfg, params, tokens, 32)
    ref_logits, ref_cache = prefill_replay(cfg, params, tokens, 32)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert set(cache) == set(ref_cache)
    for k in cache:
        np.testing.assert_array_equal(np.asarray(cache[k]), np.asarray(ref_cache[k]))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-370m"])
def test_prefill_span_names_its_path(arch):
    cfg, params = _model(arch)
    server = DisaggregatedServer(TentEngine(FabricSpec(), config=EngineConfig()), cfg, params)
    rec = HostSpans()
    server.attach_spans(rec)
    server.generate(_prompt(cfg, 2, 12), n_new=2, max_len=16)
    (pre,) = [s for s in rec.finished() if s[0] == "tent.prefill"]
    assert pre[5] == {"path": prefill_path(cfg)}


def test_prefill_jit_module_name():
    """The device trace names the serving prefill's program after the
    lowered module; the chip benchmark's `prefill_us_per_token` reads
    `jit_prefill` and not `jit_prefill_forward`."""
    cfg, params = _model("qwen2-0.5b")
    text = disagg.prefill_jit.lower(cfg, params, _prompt(cfg, 2, 16), 32).as_text()
    assert re.search(r"^module @jit_prefill\b(?!_)", text, re.M)


def test_score_budget_reaches_only_the_serving_prefill(monkeypatch):
    """At a prompt past `CHUNKED_THRESHOLD`, `forward` and `prefill_forward`
    lower the same whatever the serving prefill's score budget, while
    `prefill` lowers otherwise: no chunk decision leaked into them."""
    cfg, params = _model("qwen2-0.5b")
    tokens = jax.ShapeDtypeStruct((1, 2560), jnp.int32)

    def lowered():
        # fresh callables, so no trace is reused across budgets
        return [jax.jit(lambda p, t: forward(cfg, p, t)).lower(params, tokens).as_text(),
                jax.jit(lambda p, t: prefill_forward(cfg, p, t)).lower(params, tokens).as_text(),
                jax.jit(lambda p, t: prefill(cfg, p, t, 2560)).lower(params, tokens).as_text()]

    monkeypatch.setattr(attn_mod, "PREFILL_SCORE_BYTES", 1 << 20)
    small = lowered()
    monkeypatch.setattr(attn_mod, "PREFILL_SCORE_BYTES", 1 << 62)
    large = lowered()
    assert small[:2] == large[:2]
    assert small[2] != large[2]
