"""The KV handoff's bytes: `cache_filled_bytes` (the part of a decode cache
that a prefill of S tokens wrote, from the cache's shapes) for every cache
layout, and the `bytes` / `filled_bytes` attrs that `generate` puts on
`tent.kv.pack` only while a span recorder is attached."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import EngineConfig, FabricSpec, TentEngine
from repro.models import cache_filled_bytes, cache_shapes, init_params, prefill
from repro.obs import HostSpans
from repro.serving import DisaggregatedServer
from repro.serving import disagg

B, MAX_LEN = 2, 32


def nbytes(leaf):
    return math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize


def kv_token_bytes(cfg, dtype=jnp.bfloat16):
    """K and V of one position of one row, every layer."""
    return 2 * cfg.num_layers * cfg.num_kv_heads * cfg.resolved_head_dim * jnp.dtype(
        dtype).itemsize


def _model(arch, **over):
    cfg = get_smoke_config(arch).with_(remat="none", **over)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)


@pytest.mark.parametrize("S", [5, MAX_LEN])
def test_dense_counts_the_prompt_positions(S):
    """deepseek-7b's layout (MHA, untied): 2·L·B·S·K·Hd·2 bytes, all of the
    cache at S = max_len; the prefill left every position beyond S zero and
    wrote the ones before it."""
    cfg, params = _model("deepseek-7b")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    _, cache = prefill(cfg, params, tokens, MAX_LEN)
    got = cache_filled_bytes(cache, S)
    assert got == B * S * kv_token_bytes(cfg)
    assert (got == sum(nbytes(v) for v in cache.values())) == (S == MAX_LEN)
    for kv in ("k", "v"):
        a = np.asarray(cache[kv], np.float32)
        assert not a[:, :, S:].any()
        assert a[:, :, :S].any(axis=(0, 1, 3, 4)).all()


def test_sliding_window_ring_fills_whole_past_its_width():
    """A ring of W = window < max_len slots: a prompt longer than W fills
    every slot, a shorter one S of them."""
    cfg, params = _model("qwen2-0.5b", sliding_window=8)
    shapes = cache_shapes(cfg, B, MAX_LEN)
    W = shapes["k"].shape[2]
    assert W == 8
    ring = sum(nbytes(v) for v in shapes.values())
    assert cache_filled_bytes(shapes, 20) == ring == B * W * kv_token_bytes(cfg)
    assert cache_filled_bytes(shapes, 3) == B * 3 * kv_token_bytes(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 20), 0, cfg.vocab_size)
    _, cache = prefill(cfg, params, tokens, MAX_LEN)
    assert cache_filled_bytes(cache, 20) == ring
    assert np.asarray(cache["k"], np.float32).any(axis=(0, 1, 3, 4)).all()


@pytest.mark.parametrize("arch", ["mamba2-370m", "hymba-1.5b"])
def test_recurrent_state_counts_whole(arch):
    """SSM state and the conv buffer hold no position axis: whole at any
    S. The hybrid's K/V beside them count S positions."""
    cfg = get_smoke_config(arch)
    shapes = cache_shapes(cfg, B, MAX_LEN)
    state = nbytes(shapes["ssm_state"]) + nbytes(shapes["conv_buf"])
    assert state > 0
    kv = B * 4 * kv_token_bytes(cfg) if "k" in shapes else 0
    assert ("k" in shapes) == (arch == "hymba-1.5b")
    assert cache_filled_bytes(shapes, 4) == state + kv


def test_encoder_cross_attention_cache_counts_whole():
    """The encoder's K/V are written whole by the prefill, whatever the
    decoder's prompt; the decoder's own K/V count S positions."""
    cfg = get_smoke_config("seamless-m4t-medium")
    assert cfg.is_encdec
    shapes = cache_shapes(cfg, B, MAX_LEN, enc_len=12)
    enc = nbytes(shapes["enc_k"]) + nbytes(shapes["enc_v"])
    assert enc == 2 * cfg.num_layers * B * 12 * cfg.num_kv_heads * cfg.resolved_head_dim * 2
    assert cache_filled_bytes(shapes, 6) == enc + B * 6 * kv_token_bytes(cfg)
    assert cache_filled_bytes(shapes, 0) == enc


# ---------------------------------------------------------------------------
# The attrs on `tent.kv.pack`
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server_and_prompt():
    cfg, params = _model("deepseek-7b")
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, 7), 0, cfg.vocab_size)
    eng = TentEngine(FabricSpec(), config=EngineConfig(slice_bytes=4096))
    return DisaggregatedServer(eng, cfg, params), prompt


def test_pack_span_carries_shipped_and_filled_bytes(server_and_prompt):
    server, prompt = server_and_prompt
    rec = HostSpans()
    server.attach_spans(rec)
    try:
        res = server.generate(prompt, n_new=3, max_len=MAX_LEN)
    finally:
        server.attach_spans(None)
    [pack] = [s for s in rec.finished() if s[0] == "tent.kv.pack"]
    cfg = server.cfg
    assert pack[5] == {"bytes": res.kv_bytes,
                       "filled_bytes": B * 7 * kv_token_bytes(cfg)}
    assert res.kv_bytes == B * MAX_LEN * kv_token_bytes(cfg)


def test_detached_generate_computes_no_attr(server_and_prompt, monkeypatch):
    """With no recorder attached the filled bytes are never computed; the
    same call attached computes them once."""
    server, prompt = server_and_prompt
    seen = []
    count = disagg.cache_filled_bytes
    monkeypatch.setattr(disagg, "cache_filled_bytes",
                        lambda *a: seen.append(a) or count(*a))
    server.generate(prompt, n_new=2, max_len=MAX_LEN)
    assert seen == []
    server.attach_spans(HostSpans())
    try:
        server.generate(prompt, n_new=2, max_len=MAX_LEN)
    finally:
        server.attach_spans(None)
    assert len(seen) == 1 and seen[0][1] == 7
