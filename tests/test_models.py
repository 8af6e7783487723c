"""Model-math correctness: SSD oracle equivalence, MoE dispatch equivalence,
decode-vs-forward consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import (
    decode_step,
    forward,
    init_cache,
    init_params,
    prefill,
    prefill_forward,
    prefill_replay,
)
from repro.models.moe import moe_ffn_dense, moe_ffn_sorted
from repro.models.ssm import ssd_chunked, ssd_recurrent_ref


class TestSSD:
    @pytest.mark.parametrize("shape", [(1, 64, 2, 8, 16), (2, 128, 4, 16, 32)])
    def test_chunked_matches_recurrence(self, shape):
        b, s, h, p, n = shape
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32) * 0.5
        a = -jnp.abs(jax.random.normal(ks[1], (b, s, h), jnp.float32)) * 0.3
        B = jax.random.normal(ks[2], (b, s, n), jnp.float32) * 0.5
        C = jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.5
        y_ref, st_ref = ssd_recurrent_ref(x, a, B, C)
        y, st = ssd_chunked(x, a, B, C, chunk=32)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), rtol=2e-3, atol=2e-3)

    def test_initial_state_carries(self):
        b, s, h, p, n = 1, 64, 2, 8, 16
        key = jax.random.PRNGKey(1)
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (b, 2 * s, h, p), jnp.float32) * 0.5
        a = -jnp.abs(jax.random.normal(ks[1], (b, 2 * s, h), jnp.float32)) * 0.3
        B = jax.random.normal(ks[2], (b, 2 * s, n), jnp.float32) * 0.5
        C = jax.random.normal(ks[3], (b, 2 * s, n), jnp.float32) * 0.5
        y_full, st_full = ssd_chunked(x, a, B, C, chunk=32)
        y1, st1 = ssd_chunked(x[:, :s], a[:, :s], B[:, :s], C[:, :s], chunk=32)
        y2, st2 = ssd_chunked(
            x[:, s:], a[:, s:], B[:, s:], C[:, s:], chunk=32, initial_state=st1
        )
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, s:]), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full), rtol=2e-3, atol=2e-3)

    def test_interchunk_scan_jit_bitexact_vs_numpy(self):
        """Regression for the inter-chunk scan's fma guard: the jitted
        recurrence must reproduce an unfused numpy float32 evaluation
        (separate IEEE rounding for the product and the add) bit-exactly.
        Without the divide guard in `_interchunk_step`, XLA contracts
        `prev * dec + st` in the compiled scan body into a single-rounded
        fma and the states drift one ulp."""
        from repro.models.ssm import _interchunk_step

        rng = np.random.default_rng(11)
        c, b, h, p, n = 16, 2, 3, 4, 5
        states = rng.standard_normal((c, b, h, p, n)).astype(np.float32)
        decay = np.exp(-rng.random((c, b, h))).astype(np.float32)
        init = rng.standard_normal((b, h, p, n)).astype(np.float32)
        jitted = jax.jit(lambda i, xs: jax.lax.scan(_interchunk_step, i, xs))
        final, prevs = jitted(jnp.asarray(init),
                              (jnp.asarray(states), jnp.asarray(decay)))
        prev = init.copy()
        for k in range(c):
            np.testing.assert_array_equal(np.asarray(prevs[k]), prev)
            prev = prev * decay[k][..., None, None] + states[k]
        np.testing.assert_array_equal(np.asarray(final), prev)


@pytest.mark.slow
class TestMoE:
    def test_sorted_matches_dense_dispatch(self):
        cfg = get_smoke_config("dbrx-132b").with_(moe_capacity_factor=4.0)
        key = jax.random.PRNGKey(0)
        T, D = 64, cfg.d_model
        p = {
            "router": jax.random.normal(key, (D, cfg.num_experts), jnp.float32) * 0.1,
            "w_gate": jax.random.normal(key, (cfg.num_experts, D, cfg.d_ff), jnp.float32) * 0.05,
            "w_up": jax.random.normal(key, (cfg.num_experts, D, cfg.d_ff), jnp.float32) * 0.05,
            "w_down": jax.random.normal(key, (cfg.num_experts, cfg.d_ff, D), jnp.float32) * 0.05,
        }
        x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
        y_sorted, aux_s = moe_ffn_sorted(cfg, p, x)
        y_dense, aux_d = moe_ffn_dense(cfg, p, x)
        assert int(aux_s["dropped"]) == 0  # ample capacity: no drops
        np.testing.assert_allclose(np.asarray(y_sorted), np.asarray(y_dense), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(aux_s["lb_loss"]), float(aux_d["lb_loss"]), rtol=1e-5)

    def test_capacity_drops_bounded(self):
        cfg = get_smoke_config("qwen3-moe-235b-a22b").with_(moe_capacity_factor=1.0)
        key = jax.random.PRNGKey(0)
        D = cfg.d_model
        p = {
            "router": jax.random.normal(key, (D, cfg.num_experts), jnp.float32),
            "w_gate": jnp.ones((cfg.num_experts, D, cfg.d_ff), jnp.float32) * 0.01,
            "w_up": jnp.ones((cfg.num_experts, D, cfg.d_ff), jnp.float32) * 0.01,
            "w_down": jnp.ones((cfg.num_experts, cfg.d_ff, D), jnp.float32) * 0.01,
        }
        x = jax.random.normal(jax.random.PRNGKey(2), (128, D), jnp.float32)
        y, aux = moe_ffn_sorted(cfg, p, x)
        assert y.shape == x.shape
        assert int(aux["dropped"]) < 128 * cfg.experts_per_token  # not everything dropped


@pytest.mark.slow
class TestDecodeConsistency:
    """The replay (decode_step scanned over the prompt) must agree with the
    parallel forward."""

    @pytest.mark.parametrize(
        "arch", ["qwen2-0.5b", "deepseek-7b", "mamba2-370m", "hymba-1.5b", "granite-34b"]
    )
    def test_last_token_logits_match(self, arch):
        cfg = get_smoke_config(arch).with_(remat="none")
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key, dtype=jnp.float32)
        B, S = 2, 16
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        logits_par, _ = forward(cfg, params, tokens)
        last_dec, _ = prefill_replay(cfg, params, tokens, max_len=32)
        np.testing.assert_allclose(
            np.asarray(last_dec), np.asarray(logits_par[:, -1]), rtol=2e-3, atol=2e-3
        )

    def test_sliding_window_decode_matches_forward(self):
        cfg = get_smoke_config("qwen2-0.5b").with_(remat="none", sliding_window=8)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, S = 1, 24
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        logits_par, _ = forward(cfg, params, tokens)
        last_dec, _ = prefill_replay(cfg, params, tokens, max_len=cfg.sliding_window)
        np.testing.assert_allclose(
            np.asarray(last_dec), np.asarray(logits_par[:, -1]), rtol=2e-3, atol=2e-3
        )

    @pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-370m", "hymba-1.5b"])
    def test_prefill_forward_matches_replay(self, arch):
        """The parallel prefill (serving path) must produce the same logits
        and a decode-compatible cache vs token-by-token replay."""
        cfg = get_smoke_config(arch).with_(remat="none")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, S = 2, 16
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        max_len = max(S + 1, cfg.sliding_window)
        logits_pf, cache_pf = prefill_forward(cfg, params, tokens)
        logits_rp, cache_rp = prefill_replay(cfg, params, tokens, max_len=max_len)
        np.testing.assert_allclose(
            np.asarray(logits_pf), np.asarray(logits_rp), rtol=2e-3, atol=2e-3
        )
        # continue decoding one step from both caches: identical next logits
        tok = jnp.argmax(logits_pf, axis=-1)[:, None].astype(jnp.int32)
        # pad prefill_forward cache to the replay cache's width if needed
        if "k" in cache_pf and cache_pf["k"].shape[2] < cache_rp["k"].shape[2]:
            padw = cache_rp["k"].shape[2] - cache_pf["k"].shape[2]
            for kk in ("k", "v"):
                cache_pf[kk] = jnp.pad(cache_pf[kk], ((0, 0), (0, 0), (0, padw), (0, 0), (0, 0)))
        l1, _ = decode_step(cfg, params, cache_pf, tok, jnp.int32(S))
        l2, _ = decode_step(cfg, params, cache_rp, tok, jnp.int32(S))
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3, atol=2e-3)

    def test_encdec_decode(self):
        cfg = get_smoke_config("seamless-m4t-medium").with_(remat="none")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        B, S, SE = 2, 12, 8
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
        frames = jax.random.normal(jax.random.PRNGKey(2), (B, SE, cfg.d_model), jnp.float32)
        logits_par, _ = forward(cfg, params, tokens, enc_frames=frames)
        last_dec, _ = prefill(cfg, params, tokens, max_len=32, enc_frames=frames)
        np.testing.assert_allclose(
            np.asarray(last_dec), np.asarray(logits_par[:, -1]), rtol=2e-3, atol=2e-3
        )


@pytest.mark.slow
class TestChunkedAttention:
    @pytest.mark.parametrize("window", [0, 64])
    def test_matches_full(self, window):
        from repro.models.attention import attend_chunked, attend_full

        B, S, H, K, D = 2, 256, 4, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        out = attend_chunked(q, k, v, causal=True, window=window, chunk=64)
        ref = attend_full(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_grads_match(self):
        from repro.models.attention import attend_chunked, attend_full

        B, S, H, K, D = 1, 128, 2, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        g1 = jax.grad(lambda q: attend_chunked(q, k, v, chunk=32).sum())(q)
        g2 = jax.grad(lambda q: attend_full(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
class TestMoEExpertParallel:
    def test_ep_matches_sorted_single_device(self):
        """shard_map EP path must equal the sorted-dispatch path (1-device
        mesh: E_loc = E, psum identity)."""
        import jax
        from jax.sharding import Mesh
        from repro.models.moe import moe_ffn_ep, moe_ffn_sorted
        from repro.sharding.ctx import activation_sharding

        cfg = get_smoke_config("dbrx-132b").with_(moe_capacity_factor=4.0)
        key = jax.random.PRNGKey(0)
        D = cfg.d_model
        p = {
            "router": jax.random.normal(key, (D, cfg.num_experts), jnp.float32) * 0.1,
            "w_gate": jax.random.normal(key, (cfg.num_experts, D, cfg.d_ff), jnp.float32) * 0.05,
            "w_up": jax.random.normal(key, (cfg.num_experts, D, cfg.d_ff), jnp.float32) * 0.05,
            "w_down": jax.random.normal(key, (cfg.num_experts, cfg.d_ff, D), jnp.float32) * 0.05,
        }
        x = jax.random.normal(jax.random.PRNGKey(1), (64, D), jnp.float32)
        y_ref, aux_ref = moe_ffn_sorted(cfg, p, x)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        with mesh, activation_sharding(mesh):
            y_ep, aux_ep = jax.jit(lambda x: moe_ffn_ep(cfg, p, x))(x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(aux_ep["lb_loss"]), float(aux_ref["lb_loss"]), rtol=1e-4)
