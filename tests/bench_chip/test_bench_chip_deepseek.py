"""deepseek-7b-s8 (full MHA, untied head) at its smoke sizes against the
benchmark's plain float32 reference (`arch/dense.py`): the program's
prefill logits at the prompt's last position, and every decode step's
logits through the cache that `DisaggregatedServer` shipped, agree with
the reference's full forward pass at each served position. Logits are
compared, not tokens: with random weights the largest logit changes on
rounding."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_harness
import bench_smoke

ROOT = bench_smoke.REPO
CONFIG = "deepseek-7b-s8"
dense = bench_harness.load_module(ROOT / "benchmarks/chip/arch/dense.py", "bench_arch_dense")
B, S, N_NEW, MAX_LEN = 2, 24, 8, 48
SEEDS = [3, 2**33 + 7, 2**40 + 11]
# Largest |program - reference| over the served positions' logits, whose
# spread across the vocabulary is about 1. Both sides compute in float32
# from the same weights, so they differ by summation order alone (the
# parallel prefill's attention, the cache's route through the handoff, the
# program's matmuls against the reference's HIGHEST-precision ones): six
# seeds on the CPU read 3.3e-6 to 4.3e-6. Served in bfloat16, one
# precision below, every activation keeps 8 bits of mantissa and the same
# seeds read 0.052 to 0.067. The tolerance sits some 230x above the first
# and 50x below the second.
ATOL = 1e-3


def smoke_config():
    cfg = json.loads((ROOT / f"benchmarks/chip/configs/{CONFIG}.json").read_text())
    cfg.update(bench_smoke.smoke_spec(CONFIG)["dims"])
    return cfg


def reference_logits(cfg, params, tokens):
    """(T, V) float32 logits of the plain reference over one row of tokens:
    `arch/dense.py`'s own layer, norm and untied head, every position."""
    d = dense.dims(cfg)
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(params["embed"][tokens])
    for i in range(d.L):
        x = dense._layer(d, x, jax.tree_util.tree_map(lambda a: a[i], params["layers"]))
    x = dense._norm(x, f32(params["final_norm"]), d.eps)
    head = params["embed"].T if d.tied else params["lm_head"]
    return jnp.matmul(x, f32(head), precision=dense.HIGHEST)


def served_logits(cfg, params, prompt, monkeypatch):
    """Served tokens and the program's logits behind each: the prefill's at
    the prompt's last position, then each decode step's, through the cache
    the server shipped. Returns tokens (B, N_NEW), logits (B, N_NEW, V)."""
    from repro.core import FabricSpec, TentEngine
    from repro.serving import DisaggregatedServer, disagg

    seen = []

    def keep(fn):
        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            seen.append(np.asarray(out[0], np.float32))
            return out
        return wrapped

    monkeypatch.setattr(disagg, "prefill_jit", keep(disagg.prefill_jit))
    monkeypatch.setattr(disagg, "decode_step_jit", keep(disagg.decode_step_jit))
    server = DisaggregatedServer(TentEngine(FabricSpec()), bench_harness.program_config(cfg),
                                 params)
    res = server.generate(prompt, n_new=N_NEW, max_len=MAX_LEN)
    return res.tokens, np.stack(seen, axis=1)


def logit_error(seed, dtype, monkeypatch):
    cfg = smoke_config()
    served = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    dense.make_params(cfg, seed))
    prompt = np.random.default_rng(seed).integers(0, cfg["vocab_size"], (B, S), dtype=np.int32)
    tokens, got = served_logits(cfg, jax.tree_util.tree_map(lambda a: a.astype(dtype), served),
                                jnp.asarray(prompt), monkeypatch)
    err, spread = 0.0, []
    for b in range(B):
        row = np.concatenate([prompt[b], tokens[b, :-1]])
        want = np.asarray(reference_logits(cfg, served, jnp.asarray(row)))[S - 1:]
        assert want.shape == got[b].shape == (N_NEW, cfg["vocab_size"])
        err = max(err, float(np.abs(got[b] - want).max()))
        spread.append(want.std(axis=-1))
    assert 0.3 < np.mean(spread) < 3  # the scale the tolerance is written for
    return err


def test_smoke_sizes_are_the_programs_deepseek_smoke():
    from repro.configs import get_smoke_config

    prog, got = get_smoke_config("deepseek-7b"), bench_harness.program_config(smoke_config())
    assert (got.num_layers, got.d_model, got.num_heads, got.num_kv_heads, got.d_ff,
            got.vocab_size, got.resolved_head_dim, got.qkv_bias, got.tie_embeddings) == (
        prog.num_layers, prog.d_model, prog.num_heads, prog.num_kv_heads, prog.d_ff,
        prog.vocab_size, prog.resolved_head_dim, prog.qkv_bias, prog.tie_embeddings)
    assert got.num_kv_heads == got.num_heads and not got.tie_embeddings


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_and_shipped_decode_match_the_reference(seed, monkeypatch):
    assert logit_error(seed, jnp.float32, monkeypatch) <= ATOL


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_copy_of_the_weights_fails_the_tolerance(seed, monkeypatch):
    """The same weights served in bfloat16, one precision below the
    float32 the test states, fall outside the tolerance."""
    assert logit_error(seed, jnp.bfloat16, monkeypatch) > ATOL
