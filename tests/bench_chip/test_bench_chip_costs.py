"""The operations and bytes the benchmark credits a step with, against
counts done by hand: for the configuration of the cell, and for an MHA
model with an untied head (DeepSeek LLM 7B's published sizes, cut to 8
layers as a pipeline stage), so that both kinds of attention and head are
counted."""
import json
from pathlib import Path

import pytest

import bench_harness

ROOT = Path(__file__).resolve().parents[2]
dense = bench_harness.load_module(ROOT / "benchmarks/chip/arch/dense.py", "bench_arch_dense")


# DeepSeek LLM 7B (arXiv:2401.02954) at 8 of its 30 layers
DEEPSEEK_7B_S8 = {"hidden_size": 4096, "num_hidden_layers": 8, "num_attention_heads": 32,
                  "num_key_value_heads": 32, "head_dim": 128, "intermediate_size": 11008,
                  "vocab_size": 102400, "tie_word_embeddings": False, "qkv_bias": False,
                  "rms_norm_eps": 1e-6, "rope_theta": 10000.0}


def dims(name):
    return dense.dims(json.loads((ROOT / f"benchmarks/chip/configs/{name}.json").read_text()))


def test_qwen2_0_5b_hand_counts():
    d = dims("qwen2-0.5b")
    # per layer: q 896*896 + k,v 2*896*128 + o 896*896 + mlp 3*896*4864
    # + 2 norms 2*896 + biases (14+2+2)*64 = 14,912,384; x24, + tied
    # embedding 151,936*896 + final norm 896: Qwen2-0.5B's 494,032,768
    assert dense.param_count(d) == 494_032_768
    assert dense.weight_bytes(d) == 988_065_536
    assert dense.kv_bytes_per_token(d) == 2 * 24 * 2 * 64 * 2 == 12_288
    # matmuls per token 2*24*14,909,440 = 715,653,120; attention at pos 0
    # 4*24*14*64*1 = 86,016; head 2*896*151,936 = 272,269,312
    assert dense.decode_flops(d, 0) == 715_653_120 + 86_016 + 272_269_312
    # two prompt tokens: 2 x matmuls, attention over 1 + 2 keys, one head
    assert dense.prefill_flops(d, 2) == 2 * 715_653_120 + 86_016 * 3 + 272_269_312
    # a step at position 9 for 16 rows: every weight, 10 positions of KV,
    # float32 logits
    assert dense.decode_bytes(d, 9, 16) == 988_065_536 + 16 * 10 * 12_288 + 16 * 151_936 * 4


def test_deepseek_7b_s8_hand_counts():
    d = dense.dims(DEEPSEEK_7B_S8)
    # per layer: 4*4096^2 + 3*4096*11008 + 2*4096 = 202,383,360; x8, + an
    # untied embedding and head 2*102,400*4096, + final norm 4096
    assert dense.param_count(d) == 202_383_360 * 8 + 838_860_800 + 4096
    assert dense.kv_bytes_per_token(d) == 2 * 8 * 32 * 128 * 2 == 131_072
    per_token = 2 * 8 * (4 * 4096 * 4096 + 3 * 4096 * 11008)
    attn = 4 * 8 * 32 * 128
    head = 2 * 4096 * 102_400
    assert dense.decode_flops(d, 99) == per_token + attn * 100 + head
    assert dense.prefill_flops(d, 64) == 64 * per_token + attn * 64 * 65 // 2 + head


def test_the_published_depth_gives_deepseek_llm_7b():
    d = dense.dims(DEEPSEEK_7B_S8)._replace(L=30)
    assert dense.param_count(d) == 6_910_365_696


@pytest.mark.parametrize("name", sorted(p.stem for p in (ROOT / "benchmarks/chip/configs").glob("*.json")))
def test_matches_the_program_config(name):
    """The configuration file is the program's published configuration,
    but for the depth the file states it cut."""
    from repro.configs import get_config

    cfg = json.loads((ROOT / f"benchmarks/chip/configs/{name}.json").read_text())
    prog = get_config(name.removesuffix("-s8"))
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["qkv_bias"],
            cfg["tie_word_embeddings"], cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        prog.d_model, prog.num_heads, prog.num_kv_heads, prog.d_ff, prog.vocab_size,
        prog.qkv_bias, prog.tie_embeddings, prog.rope_theta, prog.norm_eps)
    assert cfg["head_dim"] == prog.resolved_head_dim
    depth = cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"])
    assert depth == prog.num_layers
    assert ("num_hidden_layers" in cfg["reduced"]) == (depth != cfg["num_hidden_layers"])
