"""The FLOP and byte counters behind the MFU and roofline metrics, against
hand sums for both configurations."""
import json
import math
import os

import pytest

from bench.common import counts

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def conf(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_qwen3_4b_by_hand():
    c = conf("qwen3-4b")
    d, h, kv, hd, f, v, n = 2560, 32, 8, 128, 9728, 151936, 36
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    assert counts.matmul_flops_per_token(c) == 2 * n * per_layer
    assert counts.attn_flops_per_key(c) == 2 * h * 2 * hd * n
    assert counts.kv_bytes_per_token(c) == 147456
    w = 2 * (n * (per_layer + 2 * d) + d + d * v)
    assert counts.weight_bytes(c, 16) == w
    # 36 layers + head + embedding: the published 4,022,468,096 parameters
    params = n * (per_layer + 2 * d + 2 * hd) + d + d * v
    assert params == 4022468096
    # decode: 4 sequences with 1,000 live keys in all
    fl, by = counts.decode_step(c, 4, 1000)
    assert fl == 4 * (2 * n * per_layer + 2 * d * v) + 2 * h * 2 * hd * n \
        * 1000
    assert by == w + 147456 * 1004
    # prefill chunk [256, 512): causal keys 256·256 + 256·257/2
    fl, by = counts.prefill_chunk(c, 256, 256)
    keys = 256 * 256 + 256 * 257 / 2
    assert fl == 256 * 2 * n * per_layer + 2 * h * 2 * hd * n * keys
    assert by == w + 147456 * (256 + 512)


# DeepSeek-V2-Lite's published widths, cut to 1 dense + 8 MoE layers
DEEPSEEK_V2_LITE = {
    "hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 64, "n_shared_experts": 2, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1408, "intermediate_size": 10944,
    "first_k_dense_replace": 1, "num_hidden_layers": 9, "vocab_size": 102400}


def test_deepseek_v2_lite_by_hand():
    c = DEEPSEEK_V2_LITE
    d, h, r, nope, pe, vd = 2048, 16, 512, 128, 64, 128
    e, k, fe, shared, f, v = 64, 6, 1408, 2, 10944, 102400
    n = c["num_hidden_layers"]
    attn = d * h * (nope + pe) + d * (r + pe) + r * h * (nope + vd) \
        + h * vd * d
    moe_tok = k * 3 * d * fe + shared * 3 * d * fe + d * e
    assert counts.matmul_flops_per_token(c) == \
        2 * (n * attn + 3 * d * f + (n - 1) * moe_tok)
    assert counts.attn_flops_per_key(c) == 2 * h * (nope + pe + vd) * n
    assert counts.kv_bytes_per_token(c) == n * (r + pe) * 2
    # one token reaches k experts; many tokens reach nearly all
    assert math.isclose(counts.expected_experts(e, k, 1), k)
    assert counts.expected_experts(e, k, 4096) > e - 1e-6
    w1 = counts.weight_bytes(c, 1)
    base = 2 * (n * (attn + 2 * d) + 3 * d * f + d + d * v)
    per = 2 * (k * 3 * d * fe + shared * 3 * d * fe) + 4 * d * e
    assert math.isclose(w1, base + (n - 1) * per)


def test_roofline_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 10, peaks) == (10.0, "compute")
    assert counts.roofline_seconds(10, 1000, peaks) == (100.0, "memory")
