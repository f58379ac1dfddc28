"""Operations and bytes the model needs for a step, from its published
configuration (the top-level keys of ``configs/<name>.json``).

These are model counts: what the architecture requires, not what a
program happens to compute. So

  * attention counts each query against its live keys only (causal prefix
    in prefill, the sequence's own length in decode), never the padded
    capacity or the block-table width;
  * KV bytes are the live tokens' bytes;
  * a mixture-of-experts layer reads the experts its tokens are routed
    to: the expected number of distinct experts under uniform routing,
    ``E·(1 − (1 − k/E)^tokens)``;
  * the output head is counted in decode (one row per sequence) and left
    out of prefill (one row per prompt, under 0.1% of a chunk's work).

A program that computes more than this (the capacity, every expert, a
head row per prompt position) reads a lower share, never one above 100%.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

Conf = Dict[str, Any]
BF16 = 2


def is_mla(c: Conf) -> bool:
    return "kv_lora_rank" in c


def moe_layers(c: Conf) -> int:
    if "n_routed_experts" not in c:
        return 0
    return c["num_hidden_layers"] - c.get("first_k_dense_replace", 0)


def _attn_matmul_params(c: Conf) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    if is_mla(c):
        r, nope = c["kv_lora_rank"], c["qk_nope_head_dim"]
        pe, vd = c["qk_rope_head_dim"], c["v_head_dim"]
        return d * h * (nope + pe) + d * (r + pe) + r * h * (nope + vd) \
            + h * vd * d
    kv = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def _expert_params(c: Conf) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _dense_mlp_params(c: Conf) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def _shared_params(c: Conf) -> int:
    return _expert_params(c) * c.get("n_shared_experts", 0)


def matmul_flops_per_token(c: Conf) -> float:
    """All layers' projections and MLPs for one token (no head)."""
    n, m = c["num_hidden_layers"], moe_layers(c)
    f = n * _attn_matmul_params(c) + (n - m) * _dense_mlp_params(c)
    if m:
        f += m * (c["num_experts_per_tok"] * _expert_params(c)
                  + _shared_params(c)
                  + c["hidden_size"] * c["n_routed_experts"])
    return 2.0 * f


def attn_flops_per_key(c: Conf) -> float:
    """Scores and weighted values for one (query, key) pair, all layers."""
    h = c["num_attention_heads"]
    if is_mla(c):
        dim = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    else:
        hd = c.get("head_dim") or c["hidden_size"] // h
        dim = 2 * hd
    return 2.0 * h * dim * c["num_hidden_layers"]


def head_flops(c: Conf) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def kv_bytes_per_token(c: Conf) -> int:
    n = c["num_hidden_layers"]
    if is_mla(c):
        return n * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return n * 2 * c["num_key_value_heads"] * hd * BF16


def expected_experts(experts: int, k: int, tokens: int) -> float:
    return experts * (1.0 - (1.0 - k / experts) ** tokens)


def weight_bytes(c: Conf, tokens: int) -> float:
    """Layer weights read by a step over ``tokens`` tokens, plus the final
    norm and the output head (the embedding table is gathered row-wise,
    counted as nothing)."""
    n, m, d = c["num_hidden_layers"], moe_layers(c), c["hidden_size"]
    p = n * (_attn_matmul_params(c) + 2 * d) + (n - m) * _dense_mlp_params(c)
    b = BF16 * p
    if m:
        e = c["n_routed_experts"]
        hit = expected_experts(e, c["num_experts_per_tok"], tokens)
        b += m * (BF16 * (hit * _expert_params(c) + _shared_params(c))
                  + 4 * d * e)
    return b + BF16 * (d + d * c["vocab_size"])


def decode_step(c: Conf, batch: int, live: int) -> Tuple[float, float]:
    """One decode step of ``batch`` sequences attending ``live`` keys in
    total (Σ of each sequence's length including the new token)."""
    flops = batch * (matmul_flops_per_token(c) + head_flops(c)) \
        + attn_flops_per_key(c) * live
    byts = weight_bytes(c, batch) + kv_bytes_per_token(c) * (live + batch)
    return flops, byts


def prefill_chunk(c: Conf, start: int, n: int) -> Tuple[float, float]:
    """A chunk of ``n`` prompt tokens at positions [start, start + n),
    attending its causal prefix."""
    keys = n * start + n * (n + 1) / 2
    flops = n * matmul_flops_per_token(c) + attn_flops_per_key(c) * keys
    byts = weight_bytes(c, n) + kv_bytes_per_token(c) * (start + 2 * n)
    return flops, byts


def roofline_seconds(flops: float, byts: float, peaks: Dict[str, float]
                     ) -> Tuple[float, str]:
    """Least time the chip could take, and which bound holds."""
    tf = flops / peaks["bf16_flops"]
    tb = byts / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
