"""Core layer library — pure-JAX reference implementations.

Every layer is a pure function ``f(params, x, ...) -> y`` over plain dict
params. Hot-spot layers (prefill flash attention, paged decode attention,
KV repack) have Pallas TPU kernels in ``repro.kernels``; the functions here
are the numerically-authoritative references and the CPU execution path.

Conventions:
  * activations: (B, S, d) unless stated
  * attention heads axis layout: (B, S, H, hd)
  * KV caches carry explicit position tensors so full-attention and
    sliding-window (ring-buffer) caches share one decode path.
  * softmax / norms accumulate in fp32.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import dist

Params = Dict[str, Any]

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #
def rms_norm(w: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * w.astype(dt)


def layer_norm(w: jax.Array, b: jax.Array, x: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * w.astype(dt) + b.astype(dt)


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies, fp32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate ``x`` (..., S, H, hd) by per-position angles.

    ``positions``: broadcastable to (..., S) — int32 absolute positions.
    Uses the llama half-split convention.
    """
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)                       # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv    # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Attention core (reference). Masks are additive fp32.
# --------------------------------------------------------------------------- #
def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
         scale: Optional[float] = None) -> jax.Array:
    """q: (B,Sq,H,hd)  k,v: (B,Skv,KV,hd)  mask: (B,1|H,Sq,Skv) additive.

    GQA: H must be a multiple of KV; Q heads are grouped onto KV heads.
    Returns (B,Sq,H,hd_v).
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    grp = h // kv
    qg = q.reshape(b, sq, kv, grp, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    # mask: (B|1, 1, Sq, Skv) additive → broadcast over (kv, grp)
    scores = scores + mask[:, :, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def chunked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
                 q_pos: jax.Array, k_pos: jax.Array, *,
                 causal: bool = True, window: int = 0,
                 scale: Optional[float] = None,
                 chunk: int = 1024) -> jax.Array:
    """Online-softmax attention, scanned over KV chunks (flash-style).

    Numerically equivalent to ``sdpa`` with the positional mask, but the
    score buffer is (..., Sq, chunk) instead of (..., Sq, Skv) — required
    for 32k+ prefill, and the formulation XLA pipelines on TPU.

    q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd); q_pos: (B,Sq); k_pos: (B,Skv)
    int32 absolute positions, -1 = invalid (padding). Returns (B,Sq,H,hd).
    """
    b, sq, h, hd = q.shape
    kvh, skv = k.shape[2], k.shape[1]
    grp = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nc = (skv + pad) // chunk
    qg = q.reshape(b, sq, kvh, grp, hd).astype(jnp.float32)
    kc = jnp.moveaxis(k.reshape(b, nc, chunk, kvh, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, kvh, hd), 1, 0)
    pc = jnp.moveaxis(k_pos.reshape(b, nc, chunk), 1, 0)

    m0 = jnp.full((b, kvh, grp, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, grp, sq), jnp.float32)
    # accumulator stays in the scores' (b,kv,grp,sq,hd) layout through the
    # whole scan — the PV einsum emits it natively, so no per-chunk
    # transposes of a multi-GiB buffer (one moveaxis after the loop).
    a0 = jnp.zeros((b, kvh, grp, sq, hd), jnp.float32)
    p_bf16 = dist.ctx().attn_p_bf16

    def step(carry, xs):
        m, l, acc = carry
        kj, vj, pj = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj.astype(jnp.float32)) * scale
        ok = (pj[:, None, :] >= 0)                        # (B,1,C)
        if causal:
            ok &= pj[:, None, :] <= q_pos[:, :, None]     # (B,Sq,C)
        if window > 0:
            ok &= (q_pos[:, :, None] - pj[:, None, :]) < window
        s = jnp.where(ok[:, None, None], s, NEG_INF)      # (B,KV,G,Sq,C)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        if p_bf16:
            upd = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(jnp.bfloat16),
                             vj.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
        else:
            upd = jnp.einsum("bkgqs,bskd->bkgqd", p,
                             vj.astype(jnp.float32))
        acc = acc * alpha[..., None] + upd
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc),
                                  unroll=True if dist.ctx().unroll else 1)
    l = jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(acc / l, 3, 1)                     # (B,Sq,KV,G,hd)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def causal_mask(sq: int, skv: int, q_offset: jax.Array | int = 0,
                window: int = 0) -> jax.Array:
    """(1,1,sq,skv) additive mask; query i at abs pos q_offset+i may see
    key j at abs pos j if j <= i (and i - j < window when window > 0)."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(skv)[None, :]
    ok = kj <= qi
    if window > 0:
        ok &= (qi - kj) < window
    return jnp.where(ok, 0.0, NEG_INF)[None, None].astype(jnp.float32)


def length_mask(lengths: jax.Array, skv: int) -> jax.Array:
    """(B,1,1,skv) additive mask blanking positions >= per-seq length."""
    ok = jnp.arange(skv)[None] < lengths[:, None]
    return jnp.where(ok, 0.0, NEG_INF)[:, None, None].astype(jnp.float32)


# --------------------------------------------------------------------------- #
# KV cache (dense, position-tagged). Shared by full attention (capacity =
# max_seq) and sliding window (capacity = window, ring buffer).
# --------------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array          # (B, cap, KV, hd)
    v: jax.Array          # (B, cap, KV, hd)
    pos: jax.Array        # (B, cap) int32 absolute positions, -1 = empty

    @property
    def capacity(self) -> int:
        return self.k.shape[1]


def kv_cache_init(batch: int, capacity: int, kv_heads: int, hd: int,
                  dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, kv_heads, hd), dtype),
        v=jnp.zeros((batch, capacity, kv_heads, hd), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
    )


def kv_cache_write(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                   positions: jax.Array) -> KVCache:
    """Write S_new entries per sequence at slots ``positions % capacity``.

    k_new/v_new: (B, S_new, KV, hd); positions: (B, S_new) absolute (-1 = skip).
    """
    cap = cache.capacity
    slots = jnp.where(positions >= 0, positions % cap, cap)   # cap = OOB
    b = k_new.shape[0]
    bidx = jnp.arange(b)[:, None]

    def scat(buf, new):
        # OOB slots (== cap) are dropped; in-place when the cache is donated
        return buf.at[bidx, slots].set(new.astype(buf.dtype), mode="drop")

    return KVCache(k=scat(cache.k, k_new), v=scat(cache.v, v_new),
                   pos=scat(cache.pos, positions.astype(jnp.int32)))


def kv_cache_from_prefill(cache: KVCache, k_new: jax.Array,
                          v_new: jax.Array, positions: jax.Array) -> KVCache:
    """Build a fresh cache from a full prefill pass.

    Prefill positions are contiguous-from-0, so when the capacity covers
    the prompt the cache is just the (padded) K/V — no scatter, which lets
    XLA alias buffers instead of copying multi-GB pools. Ring-buffer
    (windowed) caches fall back to the scatter path."""
    cap = cache.capacity
    s = k_new.shape[1]
    if cap < s:
        return kv_cache_write(cache, k_new, v_new,
                              _ring_positions(positions, cap))
    pad = cap - s
    def pd(x, fill=0):
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, widths, constant_values=fill)
    return KVCache(k=pd(k_new).astype(cache.k.dtype),
                   v=pd(v_new).astype(cache.v.dtype),
                   pos=pd(positions.astype(jnp.int32), -1))


def mla_cache_from_prefill(cache: "MLACache", ckv_new: jax.Array,
                           kpe_new: jax.Array,
                           positions: jax.Array) -> "MLACache":
    cap = cache.capacity
    s = ckv_new.shape[1]
    if cap < s:
        return mla_cache_write(cache, ckv_new, kpe_new,
                               _ring_positions(positions, cap))
    pad = cap - s
    def pd(x, fill=0):
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, widths, constant_values=fill)
    return MLACache(ckv=pd(ckv_new).astype(cache.ckv.dtype),
                    kpe=pd(kpe_new).astype(cache.kpe.dtype),
                    pos=pd(positions.astype(jnp.int32), -1))


def _ring_positions(positions: jax.Array, capacity: int) -> jax.Array:
    """Drop (−1) positions that have already slid out of a ring buffer."""
    last = jnp.max(positions, axis=-1, keepdims=True)
    return jnp.where(positions > last - capacity, positions, -1)


def cache_attention_mask(cache: KVCache, q_positions: jax.Array,
                         window: int = 0) -> jax.Array:
    """(B,1,Sq,cap) additive mask: valid entries with pos <= q_pos
    (and within window if sliding)."""
    cp = cache.pos[:, None, :]                   # (B,1,cap)
    qp = q_positions[:, :, None]                 # (B,Sq,1)
    ok = (cp >= 0) & (cp <= qp)
    if window > 0:
        ok &= (qp - cp) < window
    return jnp.where(ok, 0.0, NEG_INF)[:, None].astype(jnp.float32)


# --------------------------------------------------------------------------- #
# Standard attention block (GQA / MHA / MQA, optional sliding window)
# --------------------------------------------------------------------------- #
def init_attention(rng, cfg: ModelConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(k1, (d, h, hd)) * s).astype(cfg.pdtype),
        "wk": (jax.random.normal(k2, (d, kv, hd)) * s).astype(cfg.pdtype),
        "wv": (jax.random.normal(k3, (d, kv, hd)) * s).astype(cfg.pdtype),
        "wo": (jax.random.normal(k4, (h, hd, d)) * s / math.sqrt(2 * cfg.num_layers)).astype(cfg.pdtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), cfg.pdtype)
        p["bk"] = jnp.zeros((kv, hd), cfg.pdtype)
        p["bv"] = jnp.zeros((kv, hd), cfg.pdtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.pdtype)
        p["k_norm"] = jnp.ones((hd,), cfg.pdtype)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(p: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, *, causal: bool = True,
                    lengths: Optional[jax.Array] = None,
                    window: int = 0) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Self-attention over a full sequence (train / prefill).

    Returns (out, (k, v)) — k/v for cache construction. positions: (B,S).
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    dctx = dist.ctx()
    if dctx.chunk_kv and s >= dctx.chunk_kv:
        k_pos = positions
        if lengths is not None:
            k_pos = jnp.where(jnp.arange(s)[None] < lengths[:, None],
                              positions, -1)
        out = chunked_sdpa(q, k, v, positions, k_pos, causal=causal,
                           window=window, chunk=dctx.chunk_size)
    else:
        mask = causal_mask(s, s, 0, window) if causal else \
            jnp.zeros((1, 1, s, s), jnp.float32)
        if lengths is not None:
            mask = mask + length_mask(lengths, s)
        out = sdpa(q, k, v, mask)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, (k, v)


def attention_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                     positions: jax.Array, cache: KVCache,
                     window: int = 0) -> Tuple[jax.Array, KVCache]:
    """Single-token (or few-token) decode against a position-tagged cache.

    x: (B,Sq,d); positions: (B,Sq) absolute. Returns (out, new_cache).
    """
    q, k, v = _project_qkv(p, cfg, x, positions)
    cache = kv_cache_write(cache, k, v, positions)
    mask = cache_attention_mask(cache, positions, window)
    out = sdpa(q, cache.k, cache.v, mask)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache


# --------------------------------------------------------------------------- #
# Paged decode attention (serving path). Pools/tables per repro.serving.
# --------------------------------------------------------------------------- #
def attention_decode_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                           positions: jax.Array, pcache: Dict[str, jax.Array],
                           layer: jax.Array, block_table: jax.Array,
                           seq_lens: jax.Array, write_blocks: jax.Array,
                           write_slots: jax.Array, spec, window: int = 0
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token decode against paged pools.

    x: (B,1,d); positions: (B,1) == old seq_lens; pcache: the group's
    stacked pools (L, N, ...), read and written at pool ``layer`` (int32
    scalar) in place; block_table: (B,maxb); seq_lens: (B,) lengths
    BEFORE this token; write_blocks/slots: (B,). The attention is the
    length-bounded kernel or the reference gather, as
    ``ops.paged_decode_path`` chooses for ``spec``.
    """
    from repro.kernels import ops
    from repro.serving import paged_cache as PC
    q, k, v = _project_qkv(p, cfg, x, positions)
    k_pool = PC.append_token(spec, pcache["k_pool"], write_blocks, write_slots,
                             k[:, 0], layer)
    v_pool = PC.append_token(spec, pcache["v_pool"], write_blocks, write_slots,
                             v[:, 0], layer)
    new_lens = seq_lens + 1
    if ops.paged_decode_path(spec) == "paged_kernel":
        out = ops.paged_attention(q[:, 0], k_pool, v_pool, layer, block_table,
                                  new_lens, window=window)[:, None]
    else:
        out = PC.paged_attention_ref(q, k_pool, v_pool, block_table, new_lens,
                                     spec, window=window, layer=layer)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, {"k_pool": k_pool, "v_pool": v_pool}


def mla_decode_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                     positions: jax.Array, pcache: Dict[str, jax.Array],
                     block_table: jax.Array, seq_lens: jax.Array,
                     write_blocks: jax.Array, write_slots: jax.Array,
                     ckv_spec, kpe_spec
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed MLA decode against paged latent pools (kv_heads=1 pools)."""
    from repro.serving import paged_cache as PC
    m = cfg.mla
    b = x.shape[0]
    q_nope, q_pe, ckv_new, kpe_new = _mla_qkv_latent(p, cfg, x, positions)
    ckv_pool = PC.append_token(ckv_spec, pcache["ckv_pool"], write_blocks,
                               write_slots, ckv_new[:, 0, None, :])
    kpe_pool = PC.append_token(kpe_spec, pcache["kpe_pool"], write_blocks,
                               write_slots, kpe_new[:, 0, None, :])
    new_lens = seq_lens + 1
    maxb = block_table.shape[1]
    ckv = PC.pages_to_canonical(ckv_spec, ckv_pool[block_table.reshape(-1)])
    kpe = PC.pages_to_canonical(kpe_spec, kpe_pool[block_table.reshape(-1)])
    s_max = maxb * ckv_spec.block_size
    ckv = ckv.reshape(b, s_max, m.kv_lora_rank)
    kpe = kpe.reshape(b, s_max, m.qk_rope_head_dim)
    w_uk = p["w_ukv"][..., :m.qk_nope_head_dim]
    w_uv = p["w_ukv"][..., m.qk_nope_head_dim:]
    q_lat = jnp.einsum("bqhd,khd->bqhk", q_nope, w_uk.astype(x.dtype))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bqhk,bsk->bhqs", q_lat.astype(jnp.float32),
                         ckv.astype(jnp.float32)) +
              jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                         kpe.astype(jnp.float32))) * scale
    mask = jnp.where(jnp.arange(s_max)[None] < new_lens[:, None], 0.0, NEG_INF)
    probs = jax.nn.softmax(scores + mask[:, None, None, :], axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsk->bqhk", probs,
                         ckv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bqhk,khd->bqhd", ctx_lat, w_uv.astype(x.dtype))
    out = jnp.einsum("bqhd,hdo->bqo", out, p["wo"].astype(x.dtype))
    return out, {"ckv_pool": ckv_pool, "kpe_pool": kpe_pool}


# --------------------------------------------------------------------------- #
# Cross-attention (enc-dec). Cache = encoder memory K/V, built once.
# --------------------------------------------------------------------------- #
def init_cross_attention(rng, cfg: ModelConfig) -> Params:
    return init_attention(rng, cfg.with_(qkv_bias=False, qk_norm=False))


def cross_attention_kv(p: Params, cfg: ModelConfig,
                       memory: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"].astype(memory.dtype))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"].astype(memory.dtype))
    return k, v


def cross_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                    mem_kv: Tuple[jax.Array, jax.Array],
                    mem_lengths: Optional[jax.Array] = None) -> jax.Array:
    k, v = mem_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    mask = jnp.zeros((x.shape[0], 1, x.shape[1], k.shape[1]), jnp.float32)
    if mem_lengths is not None:
        mask = mask + length_mask(mem_lengths, k.shape[1])
    out = sdpa(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


# --------------------------------------------------------------------------- #
# MLA — multi-head latent attention (DeepSeek-V2). Cache = compressed latent.
# --------------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    ckv: jax.Array        # (B, cap, lora)
    kpe: jax.Array        # (B, cap, rope_dim)
    pos: jax.Array        # (B, cap)

    @property
    def capacity(self) -> int:
        return self.ckv.shape[1]


def mla_cache_init(batch: int, capacity: int, cfg: ModelConfig,
                   dtype) -> MLACache:
    m = cfg.mla
    return MLACache(
        ckv=jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        kpe=jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
        pos=jnp.full((batch, capacity), -1, jnp.int32),
    )


def mla_cache_write(cache: MLACache, ckv_new: jax.Array, kpe_new: jax.Array,
                    positions: jax.Array) -> MLACache:
    """Write S_new latent entries at slots ``positions % capacity``."""
    cap = cache.capacity
    slots = jnp.where(positions >= 0, positions % cap, cap)
    bidx = jnp.arange(ckv_new.shape[0])[:, None]

    def scat(buf, new):
        return buf.at[bidx, slots].set(new.astype(buf.dtype), mode="drop")

    return MLACache(ckv=scat(cache.ckv, ckv_new),
                    kpe=scat(cache.kpe, kpe_new),
                    pos=scat(cache.pos, positions.astype(jnp.int32)))


def init_mla(rng, cfg: ModelConfig) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    sl = 1.0 / math.sqrt(m.kv_lora_rank)
    return {
        "wq": (jax.random.normal(ks[0], (d, h, qk_hd)) * s).astype(cfg.pdtype),
        "w_dkv": (jax.random.normal(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim)) * s).astype(cfg.pdtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), cfg.pdtype),
        "w_ukv": (jax.random.normal(ks[2], (m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)) * sl).astype(cfg.pdtype),
        "wo": (jax.random.normal(ks[3], (h, m.v_head_dim, d)) * s / math.sqrt(2 * cfg.num_layers)).astype(cfg.pdtype),
    }


def _mla_qkv_latent(p: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array):
    """Shared projections → (q_nope, q_pe, ckv, kpe)."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    dkv = jnp.einsum("bsd,dk->bsk", x, p["w_dkv"].astype(x.dtype))
    ckv, kpe = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(p["kv_norm"], ckv, cfg.norm_eps)
    kpe = apply_rope(kpe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_pe, ckv, kpe


def mla_block(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
              lengths: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Prefill/train MLA: expand latent to per-head K/V (naive, FLOP-cheap
    at long Sq). Returns (out, (ckv, kpe)) for latent caching."""
    m = cfg.mla
    b, s, _ = x.shape
    q_nope, q_pe, ckv, kpe = _mla_qkv_latent(p, cfg, x, positions)
    ukv = jnp.einsum("bsk,khj->bshj", ckv, p["w_ukv"].astype(x.dtype))
    k_nope, v = jnp.split(ukv, [m.qk_nope_head_dim], axis=-1)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    dctx = dist.ctx()
    if dctx.chunk_kv and s >= dctx.chunk_kv:
        k_pos = positions
        if lengths is not None:
            k_pos = jnp.where(jnp.arange(s)[None] < lengths[:, None],
                              positions, -1)
        out = _chunked_mla_sdpa(q_nope, q_pe, k_nope, kpe, v, positions,
                                k_pos, scale=scale, chunk=dctx.chunk_size
                                ).astype(x.dtype)
    else:
        mask = causal_mask(s, s)
        if lengths is not None:
            mask = mask + length_mask(lengths, s)
        scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope.astype(jnp.float32),
                             k_nope.astype(jnp.float32)) +
                  jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                             kpe.astype(jnp.float32))) * scale
        probs = jax.nn.softmax(scores + mask, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", probs,
                         v.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bqhd,hdo->bqo", out, p["wo"].astype(x.dtype))
    return out, (ckv, kpe)


def _chunked_mla_sdpa(q_nope: jax.Array, q_pe: jax.Array, k_nope: jax.Array,
                      kpe: jax.Array, v: jax.Array, q_pos: jax.Array,
                      k_pos: jax.Array, *, scale: float,
                      chunk: int = 1024) -> jax.Array:
    """Chunked online-softmax for MLA's two-term scores (nope + rope).

    q_nope/k_nope: (B,S,H,dn); q_pe: (B,S,H,dr); kpe: (B,S,dr);
    v: (B,S,H,dv). Causal. Returns (B,Sq,H,dv) fp32."""
    b, sq, h, dn = q_nope.shape
    skv = k_nope.shape[1]
    dv = v.shape[-1]
    pad = (-skv) % chunk
    if pad:
        k_nope = jnp.pad(k_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpe = jnp.pad(kpe, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nc = (skv + pad) // chunk
    qn = q_nope.astype(jnp.float32)
    qp = q_pe.astype(jnp.float32)
    knc = jnp.moveaxis(k_nope.reshape(b, nc, chunk, h, dn), 1, 0)
    kpc = jnp.moveaxis(kpe.reshape(b, nc, chunk, kpe.shape[-1]), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, h, dv), 1, 0)
    pc = jnp.moveaxis(k_pos.reshape(b, nc, chunk), 1, 0)

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, sq, h, dv), jnp.float32)

    def step(carry, xs):
        m, l, acc = carry
        kn, kp, vj, pj = xs
        s = (jnp.einsum("bqhd,bshd->bhqs", qn, kn.astype(jnp.float32)) +
             jnp.einsum("bqhd,bsd->bhqs", qp, kp.astype(jnp.float32))) * scale
        ok = (pj[:, None, :] >= 0) & (pj[:, None, :] <= q_pos[:, :, None])
        s = jnp.where(ok[:, None], s, NEG_INF)            # (B,H,Sq,C)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bhqs,bshd->bqhd", p, vj.astype(jnp.float32))
        acc = acc * jnp.moveaxis(alpha, 1, 2)[..., None] + upd
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (knc, kpc, vc, pc),
                                  unroll=True if dist.ctx().unroll else 1)
    l = jnp.maximum(jnp.moveaxis(l, 1, 2)[..., None], 1e-30)
    return acc / l


def mla_decode(p: Params, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array, cache: MLACache
               ) -> Tuple[jax.Array, MLACache]:
    """Absorbed-weight MLA decode: attention runs in the latent space."""
    m = cfg.mla
    q_nope, q_pe, ckv_new, kpe_new = _mla_qkv_latent(p, cfg, x, positions)
    cache = mla_cache_write(cache, ckv_new, kpe_new, positions)
    w_uk = p["w_ukv"][..., :m.qk_nope_head_dim]     # (lora, H, nope)
    w_uv = p["w_ukv"][..., m.qk_nope_head_dim:]     # (lora, H, v)
    # absorb K up-projection into q
    q_lat = jnp.einsum("bqhd,khd->bqhk", q_nope, w_uk.astype(x.dtype))
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bqhk,bsk->bhqs", q_lat.astype(jnp.float32),
                         cache.ckv.astype(jnp.float32)) +
              jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                         cache.kpe.astype(jnp.float32))) * scale
    cp = cache.pos[:, None, None, :]
    qp = positions[:, None, :, None]
    mask = jnp.where((cp >= 0) & (cp <= qp), 0.0, NEG_INF)
    probs = jax.nn.softmax(scores + mask, axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsk->bqhk", probs,
                         cache.ckv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bqhk,khd->bqhd", ctx_lat, w_uv.astype(x.dtype))
    out = jnp.einsum("bqhd,hdo->bqo", out, p["wo"].astype(x.dtype))
    return out, cache


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #
def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f) / math.sqrt(2 * cfg.num_layers)
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * s_in).astype(cfg.pdtype),
        "w_up": (jax.random.normal(k2, (d, f)) * s_in).astype(cfg.pdtype),
        "w_down": (jax.random.normal(k3, (f, d)) * s_out).astype(cfg.pdtype),
    }


def swiglu_mlp(p: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, p["w_down"].astype(x.dtype))


# --------------------------------------------------------------------------- #
# MoE — top-k routed experts (+ shared), sort-based grouping + ragged_dot.
# No token dropping (capacity = T * top_k exactly, via sort).
# --------------------------------------------------------------------------- #
def init_moe(rng, cfg: ModelConfig) -> Params:
    e = cfg.moe
    d, fe = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(rng, 5)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(fe) / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": (jax.random.normal(ks[0], (d, e.num_experts)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e.num_experts, d, fe)) * s_in).astype(cfg.pdtype),
        "w_up": (jax.random.normal(ks[2], (e.num_experts, d, fe)) * s_in).astype(cfg.pdtype),
        "w_down": (jax.random.normal(ks[3], (e.num_experts, fe, d)) * s_out).astype(cfg.pdtype),
    }
    if e.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=e.num_shared_experts * fe)
    return p


def moe_route(p: Params, cfg: ModelConfig, x2d: jax.Array):
    """x2d: (T, d) → (weights (T,k), expert_idx (T,k)). Softmax-then-topk."""
    e = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, e.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx


def moe_mlp(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Dispatch → grouped GEMM (ragged_dot) → combine. x: (B,S,d) or (T,d).

    Distributed mode (ctx.moe_shard_map): local routing + expert-TP under
    shard_map — each shard routes its own tokens and computes every expert's
    d_ff slice, then psums over the model axis. The global sort/ragged path
    below would otherwise force an all-gather of every token at scale.
    """
    dctx = dist.ctx()
    if dctx.moe_shard_map and dctx.mesh is not None:
        return _moe_mlp_shard_map(p, cfg, x, dctx)
    return _moe_mlp_local(p, cfg, x)


def _moe_mlp_shard_map(p: Params, cfg: ModelConfig, x: jax.Array,
                       dctx) -> jax.Array:
    from jax.sharding import PartitionSpec as P

    M = dctx.model_axis
    dp = dctx.dp_axes if x.shape[0] % _axes_size(dctx.mesh, dctx.dp_axes) == 0 \
        else ()
    xspec = P(dp if dp else None, None, None)
    wspec = {"router": P(None, None),
             "w_gate": P(None, None, M), "w_up": P(None, None, M),
             "w_down": P(None, M, None)}
    if cfg.moe.num_shared_experts:
        wspec["shared"] = {"w_gate": P(None, M), "w_up": P(None, M),
                           "w_down": P(M, None)}

    def body(pl, xl):
        return _moe_mlp_capacity(pl, cfg, xl, psum_axis=M,
                                 capacity_factor=dctx.moe_capacity_factor)

    return jax.shard_map(body, mesh=dctx.mesh, in_specs=(wspec, xspec),
                         out_specs=xspec, check_vma=False)(
        {k: p[k] for k in wspec}, x)


def moe_mlp_dist_specs(cfg: ModelConfig, model_axis: str):
    """The weight PartitionSpecs `_moe_mlp_shard_map` expects (launch layer
    must shard MoE params exactly like this)."""
    from jax.sharding import PartitionSpec as P
    spec = {"router": P(None, None),
            "w_gate": P(None, None, model_axis),
            "w_up": P(None, None, model_axis),
            "w_down": P(None, model_axis, None)}
    if cfg.moe.num_shared_experts:
        spec["shared"] = {"w_gate": P(None, model_axis),
                          "w_up": P(None, model_axis),
                          "w_down": P(model_axis, None)}
    return spec


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return max(n, 1)


def _moe_mlp_capacity(p: Params, cfg: ModelConfig, x: jax.Array,
                      psum_axis: Optional[str] = None,
                      capacity_factor: float = 1.25) -> jax.Array:
    """Capacity-bounded grouped GEMM: sort tokens by expert, scan over
    experts with a fixed-size window into the sorted stream.

    ``jax.lax.ragged_dot`` is the right primitive on TPU, but its generic
    (non-TPU) lowering materializes O(E·T·d) masks — 192 GiB/chip for
    DeepSeek-V2 prefill. The capacity window bounds both memory (cap·d per
    expert) and FLOPs (capacity_factor × useful); tokens landing beyond an
    expert's capacity are dropped, the standard trade of dropping MoE
    implementations. Used on the distributed path; the exact sort/ragged
    path below remains the small-model/TPU route.
    """
    e = cfg.moe
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    t = x2d.shape[0]
    d = shape[-1]
    weights, idx = moe_route(p, cfg, x2d)

    flat_expert = idx.reshape(-1)                                # (T*k,)
    order = jnp.argsort(flat_expert)
    token_of = order // e.top_k
    flat_w = weights.reshape(-1)[order]                          # (T*k,)
    group_sizes = jnp.bincount(flat_expert,
                               length=e.num_experts).astype(jnp.int32)
    offsets = jnp.cumsum(group_sizes) - group_sizes
    tk = t * e.top_k
    cap = min(tk, max(8, int(math.ceil(tk / e.num_experts
                                       * capacity_factor / 8) * 8)))

    # (E, cap) window into the sorted token stream, clamped at the end;
    # positions outside an expert's true range are masked to weight 0.
    starts = jnp.minimum(offsets, tk - cap)
    pos = starts[:, None] + jnp.arange(cap)[None]                # (E, cap)
    valid = (pos >= offsets[:, None]) \
        & (pos < (offsets + group_sizes)[:, None])
    tok = token_of[pos.reshape(-1)]                              # (E*cap,)
    xw = x2d[tok].reshape(e.num_experts, cap, d)                 # (E, cap, d)
    gate_w = jnp.where(valid, flat_w[pos.reshape(-1)].reshape(pos.shape),
                       0.0)

    g = jnp.einsum("ecd,edf->ecf", xw, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", xw, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    ye = ye * gate_w[..., None].astype(x.dtype)

    y = jnp.zeros((t, d), x.dtype)
    y = y.at[tok].add(ye.reshape(-1, d))
    if e.num_shared_experts:
        y = y + swiglu_mlp(p["shared"], x2d)
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    return y.reshape(shape)


def _moe_mlp_local(p: Params, cfg: ModelConfig, x: jax.Array,
                   psum_axis: Optional[str] = None) -> jax.Array:
    e = cfg.moe
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    t = x2d.shape[0]
    weights, idx = moe_route(p, cfg, x2d)

    flat_expert = idx.reshape(-1)                                # (T*k,)
    order = jnp.argsort(flat_expert)                             # stable
    token_of = order // e.top_k
    xs = x2d[token_of]                                           # (T*k, d)
    group_sizes = jnp.bincount(flat_expert, length=e.num_experts).astype(jnp.int32)

    g = jax.lax.ragged_dot(xs, p["w_gate"].astype(x.dtype), group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"].astype(x.dtype), group_sizes)
    h = jax.nn.silu(g) * u
    y_sorted = jax.lax.ragged_dot(h, p["w_down"].astype(x.dtype), group_sizes)

    w_sorted = weights.reshape(-1)[order][:, None].astype(y_sorted.dtype)
    y = jnp.zeros((t, shape[-1]), y_sorted.dtype)
    y = y.at[token_of].add(y_sorted * w_sorted)
    if e.num_shared_experts:
        y = y + swiglu_mlp(p["shared"], x2d)
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)     # combine d_ff-sliced partials
    return y.reshape(shape)


def moe_load_balance_loss(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    e = cfg.moe
    x2d = x.reshape(-1, x.shape[-1])
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, e.top_k)
    counts = jnp.sum(jax.nn.one_hot(idx, e.num_experts, dtype=jnp.float32),
                     axis=(0, 1))
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    imp = jnp.mean(probs, axis=0)
    return e.num_experts * jnp.sum(frac * imp)


# --------------------------------------------------------------------------- #
# RG-LRU recurrent block (Griffin / RecurrentGemma)
# --------------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RGLRUState:
    h: jax.Array          # (B, w) recurrent hidden
    conv: jax.Array       # (B, d_conv-1, w) conv tail


def rglru_state_init(batch: int, cfg: ModelConfig, dtype) -> RGLRUState:
    r = cfg.recurrent
    w = r.lru_width or cfg.d_model
    return RGLRUState(h=jnp.zeros((batch, w), jnp.float32),
                      conv=jnp.zeros((batch, r.d_conv - 1, w), dtype))


def init_rglru(rng, cfg: ModelConfig) -> Params:
    r = cfg.recurrent
    d = cfg.d_model
    w = r.lru_width or d
    ks = jax.random.split(rng, 7)
    s = 1.0 / math.sqrt(d)
    # Λ init so that a = sigmoid(Λ)^(8r) sits in (0.9, 0.999)
    lam = jnp.log(jnp.expm1(
        -jnp.log(jax.random.uniform(ks[4], (w,), jnp.float32,
                                    0.9 ** (1 / 8), 0.999 ** (1 / 8)))))
    return {
        "w_x": (jax.random.normal(ks[0], (d, w)) * s).astype(cfg.pdtype),
        "w_gate": (jax.random.normal(ks[1], (d, w)) * s).astype(cfg.pdtype),
        "conv_w": (jax.random.normal(ks[2], (r.d_conv, w)) / math.sqrt(r.d_conv)).astype(cfg.pdtype),
        "conv_b": jnp.zeros((w,), cfg.pdtype),
        "lru_in_w": (jax.random.normal(ks[3], (w, w)) / math.sqrt(w) * 0.1).astype(cfg.pdtype),
        "lru_a_w": (jax.random.normal(ks[5], (w, w)) / math.sqrt(w) * 0.1).astype(cfg.pdtype),
        "lru_in_b": jnp.zeros((w,), jnp.float32),
        "lru_a_b": jnp.zeros((w,), jnp.float32),
        "lam": lam,
        "w_out": (jax.random.normal(ks[6], (w, d)) / math.sqrt(w) / math.sqrt(2 * cfg.num_layers)).astype(cfg.pdtype),
    }


def _causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array,
                   tail: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv. x: (B,S,w); w: (K,w); tail: (B,K-1,w) history."""
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
              for i in range(k))
    return out + b.astype(x.dtype)


def _rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t, h_0 given. a,b: (B,S,w) fp32. Returns h_{1..S}."""
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl
    a_, b_ = jax.lax.associative_scan(combine, (a, b), axis=1)
    return a_ * h0[:, None, :] + b_


def rglru_block(p: Params, cfg: ModelConfig, x: jax.Array,
                state: RGLRUState) -> Tuple[jax.Array, RGLRUState]:
    """Full-sequence recurrent block. x: (B,S,d). Returns (out, final state)."""
    xb = jnp.einsum("bsd,dw->bsw", x, p["w_x"].astype(x.dtype))
    gate = jnp.einsum("bsd,dw->bsw", x, p["w_gate"].astype(x.dtype))
    new_tail = jnp.concatenate([state.conv, xb], axis=1)[:, -(p["conv_w"].shape[0] - 1):]
    xb = _causal_conv1d(xb, p["conv_w"], p["conv_b"], state.conv)
    # RG-LRU gates (fp32 recurrence)
    xf = xb.astype(jnp.float32)
    r_g = jax.nn.sigmoid(xf @ p["lru_a_w"].astype(jnp.float32) + p["lru_a_b"])
    i_g = jax.nn.sigmoid(xf @ p["lru_in_w"].astype(jnp.float32) + p["lru_in_b"])
    log_a = -8.0 * r_g * jax.nn.softplus(p["lam"])          # (B,S,w)
    a = jnp.exp(log_a)
    gated_x = xf * i_g
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated_x
    h = _rglru_scan(a, b, state.h)
    out = (h.astype(x.dtype) * jax.nn.gelu(gate))
    out = jnp.einsum("bsw,wd->bsd", out, p["w_out"].astype(x.dtype))
    return out, RGLRUState(h=h[:, -1], conv=new_tail)


def rglru_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                 state: RGLRUState) -> Tuple[jax.Array, RGLRUState]:
    """Single-step decode; x: (B,1,d)."""
    return rglru_block(p, cfg, x, state)


# --------------------------------------------------------------------------- #
# Mamba-2 SSD (state-space duality, chunked)
# --------------------------------------------------------------------------- #
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMState:
    h: jax.Array          # (B, H, P, N) fp32 SSD state
    conv: jax.Array       # (B, d_conv-1, conv_dim) conv tail


def ssm_state_init(batch: int, cfg: ModelConfig, dtype) -> SSMState:
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return SSMState(h=jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
                    conv=jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype))


def init_ssd(rng, cfg: ModelConfig) -> Params:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_dim = di + 2 * s.n_groups * s.d_state
    ks = jax.random.split(rng, 5)
    sc = 1.0 / math.sqrt(d)
    return {
        "w_in": (jax.random.normal(ks[0], (d, 2 * di + 2 * s.n_groups * s.d_state + nh)) * sc).astype(cfg.pdtype),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_dim)) / math.sqrt(s.d_conv)).astype(cfg.pdtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.pdtype),
        "a_log": jnp.log(jax.random.uniform(ks[2], (nh,), jnp.float32, 1.0, 16.0)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jax.random.uniform(ks[3], (nh,), jnp.float32, 1e-3, 0.1))),
        "out_norm": jnp.ones((di,), cfg.pdtype),
        "w_out": (jax.random.normal(ks[4], (di, d)) / math.sqrt(di) / math.sqrt(2 * cfg.num_layers)).astype(cfg.pdtype),
    }


def _ssd_split(p: Params, cfg: ModelConfig, x: jax.Array):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    gn = s.n_groups * s.d_state
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(x.dtype))
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * gn], axis=-1)
    return z, xbc, dt, di, nh, gn


def _segsum_exp(da_cs: jax.Array) -> jax.Array:
    """L[i,j] = exp(cum_i - cum_j) for i>=j else 0. da_cs: (..., Q)."""
    diff = da_cs[..., :, None] - da_cs[..., None, :]
    mask = jnp.tril(jnp.ones(diff.shape[-2:], bool))
    return jnp.where(mask, jnp.exp(diff), 0.0)


def ssd_block(p: Params, cfg: ModelConfig, x: jax.Array,
              state: SSMState) -> Tuple[jax.Array, SSMState]:
    """Chunked SSD over a full sequence. x: (B,S,d); S % chunk == 0 or padded."""
    s = cfg.ssm
    b, S, _ = x.shape
    z, xbc, dt, di, nh, gn = _ssd_split(p, cfg, x)
    new_tail = jnp.concatenate([state.conv, xbc], axis=1)[:, -(s.d_conv - 1):]
    xbc = jax.nn.silu(_causal_conv1d(xbc, p["conv_w"], p["conv_b"], state.conv))
    xs, B_, C_ = jnp.split(xbc, [di, di + gn], axis=-1)
    xh = xs.reshape(b, S, nh, s.head_dim).astype(jnp.float32)       # (B,S,H,P)
    Bh = B_.reshape(b, S, s.n_groups, s.d_state).astype(jnp.float32)
    Ch = C_.reshape(b, S, s.n_groups, s.d_state).astype(jnp.float32)
    # broadcast groups → heads
    rep = nh // s.n_groups
    Bh = jnp.repeat(Bh, rep, axis=2)                                 # (B,S,H,N)
    Ch = jnp.repeat(Ch, rep, axis=2)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])     # (B,S,H)
    a = -jnp.exp(p["a_log"])                                         # (H,)
    da = dtf * a                                                     # (B,S,H)

    Q = min(s.chunk_size, S)
    pad = (-S) % Q
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
        da = jnp.pad(da, ((0, 0), (0, pad), (0, 0)))
        dtf = jnp.pad(dtf, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // Q

    def ch(t):  # (B, S', ...) -> (B, nc, Q, ...)
        return t.reshape((b, nc, Q) + t.shape[2:])
    xc, Bc, Cc, dac, dtc = map(ch, (xh, Bh, Ch, da, dtf))
    da_cs = jnp.cumsum(dac, axis=2)                                  # (B,nc,Q,H)
    # --- intra-chunk (quadratic within chunk)
    L = _segsum_exp(jnp.moveaxis(da_cs, -1, 2))                      # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Cc, Bc) * L * \
        jnp.moveaxis(dtc, -1, 2)[:, :, :, None, :]                   # (B,nc,H,Q,Q)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores, xc)
    # --- chunk-local end states
    decay_to_end = jnp.exp(da_cs[:, :, -1:, :] - da_cs)              # (B,nc,Q,H)
    states_loc = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn",
                            decay_to_end * dtc, Bc, xc)              # (B,nc,H,P,N)
    # --- inter-chunk recurrence
    chunk_decay = jnp.exp(jnp.sum(dac, axis=2))                      # (B,nc,H)

    def step(h, inp):
        dec, st = inp
        h_new = h * dec[:, :, None, None] + st
        return h_new, h
    h_final, h_prev = jax.lax.scan(
        step, state.h, (jnp.moveaxis(chunk_decay, 1, 0),
                        jnp.moveaxis(states_loc, 1, 0)),
        unroll=True if dist.ctx().unroll else 1)
    h_prev = jnp.moveaxis(h_prev, 0, 1)                              # (B,nc,H,P,N) state entering chunk
    y_inter = jnp.einsum("bcqh,bcqhn,bchpn->bcqhp",
                         jnp.exp(da_cs), Cc, h_prev)
    y = (y_intra + y_inter).reshape(b, S + pad, nh, s.head_dim)[:, :S]
    y = y + xh[:, :S] * p["d_skip"][None, None, :, None]
    y = y.reshape(b, S, di).astype(x.dtype)
    y = rms_norm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bsd,de->bse", y, p["w_out"].astype(x.dtype))
    return out, SSMState(h=h_final, conv=new_tail)


def ssd_decode(p: Params, cfg: ModelConfig, x: jax.Array,
               state: SSMState) -> Tuple[jax.Array, SSMState]:
    """Single-step SSD recurrence. x: (B,1,d)."""
    s = cfg.ssm
    b = x.shape[0]
    z, xbc, dt, di, nh, gn = _ssd_split(p, cfg, x)
    new_tail = jnp.concatenate([state.conv, xbc], axis=1)[:, -(s.d_conv - 1):]
    xbc = jax.nn.silu(_causal_conv1d(xbc, p["conv_w"], p["conv_b"], state.conv))
    xs, B_, C_ = jnp.split(xbc[:, 0], [di, di + gn], axis=-1)
    xh = xs.reshape(b, nh, s.head_dim).astype(jnp.float32)
    rep = nh // s.n_groups
    Bh = jnp.repeat(B_.reshape(b, s.n_groups, s.d_state), rep, 1).astype(jnp.float32)
    Ch = jnp.repeat(C_.reshape(b, s.n_groups, s.d_state), rep, 1).astype(jnp.float32)
    dtf = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dtf * a)                                            # (B,H)
    h = state.h * decay[:, :, None, None] + \
        jnp.einsum("bh,bhn,bhp->bhpn", dtf, Bh, xh)
    y = jnp.einsum("bhn,bhpn->bhp", Ch, h) + xh * p["d_skip"][None, :, None]
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = rms_norm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bsd,de->bse", y, p["w_out"].astype(x.dtype))
    return out, SSMState(h=h, conv=new_tail)
