"""Paged decode attention — Pallas TPU kernel (decode hot spot).

TPU adaptation of PagedAttention: the block table is a *scalar-prefetch*
operand (PrefetchScalarGridSpec), so each grid step's K/V page is DMA'd
HBM→VMEM directly from the physical page the table points at — the
data-dependent indirection happens in the BlockSpec index_map, which is
exactly how the TPU pipelines dynamic gathers. Online-softmax state lives
in VMEM scratch across the page loop (minor-most, "arbitrary" dimension).

Pool layout must be canonical "nbhd" (num_blocks, block, kv, hd) — `ops.py`
pre-permutes other vendor layouts (that permutation IS the vendor-alignment
step and is benchmarked separately via kv_repack).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_kernel(block_tbl, seq_lens, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
                  grp: int, window: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens[b]
    pos = p * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)[0]

    @pl.when(p * block_size < seq_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)                # (h, d)
        k = k_ref[0].astype(jnp.float32)                # (bs, kv, d)
        v = v_ref[0].astype(jnp.float32)
        h, d = q.shape
        bs, kv, _ = k.shape
        qg = q.reshape(kv, grp, d)
        # scores: (kv, grp, bs)
        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale
        ok = pos < seq_len
        if window > 0:
            ok &= pos >= (seq_len - window)
        s = jnp.where(ok[None, None, :], s, NEG_INF)
        s2 = s.reshape(h, bs)
        m_prev = m_ref[...]
        m_cur = jnp.max(s2, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pr = jnp.exp(s2 - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        # out: (kv, grp, d)
        o = jax.lax.dot_general(pr.reshape(kv, grp, bs), v,
                                (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + o.reshape(h, d)
        m_ref[...] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, seq_lens: jax.Array, *,
                    scale: Optional[float] = None, window: int = 0,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, d); pools: (N, bs, KV, d) canonical layout;
    block_table: (B, max_pages) int32; seq_lens: (B,) int32 (lengths
    including the current token, already appended). Returns (B, H, d)."""
    b, h, d = q.shape
    n, bs, kv, _ = k_pool.shape
    assert h % kv == 0
    grp = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    max_pages = block_table.shape[1]

    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs,
                               grp=grp, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, p_, bt, sl: (b_, 0, 0)),
            pl.BlockSpec((1, bs, kv, d),
                         lambda b_, p_, bt, sl: (bt[b_, p_], 0, 0, 0)),
            pl.BlockSpec((1, bs, kv, d),
                         lambda b_, p_, bt, sl: (bt[b_, p_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, p_, bt, sl: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, seq_lens, q, k_pool, v_pool)
