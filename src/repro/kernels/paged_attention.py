"""Paged decode attention — Pallas TPU kernel (decode hot spot).

Length-bounded: each slot reads only the pages below its length (from the
first page inside the sliding window, when there is one), never the block
table's capacity. The grid runs over slots; inside, a loop over compute
blocks of whole pages, up to ``BLOCK_ROWS`` K rows, whose trip count
comes from the scalar-prefetched ``seq_lens``. Pages are DMA'd one page per copy
(all KV heads of a canonical ``nbhd`` page are contiguous) straight from
the stacked pools in HBM, addressed by the scalar-prefetched layer index
and block table, into a double buffer: the next block's copies — the
next slot's first block at the end of a slot — fly while the current
block computes.

A block of ``T`` tokens is one ``(T * kv, d)`` matrix in VMEM (rows
token-major, head-minor, as the page stores them). The scores of every
query head against every row come from one matmul, and a row of a query
head's scores counts only where the row belongs to that head's KV group.
K and V are upcast to float32 in VMEM; scores, the online softmax (state
in VMEM scratch) and the accumulation are float32 — the mathematics of
``paged_cache.paged_attention_ref``, without its capacity-wide gather.
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
# K rows (tokens x KV heads) per compute block: 16 pages of 8 tokens at
# 8 KV heads, 256 KiB of bf16 K per buffer
BLOCK_ROWS = 1024


def _pages_per_block(block_size: int, kv: int, max_pages: int) -> int:
    return max(1, min(max_pages, BLOCK_ROWS // (block_size * kv)))


def _paged_kernel(layer_ref, tbl_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sems, buf_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_size: int, kv: int, grp: int,
                  ppb: int, max_pages: int, batch: int, window: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bs = block_size
    rows = ppb * bs * kv

    def pages(s):
        """(first page, last page, compute blocks) of slot ``s``."""
        n = lens_ref[s]
        first = jnp.maximum(n - window, 0) // bs if window > 0 else 0
        last = jnp.maximum(n - 1, 0) // bs
        return first, last, (last - first) // ppb + 1

    def copies(s, blk, slot):
        """(live, copy) of each page of block ``blk`` of slot ``s``."""
        first, last, _ = pages(s)
        p0 = first + blk * ppb
        out = []
        for j in range(ppb):
            page = tbl_ref[s * max_pages + jnp.minimum(p0 + j, last)]
            for i, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                out.append((p0 + j <= last, pltpu.make_async_copy(
                    hbm.at[layer, page], buf.at[slot, j], sems.at[i, slot])))
        return out

    def start(s, blk, slot):
        for live, cp in copies(s, blk, slot):
            pl.when(live)(cp.start)

    def wait(s, blk, slot):
        for live, cp in copies(s, blk, slot):
            pl.when(live)(cp.wait)

    @pl.when(b == 0)
    def _first():
        buf_ref[0] = 0
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    n = lens_ref[b]
    first, _, nblk = pages(b)
    h = kv * grp
    # column c of a block is token c // kv, KV head c % kv; query head r
    # belongs to KV head r // grp
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    tok = col // kv
    own = (col % kv) == (jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0)
                         // grp)
    row_tok = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // kv
    q = q_ref[0].astype(jnp.float32)                       # (h, d)

    def body(blk, _):
        slot = buf_ref[0]
        nxt = 1 - slot

        @pl.when(blk + 1 < nblk)
        def _same_slot():
            start(b, blk + 1, nxt)

        @pl.when(jnp.logical_and(blk + 1 >= nblk, b + 1 < batch))
        def _next_slot():
            start(b + 1, 0, nxt)

        wait(b, blk, slot)
        base = (first + blk * ppb) * bs
        k = kbuf[slot].astype(jnp.float32).reshape(rows, -1)
        v = vbuf[slot].astype(jnp.float32).reshape(rows, -1)
        # rows past the slot's last page were not copied this block
        v = jnp.where(base + row_tok < n, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = base + tok
        ok = own & (pos < n)
        if window > 0:
            ok &= pos >= n - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        buf_ref[0] = nxt
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    layer: jax.Array, block_table: jax.Array,
                    seq_lens: jax.Array, *, scale: Optional[float] = None,
                    window: int = 0, interpret: bool = False) -> jax.Array:
    """Decode attention of one layer, read from stacked pools.

    q: (B, H, d); pools: (L, N, bs, KV, d), canonical ``nbhd`` pages of
    every layer; layer: int32 scalar; block_table: (B, max_pages) int32;
    seq_lens: (B,) int32, each >= 1 (lengths including the current
    token, already appended). Returns (B, H, d)."""
    b, h, d = q.shape
    _, _, bs, kv, _ = k_pool.shape
    assert h % kv == 0
    max_pages = block_table.shape[1]
    ppb = _pages_per_block(bs, kv, max_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=bs, kv=kv, grp=h // kv,
        ppb=ppb, max_pages=max_pages, batch=b, window=window)
    per_slot = pl.BlockSpec((1, h, d), lambda s, *_: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[per_slot,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, bs, kv, d), k_pool.dtype),
            pltpu.VMEM((2, ppb, bs, kv, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # the next slot's first block is fetched by this slot's last step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_table.reshape(-1).astype(jnp.int32),
      seq_lens.astype(jnp.int32), q, k_pool, v_pool)
