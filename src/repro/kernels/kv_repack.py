"""KV repack — Pallas TPU kernels for the VRAM-management alignment
component (paper Fig. 3).

Two kernels implementing the paper's flatten-to-1D method as fused
gather/scatter over paged pools:

  * ``gather_pages``  — P side: pool pages (any vendor layout) → contiguous
    canonical (S, kv, hd). Source page id comes from a scalar-prefetched
    block list (data-dependent DMA, same mechanism as paged attention).
  * ``scatter_pages`` — D side: canonical → pool pages in the D vendor's
    layout/block size/dtype. The destination page id is scalar-prefetched in
    the *output* index_map; untouched pool pages are preserved through
    input-output aliasing.

Layout permutation (nbhd / nhbd / nhdb) and dtype cast happen inside the
kernel — one pass over the data, no HBM round-trip for the transpose.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.serving.paged_cache import KVPageSpec, _FROM_CANON

# inverse permutation: layout page axes → canonical (block, kv, hd)
def _to_canon_perm(layout: str) -> Tuple[int, ...]:
    perm = _FROM_CANON[layout]
    inv = [0, 0, 0]
    for i, p in enumerate(perm):
        inv[p] = i
    return tuple(inv)


def _gather_kernel(block_ids, src_ref, out_ref, *, layout: str):
    page = src_ref[0]                                   # (*page_shape)
    canon = jnp.transpose(page, _to_canon_perm(layout))  # (bs, kv, hd)
    out_ref[0] = canon.astype(out_ref.dtype)


def gather_pages(spec: KVPageSpec, pool: jax.Array, block_ids: jax.Array,
                 out_dtype=None, interpret: bool = False) -> jax.Array:
    """pool: (N, *spec.page_shape()); block_ids: (nb,) int32.
    Returns canonical pages (nb, bs, kv, hd) in ``out_dtype``."""
    nb = block_ids.shape[0]
    bs, kv, hd = spec.block_size, spec.kv_heads, spec.head_dim
    out_dtype = out_dtype or pool.dtype
    kernel = functools.partial(_gather_kernel, layout=spec.layout)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1,) + spec.page_shape(),
                               lambda i, ids: (ids[i], 0, 0, 0))],
        out_specs=pl.BlockSpec((1, bs, kv, hd),
                               lambda i, ids: (i, 0, 0, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, bs, kv, hd), out_dtype),
        interpret=interpret,
    )(block_ids, pool)


def _scatter_kernel(block_ids, canon_ref, pool_in_ref, pool_out_ref, *,
                    layout: str):
    canon = canon_ref[0]                                 # (bs, kv, hd)
    perm = _FROM_CANON[layout]
    pool_out_ref[0] = jnp.transpose(canon, perm).astype(pool_out_ref.dtype)


def scatter_pages(spec: KVPageSpec, pool: jax.Array, block_ids: jax.Array,
                  canon: jax.Array, interpret: bool = False) -> jax.Array:
    """canon: (nb, bs, kv, hd) canonical pages → write into ``pool`` at
    ``block_ids`` in the vendor layout. Returns the updated pool (aliased)."""
    nb = block_ids.shape[0]
    kernel = functools.partial(_scatter_kernel, layout=spec.layout)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1,) + (spec.block_size, spec.kv_heads,
                                 spec.head_dim),
                         lambda i, ids: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),     # aliased full pool
        ],
        out_specs=pl.BlockSpec((1,) + spec.page_shape(),
                               lambda i, ids: (ids[i], 0, 0, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, spec.jdtype),
        input_output_aliases={2: 0},   # pool (after scalar-prefetch + canon)
        interpret=interpret,
    )(block_ids, canon, pool)


def _scatter_overlay_kernel(block_ids, canon_ref, cur_ref, pool_in_ref,
                            pool_out_ref, *, layout: str, front: int,
                            seq_len: int, block_size: int, span: int):
    i = pl.program_id(0) % span                          # page within span
    canon = canon_ref[0]                                 # (bs, kv, hd)
    cur = jnp.transpose(cur_ref[0], _to_canon_perm(layout))
    row = jax.lax.broadcasted_iota(jnp.int32, canon.shape, 0)
    abs_row = i * block_size + row
    keep = (abs_row < front) | (abs_row >= front + seq_len)
    merged = jnp.where(keep, cur, canon.astype(cur.dtype))
    perm = _FROM_CANON[layout]
    pool_out_ref[0] = jnp.transpose(merged, perm).astype(pool_out_ref.dtype)


def scatter_pages_overlay(spec: KVPageSpec, pool: jax.Array,
                          block_ids: jax.Array, canon: jax.Array,
                          front: int, seq_len: int,
                          interpret: bool = False, span: int = 0
                          ) -> jax.Array:
    """Scatter canonical pages into ``pool`` while preserving rows outside
    ``[front, front + seq_len)`` of the flattened page span.

    ``span`` (default: every page) is the number of pages of one span:
    several spans — one per layer, stacked into one pool by the caller —
    go through one call, each merged at the same ``front``/``seq_len``.

    ``canon``: (nb, bs, kv, hd) pages whose flat rows ``front .. front +
    seq_len`` hold the incoming stream (outside that range the content is
    ignored). Each grid step reads the *current* destination page — the same
    data-dependent ``ids[i]`` prefetch as the scatter — and overlays only
    the covered rows, so partial head/tail blocks merge inside the kernel:
    no host-side readback, one pass per page. ``front``/``seq_len`` are
    host-known and baked into the kernel."""
    nb = block_ids.shape[0]
    kernel = functools.partial(
        _scatter_overlay_kernel, layout=spec.layout, front=front,
        seq_len=seq_len, block_size=spec.block_size, span=span or nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, spec.block_size, spec.kv_heads, spec.head_dim),
                         lambda i, ids: (i, 0, 0, 0)),
            pl.BlockSpec((1,) + spec.page_shape(),       # current dst page
                         lambda i, ids: (ids[i], 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),        # aliased full pool
        ],
        out_specs=pl.BlockSpec((1,) + spec.page_shape(),
                               lambda i, ids: (ids[i], 0, 0, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, spec.jdtype),
        input_output_aliases={3: 0},   # pool (after prefetch, canon, cur)
        interpret=interpret,
    )(block_ids, canon, pool, pool)


def repack(src: KVPageSpec, dst: KVPageSpec, src_pool: jax.Array,
           src_blocks: jax.Array, dst_pool: jax.Array,
           dst_blocks: jax.Array, seq_len: int,
           interpret: bool = False) -> jax.Array:
    """Full vendor-alignment path: gather from P pool (src layout/blocksize)
    → canonical 1-D stream → scatter into D pool (dst layout/blocksize).

    seq_len tokens move; block counts follow each side's block size."""
    canon_pages = gather_pages(src, src_pool, src_blocks,
                               out_dtype=dst.jdtype, interpret=interpret)
    flat = canon_pages.reshape(-1, src.kv_heads, src.head_dim)[:seq_len]
    nb_d = dst.blocks_for(seq_len)
    pad = nb_d * dst.block_size - seq_len
    flat = jnp.pad(flat, ((0, pad), (0, 0), (0, 0)))
    canon_d = flat.reshape(nb_d, dst.block_size, dst.kv_heads, dst.head_dim)
    return scatter_pages(dst, dst_pool, dst_blocks[:nb_d], canon_d,
                         interpret=interpret)
