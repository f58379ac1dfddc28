"""Jit'd public wrappers around the Pallas kernels.

The kernels run compiled on the TPU backend. ``force_interpret=True`` runs
the kernel body op-by-op in interpret mode instead — the correctness path
the CPU test sweeps take. Interpret mode is never chosen silently: a
compiled kernel asked for on any other backend is an error.
"""
from __future__ import annotations

from functools import partial
import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import kv_repack as _kr
from repro.kernels import paged_attention as _pa
from repro.serving.paged_cache import KVPageSpec


def _interpret(force: bool) -> bool:
    if force:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernels compile for the TPU backend only (JAX backend "
            f"is {backend!r}); pass force_interpret=True to run them in "
            f"interpret mode")
    return False


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "force_interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    force_interpret: bool = False):
    """Causal flash attention. q: (B,H,Sq,d); k,v: (B,KV,Skv,d)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret(force_interpret))


def paged_decode_path(spec: KVPageSpec) -> str:
    """The decode attention a paged pool takes: ``"paged_kernel"`` (the
    length-bounded Pallas kernel) on the TPU backend for a float ``nbhd``
    pool whose pages the chip can copy whole — KV heads a multiple of 8
    and head_dim a multiple of 128, Mosaic's (8, 128) tiling — else
    ``"ref"`` (``paged_cache.paged_attention_ref``)."""
    if (jax.default_backend() == "tpu" and spec.layout == "nbhd"
            and spec.dtype in ("bfloat16", "float32")
            and spec.kv_heads % 8 == 0 and spec.head_dim % 128 == 0):
        return "paged_kernel"
    return "ref"


@partial(jax.jit, static_argnames=("window", "force_interpret"))
def paged_attention(q, k_pool, v_pool, layer, block_table, seq_lens, *,
                    window: int = 0, force_interpret: bool = False):
    """Decode attention of pool layer ``layer`` over the pages below each
    slot's length. q: (B,H,d); pools stacked (L,N,bs,KV,d)."""
    return _pa.paged_attention(q, k_pool, v_pool, layer, block_table,
                               seq_lens, window=window,
                               interpret=_interpret(force_interpret))


@partial(jax.jit, static_argnames=("spec", "force_interpret"))
def gather_pages(spec: KVPageSpec, pool, block_ids, *,
                 force_interpret: bool = False):
    return _kr.gather_pages(spec, pool, block_ids,
                            interpret=_interpret(force_interpret))


@partial(jax.jit, static_argnames=("spec", "force_interpret"))
def scatter_pages(spec: KVPageSpec, pool, block_ids, canon, *,
                  force_interpret: bool = False):
    return _kr.scatter_pages(spec, pool, block_ids, canon,
                             interpret=_interpret(force_interpret))


@partial(jax.jit, static_argnames=("spec", "front", "seq_len", "span",
                                   "force_interpret"))
def scatter_pages_overlay(spec: KVPageSpec, pool, block_ids, canon, *,
                          front: int, seq_len: int, span: int = 0,
                          force_interpret: bool = False):
    """Scatter preserving rows outside [front, front+seq_len) of each
    ``span``-page run (streamed chunk re-page: partial head/tail blocks
    merge inside the kernel)."""
    return _kr.scatter_pages_overlay(spec, pool, block_ids, canon, front,
                                     seq_len,
                                     interpret=_interpret(force_interpret),
                                     span=span)


@partial(jax.jit, static_argnames=("src", "dst", "seq_len",
                                   "force_interpret"))
def repack(src: KVPageSpec, dst: KVPageSpec, src_pool, src_blocks,
           dst_pool, dst_blocks, seq_len: int, *,
           force_interpret: bool = False):
    """Vendor alignment: P pool → canonical 1-D → D pool (paper Fig. 3)."""
    return _kr.repack(src, dst, src_pool, src_blocks, dst_pool, dst_blocks,
                      seq_len, interpret=_interpret(force_interpret))
