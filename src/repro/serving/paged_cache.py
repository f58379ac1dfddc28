"""Paged KV cache — block pools, block tables, and a host-side allocator.

The paged representation is the substrate of the paper's *VRAM management
alignment* component: different vendors (instances) run different
``block_size`` and page *layout*; `repro.core.compat.layout` converts
between them.

Page layouts (axis order of one pool):
  "nbhd": (num_blocks, block_size, kv_heads, head_dim)   token-major (vLLM-ish)
  "nhbd": (num_blocks, kv_heads, block_size, head_dim)   head-major
  "nhdb": (num_blocks, kv_heads, head_dim, block_size)   dim-major (FT-ish)

The canonical (wire) form of one sequence's KV is the flattened 1-D view of
(S, kv_heads, head_dim) — the paper's "convert to one-dimensional tensor
before transmission" method.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LAYOUTS = ("nbhd", "nhbd", "nhdb")

# permutation from canonical page (block, kv, hd) to each layout
_FROM_CANON = {"nbhd": (0, 1, 2), "nhbd": (1, 0, 2), "nhdb": (1, 2, 0)}


@dataclasses.dataclass(frozen=True)
class KVPageSpec:
    """Vendor-specific VRAM management description of one instance."""
    block_size: int
    layout: str = "nbhd"
    dtype: str = "bfloat16"
    kv_heads: int = 1
    head_dim: int = 1

    def __post_init__(self):
        assert self.layout in LAYOUTS, self.layout

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def page_shape(self) -> Tuple[int, ...]:
        canon = (self.block_size, self.kv_heads, self.head_dim)
        perm = _FROM_CANON[self.layout]
        return tuple(canon[i] for i in perm)

    def pool_shape(self, num_blocks: int) -> Tuple[int, ...]:
        return (num_blocks,) + self.page_shape()

    def blocks_for(self, seq_len: int) -> int:
        return -(-seq_len // self.block_size)


def pages_from_canonical(spec: KVPageSpec, canon: jax.Array) -> jax.Array:
    """(nb, block, kv, hd) canonical pages → layout pages."""
    perm = _FROM_CANON[spec.layout]
    return jnp.transpose(canon, (0,) + tuple(p + 1 for p in perm))


def pages_to_canonical(spec: KVPageSpec, pages: jax.Array) -> jax.Array:
    """layout pages → (nb, block, kv, hd) canonical pages."""
    perm = _FROM_CANON[spec.layout]
    inv = [0] * 3
    for i, p in enumerate(perm):
        inv[p] = i
    return jnp.transpose(pages, (0,) + tuple(i + 1 for i in inv))


def init_pool(spec: KVPageSpec, num_blocks: int) -> jax.Array:
    return jnp.zeros(spec.pool_shape(num_blocks), spec.jdtype)


# --------------------------------------------------------------------------- #
# jnp pool ops (reference implementations; Pallas kernels in repro.kernels)
# --------------------------------------------------------------------------- #
def gather_sequence(spec: KVPageSpec, pool: jax.Array, block_ids: jax.Array,
                    seq_len: int) -> jax.Array:
    """Gather one sequence from a pool → canonical (seq_len, kv, hd).

    block_ids: (nb,) int32; seq_len static (host knows it)."""
    pages = pool[block_ids]                                # (nb, *layout)
    canon = pages_to_canonical(spec, pages)                # (nb, bs, kv, hd)
    flat = canon.reshape(-1, spec.kv_heads, spec.head_dim)
    return flat[:seq_len]


def scatter_sequence(spec: KVPageSpec, pool: jax.Array, block_ids: jax.Array,
                     kv_canon: jax.Array) -> jax.Array:
    """Write canonical (S, kv, hd) into pool pages at ``block_ids``.

    S is padded up to a whole number of blocks internally."""
    s = kv_canon.shape[0]
    nb = block_ids.shape[0]
    pad = nb * spec.block_size - s
    assert pad >= 0, (s, nb, spec.block_size)
    kv_pad = jnp.pad(kv_canon.astype(spec.jdtype), ((0, pad), (0, 0), (0, 0)))
    canon = kv_pad.reshape(nb, spec.block_size, spec.kv_heads, spec.head_dim)
    return pool.at[block_ids].set(pages_from_canonical(spec, canon))


def scatter_sequence_overlay(spec: KVPageSpec, pool: jax.Array,
                             block_ids: jax.Array, kv_canon: jax.Array,
                             front: int) -> jax.Array:
    """Write canonical (S, kv, hd) into pool pages at ``block_ids`` starting
    ``front`` rows into the first block, preserving existing rows outside
    ``[front, front + S)``.

    Boundary-only read-modify-write: only the first and last page are read
    back (the head rows before ``front`` and the tail rows after the chunk);
    interior pages are fully covered by the incoming stream. ``front`` and
    ``S`` are host-known, so the chunk's streamed re-page costs one gather
    of at most two pages plus one scatter — not a full readback of every
    touched page."""
    s = kv_canon.shape[0]
    nb = block_ids.shape[0]
    bs = spec.block_size
    back = nb * bs - front - s
    assert 0 <= front < bs and back >= 0, (front, s, nb, bs)
    head = pages_to_canonical(spec, pool[block_ids[:1]])[0, :front]
    tail = pages_to_canonical(spec, pool[block_ids[-1:]])[0, bs - back:]
    full = jnp.concatenate(
        [head.astype(spec.jdtype), kv_canon.astype(spec.jdtype),
         tail.astype(spec.jdtype)], axis=0)
    canon = full.reshape(nb, bs, spec.kv_heads, spec.head_dim)
    return pool.at[block_ids].set(pages_from_canonical(spec, canon))


def append_token(spec: KVPageSpec, pool: jax.Array, block_ids: jax.Array,
                 slot: jax.Array, kv_tok: jax.Array,
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """Write one token's KV per sequence during decode.

    block_ids: (B,) physical block of each seq's current page;
    slot: (B,) offset within the block; kv_tok: (B, kv, hd). With
    ``layer``, ``pool`` stacks every layer's pool (L, N, ...) and the
    token lands in pool ``layer``, written in place in the stack."""
    kv_tok = kv_tok.astype(spec.jdtype)
    at = (block_ids,) + {"nbhd": (slot,), "nhbd": (slice(None), slot),
                         "nhdb": (slice(None), slice(None), slot)}[spec.layout]
    if layer is not None:
        at = (layer,) + at
    return pool.at[at].set(kv_tok)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, seq_lens: jax.Array,
                        spec: KVPageSpec, scale: Optional[float] = None,
                        window: int = 0,
                        layer: Optional[jax.Array] = None) -> jax.Array:
    """Decode attention against paged KV. Reference (jnp gather) path:
    gathers every slot's whole block table.

    q: (B, 1, H, hd); block_table: (B, max_blocks); seq_lens: (B,) lengths
    INCLUDING the current token. ``window`` > 0 masks a sliding window.
    With ``layer``, the pools stack every layer's (L, N, ...) and pool
    ``layer`` is read. Returns (B, 1, H, hd)."""
    b, _, h, hd = q.shape
    max_b = block_table.shape[1]
    kv = spec.kv_heads
    ids = block_table.reshape(-1)
    if layer is not None:
        ids = (layer, ids)
    kp = pages_to_canonical(spec, k_pool[ids])
    vp = pages_to_canonical(spec, v_pool[ids])
    s_max = max_b * spec.block_size
    k = kp.reshape(b, s_max, kv, hd)
    v = vp.reshape(b, s_max, kv, hd)
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    grp = h // kv
    qg = q.reshape(b, 1, kv, grp, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max)[None]
    ok = pos < seq_lens[:, None]
    if window > 0:
        ok &= pos >= (seq_lens[:, None] - window)
    mask = jnp.where(ok, 0.0, -1e30)
    scores = scores + mask[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Host-side block allocator (one per instance). Invariants tested with
# hypothesis: a live block is owned by exactly one sequence; free+owned
# partitions the pool.
# --------------------------------------------------------------------------- #
class BlockAllocator:
    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._owned: Dict[str, List[int]] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, seq_id: str, n: int) -> List[int]:
        if len(self._free) < n:
            raise MemoryError(
                f"paged pool exhausted: want {n}, free {len(self._free)}")
        blocks = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(seq_id, []).extend(blocks)
        return blocks

    def blocks_of(self, seq_id: str) -> List[int]:
        return list(self._owned.get(seq_id, []))

    def free(self, seq_id: str) -> int:
        blocks = self._owned.pop(seq_id, [])
        self._free.extend(reversed(blocks))
        return len(blocks)

    def transfer_block(self, from_id: str, to_id: str, block_id: int) -> None:
        """Move one block's ownership between owners without touching the
        free list (prefix-cache adoption of a sequence's prompt blocks)."""
        owned = self._owned.get(from_id)
        if owned is None or block_id not in owned:
            raise ValueError(f"block {block_id} is not owned by {from_id!r}")
        owned.remove(block_id)
        if not owned:
            self._owned.pop(from_id, None)
        self._owned.setdefault(to_id, []).append(block_id)

    def free_block(self, seq_id: str, block_id: int) -> None:
        """Return a single owned block to the free list (prefix-cache
        eviction frees blocks one at a time, LRU order)."""
        owned = self._owned.get(seq_id)
        if owned is None or block_id not in owned:
            raise ValueError(f"block {block_id} is not owned by {seq_id!r}")
        owned.remove(block_id)
        if not owned:
            self._owned.pop(seq_id, None)
        self._free.append(block_id)

    def check_invariants(self) -> None:
        owned = [b for bs in self._owned.values() for b in bs]
        assert len(set(owned)) == len(owned), "double-owned block"
        assert set(owned).isdisjoint(self._free), "owned block in free list"
        assert len(owned) + len(self._free) == self.num_blocks, "leaked block"
