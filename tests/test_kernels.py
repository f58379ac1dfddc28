"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels import paged_attention as _pa
from repro.serving.paged_cache import KVPageSpec

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,sq,skv,d", [
    (1, 4, 4, 16, 16, 32),       # MHA, square
    (2, 8, 2, 24, 48, 64),       # GQA, rectangular, non-multiple of block
    (1, 4, 1, 7, 133, 32),       # MQA, ragged
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 9), (False, 0)])
def test_flash_attention_sweep(b, h, kv, sq, skv, d, dtype, causal, window):
    if not causal and sq != skv:
        pytest.skip("non-causal used for encoder (square) only")
    ks = jax.random.split(jax.random.key(hash((b, h, sq)) % 2**31), 3)
    q = _rand(ks[0], (b, h, sq, d), dtype)
    k = _rand(ks[1], (b, kv, skv, d), dtype)
    v = _rand(ks[2], (b, kv, skv, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=16, block_k=16, force_interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype])


def _paged_case(b, h, kv, d, bs, pages, dtype, seq_lens, *, layers=3,
                layer=1, window=0, idle=(),
                interpret=None):
    """The kernel on pool ``layer`` of a stack of ``layers`` pools against
    the reference on that layer alone. Slots in ``idle`` hold the scratch
    block 0 in every table entry, as the engine's idle slots do. With
    ``interpret`` (``pltpu.InterpretParams``) the kernel runs in the TPU
    interpreter, which simulates its copies and semaphores."""
    n_blocks = b * pages + 1
    ks = jax.random.split(jax.random.key(hash((b, h, d)) % 2**31), 4)
    q = _rand(ks[0], (b, h, d), dtype)
    k_pool = _rand(ks[1], (layers, n_blocks, bs, kv, d), dtype)
    v_pool = _rand(ks[2], (layers, n_blocks, bs, kv, d), dtype)
    rng = np.random.default_rng(0)
    table = rng.permutation(n_blocks - 1)[:b * pages].reshape(b, pages) + 1
    table[list(idle)] = 0
    table = jnp.asarray(table, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    if interpret is None:
        got = ops.paged_attention(q, k_pool, v_pool, jnp.int32(layer), table,
                                  seq_lens, window=window,
                                  force_interpret=True)
    else:
        got = _pa.paged_attention(q, k_pool, v_pool, jnp.int32(layer), table,
                                  seq_lens, window=window,
                                  interpret=interpret)
    want = ref.paged_attention_ref(q, k_pool[layer], v_pool[layer], table,
                                   seq_lens, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,d,bs,pages", [
    (2, 4, 4, 32, 8, 4),
    (3, 8, 2, 64, 16, 3),
    (1, 4, 1, 32, 4, 7),
])
@pytest.mark.parametrize("window", [0, 11])
def test_paged_attention_sweep(b, h, kv, d, bs, pages, dtype, window):
    seq_lens = np.random.default_rng(0).integers(1, bs * pages + 1, b)
    _paged_case(b, h, kv, d, bs, pages, dtype, seq_lens, window=window)


@pytest.fixture
def block_rows(monkeypatch):
    """Sets the kernel's compute block to a number of K rows, so that a
    small pool spans several blocks. The jitted wrapper reads the block at
    trace time: its traces are dropped on either side of the change."""
    def set_rows(rows):
        ops.paged_attention.clear_cache()
        monkeypatch.setattr(_pa, "BLOCK_ROWS", rows)
    yield set_rows
    ops.paged_attention.clear_cache()


# (group size, block size, lengths, window, idle slots); 4 slots of 6
# pages, compute blocks of 2 pages, so a slot spans up to 3 blocks
_CASES = {
    "edges_bs8": (4, 8, [1, 7, 8, 9], 0, ()),
    "edges_bs16": (4, 16, [1, 15, 16, 17], 0, ()),
    "block_multiples": (4, 8, [16, 32, 48, 17], 0, ()),
    "full_capacity": (4, 8, [48, 48, 47, 1], 0, ()),
    "idle_next_to_busy": (4, 8, [1, 40, 1, 23], 0, (0, 2)),
    "window_mid_page": (4, 8, [40, 9, 26, 48], 11, ()),
    "group_1": (1, 16, [5, 96, 33, 64], 0, ()),
    "group_8": (8, 8, [2, 30, 48, 12], 5, ()),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_paged_attention_lengths(case, dtype, block_rows):
    """Lengths at page and compute-block edges, idle slots, a window that
    starts mid-page, group sizes 1/4/8 and block sizes 8/16: the
    length-bounded loop reads exactly the pages the reference masks to."""
    grp, bs, seq_lens, window, idle = _CASES[case]
    kv = 2
    block_rows(2 * bs * kv)
    _paged_case(4, kv * grp, kv, 32, bs, 6, dtype, seq_lens, window=window,
                idle=idle)


@pytest.mark.parametrize("dma", ["on_wait", "eager"])
@pytest.mark.parametrize("case", ["idle_next_to_busy", "window_mid_page",
                                  "group_8"])
def test_paged_attention_copies(case, dma, block_rows):
    """In the TPU interpreter, with scratch memory filled with NaN and each
    copy landing when it is waited for (or as soon as it starts): every
    block is waited for before it is read, the double buffer is never read
    stale, and rows past a slot's last page never reach the result."""
    grp, bs, seq_lens, window, idle = _CASES[case]
    kv = 2
    block_rows(2 * bs * kv)
    _paged_case(4, kv * grp, kv, 32, bs, 6, jnp.float32, seq_lens,
                window=window, idle=idle,
                interpret=pltpu.InterpretParams(uninitialized_memory="nan",
                                                dma_execution_mode=dma))


def test_paged_attention_stacked_layer_append(block_rows):
    """The decode append writes pool ``layer`` of the stack and no other;
    the kernel then reads that layer with the new token."""
    from repro.serving import paged_cache as PC
    layers, layer, b, kv, d, bs = 4, 2, 3, 2, 32, 8
    spec = KVPageSpec(bs, "nbhd", "float32", kv, d)
    ks = jax.random.split(jax.random.key(7), 5)
    pools = [_rand(k, (layers, 10, bs, kv, d), jnp.float32) for k in ks[:2]]
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
    old_lens = np.asarray([3, 8, 20])
    blocks = table[np.arange(b), old_lens // bs]
    slots = jnp.asarray(old_lens % bs, jnp.int32)
    toks = [_rand(k, (b, kv, d), jnp.float32) for k in ks[2:4]]
    new = [PC.append_token(spec, p, blocks, slots, t, jnp.int32(layer))
           for p, t in zip(pools, toks)]
    for p, n, t in zip(pools, new, toks):
        others = np.asarray([i for i in range(layers) if i != layer])
        np.testing.assert_array_equal(np.asarray(n[others]),
                                      np.asarray(p[others]))
        np.testing.assert_array_equal(
            np.asarray(n[layer, blocks, slots]), np.asarray(t))
    q = _rand(ks[4], (b, kv * 4, d), jnp.float32)
    lens = jnp.asarray(old_lens + 1, jnp.int32)
    block_rows(bs * kv)                     # one page per compute block
    got = ops.paged_attention(q, new[0], new[1], jnp.int32(layer), table,
                              lens, force_interpret=True)
    want = ref.paged_attention_ref(q, new[0][layer], new[1][layer], table,
                                   lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL[jnp.float32])


@pytest.mark.parametrize("layout", ["nbhd", "nhbd", "nhdb"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gather_scatter_pages_sweep(layout, dtype):
    spec = KVPageSpec(block_size=8, layout=layout, dtype=dtype, kv_heads=4,
                      head_dim=16)
    pool = jax.random.normal(jax.random.key(0),
                             spec.pool_shape(10)).astype(spec.jdtype)
    ids = jnp.asarray([3, 1, 7], jnp.int32)
    got = ops.gather_pages(spec, pool, ids, force_interpret=True)
    want = ref.gather_pages_ref(spec, pool, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    canon = jax.random.normal(jax.random.key(1),
                              (3, 8, 4, 16)).astype(spec.jdtype)
    got_p = ops.scatter_pages(spec, pool, ids, canon, force_interpret=True)
    want_p = ref.scatter_pages_ref(spec, pool, ids, canon)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


@pytest.mark.parametrize("src_layout,dst_layout,src_bs,dst_bs,src_dt,dst_dt", [
    ("nbhd", "nhbd", 8, 4, "float32", "float32"),
    ("nhdb", "nbhd", 4, 16, "float32", "bfloat16"),
    ("nhbd", "nhdb", 16, 8, "bfloat16", "float32"),
])
def test_repack_vendor_alignment_sweep(src_layout, dst_layout, src_bs,
                                       dst_bs, src_dt, dst_dt):
    """The paper's Fig. 3 path: P layout/blocksize/dtype → D's, exactly."""
    kv, hd, seq = 2, 16, 27
    src = KVPageSpec(src_bs, src_layout, src_dt, kv, hd)
    dst = KVPageSpec(dst_bs, dst_layout, dst_dt, kv, hd)
    nb_s = src.blocks_for(seq)
    nb_d = dst.blocks_for(seq)
    src_pool = jax.random.normal(jax.random.key(0),
                                 src.pool_shape(nb_s + 2)).astype(src.jdtype)
    dst_pool = jnp.zeros(dst.pool_shape(nb_d + 2), dst.jdtype)
    src_blocks = jnp.arange(1, nb_s + 1, dtype=jnp.int32)
    dst_blocks = jnp.arange(1, nb_d + 1, dtype=jnp.int32)
    got = ops.repack(src, dst, src_pool, src_blocks, dst_pool, dst_blocks,
                     seq, force_interpret=True)
    want = ref.repack_ref(src, dst, src_pool, src_blocks, dst_pool,
                          dst_blocks, seq)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # token stream identical through the round trip
    src_canon = ref.gather_pages_ref(src, src_pool, src_blocks,
                                     out_dtype=dst.jdtype)
    dst_canon = ref.gather_pages_ref(dst, got, dst_blocks)
    a = src_canon.reshape(-1, kv, hd)[:seq]
    b = dst_canon.reshape(-1, kv, hd)[:seq]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
