"""The served path's kernels and one whole Qwen3-4B decode step compile
for a described TPU v5e at real widths — the chip's own compiler refuses
what interpret mode accepts (unaligned slices, too much fast memory, a
program larger than the chip). Nothing runs: these are compiles only.

The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the one given this file
loads the TPU compiler."""
import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as S
from repro.configs import get_config
from repro.core import disagg
from repro.kernels import flash_attention as _fa
from repro.kernels import ops
from repro.kernels import paged_attention as _pa
from repro.models import model as M
from repro.serving.engine import page_specs_for
from repro.serving.paged_cache import KVPageSpec
from tests.conftest import hlo_without_metadata

HBM_BYTES = 16 * 2**30                     # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip program cannot be read back from the persistent
    # cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The backend here is the CPU, which the served path would refuse a
    compiled kernel on; these compiles target the described chip."""
    monkeypatch.setattr(ops, "_interpret", lambda force: bool(force))


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("kv_heads,head_dim,layout,dtype", [
    (8, 128, "nbhd", "bfloat16"),           # Qwen3-4B KV
    (8, 128, "nhbd", "float32"),
    (1, 512, "nbhd", "bfloat16"),           # MLA latent ckv
    (1, 64, "nhbd", "bfloat16"),            # MLA rope kpe
])
def test_repage_kernel_compiles(one_chip, compiled_kernels, block_size,
                                kv_heads, head_dim, layout, dtype):
    spec = KVPageSpec(block_size, layout, dtype, kv_heads, head_dim)
    layers, blocks, chunk, front = 36, 512, 256, 3
    fn = jax.jit(partial(disagg._repage_pool_body, spec, front=front,
                         rmw=True, kernel=True))
    nb = -(-(front + chunk) // block_size)
    compiled = fn.lower(
        _sds(one_chip, (layers,) + spec.pool_shape(blocks), dtype),
        _sds(one_chip, (nb + 4,), "int32"),
        _sds(one_chip, (layers, chunk, kv_heads, head_dim), dtype),
        _sds(one_chip, (), "int32")).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("b,bs,pages,blocks", [
    (16, 8, 320, 1370),                     # the qwen3-4b.chat D engine
    (8, 16, 64, 1024),                      # 16-token pages
])
@pytest.mark.parametrize("window", [0, 256])
def test_paged_attention_compiles(one_chip, window, b, bs, pages, blocks):
    """The length-bounded decode kernel over 36 stacked bf16 pools at
    Qwen3-4B's KV widths: the qwen3-4b.chat cell's D engine (16 slots of
    320 pages of 8 tokens) and a pool of 16-token pages."""
    h, kv, d, layers = 32, 8, 128, 36
    fn = jax.jit(partial(_pa.paged_attention, window=window,
                         interpret=False))
    pool = _sds(one_chip, (layers, blocks, bs, kv, d), "bfloat16")
    compiled = fn.lower(
        _sds(one_chip, (b, h, d), "bfloat16"), pool, pool,
        _sds(one_chip, (), "int32"),
        _sds(one_chip, (b, pages), "int32"),
        _sds(one_chip, (b,), "int32")).compile()
    assert _has_kernel(compiled)


@pytest.fixture
def kernel_path(compiled_kernels, monkeypatch):
    """The backend here is the CPU, on which the model takes the reference
    decode attention; these compiles take the chip's path, the kernel."""
    monkeypatch.setattr(ops, "paged_decode_path",
                        lambda spec: "paged_kernel")


@pytest.mark.parametrize("window", [0, 256])
def test_flash_attention_compiles(one_chip, window):
    s, h, kv, d = 2048, 32, 8, 128
    fn = jax.jit(partial(_fa.flash_attention, causal=True, window=window,
                         interpret=False))
    compiled = fn.lower(
        _sds(one_chip, (1, h, s, d), "bfloat16"),
        _sds(one_chip, (1, kv, s, d), "bfloat16"),
        _sds(one_chip, (1, kv, s, d), "bfloat16")).compile()
    assert _has_kernel(compiled)


def test_qwen3_4b_decode_step_fits_one_chip(one_chip, kernel_path):
    """The decode step chip_smoke.py serves — 36 layers at published
    widths, its batch and its D pool, pools donated as the engine donates
    them, attention in the paged kernel — compiles and fits the chip's
    16 GiB."""
    cfg = get_config("qwen3-4b")
    specs = page_specs_for(cfg, S.D_VENDOR.block_size, S.D_VENDOR.layout,
                           S.D_VENDOR.kv_dtype)
    b = S.MAX_BATCH
    blocks = S.d_pool_blocks(S.LENGTHS, S.MAX_NEW, S.D_VENDOR.block_size, b)
    per_seq = -(-(max(S.LENGTHS) + S.MAX_NEW) // S.D_VENDOR.block_size)
    place = lambda t: jax.tree.map(                          # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    params = place(M.abstract_params(cfg))
    caches = place(jax.eval_shape(
        lambda: M.init_paged_caches(cfg, specs, blocks, batch=b)))

    fn = jax.jit(lambda p, t, sl, bt, wb, ws, c: M.decode_step_paged(
        p, cfg, t, sl, bt, wb, ws, c, specs), donate_argnums=(6,))
    compiled = fn.lower(params, _sds(one_chip, (b, 1), "int32"),
                        _sds(one_chip, (b,), "int32"),
                        _sds(one_chip, (b, per_seq), "int32"),
                        _sds(one_chip, (b,), "int32"),
                        _sds(one_chip, (b,), "int32"), caches).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    assert ma.alias_size_in_bytes >= pool      # the pools update in place
    assert total <= HBM_BYTES, (total, ma)
    assert _has_kernel(compiled)
    # the kernel reads each layer's pages inside the stack: no pool, per
    # layer or stacked, is copied or sliced out whole
    shape = f"{blocks},{S.D_VENDOR.block_size},{cfg.num_kv_heads},"
    assert not [line for line in compiled.as_text().splitlines()
                if shape in line and any(
                    f" {op}(" in line for op in ("copy", "copy-start",
                                                 "dynamic-slice"))]


def test_decode_step_scopes_change_metadata_only(one_chip, kernel_path,
                                                 monkeypatch):
    """The attention / mlp / lm_head scopes reach the chip's optimised
    decode program as op metadata and change nothing else in it (two
    layers at Qwen3-4B widths). The paged kernel's custom call sits under
    ``attention``, and no instruction copies or slices a whole per-layer
    pool out of the stack."""
    cfg = get_config("qwen3-4b").with_(num_layers=2)
    specs = page_specs_for(cfg, 8, "nbhd", "bfloat16")
    b, blocks, per_seq = 4, 64, 16
    place = lambda t: jax.tree.map(                          # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    args = (place(M.abstract_params(cfg)),
            _sds(one_chip, (b, 1), "int32"), _sds(one_chip, (b,), "int32"),
            _sds(one_chip, (b, per_seq), "int32"),
            _sds(one_chip, (b,), "int32"), _sds(one_chip, (b,), "int32"),
            place(jax.eval_shape(
                lambda: M.init_paged_caches(cfg, specs, blocks, batch=b))))

    def compiled_text():
        fn = jax.jit(lambda p, t, sl, bt, wb, ws, c: M.decode_step_paged(
            p, cfg, t, sl, bt, wb, ws, c, specs), donate_argnums=(6,))
        return fn.lower(*args).compile().as_text()

    scoped = compiled_text()
    for scope in ("attention", "mlp", "lm_head"):
        assert f"/{scope}/" in scoped, scope
    kernels = [line for line in scoped.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert kernels and all("/attention/" in k for k in kernels), kernels
    assert f"[{blocks},8,8,128]" not in scoped    # no per-layer pool
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_text()
    assert "/attention/" not in plain
    assert hlo_without_metadata(scoped) == hlo_without_metadata(plain)
