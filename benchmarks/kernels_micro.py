"""Kernel micro-benchmarks: Pallas (interpret=True, CPU) vs pure-jnp oracle.

Absolute µs on CPU interpret mode are NOT TPU performance — the value here
is (a) correctness at benchmark shapes, (b) the bytes/flops each kernel
moves (roofline inputs), (c) a regression guard on the reference path.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.serving.paged_cache import KVPageSpec


def _t(fn, *args, reps=3, **kw):
    fn(*args, **kw).block_until_ready()          # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def main() -> list:
    rows = []
    print("== kernel micro (CPU interpret vs jnp oracle) ==")
    print(f"{'kernel':34s} {'shape':28s} {'ref_us':>10s} {'max_err':>9s}")

    ks = jax.random.split(jax.random.key(0), 3)
    for (b, h, kv, s, d) in [(1, 8, 2, 256, 64), (2, 16, 8, 512, 128)]:
        q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, kv, s, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, kv, s, d), jnp.bfloat16)
        t_ref = _t(ref.flash_attention_ref, q, k, v)
        got = ops.flash_attention(q, k, v, force_interpret=True)
        err = float(jnp.max(jnp.abs(
            got.astype(jnp.float32)
            - ref.flash_attention_ref(q, k, v).astype(jnp.float32))))
        name = "flash_attention(causal)"
        print(f"{name:34s} b{b} h{h}/{kv} s{s} d{d:<6d} {t_ref:10.0f} {err:9.3f}")
        rows.append((name, t_ref, err))
        assert err < 3e-2

    for (b, h, kv, d, bs, pages) in [(4, 8, 2, 64, 16, 8),
                                     (8, 16, 8, 128, 16, 16)]:
        n, layers, layer = b * pages + 1, 2, 1
        q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (layers, n, bs, kv, d), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (layers, n, bs, kv, d), jnp.bfloat16)
        table = jnp.asarray(
            np.random.default_rng(0).permutation(n - 1)[:b * pages]
            .reshape(b, pages) + 1, jnp.int32)
        lens = jnp.full((b,), bs * pages - 3, jnp.int32)
        t_ref = _t(ref.paged_attention_ref, q, kp[layer], vp[layer], table,
                   lens)
        got = ops.paged_attention(q, kp, vp, jnp.int32(layer), table, lens,
                                  force_interpret=True)
        err = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) -
            ref.paged_attention_ref(q, kp[layer], vp[layer], table, lens)
            .astype(jnp.float32))))
        name = "paged_attention(decode)"
        print(f"{name:34s} b{b} h{h}/{kv} {pages}p×{bs} d{d:<3d} "
              f"{t_ref:10.0f} {err:9.3f}")
        rows.append((name, t_ref, err))
        assert err < 3e-2

    for (src_l, dst_l, sbs, dbs) in [("nbhd", "nhdb", 16, 8),
                                     ("nhbd", "nbhd", 8, 16)]:
        kvh, hd, seq = 8, 128, 250
        src = KVPageSpec(sbs, src_l, "bfloat16", kvh, hd)
        dst = KVPageSpec(dbs, dst_l, "bfloat16", kvh, hd)
        sp = jax.random.normal(ks[0], src.pool_shape(src.blocks_for(seq) + 1)
                               ).astype(jnp.bfloat16)
        dpool = jnp.zeros(dst.pool_shape(dst.blocks_for(seq) + 1),
                          jnp.bfloat16)
        sb = jnp.arange(1, src.blocks_for(seq) + 1, dtype=jnp.int32)
        db = jnp.arange(1, dst.blocks_for(seq) + 1, dtype=jnp.int32)
        t_ref = _t(ref.repack_ref, src, dst, sp, sb, dpool, db, seq)
        got = ops.repack(src, dst, sp, sb, dpool, db, seq,
                         force_interpret=True)
        want = ref.repack_ref(src, dst, sp, sb, dpool, db, seq)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        name = f"kv_repack({src_l}{sbs}→{dst_l}{dbs})"
        print(f"{name:34s} seq{seq} kv{kvh} hd{hd:<7d} {t_ref:10.0f} {err:9.3f}")
        rows.append((name, t_ref, err))
        assert err == 0.0
    return rows


if __name__ == "__main__":
    main()
