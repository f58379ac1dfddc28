#!/usr/bin/env python3
"""Run the disaggregated P→D serving path once on TPU chips at Qwen3-4B's
published widths, and check what it serves.

    python chip_smoke.py               # one chip: the single-process path
    python chip_smoke.py --four-chips  # 2P×2D cluster, one worker per chip,
                                       # checked against the one-chip path

One chip: a ``GlobalScheduler`` drives a prefill engine (block 16, nhbd
layout, tp 2) and a decode engine (block 8, nbhd, tp 1), both bf16 and
sharing one parameter tree, so every handoff re-pages across mismatched
vendor profiles. Prompts stream in chunks over a raw bf16 wire, first
through the in-process connector, then through shared memory. The first
token's logits are checked against a float32 reference forward pass.

Weights are random from ``SEED``; nothing is downloaded or read from
outside the checkout. Any failure, and a host where JAX finds no TPU,
exits non-zero. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Each phase is a function of a ``ModelConfig`` (the CPU tests drive them
at a tiny size); ``main`` refuses every backend but the TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

try:
    from repro.configs import ModelConfig, get_config  # noqa: E402
    from repro.core.compat.precision import WireFormat  # noqa: E402
    from repro.serving.engine import VendorProfile  # noqa: E402
    from repro.serving.request import Request  # noqa: E402
except ImportError as e:      # a copy of this script without the repo
    sys.exit(f"chip_smoke.py: cannot import the repro package from "
             f"{os.path.join(ROOT, 'src')}: {e}")

MODEL = "qwen3-4b"
# P and D vendor profiles that differ in block size, page layout and TP
# degree: every handoff runs the compat re-page
P_VENDOR = VendorProfile("vendorP", block_size=16, layout="nhbd",
                         kv_dtype="bfloat16", tp=2)
D_VENDOR = VendorProfile("vendorD", block_size=8, layout="nbhd",
                         kv_dtype="bfloat16", tp=1)
WIRE = WireFormat("raw", "bfloat16")
# traffic: 8 requests in 4 prompt-length buckets (one compiled prefill
# program per bucket), 32 new tokens each
LENGTHS = (128, 256, 512, 1024) * 2
MAX_NEW = 32
PREFILL_CHUNK = 256
MAX_BATCH = 8
SEED = 0
# served-vs-reference first-token logits: relative L2 error of bf16
# compute through every layer against a float32 forward pass
REL_L2_TOL = 0.1
HBM_BYTES = 16 * 2**30                # one TPU v5e chip


def fail(msg: str) -> None:
    print(f"chip_smoke.py: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


# --------------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------------- #
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (backend compile
    time is the cache read on a persistent-cache hit), and backend
    compile count."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.programs += event == self.EVENTS[-1]

    def snapshot(self) -> Tuple[float, int]:
        return self.seconds, self.programs


def param_count(cfg: ModelConfig) -> int:
    import jax

    from repro.models import model as M
    return sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(M.abstract_params(cfg)))


def d_pool_blocks(lengths: Sequence[int], max_new: int, block_size: int,
                  max_batch: int) -> int:
    """Decode pool that holds ``max_batch`` of the longest sequences, plus
    the engine's scratch page."""
    per_seq = -(-(max(lengths) + max_new) // block_size)
    return max_batch * per_seq + 1


def build_requests(cfg: ModelConfig, lengths: Sequence[int], max_new: int,
                   seed: int, tag: str = "req") -> List[Request]:
    rng = np.random.default_rng(seed)
    return [Request(req_id=f"{tag}-{i:02d}-{n}",
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]


def build_engines(cfg: ModelConfig, params, lengths: Sequence[int],
                  max_new: int, max_batch: int = MAX_BATCH):
    """The P engine (no paged pool) and the D engine, sharing ``params``."""
    from repro.serving.engine import Engine
    max_seq = max(lengths) + max_new
    p = Engine("P0", cfg, params, P_VENDOR, num_blocks=1,
               max_batch=max_batch, max_seq_len=max_seq, role="prefill")
    d = Engine("D0", cfg, params, D_VENDOR,
               num_blocks=d_pool_blocks(lengths, max_new,
                                        D_VENDOR.block_size, max_batch),
               max_batch=max_batch, max_seq_len=max_seq, role="decode")
    return p, d


def pool_bytes(engine) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(engine.caches))


def shm_capacity() -> int:
    """Staging capacity for the shared-memory connector: a quarter of the
    free /dev/shm, at most 1 GiB (backpressure beyond that)."""
    st = os.statvfs("/dev/shm")
    return int(min(1 << 30, st.f_bavail * st.f_frsize // 4))


def serve(p, d, requests: List[Request], connector: str,
          prefill_chunk: int, clock: CompileClock = None) -> Dict[str, Any]:
    """Serve ``requests`` through GlobalScheduler → DisaggPipeline with
    streamed chunked prefill; returns tokens and counts."""
    import jax

    from repro.configs.base import ConnectorConfig
    from repro.core.disagg import DisaggPipeline
    from repro.serving.scheduler import GlobalScheduler

    conn = ConnectorConfig(kind=connector,
                           buffer_capacity_bytes=shm_capacity()).build()
    pipeline = DisaggPipeline(conn, WIRE)
    sched = GlobalScheduler(pipeline, prefill_chunk=prefill_chunk)
    sched.add_instance(p)
    sched.add_instance(d)
    c0 = clock.snapshot() if clock else (0.0, 0)
    t0 = time.perf_counter()
    sched.run(requests, max_ticks=100_000)
    jax.block_until_ready(d.caches)
    wall = time.perf_counter() - t0
    c1 = clock.snapshot() if clock else (0.0, 0)
    ts = pipeline.transfer.stats
    conn.close()
    return {"tokens": {r.req_id: list(r.output_tokens) for r in requests},
            "finished": sched.stats.finished, "failed": sched.stats.failed,
            "wall_s": wall, "compile_s": c1[0] - c0[0],
            "programs": c1[1] - c0[1], "chunks": ts.chunks,
            "wire_bytes": ts.bytes_moved}


def check_finished(requests: List[Request], result: Dict[str, Any]) -> None:
    short = {r.req_id: len(r.output_tokens) for r in requests
             if len(r.output_tokens) != r.max_new_tokens}
    if result["finished"] != len(requests) or short:
        fail(f"{result['finished']}/{len(requests)} finished; requests "
             f"without their max_new_tokens: {short}")


def reference_logits(cfg: ModelConfig, params, prompt: np.ndarray
                     ) -> np.ndarray:
    """Plain forward pass over the whole prompt (``M.prefill``), float32
    compute and matmul precision over the stored weights: the last
    position's logits (V,)."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    tokens = jnp.asarray(prompt, jnp.int32)[None]

    @jax.jit
    def fwd(params, tokens):
        caches = M.init_caches(cfg32, 1, tokens.shape[1], jnp.float32)
        return M.prefill(params, cfg32, {"tokens": tokens}, caches)[0]

    with jax.default_matmul_precision("float32"):
        return np.asarray(fwd(params, tokens)[0], np.float32)


def compare_logits(got: np.ndarray, want: np.ndarray) -> Dict[str, Any]:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return {"rel_l2": float(np.linalg.norm(got - want)
                            / max(np.linalg.norm(want), 1e-30)),
            "max_abs": float(np.max(np.abs(got - want))),
            "ref_max_abs": float(np.max(np.abs(want))),
            "argmax_equal": bool(np.argmax(got) == np.argmax(want)),
            "finite": bool(np.all(np.isfinite(got)))}


def monolithic_first(p, req: Request) -> Tuple[int, np.ndarray]:
    """The same request prefilled in one pass on the P engine (no chunks):
    its first token and last-position logits."""
    fresh = Request(req_id=req.req_id + "-mono", prompt=req.prompt,
                    max_new_tokens=req.max_new_tokens)
    pkg = p.prefill(fresh)
    return pkg["first_token"], fresh.first_logits


def check_logits(cfg: ModelConfig, params, p, served: List[Request]
                 ) -> List[Dict[str, Any]]:
    """Served first-token logits of the shortest and the longest request
    against the float32 reference, and the longest one's chunked (served)
    prefill against a monolithic prefill on the same P engine."""
    short = min(served, key=lambda r: r.prompt_len)
    long = max(served, key=lambda r: r.prompt_len)
    out = []
    for req in (short, long):
        cmp = compare_logits(req.first_logits,
                             reference_logits(cfg, params, req.prompt))
        out.append(dict(cmp, what="served vs float32 reference",
                        req=req.req_id, prompt_len=req.prompt_len))
    tok, logits = monolithic_first(p, long)
    cmp = compare_logits(long.first_logits, logits)
    out.append(dict(cmp, what="chunked vs monolithic prefill",
                    req=long.req_id, prompt_len=long.prompt_len,
                    tokens=(long.output_tokens[0], tok)))
    return out


def cluster_spec(cfg: ModelConfig, lengths: Sequence[int], max_new: int,
                 seed: int):
    """2P×2D, each engine built as ``build_engines`` builds its pair."""
    from repro.serving.multiproc import ClusterSpec, EngineSpec
    max_seq = max(lengths) + max_new
    blocks = d_pool_blocks(lengths, max_new, D_VENDOR.block_size, MAX_BATCH)
    return ClusterSpec(
        p=tuple(EngineSpec(f"P{i}", cfg, P_VENDOR, params_seed=seed,
                           num_blocks=1, max_batch=MAX_BATCH,
                           max_seq_len=max_seq, role="prefill")
                for i in range(2)),
        d=tuple(EngineSpec(f"D{i}", cfg, D_VENDOR, params_seed=seed,
                           num_blocks=blocks, max_batch=MAX_BATCH,
                           max_seq_len=max_seq, role="decode")
                for i in range(2)))


def serve_cluster(cfg: ModelConfig, requests: List[Request],
                  lengths: Sequence[int], max_new: int, seed: int,
                  prefill_chunk: int, timeout_s: float = 1200.0
                  ) -> Tuple[Dict[str, List[int]], Any]:
    """2P×2D ``ClusterRuntime``, one worker process per chip, KV over the
    shared-memory connector. The caller must not have touched JAX."""
    from repro.serving.multiproc import ClusterRuntime
    rt = ClusterRuntime(cluster_spec(cfg, lengths, max_new, seed),
                        wire=WIRE, prefill_chunk=prefill_chunk,
                        connector_kwargs={
                            "buffer_capacity_bytes": shm_capacity()},
                        stall_timeout_s=timeout_s)
    rt.start(spawn_timeout_s=timeout_s)
    try:
        tokens = rt.serve(requests, max_wall_s=timeout_s)
    finally:
        rt.shutdown()
    return tokens, rt


# --------------------------------------------------------------------------- #
# Entry points
# --------------------------------------------------------------------------- #
def _device() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _require_tpu() -> Dict[str, Any]:
    dev = _device()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        fail(f"JAX found no TPU (backend {dev['platform']!r}); this smoke "
             f"run does not fall back to another device")
    return dev


def one_chip() -> Dict[str, Any]:
    import jax

    from repro.models import model as M
    from repro.serving.jit_cache import enable_jit_cache

    cache_dir = enable_jit_cache()
    clock = CompileClock()
    dev = _require_tpu()
    print(f"compile cache: {cache_dir}", flush=True)
    cfg = get_config(MODEL)
    t0 = time.perf_counter()
    params = jax.block_until_ready(M.init_params(jax.random.key(SEED), cfg))
    print(f"model: {cfg.name} [{cfg.source}] {cfg.num_layers} layers, "
          f"d_model {cfg.d_model}, {param_count(cfg)} params in "
          f"{cfg.param_dtype}, built in {time.perf_counter() - t0:.3f} s",
          flush=True)
    p, d = build_engines(cfg, params, LENGTHS, MAX_NEW)
    print(f"engines: P0 prefill block {P_VENDOR.block_size}/"
          f"{P_VENDOR.layout}/tp{P_VENDOR.tp}/{P_VENDOR.kv_dtype} (no paged "
          f"pool); D0 decode block {D_VENDOR.block_size}/{D_VENDOR.layout}/"
          f"tp{D_VENDOR.tp}/{D_VENDOR.kv_dtype}, pool "
          f"{d.allocator.num_blocks} blocks = {pool_bytes(d)} bytes",
          flush=True)

    # warm-up over the same shapes: every program compiles here
    warm = build_requests(cfg, LENGTHS, MAX_NEW, SEED + 1, tag="warm")
    r = serve(p, d, warm, "inproc", PREFILL_CHUNK, clock)
    check_finished(warm, r)
    print(f"warm-up: {r['finished']} requests, compile {r['compile_s']:.3f} "
          f"s ({r['programs']} programs), wall {r['wall_s']:.3f} s",
          flush=True)

    results = {}
    for conn in ("inproc", "shm"):
        reqs = build_requests(cfg, LENGTHS, MAX_NEW, SEED)
        r = serve(p, d, reqs, conn, PREFILL_CHUNK, clock)
        check_finished(reqs, r)
        ntok = sum(len(t) for t in r["tokens"].values())
        print(f"serve[{conn}]: {r['finished']}/{len(reqs)} finished through "
              f"P0→D0, {ntok} tokens, {r['chunks']} KV chunks, "
              f"{r['wire_bytes']} wire bytes; serving {r['wall_s']:.3f} s, "
              f"compile {r['compile_s']:.3f} s ({r['programs']} programs)",
              flush=True)
        results[conn] = (reqs, r)
    reqs, r = results["inproc"]
    if results["shm"][1]["tokens"] != r["tokens"]:
        fail("shm-connector tokens differ from in-process tokens")
    print("serve[shm]: tokens identical to serve[inproc]", flush=True)

    for c in check_logits(cfg, params, p, reqs):
        extra = ""
        if "tokens" in c:
            extra = (f", first token {c['tokens'][0]} vs {c['tokens'][1]} "
                     f"(equal={c['tokens'][0] == c['tokens'][1]})")
        print(f"check {c['req']} ({c['prompt_len']} prompt tokens), "
              f"{c['what']}: rel_l2={c['rel_l2']:.6f} (tolerance "
              f"{REL_L2_TOL}), max_abs={c['max_abs']:.6f} of "
              f"{c['ref_max_abs']:.6f}, argmax equal={c['argmax_equal']}"
              f"{extra}", flush=True)
        if not c["finite"] or c["rel_l2"] > REL_L2_TOL:
            fail(f"{c['req']}: {c['what']} outside tolerance: {c}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak} of {HBM_BYTES}", flush=True)
    return dev


def serve_single(cfg: ModelConfig, lengths: Sequence[int], max_new: int,
                 seed: int, prefill_chunk: int) -> Dict[str, Any]:
    """The single-process path from a seed: build, serve, check."""
    import jax

    from repro.models import model as M
    params = M.init_params(jax.random.key(seed), cfg)
    p, d = build_engines(cfg, params, lengths, max_new)
    reqs = build_requests(cfg, lengths, max_new, seed)
    r = serve(p, d, reqs, "inproc", prefill_chunk)
    check_finished(reqs, r)
    return r


def _reference_child(out) -> None:
    """One-chip single-process serving of the smoke requests, in a child
    process (the four-chip parent stays off JAX)."""
    from repro.serving.jit_cache import enable_jit_cache
    enable_jit_cache()
    dev = _require_tpu()
    r = serve_single(get_config(MODEL), LENGTHS, MAX_NEW, SEED,
                     PREFILL_CHUNK)
    out.put({"tokens": r["tokens"], "device": dev, "wall_s": r["wall_s"]})


def four_chips() -> Dict[str, Any]:
    from repro.serving.multiproc import chips

    n = chips.tpu_chip_count()
    print(f"host: {n} TPU chip(s) on the PCI bus", flush=True)
    if n < 4:
        fail(f"--four-chips needs 4 TPU chips, this host has {n}")
    cfg = get_config(MODEL)

    # the one-chip reference runs in a child that exits before the
    # workers start: the chip belongs to one process at a time
    t0 = time.perf_counter()
    try:
        ref = chips.run_in_child(_reference_child, env=chips.worker_env(0))
    except RuntimeError as e:
        fail(f"one-chip reference: {e}")
    print(f"reference: one chip ({ref['device']['kind']}), single-process "
          f"path, {len(ref['tokens'])} requests in "
          f"{time.perf_counter() - t0:.3f} s (child, compile included)",
          flush=True)

    reqs = build_requests(cfg, LENGTHS, MAX_NEW, SEED)
    t0 = time.perf_counter()
    tokens, rt = serve_cluster(cfg, reqs, LENGTHS, MAX_NEW, SEED,
                               PREFILL_CHUNK)
    print(f"cluster: {rt.cluster.ratio()} finished {rt.stats.finished}/"
          f"{len(reqs)} in {time.perf_counter() - t0:.3f} s (spawn and "
          f"compile included), KV over shm", flush=True)
    devs = rt.worker_devices
    for iid in sorted(devs):
        print(f"worker {iid}: pid {rt.worker_pids.get(iid)} device {devs[iid]}",
              flush=True)
    if rt.stats.finished != len(reqs):
        fail(f"cluster finished {rt.stats.finished}/{len(reqs)}")
    if len(devs) != 4 or any(v["platform"] != "tpu" or v["count"] != 1
                             for v in devs.values()):
        fail(f"want 4 workers with one TPU device each, got {devs}")
    if len({v["chip"] for v in devs.values()}) != 4:
        fail(f"workers do not hold distinct chips: {devs}")
    bad = sorted(rid for rid in ref["tokens"]
                 if tokens.get(rid) != ref["tokens"][rid])
    for rid in bad:
        a, b = ref["tokens"][rid], tokens.get(rid) or []
        div = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                   min(len(a), len(b)))
        print(f"  {rid}: diverges at token {div}", file=sys.stderr)
    if bad:
        fail(f"{len(bad)}/{len(reqs)} requests differ between the 2P×2D "
             f"cluster and the one-chip path")
    print(f"check: {len(reqs)} requests token-exact between the 2P×2D "
          f"cluster and the one-chip single-process path", flush=True)
    first = devs[sorted(devs)[0]]
    return {"platform": first["platform"], "kind": first["kind"],
            "count": len({v["chip"] for v in devs.values()})}


def main(argv: Sequence[str] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2P×2D cluster across four chips and "
                         "its comparison with the one-chip path")
    args = ap.parse_args(argv)
    dev = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
