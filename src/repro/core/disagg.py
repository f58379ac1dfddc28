"""P/D disaggregation orchestrator — the paper's §III system glue.

``DisaggPipeline`` moves prefill KV from a P instance to a D instance
through the three alignment components:

  1. precision  (``compat.precision``)  — wire dtype / int8 wire
  2. VRAM mgmt  (``compat.layout``)     — flatten-to-1D, re-page re-layout
  3. parallel   (``compat.parallel_align``) — TP merge/split of KV shards

Two handoff shapes share the same encode/materialize core:

  * ``handoff``          — monolithic: whole-prompt prefill, one wire
    payload, one re-page (the paper's baseline transmission).
  * ``begin_handoff`` / ``StreamedHandoff`` — chunked streaming: the D slot
    is reserved up front, each prefill chunk's KV is encoded and staged
    into the pinned pool while the next chunk computes, and the D instance
    re-pages chunks as they land (Mooncake-style layer/chunk-wise
    streaming); ``finalize`` ships recurrent/cross state and activates the
    slot. Per-token wire encodings (raw cast, per-token-per-head int8
    scales) make chunk splitting lossless, so streaming lands bit-identical
    pool contents vs the monolithic wire.

The wire itself is a pluggable :class:`~repro.core.transport.KVConnector`:
``send_chunk`` stages a chunk and *issues* an async read
(:class:`~repro.core.transport.TransferHandle`); ``poll_reads`` re-pages
chunks whose handles report complete. With an instant backend (inproc/shm)
a chunk is re-paged in the tick it was sent; with a modeled-latency
backend (rdma) handles complete over later ticks and the scheduler runs
decode steps while chunks are still on the wire.

The same pipeline with P == D and a raw wire is the *integrated* baseline
(prefill materializes into the local pools with no conversion), which is
what the paper's Figs. 9–10 compare against.
"""
from __future__ import annotations

import collections
import functools
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compat import parallel_align, precision
from repro.core.compat.precision import WireFormat
from repro.core.transport import KVConnector, TransferHandle, WireChunk
from repro.kernels import ops as kops
from repro.serving import paged_cache as PC
from repro.serving.engine import (Engine, kv_entries_with_start,
                                  slice_kv_entries)
from repro.serving.request import Request
from repro.serving.tracing import span


def _to_device(payload):
    """Staged wire payload (host numpy) → device arrays for materialize."""
    return jax.tree.map(
        lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x, payload)


def _repage_pool_body(spec: PC.KVPageSpec, pool: jax.Array, block_ids,
                      canon: jax.Array, lo_block, *, front: int, rmw: bool,
                      kernel: bool, interpret: bool = False) -> jax.Array:
    """Single-pass re-page of canon (count, S, kv, hd) landing ``front``
    rows into block ``lo_block``'s first page, vmapped over the layer
    count. ``lo_block`` is *traced* and ``front`` (= start % block_size)
    static: chunks at different absolute starts share one compiled
    program as long as their in-page offset matches — streaming a long
    prompt compiles per (chunk shape, offset-in-page), not per chunk.

    Unlike the legacy rmw path — which reads back *every* touched page
    and splices — the overlay scatter only reads the first/last partial
    page (jnp path) or merges partial rows inside the Pallas kernel
    (``kernel=True``), so interior pages move exactly once. ``interpret``
    runs that kernel in Pallas interpret mode (CPU tests)."""
    bs = spec.block_size
    s = canon.shape[1]
    s_tot = front + s
    nb = -(-s_tot // bs)
    use = jax.lax.dynamic_slice_in_dim(block_ids, lo_block, nb)
    if not rmw:
        if front:
            canon = jnp.pad(canon, ((0, 0), (front, 0), (0, 0), (0, 0)))
        return jax.vmap(lambda pl, cn: PC.scatter_sequence(spec, pl, use, cn)
                        )(pool, canon)
    if kernel:
        # one kernel call for every layer: the layer axis folds into the
        # page axis (layer l's pages sit at l·N + id), so the aliased pool
        # stays one unbatched array — a vmapped call would give it a batch
        # block the TPU lowering refuses
        count, n = pool.shape[0], pool.shape[1]
        cp = jnp.pad(canon, ((0, 0), (front, nb * bs - s_tot),
                             (0, 0), (0, 0)))
        cp = cp.reshape(count * nb, bs, spec.kv_heads, spec.head_dim)
        ids = (jnp.arange(count, dtype=use.dtype)[:, None] * n
               + use[None]).reshape(-1)
        out = kops.scatter_pages_overlay(
            spec, pool.reshape((count * n,) + pool.shape[2:]), ids, cp,
            front=front, seq_len=s, span=nb, force_interpret=interpret)
        return out.reshape(pool.shape)
    return jax.vmap(lambda pl, cn: PC.scatter_sequence_overlay(
        spec, pl, use, cn, front))(pool, canon)


_repage_pool = jax.jit(_repage_pool_body,
                       static_argnames=("spec", "front", "rmw", "kernel",
                                        "interpret"))


@functools.partial(jax.jit, static_argnames=("spec", "wire", "tp_p", "tp_d",
                                             "count", "front", "rmw",
                                             "kernel", "interpret"))
def _repage_kv_entry(spec: PC.KVPageSpec, k_pool: jax.Array,
                     v_pool: jax.Array, block_ids, pay, sc, lo_block, *,
                     wire: WireFormat, tp_p: int, tp_d: int, count: int,
                     front: int, rmw: bool, kernel: bool, interpret: bool):
    """One compiled program per (chunk shape, in-page offset): dequantize
    the whole shard-major slab (2·tp_p, count, S, kvs, hd) in one pass,
    realign TP shards, overlay-scatter both pools. The landing block
    index rides in traced ``lo_block`` so successive chunks of a stream
    reuse the same executable."""
    sc_j = None if sc is None else sc.reshape(pay.shape[:-1] + (1,))
    dec = precision.decode_wire(pay, sc_j, wire, spec.jdtype)
    s = pay.shape[2]
    dec = dec.reshape(2 * tp_p, count * s, -1, spec.head_dim)
    k_d = jnp.concatenate(
        parallel_align.realign_shards(list(dec[:tp_p]), tp_d),
        axis=1).reshape(count, s, -1, spec.head_dim)
    v_d = jnp.concatenate(
        parallel_align.realign_shards(list(dec[tp_p:]), tp_d),
        axis=1).reshape(count, s, -1, spec.head_dim)
    return (_repage_pool_body(spec, k_pool, block_ids, k_d, lo_block,
                              front=front, rmw=rmw, kernel=kernel,
                              interpret=interpret),
            _repage_pool_body(spec, v_pool, block_ids, v_d, lo_block,
                              front=front, rmw=rmw, kernel=kernel,
                              interpret=interpret))


@functools.partial(jax.jit, static_argnames=("spec", "wire", "count",
                                             "front", "rmw", "kernel",
                                             "interpret"))
def _repage_mla_part(spec: PC.KVPageSpec, pool: jax.Array, block_ids,
                     pay, sc, lo_block, *, wire: WireFormat, count: int,
                     front: int, rmw: bool, kernel: bool,
                     interpret: bool) -> jax.Array:
    sc_j = None if sc is None else sc.reshape(pay.shape[0], 1, 1)
    d = precision.decode_wire(pay, sc_j, wire, spec.jdtype)
    d = d.reshape(count, -1, 1, spec.head_dim)
    return _repage_pool_body(spec, pool, block_ids, d, lo_block,
                             front=front, rmw=rmw, kernel=kernel,
                             interpret=interpret)


# chunk wire codecs: "fixed" stages zero-copy WireChunks (fixed binary
# layout, single-pass vectorized re-page); "pickle" is the legacy pytree
# blob (kept as the parity/compat baseline)
CODECS = ("fixed", "pickle")


class DisaggPipeline:
    def __init__(self, transfer: KVConnector,
                 wire: Optional[WireFormat] = None,
                 codec: str = "fixed", repage_kernel: bool = False,
                 kernel_interpret: bool = False):
        assert codec in CODECS, codec
        self.transfer = transfer
        self.wire = wire or WireFormat(kind="raw", dtype="bfloat16")
        self.codec = codec
        # route the chunk re-page scatter through the Pallas overlay kernel
        # (partial blocks merge inside the kernel) instead of the jnp path
        self.repage_kernel = repage_kernel
        # run that kernel in Pallas interpret mode (CPU tests); otherwise
        # it compiles for the TPU and any other backend is an error
        self.kernel_interpret = kernel_interpret

    # ------------------------------------------------------------------ #
    # P side: package → wire
    # ------------------------------------------------------------------ #
    def _encode_entry(self, tp_p: int, kind: str, gi: int, pi: int,
                      ent: Dict[str, Any]) -> Dict[str, Any]:
        """One normalized KV entry (chronological, with absolute start) →
        wire entry. Row-wise encodings keep this chunk-split invariant."""
        if kind == "mla":
            # latent cache is TP-replicated — ship rank-0 copy only
            ckv, kpe = np.asarray(ent["ckv"]), np.asarray(ent["kpe"])
            pl_c, sc_c = precision.encode_wire(
                jnp.asarray(ckv)[..., None, :].reshape(-1, 1, ckv.shape[-1]),
                self.wire)
            pl_p, sc_p = precision.encode_wire(
                jnp.asarray(kpe)[..., None, :].reshape(-1, 1, kpe.shape[-1]),
                self.wire)
            return {"kind": "mla", "gi": gi, "pi": pi,
                    "count": ckv.shape[0], "seq": ckv.shape[1],
                    "start": ent["start"],
                    "payloads": [pl_c, pl_p], "scales": [sc_c, sc_p]}
        k, v = np.asarray(ent["k"]), np.asarray(ent["v"])
        count, s, _kv_heads, hd = k.shape
        # TP shard split (P's parallel strategy), per Fig. 4
        shards_k = np.split(k, tp_p, axis=2)
        shards_v = np.split(v, tp_p, axis=2)
        payloads, scales = [], []
        for sh in shards_k + shards_v:
            pl, sc = precision.encode_wire(
                jnp.asarray(sh).reshape(-1, sh.shape[2], hd), self.wire)
            payloads.append(pl)
            scales.append(sc)
        return {"kind": "kv", "gi": gi, "pi": pi, "count": count,
                "seq": s, "start": ent["start"], "tp_p": tp_p,
                "payloads": payloads, "scales": scales}

    def encode_package(self, p_engine: Engine, package: Dict[str, Any]
                       ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        tp_p = p_engine.vendor.tp
        out_kv = [self._encode_entry(tp_p, kind, gi, pi, ent)
                  for kind, gi, pi, ent in
                  kv_entries_with_start(package["kv"])]
        wire_pkg = {"kv": out_kv, "states": package["states"],
                    "cross": package["cross"]}
        meta = {"first_token": package["first_token"],
                "seq_len": package["seq_len"], "tp_p": tp_p,
                "wire": self.wire}
        return wire_pkg, meta

    def encode_chunk(self, p_engine: Engine, chunk: Dict[str, Any]):
        """One prefill chunk ({"kv": normalized entries}) → wire chunk.

        Fixed codec: returns a *planned* :class:`WireChunk` — no KV bytes
        move here; the connector executes the slab plan straight into its
        segment (``write_into``), so the encode is a dtype cast / quantize
        through buffer views with no pickle and no intermediate blob."""
        tp_p = p_engine.vendor.tp
        if self.codec == "fixed":
            return WireChunk.from_entries(chunk["kv"], self.wire, tp_p,
                                          seq_len=chunk.get("length", 0))
        return {"kv": [self._encode_entry(tp_p, kind, gi, pi, ent)
                       for kind, gi, pi, ent in chunk["kv"]]}

    # ------------------------------------------------------------------ #
    # D side: wire → pools
    # ------------------------------------------------------------------ #
    def materialize(self, d_engine: Engine, slot: int, block_ids: np.ndarray,
                    payload: Dict[str, Any], meta: Dict[str, Any], *,
                    rmw: bool = False) -> None:
        """Re-page wire KV entries (and any states/cross rows) into the D
        instance's pools. ``rmw`` preserves the untouched rows of partially
        covered blocks — required when streaming chunks whose boundaries do
        not align with the D vendor's block size."""
        if isinstance(payload, WireChunk):
            self._materialize_wire(d_engine, slot, block_ids, payload,
                                   rmw=rmw)
            return
        tp_d = d_engine.vendor.tp
        wire: WireFormat = meta["wire"]
        caches = [list(g) for g in d_engine.caches]
        bids = jnp.asarray(block_ids, jnp.int32)

        for entry in payload.get("kv", []):
            gi, pi = entry["gi"], entry["pi"]
            count, s, start = entry["count"], entry["seq"], entry["start"]
            if entry["kind"] == "mla":
                spec_c = d_engine.specs["ckv"]
                spec_p = d_engine.specs["kpe"]
                ckv = precision.decode_wire(entry["payloads"][0],
                                            entry["scales"][0], wire,
                                            spec_c.jdtype)
                kpe = precision.decode_wire(entry["payloads"][1],
                                            entry["scales"][1], wire,
                                            spec_p.jdtype)
                ckv = ckv.reshape(count, s, 1, spec_c.head_dim)
                kpe = kpe.reshape(count, s, 1, spec_p.head_dim)
                pools = caches[gi][pi]
                caches[gi][pi] = dict(
                    pools,
                    ckv_pool=self._write_pages(spec_c, pools["ckv_pool"],
                                               bids, ckv, start, rmw=rmw),
                    kpe_pool=self._write_pages(spec_p, pools["kpe_pool"],
                                               bids, kpe, start, rmw=rmw))
                continue
            spec = d_engine.specs["kv"]
            tp_p = entry["tp_p"]
            half = tp_p
            dec = [precision.decode_wire(pl, sc, wire, spec.jdtype)
                   for pl, sc in zip(entry["payloads"], entry["scales"])]
            shards_k = [d.reshape(count, s, -1, spec.head_dim)
                        for d in dec[:half]]
            shards_v = [d.reshape(count, s, -1, spec.head_dim)
                        for d in dec[half:]]
            # parallel-strategy alignment (merge/split), then assemble the
            # full head set for this (tp=1 runtime) D engine's pools.
            k_d = jnp.concatenate(
                parallel_align.realign_shards(
                    [s_.reshape(count * s, -1, spec.head_dim) for s_ in shards_k],
                    tp_d), axis=1).reshape(count, s, -1, spec.head_dim)
            v_d = jnp.concatenate(
                parallel_align.realign_shards(
                    [s_.reshape(count * s, -1, spec.head_dim) for s_ in shards_v],
                    tp_d), axis=1).reshape(count, s, -1, spec.head_dim)
            pools = caches[gi][pi]
            caches[gi][pi] = dict(
                pools,
                k_pool=self._write_pages(spec, pools["k_pool"], bids, k_d,
                                         start, rmw=rmw),
                v_pool=self._write_pages(spec, pools["v_pool"], bids, v_d,
                                         start, rmw=rmw))

        # recurrent / SSM states: place rows at the slot
        for _, gi, pi, state in payload.get("states", []):
            caches[gi][pi] = d_engine._place_fn(caches[gi][pi], state, slot)
        # enc-dec cross attention memory
        for gi, pi, cr in payload.get("cross", []):
            c = dict(caches[gi][pi])
            for name in ("cross_k", "cross_v", "mem_len"):
                c[name] = c[name].at[:, slot].set(
                    jnp.asarray(cr[name]).astype(c[name].dtype))
            caches[gi][pi] = c

        d_engine.caches = tuple(tuple(g) for g in caches)

    def _materialize_wire(self, d_engine: Engine, slot: int,
                          block_ids: np.ndarray, chunk: WireChunk, *,
                          rmw: bool = False) -> None:
        """Fixed-codec fast path: one vectorized decode + one scatter per
        pool, per chunk entry.

        The chunk's kv slab is already shard-major (2·tp_p, count, S, kvs,
        hd) — all shards of all layers in one contiguous view — so a single
        ``decode_wire`` dequantizes the whole entry (vs per-shard decode
        loops), and the re-page is one ``scatter_sequence_overlay`` per
        pool with boundary-only read-modify-write (vs readback of every
        touched page). Bit-identical to the legacy per-entry path."""
        tp_d = d_engine.vendor.tp
        wire = chunk.wire
        caches = [list(g) for g in d_engine.caches]
        bids = jnp.asarray(block_ids, jnp.int32)
        kernel = self.repage_kernel
        interpret = self.kernel_interpret

        for entry in chunk.entries():
            gi, pi = entry["gi"], entry["pi"]
            count, s, start = entry["count"], entry["seq"], entry["start"]
            if entry["kind"] == "mla":
                pools = caches[gi][pi]
                new = {}
                for pay, sc, name in zip(entry["payloads"], entry["scales"],
                                         ("ckv", "kpe")):
                    spec_m = d_engine.specs[name]
                    new[name + "_pool"] = _repage_mla_part(
                        spec_m, pools[name + "_pool"], bids,
                        jnp.array(pay),   # copy: don't alias the segment
                        None if sc is None else jnp.array(sc),
                        start // spec_m.block_size, wire=wire, count=count,
                        front=start % spec_m.block_size, rmw=rmw,
                        kernel=kernel, interpret=interpret)
                caches[gi][pi] = dict(pools, **new)
                continue
            spec = d_engine.specs["kv"]
            tp_p = entry["tp_p"]
            pay = entry["payload"]           # (2·tp_p, count, S, kvs, hd)
            sc = entry["scales"]
            pools = caches[gi][pi]
            k_pool, v_pool = _repage_kv_entry(
                spec, pools["k_pool"], pools["v_pool"], bids,
                jnp.array(pay),      # copy: don't alias the shm segment
                None if sc is None else jnp.array(sc),
                start // spec.block_size,
                wire=wire, tp_p=tp_p, tp_d=tp_d, count=count,
                front=start % spec.block_size, rmw=rmw, kernel=kernel,
                interpret=interpret)
            caches[gi][pi] = dict(pools, k_pool=k_pool, v_pool=v_pool)

        d_engine.caches = tuple(tuple(g) for g in caches)

    @staticmethod
    def _write_pages_vec(spec: PC.KVPageSpec, pool: jax.Array, block_ids,
                         canon: jax.Array, start: int, *, rmw: bool = False,
                         kernel: bool = False,
                         interpret: bool = False) -> jax.Array:
        """Jit-compiled single-pass re-page (see
        :func:`_repage_pool_body`); one compiled program per
        (spec, chunk shape, in-page offset)."""
        return _repage_pool(spec, pool, jnp.asarray(block_ids, jnp.int32),
                            jnp.asarray(canon), start // spec.block_size,
                            front=start % spec.block_size, rmw=rmw,
                            kernel=kernel, interpret=interpret)

    @staticmethod
    def _write_pages(spec: PC.KVPageSpec, pool: jax.Array, block_ids,
                     canon: jax.Array, start: int, *,
                     rmw: bool = False) -> jax.Array:
        """canon: (count, S, kv, hd) holding absolute positions
        [start, start+S) → scatter into pages (vmapped over layer count).

        Whole-sequence writes zero-fill block padding; ``rmw`` reads the
        touched pages back and overlays only [start, start+S), so a later
        chunk cannot clobber an earlier chunk sharing its first block."""
        bs = spec.block_size
        lo_block = start // bs
        front = start - lo_block * bs
        s_tot = front + canon.shape[1]
        nb = -(-s_tot // bs)
        use = block_ids[lo_block:lo_block + nb]
        if not rmw:
            if front:
                canon = jnp.pad(canon, ((0, 0), (front, 0), (0, 0), (0, 0)))
            return jax.vmap(lambda pl, cn: PC.scatter_sequence(spec, pl, use, cn)
                            )(pool, canon)

        def wr(pl, cn):
            cur = PC.pages_to_canonical(spec, pl[use])       # (nb, bs, kv, hd)
            flat = cur.reshape(nb * bs, spec.kv_heads, spec.head_dim)
            flat = jax.lax.dynamic_update_slice(
                flat, cn.astype(flat.dtype), (front, 0, 0))
            pages = PC.pages_from_canonical(
                spec, flat.reshape(nb, bs, spec.kv_heads, spec.head_dim))
            return pl.at[use].set(pages)

        return jax.vmap(wr)(pool, canon)

    # ------------------------------------------------------------------ #
    # Monolithic handoff (baseline transmission)
    # ------------------------------------------------------------------ #
    def handoff(self, req: Request, p_engine: Engine, d_engine: Engine
                ) -> Dict[str, Any]:
        """prefill-package → stage → issue_read → wait → materialize.

        Synchronous by construction: the monolithic wire has nothing to
        overlap, so ``wait()`` force-completes the read (with a modeled
        backend the whole wire time lands exposed). Returns meta."""
        self.transfer.register(p_engine.name, role="prefill")
        self.transfer.register(d_engine.name, role="decode")
        package = p_engine.prefill(req)
        wire_pkg, meta = self.encode_package(p_engine, package)
        # retry-unique key: a failed handoff leaves no stale staging to
        # collide with the requeued attempt
        key = f"{req.req_id}@{p_engine.name}#t{req.retries}"
        nbytes = self.transfer.stage(key, wire_pkg, meta)
        try:
            payload, meta = self.transfer.issue_read(key).wait()
            payload = _to_device(payload)

            def materialize_fn(engine, slot, bids, _pkg):
                self.materialize(engine, slot, bids, payload, meta)

            d_engine.add_sequence(req, {"first_token": meta["first_token"],
                                        "seq_len": meta["seq_len"]},
                                  materialize_fn)
        except Exception:
            self.transfer.drop(key)    # free the pinned staging on failure
            raise
        self.transfer.complete(key)
        meta["bytes"] = nbytes
        return meta

    # ------------------------------------------------------------------ #
    # Streamed chunked handoff (overlapped transmission)
    # ------------------------------------------------------------------ #
    def begin_handoff(self, req: Request, p_engine: Engine, d_engine: Engine,
                      seq_len: int,
                      compute_overlapped: bool = False) -> "StreamedHandoff":
        """Reserve the D slot/blocks and open a chunk stream for ``req``.

        ``compute_overlapped``: the chunks come from an *incremental*
        prefill, so each chunk's wire time hides under the next chunk's
        compute (credited to TransferStats.overlap_modeled_seconds). A
        monolithic-compute stream ships after all P compute finished —
        nothing to hide under, no overlap credit."""
        return StreamedHandoff(self, req, p_engine, d_engine, seq_len,
                               compute_overlapped=compute_overlapped)

    def handoff_streamed(self, req: Request, p_engine: Engine,
                         d_engine: Engine,
                         chunk_tokens: Optional[int] = None,
                         chunked_compute: Optional[bool] = None,
                         mode=None) -> Dict[str, Any]:
        """Drive a full streamed handoff synchronously (tests / examples;
        the global scheduler advances the same protocol tick by tick)."""
        stream = p_engine.prefill_stream(req, chunk_tokens, chunked_compute,
                                         mode=mode)
        h = self.begin_handoff(req, p_engine, d_engine, stream.seq_len,
                               compute_overlapped=stream.chunked_compute)
        try:
            while True:
                chunk = stream.next_chunk()
                if chunk is None:
                    break
                if not chunk["kv"] and chunk["length"] == 0:
                    continue            # compute-only progress marker
                h.send_chunk(chunk)
                h.poll_reads()          # re-page whatever the wire delivered
            return h.finalize(stream.first_token, stream.tail_package())
        except Exception:
            h.abort()
            raise


class StreamedHandoff:
    """State of one in-flight chunked P→D handoff.

    Lifecycle: reserve (ctor) → (``send_chunk`` | ``poll_reads``)×N →
    ``finalize`` | ``abort``. ``send_chunk`` encodes one chunk, stages it
    into the pinned pool, and *issues* an async wire read; ``poll_reads``
    re-pages chunks whose :class:`TransferHandle` reports complete — the
    D-side re-page runs on its own tick budget, decoupled from wire time.
    Chunks re-page in issue order (the wire is an ordered channel), so a
    later chunk never lands before an earlier one that shares a block."""

    def __init__(self, pipeline: DisaggPipeline, req: Request,
                 p_engine: Engine, d_engine: Engine, seq_len: int, *,
                 compute_overlapped: bool = False):
        self.pipeline = pipeline
        self.req = req
        self.p_engine = p_engine
        self.d_engine = d_engine
        self.seq_len = seq_len
        self.compute_overlapped = compute_overlapped
        pipeline.transfer.register(p_engine.name, role="prefill")
        pipeline.transfer.register(d_engine.name, role="decode")
        self.slot, self.block_ids = d_engine.reserve_sequence(
            req, seq_len, use_prefix_cache=True)
        # prefix tokens already resident on D: chunks below this position
        # never touch the wire (send_chunk slices / drops them)
        self.wire_skip = d_engine.slot_prefix_tokens[self.slot]
        self.meta = {"seq_len": seq_len, "tp_p": p_engine.vendor.tp,
                     "wire": pipeline.wire}
        self.chunks_sent = 0
        self.chunks_repaged = 0
        self.bytes = 0
        self._skipped_tokens = 0
        self._sent_tokens = 0
        self._pending: Deque[Tuple[str, TransferHandle, float, float]] = \
            collections.deque()
        self._chunk_modeled: List[float] = []
        self._chunk_compute: List[float] = []
        # wall-clock (measured) handoff timings — time.monotonic so the
        # same accounting is comparable across OS processes on one host
        self._t_first_stage: Optional[float] = None
        self._t_last_repage: Optional[float] = None
        self._chunk_wall_pending: List[float] = []
        self._closed = False

    # -- wire side -------------------------------------------------------- #
    def can_send(self) -> bool:
        """Channel has room for another issued-but-unread chunk (the
        connector's ``max_inflight`` capability, not a constant here).
        The channel is shared: concurrent flights throttle against the
        connector's *global* in-flight count, not their own queue."""
        caps = self.pipeline.transfer.capabilities()
        return self.pipeline.transfer.inflight_reads() < caps.max_inflight

    def pending_reads(self) -> int:
        """Chunks issued on the wire but not yet re-paged on D."""
        return len(self._pending)

    def send_chunk(self, chunk: Dict[str, Any]) -> int:
        """Encode → stage → issue the wire read for one chunk. Returns its
        staged bytes. If the channel is full, force-completes the oldest
        read first (blocking send — its wire time lands exposed)."""
        assert not self._closed, "send_chunk on a closed handoff"
        if self.d_engine.failed:
            raise RuntimeError(f"instance {self.d_engine.name} is down")
        start, length = chunk["start"], chunk["length"]
        if self.wire_skip > start:
            skipped = min(self.wire_skip, start + length) - start
            self._skipped_tokens += skipped
            self.pipeline.transfer.stats.prefix_hit_tokens += skipped
            if start + length <= self.wire_skip:
                return 0               # fully resident on D: skip the wire
            chunk = dict(chunk,
                         kv=slice_kv_entries(chunk["kv"], self.wire_skip,
                                             start + length),
                         start=self.wire_skip,
                         length=start + length - self.wire_skip)
        self._sent_tokens += chunk["length"]
        while not self.can_send():
            if not self._repage_head(force=True):
                break                  # channel held by other flights —
        #                                issue_read below surfaces the limit
        tr = self.pipeline.transfer
        rid = self.req.req_id
        with span("pd.handoff.encode", req=rid):
            wire_chunk = self.pipeline.encode_chunk(self.p_engine, chunk)
        key = f"{self.req.req_id}@{self.p_engine.name}" \
              f"#t{self.req.retries}c{self.chunks_sent}"
        if self._t_first_stage is None:
            self._t_first_stage = time.monotonic()
        with span("pd.handoff.stage", req=rid,
                  bytes=getattr(wire_chunk, "nbytes", 0)):
            nbytes = tr.stage(key, wire_chunk, self.meta)
        try:
            handle = tr.issue_read(key)
        except Exception:
            tr.drop(key)
            raise
        self._pending.append((key, handle,
                              chunk.get("compute_seconds", 0.0),
                              time.monotonic()))
        self.chunks_sent += 1
        self.bytes += nbytes
        return nbytes

    # -- D side ----------------------------------------------------------- #
    def _repage_head(self, force: bool = False) -> bool:
        """Re-page the oldest pending chunk if its read completed (or
        unconditionally when ``force``). Returns True if it re-paged."""
        if not self._pending:
            return False
        key, handle, compute_s, t_issue = self._pending[0]
        if not force and not handle.poll():
            return False
        if self.d_engine.failed:
            raise RuntimeError(f"instance {self.d_engine.name} is down")
        tr = self.pipeline.transfer
        rid = self.req.req_id
        with span("pd.handoff.read", req=rid):
            payload, meta = handle.wait()
        with span("pd.handoff.to_device", req=rid):
            dev_payload = _to_device(payload)
        with span("pd.handoff.repage", req=rid):
            self.pipeline.materialize(self.d_engine, self.slot,
                                      self.block_ids, dev_payload, meta,
                                      rmw=True)
        if hasattr(payload, "release"):
            payload.release()      # drop zero-copy views before the segment
            #                        backing this chunk is closed
        tr.complete(key)
        tr.stats.chunks += 1
        self._chunk_modeled.append(tr.modeled_latency(handle.nbytes))
        self._chunk_compute.append(compute_s)
        self._t_last_repage = time.monotonic()
        self._chunk_wall_pending.append(self._t_last_repage - t_issue)
        self._pending.popleft()
        self.chunks_repaged += 1
        return True

    def poll_reads(self, budget: Optional[int] = None) -> int:
        """Re-page up to ``budget`` completed chunks (None = every chunk
        whose handle polls complete). The scheduler calls this with its
        per-tick re-page budget — separate from the chunk-send budget."""
        done = 0
        while (budget is None or done < budget) and self._repage_head():
            done += 1
        return done

    def drain(self) -> int:
        """Force-complete and re-page every pending read (sync fallback)."""
        done = 0
        while self._repage_head(force=True):
            done += 1
        return done

    def finalize(self, first_token: int, tail_package: Dict[str, Any]
                 ) -> Dict[str, Any]:
        """Ship recurrent/cross state, activate the D slot, account overlap."""
        assert not self._closed
        self.drain()
        tr = self.pipeline.transfer
        if tail_package.get("states") or tail_package.get("cross"):
            key = f"{self.req.req_id}@{self.p_engine.name}" \
                  f"#t{self.req.retries}tail"
            nbytes = tr.stage(key, {"states": tail_package["states"],
                                    "cross": tail_package["cross"]},
                              self.meta)
            payload, meta = tr.issue_read(key).wait()
            self.pipeline.materialize(self.d_engine, self.slot,
                                      self.block_ids, _to_device(payload),
                                      meta)
            tr.complete(key)
            self.bytes += nbytes
        self.d_engine.activate_sequence(self.slot, first_token, self.seq_len)
        # incremental compute: chunk i's wire time hides under chunk i+1's
        # compute, but only as much of it as that compute can cover — on a
        # wire-bound link most of the transfer stays exposed (same residue
        # the planner's handoff_exposed_seconds models). Monolithic compute
        # ships after all P compute: no overlap credit at all.
        if self.compute_overlapped:
            tr.stats.overlap_modeled_seconds += sum(
                min(xfer, comp) for xfer, comp in
                zip(self._chunk_modeled[:-1], self._chunk_compute[1:]))
            # measured counterpart: wall time a chunk actually spent pending
            # on the wire, capped by the next chunk's compute wall time. On
            # an instant in-process wire this is ~0 (nothing truly ran
            # concurrently); in the two-process runtime the launcher
            # measures real cross-process concurrency instead.
            tr.stats.wall_overlap_seconds += sum(
                min(pend, comp) for pend, comp in
                zip(self._chunk_wall_pending[:-1], self._chunk_compute[1:]))
        if self._t_first_stage is not None and self._t_last_repage is not None:
            tr.stats.wall_handoff_seconds += \
                self._t_last_repage - self._t_first_stage
        if self._skipped_tokens and self._sent_tokens and self.bytes:
            # the flight's own measured bytes/token prices what the
            # skipped tokens would have cost on this wire format
            tr.stats.bytes_saved += int(
                self.bytes / self._sent_tokens * self._skipped_tokens)
        self._closed = True
        return {"first_token": first_token, "seq_len": self.seq_len,
                "tp_p": self.meta["tp_p"], "wire": self.pipeline.wire,
                "bytes": self.bytes, "chunks": self.chunks_sent}

    def abort(self) -> None:
        """Failure path: drop staged-but-unread chunks and free the D
        reservation (their handles fail with TransferError if waited)."""
        if self._closed:
            return
        self._closed = True
        tr = self.pipeline.transfer
        while self._pending:
            key, handle, _comp, _t = self._pending.popleft()
            handle.cancel()
            tr.drop(key)
        self.d_engine.abort_reservation(self.slot)
