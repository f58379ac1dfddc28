"""Zero-copy fixed-layout KV wire + shared-link congestion arbitration.

Covers the wirefmt codec end to end:

  * planned-vs-bound ``WireChunk`` round trip is bit-exact, and the host
    numpy encode path matches the legacy jnp ``precision.encode_wire``
    bit for bit (payloads AND int8 scales, per shard);
  * the fixed codec lands D pools bit-identical to the legacy pickle
    codec across wire formats × D vendor layouts × mismatched P/D block
    sizes (chunk boundaries straddling block edges → overlay re-page);
  * a chunk adopted in *another OS process* reads back the exact staged
    bytes through zero-copy views (and the two-process runtime is
    token-exact across codecs);
  * later chunks never clobber earlier ones (boundary-only overlay RMW,
    jnp and Pallas-kernel paths);
  * fair-share link arbitration: two concurrent flights on one modeled
    link each finish later than either alone, within tolerance of the
    processor-sharing prediction, and the extra time is accounted to
    ``congested_seconds``;
  * ``SharedMemoryConnector._get`` reuses its held mapping (no
    attach-by-name per read), and ``TransferStats`` splits wire bytes
    from raw payload bytes.
"""
import multiprocessing as mp

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.compat import precision
from repro.core.compat.precision import WireFormat
from repro.core.disagg import DisaggPipeline
from repro.core.transport import (InProcessConnector, ModeledRDMAConnector,
                                  SharedMemoryConnector, WireChunk,
                                  make_connector)
from repro.core.transport import wirefmt
from repro.models import model as M
from repro.serving.engine import Engine, VendorProfile
from repro.serving.paged_cache import (LAYOUTS, KVPageSpec, gather_sequence,
                                       scatter_sequence)
from repro.serving.request import Request
from tests.conftest import TINY_FAMILIES

WIRES = [WireFormat("raw", "float32"), WireFormat("raw", "bfloat16"),
         WireFormat("int8")]
WIRE_IDS = [f"{w.kind}-{w.dtype}" for w in WIRES]


def _entries(seed=0, tp_p=2, with_mla=True):
    """Synthetic normalized chunk entries (what ``prefill_stream`` emits)."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(3, 13, 4, 8)).astype(np.float32)
    v = rng.normal(size=(3, 13, 4, 8)).astype(np.float32)
    ents = [("kv", 0, 0, {"k": k, "v": v, "start": 5})]
    if with_mla:
        ckv = rng.normal(size=(2, 13, 16)).astype(np.float32)
        kpe = rng.normal(size=(2, 13, 8)).astype(np.float32)
        ents.append(("mla", 1, 0, {"ckv": ckv, "kpe": kpe, "start": 5}))
    return ents


def _entry_bytes(chunk):
    """Flat (payload_bytes, scales_bytes) per entry — dtype-agnostic."""
    out = []
    for e in chunk.entries():
        if e["kind"] == "mla":
            pay = b"".join(p.tobytes() for p in e["payloads"])
            sc = b"".join(s.tobytes() for s in e["scales"]
                          if s is not None)
        else:
            pay = e["payload"].tobytes()
            sc = b"" if e["scales"] is None else e["scales"].tobytes()
        out.append((e["kind"], e["gi"], e["start"], pay, sc))
    return out


# --------------------------------------------------------------------- #
# codec: planned vs bound round trip, legacy bit-parity
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("wire", WIRES, ids=WIRE_IDS)
def test_wirechunk_planned_vs_bound_bit_exact(wire):
    chunk = WireChunk.from_entries(_entries(), wire, tp_p=2, seq_len=13)
    # payload_nbytes counts *raw* source KV bytes (pre-cast/quantize);
    # nbytes is what actually crosses the wire
    assert chunk.nbytes > chunk.header_nbytes
    assert chunk.header_nbytes >= wirefmt.nominal_header_bytes(0)
    if wire.kind == "raw" and wire.dtype == "float32":
        # identity wire: only headers + slab alignment on top of payload
        assert chunk.header_nbytes + chunk.payload_nbytes <= chunk.nbytes \
            <= chunk.header_nbytes + chunk.payload_nbytes + 64 * 4
    else:
        assert chunk.nbytes < chunk.payload_nbytes   # compressed wire
    buf = bytearray(chunk.nbytes)
    chunk.write_into(buf)
    assert bytes(buf[:8]) == wirefmt.MAGIC
    bound = WireChunk.from_buffer(buf)
    assert bound.wire.kind == wire.kind
    assert bound.tp_p == 2 and bound.seq_len == 13
    assert bound.nbytes == chunk.nbytes
    assert bound.payload_nbytes == chunk.payload_nbytes
    assert _entry_bytes(bound) == _entry_bytes(chunk)
    bound.release()


@pytest.mark.parametrize("wire", WIRES, ids=WIRE_IDS)
def test_wirechunk_encode_matches_legacy_jnp(wire):
    """The single-pass numpy encode (cast / absmax-quantize through buffer
    views) is bit-identical to the legacy per-shard jnp encode — payloads
    and int8 scales both, so fixed-codec pools can equal pickle pools."""
    ents = _entries(seed=1, with_mla=False)
    _, _, _, ent = ents[0]
    k, v = ent["k"], ent["v"]
    count, s, kv_heads, hd = k.shape
    tp_p = 2
    chunk = WireChunk.from_entries(ents, wire, tp_p=tp_p, seq_len=s)
    (e,) = chunk.entries()
    pay, sc = e["payload"], e["scales"]          # (2·tp, count, s, kvs, hd)
    if sc is not None:
        sc = sc.reshape(2 * tp_p, count, s, kv_heads // tp_p, 1)
    shards = np.split(k, tp_p, axis=2) + np.split(v, tp_p, axis=2)
    for i, sh in enumerate(shards):
        lp, ls = precision.encode_wire(
            jnp.asarray(sh).reshape(-1, sh.shape[2], hd), wire)
        got = pay[i].reshape(count * s, kv_heads // tp_p, hd)
        assert np.asarray(lp).tobytes() == np.asarray(got).tobytes(), i
        if ls is not None:
            got_s = sc[i].reshape(count * s, kv_heads // tp_p, 1)
            assert np.asarray(ls).tobytes() == got_s.tobytes(), i
    chunk.release()


def test_wirechunk_header_overhead_is_fixed_and_small():
    wire = WireFormat("raw", "float32")
    one = WireChunk.from_entries(_entries(with_mla=False), wire, 2, 13)
    assert one.header_nbytes <= wirefmt.nominal_header_bytes(2, 2)
    # headers don't scale with tokens — only with entry count
    big_ents = _entries(seed=2, with_mla=False)
    big_ents[0][3]["k"] = np.repeat(big_ents[0][3]["k"], 4, axis=1)
    big_ents[0][3]["v"] = np.repeat(big_ents[0][3]["v"], 4, axis=1)
    big = WireChunk.from_entries(big_ents, wire, 2, 52)
    assert big.header_nbytes == one.header_nbytes


# --------------------------------------------------------------------- #
# fixed vs pickle codec: bit-identical D pools (in-process)
# --------------------------------------------------------------------- #
def _pd_pair(cfg, params, vd, bs_p=8):
    vp = VendorProfile("B", block_size=bs_p, layout="nhbd",
                       kv_dtype="float32", tp=2)
    p = Engine("P0", cfg, params, vp, num_blocks=64, max_batch=4,
               max_seq_len=64, role="prefill")
    d = Engine("D0", cfg, params, vd, num_blocks=64, max_batch=4,
               max_seq_len=64, role="decode")
    return p, d


def _req(cfg, plen, rid="r0", seed=3):
    rng = np.random.default_rng(seed)
    return Request(req_id=rid,
                   prompt=rng.integers(0, cfg.vocab_size,
                                       plen).astype(np.int32),
                   max_new_tokens=4)


def _stream_pools(cfg, params, vd, wire, codec, backend="inproc",
                  chunk_tokens=5, repage_kernel=False):
    p, d = _pd_pair(cfg, params, vd)
    conn = make_connector(backend)
    pipe = DisaggPipeline(conn, wire, codec=codec,
                          repage_kernel=repage_kernel,
                          kernel_interpret=repage_kernel)
    pipe.handoff_streamed(_req(cfg, plen=13), p, d, chunk_tokens=chunk_tokens,
                          chunked_compute=False)
    assert conn.pool.in_use == 0
    if hasattr(conn, "_deferred_close"):
        assert conn._deferred_close == []      # all views released
    conn.close()
    return d


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("wire", WIRES, ids=WIRE_IDS)
def test_fixed_codec_pools_equal_pickle_codec(wire, layout):
    """Acceptance: across wire formats × D vendor layouts (with D blocks
    of 4 vs 5-token chunks vs P blocks of 8 — boundaries straddle block
    edges on both sides), the zero-copy fixed codec lands D pools
    bit-identical to the legacy pickled wire."""
    cfg = TINY_FAMILIES["dense"]
    params = M.init_params(jax.random.key(1), cfg)
    vd = VendorProfile("A", block_size=4, layout=layout,
                       kv_dtype="float32")
    d_fix = _stream_pools(cfg, params, vd, wire, "fixed", backend="shm")
    d_leg = _stream_pools(cfg, params, vd, wire, "pickle")
    for a, b in zip(jax.tree.leaves(d_fix.caches),
                    jax.tree.leaves(d_leg.caches)):
        assert a.dtype == b.dtype
        assert bool(jnp.array_equal(a, b)), (wire.kind, layout)
    assert d_fix.decode_step()[0][2] == d_leg.decode_step()[0][2]


@pytest.mark.parametrize("family", ["mla", "hybrid"])
def test_fixed_codec_pools_equal_pickle_codec_other_families(family):
    """mla (latent-KV entries, 2 parts/entry) and hybrid (KV + recurrent
    tail states through the pickle side channel) stream bit-identically
    under the fixed codec."""
    cfg = TINY_FAMILIES[family]
    params = M.init_params(jax.random.key(1), cfg)
    vd = VendorProfile("A", block_size=4, layout="nbhd",
                       kv_dtype="float32")
    wire = WireFormat("int8")
    d_fix = _stream_pools(cfg, params, vd, wire, "fixed", backend="shm")
    d_leg = _stream_pools(cfg, params, vd, wire, "pickle")
    for a, b in zip(jax.tree.leaves(d_fix.caches),
                    jax.tree.leaves(d_leg.caches)):
        assert bool(jnp.array_equal(a, b)), family
    assert d_fix.decode_step()[0][2] == d_leg.decode_step()[0][2]


def test_repage_kernel_path_matches_jnp_path():
    """The Pallas overlay-scatter re-page (partial blocks merged inside
    the kernel) lands the same pools as the jnp boundary-RMW path."""
    cfg = TINY_FAMILIES["dense"]
    params = M.init_params(jax.random.key(1), cfg)
    vd = VendorProfile("A", block_size=4, layout="nhbd",
                       kv_dtype="float32")
    wire = WireFormat("raw", "float32")
    d_jnp = _stream_pools(cfg, params, vd, wire, "fixed")
    d_ker = _stream_pools(cfg, params, vd, wire, "fixed",
                          repage_kernel=True)
    for a, b in zip(jax.tree.leaves(d_jnp.caches),
                    jax.tree.leaves(d_ker.caches)):
        assert bool(jnp.array_equal(a, b))


# --------------------------------------------------------------------- #
# overlay re-page: later chunks never clobber earlier ones
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("bs,chunk", [(4, 5), (8, 6), (4, 3)])
def test_overlay_chunk_sequence_never_clobbers(layout, bs, chunk):
    """Stream S=13 tokens in ``chunk``-token pieces into ``bs``-token
    blocks (boundaries straddle): after every chunk the previously landed
    prefix is bit-intact, and the final pool equals a one-shot scatter."""
    rng = np.random.default_rng(0)
    spec = KVPageSpec(block_size=bs, layout=layout, dtype="float32",
                      kv_heads=2, head_dim=4)
    S, L, N = 13, 3, 16
    nb = spec.blocks_for(S)
    pool = jnp.asarray(rng.normal(size=(L,) + spec.pool_shape(N))
                       .astype(np.float32))
    ids = np.asarray([3, 9, 1, 6][:nb], np.int32)
    stream = jnp.asarray(rng.normal(size=(L, S, 2, 4)).astype(np.float32))

    for kernel in (False, True):
        cur = pool
        for st in range(0, S, chunk):
            cn = stream[:, st:st + chunk]
            cur = DisaggPipeline._write_pages_vec(spec, cur, ids, cn, st,
                                                  rmw=True, kernel=kernel,
                                                  interpret=kernel)
            got = jax.vmap(lambda pl: gather_sequence(spec, pl, ids,
                                                      min(st + chunk, S))
                           )(cur)
            assert bool(jnp.array_equal(got,
                                        stream[:, :st + chunk])), \
                (layout, bs, chunk, st, kernel)
        ref = jax.vmap(lambda pl, cn: scatter_sequence(
            spec, pl, jnp.asarray(ids), cn))(pool, stream)
        # the overlay stream and the one-shot scatter agree on every row
        # the stream covered (the one-shot zero-fills tail padding)
        got = jax.vmap(lambda pl: gather_sequence(spec, pl, ids, S))(cur)
        want = jax.vmap(lambda pl: gather_sequence(spec, pl, ids, S))(ref)
        assert bool(jnp.array_equal(got, want)), (layout, bs, chunk, kernel)
        # untouched pool pages are preserved
        mask = np.ones(N, bool)
        mask[ids] = False
        assert bool(jnp.array_equal(cur[:, mask], pool[:, mask]))


@pytest.mark.parametrize("start", [0, 3, 5])
def test_write_pages_vec_matches_legacy_write_pages(start):
    rng = np.random.default_rng(1)
    spec = KVPageSpec(block_size=4, layout="nhdb", dtype="bfloat16",
                      kv_heads=2, head_dim=4)
    L, N, S = 2, 12, 7
    pool = jnp.asarray(rng.normal(size=(L,) + spec.pool_shape(N))
                       .astype(np.float32)).astype(spec.jdtype)
    ids = jnp.asarray(range(spec.blocks_for(start + S)), jnp.int32)
    canon = jnp.asarray(rng.normal(size=(L, S, 2, 4)).astype(np.float32))
    legacy = DisaggPipeline._write_pages(spec, pool, ids, canon, start,
                                         rmw=True)
    vec = DisaggPipeline._write_pages_vec(spec, pool, ids, canon, start,
                                          rmw=True)
    ker = DisaggPipeline._write_pages_vec(spec, pool, ids, canon, start,
                                          rmw=True, kernel=True,
                                          interpret=True)
    assert bool(jnp.array_equal(legacy, vec))
    assert bool(jnp.array_equal(legacy, ker))


# --------------------------------------------------------------------- #
# cross-process: adopted segment reads the exact staged bytes, zero-copy
# --------------------------------------------------------------------- #
def _adopt_and_dump(desc, q):
    """Child: adopt the staged segment, read it, ship the bytes home."""
    from repro.core.transport import SharedMemoryConnector
    conn = SharedMemoryConnector()
    try:
        conn.adopt_segment(desc["key"], desc["segment"], desc["nbytes"])
        payload, meta = conn.issue_read(desc["key"]).wait()
        ents = [(k, gi, st, pay, sc)
                for k, gi, st, pay, sc in _entry_bytes(payload)]
        m = (meta["wire"].kind, meta["tp_p"], meta["seq_len"])
        payload.release()
        conn.complete(desc["key"])
        q.put(("ok", ents, m))
    except Exception as e:                     # noqa: BLE001 — report home
        q.put(("err", repr(e), None))
    finally:
        conn.close()


@pytest.mark.parametrize("wire", WIRES, ids=WIRE_IDS)
def test_cross_process_adopted_chunk_is_bit_exact(wire):
    conn = SharedMemoryConnector()
    chunk = WireChunk.from_entries(_entries(seed=4), wire, tp_p=2,
                                   seq_len=13)
    want = _entry_bytes(chunk)                 # planned-side reference
    conn.stage("x@P0#c0", chunk, chunk.meta())
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    proc = ctx.Process(target=_adopt_and_dump,
                       args=(conn.export_descriptor("x@P0#c0"), q))
    proc.start()
    status, ents, m = q.get(timeout=120)
    proc.join(timeout=30)
    assert status == "ok", ents
    assert m == (wire.kind, 2, 13)
    assert ents == [(k, gi, st, pay, sc) for k, gi, st, pay, sc in want]
    conn.complete("x@P0#c0")
    assert conn.pool.in_use == 0
    conn.close()


def test_cross_process_runtime_codec_parity():
    """The real 1P+1D runtime (separate OS processes, KV over adopted shm
    segments) is token-exact between the zero-copy fixed codec and the
    legacy pickle codec, and the fixed wire's stats split survives the
    trip home through the workers' merged TransferStats."""
    from tests.test_multiproc import (CHUNK, VENDOR_D, VENDOR_P, _requests,
                                      _shm_files, _spec)
    from repro.serving.multiproc.launcher import serve_two_process
    before = _shm_files()
    tokens = {}
    stats = {}
    for codec in ("fixed", "pickle"):
        tokens[codec], rt = serve_two_process(
            _spec("P0", VENDOR_P, "prefill"), _spec("D0", VENDOR_D, "decode"),
            _requests(n=2), prefill_chunk=CHUNK, codec=codec,
            max_wall_s=300.0)
        assert rt.stats.finished == 2
        stats[codec] = rt.transfer_stats
    assert tokens["fixed"] == tokens["pickle"]
    assert stats["fixed"].payload_bytes > 0          # wire/raw split home
    assert stats["fixed"].bytes_moved > 0
    after = _shm_files()
    if before is not None:
        assert after - before == set()


# --------------------------------------------------------------------- #
# link congestion: fair-share arbitration + measured attribution
# --------------------------------------------------------------------- #
def test_fair_share_two_flights_slower_than_alone_but_equal():
    """Two equal concurrent reads on one fair-share link: each finishes
    later than it would alone (the link is genuinely shared), both finish
    together within tolerance, and the extra time is accounted."""
    B = 10_000_000
    conn = ModeledRDMAConnector(bandwidth_gbps=0.01, fixed_latency_s=0.1,
                                tick_seconds=0.05)
    assert conn.capabilities().link_sharing == "fair"
    conn.stage("a", {"x": np.zeros(B, np.uint8)})
    conn.stage("b", {"x": np.zeros(B, np.uint8)})
    ha = conn.issue_read("a")
    hb = conn.issue_read("b")
    alone = 0.1 + B / 0.01e9                   # 1.1 s
    shared = 0.1 + 2 * B / 0.01e9              # 2.1 s (processor sharing)
    t, t_a = 0.0, None
    while not (ha.poll() and hb.poll()):
        conn.tick()
        t += conn.tick_seconds
        if t_a is None and ha.poll():
            t_a = t
        assert t < 10.0, "fair-share link never delivered"
    # neither flight finished in its alone-on-the-link time
    assert t_a is not None and t_a > alone + 0.5
    # fair: both flights completed on the same tick (equal progress)
    assert t_a == pytest.approx(t)
    assert t == pytest.approx(shared, abs=2 * conn.tick_seconds)
    ha.wait()
    hb.wait()
    assert conn.stats.congested_seconds == \
        pytest.approx(2 * (shared - alone), abs=0.01)
    assert conn.stats.concurrent_reads_peak == 2
    conn.complete("a")
    conn.complete("b")
    conn.close()


def test_fair_share_wait_fast_forwards_through_contention():
    B = 10_000_000
    conn = ModeledRDMAConnector(bandwidth_gbps=0.01, fixed_latency_s=0.1)
    conn.stage("a", {"x": np.zeros(B, np.uint8)})
    conn.stage("b", {"x": np.zeros(B, np.uint8)})
    ha = conn.issue_read("a")
    hb = conn.issue_read("b")
    ha.wait()
    assert conn._now == pytest.approx(0.1 + 2 * B / 0.01e9)
    hb.wait()                                  # already done: no advance
    assert conn._now == pytest.approx(0.1 + 2 * B / 0.01e9)
    assert conn.stats.contended_read_seconds > 0   # measured attribution
    conn.complete("a")
    conn.complete("b")
    conn.close()


def test_cancelled_flight_stops_charging_the_link():
    """A cancelled read leaves the fair-share link: the survivor drains at
    full bandwidth afterwards."""
    B = 10_000_000
    conn = ModeledRDMAConnector(bandwidth_gbps=0.01, fixed_latency_s=0.0)
    conn.stage("a", {"x": np.zeros(B, np.uint8)})
    conn.stage("b", {"x": np.zeros(B, np.uint8)})
    ha = conn.issue_read("a")
    hb = conn.issue_read("b")
    hb.cancel()
    ha.wait()
    assert conn._now == pytest.approx(B / 0.01e9)  # alone time, no sharing
    conn.close()


# --------------------------------------------------------------------- #
# shm: held-mapping reuse, zero-copy stage, stats split
# --------------------------------------------------------------------- #
def test_shm_get_reuses_held_mapping(monkeypatch):
    """A read never re-attaches the segment by name: staging (P) and
    adoption (D) each attach once, and ``_get`` reuses that mapping."""
    import repro.core.transport.shared_memory as shm_mod
    conn = SharedMemoryConnector()
    chunk = WireChunk.from_entries(_entries(with_mla=False),
                                   WireFormat("raw", "float32"), 2, 13)
    conn.stage("zc", chunk, chunk.meta())
    conn.stage("legacy", {"x": np.arange(8)}, {})
    attaches = []
    real = shm_mod.shared_memory.SharedMemory

    class Counting(real):
        def __init__(self, *a, **kw):
            attaches.append((a, kw))
            super().__init__(*a, **kw)

    monkeypatch.setattr(shm_mod.shared_memory, "SharedMemory", Counting)
    pay, _ = conn.issue_read("zc").wait()
    assert isinstance(pay, WireChunk)
    pay.release()
    conn.issue_read("legacy").wait()
    assert attaches == []                      # no attach-by-name per read
    conn.complete("zc")
    conn.complete("legacy")
    conn.close()


def test_shm_stages_wirechunk_zero_copy_and_splits_stats():
    conn = SharedMemoryConnector()
    for key, wire in (("raw", WireFormat("raw", "float32")),
                      ("int8", WireFormat("int8"))):
        chunk = WireChunk.from_entries(_entries(with_mla=False), wire, 2, 13)
        n = conn.stage(key, chunk, chunk.meta())
        assert n == chunk.nbytes               # segment == wire layout
        pay, meta = conn.issue_read(key).wait()
        assert isinstance(pay, WireChunk) and meta["wire"].kind == wire.kind
        pay.release()
        conn.complete(key)
    # raw f32 over f32 source: wire ≈ payload + headers (ratio slightly >1)
    # int8: wire ≈ payload/4 + scales — the split exposes the compression
    assert conn.stats.payload_bytes > conn.stats.bytes_moved
    assert conn.stats.wire_compression < 1.0
    assert conn.stats.transfers == 2
    assert conn.pool.in_use == 0 and conn._deferred_close == []
    conn.close()


def test_capabilities_declare_codec_and_sharing():
    inproc = InProcessConnector().capabilities()
    shm = SharedMemoryConnector().capabilities()
    fair = ModeledRDMAConnector().capabilities()
    serial = ModeledRDMAConnector(link_sharing="serial").capabilities()
    for caps in (inproc, shm, fair):
        assert caps.wire_codec == "fixed"
        assert caps.header_bytes == wirefmt.nominal_header_bytes()
    assert shm.zero_copy and shm.cross_process
    assert fair.link_sharing == "fair"
    assert serial.link_sharing == "exclusive"


# --------------------------------------------------------------------- #
# planner: connector-sourced wire model knows headers and link sharing
# --------------------------------------------------------------------- #
def test_connector_wire_time_headers_and_concurrency():
    from repro.core.planner.simulator import connector_wire_time
    nbytes = 1e6
    flat = InProcessConnector(bandwidth_gbps=25.0).capabilities()
    hdr = flat.header_bytes
    assert hdr > 0
    assert connector_wire_time(nbytes, flat) == \
        pytest.approx((nbytes + hdr) / 25e9)
    fair = ModeledRDMAConnector(bandwidth_gbps=25.0,
                                fixed_latency_s=1e-3).capabilities()
    serial = ModeledRDMAConnector(bandwidth_gbps=25.0, fixed_latency_s=1e-3,
                                  link_sharing="serial").capabilities()
    one = 1e-3 + (nbytes + hdr) / 25e9
    # fair share: n flights divide bandwidth, one setup latency each
    assert connector_wire_time(nbytes, fair, concurrent=3) == \
        pytest.approx(1e-3 + 3 * (nbytes + hdr) / 25e9)
    # exclusive link: the last read waits out the queue
    assert connector_wire_time(nbytes, serial, concurrent=3) == \
        pytest.approx(3 * one)
    assert connector_wire_time(nbytes, fair, concurrent=1) == \
        pytest.approx(one)
    assert connector_wire_time(0, fair, concurrent=4) == 0.0
