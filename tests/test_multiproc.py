"""Multi-instance P/D serving runtime (repro.serving.multiproc).

The acceptance bar for genuine disaggregation:

  1. *parity*: the multi-process runtime (P and D engines in separate OS
     processes, control plane over queues, KV data plane over shared
     memory) produces token-exact output vs the single-process
     ``GlobalScheduler`` serving loop — for the degenerate 1P+1D cluster
     AND a routed 2P×2D cluster.
  2. *failure surfacing*: the P process dying hard (``os._exit``)
     mid-stream must strand no shared-memory segments, the D process must
     surface a transfer failure, and the launcher must requeue — with the
     retry visible in ``TransferStats.retries`` across the process
     boundary — and still finish every request after the respawn. A D
     instance dying in a pool with a *surviving* D must fail over (all
     streams finish on the survivor, no respawn).
  3. *no leaks*: no named shared-memory segments survive a connector
     ``close()``, nor a connector that is dropped without ``close()``
     (the ``weakref.finalize`` guard).
  4. *planner round trip*: ``plan_deployment``'s chosen instance counts
     launch unmodified through ``DeploymentPlan.to_cluster_spec``.
  5. *one chip per worker*: the parent stays off the accelerator, every
     worker is handed its own chip through its environment, and a
     topology wider than the host fails before anything is spawned (the
     chip count is stubbed here; the workers still compute on the CPU).
"""
import gc
import os

import numpy as np
import pytest

import jax

from repro.core.compat.precision import WireFormat
from repro.core.disagg import DisaggPipeline
from repro.core.transport import SharedMemoryConnector
from repro.core.transport.base import TransferStats
from repro.models import model as M
from repro.serving import router
from repro.serving.engine import Engine, VendorProfile
from repro.serving.multiproc import (ClusterRuntime, ClusterSpec, EngineSpec,
                                     TwoProcessRuntime, chips, serve_cluster,
                                     serve_two_process)
from repro.serving.multiproc.launcher import _interval_overlap, _union
from repro.serving.request import Request
from repro.serving.scheduler import GlobalScheduler
from repro.serving.server import Server
from tests.conftest import TINY_FAMILIES

CFG = TINY_FAMILIES["dense"]
# heterogeneous pair: different block size, layout, and TP degree per side
VENDOR_P = VendorProfile("B", block_size=8, layout="nhbd",
                         kv_dtype="float32", tp=2)
VENDOR_D = VendorProfile("A", block_size=4, layout="nbhd",
                         kv_dtype="float32", tp=1)
SEED = 0
CHUNK = 8


def _requests(n=3, max_new=4):
    rng = np.random.default_rng(7)
    return [Request(req_id=f"req-{i}",
                    prompt=rng.integers(0, CFG.vocab_size,
                                        int(rng.integers(14, 30))
                                        ).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _spec(name, vendor, role):
    return EngineSpec(name, CFG, vendor, params_seed=SEED, num_blocks=64,
                      max_batch=4, max_seq_len=64, role=role)


def _serve_single(reqs):
    """Single-process reference: same engines, same connector kind."""
    params = M.init_params(jax.random.key(SEED), CFG)
    mk = lambda name, vendor, role: Engine(
        name, CFG, params, vendor, num_blocks=64, max_batch=4,
        max_seq_len=64, role=role)
    connector = SharedMemoryConnector()
    sched = GlobalScheduler(DisaggPipeline(connector,
                                           WireFormat("raw", "float32")),
                            prefill_chunk=CHUNK)
    sched.add_instance(mk("P0", VENDOR_P, "prefill"))
    sched.add_instance(mk("D0", VENDOR_D, "decode"))
    server = Server(sched)
    for r in reqs:
        server.submit(r)
    ticks = 0
    while sched.stats.finished < len(reqs) and ticks < 2000:
        sched.step()
        ticks += 1
    assert sched.stats.finished == len(reqs)
    connector.close()
    return {r.req_id: list(r.output_tokens) for r in reqs}


def _shm_files():
    """Named shared-memory data segments this test process owns: its own
    connectors' and its cluster workers' (``psm_<this pid>_*``). Segments
    of tests running concurrently in other processes don't count."""
    if not os.path.isdir("/dev/shm"):
        return None
    mine = f"psm_{os.getpid():x}_"
    return {f for f in os.listdir("/dev/shm") if f.startswith(mine)}


# --------------------------------------------------------------------- #
# 1. parity: two OS processes, token-exact vs single-process
# --------------------------------------------------------------------- #
def test_two_process_token_exact_vs_single_process():
    before = _shm_files()
    ref = _serve_single(_requests())

    reqs = _requests()
    tokens, rt = serve_two_process(_spec("P0", VENDOR_P, "prefill"),
                                   _spec("D0", VENDOR_D, "decode"),
                                   reqs, prefill_chunk=CHUNK,
                                   max_wall_s=300.0)
    # really two other OS processes, instance-addressed
    assert set(rt.worker_pids) == {"P0", "D0"}
    assert len({os.getpid(), *rt.worker_pids.values()}) == 3
    assert rt.stats.finished == len(reqs)
    assert tokens == ref

    # KV moved through shared memory (both sides' stats merged home) and
    # the launcher measured real wall-clock handoff intervals
    assert rt.transfer_stats.transfers > 0
    assert rt.transfer_stats.bytes_moved > 0
    assert rt.transfer_stats.wall_handoff_seconds > 0
    assert 0 <= rt.transfer_stats.wall_overlap_seconds \
        <= rt.transfer_stats.wall_handoff_seconds
    # no stranded segments after shutdown
    after = _shm_files()
    if before is not None:
        assert after - before == set()


def test_two_process_backpressure_on_one_slot_channel():
    """A burst of ChunkReady messages must back-pressure on the
    connector's ``max_inflight``, not overrun the channel and fail
    streams: with a 1-read channel every request still completes."""
    reqs = _requests(n=3)
    tokens, rt = serve_two_process(_spec("P0", VENDOR_P, "prefill"),
                                   _spec("D0", VENDOR_D, "decode"),
                                   reqs, prefill_chunk=CHUNK,
                                   connector_kwargs={"max_inflight": 1},
                                   max_wall_s=300.0)
    assert rt.stats.finished == len(reqs)
    assert rt.stats.failed == 0
    assert not rt.stream_failures
    for r in reqs:
        assert len(tokens[r.req_id]) == r.max_new_tokens


# --------------------------------------------------------------------- #
# 2. P dies hard mid-stream → D surfaces it, launcher requeues, recovers
# --------------------------------------------------------------------- #
def test_p_crash_mid_stream_surfaces_failure_and_requeues():
    before = _shm_files()
    reqs = _requests(n=2)
    rt = TwoProcessRuntime(_spec("P0", VENDOR_P, "prefill"),
                           _spec("D0", VENDOR_D, "decode"),
                           prefill_chunk=CHUNK,
                           fault_exit_after_chunks=2)
    rt.start()
    try:
        tokens = rt.serve(reqs, max_wall_s=300.0)
    finally:
        rt.shutdown()

    assert rt.crashes["P"] == 1                # died once, was respawned
    # the D side surfaced the broken stream (abort / lost segment), and the
    # retry crossed the process boundary into the wire's accounting
    assert rt.stream_failures
    assert rt.stats.requeues >= 1
    assert rt.transfer_stats.retries >= 1
    # serving still completed, and the re-prefill was from scratch (the
    # crash hit during prefill, before any generated prefix existed)
    assert rt.stats.finished == len(reqs)
    assert rt.stats.failed == 0
    for r in reqs:
        assert len(tokens[r.req_id]) == r.max_new_tokens
    # the dead attempt's segments were unlinked, not stranded
    after = _shm_files()
    if before is not None:
        assert after - before == set()


# --------------------------------------------------------------------- #
# 2b. N×M cluster: routed 2P×2D parity, D-crash failover onto a survivor
# --------------------------------------------------------------------- #
def _cluster(n_p, n_d):
    return ClusterSpec(
        p=tuple(_spec(f"P{i}", VENDOR_P, "prefill") for i in range(n_p)),
        d=tuple(_spec(f"D{i}", VENDOR_D, "decode") for i in range(n_d)))


def test_cluster_2p2d_token_exact_vs_single_process():
    """Routing across 2 P and 2 D instances (same seed everywhere) must
    not change a single token vs the single-process loop."""
    before = _shm_files()
    reqs = _requests(n=6)
    ref = _serve_single(_requests(n=6))
    tokens, rt = serve_cluster(_cluster(2, 2), reqs, prefill_chunk=CHUNK,
                               max_wall_s=300.0)
    # four real worker processes, all instance-addressed
    assert set(rt.worker_pids) == {"P0", "P1", "D0", "D1"}
    assert len({os.getpid(), *rt.worker_pids.values()}) == 5
    assert rt.stats.finished == len(reqs)
    assert tokens == ref
    # the router actually used the pool: every dispatch is attributed to
    # an instance, and with 6 requests × 2 instances both roles spread
    assert sum(rt.stats.p_dispatches.values()) == len(reqs)
    assert sum(rt.stats.d_dispatches.values()) == len(reqs)
    assert len(rt.stats.d_dispatches) == 2      # both Ds served work
    # the dispatch stamp splits each TTFT into queue wait and flight
    for r in reqs:
        assert r.arrival_time <= r.dispatch_time <= r.first_token_time
    after = _shm_files()
    if before is not None:
        assert after - before == set()


def test_d_crash_fails_over_to_surviving_d_without_respawn():
    """One of two D instances dies hard mid-decode: its streams must
    re-prefill onto the *surviving* D (generated prefix appended — still
    token-exact) with no respawn, and every request must finish."""
    before = _shm_files()
    reqs = _requests(n=4, max_new=4)
    ref = _serve_single(_requests(n=4, max_new=4))
    rt = ClusterRuntime(_cluster(1, 2), prefill_chunk=CHUNK,
                        fault_exit_after_tokens=3)    # lands on D0
    rt.start()
    try:
        tokens = rt.serve(reqs, max_wall_s=300.0)
    finally:
        rt.shutdown()
    assert rt.crashes["D"] == 1
    assert rt.respawns["D"] == 0               # survivor took over instead
    assert "D0" not in rt._instances           # dead member left the pool
    assert rt.stats.finished == len(reqs)
    assert rt.stats.failed == 0
    assert rt.stats.requeues >= 1              # the failover re-prefill
    for r in reqs:
        assert len(tokens[r.req_id]) == r.max_new_tokens
    assert tokens == ref                       # greedy: failover is exact
    # everything finished on the survivor after the crash
    after = _shm_files()
    if before is not None:
        assert after - before == set()


def test_d_crash_failover_retry_resumes_from_prefix_cache():
    """With the prefix cache on, a failover retry must not pay for the
    whole prompt again: the re-prefill replays from P's prefix store
    (at least one full block skipped) and stays token-exact."""
    reqs = _requests(n=4, max_new=4)
    ref = _serve_single(_requests(n=4, max_new=4))
    pspec = lambda name, vendor, role: EngineSpec(
        name, CFG, vendor, params_seed=SEED, num_blocks=64, max_batch=4,
        max_seq_len=64, role=role, prefix_cache=True)
    spec = ClusterSpec(
        p=(pspec("P0", VENDOR_P, "prefill"),),
        d=tuple(pspec(f"D{i}", VENDOR_D, "decode") for i in range(2)))
    rt = ClusterRuntime(spec, prefill_chunk=CHUNK,
                        fault_exit_after_tokens=3)
    rt.start()
    try:
        tokens = rt.serve(reqs, max_wall_s=300.0)
    finally:
        rt.shutdown()
    assert rt.crashes["D"] == 1
    assert rt.respawns["D"] == 0               # survivor took over
    assert rt.stats.finished == len(reqs)
    assert rt.stats.failed == 0
    assert rt.stats.requeues >= 1
    assert tokens == ref                       # cached replay is exact
    # the retry resumed from the longest cached prefix instead of
    # recomputing the prompt from scratch
    assert rt.worker_stats["P0"]["prefix_cached_tokens"] \
        >= VENDOR_P.block_size


# --------------------------------------------------------------------- #
# 2c. planner → runtime round trip
# --------------------------------------------------------------------- #
def test_plan_to_cluster_spec_launches_planned_topology():
    from repro.core.planner.hardware import GPU_A, GPU_B
    from repro.core.planner.optimizer import plan_deployment
    from repro.core.planner.workload import Workload

    # loose SLOs so the tiny config is feasible on the modeled hardware
    wl = Workload(qps=0.1, input_len=32, output_len=8,
                  slo_ttft_s=1e3, slo_tpot_s=1e3)
    plan = plan_deployment(CFG, wl, GPU_B, GPU_A)
    spec = plan.to_cluster_spec(CFG, p_vendor=VENDOR_P, d_vendor=VENDOR_D,
                                params_seed=SEED, num_blocks=64,
                                max_batch=4, max_seq_len=64)
    # the planner's instance allocation is what actually launches
    assert len(spec.p) == plan.n_prefill
    assert len(spec.d) == plan.n_decode
    # default vendors: KV-shard TP must divide the model's KV heads even
    # when the planned compute TP does not
    auto = plan.to_cluster_spec(CFG)
    assert CFG.num_kv_heads % auto.p[0].vendor.tp == 0
    assert CFG.num_kv_heads % auto.d[0].vendor.tp == 0
    # --num-p/--num-d style override
    assert len(plan.to_cluster_spec(CFG, num_p=2, num_d=3).p) == 2
    assert len(plan.to_cluster_spec(CFG, num_p=2, num_d=3).d) == 3

    reqs = _requests(n=3)
    ref = _serve_single(_requests(n=3))
    tokens, rt = serve_cluster(spec, reqs, prefill_chunk=CHUNK,
                               max_wall_s=300.0)
    assert rt.stats.finished == len(reqs)
    assert tokens == ref


# --------------------------------------------------------------------- #
# 2d. routing policy (pure, no processes)
# --------------------------------------------------------------------- #
def test_pick_p_least_outstanding_tokens():
    snaps = [router.PSnapshot("P0", queue_reqs=1, queue_tokens=100),
             router.PSnapshot("P1", queue_reqs=3, queue_tokens=40)]
    assert router.pick_p(snaps) == "P1"        # tokens beat request count
    assert router.pick_p([]) is None
    tie = [router.PSnapshot("P1", 1, 10), router.PSnapshot("P0", 1, 10)]
    assert router.pick_p(tie) == "P0"          # deterministic tiebreak


def _dsnap(iid, active=0, free_blocks=15, max_batch=4, block_size=4,
           max_seq_len=64, block_bytes=1024):
    return router.DSnapshot(iid=iid, active=active, max_batch=max_batch,
                            free_blocks=free_blocks, block_size=block_size,
                            max_blocks_per_seq=-(-max_seq_len // block_size),
                            max_seq_len=max_seq_len, block_bytes=block_bytes)


def test_pick_d_admission_and_load_order():
    # seq 20 + 4 new = 24 tokens → 6 blocks of 4
    assert router.pick_d([_dsnap("D0")], 20, 4) == ("D0", 6)
    # full batch and too-long sequences are inadmissible
    assert router.pick_d([_dsnap("D0", active=4)], 20, 4) is None
    assert router.pick_d([_dsnap("D0")], 80, 4) is None
    assert router.pick_d([_dsnap("D0", free_blocks=5)], 20, 4) is None
    # least occupied wins; free KV-pool bytes breaks occupancy ties
    snaps = [_dsnap("D0", active=2), _dsnap("D1", active=1)]
    assert router.pick_d(snaps, 20, 4)[0] == "D1"
    tie = [_dsnap("D0", active=1, free_blocks=6),
           _dsnap("D1", active=1, free_blocks=12)]
    assert router.pick_d(tie, 20, 4)[0] == "D1"


def test_blocks_needed_mirrors_engine_reservation():
    eng_spec = _spec("Dx", VENDOR_D, "decode")
    eng = eng_spec.build()
    req = Request(req_id="probe",
                  prompt=np.arange(18, dtype=np.int32) % CFG.vocab_size,
                  max_new_tokens=5)
    slot, block_ids = eng.reserve_sequence(req, req.prompt_len)
    want = router.blocks_needed(req.prompt_len + req.max_new_tokens,
                                eng.block_size, eng.max_blocks_per_seq)
    assert len(block_ids) == want


# --------------------------------------------------------------------- #
# 3. segment-leak guard on the connector itself
# --------------------------------------------------------------------- #
def _stage_some(conn, n=3):
    names = []
    for i in range(n):
        key = f"leak-{i}"
        conn.stage(key, {"k": np.arange(64, dtype=np.float32)}, {"i": i})
        names.append(conn.segment_name(key))
    return names


def _assert_unlinked(names):
    from multiprocessing import shared_memory
    for name in names:
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=name)


def test_shm_close_unlinks_every_segment():
    conn = SharedMemoryConnector()
    names = _stage_some(conn)
    conn.close()
    _assert_unlinked(names)


def test_shm_finalizer_unlinks_on_drop_without_close():
    conn = SharedMemoryConnector()
    names = _stage_some(conn)
    del conn                               # no drop(), no close()
    gc.collect()
    _assert_unlinked(names)


def test_shm_adopted_segment_not_unlinked_by_reader():
    """The reader detaches on complete(); only the creator unlinks."""
    creator = SharedMemoryConnector()
    reader = SharedMemoryConnector()
    creator.stage("x", {"k": np.ones(8, np.float32)}, {})
    desc = creator.export_descriptor("x")
    reader.adopt_segment(desc["key"], desc["segment"], desc["nbytes"])
    payload, _meta = reader.issue_read("x").wait()
    np.testing.assert_array_equal(payload["k"], np.ones(8, np.float32))
    reader.complete("x")                   # detach only
    from multiprocessing import shared_memory
    seg = shared_memory.SharedMemory(name=desc["segment"])   # still alive
    seg.close()
    creator.complete("x")                  # creator unlinks
    _assert_unlinked([desc["segment"]])
    reader.close()
    creator.close()


# --------------------------------------------------------------------- #
# 4. launcher accounting helpers
# --------------------------------------------------------------------- #
def test_transfer_stats_merge_sums_counters_and_maxes_peak():
    a = TransferStats(transfers=2, bytes_moved=100, retries=1,
                      peak_buffer_bytes=50, wall_handoff_seconds=1.0)
    b = TransferStats(transfers=3, bytes_moved=10, retries=0,
                      peak_buffer_bytes=80, wall_overlap_seconds=0.5)
    a.merge(b)
    assert (a.transfers, a.bytes_moved, a.retries) == (5, 110, 1)
    assert a.peak_buffer_bytes == 80       # high-water, not a sum
    assert a.wall_handoff_seconds == 1.0
    assert a.wall_overlap_seconds == 0.5


def test_interval_overlap():
    spans = [(0.0, 1.0), (2.0, 3.0)]
    assert _interval_overlap((0.5, 2.5), spans) == pytest.approx(1.0)
    assert _interval_overlap((1.0, 2.0), spans) == 0.0
    assert _interval_overlap((-1.0, 4.0), spans) == pytest.approx(2.0)


def test_union_merges_overlapping_and_drops_empty():
    assert _union([(2.0, 3.0), (0.0, 1.5), (1.0, 2.5), (5.0, 5.0)]) \
        == [(0.0, 3.0)]
    # concurrent in-flight chunks must not double-count overlap: the
    # union of their wire intervals is what gets intersected with compute
    wire = _union([(0.0, 2.0), (1.0, 3.0)])
    assert sum(_interval_overlap(w, [(0.0, 10.0)]) for w in wire) \
        == pytest.approx(3.0)


# --------------------------------------------------------------------- #
# 5. one chip per worker
# --------------------------------------------------------------------- #
def test_topology_wider_than_host_fails_fast(monkeypatch):
    monkeypatch.setattr(chips, "tpu_chip_count", lambda: 2)
    rt = ClusterRuntime(_cluster(2, 2))
    with pytest.raises(RuntimeError, match="4 worker processes, one chip "
                                           "each, but this host has 2"):
        rt.start()
    assert all(i.proc is None for i in rt._instances.values())


def test_parent_holding_the_accelerator_is_refused(monkeypatch):
    jax.devices()                                  # backend initialized
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rt = ClusterRuntime(_cluster(1, 1))
    with pytest.raises(RuntimeError, match="holds its devices"):
        rt.start()


def test_worker_env_confines_one_chip():
    env = chips.worker_env(3)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == \
        f"localhost:{env['TPU_PROCESS_PORT']}"


def test_cluster_workers_hold_one_chip_each(monkeypatch):
    """chip_smoke.py's four-chip path at a tiny size: 2P×2D with four
    (stubbed) chips, each worker reports its own chip and one device, and
    serves what the single-process path serves."""
    import chip_smoke as S
    from test_chip_smoke import TINY
    monkeypatch.setattr(chips, "tpu_chip_count", lambda: 4)
    lengths, max_new, chunk = (8, 16, 24, 40), 3, 16
    reqs = S.build_requests(TINY, lengths, max_new, seed=0)
    tokens, rt = S.serve_cluster(TINY, reqs, lengths, max_new, seed=0,
                                 prefill_chunk=chunk, timeout_s=300.0)
    ref = S.serve_single(TINY, lengths, max_new, seed=0,
                         prefill_chunk=chunk)["tokens"]
    devs = rt.worker_devices
    assert sorted(devs) == ["D0", "D1", "P0", "P1"]
    assert sorted(v["chip"] for v in devs.values()) == ["0", "1", "2", "3"]
    assert all(v["count"] == 1 for v in devs.values())
    assert tokens == ref
