"""Global scheduler: load-aware routing, fault tolerance (failed D →
re-prefill with prefix), straggler penalty, elastic scale-down, and the
no-lost-request invariant."""
import numpy as np
import pytest

import jax

from repro.core.compat.precision import WireFormat
from repro.core.disagg import DisaggPipeline
from repro.core.kv_transfer import TransferEngine
from repro.models import model as M
from repro.serving.engine import Engine, VendorProfile
from repro.serving.request import Request, State
from repro.serving.scheduler import GlobalScheduler
from tests.conftest import TINY_FAMILIES

CFG = TINY_FAMILIES["dense"]


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.key(1), CFG)


def _engine(name, params, role, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    return Engine(name, CFG, params, VendorProfile("A", block_size=8),
                  role=role, **kw)


def _sched(*engines):
    sched = GlobalScheduler(DisaggPipeline(TransferEngine(),
                                           WireFormat("raw", "float32")))
    for e in engines:
        sched.add_instance(e)
    return sched


def _reqs(n, seed=0, max_new=5):
    rng = np.random.default_rng(seed)
    return [Request(req_id=f"q{i}",
                    prompt=rng.integers(0, CFG.vocab_size, 8).astype(np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def test_all_requests_finish_and_route_across_pool(params):
    p = [_engine(f"P{i}", params, "prefill") for i in range(2)]
    d = [_engine(f"D{i}", params, "decode") for i in range(3)]
    sched = _sched(*(p + d))
    reqs = _reqs(12)
    done = sched.run(reqs, max_ticks=500)
    assert len(done) == 12
    assert all(r.state == State.FINISHED for r in reqs)
    assert sum(sched.stats.p_dispatches.values()) == 12
    # load-aware routing should spread decode work
    assert len([k for k, v in sched.stats.d_dispatches.items() if v > 0]) >= 2


def test_decode_failure_requeues_and_finishes(params):
    """Kill a D instance mid-decode: its KV is lost; the scheduler must
    re-prefill (prefix preserved) and still deliver max_new_tokens."""
    p = _engine("P0", params, "prefill")
    d = _engine("D0", params, "decode")
    sched = _sched(p, d)
    reqs = _reqs(3, max_new=8)
    for r in reqs:
        sched.submit(r)
    for _ in range(3):
        sched.step()
    d.fail()                                  # node dies, volatile KV gone
    for _ in range(200):
        if sched.stats.finished >= 3:
            break
        sched.step()
    assert sched.stats.finished == 3
    assert sched.stats.requeues >= 1
    for r in reqs:
        assert len(r.output_tokens) == 8
        assert r.retries >= 0


def test_prefill_failure_falls_back(params):
    p0 = _engine("P0", params, "prefill")
    p1 = _engine("P1", params, "prefill")
    d = _engine("D0", params, "decode")
    sched = _sched(p0, p1, d)
    p0.fail()
    reqs = _reqs(4)
    done = sched.run(reqs, max_ticks=400)
    assert len(done) == 4
    assert sched.stats.p_dispatches.get("P0", 0) == 0
    assert sched.stats.p_dispatches["P1"] == 4


def test_elastic_drain_stops_new_work(params):
    p = _engine("P0", params, "prefill")
    d0 = _engine("D0", params, "decode")
    d1 = _engine("D1", params, "decode")
    sched = _sched(p, d0, d1)
    sched.remove_instance("D1")               # drain: no new routing
    reqs = _reqs(6)
    done = sched.run(reqs, max_ticks=500)
    assert len(done) == 6
    assert sched.stats.d_dispatches.get("D1", 0) == 0


def test_straggler_penalty_prefers_fast_instance(params):
    p = _engine("P0", params, "prefill")
    d0 = _engine("D0", params, "decode")
    d1 = _engine("D1", params, "decode")
    sched = _sched(p, d0, d1)
    # mark D0 as a 100× straggler via the latency EMA
    sched._ema["D0"] = 1.0
    sched._ema["D1"] = 0.01
    reqs = _reqs(4)
    sched.run(reqs, max_ticks=400)
    assert sched.stats.d_dispatches.get("D1", 0) \
        > sched.stats.d_dispatches.get("D0", 0)


def test_admission_respects_capacity(params):
    """A D pool too small for the request must not admit it."""
    d = _engine("D0", params, "decode", num_blocks=4, max_seq_len=16)
    assert not d.can_admit(seq_len=12, new_tokens=30)
    assert d.can_admit(seq_len=4, new_tokens=4)


def test_engine_stats_accumulate(params):
    p = _engine("P0", params, "prefill")
    d = _engine("D0", params, "decode")
    sched = _sched(p, d)
    sched.run(_reqs(2), max_ticks=200)
    assert p.stats.prefill_tokens > 0
    assert d.stats.decode_tokens > 0
    assert 0 < d.stats.decode_steps <= d.stats.decode_tokens
    # each decoding slot holds at least the page its next token lands in
    assert d.stats.decode_kv_pages >= d.stats.decode_tokens
