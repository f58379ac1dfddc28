"""Program spans (``repro.serving.tracing``) on the P→D path, the
dispatch stamp that splits TTFT, and the named scopes of the decode
program."""
import contextlib
import glob
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ConnectorConfig
from repro.core.compat.precision import WireFormat
from repro.core.disagg import DisaggPipeline
from repro.models import model as M
from repro.serving import tracing
from repro.serving.engine import Engine, VendorProfile
from repro.serving.request import Request, State
from repro.serving.scheduler import GlobalScheduler
from tests.conftest import TINY_FAMILIES, hlo_without_metadata

CFG = TINY_FAMILIES["dense"]
CHUNK = 8

SPANS = ("pd.tick", "pd.sched.dispatch", "pd.prefill.chunk",
         "pd.prefill.first_token", "pd.handoff.extract", "pd.handoff.encode",
         "pd.handoff.stage", "pd.handoff.read", "pd.handoff.to_device",
         "pd.handoff.repage", "pd.handoff.finalize", "pd.decode.step",
         "pd.decode.prepare", "pd.decode.launch", "pd.decode.fetch",
         "pd.decode.sample")


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.key(3), CFG)


def _engines(params):
    p = Engine("P0", CFG, params, VendorProfile("P", block_size=16, tp=2),
               num_blocks=1, max_batch=4, max_seq_len=64, role="prefill")
    d = Engine("D0", CFG, params, VendorProfile("D", block_size=8),
               num_blocks=64, max_batch=4, max_seq_len=64, role="decode")
    return p, d


def _sched(p, d, clock=None):
    kw = {} if clock is None else {"clock": clock}
    sched = GlobalScheduler(
        DisaggPipeline(ConnectorConfig(kind="inproc").build(),
                       WireFormat("raw", "float32")),
        prefill_chunk=CHUNK, **kw)
    sched.add_instance(p)
    sched.add_instance(d)
    return sched


def _reqs(n, prompt_len=20, max_new=4):
    rng = np.random.default_rng(0)
    return [Request(req_id=f"t{i}", max_new_tokens=max_new,
                    prompt=rng.integers(0, CFG.vocab_size,
                                        prompt_len).astype(np.int32))
            for i in range(n)]


def _host_events(log_dir):
    """(name, start, end, args) of every ``pd.*`` event on a host line,
    per line."""
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("pd.")]
            if evs:
                lines.append(evs)
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_of_a_served_run(params, tmp_path):
    p, d = _engines(params)
    _sched(p, d).run(_reqs(2), max_ticks=200)    # compile outside the trace
    more = _reqs(2)
    for r in more:
        r.req_id = "u" + r.req_id
    pages0 = d.stats.decode_kv_pages
    with jax.profiler.trace(str(tmp_path)):
        _sched(p, d).run(more, max_ticks=200)
    assert all(r.state == State.FINISHED for r in more)
    events = [e for line in _host_events(tmp_path) for e in line]
    names = {e[0] for e in events}
    assert set(SPANS) <= names, set(SPANS) - names
    ids = {r.req_id for r in more}
    for name, _t0, _t1, args in events:
        if name.startswith(("pd.handoff.", "pd.prefill.")):
            assert args.get("req") in ids, (name, args)
        if name == "pd.prefill.chunk":
            assert 0 < args["tokens"] <= CHUNK
        if name == "pd.decode.step":
            assert 1 <= args["batch"] <= 2
            # the CPU takes the reference; pages below the slots' lengths
            assert args["attn"] == "ref"
            assert args["batch"] <= args["kv_pages"] <= args["batch"] * 4
    assert sum(e[3]["kv_pages"] for e in events
               if e[0] == "pd.decode.step") == (
                   d.stats.decode_kv_pages - pages0)
    # every request's handoff is spanned under its own id
    for rid in ids:
        assert {"pd.prefill.chunk", "pd.handoff.extract",
                "pd.handoff.stage", "pd.handoff.repage",
                "pd.handoff.finalize"} <= {
                    e[0] for e in events if e[3].get("req") == rid}
    # nesting on the host thread: decode phases under the step, the step
    # and the P-side work under a tick
    ticks = [e for e in events if e[0] == "pd.tick"]
    steps = [e for e in events if e[0] == "pd.decode.step"]
    for e in events:
        if e[0].startswith("pd.decode.") and e[0] != "pd.decode.step":
            assert any(_inside(e, s) for s in steps), e
        if e[0] != "pd.tick":
            assert any(_inside(e, t) for t in ticks), e


def test_span_is_a_shared_null_context_without_a_trace():
    a = tracing.span("pd.decode.step", batch=3)
    b = tracing.span("pd.tick")
    assert a is b
    assert isinstance(a, contextlib.nullcontext)
    with a:
        pass


def test_dispatch_time_is_stamped_once_and_kept_across_a_requeue(params):
    ticks = iter(range(1, 10_000))
    p, d = _engines(params)
    sched = _sched(p, d, clock=lambda: float(next(ticks)))
    req = _reqs(1, prompt_len=3 * CHUNK + 2)[0]
    sched.submit(req)
    assert req.dispatch_time is None
    sched.step()                           # dispatched, first chunk sent
    assert req.state == State.PREFILLING
    first = req.dispatch_time
    assert req.arrival_time <= first
    d.fail()                               # the flight dies mid-stream
    sched.step()
    assert req.retries == 1 and req.dispatch_time == first
    for _ in range(100):
        if req.state == State.FINISHED:
            break
        sched.step()
    assert req.state == State.FINISHED
    assert req.dispatch_time == first
    assert req.arrival_time <= req.dispatch_time <= req.first_token_time
    assert req.ttft() == pytest.approx(
        (req.dispatch_time - req.arrival_time)
        + (req.first_token_time - req.dispatch_time))


def _decode_args(eng):
    b = eng.max_batch
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return (eng.params, i32(np.zeros((b, 1))), i32(np.arange(b)),
            i32(eng.block_tables), i32(np.zeros(b)), i32(np.arange(b)),
            eng.caches)


def test_decode_program_scopes_change_metadata_only(params, monkeypatch):
    _, d = _engines(params)
    lowered = d._decode_fn.lower(*_decode_args(d))
    text = lowered.as_text(dialect="hlo", debug_info=True)
    for scope in ("attention", "mlp", "lm_head"):
        assert re.search(rf'op_name="([^"]*/)?{scope}/', text), scope
    scoped = lowered.compile().as_text()
    assert "/attention/" in scoped and "/lm_head/" in scoped
    # the same program built with every scope a no-op
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, bare = _engines(params)
    plain = bare._decode_fn.lower(*_decode_args(bare)).compile().as_text()
    assert "/attention/" not in plain
    assert hlo_without_metadata(scoped) == hlo_without_metadata(plain)
