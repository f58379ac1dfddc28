"""The program's spans, scopes and stamps in a traced run: idle
attribution to ``pd.*`` spans, the scope split of the decode programs,
and the readers built on them."""
import gzip
import json
import math
import os
import random
import re
import types

import pytest

from bench.common import harness
from bench.common import program_trace as PT
from bench.common import trace as T
from bench.common.readers import View

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def host(*events):
    return plane("/host:CPU", python=list(events))


def without_scopes(p):
    """A plane as ``trace.reduce_planes`` reads it: (name, start, dur)."""
    return {"name": p["name"],
            "lines": [{"name": line["name"],
                       "events": [tuple(e[:3]) for e in line["events"]]}
                      for line in p["lines"]]}


# window 0–12 ms (the harness's spans); program spans nested inside
HOST = host(("bench.scheduler_step", 0, 10 * MS),
            ("bench.wait_for_arrival", 10 * MS, 2 * MS),
            ("pd.tick", 0, 10 * MS),
            ("pd.decode.step", 1 * MS, 4 * MS),
            ("pd.decode.fetch", 2 * MS, 1 * MS),
            ("pd.handoff.repage", 6 * MS, 1 * MS),
            ("PjitFunction(f)", 0, 1 * MS))
DEV_A = plane("/device:TPU:0", **{
    "XLA Modules": [("jit__decode(12)", 0, 4.5 * MS),
                    ("jit__prefill_chunk(3)", 8 * MS, 1 * MS)],
    "XLA Ops": [("fusion.1", 0, 1 * MS, "attention"),
                ("fusion.2", 3 * MS, 1 * MS, "mlp"),
                ("fusion.3", 8 * MS, 1 * MS, "attention")]})
DEV_B = plane("/device:TPU:1", **{
    "XLA Modules": [("jit__decode(12)", 2.5 * MS, 4 * MS)],
    "XLA Ops": [("fusion.7", 0, 2 * MS, "lm_head"),
                ("fusion.8", 2.5 * MS, 4 * MS, "attention"),
                ("fusion.9", 11 * MS, 1 * MS, "")]})


def test_idle_goes_to_the_innermost_program_span_averaged_over_devices():
    s = PT.reduce_planes([HOST, DEV_A, DEV_B])
    assert s["devices"] == 2
    # device A idle 1–3, 4–8, 9–12 ms: 2–3 under the fetch, 1–2 and 4–5
    # under the step, 6–7 under the re-page, 5–6, 7–8 and 9–10 under the
    # tick alone, 10–12 under no program span. Device B idle 2–2.5 ms
    # (fetch) and 6.5–11 ms: 6.5–7 re-page, 7–10 tick, 10–11 outside.
    want = {"pd.decode.fetch": 0.75, "pd.decode.step": 1.0,
            "pd.handoff.repage": 0.75, "pd.tick": 3.0,
            PT.OUTSIDE: 1.5}
    assert set(s["idle"]) == set(want)
    for k, v in want.items():
        assert s["idle"][k] == pytest.approx(v * 1e-3), k
    assert PT.idle_under(s, "pd.decode.") == pytest.approx(1.75e-3)
    assert PT.idle_under(s, "pd.handoff.") == pytest.approx(0.75e-3)
    # every idle second of the window is attributed exactly once: the
    # sum is the harness's idle time (window − busy)
    t = T.reduce_planes([without_scopes(p) for p in (HOST, DEV_A, DEV_B)])
    assert sum(s["idle"].values()) == pytest.approx(12e-3 - t["busy_s"])
    assert sum(s["idle"].values()) == pytest.approx(
        sum(t["idle_gaps"].values()))


def test_scope_split_of_the_decode_programs():
    s = PT.reduce_planes([HOST, DEV_A, DEV_B])
    # one decode program on each device; ops outside them do not count
    assert s["decode_calls"] == 1.0
    assert s["scopes"] == {"attention": pytest.approx(2.5e-3),
                           "mlp": pytest.approx(0.5e-3)}


def test_scope_of_an_op_path():
    assert PT.scope_of("jit(_decode)/while/body/closed_call/attention/"
                       "bshk,hkd->bsd/dot_general") == "attention"
    assert PT.scope_of("jit(_decode)/while/body/mlp/dot_general") == "mlp"
    assert PT.scope_of("jit(_decode)/lm_head/dot_general") == "lm_head"
    assert PT.scope_of("jit(_decode)/attention_decode/add") == ""
    assert PT.scope_of("fusion.12") == ""


@pytest.mark.parametrize("seed", range(4))
def test_split_follows_the_harness_rule(seed):
    """The same attribution as ``trace._attribute`` on random gaps and
    overlapping spans, nested or not."""
    rng = random.Random(seed)
    for _ in range(100):
        gaps, t = [], 0
        for _ in range(rng.randint(0, 25)):
            t += rng.randint(0, 50)
            a = t
            t += rng.randint(1, 40)
            gaps.append((a, t))
        spans = []
        for k in range(rng.randint(0, 12)):
            a = rng.randint(0, t + 20)
            spans.append((a, a + rng.randint(0, 200), f"s{k % 4}"))
        want = T._attribute(gaps, spans)
        got = PT.attribute(gaps, spans, "outside_harness_spans")
        assert set(got) == set(want)
        for k, v in want.items():
            assert got[k] == pytest.approx(v, abs=1e-15)


def test_a_trace_without_program_spans_or_scopes_reads_nothing():
    """The parent program: harness spans only, ops without scopes."""
    bare = [host(("bench.scheduler_step", 0, 10 * MS)),
            plane("/device:TPU:0", **{
                "XLA Modules": [("jit__decode(1)", 0, 2 * MS)],
                "XLA Ops": [("fusion.1", 0, 2 * MS)]})]
    s = PT.reduce_planes(bare)
    assert s["idle"] == {PT.OUTSIDE: pytest.approx(8e-3)}
    assert PT.idle_under(s, "pd.decode.") is None
    assert s["scopes"] == {"": pytest.approx(2e-3)}
    assert PT.reduce_planes([host()]) is None
    assert PT.idle_under(None, "pd.") is None


def test_decode_scopes_come_from_the_programs_hlo_in_the_trace(tmp_path):
    """A CPU trace of a scanned ``_decode`` program: the profiler keeps its
    optimised HLO in the metadata plane, and each instruction maps to the
    scope its op path names."""
    import jax
    import jax.numpy as jnp

    def layer(c, w):
        with jax.named_scope("attention"):
            y = jnp.tanh(c @ w)
        with jax.named_scope("mlp"):
            y = y + jax.nn.silu(y @ w)
        return y, None

    @jax.jit
    def _decode(x, ws):
        y, _ = jax.lax.scan(layer, x, ws)
        with jax.named_scope("lm_head"):
            return y @ ws[0]

    @jax.jit
    def _prefill_chunk(x, ws):
        with jax.named_scope("attention"):
            return x @ ws[0]

    x, ws = jnp.ones((4, 16)), jnp.ones((2, 16, 16)) * 0.01
    _decode(x, ws).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        _decode(x, ws).block_until_ready()
        _prefill_chunk(x, ws).block_until_ready()
    with open(T.find_xplane(str(tmp_path)), "rb") as f:
        scopes = PT.decode_scopes(f.read())
    names = [n for n in scopes if T.program_class(n) == "decode"]
    assert "jit__decode" in names and len(names) == 2   # with and without id
    assert set(scopes["jit__decode"].values()) == {"attention", "mlp",
                                                  "lm_head"}
    hlo = _decode.lower(x, ws).compile().as_text()
    for op, scope in scopes["jit__decode"].items():
        line = re.search(rf"%{re.escape(op)} = .*", hlo).group(0)
        assert f"/{scope}/" in line, (op, line)


def test_ops_take_the_scope_of_the_decode_program_they_run_in():
    ev = lambda name, t0, d: types.SimpleNamespace(  # noqa: E731
        name=name, start_ns=t0, duration_ns=d)
    mods = [("jit__decode(7)", 0.0, 10.0), ("jit__prefill_chunk(3)", 20.0,
                                            10.0)]
    scopes = {"jit__decode": {"fusion.1": "attention", "fusion.2": "mlp"}}
    ops = PT._scoped_ops([
        ev("%fusion.1 = bf16[16,2560]{1,0} fusion(%p0), kind=kOutput", 1, 2),
        ev("%fusion.2 = bf16[16,2560]{1,0} fusion(%fusion.1)", 4, 2),
        ev("%fusion.9 = f32[16]{0} fusion(%p1)", 7, 1),
        ev("%fusion.1 = bf16[1,256,2560]{2,1,0} fusion(%p0)", 21, 2),
        ev("%copy.3 = bf16[16]{0} copy(%p2)", 15, 1)], mods, scopes)
    assert [o[3] for o in ops] == ["attention", "mlp", "", "", ""]
    assert ops[0][1:3] == (1.0, 2.0)


def recorded():
    with gzip.open(os.path.join(HERE, "data",
                                "tpu_v5e_chat_program_trace.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    return [{"name": p["name"],
             "lines": [{"name": line["name"],
                        "events": [tuple(e) for e in line["events"]]}
                       for line in p["lines"]]} for p in rec["planes"]]


def test_recorded_tpu_trace():
    """0.65 s of a traced qwen3-4b.chat window on a TPU v5e: three prompt
    chunks with their handoff, six decode steps. The sums below are the
    harness's own attribution rule (``trace._attribute``) applied to the
    ``pd.*`` spans, and plain sums of the scoped ops' durations."""
    planes = recorded()
    s = PT.reduce_planes(planes)
    idle = s["idle"]
    want = {"pd.handoff.repage": 0.150066767,
            "pd.handoff.extract": 0.047965337,
            "pd.decode.fetch": 0.025680891,
            "pd.decode.sample": 0.02188473,
            "pd.prefill.chunk": 0.014805892,
            "pd.decode.prepare": 0.0092187,
            "pd.tick": 0.006002992,
            "pd.decode.launch": 0.001918275,
            "pd.prefill.first_token": 0.001894902,
            "pd.handoff.encode": 0.00028545,
            "pd.sched.dispatch": 0.00028031,
            "pd.decode.step": 0.000086181,
            "pd.handoff.read": 0.00006461,
            "pd.handoff.stage": 0.00006053,
            "pd.handoff.to_device": 0.00003703,
            PT.OUTSIDE: 0.00014313}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v, rel=1e-6), k
    # the program spans account for no more than the traced idle time:
    # with what lies outside them, exactly the window less the busy time
    t = T.reduce_planes([without_scopes(p) for p in planes])
    traced_idle = 0.65 - t["busy_s"]
    assert t["busy_s"] == pytest.approx(0.369604273, rel=1e-6)
    assert sum(v for k, v in idle.items() if k != PT.OUTSIDE) <= traced_idle
    assert sum(idle.values()) == pytest.approx(traced_idle, rel=1e-9)
    assert sum(idle.values()) == pytest.approx(sum(t["idle_gaps"].values()))
    assert PT.idle_under(s, "pd.handoff.") == pytest.approx(0.198479724)
    assert PT.idle_under(s, "pd.decode.") == pytest.approx(0.058788777)
    assert s["decode_calls"] == 6
    assert s["scopes"]["attention"] == pytest.approx(0.176680034, rel=1e-6)
    assert s["scopes"]["mlp"] == pytest.approx(0.045046323, rel=1e-6)
    assert s["scopes"]["lm_head"] == pytest.approx(0.006221436, rel=1e-6)


# -- readers -------------------------------------------------------------- #
def req(arrival, dispatch, first):
    return types.SimpleNamespace(arrival_time=arrival, dispatch_time=dispatch,
                                 first_token_time=first)


def view(requests=(), records=(), trace_dir=None):
    oc = types.SimpleNamespace(requests=list(requests), records=list(records),
                               trace_window=None, trace_dir=trace_dir)
    return View(oc, {}, {}, None)


def read(name, v):
    return harness.metric_reader(name).read(v)


def test_queue_wait_and_prefill_flight_with_a_miss():
    # 20 requests: waits 0..18 ms, flights 100..118 ms, one never dispatched
    reqs = [req(1.0, 1.0 + i / 1000, 1.1 + 2 * i / 1000) for i in range(19)]
    reqs.append(req(1.0, None, None))
    v = view(reqs)
    # p90 of 20 values sits at rank 17.1: 17 ms and 18 ms, the miss last
    assert read("queue_wait_p90_ms", v) == pytest.approx(17.1)
    assert read("prefill_flight_p90_ms", v) == pytest.approx(117.1)
    # a miss inside the tail reads as a miss
    v = view(reqs[:9] + [req(1.0, None, None)])
    assert read("queue_wait_p90_ms", v) == math.inf
    # dispatched but no first token: only the flight misses
    v = view([req(1.0, 1.002, None), req(1.0, 1.004, 1.2)])
    assert read("queue_wait_p90_ms", v) == pytest.approx(3.8)
    assert read("prefill_flight_p90_ms", v) == math.inf


def test_stamp_readers_on_an_empty_window_or_an_older_program():
    assert read("queue_wait_p90_ms", view()) is None
    assert read("prefill_flight_p90_ms", view()) is None
    old = [types.SimpleNamespace(arrival_time=1.0, first_token_time=1.5)]
    assert read("queue_wait_p90_ms", view(old)) is None
    assert read("prefill_flight_p90_ms", view(old)) is None


def rec(kind, tokens):
    return types.SimpleNamespace(kind=kind, t0=0.0, t1=0.0, tokens=tokens,
                                 context=0)


def test_trace_readers(monkeypatch):
    s = PT.reduce_planes([HOST, DEV_A, DEV_B])
    monkeypatch.setattr(PT, "summary", lambda v: s)
    v = view(records=[rec("prefill", 256), rec("prefill", 244),
                      rec("decode", 4)])
    # 0.75 ms of re-page idle over 0.5 k prompt tokens
    assert read("handoff_idle_ms_per_ktok", v) == pytest.approx(1.5)
    # 1.75 ms under pd.decode.* over one decode program
    assert read("decode_host_idle_ms.online", v) == pytest.approx(1.75)
    assert read("decode_attention_ms.online", v) == pytest.approx(2.5)
    # no prompt tokens in the window
    assert read("handoff_idle_ms_per_ktok", view()) is None


def test_trace_readers_without_a_trace_or_program_spans(monkeypatch):
    v = view(records=[rec("prefill", 256)])
    for name in ("handoff_idle_ms_per_ktok", "decode_host_idle_ms.online",
                 "decode_attention_ms.online"):
        assert read(name, v) is None            # no trace at all
    bare = PT.reduce_planes([
        host(("bench.scheduler_step", 0, 10 * MS)),
        plane("/device:TPU:0", **{
            "XLA Modules": [("jit__decode(1)", 0, 2 * MS)],
            "XLA Ops": [("fusion.1", 0, 2 * MS, "")]})])
    monkeypatch.setattr(PT, "summary", lambda v: bare)
    for name in ("handoff_idle_ms_per_ktok", "decode_host_idle_ms.online",
                 "decode_attention_ms.online"):
        assert read(name, v) is None, name
