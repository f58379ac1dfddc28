"""The reduction from a profiler trace to device busy time, program time
and attributed idle gaps."""
import gzip
import json
import os

import pytest

from bench.common import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def test_busy_union_programs_and_gaps():
    ms = 1_000_000
    dev = plane("/device:TPU:0",
                **{"XLA Ops": [("fusion.1", 0, 2 * ms),
                               ("fusion.2", 1 * ms, 2 * ms),
                               ("fusion.3", 6 * ms, 2 * ms)],
                   "XLA Modules": [("jit__decode(12)", 0, 3 * ms),
                                   ("jit__prefill_chunk(7)", 6 * ms, 2 * ms)]})
    host = plane("/host:CPU",
                 python=[("bench.scheduler_step", 0, 5 * ms),
                         ("bench.decode_step", 0, 4 * ms),
                         ("bench.wait_for_arrival", 5 * ms, 1 * ms),
                         ("bench.scheduler_step", 6 * ms, 4 * ms),
                         ("PjitFunction(f)", 0, 1 * ms)])
    s = T.reduce_planes([host, dev])
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(5e-3)
    assert s["programs"]["jit__decode"] == [pytest.approx(3e-3), 1]
    assert s["classes"]["decode"][0] == pytest.approx(3e-3)
    assert s["classes"]["prefill"][0] == pytest.approx(2e-3)
    # gaps 3-6 ms and 8-10 ms: 3-4 under decode_step, 4-5 under the step,
    # 5-6 waiting for an arrival, 8-10 under the second step
    g = s["idle_gaps"]
    assert g["bench.decode_step"] == pytest.approx(1e-3)
    assert g["bench.scheduler_step"] == pytest.approx(3e-3)
    assert g["bench.wait_for_arrival"] == pytest.approx(1e-3)
    b = T.breakdown(s)
    assert b["device_ops"][0][0] == "jit__decode"
    assert len(b["idle_gaps"]) == 3


def test_no_device_plane_reads_nothing():
    assert T.reduce_planes([plane("/host:CPU", python=[])]) is None


def test_program_classes():
    assert T.program_class("jit__decode") == "decode"
    assert T.program_class("jit__prefill_chunk") == "prefill"
    assert T.program_class("jit__repage_kv_entry") == "repage"
    assert T.program_class("jit__repage_mla_part") == "repage"
    assert T.program_class("jit_decode_wire") is None


def test_recorded_tpu_trace():
    """A 0.65 s slice of a traced qwen3-4b.chat window on a TPU v5e, cut
    to the lines the reduction reads (device ops and modules, harness
    spans), times in ns from the slice's start."""
    with gzip.open(os.path.join(HERE, "data", "tpu_v5e_chat_trace.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    planes = [{"name": p["name"],
               "lines": [{"name": l["name"],
                          "events": [tuple(e) for e in l["events"]]}
                         for l in p["lines"]]} for p in rec["planes"]]
    s = T.reduce_planes(planes)
    assert s["devices"] == 1
    assert s["busy_s"] == pytest.approx(0.315470394, rel=1e-6)
    assert s["classes"]["decode"] == [pytest.approx(0.219600812), 5]
    assert s["classes"]["prefill"] == [pytest.approx(0.069826217), 4]
    assert s["classes"]["repage"] == [pytest.approx(0.020583967), 4]
    gaps = s["idle_gaps"]
    assert gaps["bench.scheduler_step"] == pytest.approx(0.244256044)
    assert sum(gaps.values()) + s["busy_s"] == pytest.approx(0.65, abs=0.02)
