"""The program's own spans and scopes in a traced run.

The program marks its host phases with ``pd.*`` spans
(``repro.serving.tracing``) and its device code with ``jax.named_scope``
(attention, MLP, LM head); both land in the profiler trace that
``trace.py`` reduces. This module re-reads the run's ``.xplane.pb`` for
them, once per path:

  * idle by program span: each device-idle gap inside the traced window
    goes to the innermost ``pd.*`` span that covers it, else to
    ``outside_program_spans`` (the rule ``trace.py`` applies to the
    harness's ``bench.*`` spans), averaged over devices;
  * scope split: device time of the "XLA Ops" events that run inside a
    decode program, by the scope their op path names, per device. The
    device's events name only the HLO instruction; its op path (the
    ``op_name`` metadata, where ``jax.named_scope`` writes) comes from the
    program's HLO, which the profiler keeps in the trace's
    ``/host:metadata`` plane.

It also reads the program's request stamps. A trace or a request
without such spans, scopes or stamps reads as empty: the readers then
return None.
"""
from __future__ import annotations

import bisect
import functools
from typing import Any, Dict, List, Optional, Tuple

from bench.common import stats
from bench.common.trace import clean_name, find_xplane, program_class, union

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "pd."
HARNESS_PREFIX = "bench."
OUTSIDE = "outside_program_spans"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
SCOPES = ("attention", "mlp", "lm_head")


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` named in an op path
    (``jit(_decode)/while/body/attention/dot_general`` → ``attention``),
    else ""."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return ""


def split(gaps: List[Tuple[float, float]],
          spans: List[Tuple[float, float, str]],
          outside: str = OUTSIDE) -> Dict[str, List[Tuple[float, float]]]:
    """The parts of ``gaps`` (disjoint intervals) under each span name,
    each part going to the innermost (shortest) span that covers it; what
    no span covers goes to ``outside``. Spans are taken shortest first,
    each cutting from what the shorter ones left."""
    out: Dict[str, List[Tuple[float, float]]] = {}
    left = sorted(gaps)
    for s0, s1, name in sorted(spans, key=lambda s: s[1] - s[0]):
        i = max(bisect.bisect_right(left, s0, key=_start) - 1, 0)
        j = bisect.bisect_left(left, s1, lo=i, key=_start)
        keep = []
        for a, b in left[i:j]:
            lo, hi = max(a, s0), min(b, s1)
            if lo < hi:
                out.setdefault(name, []).append((lo, hi))
                if a < lo:
                    keep.append((a, lo))
                if hi < b:
                    keep.append((hi, b))
            else:
                keep.append((a, b))
        left[i:j] = keep
    if left:
        out[outside] = left
    return out


def _start(iv: Tuple[float, float]) -> float:
    return iv[0]


def attribute(gaps: List[Tuple[float, float]],
              spans: List[Tuple[float, float, str]],
              outside: str = OUTSIDE) -> Dict[str, float]:
    """Seconds of ``gaps`` (ns intervals) under each span name, by the
    rule of ``split``."""
    return {k: sum(b - a for a, b in v) * 1e-9
            for k, v in split(gaps, spans, outside).items()}


def idle_gaps(busy: List[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi) that no busy interval covers."""
    gaps, prev = [], lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, min(a, hi)))
        prev = max(prev, b)
    if hi > prev:
        gaps.append((prev, hi))
    return [(a, b) for a, b in gaps if b > a]


def host_spans(planes, prefix: str) -> List[Tuple[float, float, str]]:
    """(start, end, name) of the host events named ``prefix``*."""
    out = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                out += [(e[1], e[1] + e[2], e[0]) for e in line["events"]
                        if e[0].startswith(prefix)]
    return out


def reduce_planes(planes: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """``planes`` as ``trace.reduce_planes`` takes them, where an "XLA
    Ops" event may carry a fourth field, its scope. None when no device
    plane holds an operation. The window is the harness's spans' extent
    (as in ``trace.reduce_planes``), else the program's, else the busy
    extent."""
    spans = host_spans(planes, PREFIX)
    frame = host_spans(planes, HARNESS_PREFIX) or spans
    idle: Dict[str, float] = {}
    scopes: Dict[str, float] = {}
    decode_calls = 0
    n = 0
    for p in planes:
        if not p["name"].startswith("/device:") \
                or p["name"].startswith("/device:CPU"):
            continue
        lines = {line["name"]: line["events"] for line in p["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        n += 1
        busy = union([(e[1], e[1] + e[2]) for e in ops])
        lo = min((s[0] for s in frame), default=busy[0][0])
        hi = max((s[1] for s in frame), default=busy[-1][1])
        for k, v in attribute(idle_gaps(busy, lo, hi), spans).items():
            idle[k] = idle.get(k, 0.0) + v
        decode = sorted((e[1], e[1] + e[2]) for e in lines.get(MODULES_LINE, [])
                        if program_class(e[0]) == "decode"
                        and lo <= e[1] < hi)
        decode_calls += len(decode)
        scopes_dev = _scope_split(ops, decode)
        for k, v in scopes_dev.items():
            scopes[k] = scopes.get(k, 0.0) + v
    if not n:
        return None
    return {"devices": n,
            "idle": {k: v / n for k, v in idle.items()},
            "program_spans": sorted({s[2] for s in spans}),
            "decode_calls": decode_calls / n,
            "scopes": {k: v / n for k, v in scopes.items()}}


def _scope_split(ops, programs: List[Tuple[float, float]]
                 ) -> Dict[str, float]:
    """Device seconds of the ops that start inside one of ``programs``
    (sorted intervals), by scope ("" for none): the union of each scope's
    op intervals, so an op that encloses others (a loop) counts once."""
    if not programs:
        return {}
    starts = [a for a, _ in programs]
    spans: Dict[str, List[Tuple[float, float]]] = {}
    for e in ops:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i < 0 or e[1] >= programs[i][1]:
            continue
        spans.setdefault(e[3] if len(e) > 3 else "", []).append(
            (e[1], e[1] + e[2]))
    return {k: sum(b - a for a, b in union(v)) * 1e-9
            for k, v in spans.items()}


def read_planes(path: str) -> List[Dict[str, Any]]:
    """The host lines and the device "XLA Ops" / "XLA Modules" lines of
    an ``.xplane.pb``. An op that runs inside a decode program carries the
    scope its HLO instruction's op path names, as a fourth field."""
    import jax
    with open(path, "rb") as f:
        raw = f.read()
    scopes = decode_scopes(raw)
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            out.append({"name": plane.name, "lines": [
                {"name": line.name, "events": [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]} for line in plane.lines]})
            continue
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = [(e.name, float(e.start_ns), float(e.duration_ns))
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else [])]
        got = [{"name": MODULES_LINE, "events": mods}]
        if OPS_LINE in lines:
            got.append({"name": OPS_LINE, "events": _scoped_ops(
                lines[OPS_LINE].events, mods, scopes)})
        out.append({"name": plane.name, "lines": got})
    return out


def _scoped_ops(events, mods, scopes: Dict[str, Dict[str, str]]):
    """(name, start, duration, scope) of each op; the scope is looked up
    in the HLO of the decode program the op runs in, "" elsewhere."""
    mods = sorted((t0, t0 + d, scopes.get(name) or scopes.get(
        clean_name(name)) or {}) for name, t0, d in mods)
    starts = [m[0] for m in mods]
    out = []
    for e in events:
        t0 = float(e.start_ns)
        i = bisect.bisect_right(starts, t0) - 1
        table = mods[i][2] if i >= 0 and t0 < mods[i][1] else {}
        # "%fusion.28 = bf16[16,2560]{...} fusion(...), ..." → fusion.28
        op = e.name.split(" ", 1)[0].lstrip("%")
        out.append((e.name, t0, float(e.duration_ns), table.get(op, "")))
    return out


# -- the program's HLO, from the trace's metadata plane ------------------- #
def decode_scopes(raw: bytes) -> Dict[str, Dict[str, str]]:
    """{program name: {HLO instruction: scope}} of the decode programs
    whose HLO the serialized XSpace ``raw`` carries; a program is named
    both as the trace names it (``jit__decode(<id>)``) and without its id
    when that name is unique."""
    out: Dict[str, Dict[str, str]] = {}
    buf = memoryview(raw)
    for num, val in _fields(buf):
        if num != 1:                                  # XSpace.planes
            continue
        plane = buf[val[0]:val[0] + val[1]]
        fields = list(_fields(plane))
        name = next((bytes(plane[v[0]:v[0] + v[1]]).decode()
                     for k, v in fields if k == 2), "")
        if name != METADATA_PLANE:
            continue
        hlo_id = None
        for k, v in fields:
            if k == 5:                                # stat_metadata map
                md = _sub(plane[v[0]:v[0] + v[1]], 2)  # XStatMetadata
                if _string(md, 2) == HLO_PROTO_STAT:
                    hlo_id = _int(md, 1)
        for k, v in fields:
            if k != 4 or hlo_id is None:              # event_metadata map
                continue
            em = _sub(plane[v[0]:v[0] + v[1]], 2)     # XEventMetadata
            prog = _string(em, 2)
            if program_class(prog) != "decode":
                continue
            for k2, v2 in _fields(em):
                if k2 != 5:                           # XEventMetadata.stats
                    continue
                stat = em[v2[0]:v2[0] + v2[1]]
                if _int(stat, 1) == hlo_id:
                    out[prog] = _instruction_scopes(_sub(stat, 6))
    by_short: Dict[str, List[str]] = {}
    for prog in out:
        by_short.setdefault(clean_name(prog), []).append(prog)
    for short, progs in by_short.items():
        if len(progs) == 1 and short not in out:
            out[short] = out[progs[0]]
    return out


def _instruction_scopes(hlo: memoryview) -> Dict[str, str]:
    """HloProto → {instruction name: scope of its ``op_name``}."""
    out = {}
    module = _sub(hlo, 1)                            # HloProto.hlo_module
    for k, v in _fields(module):
        if k != 3:                                    # computations
            continue
        comp = module[v[0]:v[0] + v[1]]
        for k2, v2 in _fields(comp):
            if k2 != 2:                               # instructions
                continue
            inst = comp[v2[0]:v2[0] + v2[1]]
            meta = _sub(inst, 7)                      # OpMetadata
            scope = scope_of(_string(meta, 2)) if meta is not None else ""
            if scope:
                out[_string(inst, 1)] = scope
    return out


def _fields(buf: memoryview):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, (offset, length) for bytes."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, n)
            i += n
        elif wire in (1, 5):
            val = None
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, val


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _sub(buf: memoryview, num: int) -> Optional[memoryview]:
    """The last submessage of field ``num`` (as protobuf merges), or None."""
    got = None
    for k, val in _fields(buf):
        if k == num:
            got = buf[val[0]:val[0] + val[1]]
    return got


def _string(buf: memoryview, num: int) -> str:
    s = _sub(buf, num)
    return "" if s is None else bytes(s).decode()


def _int(buf: memoryview, num: int) -> Optional[int]:
    got = None
    for k, val in _fields(buf):
        if k == num:
            got = val
    return got


@functools.lru_cache(maxsize=1)
def _summary_at(path: str) -> Optional[Dict[str, Any]]:
    return reduce_planes(read_planes(path))


def summary(v) -> Optional[Dict[str, Any]]:
    """The reduction of a traced run's ``.xplane.pb`` (None without one);
    the readers of one run share one parse."""
    d = v.outcome.trace_dir
    path = find_xplane(d) if d else None
    return _summary_at(path) if path else None


def idle_under(s: Optional[Dict[str, Any]], prefix: str) -> Optional[float]:
    """Idle seconds under program spans named ``prefix``*, or None when
    the trace holds no such span."""
    if not s or not any(n.startswith(prefix) for n in s["program_spans"]):
        return None
    return sum(v for k, v in s["idle"].items() if k.startswith(prefix))


def stamp_gap_ms(v, start: str, end: str, q: float) -> Optional[float]:
    """``q``-th percentile, over every request offered in the window, of
    ``end − start`` between two stamps of the request; a request that
    lacks either is a miss. None when no request carries the stamps."""
    reqs = v.outcome.requests
    if not reqs or not all(hasattr(r, start) and hasattr(r, end)
                           for r in reqs):
        return None
    gaps = stats.with_misses(
        None if getattr(r, start) is None or getattr(r, end) is None
        else getattr(r, end) - getattr(r, start) for r in reqs)
    return 1000.0 * stats.percentile(gaps, q)
