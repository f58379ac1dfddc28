"""Profiler trace: taking it, and reducing it to device numbers.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX:

  * busy time: the union of the intervals in which an operation ran on a
    device (line "XLA Ops" of each device plane), averaged over devices;
  * program time: device durations of each compiled program (line "XLA
    Modules"), by name, and by class (prefill chunk, decode step, re-page);
  * idle gaps: each gap between busy intervals, attributed to the
    innermost harness span (``bench.*`` host annotations) that covers it.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Any, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLASSES = (("prefill", re.compile(r"prefill_chunk")),
           ("repage", re.compile(r"repage|write_pages")),
           ("decode", re.compile(r"(^|[^a-z])_?decode($|[^a-z_])")))


def program_class(name: str) -> Optional[str]:
    for cls, pat in CLASSES:
        if pat.search(name):
            return cls
    return None


def clean_name(name: str) -> str:
    """``jit__decode(1234)`` → ``jit__decode``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def start(log_dir: str) -> None:
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _attribute(gaps, spans) -> Dict[str, float]:
    """Seconds of each gap under the innermost (shortest) covering span."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1] - s[0])
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for s0, s1, name in spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, s0), min(b, s1)
                if lo < hi:
                    out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
                    if a < lo:
                        nxt.append((a, lo))
                    if hi < b:
                        nxt.append((hi, b))
                else:
                    nxt.append((a, b))
            left = nxt
        for a, b in left:
            out["outside_harness_spans"] = out.get(
                "outside_harness_spans", 0.0) + (b - a) * 1e-9
    return out


def reduce_planes(planes: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]. None when no device plane holds an operation."""
    devices = [p for p in planes if p["name"].startswith("/device:")
               and not p["name"].startswith("/device:CPU")]
    spans = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                for name, t0, dur in line["events"]:
                    if name.startswith("bench."):
                        spans.append((t0, t0 + dur, name))
    busy_per_dev, programs, gaps_all = [], {}, {}
    lo = min((s[0] for s in spans), default=None)
    hi = max((s[1] for s in spans), default=None)
    for p in devices:
        lines = {line["name"]: line["events"] for line in p["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        iv = union([(t0, t0 + d) for _, t0, d in ops])
        busy_per_dev.append(sum(b - a for a, b in iv) * 1e-9)
        for name, t0, d in lines.get(MODULES_LINE, []):
            n = clean_name(name)
            tot = programs.setdefault(n, [0.0, 0])
            tot[0] += d * 1e-9
            tot[1] += 1
        a0 = lo if lo is not None else iv[0][0]
        b1 = hi if hi is not None else iv[-1][1]
        gaps = []
        prev = a0
        for a, b in iv:
            if a > prev:
                gaps.append((prev, min(a, b1)))
            prev = max(prev, b)
        if b1 > prev:
            gaps.append((prev, b1))
        for k, v in _attribute([g for g in gaps if g[1] > g[0]],
                               spans).items():
            gaps_all[k] = gaps_all.get(k, 0.0) + v
    if not busy_per_dev:
        return None
    n = len(busy_per_dev)
    classes: Dict[str, List[float]] = {}
    for name, (s, c) in programs.items():
        cls = program_class(name)
        if cls:
            tot = classes.setdefault(cls, [0.0, 0])
            tot[0] += s / n
            tot[1] += c / n
    return {"devices": n,
            "busy_s": sum(busy_per_dev) / n,
            "programs": {k: [v[0] / n, v[1] / n] for k, v in programs.items()},
            "classes": classes,
            "idle_gaps": {k: v / n for k, v in gaps_all.items()}}


def read_planes(path: str) -> List[Dict[str, Any]]:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events]})
        out.append({"name": plane.name, "lines": lines})
    return out


def reduce_dir(log_dir: str) -> Optional[Dict[str, Any]]:
    path = find_xplane(log_dir)
    if path is None:
        return None
    return reduce_planes(read_planes(path))


def breakdown(summary: Dict[str, Any]) -> Dict[str, List[List[Any]]]:
    ops = sorted(summary["programs"].items(), key=lambda kv: -kv[1][0])[:10]
    gaps = sorted(summary["idle_gaps"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v[0]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
