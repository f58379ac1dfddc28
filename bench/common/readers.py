"""What the per-layer metric readers (``metrics/<name>.py``) share.

A reader gets a ``View`` of one traced run and returns a number, or None
when there is nothing to read (no trace, no such program, no such step):
the harness then leaves the metric out of the line.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from bench.common import counts, stats
from bench.common.outcome import Outcome


@dataclasses.dataclass
class View:
    outcome: Outcome
    conf: Dict[str, Any]
    peaks: Dict[str, float]
    trace: Optional[Dict[str, Any]]        # trace.reduce_* summary

    def records(self, kind: str) -> List[Any]:
        """Step records inside the traced window."""
        w = self.outcome.trace_window
        recs = [r for r in self.outcome.records if r.kind == kind]
        if w is None:
            return recs
        return [r for r in recs if r.t0 >= w[0] and r.t1 <= w[1]]

    def device_seconds(self, cls: str) -> Optional[float]:
        if not self.trace or cls not in self.trace["classes"]:
            return None
        s = self.trace["classes"][cls][0]
        return s if s > 0 else None

    def device_calls(self, cls: str) -> Optional[float]:
        if not self.trace or cls not in self.trace["classes"]:
            return None
        return self.trace["classes"][cls][1]

    def window_s(self) -> Optional[float]:
        w = self.outcome.trace_window
        return (w[1] - w[0]) if w else None


def step_counts(v: View, kind: str):
    """Σ model FLOPs, Σ bytes and Σ roofline seconds of the window's
    steps of ``kind``, and the bound that holds for most of that time."""
    flops = byts = roof = 0.0
    bound_s = {"compute": 0.0, "memory": 0.0}
    for r in v.records(kind):
        if kind == "decode":
            f, b = counts.decode_step(v.conf, r.tokens, r.context)
        else:
            f, b = counts.prefill_chunk(v.conf, r.context, r.tokens)
        t, bound = counts.roofline_seconds(f, b, v.peaks)
        flops, byts, roof = flops + f, byts + b, roof + t
        bound_s[bound] += t
    return flops, byts, roof, max(bound_s, key=bound_s.get)


def mfu(v: View, kind: str, cls: str) -> Optional[float]:
    dev = v.device_seconds(cls)
    if dev is None or not v.records(kind):
        return None
    flops = step_counts(v, kind)[0]
    return 100.0 * flops / (dev * v.peaks["bf16_flops"])


def roofline(v: View, kind: str, cls: str) -> Optional[float]:
    dev = v.device_seconds(cls)
    if dev is None or not v.records(kind):
        return None
    return 100.0 * step_counts(v, kind)[2] / dev


def prompt_ktok(v: View) -> Optional[float]:
    n = sum(r.tokens for r in v.records("prefill"))
    return n / 1000.0 if n else None


def idle_share(v: View) -> Optional[float]:
    w = v.window_s()
    if not v.trace or not w:
        return None
    return 100.0 * max(0.0, 1.0 - v.trace["busy_s"] / w)


def ttft_ms(v: View, q: float) -> Optional[float]:
    vals = stats.with_misses(r.ttft() for r in v.outcome.requests)
    p = stats.percentile(vals, q)
    return None if p is None else 1000.0 * p
