"""Program spans on the profiler's clock.

``span(name, **args)`` marks a stretch of host work in the trace that
``jax.profiler`` records, so the program's own phases share a clock with
the device's operations. Names follow ``pd.<layer>.<what>``; spans of one
request carry ``req=<req_id>``; nesting on the host thread gives the
parent. Capture them by running serving under ``jax.profiler.trace``.

While no trace records, ``span`` returns one shared null context and
formats nothing, so a span costs one enabled-flag check. Spans only time
host work: none waits for the device, they wrap the host's own blocking
calls where it already has them.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A named host span in the running profiler trace, else a no-op."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args)
    return _OFF
