"""Open-loop arrivals into the one-chip P→D path.

A ``GlobalScheduler`` drives a P engine and a D engine on one chip through
a ``DisaggPipeline`` with the in-process connector. Each request is
stamped with its scheduled arrival before it is submitted (as
``repro.serving.loadgen.driver.run_open_loop`` does for a cluster), so
TTFT includes queueing and any lag of this loop. Arrivals fall in
``[0, seconds)``; after the window every admitted request is driven to a
terminal state.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
from typing import Any, Dict, List

from bench.common import check
from bench.common import trace as T
from bench.common import traffic
from bench.common.harness import peak_memory_bytes
from bench.common.outcome import Outcome
from bench.common.single import SPAN_STEP, SPAN_SUBMIT, SPAN_WAIT, System
from bench.common.single import annotate as span

DRAIN_LIMIT_S = 150.0


def snapshot(system: System) -> Dict[str, Any]:
    return {"engine_stats": {e.name: dataclasses.asdict(e.stats)
                             for e in (system.p, system.d)},
            "transfer_stats": {
                k: v for k, v in dataclasses.asdict(
                    system.pipeline.transfer.stats).items()
                if isinstance(v, (int, float))},
            "records": list(system.records)}


def build(ctx, items: List[traffic.Item]) -> System:
    """Weights, engines and a warm-up over the prompt lengths of ``items``."""
    conf, mix = ctx.cell.config, ctx.cell.mix
    system = System(conf, ctx.cell.reference(), ctx.seed,
                    traffic.max_seq_len(mix), ctx.hbm_default)
    system.warm(items, conf["vocab_size"])
    return system


def serve(ctx, system: System, items: List[traffic.Item], seconds: float,
          trace_dir=None) -> Outcome:
    """Offer ``items`` on their schedule for ``seconds``, then drain."""
    from repro.serving.request import Request, State
    reqs = [Request(req_id=f"r{it.index:04d}", prompt=it.prompt,
                    max_new_tokens=it.max_new) for it in items]
    chk = ctx.cell.config["check"]
    samples = check.sample(reqs, ctx.seed, chk["sample_tokens"],
                           chk["sample_max"])
    system.capture = {r.req_id for r in samples}
    system.decode_logits = {}
    due = collections.deque(zip(items, reqs))
    tr = trace_dir is not None
    c0 = ctx.clock.snapshot()[1]
    setup_s = time.monotonic() - ctx.process_t0
    if tr:
        T.start(trace_dir)
        system.tracing = True
    tw0 = time.perf_counter()
    tw1 = None
    t0 = time.monotonic()
    t_end = t0 + seconds
    system.recording = True
    closed = None
    lag = 0.0
    sched = system.sched
    while True:
        now = time.monotonic()
        if closed is None and now >= t_end:
            closed = snapshot(system)
            if tr:
                tw1 = time.perf_counter()
                T.stop()
                tr = system.tracing = False
        if now > t_end + DRAIN_LIMIT_S:
            break
        if due and t0 + due[0][0].offset_s <= now:
            with span(SPAN_SUBMIT, tr):
                while due and t0 + due[0][0].offset_s <= now:
                    it, req = due.popleft()
                    req.arrival_time = t0 + it.offset_s
                    lag = max(lag, now - req.arrival_time)
                    sched.submit(req)
        if system.busy():
            with span(SPAN_STEP, tr):
                sched.step()
            continue
        if not due and closed is not None:
            break
        nxt = t0 + due[0][0].offset_s if due else t_end
        with span(SPAN_WAIT, tr):
            time.sleep(max(0.0, min(nxt, t_end) - time.monotonic()))
    system.recording = False
    t_done = time.monotonic()
    compiles = ctx.clock.snapshot()[1] - c0
    compiled = ctx.clock.names_since(t0)
    failed = sum(1 for r in reqs if r.state != State.FINISHED)
    return Outcome(requests=reqs, attempted=len(reqs), failed=failed,
                   setup_s=setup_s, window_s=seconds, window_t0=t0,
                   t_done=t_done,
                   max_batch=system.max_batch,
                   engine_stats=closed["engine_stats"],
                   transfer_stats=closed["transfer_stats"],
                   records=closed["records"], trace_dir=trace_dir,
                   trace_window=(tw0, tw1) if trace_dir else None,
                   compiles_in_window=compiles,
                   memory_peak_bytes=peak_memory_bytes(),
                   generator_lag_s=lag, release=system.release,
                   notes=([f"compiled in window: {compiled}"]
                          if compiled else []),
                   spec=system.spec, samples=samples,
                   decode_logits=system.decode_logits,
                   info={"bytes_limit": system.hbm,
                         "d_pool_blocks": system.num_blocks,
                         "max_batch": system.max_batch,
                         "prefill_chunk": system.prefill_chunk})


def run(ctx) -> Outcome:
    items = traffic.build(ctx.cell.mix, ctx.seed, ctx.seconds,
                          ctx.cell.config["vocab_size"])
    system = build(ctx, items)
    trace_dir = os.path.join(ctx.out_dir, "trace") if ctx.trace else None
    return serve(ctx, system, items, ctx.seconds, trace_dir)
