"""The one-chip system under test: a P engine and a D engine on one
device, sharing one parameter tree, behind a ``GlobalScheduler`` and a
``DisaggPipeline`` with the in-process connector.

Everything here reads the configuration file: the program's registry
name and the options it is run with, the P and D vendor profiles, the
wire, and the rule that sizes the D pool from the memory left after
weights and transients. The harness wraps the engines' step calls to
record what each step did (tokens, live KV), on the host clock, and keeps
the logits the D engine samples the decode tokens of the requests in
``capture`` from, for the comparison with the reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench.common import counts
from bench.common import weights as W

# harness span names, written into the profiler trace when it is on
SPAN_STEP = "bench.scheduler_step"
SPAN_SUBMIT = "bench.submit"
SPAN_WAIT = "bench.wait_for_arrival"
SPAN_DECODE = "bench.decode_step"
SPAN_CHUNK = "bench.prefill_chunk"


def annotate(name: str, on: bool):
    """A harness span in the profiler trace, or nothing when not tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def program_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig``: its registry entry with the options
    the configuration file gives (depth, an untied head)."""
    from repro.configs import get_config
    prog = conf["program"]
    cfg = get_config(prog["registry"])
    over = dict(prog.get("overrides", {}))
    over.setdefault("num_layers", conf["num_hidden_layers"])
    return cfg.with_(**over)


def check_layout(cfg, spec: W.Spec) -> None:
    """The benchmark's weight tree has the program's structure, shapes and
    dtypes, leaf for leaf."""
    import jax

    from repro.models import model as M
    want = M.abstract_params(cfg)
    got = jax.eval_shape(lambda: W.program_params(spec, 0))
    ws = jax.tree_util.tree_structure(want)
    gs = jax.tree_util.tree_structure(got)
    if ws != gs:
        raise ValueError(f"weight tree differs from the program's:\n"
                         f"program {ws}\nbench   {gs}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {b.shape}/{b.dtype} where the program "
                             f"has {a.shape}/{a.dtype}")


def device_bytes_limit(default: int) -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", default))


def pool_blocks(conf: Dict[str, Any], spec: W.Spec, hbm: int) -> int:
    """D pool, in D blocks: half of what is left of ``hbm_fraction`` of the
    device after the weights and ``transient_bytes`` for the step
    programs. Half, because each re-page program returns a new copy of
    the pool before the old one is freed (its pools are not donated)."""
    s = conf["serving"]
    if "pool_blocks" in s:
        return int(s["pool_blocks"])
    left = hbm * s["hbm_fraction"] - W.param_bytes(spec) - s["transient_bytes"]
    per_block = s["d_vendor"]["block_size"] * counts.kv_bytes_per_token(conf)
    blocks = int(left / 2 // per_block)
    if blocks < 2:
        raise ValueError(f"no room for a D pool: {left / 2**30:.2f} GiB left")
    return blocks


@dataclasses.dataclass
class StepRecord:
    """One step call, as the harness saw it (host clock)."""
    kind: str              # "prefill" | "decode"
    t0: float
    t1: float
    tokens: int            # new tokens computed
    context: int           # prefill: chunk start; decode: Σ live lengths


class System:
    """Engines, pipeline and scheduler for one run, with step recording."""

    def __init__(self, conf: Dict[str, Any], ref_module, seed: int,
                 max_seq_len: int, hbm_default: int):
        import jax

        from repro.core.compat.precision import WireFormat
        from repro.serving.engine import Engine, VendorProfile
        self.conf = conf
        self.cfg = cfg = program_config(conf)
        self.spec = ref_module.spec(conf)
        check_layout(cfg, self.spec)
        s = conf["serving"]
        self.params = jax.block_until_ready(W.program_params(self.spec, seed))
        pv = VendorProfile("vendorP", **s["p_vendor"])
        dv = VendorProfile("vendorD", **s["d_vendor"])
        self.wire = WireFormat(s["wire"]["kind"], s["wire"]["dtype"])
        self.prefill_chunk = int(s["prefill_chunk"])
        self.max_batch = int(s["max_batch"])
        self.hbm = device_bytes_limit(hbm_default)
        self.num_blocks = pool_blocks(conf, self.spec, self.hbm)
        self.p = Engine("P0", cfg, self.params, pv, num_blocks=1,
                        max_batch=self.max_batch, max_seq_len=max_seq_len,
                        role="prefill")
        self.d = Engine("D0", cfg, self.params, dv,
                        num_blocks=self.num_blocks, max_batch=self.max_batch,
                        max_seq_len=max_seq_len, role="decode")
        self.records: List[StepRecord] = []
        self.recording = False
        self.tracing = False
        self.capture: set = set()           # req_ids whose logits are kept
        self.decode_logits: Dict[str, List[np.ndarray]] = {}
        self._instrument()
        self.new_scheduler()

    # -- instrumentation ------------------------------------------------- #
    def _instrument(self) -> None:
        d, p = self.d, self.p
        decode = d.decode_step
        open_stream = p.prefill_stream
        sample = d._sample

        def sample_kept(logits, req):
            if req.req_id in self.capture:
                self.decode_logits.setdefault(req.req_id, []).append(
                    np.array(logits[0], np.float32))
            return sample(logits, req)

        def decode_step():
            active = [i for i, r in enumerate(d.slot_req)
                      if r is not None and d.slot_ready[i]]
            live = int(sum(int(d.seq_lens[i]) + 1 for i in active))
            t0 = time.perf_counter()
            with annotate(SPAN_DECODE, self.tracing):
                out = decode()
            if self.recording and active:
                self.records.append(StepRecord(
                    "decode", t0, time.perf_counter(), len(active), live))
            return out

        def prefill_stream(*args, **kwargs):
            stream = open_stream(*args, **kwargs)
            compute = stream._compute_chunk

            def compute_chunk(c0, c1):
                t0 = time.perf_counter()
                with annotate(SPAN_CHUNK, self.tracing):
                    out = compute(c0, c1)
                if self.recording:
                    self.records.append(StepRecord(
                        "prefill", t0, time.perf_counter(), c1 - c0, c0))
                return out

            stream._compute_chunk = compute_chunk
            return stream

        d.decode_step = decode_step
        d._sample = sample_kept
        p.prefill_stream = prefill_stream

    def new_scheduler(self) -> None:
        """A fresh scheduler, pipeline and counters (engines are kept)."""
        from repro.configs.base import ConnectorConfig
        from repro.core.disagg import DisaggPipeline
        from repro.serving.engine import EngineStats
        from repro.serving.scheduler import GlobalScheduler
        self.conn = ConnectorConfig(kind="inproc").build()
        self.pipeline = DisaggPipeline(self.conn, self.wire)
        self.sched = GlobalScheduler(self.pipeline,
                                     prefill_chunk=self.prefill_chunk)
        self.sched.add_instance(self.p)
        self.sched.add_instance(self.d)
        self.p.stats = EngineStats()
        self.d.stats = EngineStats()
        self.records = []
        self.capture, self.decode_logits = set(), {}

    def busy(self) -> bool:
        s = self.sched
        return bool(s.pending or s.inflight
                    or any(r is not None for r in self.d.slot_req))

    def warm_plan(self, items) -> List[tuple]:
        """(prompt length, new tokens, cut after the handoff) of the
        warm-up requests for ``items``.

        One request per distinct prompt length compiles (or loads) its
        prefill chunk programs. The re-page programs are keyed on the
        chunk length and on the number of D blocks the request reserves,
        ceil((prompt + new tokens) / block): one short request per
        distinct (last-chunk length, blocks) pair reserves the same
        blocks with a prompt of at most two chunks, and is cut once its
        KV has landed."""
        c, bs = self.prefill_chunk, self.d.block_size
        plan = {("len", n): (n, 2, False)
                for n in sorted({it.prompt_len for it in items})}
        for it in items:
            n, new = it.prompt_len, it.max_new
            tail = n - c * (-(-n // c) - 1)
            short = tail if n <= c else c + tail
            blocks = -(-(n + new) // bs)
            plan.setdefault(("blocks", short, blocks),
                            (short, n + new - short, True))
        return list(plan.values())

    def warm(self, items, vocab: int) -> None:
        """Serve the warm-up requests of ``warm_plan``: every prefill
        chunk program, re-page program and the decode step the run will
        use compiles or loads here, outside the measured window."""
        from repro.serving.request import Request, State
        rng = np.random.default_rng(12345)
        reqs, cut = [], []
        for i, (n, new, c) in enumerate(self.warm_plan(items)):
            r = Request(req_id=f"warm-{i}", max_new_tokens=new,
                        prompt=rng.integers(0, vocab, n).astype(np.int32))
            reqs.append(r)
            if c:
                cut.append(r)
        sched = self.sched
        for r in reqs:
            sched.submit(r)
        for _ in range(10_000_000):
            if sched.stats.finished + sched.stats.failed >= len(reqs):
                break
            sched.step()
            # the blocks are reserved at dispatch: finish at the handoff
            for r in cut:
                if r.state != State.QUEUED:
                    r.max_new_tokens = min(r.max_new_tokens, 1)
        bad = [r.req_id for r in reqs if r.state != State.FINISHED]
        if bad:
            raise RuntimeError(f"warm-up requests did not finish: {bad}")
        import jax
        jax.block_until_ready(self.d.caches)
        self.new_scheduler()

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` in both engines (same shapes, so no
        program changes); drops the old ones first."""
        import jax
        self.drop_params()
        self.params = jax.block_until_ready(
            W.program_params(self.spec, seed))
        self.p.params = self.d.params = self.params

    def drop_params(self) -> None:
        if self.params is None:
            return
        import jax
        for x in jax.tree.leaves(self.params):
            x.delete()
        self.params = self.p.params = self.d.params = None

    def release(self) -> None:
        """Free the program's device state (params, pools)."""
        import jax
        for tree in (self.d.caches, self.params):
            for x in jax.tree.leaves(tree):
                try:
                    x.delete()
                except Exception:
                    pass
        self.conn.close()
        self.p = self.d = self.sched = self.pipeline = self.params = None
        gc.collect()
