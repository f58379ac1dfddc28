"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's weights on the device from ``--seed``, warms every shape
its traffic uses (set-up), offers the traffic for ``--seconds``, drives
every admitted request to its end, frees the program's state and compares
a sample of what was served with the plain float32 reference. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""
import time

PROCESS_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# one fixed compile cache inside the checkout: only a cell's first run in
# a checkout compiles. It is set over any JAX_COMPILATION_CACHE_DIR from
# outside, so that two checkouts never share compiled programs.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")


class Context:
    def __init__(self, cell, seed, seconds, trace, clock, hbm_default):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.clock = trace, clock
        self.hbm_default = hbm_default
        self.process_t0 = PROCESS_T0
        self.out_dir = os.path.join(OUT_DIR, cell.name)


def setup_cache():
    """JAX's persistent compile cache at the checkout's fixed directory,
    through the program's own helper (which takes it from the variable)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.makedirs(CACHE_DIR, exist_ok=True)
    from repro.serving.jit_cache import enable_jit_cache
    enable_jit_cache()


def latencies(oc):
    """TTFT and TPOT of every offered request. One that never finished
    is a miss: it is charged everything it waited, to the drain's end."""
    ttft, tpot = [], []
    for r in oc.requests:
        if r.finish_time is None:
            first = r.first_token_time or r.arrival_time
            ttft.append(r.ttft() or oc.t_done - r.arrival_time)
            tpot.append(oc.t_done - first)
        else:
            ttft.append(r.ttft())
            tpot.append(r.tpot())
    return ttft, tpot


def end_to_end(names, oc):
    from bench.common import stats
    out = {}
    ttft, tpot = latencies(oc)
    if "ttft_p90_ms" in names:
        out["ttft_p90_ms"] = 1000.0 * stats.percentile(ttft, 90)
    if "tpot_p90_ms" in names:
        out["tpot_p90_ms"] = 1000.0 * stats.percentile(tpot, 90)
    if "setup_s" in names:
        out["setup_s"] = oc.setup_s
    return out


def finite(x):
    return x if x is not None and math.isfinite(x) else None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.common import check, harness, traffic
    from bench.common import trace as T
    from bench.common.peaks import peaks_for
    from bench.common.readers import View, step_counts

    cell = harness.load_cell(args.workload)
    setup_cache()
    dev = harness.require_tpu(cell.chips)
    peaks = peaks_for(dev["kind"])
    clock = harness.CompileClock()
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), clock,
                  int(peaks["hbm_bytes"]))
    oc = cell.driver().run(ctx)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    ttfts = [r.ttft() for r in oc.requests if r.ttft() is not None]
    log(f"requests: {oc.attempted} offered, {oc.failed} failed, "
        f"{len(ttfts)} with a first token; setup {oc.setup_s:.3f} s; "
        f"window {oc.window_s} s; generator lag {oc.generator_lag_s:.4f} s; "
        f"compiles in window {oc.compiles_in_window}; compile "
        f"{clock.seconds:.3f} s over {clock.programs} programs in all; "
        f"{oc.info}")

    for note in oc.notes:
        log(note)
    e2e_names = [m["name"] for m in cell.end_to_end]
    metrics = {}
    result_extra = {}
    if args.trace:
        summary = T.reduce_dir(oc.trace_dir) if oc.trace_dir else None
        view = View(oc, cell.config, peaks, summary)
        for m in cell.per_layer:
            val = finite(harness.metric_reader(m["name"]).read(view))
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        for kind in ("prefill", "decode"):
            flops, byts, roof, bound = step_counts(view, kind)
            log(f"roofline {kind}: {len(view.records(kind))} steps, "
                f"{flops:.4e} FLOPs, {byts:.4e} bytes, {roof:.6f} s at "
                f"the roofline, bound by {bound}")
        if summary:
            dev = dict(dev, busy_s=summary["busy_s"],
                       window_s=view.window_s())
            result_extra["breakdown"] = T.breakdown(summary)
            log(f"trace: {json.dumps(summary['classes'])}")
        shutil.rmtree(oc.trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for k, v in end_to_end(e2e_names, oc).items():
            metrics[k] = {"value": v, "unit": units[k]}
    dev["memory_peak_bytes"] = oc.memory_peak_bytes

    # correctness: after the window, with the program's state freed
    spec = oc.spec
    oc.release()
    chk = cell.config["check"]
    samples = check.finished(oc.samples, oc.decode_logits)
    t0 = time.monotonic()
    gap = first = dec = math.inf
    if samples:
        res = check.reference_gaps(
            cell.reference(), cell.config, spec, args.seed,
            [(r.prompt, list(r.output_tokens)) for r in samples],
            traffic.max_seq_len(cell.mix),
            first_logits=[r.first_logits for r in samples],
            decode_logits=[oc.decode_logits[r.req_id] for r in samples])
        gap, first = res["gap"], res["first_rel_l2"]
        dec = res["decode_rel_l2"]
    ref_s = time.monotonic() - t0
    served = sum(len(r.output_tokens) for r in samples)
    checks = {
        "first_logit_rel_l2": {"value": first,
                               "limit": chk["first_logit_rel_l2_limit"]},
        "decode_logit_rel_l2": {"value": dec,
                                "limit": chk["decode_logit_rel_l2_limit"]},
        "logit_gap": {"value": gap, "limit": chk["logit_gap_limit"]}}
    correct = len(samples) == len(oc.samples) and all(
        c["value"] <= c["limit"] for c in checks.values())
    log(f"reference: {len(samples)} of {len(oc.samples)} sampled requests "
        f"finished, {served} served tokens, "
        f"longest {max((r.prompt_len + len(r.output_tokens) for r in samples), default=0)} "
        f"tokens, {ref_s:.3f} s")
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    for c in checks.values():
        if not math.isfinite(c["value"]):
            c["value"] = None
    line = {"correct": correct, "attempted": oc.attempted,
            "failed": oc.failed, "metrics": metrics, "device": dev}
    line.update(result_extra)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
