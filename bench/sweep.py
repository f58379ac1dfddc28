"""Find a cell's knee once: serve its mix at several fixed rates, one
window each, on several seeds, from one set-up, and print what each
window did.

    python3 bench/sweep.py --workload <name> --seeds 5,6 --seconds 50 --rates 0.6,0.8,1

Every rate serves the same requests (the mix's sizes and schedule, time
scaled). A rate is sustained when TTFT does not grow across the window:
on every seed, the ratio of the median TTFT of the requests that arrive
in the window's last third to that of its first third is at most
``GROWTH`` times the same ratio at the lowest rate, which stands for the
unloaded system (it corrects for which sizes fall in which third). Not
part of a benchmark run; the cell's mix keeps the rate chosen from it.
"""
import time

PROCESS_T0 = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
GROWTH = 1.5


def thirds(requests, t0, seconds):
    """Median TTFT (s) of the requests arriving in each third of the
    window, ``None`` for a third where none got a first token."""
    out = []
    for k in range(3):
        lo, hi = t0 + k * seconds / 3, t0 + (k + 1) * seconds / 3
        tt = sorted(r.ttft() for r in requests
                    if lo <= r.arrival_time < hi and r.ttft() is not None)
        out.append(statistics.median(tt) if tt else None)
    return out


def growth(third_medians):
    """Last third's median TTFT over the first third's, or None."""
    first, _, last = third_medians
    return last / first if first and last is not None else None


def sustained(third_medians, unloaded) -> bool:
    g, g0 = growth(third_medians), growth(unloaded)
    return g is not None and g0 is not None and g <= GROWTH * g0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import run as R
    from bench.common import harness, stats, traffic
    from bench.common.peaks import peaks_for
    from bench.drivers import open_loop_single as D

    cell = harness.load_cell(args.workload)
    R.setup_cache()
    dev = harness.require_tpu(cell.chips)
    clock = harness.CompileClock()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = R.Context(cell, seeds[0], args.seconds, False, clock,
                    int(peaks_for(dev["kind"])["hbm_bytes"]))
    vocab = cell.config["vocab_size"]
    runs = []
    for seed in seeds:
        for rate in sorted(float(r) for r in args.rates.split(",")):
            mix = copy.deepcopy(cell.mix)
            mix["arrivals"]["rate_rps"] = rate
            runs.append((seed, rate, traffic.build(mix, seed, args.seconds,
                                                   vocab)))
    system = D.build(ctx, [it for _, _, items in runs for it in items])
    verdict, unloaded = {}, {}
    for seed, rate, items in runs:
        if seed != ctx.seed:
            system.reseed(seed)
            ctx.seed = seed
        system.new_scheduler()
        oc = D.serve(ctx, system, items, args.seconds)
        t_close = oc.window_t0 + args.seconds
        drain = max((r.finish_time or t_close) for r in oc.requests) - t_close
        tt = stats.with_misses(r.ttft() for r in oc.requests)
        tp = stats.with_misses(r.tpot() for r in oc.requests
                               if r.max_new_tokens > 1)
        th = thirds(oc.requests, oc.window_t0, args.seconds)
        unloaded.setdefault(seed, th)
        ok = sustained(th, unloaded[seed])
        verdict[rate] = verdict.get(rate, True) and ok
        print(json.dumps({
            "seed": seed, "rate_rps": rate, "requests": oc.attempted,
            "failed": oc.failed,
            "ttft_p50_ms": 1000 * stats.percentile(tt, 50),
            "ttft_p90_ms": 1000 * stats.percentile(tt, 90),
            "tpot_p50_ms": 1000 * stats.percentile(tp, 50),
            "tpot_p90_ms": 1000 * stats.percentile(tp, 90),
            "ttft_p50_by_third_ms": [None if m is None else 1000 * m
                                     for m in th],
            "growth": growth(th), "sustained": ok, "drain_s": drain,
            "compiles_in_window": oc.compiles_in_window,
            "generator_lag_s": oc.generator_lag_s,
            "memory_peak_bytes": oc.memory_peak_bytes}), flush=True)
    knee = None
    for rate in sorted(verdict):
        if not verdict[rate]:
            break
        knee = rate
    print(json.dumps({"sustained_on_every_seed": sorted(
        r for r, ok in verdict.items() if ok), "knee_rps": knee}), flush=True)


if __name__ == "__main__":
    main()
