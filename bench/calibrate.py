"""Readings that set a cell's limits: the program's numbers and the float8
control's, on several seeds, in one process.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 --seconds 10

One set-up serves every seed (new weights per seed, the same programs):
each seed's traffic is offered for ``--seconds`` at the cell's own load
through the open-loop driver's ``serve``, the weights are dropped, and the
reference and the control run over the same sample of served requests.
It prints one JSON line per seed and a summary: for each number the
largest program reading (the lower reading) and the smallest control
reading (the upper reading). Not part of a benchmark run.
"""
import time

PROCESS_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
NUMBERS = ("gap", "first_rel_l2", "decode_rel_l2")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import run as R
    from bench.common import check, harness
    from bench.common.peaks import peaks_for

    cell = harness.load_cell(args.workload)
    R.setup_cache()
    dev = harness.require_tpu(cell.chips)
    peaks = peaks_for(dev["kind"])
    clock = harness.CompileClock()
    from bench.common import traffic
    from bench.drivers import open_loop_single as D
    seeds = [int(s) for s in args.seeds.split(",")]
    vocab = cell.config["vocab_size"]
    runs = {s: traffic.build(cell.mix, s, args.seconds, vocab)
            for s in seeds}
    ctx = R.Context(cell, seeds[0], args.seconds, False, clock,
                    int(peaks["hbm_bytes"]))
    # one set-up for every seed: each seed's traffic is the same size set
    system = D.build(ctx, [it for items in runs.values() for it in items])
    rows = []
    for seed in seeds:
        if seed != seeds[0]:
            system.reseed(seed)
        system.new_scheduler()
        oc = D.serve(ctx, system, runs[seed], args.seconds)
        system.drop_params()
        samples = check.finished(oc.samples, oc.decode_logits)
        t1 = time.monotonic()
        res = check.reference_gaps(
            cell.reference(), cell.config, system.spec, seed,
            [(r.prompt, list(r.output_tokens)) for r in samples],
            traffic.max_seq_len(cell.mix),
            control=True, first_logits=[r.first_logits for r in samples],
            decode_logits=[oc.decode_logits[r.req_id] for r in samples])
        row = {"seed": seed}
        for k in NUMBERS:
            row[k], row["control_" + k] = res[k], res["control_" + k]
        row.update({
            "tokens": res["tokens"], "sampled": len(oc.samples),
            "requests": len(samples), "attempted": oc.attempted,
            "failed": oc.failed, "compiles": oc.compiles_in_window,
            "reference_s": time.monotonic() - t1,
            "argmax_equal": sum(o["ref_argmax_equal"]
                                for o in res["per_seq"]),
            "longest": max(r.prompt_len + len(r.output_tokens)
                           for r in samples)})
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"seeds": len(rows)}
    for k in NUMBERS:
        summary[k + "_lower"] = max(r[k] for r in rows)
        summary[k + "_upper"] = min(r["control_" + k] for r in rows)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
