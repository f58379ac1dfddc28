"""The control of the comparison: the plain reference put in the
program's place, computed in float8, fails a limit that the program
passes. Tiny sizes; the readings that set the cells' limits come from the
chip."""
import time

import numpy as np
import pytest

import bench_tiny as BT
from bench.common import check, harness, traffic


def test_float8_control_fails_where_the_program_passes():
    conf = BT.DENSE
    cell = harness.Cell("t", 1, conf, "t", "t", BT.OPEN_LOOP, [], [])

    class Ctx:
        pass
    ctx = Ctx()
    ctx.cell, ctx.seed, ctx.seconds, ctx.trace = cell, 4242, 1.0, False
    ctx.clock = harness.CompileClock()
    ctx.hbm_default, ctx.process_t0 = 1 << 30, time.monotonic()
    ctx.out_dir = None
    oc = cell.driver().run(ctx)
    spec = oc.spec
    oc.release()
    samples = check.finished(oc.samples, oc.decode_logits)
    assert samples and samples == oc.samples
    res = check.reference_gaps(
        cell.reference(), conf, spec, ctx.seed,
        [(r.prompt, list(r.output_tokens)) for r in samples],
        traffic.max_seq_len(cell.mix), control=True,
        first_logits=[r.first_logits for r in samples],
        decode_logits=[oc.decode_logits[r.req_id] for r in samples])
    lim = conf["check"]
    assert res["gap"] <= lim["logit_gap_limit"]
    assert res["first_rel_l2"] <= lim["first_logit_rel_l2_limit"]
    assert res["decode_rel_l2"] <= lim["decode_logit_rel_l2_limit"]
    # the control fails the decode number (and, here, the others)
    assert res["control_decode_rel_l2"] > lim["decode_logit_rel_l2_limit"]


def test_sample_holds_the_longest_request():
    class Rq:
        def __init__(self, i, n, m):
            self.req_id, self.prompt = f"r{i}", np.zeros(n, np.int32)
            self.output_tokens, self.max_new_tokens = [1] * m, m

        @property
        def prompt_len(self):
            return len(self.prompt)
    reqs = [Rq(i, 10 + i, 5) for i in range(20)]
    s = check.sample(reqs, 3, 12, 6)
    assert s[0].req_id == "r19" and sum(r.max_new_tokens for r in s) >= 12
    assert check.sample(reqs, 3, 12, 6) == s
    assert check.sample(reqs, 2**31 + 3, 12, 6)[0].req_id == "r19"


def test_only_sampled_requests_that_finished_with_their_logits_count():
    class Rq:
        def __init__(self, i, served, planned):
            self.req_id = f"r{i}"
            self.output_tokens, self.max_new_tokens = [1] * served, planned
    full, short, unkept = Rq(0, 4, 4), Rq(1, 2, 4), Rq(2, 4, 4)
    logits = {"r0": [0] * 3, "r1": [0], "r2": [0] * 2}
    assert check.finished([full, short, unkept], logits) == [full]


def test_rows_rel_l2_is_the_widest_row():
    want = np.ones((3, 4), np.float32)
    got = want.copy()
    got[1] *= 1.5
    assert abs(check.rows_rel_l2(got, want) - 0.5) < 1e-6
