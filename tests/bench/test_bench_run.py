"""CPU rehearsals of the drivers and of whole runs at a tiny size.

``run.py`` refuses the CPU outright. The other tests skip only its look
for a chip (a stand-in reports a TPU v5e) and drive everything else: the
driver, the per-layer readers, the reference comparison. With a fault
planted in the timed path (every served token altered where it is
sampled, or the D engine's decode step run on float8 weights)
``correct`` comes out false.
"""
import json
import logging
import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny as BT
from bench import run as R
from bench.common import check, harness

FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CELLS = [{"name": "tiny-dense.chat", "config": "tiny-dense",
          "traffic": "tiny-chat", "like": "qwen3-4b.chat"}]
ARGS = ["--workload", "tiny-dense.chat", "--seed", "123456789012",
        "--seconds", "1.5", "--trace", "0"]


@pytest.fixture
def root(tmp_path, monkeypatch):
    r = BT.layout(str(tmp_path), {"tiny-dense": BT.DENSE},
                  {"tiny-chat": BT.OPEN_LOOP}, CELLS)
    monkeypatch.setattr(harness, "ROOT", r)
    monkeypatch.setattr(R, "setup_cache", lambda: None)
    monkeypatch.setattr(R, "OUT_DIR", str(tmp_path / "out"))
    log_compiles = jax.config.jax_log_compiles
    yield r
    jax.config.update("jax_log_compiles", log_compiles)
    for lg in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
        logging.getLogger(lg).propagate = True


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_run_refuses_the_cpu(root, capsys):
    with pytest.raises(SystemExit) as e:
        R.main(["--workload", "tiny-dense.chat", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


@pytest.mark.parametrize("cell", [c["name"] for c in CELLS])
def test_driver_rehearsal(root, cell):
    c = harness.load_cell(cell)

    class Ctx:
        pass
    ctx = Ctx()
    ctx.cell, ctx.seed, ctx.seconds, ctx.trace = c, 2**31 + 11, 1.5, False
    ctx.clock = harness.CompileClock()
    ctx.hbm_default, ctx.process_t0 = 1 << 30, time.monotonic()
    ctx.out_dir = None
    oc = c.driver().run(ctx)
    assert oc.attempted > 3 and oc.failed == 0
    assert oc.compiles_in_window == 0, oc.notes
    assert all(len(r.output_tokens) == r.max_new_tokens
               for r in oc.requests)
    kinds = {r.kind for r in oc.records}
    assert kinds == {"decode", "prefill"}
    assert all(r.ttft() > 0 for r in oc.requests)
    # the sampled requests' decode logits are kept, one row per token
    # after the first, and nobody else's
    assert oc.samples and set(oc.decode_logits) == {
        r.req_id for r in oc.samples}
    assert check.finished(oc.samples, oc.decode_logits) == oc.samples
    oc.release()


def test_whole_run_is_correct_and_a_planted_fault_is_not(root, capsys,
                                                         monkeypatch):
    monkeypatch.setattr(harness, "require_tpu", lambda chips: dict(FAKE_TPU))
    R.main(ARGS)
    ok = last_line(capsys)
    assert ok["correct"] is True
    assert list(ok)[-1] == "checks"
    assert set(ok["checks"]) == {"first_logit_rel_l2",
                                 "decode_logit_rel_l2", "logit_gap"}
    assert set(ok["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in ok["checks"].values())

    from repro.serving.engine import Engine
    sample = Engine._sample

    def altered(self, logits, req):
        return (sample(self, logits, req) + 1) % logits.shape[-1]
    monkeypatch.setattr(Engine, "_sample", altered)
    R.main(ARGS)
    bad = last_line(capsys)
    assert bad["correct"] is False
    assert bad["checks"]["logit_gap"]["value"] > \
        bad["checks"]["logit_gap"]["limit"]


def test_decode_step_in_lower_precision_is_not_correct(root, capsys,
                                                       monkeypatch):
    """The D engine's decode step on float8 weights: the P engine's first
    tokens stay right, the decode logits do not."""
    monkeypatch.setattr(harness, "require_tpu", lambda chips: dict(FAKE_TPU))
    from repro.models import model as M
    step = M.decode_step_paged

    def low(params, *a, **k):
        params = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.ndim >= 2 else x, params)
        return step(params, *a, **k)
    monkeypatch.setattr(M, "decode_step_paged", low)
    R.main(ARGS)
    bad = last_line(capsys)
    assert bad["correct"] is False
    c = bad["checks"]
    assert c["first_logit_rel_l2"]["value"] <= c["first_logit_rel_l2"]["limit"]
    assert c["decode_logit_rel_l2"]["value"] > \
        c["decode_logit_rel_l2"]["limit"]


def test_traced_run_reports_per_layer_metrics(root, capsys, monkeypatch):
    monkeypatch.setattr(harness, "require_tpu", lambda chips: dict(FAKE_TPU))
    R.main(["--workload", "tiny-dense.chat", "--seed", "5",
            "--seconds", "1.5", "--trace", "1"])
    line = last_line(capsys)
    assert line["correct"] is True
    # the CPU trace has no device plane: only host-side readers report
    assert {"ttft_p50_ms", "wire_host_ms_per_ktok"} <= set(line["metrics"])
    assert not any(k.startswith(("decode_mfu", "idle_share"))
                   for k in line["metrics"])
    assert "busy_s" not in line["device"]
