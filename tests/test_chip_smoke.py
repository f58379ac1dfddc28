"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to run
anywhere but on a TPU. The script's own ``main`` runs them at Qwen3-4B's
widths on the chip."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke as S
from repro.configs.base import ModelConfig
from repro.models import model as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ModelConfig(name="smoke-tiny", family="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                   d_ff=128, vocab_size=256, qk_norm=True,
                   tie_embeddings=True)
LENGTHS = (8, 16, 24, 40)
MAX_NEW = 4
CHUNK = 16


@pytest.fixture(scope="module")
def served():
    params = M.init_params(jax.random.key(0), TINY)
    p, d = S.build_engines(TINY, params, LENGTHS, MAX_NEW, max_batch=4)
    assert p.caches is None                    # prefill role: no paged pool
    out = {}
    for conn in ("inproc", "shm"):
        reqs = S.build_requests(TINY, LENGTHS, MAX_NEW, seed=3)
        out[conn] = (reqs, S.serve(p, d, reqs, conn, CHUNK))
    return params, p, out


def test_phases_serve_every_request_across_connectors(served):
    _params, _p, out = served
    for conn, (reqs, r) in out.items():
        S.check_finished(reqs, r)
        assert r["chunks"] > len(reqs)        # prompts streamed in chunks
        assert all(len(t) == MAX_NEW for t in r["tokens"].values()), conn
    assert out["shm"][1]["tokens"] == out["inproc"][1]["tokens"]


def test_phases_logits_checks_within_tolerance(served):
    params, p, out = served
    reqs = out["inproc"][0]
    checks = S.check_logits(TINY, params, p, reqs)
    assert [c["prompt_len"] for c in checks] == [8, 40, 40]
    for c in checks:
        assert c["finite"] and c["rel_l2"] <= S.REL_L2_TOL, c
    tok_chunked, tok_mono = checks[-1]["tokens"]
    assert tok_chunked == reqs[-1].output_tokens[0]
    assert isinstance(tok_mono, int)


def test_reference_is_float32_forward():
    params = M.init_params(jax.random.key(0), TINY)
    prompt = np.arange(12, dtype=np.int32)
    ref = S.reference_logits(TINY, params, prompt)
    assert ref.shape == (TINY.vocab_size,) and ref.dtype == np.float32
    assert np.all(np.isfinite(ref))


def test_d_pool_holds_a_full_batch_of_longest_sequences():
    assert S.d_pool_blocks((128, 1024), 32, 8, 8) == 8 * 132 + 1


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_the_cpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                           *argv], env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "FAIL" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
