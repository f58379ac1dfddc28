"""Model-builder behaviour: train forward, prefill+decode parity with the
full forward, loss masking — for every cache family."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import model as M


def _batch(cfg, rng, b=2, s=12):
    toks = rng.integers(0, cfg.vocab_size, (b, s + 1)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    if cfg.is_enc_dec:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, 10, cfg.d_model)), jnp.float32)
    if cfg.frontend.kind == "vision":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(b, cfg.frontend.num_patches, cfg.d_model)),
            jnp.float32)
    return batch


def test_train_forward_and_loss(family_cfg, rng):
    cfg = family_cfg
    params = M.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg, rng)
    logits = M.train_forward(params, cfg, batch, remat=False)
    s = batch["tokens"].shape[1]
    if cfg.frontend.kind == "vision":
        s += cfg.frontend.num_patches
    assert logits.shape == (2, s, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    loss = M.loss_fn(params, cfg, batch, remat=False)
    assert np.isfinite(float(loss))
    assert 0.0 < float(loss) < 2 * np.log(cfg.vocab_size)


def test_remat_does_not_change_loss(family_cfg, rng):
    cfg = family_cfg
    params = M.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg, rng)
    l1 = float(M.loss_fn(params, cfg, batch, remat=False))
    l2 = float(M.loss_fn(params, cfg, batch, remat=True))
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_prefill_decode_matches_full_forward(family_cfg, rng):
    """Greedy decode through caches == slicing the teacher-forced forward."""
    cfg = family_cfg
    params = M.init_params(jax.random.key(0), cfg)
    b, s_prompt, n_new = 2, 9, 4
    toks = rng.integers(0, cfg.vocab_size, (b, s_prompt + n_new)
                        ).astype(np.int32)
    inputs = {"tokens": jnp.asarray(toks[:, :s_prompt])}
    full_batch = {"tokens": jnp.asarray(toks)}
    offset = 0
    if cfg.is_enc_dec:
        fr = jnp.asarray(rng.normal(size=(b, 10, cfg.d_model)), jnp.float32)
        inputs["frames"] = fr
        full_batch["frames"] = fr
    if cfg.frontend.kind == "vision":
        pt = jnp.asarray(rng.normal(
            size=(b, cfg.frontend.num_patches, cfg.d_model)), jnp.float32)
        inputs["patches"] = pt
        full_batch["patches"] = pt
        offset = cfg.frontend.num_patches
    full = M.train_forward(params, cfg, full_batch, remat=False)

    caches = M.init_caches(cfg, b, s_prompt + n_new + offset,
                           jnp.float32, mem_len=10)
    last, caches = M.prefill(params, cfg, inputs, caches)
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(full[:, offset + s_prompt - 1]),
                               atol=2e-4)
    for t in range(n_new - 1):
        pos = jnp.full((b, 1), offset + s_prompt + t, jnp.int32)
        logits, caches = M.decode_step(params, cfg,
                                       jnp.asarray(toks[:, s_prompt + t:
                                                        s_prompt + t + 1]),
                                       pos, caches)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]),
            np.asarray(full[:, offset + s_prompt + t]), atol=2e-4)


def test_loss_ignores_negative_labels(rng):
    from tests.conftest import tiny
    cfg = tiny("mask")
    params = M.init_params(jax.random.key(0), cfg)
    toks = rng.integers(0, cfg.vocab_size, (2, 13)).astype(np.int32)
    lab = toks[:, 1:].copy()
    batch_full = {"tokens": jnp.asarray(toks[:, :-1]),
                  "labels": jnp.asarray(lab)}
    lab_mask = lab.copy()
    lab_mask[:, 8:] = -1
    batch_mask = {"tokens": jnp.asarray(toks[:, :-1]),
                  "labels": jnp.asarray(lab_mask)}
    l_full = float(M.loss_fn(params, cfg, batch_full, remat=False))
    l_mask = float(M.loss_fn(params, cfg, batch_mask, remat=False))
    assert l_full != pytest.approx(l_mask)
    # masked loss equals loss over the first 8 positions only
    lp = M.train_forward(params, cfg, batch_mask, remat=False)
    lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
    nll = -np.take_along_axis(np.asarray(lp[:, :8]),
                              lab[:, :8, None], axis=-1).mean()
    np.testing.assert_allclose(l_mask, nll, rtol=1e-5)


def test_abstract_params_match_real(family_cfg):
    cfg = family_cfg
    abs_p = M.abstract_params(cfg)
    real = M.init_params(jax.random.key(0), cfg)
    ab, rb = jax.tree.leaves(abs_p), jax.tree.leaves(real)
    assert len(ab) == len(rb)
    for a, r in zip(ab, rb):
        assert a.shape == r.shape and a.dtype == r.dtype


def test_paged_decode_kernel_path_matches_reference(monkeypatch):
    """A tiny Qwen3-shaped model (GQA, qk-norm, bf16) decodes a few steps
    over paged bf16 ``nbhd`` pools on the length-bounded kernel (interpret
    mode) and on the reference gather: logits agree to bf16 tolerance and
    the pools, written by the same in-place append, are bit-identical."""
    from repro.configs.base import ModelConfig
    from repro.kernels import ops
    from repro.serving.engine import page_specs_for

    cfg = ModelConfig(name="qwen3-tiny", family="dense", num_layers=3,
                      d_model=64, num_heads=8, num_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=128, qk_norm=True,
                      param_dtype="bfloat16", compute_dtype="bfloat16")
    specs = page_specs_for(cfg, 8, "nbhd", "bfloat16")
    b, blocks, per_seq, steps = 4, 16, 4, 4
    params = M.init_params(jax.random.key(0), cfg)
    caches = M.init_paged_caches(cfg, specs, blocks, batch=b)
    caches = jax.tree.map(lambda c: jax.random.normal(
        jax.random.key(1), c.shape).astype(c.dtype), caches)
    table = np.asarray([[1, 2, 3, 4], [0, 0, 0, 0], [5, 6, 7, 8],
                        [9, 10, 11, 12]], np.int32)      # slot 1 idle
    lens0 = np.asarray([3, 0, 8, 17], np.int32)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (steps, b))

    def run(path):
        monkeypatch.setattr(ops, "paged_decode_path", lambda spec: path)
        monkeypatch.setattr(ops, "_interpret", lambda force: True)
        step = jax.jit(lambda t, sl, wb, ws, c: M.decode_step_paged(
            params, cfg, t, sl, jnp.asarray(table), wb, ws, c, specs))
        c, lens, out = caches, lens0.copy(), []
        for i in range(steps):
            wb = table[np.arange(b), lens // 8]
            logits, c = step(jnp.asarray(tokens[i][:, None], jnp.int32),
                             jnp.asarray(lens), jnp.asarray(wb),
                             jnp.asarray(lens % 8), c)
            out.append(np.asarray(logits, np.float32))
            lens = lens + np.asarray([1, 0, 1, 1], np.int32)
        return out, c

    want, want_c = run("ref")
    got, got_c = run("paged_kernel")
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-2, rtol=3e-2)
    for g, w in zip(jax.tree.leaves(got_c), jax.tree.leaves(want_c)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
