"""Substrate tests: optimizer, checkpoint-restart, train loop fault
tolerance, grad compression, data pipeline, int8 KV cache."""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import RunConfig, ShapeConfig
from repro.data import pipeline as data_pipeline
from repro.dist import compression
from repro.models import model as model_mod
from repro.models import params as pm
from repro.train import checkpoint, loop, optimizer, train_step as ts


@pytest.fixture(scope="module")
def tiny():
    cfg = configs.smoke_config(configs.get_config("llama3-8b"))
    params = pm.init_params(model_mod.model_spec(cfg), jax.random.key(0))
    return cfg, params


def _run(cfg):
    return RunConfig(model=cfg, shape=ShapeConfig("t", 32, 4, "train"),
                     remat="none", learning_rate=3e-3, lr_warmup=5)


def _batches(cfg, batch=4, seq=32, seed=0):
    return data_pipeline.synthetic_lm_batches(cfg.vocab, batch, seq, seed,
                                              effective_vocab=32)


# ---------------------------------------------------------------------------
# optimizer + training
# ---------------------------------------------------------------------------
def test_train_loss_decreases(tiny):
    cfg, params = tiny
    run = _run(cfg)
    step = jax.jit(ts.make_train_step(cfg, run))
    opt = optimizer.init(params)
    data = _batches(cfg)
    losses = []
    for _ in range(40):
        params, opt, m = step(params, opt, next(data))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 1.0, losses[::8]


def test_microbatched_grads_match(tiny):
    cfg, params = tiny
    batch = next(_batches(cfg, batch=4))
    run1 = _run(cfg)
    run4 = RunConfig(model=cfg, shape=run1.shape, remat="none",
                     learning_rate=1e-3, microbatches=4)
    s1 = ts.make_train_step(cfg, run1)
    s4 = ts.make_train_step(cfg, run4)
    opt = optimizer.init(params)
    p1, _, m1 = jax.jit(s1)(params, opt, batch)
    p4, _, m4 = jax.jit(s4)(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=2e-2)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p4)
    assert max(jax.tree.leaves(d)) < 2e-2


def test_cosine_lr_schedule():
    lrs = [float(optimizer.cosine_lr(jnp.int32(s), peak=1e-3)) for s in
           [0, 50, 100, 5000, 9999]]
    assert lrs[0] < lrs[1] < lrs[2]          # warmup
    assert lrs[2] >= lrs[3] >= lrs[4]        # decay
    assert lrs[4] >= 1e-4 * 0.99             # floor


# ---------------------------------------------------------------------------
# checkpoint / restart
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path, tiny):
    cfg, params = tiny
    opt = optimizer.init(params)
    checkpoint.save(tmp_path, 7, {"params": params, "opt": opt})
    assert checkpoint.latest_step(tmp_path) == 7
    restored = checkpoint.restore(tmp_path, 7, {"params": params, "opt": opt})
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        {"params": params, "opt": opt}, restored)


def test_checkpoint_async_and_gc(tmp_path, tiny):
    cfg, params = tiny
    t = None
    for s in (1, 2, 3, 4, 5):
        t = checkpoint.save(tmp_path, s, {"p": params}, keep=2, async_=True)
    t.join()
    steps = sorted(int(d.name.split("_")[1]) for d in tmp_path.glob("step_*"))
    assert steps[-1] == 5 and len(steps) <= 3


def test_fit_resume_continuity(tmp_path, tiny):
    """Kill training mid-run; resume must continue from the checkpoint."""
    cfg, _ = tiny
    run = _run(cfg)
    r1 = loop.fit(cfg, run, _batches(cfg, seed=1), steps=6,
                  ckpt_dir=tmp_path, ckpt_every=3, seed=1)
    assert r1.steps_run == 6
    # "crash" after step 6 (checkpoint exists at 6); rerun to 10
    r2 = loop.fit(cfg, run, _batches(cfg, seed=2), steps=10,
                  ckpt_dir=tmp_path, ckpt_every=3, seed=1)
    assert r2.resumed_from == 6
    assert r2.steps_run == 4
    assert r2.final_step == 10


def test_fit_preemption_checkpoint(tmp_path, tiny):
    cfg, _ = tiny
    run = _run(cfg)

    calls = {"n": 0}

    def on_metrics(step, m):
        calls["n"] += 1
        if calls["n"] == 2:  # simulate a SIGTERM mid-run
            os.kill(os.getpid(), signal.SIGTERM)

    r = loop.fit(cfg, run, _batches(cfg, seed=3), steps=50,
                 ckpt_dir=tmp_path, ckpt_every=1000, seed=3,
                 on_metrics=on_metrics)
    assert r.steps_run <= 3
    assert checkpoint.latest_step(tmp_path) == r.final_step


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_int8_ef_unbiased_over_time():
    rng = np.random.default_rng(0)
    true = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32)) * 1e-3
    errs = [jnp.zeros_like(true)]
    acc_q = jnp.zeros_like(true)
    for _ in range(50):
        deqs, errs = compression.compress_decompress([true], errs)
        acc_q = acc_q + deqs[0]
    # error feedback keeps the long-run average unbiased
    np.testing.assert_allclose(np.asarray(acc_q) / 50, np.asarray(true),
                               atol=1e-6)


def test_quantise_range():
    x = jnp.asarray(np.linspace(-3, 3, 1000, dtype=np.float32))
    q, scale = compression.quantise_tensor(x)
    assert int(jnp.max(q)) == 127 and int(jnp.min(q)) == -127
    np.testing.assert_allclose(np.asarray(q, np.float32) * float(scale),
                               np.asarray(x), atol=float(scale) * 0.51)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_morph_preprocessor_roots():
    pre = data_pipeline.MorphPreprocessor(n_tri=500, n_quad=60)
    toks, ids = pre(["سيلعبون", "يدرسون", "قال"])
    assert toks.shape == (3, 16)
    assert (ids > 0).all()  # all three have extractable roots


def test_morph_lm_stream_shapes():
    it = data_pipeline.morph_lm_batches(batch_words=64, seq=32)
    b = next(it)
    assert b["tokens"].shape == (1, 32)
    assert b["labels"].shape == (1, 32)
    assert b["tokens"].max() <= b["vocab"]


def test_morph_lm_root_ids_align_with_chunk():
    """Regression: each chunk must carry exactly the root ids of the words
    whose characters appear in it, not the whole-batch array."""
    from repro.core import corpus

    pre = data_pipeline.MorphPreprocessor(n_tri=500, n_quad=60)
    words, _, _ = corpus.build_corpus(n_words=64, seed=0)  # epoch-0 corpus
    _, all_ids = pre(words)
    it = data_pipeline.morph_lm_batches(batch_words=64, seq=32, preproc=pre)
    spans = []
    for _ in range(8):
        b = next(it)
        w0, w1 = b["word_span"]
        assert 0 <= w0 < w1 <= len(words)
        assert b["root_ids"].shape == (w1 - w0,)
        assert w1 - w0 < len(words)  # the old bug shipped the whole batch
        np.testing.assert_array_equal(b["root_ids"], all_ids[w0:w1])
        spans.append((w0, w1))
    # consecutive chunks advance through the corpus without gaps (the
    # boundary word may straddle two chunks)
    for (_, a1), (b0, _) in zip(spans, spans[1:]):
        assert b0 in (a1 - 1, a1)


# ---------------------------------------------------------------------------
# int8 KV cache (beyond-paper serving feature)
# ---------------------------------------------------------------------------
def test_int8_kv_decode_matches_bf16():
    import dataclasses

    import jax.numpy as jnp

    from repro import configs as cfgs
    from repro.models import model as mm
    from repro.models import params as pmod

    cfg = cfgs.smoke_config(cfgs.get_config("llama3-8b"))
    cfg_q = dataclasses.replace(cfg, kv_quant=True)
    params = pmod.init_params(mm.model_spec(cfg), jax.random.key(5))
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 10)).astype(np.int32))

    def run(c):
        caches = mm.init_caches(c, 2, cache_len=10)
        logits = None
        for i in range(10):
            logits, caches = mm.decode_step(
                params, c, toks[:, i : i + 1], caches, jnp.int32(i))
        return np.asarray(logits, np.float32)

    full = run(cfg)
    quant = run(cfg_q)
    # int8 KV introduces bounded quantisation noise only
    np.testing.assert_allclose(quant, full, rtol=0.2, atol=0.3)
    corr = np.corrcoef(full.ravel(), quant.ravel())[0, 1]
    assert corr > 0.99


def test_quantise_kv_roundtrip():
    from repro.models import attention as attn

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, 4, 16)).astype(np.float32) * 3)
    q, s = attn.quantise_kv(x)
    back = attn.dequantise_kv(q, s, jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert err.max() <= np.asarray(s).max() * 0.51
