"""End-to-end behaviour tests for the full system."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import random_krondpp
from repro.data import DPPBatchSelector, TokenPipeline, synthetic_corpus
from repro.models import LM
from repro.optim import AdamW, cosine_schedule
from repro.train import Trainer, TrainerConfig, make_train_step


def _train(arch="qwen2-0.5b", steps=12, selector=None, microbatches=1):
    cfg = smoke_config(arch)
    lm = LM(cfg)
    opt = AdamW(lr=3e-3, schedule=cosine_schedule(2, steps))
    params = lm.init_params(jax.random.PRNGKey(0))
    ost = opt.init(params)
    step = jax.jit(make_train_step(lm, opt, microbatches=microbatches))
    corpus = synthetic_corpus(128, 32, cfg.vocab, n_topics=8)
    pipe = TokenPipeline(corpus, 8, seed=0, selector=selector)
    tr = Trainer(lm, opt, step, TrainerConfig(total_steps=steps, log_every=1))
    return tr.fit(params, ost, iter(pipe))


def test_training_reduces_loss():
    res = _train(steps=12)
    losses = [h["loss"] for h in res["history"]]
    assert losses[-1] < losses[0] - 0.1, losses


def test_training_with_microbatches_matches_trend():
    res = _train(steps=8, microbatches=2)
    losses = [h["loss"] for h in res["history"]]
    assert losses[-1] < losses[0], losses


def test_training_with_dpp_batch_selection():
    """The paper feature in the loop: KronDPP-selected diverse batches."""
    corpus = synthetic_corpus(144, 32, 256, n_topics=8)
    rng = np.random.default_rng(0)
    proj = rng.standard_normal((256, 8)).astype(np.float32) / 8
    feats = np.stack([proj[c].mean(0) for c in corpus])
    sel = DPPBatchSelector.from_features(feats, 12, 12)
    cfg = smoke_config("qwen2-0.5b")
    lm = LM(cfg)
    opt = AdamW(lr=3e-3)
    params = lm.init_params(jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(lm, opt))
    pipe = TokenPipeline(corpus, 8, seed=0, selector=sel)
    tr = Trainer(lm, opt, step, TrainerConfig(total_steps=6, log_every=1))
    out = tr.fit(params, opt.init(params), iter(pipe))
    assert len(out["history"]) == 6
    assert np.isfinite([h["loss"] for h in out["history"]]).all()


def test_dpp_batches_are_more_diverse_than_random():
    """KronDPP selection yields at least comparable topic coverage vs
    uniform sampling (and never fails to fill the batch)."""
    rng = np.random.default_rng(0)
    n_topics = 12
    corpus = synthetic_corpus(144, 24, 256, seed=1, n_topics=n_topics)
    proj = rng.standard_normal((256, 8)).astype(np.float32) / 8
    feats = np.stack([proj[c].mean(0) for c in corpus])
    sel = DPPBatchSelector.from_features(feats, 12, 12, scale=4.0)
    topics = np.random.default_rng(1).integers(0, n_topics, 144)

    cov_dpp, cov_rand = [], []
    for t in range(20):
        idx = sel.select(rng, 12)
        assert len(idx) == 12
        cov_dpp.append(len(set(topics[idx])))
        cov_rand.append(len(set(topics[rng.choice(144, 12, replace=False)])))
    assert np.mean(cov_dpp) >= np.mean(cov_rand) - 0.5


def test_selector_learns_from_subsets():
    rng = np.random.default_rng(0)
    feats = rng.standard_normal((36, 4)).astype(np.float32)
    sel = DPPBatchSelector.from_features(feats, 6, 6)
    subs = [list(rng.choice(36, 6, replace=False)) for _ in range(10)]
    sel2 = sel.fit_from_subsets(subs, iters=3)
    assert sel2.dpp.factors[0].shape == sel.dpp.factors[0].shape
    idx = sel2.select(rng, 8)
    assert len(idx) == 8


def test_straggler_hook_fires():
    import time
    cfg = smoke_config("qwen2-0.5b")
    lm = LM(cfg)
    opt = AdamW(lr=1e-3)
    params = lm.init_params(jax.random.PRNGKey(0))
    fired = []

    calls = {"n": 0}
    jitted = jax.jit(make_train_step(lm, opt))

    def slow_step(p, o, b):
        calls["n"] += 1
        if calls["n"] == 9:
            time.sleep(1.5)        # synthetic straggler
        return jitted(p, o, b)

    corpus = synthetic_corpus(64, 32, cfg.vocab)
    tr = Trainer(lm, opt, slow_step,
                 TrainerConfig(total_steps=10, log_every=100,
                               straggler_deadline_factor=3.0),
                 straggler_hook=lambda s, dt: fired.append((s, dt)))
    tr.fit(params, opt.init(params), iter(TokenPipeline(corpus, 4)))
    assert fired, "straggler deadline hook did not fire"


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes
    to the checkout's fixed, git-ignored .jax_cache."""
    import pathlib
    from repro import compile_cache
    root = pathlib.Path(__file__).resolve().parents[1]
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.configure() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.configure() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
