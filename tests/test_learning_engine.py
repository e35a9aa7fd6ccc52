"""repro.learning engine: host-loop equivalence, Armijo guarantees,
checkpoint round-trips, factored-LL agreement, Θ-caching satellites."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KronDPP, SubsetBatch, random_krondpp, sample_krondpp
from repro.core.dpp import log_likelihood as dense_log_likelihood
from repro.core.krk_picard import krk_picard_step, krk_picard_stochastic_step
from repro.learning import (LearningEngine, fit, log_likelihood_factored,
                            schedules, select_minibatch)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(2)
    true = random_krondpp(jax.random.PRNGKey(7), (4, 5))
    subs = [s for s in (sample_krondpp(rng, true) for _ in range(50)) if s]
    return SubsetBatch.from_lists(subs, k_max=max(len(s) for s in subs))


@pytest.fixture(scope="module")
def init():
    return random_krondpp(jax.random.PRNGKey(3), (4, 5))


# ---------------------------------------------------------------------------
# Factored objective
# ---------------------------------------------------------------------------

def test_factored_ll_matches_dense(data, init):
    ll_f = float(log_likelihood_factored(init.factors, data))
    ll_dense = float(dense_log_likelihood(init.full_matrix(), data))
    ll_kron = float(init.log_likelihood(data))
    np.testing.assert_allclose(ll_f, ll_dense, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(ll_f, ll_kron, rtol=1e-4, atol=1e-3)


def test_factored_ll_three_factors(data):
    m3 = random_krondpp(jax.random.PRNGKey(5), (2, 2, 5))
    ll_f = float(log_likelihood_factored(m3.factors, data))
    ll_dense = float(dense_log_likelihood(m3.full_matrix(), data))
    np.testing.assert_allclose(ll_f, ll_dense, rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# Engine vs host equivalence (fixed seeds)
# ---------------------------------------------------------------------------

def test_engine_batch_matches_host_loop(data, init):
    rep = fit(init, data, algorithm="krk", iters=5, a=1.0)
    L1, L2 = init.factors
    lls = [float(KronDPP((L1, L2)).log_likelihood(data))]
    for _ in range(5):
        L1, L2 = krk_picard_step(L1, L2, data, 1.0)
        lls.append(float(KronDPP((L1, L2)).log_likelihood(data)))
    np.testing.assert_allclose(rep.model.factors[0], L1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rep.model.factors[1], L2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rep.log_likelihoods, lls, rtol=1e-4, atol=1e-3)


def test_engine_stochastic_matches_host_reference(data, init):
    """The documented key chain (split -> select_minibatch) replayed on the
    host reproduces the compiled scan exactly."""
    rep = fit(init, data, algorithm="krk-stochastic", iters=6, a=0.7,
              minibatch_size=8, seed=1)
    key = jax.random.PRNGKey(1)
    L1, L2 = init.factors
    for _ in range(6):
        key, k_sel = jax.random.split(key)
        sub = select_minibatch(k_sel, data, 8)
        L1, L2 = krk_picard_step(L1, L2, sub, 0.7)
    np.testing.assert_allclose(rep.model.factors[0], L1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rep.model.factors[1], L2, rtol=1e-5, atol=1e-5)


def test_minibatch_request_promotes_to_stochastic(data, init):
    """fit(algorithm="krk", minibatch_size=m) must run stochastic sweeps,
    not silently fall back to full-batch ones."""
    promoted = fit(init, data, algorithm="krk", iters=3, minibatch_size=8,
                   seed=1)
    explicit = fit(init, data, algorithm="krk-stochastic", iters=3,
                   minibatch_size=8, seed=1)
    np.testing.assert_allclose(promoted.model.factors[0],
                               explicit.model.factors[0], rtol=1e-6)
    with pytest.raises(ValueError):
        LearningEngine(algorithm="em", minibatch_size=8)


def test_engine_em_matches_host_loop(data, init):
    from repro.core.em import e_step, eigvec_ascent, m_step_eigvals
    rep = fit(init.full_matrix(), data, algorithm="em", iters=4, a=1e-3)
    lam, V = jnp.linalg.eigh(init.full_matrix())
    lam = jnp.maximum(lam, 1e-6)
    for _ in range(4):
        q = e_step(lam, V, data)
        lam = m_step_eigvals(q)
        V = eigvec_ascent(lam, V, data, 1e-3)
    np.testing.assert_allclose(rep.model, (V * lam[None, :]) @ V.T,
                               rtol=1e-4, atol=1e-4)


def test_chunked_ll_subsamples_sweep_ll(data, init):
    """ll_mode="chunk" values must equal the per-sweep trajectory at chunk
    boundaries — chunking changes sync cadence, never the math."""
    full = fit(init, data, algorithm="krk", iters=6, a=1.0)
    chunked = fit(init, data, algorithm="krk", iters=6, a=1.0,
                  log_every=3, ll_mode="chunk")
    assert chunked.ll_sweeps == [0, 3, 6]
    np.testing.assert_allclose(
        chunked.log_likelihoods,
        [full.log_likelihoods[i] for i in chunked.ll_sweeps],
        rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Armijo schedule: PSD + monotone ascent (Thm 3.2)
# ---------------------------------------------------------------------------

def test_armijo_monotone_and_pd(data, init):
    rep = fit(init, data, algorithm="krk", iters=6,
              schedule=schedules.armijo(a0=2.0))
    lls = np.asarray(rep.log_likelihoods)
    assert np.all(np.diff(lls) > -1e-3), lls
    for f in rep.model.factors:
        assert np.linalg.eigvalsh(np.asarray(f)).min() > 0


def test_armijo_backtracks_oversized_step(data, init):
    """An absurd a0 must be shrunk on device, still yielding ascent."""
    rep = fit(init, data, algorithm="krk", iters=4,
              schedule=schedules.armijo(a0=64.0, max_backtracks=12))
    lls = np.asarray(rep.log_likelihoods)
    assert int(rep.state.sched.backtracks) > 0
    assert np.all(np.diff(lls) > -1e-3), lls
    for f in rep.model.factors:
        assert np.linalg.eigvalsh(np.asarray(f)).min() > 0


def test_armijo_rejected_for_em():
    with pytest.raises(ValueError):
        LearningEngine(algorithm="em", schedule=schedules.armijo())


# ---------------------------------------------------------------------------
# Checkpoint save/resume mid-fit
# ---------------------------------------------------------------------------

def test_checkpoint_resume_roundtrip(data, init, tmp_path):
    ck = str(tmp_path / "ck")
    kw = dict(algorithm="krk-stochastic", minibatch_size=8, seed=5,
              schedule=schedules.inv_sqrt(1.0))
    fit(init, data, iters=4, checkpoint_dir=ck, save_every=2, **kw)
    resumed = fit(init, data, iters=8, checkpoint_dir=ck, resume=True,
                  save_every=2, **kw)
    oneshot = fit(init, data, iters=8, **kw)
    assert resumed.sweeps == 8
    assert resumed.ll_sweeps[0] == 5   # continued, not restarted
    np.testing.assert_allclose(resumed.model.factors[0],
                               oneshot.model.factors[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(resumed.model.factors[1],
                               oneshot.model.factors[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        resumed.log_likelihoods, oneshot.log_likelihoods[5:],
        rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Θ-statistics satellites
# ---------------------------------------------------------------------------

def test_stochastic_step_threads_dense_theta(data, init):
    L1, L2 = init.factors
    s1 = krk_picard_stochastic_step(L1, L2, data, 1.0, use_dense_theta=True)
    s2 = krk_picard_step(L1, L2, data, 1.0, use_dense_theta=True)
    np.testing.assert_allclose(s1[0], s2[0], rtol=1e-6)
    np.testing.assert_allclose(s1[1], s2[1], rtol=1e-6)


def test_cached_theta_routes_agree(data, init):
    """With Θ cached across the half-updates, the dense and sparse routes
    still compute the same sweep."""
    L1, L2 = init.factors
    c_dense = krk_picard_step(L1, L2, data, 1.0, use_dense_theta=True,
                              fresh_theta=False)
    c_sparse = krk_picard_step(L1, L2, data, 1.0, use_dense_theta=False,
                               fresh_theta=False)
    np.testing.assert_allclose(c_dense[0], c_sparse[0], rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(c_dense[1], c_sparse[1], rtol=1e-3, atol=1e-4)


def test_cached_theta_still_ascends(data, init):
    rep = fit(init, data, algorithm="krk", iters=6, a=1.0, fresh_theta=False)
    lls = rep.log_likelihoods
    assert lls[-1] > lls[0]
    for f in rep.model.factors:
        assert np.linalg.eigvalsh(np.asarray(f)).min() > 0


# ---------------------------------------------------------------------------
# Distributed drop-in
# ---------------------------------------------------------------------------

def test_distributed_fit_matches_local():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",   # never the chip
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import jax, numpy as np
        from repro.core import SubsetBatch, random_krondpp, sample_krondpp
        from repro.learning import fit
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        true = random_krondpp(jax.random.PRNGKey(7), (4, 5))
        subs = [s for s in (sample_krondpp(rng, true) for _ in range(40)) if s][:32]
        batch = SubsetBatch.from_lists(subs, k_max=max(len(s) for s in subs))
        init = random_krondpp(jax.random.PRNGKey(3), (4, 5))
        local = fit(init, batch, algorithm="krk", iters=3, a=1.0)
        with mesh:
            dist = fit(init, batch, algorithm="krk", iters=3, a=1.0, mesh=mesh)
        np.testing.assert_allclose(np.asarray(dist.model.factors[0]),
                                   np.asarray(local.model.factors[0]),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dist.model.factors[1]),
                                   np.asarray(local.model.factors[1]),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(dist.log_likelihoods[-1],
                                   local.log_likelihoods[-1], rtol=1e-3, atol=1e-2)
        print("DIST_FIT_OK")
    """)], capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DIST_FIT_OK" in out.stdout


# ---------------------------------------------------------------------------
# Throughput acceptance (excluded from tier-1 via the `slow` marker)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_speedup_over_host_loop():
    """Acceptance: >= 3x sweeps/sec over the per-sweep host loop at
    minibatch <= 64 on CPU (the committed benchmark report shows ~40x;
    this smoke run keeps a conservative floor)."""
    from benchmarks.paper_fig1_engine import run
    res = run()
    for row in res["rows"]:
        assert row["speedup"] >= 3.0, row
        assert row["ll_match_fp32"], row
