"""The ``repro.dpp`` facade: one shared property suite over ``Dense``,
m=2 ``Kron`` and full-rank ``LowRank`` (all three are the same protocol,
so they are tested by the same code), closure operations (``condition`` /
``marginal``) validated against brute-force enumeration over the full
kernel at small N, the deprecation contract of the pre-facade free
functions, and the architectural rule that every consumer layer routes
through ``repro.dpp``.
"""

import itertools
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dpp
from repro.core import SubsetBatch
from repro.core.dpp import enumerate_probabilities, marginal_kernel

N = 6          # ground set size — small enough to enumerate all 2^N subsets


def _make_model(kind: str):
    if kind == "kron":
        return dpp.random_kron(jax.random.PRNGKey(5), (2, 3))
    if kind == "lowrank":
        # full-rank r = N so brute-force enumeration semantics hold on
        # every subset (a rank-deficient basis would send |Y| > r to -inf)
        V = jax.random.normal(jax.random.PRNGKey(6), (N, N)) * 0.6
        q = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (N,))) + 0.5
        return dpp.LowRank(V, q)
    kern = dpp.random_kron(jax.random.PRNGKey(5), (2, 3)).dense_kernel()
    return dpp.from_kernel(kern)


@pytest.fixture(scope="module", params=["dense", "kron", "lowrank"])
def model(request):
    return _make_model(request.param)


@pytest.fixture(scope="module")
def oracle(model):
    """Brute-force probabilities + marginal kernel for the same kernel."""
    L = np.asarray(model.dense_kernel())
    return enumerate_probabilities(L), np.asarray(marginal_kernel(L))


def _membership(batch: SubsetBatch, n_items: int) -> np.ndarray:
    idx = np.asarray(batch.indices)
    msk = np.asarray(batch.mask)
    out = np.zeros((batch.n, n_items))
    for i in range(batch.n):
        out[i, idx[i][msk[i]]] = 1.0
    return out


# ---------------------------------------------------------------------------
# shared property suite — identical assertions for Dense and Kron
# ---------------------------------------------------------------------------

def test_log_prob_matches_enumerated_reference(model, oracle):
    probs, _ = oracle
    subsets = [[0], [1, 3], [0, 2, 5], [2], [0, 1, 2, 3, 4, 5]]
    batch = SubsetBatch.from_lists(subsets)
    lp = np.asarray(model.log_prob(batch))
    ref = [np.log(probs[tuple(sorted(s))]) for s in subsets]
    np.testing.assert_allclose(lp, ref, rtol=1e-4, atol=1e-5)
    # log_likelihood is the batch mean of log_prob
    np.testing.assert_allclose(float(model.log_likelihood(batch)),
                               np.mean(ref), rtol=1e-4, atol=1e-5)
    # the empty set: log P(∅) = -log det(L + I)
    empty = SubsetBatch(jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1, 2), bool))
    np.testing.assert_allclose(float(model.log_prob(empty)[0]),
                               np.log(probs[()]), rtol=1e-4, atol=1e-5)


def test_sample_marginals_match_marginal_kernel(model, oracle):
    _, K = oracle
    S = 3000
    batch = model.sample(jax.random.PRNGKey(0), S)
    assert batch.n == S
    mem = _membership(batch, N)
    np.testing.assert_allclose(mem.mean(0), np.diag(K), atol=0.04)
    # pair inclusions: P({i,j} ⊆ Y) = det(K_{ij})
    for i, j in [(0, 3), (1, 5)]:
        exact = K[i, i] * K[j, j] - K[i, j] ** 2
        assert abs((mem[:, i] * mem[:, j]).mean() - exact) < 0.04


def test_kdpp_sample_exactly_k(model):
    batch = model.sample(jax.random.PRNGKey(1), 200, k=2)
    sizes = np.asarray(batch.sizes())
    assert (sizes == 2).all()
    idx = np.asarray(batch.indices)
    assert all(len(set(row.tolist())) == 2 for row in idx)


def test_host_runtime_matches_device_size_distribution(model):
    host = model.sample(jax.random.PRNGKey(2), 400, runtime=dpp.Host())
    dev = model.sample(jax.random.PRNGKey(3), 400)
    h = np.bincount(np.asarray(host.sizes()), minlength=N + 1) / 400
    d = np.bincount(np.asarray(dev.sizes()), minlength=N + 1)[:N + 1] / 400
    assert np.abs(h - d).max() < 0.12
    # the Host oracle draws k-DPPs too: k distinct items in every row
    hk = model.sample(jax.random.PRNGKey(0), 20, k=2, runtime=dpp.Host())
    assert all(len(r) == len(set(r)) == 2 for r in hk.to_lists())


def test_marginal_matches_bruteforce(model, oracle):
    probs, K = oracle
    # singleton
    for i in (0, 4):
        bf = sum(p for Y, p in probs.items() if i in Y)
        np.testing.assert_allclose(float(model.marginal(i)), bf,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(model.marginal(i)), K[i, i],
                                   rtol=1e-4, atol=1e-5)
    # sets, via det(K_S) and via enumeration
    for S in ([1, 4], [0, 2, 5]):
        bf = sum(p for Y, p in probs.items() if set(S) <= set(Y))
        np.testing.assert_allclose(float(model.marginal(S)), bf,
                                   rtol=1e-3, atol=1e-5)


def test_expected_size_is_trace_of_marginal_kernel(model, oracle):
    _, K = oracle
    np.testing.assert_allclose(model.expected_size(), np.trace(K),
                               rtol=1e-4)


def test_condition_matches_bruteforce(model, oracle):
    probs, _ = oracle
    A = [2]
    cond = model.condition(A)
    # closure: LowRank conditions in feature space and stays LowRank;
    # Dense/Kron close over the dense Schur complement
    want_type = dpp.LowRank if type(model) is dpp.LowRank else dpp.Dense
    assert type(cond) is want_type
    comp = [i for i in range(N) if i not in A]
    assert cond.N == len(comp)
    Z_A = sum(p for Y, p in probs.items() if set(A) <= set(Y))
    # conditional subset probabilities: P(B ∪ A | A ⊆ Y)
    for B in ([], [1], [1, 4], [0, 3, 5]):
        want = probs[tuple(sorted(set(B) | set(A)))] / Z_A
        local = [comp.index(b) for b in B]
        batch = SubsetBatch.from_lists([local], k_max=max(1, len(local)))
        if not local:
            batch = SubsetBatch(jnp.zeros((1, 1), jnp.int32),
                                jnp.zeros((1, 1), bool))
        got = float(jnp.exp(cond.log_prob(batch)[0]))
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)
    # conditional marginals: P(i ∈ Y | A ⊆ Y)
    for i in comp:
        bf = sum(p for Y, p in probs.items()
                 if set(A) <= set(Y) and i in Y) / Z_A
        np.testing.assert_allclose(float(cond.marginal(comp.index(i))), bf,
                                   rtol=1e-3, atol=1e-5)


def test_condition_two_items_then_sample(model, oracle):
    """Conditioning composes with sampling: empirical singleton marginals
    of the conditioned model match the brute-force conditional marginals."""
    probs, _ = oracle
    A = [0, 3]
    cond = model.condition(A)
    comp = [i for i in range(N) if i not in A]
    Z_A = sum(p for Y, p in probs.items() if set(A) <= set(Y))
    want = np.array([sum(p for Y, p in probs.items()
                         if set(A) <= set(Y) and i in Y) / Z_A
                     for i in comp])
    S = 3000
    mem = _membership(cond.sample(jax.random.PRNGKey(7), S), cond.N)
    np.testing.assert_allclose(mem.mean(0), want, atol=0.045)


def test_condition_input_validation(model):
    with pytest.raises(ValueError):
        model.condition([0, N])              # out of range
    assert model.condition([]) is model      # empty observed is a no-op


def test_condition_on_zero_probability_set_raises():
    """Conditioning on linearly dependent items of a rank-deficient kernel
    (P(A ⊆ Y) = 0) must fail loudly, not return a silent all-NaN model."""
    x = jnp.asarray([1.0, 1.0, 0.5, -0.2])
    rank1 = dpp.from_kernel(jnp.outer(x, x))
    with pytest.raises(ValueError, match="singular"):
        rank1.condition([0, 1])


def test_kron_fit_em_max_dense_override():
    """algorithm='em' on a Kron model materializes the kernel behind the
    guard; fit(max_dense=...) must reach that materialization so callers
    can raise (or here: lower) the bound."""
    m = dpp.random_kron(jax.random.PRNGKey(0), (3, 4))       # N = 12
    batch = SubsetBatch.from_lists([[0, 1], [2]])
    with pytest.raises(ValueError, match="max_dense"):
        m.fit(batch, algorithm="em", iters=1, max_dense=8)   # 12 > 8
    rep = m.fit(batch, algorithm="em", iters=1, max_dense=16)
    assert type(rep.model) is dpp.Dense


def test_kron_supports_dataclasses_replace_on_reports():
    """Kron is not a dataclass (constructor normalizes its argument);
    FitReport-style dataclasses.replace around it must still work, and
    Dense — which is a dataclass — must replace cleanly."""
    import dataclasses
    d = _make_model("dense")
    d2 = dataclasses.replace(d, L=d.L * 2.0)
    np.testing.assert_allclose(np.asarray(d2.L), 2.0 * np.asarray(d.L))
    k = _make_model("kron")
    assert repr(k) == f"Kron(sizes={k.sizes})"


def test_marginal_input_validation(model, oracle):
    _, K = oracle
    for bad in (N, -1, [0, N]):
        with pytest.raises(ValueError, match="out of range"):
            model.marginal(bad)
    # duplicate indices have set semantics: P({3,3} ⊆ Y) = P(3 ∈ Y)
    np.testing.assert_allclose(float(model.marginal([3, 3])), K[3, 3],
                               rtol=1e-4, atol=1e-5)


def test_model_equality_does_not_crash(model):
    assert model != _make_model("kron")      # no ambiguous-truth ValueError
    assert model == model


def test_map_is_valid_and_greedy(model):
    picks = np.asarray(model.map(3))
    assert picks.shape == (3,)
    assert len(set(picks.tolist())) == 3
    assert (picks >= 0).all() and (picks < N).all()
    # first greedy pick is the max-variance item
    L = np.asarray(model.dense_kernel())
    assert picks[0] == int(np.argmax(np.diag(L)))


def test_rescale_hits_target_expected_size(model):
    r = model.rescale(2.5)
    assert type(r) is type(model)
    np.testing.assert_allclose(r.expected_size(), 2.5, atol=1e-3)


def test_fit_returns_wrapped_model_and_ascends(model):
    data = model.sample(jax.random.PRNGKey(11), 32)
    rep = model.fit(data, iters=3, a=0.5)
    assert isinstance(rep.model, dpp.DPPModel)
    if isinstance(model, dpp.Kron):
        assert type(rep.model) is dpp.Kron           # krk default
        lls = rep.log_likelihoods
        assert all(b >= a - 1e-3 for a, b in zip(lls, lls[1:])), lls
    elif isinstance(model, dpp.LowRank):
        assert type(rep.model) is dpp.LowRank        # dual learner default
        lls = rep.log_likelihoods
        assert all(b >= a - 1e-3 for a, b in zip(lls, lls[1:])), lls
    else:
        assert type(rep.model) is dpp.Dense          # em default
    # the fitted model is a full facade citizen
    assert np.isfinite(float(rep.model.log_likelihood(data)))


def test_spectrum_is_cached_across_facade_calls(model):
    cache = dpp.SpectralCache()
    model.log_prob(model.sample(jax.random.PRNGKey(0), 4, cache=cache),
                   cache=cache)
    model.marginal(0, cache=cache)
    model.expected_size(cache=cache)
    assert cache.stats()["misses"] == model.m     # one eigh per factor ever
    assert cache.stats()["evictions"] == 0


def test_service_runs_off_facade_model(model):
    svc = model.service(seed=0, cache=dpp.SpectralCache())
    rows = svc.sample(5)
    assert len(rows) == 5
    assert all(all(0 <= i < N for i in r) for r in rows)


def test_lowrank_q_update_costs_one_dual_eigh():
    """The per-tenant pattern — shared basis V, swapped quality q — must
    cost exactly one extra r×r dual eigh per q (no miss storm: every
    facade call on the same (V, q) pair is a cache hit)."""
    cache = dpp.SpectralCache()
    V = jax.random.normal(jax.random.PRNGKey(0), (N, 4))
    m1 = dpp.LowRank(V, jnp.ones(N))
    m1.expected_size(cache=cache)
    m1.marginal(0, cache=cache)
    m1.log_prob(m1.sample(jax.random.PRNGKey(1), 4, cache=cache),
                cache=cache)
    assert cache.stats()["misses"] == 1
    q2 = jnp.full((N,), 2.0)
    m2 = dpp.LowRank(V, q2)
    m2.expected_size(cache=cache)
    m2.log_prob(m2.sample(jax.random.PRNGKey(2), 4, cache=cache),
                cache=cache)
    stats = cache.stats()
    assert stats["misses"] == 2          # one r×r eigh for the new q
    assert stats["evictions"] == 0
    assert stats["hits"] >= 4


# ---------------------------------------------------------------------------
# Kron-specific guards
# ---------------------------------------------------------------------------

def test_kron_dense_fallback_guard():
    big = dpp.random_kron(jax.random.PRNGKey(0), (80, 80))   # N = 6400
    with pytest.raises(ValueError, match="max_dense"):
        big.condition([0])
    with pytest.raises(ValueError, match="max_dense"):
        big.map(4)
    with pytest.raises(ValueError, match="max_dense"):
        big.dense_kernel()


def test_dense_rejects_factored_learners():
    d = _make_model("dense")
    with pytest.raises(ValueError, match="em"):
        d.fit(SubsetBatch.from_lists([[0, 1]]), algorithm="krk")


# ---------------------------------------------------------------------------
# deprecation contract of the pre-facade entry points
# ---------------------------------------------------------------------------

def _tiny_fit_inputs():
    m = dpp.random_kron(jax.random.PRNGKey(0), (2, 3))
    batch = SubsetBatch.from_lists([[0, 2], [1], [3, 4]])
    return m.to_krondpp(), batch


def test_core_fit_shims_warn():
    from repro.core import fit_em, fit_joint_picard, fit_krk_picard
    krondpp, batch = _tiny_fit_inputs()
    with pytest.warns(DeprecationWarning, match="repro.dpp"):
        fit_krk_picard(krondpp, batch, iters=1)
    with pytest.warns(DeprecationWarning, match="repro.dpp"):
        fit_joint_picard(krondpp, batch, iters=1)
    with pytest.warns(DeprecationWarning, match="repro.dpp"):
        fit_em(krondpp.full_matrix(), batch, iters=1)


def test_facade_paths_do_not_warn():
    """The facade must not route through its own deprecated shims."""
    m = dpp.random_kron(jax.random.PRNGKey(0), (2, 3))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        batch = m.sample(jax.random.PRNGKey(1), 4)
        m.sample(jax.random.PRNGKey(2), 2, k=2)
        m.log_prob(batch)
        m.marginal([0, 1])
        m.condition([0]).sample(jax.random.PRNGKey(3), 2)
        m.map(2)
        m.fit(batch, iters=1)
        m.service(cache=dpp.SpectralCache()).sample(2)


# ---------------------------------------------------------------------------
# architecture: consumer layers route through repro.dpp only
# ---------------------------------------------------------------------------

def test_consumer_layers_do_not_import_subsystem_internals():
    """The invariant lives in repro.analysis as the ``facade-boundary``
    rule (with TP/TN fixtures and a parity test in test_analysis.py);
    here we pin that the real tree runs clean — including serving/ and
    benchmarks/, which the rule scans and the old ad-hoc scan did not."""
    from repro.analysis import analyze_paths
    root = pathlib.Path(__file__).resolve().parent.parent
    findings, errors, n_files = analyze_paths(
        [root / "src", root / "examples", root / "benchmarks"],
        select=["facade-boundary"], root=root)
    assert not errors, [e.render() for e in errors]
    assert not findings, [f.render() for f in findings]
    assert n_files >= 12             # the rule actually scanned the tree
