"""The device-resident batched sampling subsystem (repro.sampling).

Statistical exactness is checked against closed forms (marginal kernel,
conditional k-DPP probabilities) on kernels small enough to enumerate —
the same oracles the host numpy sampler is validated against — plus the
subsystem contracts: fixed-shape jit/vmap cleanliness, spectral-cache
hit/miss behavior, and service coalescing.
"""

import itertools
import math
import os
import pathlib
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dpp, obs
from repro.core import KronDPP, random_krondpp
from repro.core.dpp import marginal_kernel
from repro.sampling import (FactorSpectrum, SamplingService, SpectralCache,
                            compile_cache_size, log_esp_table,
                            picks_to_lists)
# engine entry points live in the submodules (repro.sampling does not
# re-export them; consumers go through the repro.dpp facade)
from repro.sampling.batched import (sample_krondpp_batched,
                                    sample_krondpp_keyed)
from repro.sampling.kdpp import sample_kdpp_batched, sample_kdpp_dense


def _membership(picks, N):
    """(B, k_max) padded picks -> (B, N) 0/1 membership matrix."""
    arr = np.asarray(picks)
    out = np.zeros((arr.shape[0], N))
    for b, row in enumerate(arr):
        out[b, row[row >= 0]] = 1.0
    return out


# ---------------------------------------------------------------------------
# exactness vs the closed-form oracles
# ---------------------------------------------------------------------------

def test_singleton_and_pair_marginals_match_reference():
    m = random_krondpp(jax.random.PRNGKey(5), (2, 3))
    K = np.asarray(marginal_kernel(np.asarray(m.full_matrix())))
    spec = SpectralCache().spectrum(m)
    S = 4000
    picks, counts, _ = sample_krondpp_batched(jax.random.PRNGKey(0), spec,
                                              num_samples=S)
    mem = _membership(picks, m.N)
    # singleton: P(i in Y) = K_ii
    np.testing.assert_allclose(mem.mean(0), np.diag(K), atol=0.04)
    # pairs: P({i,j} subset Y) = K_ii K_jj - K_ij^2
    for i, j in [(0, 3), (3, 4), (1, 5)]:
        exact = K[i, i] * K[j, j] - K[i, j] ** 2
        emp = (mem[:, i] * mem[:, j]).mean()
        assert abs(emp - exact) < 0.04, (i, j, emp, exact)
    # counts column is consistent with the padding
    assert (counts == mem.sum(1)).all()


def test_matches_host_reference_sampler_size_distribution():
    m = random_krondpp(jax.random.PRNGKey(3), (2, 3))
    from repro.core import sample_krondpp
    rng = np.random.default_rng(0)
    S = 1200
    sizes_host = np.zeros(7)
    for _ in range(S):
        sizes_host[len(sample_krondpp(rng, m))] += 1
    spec = SpectralCache().spectrum(m)
    _, counts, _ = sample_krondpp_batched(jax.random.PRNGKey(1), spec,
                                          num_samples=S)
    sizes_dev = np.bincount(np.asarray(counts), minlength=7)[:7]
    assert np.abs(sizes_host - sizes_dev).max() / S < 0.08


def test_three_factor_kernel():
    m = random_krondpp(jax.random.PRNGKey(2), (2, 2, 2))
    K = np.asarray(marginal_kernel(np.asarray(m.full_matrix())))
    spec = SpectralCache().spectrum(m)
    picks, _, _ = sample_krondpp_batched(jax.random.PRNGKey(4), spec,
                                         num_samples=3000)
    mem = _membership(picks, 8)
    np.testing.assert_allclose(mem.mean(0), np.diag(K), atol=0.05)


def test_kdpp_exactly_k_and_conditional_distribution():
    m = random_krondpp(jax.random.PRNGKey(3), (2, 3))
    L = np.asarray(m.full_matrix())
    k = 2
    dets = {Y: np.linalg.det(L[np.ix_(Y, Y)])
            for Y in itertools.combinations(range(6), k)}
    Z = sum(dets.values())
    spec = SpectralCache().spectrum(m)
    S = 4000
    picks = sample_kdpp_batched(jax.random.PRNGKey(9), spec, k, S)
    rows = picks_to_lists(picks)
    assert all(len(set(r)) == k for r in rows)
    from collections import Counter
    cnt = Counter(tuple(sorted(r)) for r in rows)
    for Y, d in dets.items():
        assert abs(cnt.get(Y, 0) / S - d / Z) < 0.04, Y


def test_log_esp_table_matches_bruteforce():
    rng = np.random.default_rng(0)
    lam = np.abs(rng.standard_normal(10))
    tab = np.asarray(log_esp_table(jnp.log(jnp.asarray(lam)), 4))
    for n in range(11):
        for j in range(5):
            want = sum(np.prod(c) for c in
                       itertools.combinations(lam[:n], j)) if j else 1.0
            got = np.exp(tab[n, j])
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


def test_sample_kdpp_dense_vmaps():
    keys = jax.random.normal(jax.random.PRNGKey(0), (3, 12, 4))
    Ls = jnp.einsum("hsd,htd->hst", keys, keys) + 1e-3 * jnp.eye(12)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    picks = jax.jit(jax.vmap(lambda key, L: sample_kdpp_dense(key, L, 4))
                    )(ks, Ls)
    arr = np.asarray(picks)
    assert arr.shape == (3, 4)
    for row in arr:
        assert len(set(row.tolist())) == 4
        assert (row >= 0).all() and (row < 12).all()


def test_huge_spectrum_no_float32_overflow():
    """Product eigenvalues past float32 max used to overflow the linear
    phase-1 fold: inf/(1+inf) = NaN probabilities -> silently empty
    samples, and NaN E|Y| crashed SamplingService construction."""
    big = KronDPP((1e20 * jnp.eye(4), 1e20 * jnp.eye(4)))   # λ = 1e40
    spec = SpectralCache().spectrum(big)
    assert np.isfinite(spec.expected_size())
    assert abs(spec.expected_size() - 16.0) < 1e-3          # p -> 1
    picks, counts, _ = sample_krondpp_batched(jax.random.PRNGKey(0), spec,
                                              num_samples=4)
    assert (np.asarray(counts) == 16).all()                 # everything in
    svc = SamplingService(big)                              # no NaN ceil
    assert all(len(s) == 16 for s in svc.sample(2))


def test_factored_columns_match_materialized_eigvecs():
    """phase 2 runs on factored columns; they must reproduce the
    materialized Kronecker eigenvectors (assemble_eigvecs)."""
    from repro.kernels.phase2_select import (_dot_nt, canonical_pair,
                                             init_norms, pad_pair)
    from repro.sampling.batched import assemble_eigvecs, gather_factor_columns
    m = random_krondpp(jax.random.PRNGKey(8), (3, 4))
    spec = SpectralCache().spectrum(m)
    sel = jnp.asarray([0, 5, 11, 7], jnp.int32)
    valid = jnp.asarray([True, True, True, False])
    sizes = (3, 4)
    V = np.asarray(assemble_eigvecs(spec.vecs, sizes, sel, valid))
    Gs = gather_factor_columns(spec.vecs, sizes, sel, valid)
    q = jnp.asarray([0.3, -1.2, 0.5, 2.0], jnp.float32)
    qp, g1, gr = pad_pair(q, *canonical_pair(Gs))
    # the phase-2 step's (N1, Nr) grid of V q and of the residual norms
    np.testing.assert_allclose(
        np.asarray(_dot_nt(g1 * qp, gr))[:3, :4].reshape(-1),
        V @ np.asarray(q), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(init_norms(g1, gr))[:3, :4].reshape(-1), (V * V).sum(1),
        rtol=1e-5, atol=1e-6)
    for i in (0, 7, 11):
        np.testing.assert_allclose(
            np.asarray(g1[i // 4] * gr[i % 4])[:4], V[i], rtol=1e-5,
            atol=1e-6)
    assert not np.asarray(g1[3:]).any() and not np.asarray(gr[4:]).any()


# ---------------------------------------------------------------------------
# subsystem contracts
# ---------------------------------------------------------------------------

def test_spectral_cache_hit_miss_and_eviction():
    cache = SpectralCache(maxsize=3)
    m1 = random_krondpp(jax.random.PRNGKey(0), (3, 4))
    m2 = random_krondpp(jax.random.PRNGKey(1), (3, 4))
    cache.spectrum(m1)
    assert cache.stats() == {"hits": 0, "misses": 2, "evictions": 0,
                             "size": 2}
    assert cache.stats["misses"] == 2     # PR-1 property spelling still works
    cache.spectrum(m1)
    assert cache.stats()["hits"] == 2 and cache.stats()["misses"] == 2
    cache.spectrum(m2)                       # 2 more misses, evicts one of m1
    assert cache.stats()["misses"] == 4 and len(cache) == 3
    assert cache.stats()["evictions"] == 1   # the LRU entry fell out
    # shared factor objects across models hit (m1.factors[1] survived the
    # eviction, m2's factors are fresh)
    m3 = KronDPP((m2.factors[0], m1.factors[1]))
    cache.spectrum(m3)
    assert cache.stats()["hits"] == 4 and cache.stats()["misses"] == 4


# ---------------------------------------------------------------------------
# the phase-2 budget: size moments memoized with the cached spectrum
# ---------------------------------------------------------------------------

ROOT = pathlib.Path(__file__).resolve().parent.parent
MEMO_KINDS = ["kron2", "kron3", "dense"]


def _memo_model(kind):
    if kind == "kron2":
        return dpp.random_kron(jax.random.PRNGKey(21), (3, 4)).rescale(3.0)
    if kind == "kron3":
        return dpp.random_kron(jax.random.PRNGKey(22),
                               (2, 3, 4)).rescale(3.0)
    kern = dpp.random_kron(jax.random.PRNGKey(23), (3, 4)).dense_kernel()
    return dpp.from_kernel(kern).rescale(3.0)


def _counted(fn):
    """fn() under a fresh InMemoryTracker -> (result, counters)."""
    with obs.use(obs.InMemoryTracker()) as t:
        out = fn()
    return out, dict(t.counters)


def _assert_memo_miss(counters):
    assert counters["spectral_cache.moments_misses"] == 1
    assert counters["dpp.host_syncs"] == 2       # expected_size, size_std
    assert "spectral_cache.moments_hits" not in counters


def _assert_memo_hit(counters):
    assert counters["spectral_cache.moments_hits"] == 1
    assert "dpp.host_syncs" not in counters
    assert "spectral_cache.moments_misses" not in counters


@pytest.mark.parametrize("kind", MEMO_KINDS)
def test_second_sample_reads_the_memoized_k_max(kind):
    model, cache = _memo_model(kind), SpectralCache()
    key = jax.random.PRNGKey(0)
    first, c = _counted(lambda: model.sample(key, 4, cache=cache))
    _assert_memo_miss(c)
    second, c = _counted(lambda: model.sample(key, 4, cache=cache))
    _assert_memo_hit(c)
    assert first.indices.shape == second.indices.shape


@pytest.mark.parametrize("kind", MEMO_KINDS)
def test_memoized_k_max_equals_a_fresh_eager_computation(kind):
    model, cache = _memo_model(kind), SpectralCache()
    k_first = model.spectrum(cache).suggested_k_max()
    spec = model.spectrum(cache)
    ll = spec.log_eigenvalues()
    e = float(jnp.sum(jax.nn.sigmoid(ll)))
    sd = float(jnp.sqrt(jnp.sum(jax.nn.sigmoid(ll) * jax.nn.sigmoid(-ll))))
    fresh = FactorSpectrum(spec.lams, spec.vecs)        # its own cold memo
    assert spec.size_moments() == (e, sd) \
        == (fresh.expected_size(), fresh.size_std())
    want = max(1, min(math.ceil(e + 6.0 * sd) + 1, model.N))
    assert k_first == spec.suggested_k_max() == fresh.suggested_k_max() \
        == want
    assert model.sample(jax.random.PRNGKey(1), 2,
                        cache=cache).indices.shape[1] == want


def test_rescaled_model_misses_the_memo():
    model, cache = _memo_model("kron2"), SpectralCache()
    key = jax.random.PRNGKey(2)
    model.sample(key, 4, cache=cache)
    scaled = model.rescale(2.0, cache=cache)        # new factor arrays
    _, c = _counted(lambda: scaled.sample(key, 4, cache=cache))
    _assert_memo_miss(c)
    _, c = _counted(lambda: model.sample(key, 4, cache=cache))
    _assert_memo_hit(c)


@pytest.mark.parametrize("drop", ["clear", "evict"])
def test_clear_and_lru_eviction_drop_the_memo(drop):
    model, cache = _memo_model("kron2"), SpectralCache(maxsize=2)
    key = jax.random.PRNGKey(3)
    model.sample(key, 4, cache=cache)
    if drop == "clear":
        cache.clear()
        assert not cache._spectra            # no factor stays pinned
    else:                    # two fresh factors push the model's out
        _memo_model("dense").sample(key, 4, cache=cache)
        _memo_model("kron3").sample(key, 4, cache=cache)
        assert len(cache._spectra) <= cache.maxsize
    _, c = _counted(lambda: model.sample(key, 4, cache=cache))
    _assert_memo_miss(c)


@pytest.mark.parametrize("kind", MEMO_KINDS)
def test_draws_are_bit_identical_with_the_memo_cold_and_warm(kind):
    model, cache = _memo_model(kind), SpectralCache()
    key = jax.random.PRNGKey(9)
    cold = model.sample(key, 16, cache=cache)
    warm = model.sample(key, 16, cache=cache)
    other = model.sample(key, 16, cache=SpectralCache())
    for b in (warm, other):
        for x, y in ((cold.indices, b.indices), (cold.mask, b.mask),
                     (cold.truncated, b.truncated)):
            assert x.dtype == y.dtype
            assert (np.asarray(x) == np.asarray(y)).all()


@pytest.mark.threaded
def test_threads_sampling_one_fresh_model_fill_the_memo_once():
    model, cache = _memo_model("kron3"), SpectralCache()
    n = 8
    barrier = threading.Barrier(n)
    widths, errors = [], []

    def draw(seed):
        try:
            barrier.wait(timeout=60)
            out = model.sample(jax.random.PRNGKey(seed), 4, cache=cache)
            widths.append(int(out.indices.shape[1]))
        except Exception as e:    # noqa: BLE001 — surfaced below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.use(obs.InMemoryTracker()) as t:
            threads = [threading.Thread(target=draw, args=(s,))
                       for s in range(n)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in threads)
    assert errors == [] and len(widths) == n
    assert set(widths) == {model.spectrum(cache).suggested_k_max()}
    # the memo's lock lets one thread compute; the others read its result
    assert t.counters["spectral_cache.moments_misses"] == 1
    assert t.counters["spectral_cache.moments_hits"] == n - 1
    assert t.counters["dpp.host_syncs"] == 2


def test_null_tracker_warm_sample_runs_no_moment_arithmetic(monkeypatch):
    model, cache = _memo_model("kron2"), SpectralCache()
    key = jax.random.PRNGKey(4)
    cold = model.sample(key, 8, cache=cache)

    def no_sync(self):
        raise AssertionError("a warm sample recomputed a size moment")

    monkeypatch.setattr(FactorSpectrum, "expected_size", no_sync)
    monkeypatch.setattr(FactorSpectrum, "size_std", no_sync)
    assert not obs.enabled(obs.current_tracker())
    warm = model.sample(key, 8, cache=cache)
    assert (np.asarray(cold.indices) == np.asarray(warm.indices)).all()


def _mesh_memo_checks():
    """Shared body: a Mesh draw's placed spectrum carries the memo."""
    assert jax.device_count() >= 2, jax.device_count()
    rt = dpp.Mesh(axes={"data": 2})
    model, cache = _memo_model("kron2"), SpectralCache()
    key = jax.random.PRNGKey(6)
    first, c = _counted(lambda: model.sample(key, 8, runtime=rt,
                                             cache=cache))
    _assert_memo_miss(c)
    again, c = _counted(lambda: model.sample(key, 8, runtime=rt,
                                             cache=cache))
    _assert_memo_hit(c)
    local, c = _counted(lambda: model.sample(key, 8, cache=cache))
    _assert_memo_hit(c)                 # placed and local share one memo
    for b in (again, local):
        assert (np.asarray(first.indices) == np.asarray(b.indices)).all()


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs >= 8 devices (the CI mesh job)")
def test_mesh_sample_hits_the_memo_in_process():
    _mesh_memo_checks()


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="already covered by the in-process variant")
def test_mesh_sample_hits_the_memo_subprocess():
    env = dict(os.environ, JAX_PLATFORMS="cpu",   # never the chip
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + str(ROOT / "tests"))
    out = subprocess.run(
        [sys.executable, "-c",
         "import test_sampling_batched as t; t._mesh_memo_checks(); "
         "print('MESH_MEMO_OK')"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_MEMO_OK" in out.stdout


def _entry_model(kind):
    if kind == "kron34":
        return dpp.random_kron(jax.random.PRNGKey(5), (3, 4)).rescale(3.0)
    if kind == "kron232":
        return dpp.random_kron(jax.random.PRNGKey(8), (2, 3, 2)).rescale(3.0)
    if kind == "dense":
        return dpp.from_kernel(dpp.random_kron(
            jax.random.PRNGKey(5), (3, 5)).dense_kernel()).rescale(3.0)
    V = jax.random.normal(jax.random.PRNGKey(6), (20, 6)) * 0.6
    return dpp.LowRank(V, jnp.abs(jax.random.normal(
        jax.random.PRNGKey(7), (20,))) + 0.5)


@pytest.mark.parametrize("kind", ["kron34", "kron232", "dense", "lowrank"])
def test_batched_draw_is_keyed_draw_on_split_keys(kind):
    """``sample_krondpp_batched(key, .., n)`` is the keyed draw on
    ``split(key, n)``, row for row, for every spectrum family (the
    low-rank one through its ``sample_rows`` hook)."""
    spec = _entry_model(kind).spectrum()
    key, n, k_max = jax.random.PRNGKey(3), 11, 5
    got = sample_krondpp_batched(key, spec, k_max, n)
    want = sample_krondpp_keyed(jax.random.split(key, n), spec, k_max)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert (np.asarray(got[1]) > 0).any()          # not all-empty draws


def test_one_compile_per_shape():
    c0 = compile_cache_size()
    if c0 < 0:
        pytest.skip("jit cache introspection unavailable")
    m = random_krondpp(jax.random.PRNGKey(11), (3, 3))
    spec = SpectralCache().spectrum(m)
    sample_krondpp_batched(jax.random.PRNGKey(0), spec, 5, 7)
    c1 = compile_cache_size()
    sample_krondpp_batched(jax.random.PRNGKey(1), spec, 5, 7)   # same shape
    assert compile_cache_size() == c1
    sample_krondpp_batched(jax.random.PRNGKey(2), spec, 5, 9)   # new batch
    assert compile_cache_size() == c1 + 1


def test_service_coalesces_and_scatters():
    m = random_krondpp(jax.random.PRNGKey(0), (3, 4))
    cache = SpectralCache()
    svc = SamplingService(m, cache=cache, seed=0)
    t1, t2, t3 = svc.submit(2), svc.submit(3), svc.submit(1)
    r2 = t2.result()                      # triggers one coalesced flush
    assert svc.stats.flushes == 1 and svc.stats.device_calls == 1
    assert len(t1.result()) == 2 and len(r2) == 3 and len(t3.result()) == 1
    # deterministic under identical seed + submission pattern
    svc_b = SamplingService(m, cache=cache, seed=0)
    u1, u2, u3 = svc_b.submit(2), svc_b.submit(3), svc_b.submit(1)
    svc_b.flush()
    assert u1.result() == t1.result() and u2.result() == r2 \
        and u3.result() == t3.result()
    # second service against the same factors does no new eigh work
    assert cache.stats()["misses"] == 2


def test_service_round_up_shapes_with_non_pow2_max_batch():
    m = random_krondpp(jax.random.PRNGKey(0), (3, 4))
    svc = SamplingService(m, max_batch=1000)
    assert svc._round_up(600) == 1000          # capped, not 1024
    assert svc._round_up(3) == 4
    assert svc._round_up(1000) == 1000
    assert svc._round_up(1001) == 2000         # multiple of max_batch


@pytest.mark.parametrize("method", ["map", "sample"])
def test_kv_recency_excluded_even_without_valid_len(method):
    """valid_len=None with recency>0 used to leave the force-kept recency
    window selectable, returning duplicated positions."""
    from repro.serve import dpp_select_tokens
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.standard_normal((32, 4)), jnp.float32)
    for seed in range(5):
        picks = np.asarray(dpp_select_tokens(
            keys, 16, recency=8, method=method,
            key=jax.random.PRNGKey(seed)))
        assert len(set(picks.tolist())) == 16, picks


def test_kv_sample_mode_never_leaks_excluded_slots():
    """Exact k-DPP eviction with k beyond the valid keys' numerical rank
    used to leak recency-window / invalid positions whose soft-exclusion
    ridge eigenvalues competed in the exactly-k draw."""
    from repro.serve import dpp_select_tokens
    rng = np.random.default_rng(0)
    S, hd, valid_len, recency, budget = 32, 2, 24, 4, 12   # k_dpp=8 > hd=2
    keys = jnp.asarray(rng.standard_normal((S, hd)), jnp.float32)
    for seed in range(5):
        picks = np.asarray(dpp_select_tokens(
            keys, budget, recency=recency, valid_len=valid_len,
            method="sample", key=jax.random.PRNGKey(seed)))
        assert picks.shape == (budget,)
        assert len(set(picks.tolist())) == budget          # no duplicates
        assert (picks < valid_len).all() and (picks >= 0).all()
        # recency window always kept
        assert set(range(valid_len - recency, valid_len)) <= set(picks)


def test_service_kdpp_exact_k():
    m = random_krondpp(jax.random.PRNGKey(0), (3, 4))
    svc = SamplingService(m, seed=1)
    rows = svc.sample_kdpp(3, num_samples=5)
    assert len(rows) == 5 and all(len(set(r)) == 3 for r in rows)


# ---------------------------------------------------------------------------
# greedy MAP degenerate-rank regression (satellite fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["core", "ops"])
def test_greedy_map_rank_deficient_no_nan(impl):
    """k beyond numerical rank used to divide by a collapsed conditional
    variance, turning d into NaN and poisoning every later pick."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((8, 2)).astype(np.float32)   # rank 2, N=8
    L = jnp.asarray(X @ X.T)
    k = 6
    if impl == "core":
        from repro.core.sampling import greedy_map_kdpp
        picks = np.asarray(greedy_map_kdpp(L, k))
    else:
        from repro.kernels import ops
        picks = np.asarray(ops.greedy_map_kdpp(L, k))
    assert picks.shape == (k,)
    assert (picks >= 0).all() and (picks < 8).all()
    assert len(set(picks.tolist())) == k      # no repeated/poisoned picks
    # the first (rank) picks must match the full-rank greedy on L + ridge
    from repro.core.sampling import greedy_map_kdpp as core_greedy
    ref = np.asarray(core_greedy(L + 1e-5 * jnp.eye(8), k))
    assert (picks[:2] == ref[:2]).all()


@pytest.mark.parametrize("impl", ["core", "ops"])
def test_greedy_map_scale_equivariant(impl):
    """The degeneracy gate must be relative to kernel scale: an absolute
    cutoff silently zeroed every update for small-magnitude kernels,
    degrading picks to top-k-diagonal order."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((16, 8)).astype(np.float32)
    L = jnp.asarray(X @ X.T)
    if impl == "core":
        from repro.core.sampling import greedy_map_kdpp as fn
    else:
        from repro.kernels.ops import greedy_map_kdpp as fn
    base = np.asarray(fn(L, 5))
    for scale in (1e-10, 1e8):
        assert (np.asarray(fn(L * scale, 5)) == base).all(), scale
