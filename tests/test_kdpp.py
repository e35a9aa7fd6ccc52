"""Exact k-DPP draws: the float64 Host oracle against brute-force
enumeration, the Local device draws against the keyed float64 replay of
the benchmark (``bench/kdpp_ref.py``), and the ``dpp.kdpp.esp_builds``
counter.

A k-DPP gives P(Y) = det(L_Y) / e_k(λ) on |Y| = k. Below rank, where
|Y| = k has probability 0, every sampler draws exactly rank items,
P(Y) ∝ det(L_Y) on |Y| = rank.
"""

import itertools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dpp, obs
from repro.core.sampling import log_esp_table, sample_kdpp
from repro.sampling.kdpp import _phase1_kdpp
from repro.sampling.spectral import log_product_spectrum

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))          # `import bench.*`

from bench import data, draw_ref, kdpp_ref                 # noqa: E402

KDPP_LIMITS = json.loads(
    (ROOT / "bench" / "configs" / "kron-kdpp-1e4.json").read_text())["limits"]


def _kdpp_probs(L: np.ndarray, k: int):
    """{Y: det(L_Y) / sum} over |Y| = min(k, rank), by enumeration; the
    rank at the float32 precision the kernel is given in."""
    lam = np.linalg.eigvalsh(L)
    rank = int(np.sum(lam > L.shape[0] * np.finfo(np.float32).eps
                      * np.abs(lam).max()))
    size = min(k, rank)
    dets = {Y: np.linalg.det(L[np.ix_(Y, Y)])
            for Y in itertools.combinations(range(L.shape[0]), size)}
    total = sum(dets.values())
    return {Y: d / total for Y, d in dets.items()}


def _model(kind: str):
    if kind == "kron":                               # m = 2, N = 6
        return dpp.random_kron(jax.random.PRNGKey(5), (2, 3))
    if kind == "dense":                              # m = 1, N = 6
        return dpp.from_kernel(
            dpp.random_kron(jax.random.PRNGKey(5), (2, 3)).dense_kernel())
    # m = 1, N = 6 of rank 3
    B = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (6, 3)))
    return dpp.from_kernel(jnp.asarray(B @ B.T, jnp.float32))


@pytest.mark.parametrize("kind,k", [
    ("dense", 2), ("dense", 4), ("kron", 2), ("kron", 3),
    ("rank3", 2), ("rank3", 3), ("rank3", 5)])
def test_host_kdpp_matches_bruteforce(kind, k):
    model = _model(kind)
    L = np.asarray(model.dense_kernel(), np.float64)
    probs = _kdpp_probs(L, k)
    n = 3000
    batch = model.sample(jax.random.PRNGKey(11), n, k=k, runtime=dpp.Host())
    rows = batch.to_lists()
    size = len(next(iter(probs)))
    assert all(len(r) == len(set(r)) == size for r in rows)
    counts = {}
    for r in rows:
        Y = tuple(sorted(int(i) for i in r))
        counts[Y] = counts.get(Y, 0) + 1
    assert set(counts) <= set(probs)
    for Y, p in probs.items():
        f = counts.get(Y, 0) / n
        assert abs(f - p) < 5 * np.sqrt(p * (1 - p) / n) + 1e-3, (Y, f, p)


def test_host_log_esp_table_matches_enumeration():
    lam = np.array([0.3, 2.0, 0.0, 1.5, 0.7])
    with np.errstate(divide="ignore"):
        T = log_esp_table(np.log(lam), 3)
    for n in range(len(lam) + 1):
        for j in range(4):
            e = sum(np.prod(lam[list(c)])
                    for c in itertools.combinations(range(n), j))
            with np.errstate(divide="ignore"):
                assert np.isclose(np.exp(T[n, j]), e) or (
                    e == 0 and T[n, j] == -np.inf)


def test_host_kdpp_draws_from_numpy_rng():
    """The oracle is numpy's: the same generator state gives the same
    draws."""
    model = _model("kron")
    factors = [np.asarray(f) for f in model.factors]
    a = sample_kdpp(np.random.default_rng(3), factors, 3, 20)
    b = sample_kdpp(np.random.default_rng(3), factors, 3, 20)
    assert a == b and all(len(set(r)) == 3 for r in a)
    host = model.sample(jax.random.PRNGKey(4), 20, k=3, runtime=dpp.Host())
    assert host.indices.shape == (20, 3) and bool(host.mask.all())


def test_local_kdpp_matches_the_keyed_float64_replay():
    """64 rows of 6 x 5 factors at k = 3: the program's phase 1 keeps the
    replay's eigen-indices row for row, and its picks follow the float64
    chain rule within the configuration's phase-2 limit."""
    k, n = 3, 64
    factors = data.kron_factors(jax.random.PRNGKey(21), (6, 5), 3.0)
    model = dpp.Kron(factors)
    key = jax.random.PRNGKey(22)
    rows = model.sample(key, n, k=k).to_lists()
    row_keys = jax.random.split(key, n)

    checker = kdpp_ref.KdppChecker(draw_ref.factor_spectra(factors), k)
    u, us = checker.uniforms(row_keys)
    ll = log_product_spectrum(tuple(model.spectrum().lams))
    masks = np.asarray(jax.vmap(
        lambda rk: _phase1_kdpp(jax.random.split(rk)[0], ll, k))(row_keys))
    gap1 = gap2 = 0.0
    for b in range(n):
        J, _ = checker.draw(u[b])
        assert np.flatnonzero(masks[b]).tolist() == J.tolist()
        assert not checker.wrong_size(rows[b])
        g1, g2 = checker.check_row(u[b], us[b], rows[b])
        gap1, gap2 = max(gap1, g1), max(gap2, g2)
    assert gap1 == 0.0
    assert gap2 <= KDPP_LIMITS["phase2_gap"]


@pytest.mark.parametrize("kind", ["kron", "lowrank"])
def test_esp_builds_count_one_per_kdpp_call(kind):
    if kind == "kron":
        model = dpp.random_kron(jax.random.PRNGKey(3), (2, 3, 4)).rescale(3.0)
    else:
        V = jax.random.normal(jax.random.PRNGKey(6), (12, 4))
        model = dpp.LowRank(V, jnp.ones((12,)))
    key = jax.random.PRNGKey(7)
    with obs.use(obs.InMemoryTracker()) as t:
        model.sample(key, 8, k=2)
        model.sample(key, 4, k=3)
    assert t.counters["dpp.kdpp.esp_builds"] == 2
    with obs.use(obs.InMemoryTracker()) as plain:
        model.sample(key, 8)
    assert "dpp.kdpp.esp_builds" not in plain.counters
