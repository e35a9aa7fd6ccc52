"""Exact k-DPP draws: the float64 Host oracle against brute-force
enumeration, the Local device draws against the keyed float64 replay of
the benchmark (``bench/kdpp_ref.py``), the k-pass conditional draw
against the N-step serial scan it replaces, the per-(spectrum, k) memo
of its keep-probability table against the in-trace build, and the
``dpp.kdpp.esp_builds`` / ``dpp.kdpp.esp_hits`` counters.

A k-DPP gives P(Y) = det(L_Y) / e_k(λ) on |Y| = k. Below rank, where
|Y| = k has probability 0, every sampler draws exactly rank items,
P(Y) ∝ det(L_Y) on |Y| = rank.
"""

import functools
import itertools
import json
import math
import os
import pathlib
import subprocess
import sys
import threading

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dpp, obs
from repro.core.sampling import log_esp_table, sample_kdpp
from repro.kernels import ops as kernel_ops
from repro.sampling.batched import draw_slots, gather_factor_columns
from repro.sampling.kdpp import (_phase1_kdpp, _sample_kdpp_batched,
                                 sample_kdpp_batched)
from repro.sampling.kdpp import log_esp_table as log_esp_table_jax
from repro.sampling.spectral import KEEP_TABLES_MAX, log_product_spectrum

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
    """Each k-DPP call counts the table it built: a Kron spectrum builds
    once per k and counts a repeat as 0 builds and one hit; the low-rank
    dual builds its table in every call."""
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
    assert "dpp.kdpp.esp_hits" not in t.counters
    with obs.use(t):
        model.sample(jax.random.PRNGKey(8), 8, k=2)
    if kind == "kron":
        assert t.counters["dpp.kdpp.esp_builds"] == 2
        assert t.counters["dpp.kdpp.esp_hits"] == 1
    else:
        assert t.counters["dpp.kdpp.esp_builds"] == 3
        assert "dpp.kdpp.esp_hits" not in t.counters
    with obs.use(obs.InMemoryTracker()) as plain:
        model.sample(key, 8)
    assert "dpp.kdpp.esp_builds" not in plain.counters


def _builds(fn):
    """(fn(), esp_builds, esp_hits) under a fresh tracker."""
    with obs.use(obs.InMemoryTracker()) as t:
        out = fn()
    return (out, t.counters.get("dpp.kdpp.esp_builds"),
            t.counters.get("dpp.kdpp.esp_hits", 0))


def test_keep_table_is_built_once_per_spectrum_and_k():
    """Same spectrum and k: one build, then hits. Another k builds; a
    rescaled model (new factors, a new spectrum) builds again."""
    model = dpp.random_kron(jax.random.PRNGKey(40), (3, 4)).rescale(3.0)
    cache = dpp.SpectralCache()
    draw = lambda m, k, i: m.sample(jax.random.PRNGKey(i), 6, k=k,  # noqa
                                    cache=cache).indices
    first, b, h = _builds(lambda: draw(model, 3, 0))
    assert (b, h) == (1, 0)
    again, b, h = _builds(lambda: draw(model, 3, 0))
    assert (b, h) == (0, 1)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    assert _builds(lambda: draw(model, 2, 1))[1:] == (1, 0)
    assert _builds(lambda: draw(model, 3, 2))[1:] == (0, 1)
    rescaled = model.rescale(5.0)
    assert _builds(lambda: draw(rescaled, 3, 0))[1:] == (1, 0)
    assert _builds(lambda: draw(rescaled, 3, 0))[1:] == (0, 1)


def test_keep_table_memo_keeps_the_last_few_k():
    """A spectrum holds at most ``KEEP_TABLES_MAX`` tables, least recently
    used out first."""
    spec = dpp.random_kron(jax.random.PRNGKey(41), (3, 4)).spectrum(
        dpp.SpectralCache())
    ks = range(1, KEEP_TABLES_MAX + 2)
    assert [spec.kdpp_keep_table(k)[1] for k in ks] == [True] * len(ks)
    assert spec.kdpp_keep_table(ks[-1])[1] is False
    assert spec.kdpp_keep_table(ks[0])[1] is True       # evicted first
    P, k0 = spec.kdpp_keep_table(ks[-1])[0]
    assert P.shape == (12, ks[-1] + 1) and int(k0) == ks[-1]


def test_concurrent_first_callers_build_the_keep_table_once():
    spec = dpp.random_kron(jax.random.PRNGKey(42), (3, 4)).spectrum(
        dpp.SpectralCache())
    n = 16
    barrier = threading.Barrier(n)
    got = []

    def call():
        barrier.wait(timeout=30)
        got.append(spec.kdpp_keep_table(3))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=call) for _ in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert len(got) == n
    assert sum(built for _, built in got) == 1
    assert all(table[0] is got[0][0][0] for table, _ in got)


def test_kdpp_call_under_an_outer_jit_stores_no_table():
    """Under an outer ``jax.jit`` the table is built in the trace and not
    memoized: the next eager call still builds it, and both draw alike."""
    spec = dpp.random_kron(jax.random.PRNGKey(43), (3, 4)).rescale(
        3.0).spectrum(dpp.SpectralCache())
    key = jax.random.PRNGKey(44)
    traced, b, _ = _builds(lambda: jax.jit(
        lambda kk: sample_kdpp_batched(kk, spec, 3, 5))(key))
    assert b == 1
    eager, b, h = _builds(lambda: sample_kdpp_batched(key, spec, 3, 5))
    assert (b, h) == (1, 0)
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(eager))


@functools.partial(jax.jit, static_argnames=("k",))
def _in_trace_draw(keys, lams, vecs, k):
    """The draw with its table built inside the trace, as before the
    memo: ``_phase1_kdpp`` in ``draw_slots``, then phase 2."""
    sizes = tuple(v.shape[0] for v in vecs)
    ll = log_product_spectrum(lams)

    def one(kk):
        us, sel, valid, k_eff, _ = draw_slots(
            kk, lambda k1: _phase1_kdpp(k1, ll, k), k)
        return us, gather_factor_columns(vecs, sizes, sel, valid), k_eff
    us, Gs, k_eff = jax.vmap(one)(keys)
    return kernel_ops.phase2_select(us, Gs, sizes, k_eff)


@pytest.mark.parametrize("kind,k", [
    ("kron", 1), ("kron", 3), ("kron", 8), ("dense", 4), ("rank3", 5)])
def test_memoized_picks_equal_the_in_trace_draw(kind, k):
    """Picks drawn with the memoized table (the building call and a hit)
    equal the in-trace build's, bit for bit, on fixed keys."""
    spec = _model(kind).spectrum(dpp.SpectralCache())
    key = jax.random.PRNGKey(45)
    want = np.asarray(_in_trace_draw(jax.random.split(key, 48),
                                     tuple(spec.lams), tuple(spec.vecs), k))
    (built, b, _), (hit, _, h) = (
        _builds(lambda: sample_kdpp_batched(key, spec, k, 48))
        for _ in range(2))
    assert (b, h) == (1, 1)
    np.testing.assert_array_equal(np.asarray(built), want)
    np.testing.assert_array_equal(np.asarray(hit), want)


def _phase1_serial_scan(key, log_lam, k):
    """The N-step serial scan that ``_phase1_kdpp`` replaces, kept as an
    oracle: from the last eigenvalue to the first, item n is kept iff its
    uniform falls under its keep probability at the count still to keep."""
    N = log_lam.shape[0]
    table = log_esp_table_jax(log_lam, k)
    k0 = jnp.minimum(jnp.asarray(k, jnp.int32),
                     jnp.sum(jnp.isfinite(log_lam)).astype(jnp.int32))
    u = jax.random.uniform(key, (N,))

    def body(k_rem, inp):
        n, ll, un = inp                       # n runs N..1
        log_num = ll + table[n - 1, jnp.maximum(k_rem - 1, 0)]
        log_den = table[n, k_rem]
        p = jnp.exp(jnp.minimum(log_num - log_den, 0.0))
        p = jnp.where((k_rem > 0) & jnp.isfinite(log_den), p, 0.0)
        inc = un < p
        return k_rem - inc.astype(k_rem.dtype), inc

    ns = jnp.arange(N, 0, -1)
    _, incs = jax.lax.scan(body, k0, (ns, log_lam[::-1], u))
    return incs[::-1]


def _log_spectrum(kind: str) -> jax.Array:
    if kind == "kron_1e4":          # the slate cell's kernel, N = 100 x 100
        factors = data.kron_factors(jax.random.PRNGKey(31), (100, 100), 10.0)
        return log_product_spectrum(tuple(dpp.Kron(factors).spectrum().lams))
    if kind == "kron_zeros":        # N = 5 x 4 of rank 3 x 3 = 9
        return log_product_spectrum((jnp.array([0.0, 0.5, 2.0, 0.0, 1.3]),
                                     jnp.array([0.7, 0.0, 1.1, 3.0])))
    if kind == "kron_small":        # N = 6 x 5 of full rank
        return log_product_spectrum(tuple(
            dpp.random_kron(jax.random.PRNGKey(32), (6, 5)).spectrum().lams))
    # a LowRank dual spectrum: r = 4 dual eigenvalues of a 12-item kernel
    V = jax.random.normal(jax.random.PRNGKey(33), (12, 4))
    return dpp.LowRank(V, jnp.ones((12,))).spectrum().log_eigenvalues()


@pytest.mark.parametrize("kind,k", [
    ("kron_1e4", 8),                # many keys at the cell's size
    ("kron_zeros", 12),             # rank 9 < k
    ("kron_zeros", 1),
    ("kron_zeros", 9),              # k = rank, with zero eigenvalues
    ("kron_small", 1),
    ("kron_small", 30),             # k = rank = N
    ("lowrank_dual", 2),
    ("lowrank_dual", 4),            # k = r
])
def test_phase1_kdpp_matches_the_serial_scan(kind, k):
    """The k-pass draw keeps exactly the items of the serial scan, bit for
    bit, on 64 keys vmapped as the batch path draws them."""
    ll = _log_spectrum(kind)
    keys = jax.random.split(jax.random.PRNGKey(34), 64)
    new = np.asarray(jax.jit(jax.vmap(
        lambda kk: _phase1_kdpp(kk, ll, k)))(keys))
    old = np.asarray(jax.jit(jax.vmap(
        lambda kk: _phase1_serial_scan(kk, ll, k)))(keys))
    rank = int(np.isfinite(np.asarray(ll)).sum())
    assert (old.sum(axis=1) == min(k, rank)).all()
    np.testing.assert_array_equal(new, old)


def _loop_lengths(jaxpr) -> list:
    """Trip counts of every loop in a jaxpr and its sub-jaxprs (None for a
    ``while``, whose count is not static)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            out.append(eqn.params.get("length"))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _loop_lengths(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _loop_lengths(sub)
    return out


def test_phase1_kdpp_has_one_n_step_loop():
    """At N = 10^4, k = 8 the only N-trip loop is the ESP table's; the
    conditional draw is k trips, and no loop has an unknown count."""
    N, k = 10_000, 8
    jaxpr = jax.make_jaxpr(lambda key, ll: _phase1_kdpp(key, ll, k))(
        jax.random.PRNGKey(0), jnp.zeros((N,)))
    lengths = _loop_lengths(jaxpr.jaxpr)
    assert lengths.count(N) == 1, lengths
    assert None not in lengths and max(n for n in lengths if n != N) <= k


def test_kdpp_draw_with_a_supplied_table_has_no_n_step_loop():
    """With ``(P, k0)`` supplied, the jitted draw at N = 10^4, k = 8 holds
    the k-trip conditional draw, the compaction's binary search and the
    phase-2 loop (at most k steps): no loop of N trips."""
    N, k = 10_000, 8
    jaxpr = jax.make_jaxpr(
        lambda keys, v1, v2, P, k0: _sample_kdpp_batched(
            keys, (v1, v2), P, k0, k))(
        jax.random.split(jax.random.PRNGKey(0), 64), jnp.zeros((100, 100)),
        jnp.zeros((100, 100)), jnp.zeros((N, k + 1)), jnp.int32(k))
    lengths = _loop_lengths(jaxpr.jaxpr)
    assert k in lengths, lengths
    assert max(n for n in lengths if n is not None) <= math.ceil(
        math.log2(N)), lengths


def _mesh_keep_table_checks():
    """Shared body: the Mesh-placed copies made on every call share the
    spectrum's memo. Each placement builds its table once, where its
    arrays live, whichever draws first, and draws what ``Local`` draws."""
    assert jax.device_count() >= 2, jax.device_count()
    rt = dpp.Mesh(axes={"data": 2})
    model = dpp.random_kron(jax.random.PRNGKey(46), (3, 4)).rescale(3.0)
    cache = dpp.SpectralCache()
    key = jax.random.PRNGKey(47)
    draw = lambda k, runtime: model.sample(  # noqa: E731
        key, 13, k=k, cache=cache, runtime=runtime).indices
    for k, order in ((3, (rt, dpp.Local())), (2, (dpp.Local(), rt))):
        rows = []
        for runtime in order:
            for want in ((1, 0), (0, 1)):         # a build, then a hit
                got, b, h = _builds(lambda: draw(k, runtime))
                assert (b, h) == want, (k, runtime, b, h)
                rows.append(np.asarray(got))
            if not runtime.is_mesh:               # stays on one device
                assert len(got.sharding.device_set) == 1, got.sharding
        for r in rows[1:]:
            np.testing.assert_array_equal(rows[0], r)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (the CI mesh job)")
def test_mesh_copy_shares_the_keep_table_in_process():
    _mesh_keep_table_checks()


@pytest.mark.skipif(jax.device_count() >= 2,
                    reason="already covered by the in-process variant")
def test_mesh_copy_shares_the_keep_table_subprocess():
    env = dict(os.environ, JAX_PLATFORMS="cpu",   # never the chip
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + str(ROOT / "tests") + os.pathsep + str(ROOT))
    out = subprocess.run(
        [sys.executable, "-c",
         "import test_kdpp as t; t._mesh_keep_table_checks(); "
         "print('MESH_KEEP_TABLE_OK')"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_KEEP_TABLE_OK" in out.stdout
