"""repro.dpp.runtime — the unified execution-placement seam.

Three layers of coverage:

  * single-device: Local/Host runtimes, `from_spec`, and the deprecation
    shims (``backend=`` strings, ``fit(mesh=...)``, selector ``backend=``)
    — every shim must warn AND produce the runtime-equivalent result.
  * architecture (AST scan): no in-repo consumer outside the shim
    definitions passes ``backend="device"|"host"`` placement strings or
    references ``--distributed`` anymore.
  * mesh equivalence: under 8 (forced host) devices, ``Mesh`` sampling
    reproduces ``Local`` bit-for-bit on shared keys, fits match across
    constant/Armijo schedules (identical accepted step sizes and
    backtrack counts), the sharded stochastic sweep replays on the host
    via the documented ``fold_in(key, shard)`` chain, and
    ``SamplingService`` stats aggregate across shards. Runs in-process
    when the interpreter already has >= 8 devices (the CI ``mesh`` job);
    otherwise the same checks run in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (tier-1).
"""

import os
import pathlib
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dpp
from repro.core import SubsetBatch

ROOT = pathlib.Path(__file__).resolve().parent.parent
SRC = str(ROOT / "src")


def _model():
    return dpp.random_kron(jax.random.PRNGKey(0), (4, 5)).rescale(4.0)


# ---------------------------------------------------------------------------
# Local / Host runtimes and the deprecation shims (single device)
# ---------------------------------------------------------------------------

def test_local_runtime_is_the_default():
    m = _model()
    dflt = m.sample(jax.random.PRNGKey(1), 8)
    loc = m.sample(jax.random.PRNGKey(1), 8, runtime=dpp.Local())
    np.testing.assert_array_equal(np.asarray(dflt.indices),
                                  np.asarray(loc.indices))
    np.testing.assert_array_equal(np.asarray(dflt.mask), np.asarray(loc.mask))


def test_backend_strings_warn_and_map_onto_runtimes():
    m = _model()
    with pytest.warns(DeprecationWarning, match="backend= placement"):
        h_shim = m.sample(jax.random.PRNGKey(2), 3, backend="host")
    h_rt = m.sample(jax.random.PRNGKey(2), 3, runtime=dpp.Host())
    np.testing.assert_array_equal(np.asarray(h_shim.indices),
                                  np.asarray(h_rt.indices))
    with pytest.warns(DeprecationWarning, match="backend= placement"):
        d_shim = m.sample(jax.random.PRNGKey(3), 4, backend="device")
    d_rt = m.sample(jax.random.PRNGKey(3), 4)
    np.testing.assert_array_equal(np.asarray(d_shim.indices),
                                  np.asarray(d_rt.indices))
    with pytest.raises(ValueError, match="backend"):
        m.sample(jax.random.PRNGKey(0), 1, backend="gpu")
    with pytest.raises(ValueError, match="exactly one"):
        m.sample(jax.random.PRNGKey(0), 1, backend="device",
                 runtime=dpp.Local())


def test_fit_mesh_kwarg_warns_and_matches_runtime():
    m = _model()
    batch = m.sample(jax.random.PRNGKey(4), 16)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.warns(DeprecationWarning, match="mesh= is deprecated"):
        shim = m.fit(batch, iters=2, a=1.0, mesh=mesh)
    rt = m.fit(batch, iters=2, a=1.0,
               runtime=dpp.Mesh.from_jax_mesh(mesh))
    local = m.fit(batch, iters=2, a=1.0)
    for a, b in ((shim, rt), (shim, local)):
        np.testing.assert_allclose(np.asarray(a.model.factors[0]),
                                   np.asarray(b.model.factors[0]),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(shim.log_likelihoods, local.log_likelihoods,
                               rtol=1e-5, atol=1e-5)


def test_selector_backend_shim_warns_and_resolves():
    from repro.data.dpp_selection import DPPBatchSelector
    feats = np.random.default_rng(0).standard_normal((12, 3))
    with pytest.warns(DeprecationWarning, match="backend= placement"):
        sel = DPPBatchSelector.from_features(feats, 3, 4, backend="host")
    assert sel.runtime.kind == "host"
    assert sel.backend is None          # consumed: replace() must not re-warn
    quiet = DPPBatchSelector.from_features(feats, 3, 4)
    assert quiet.runtime.kind == "local"


def test_from_spec_and_resolution_guards():
    rt = dpp.runtime
    assert isinstance(rt.from_spec("local"), dpp.Local)
    assert isinstance(rt.from_spec("host"), dpp.Host)
    assert isinstance(rt.from_spec("mesh"), dpp.Mesh)
    assert isinstance(rt.from_spec(None), dpp.Local)
    passthrough = dpp.Host()
    assert rt.from_spec(passthrough) is passthrough
    with pytest.raises(ValueError, match="unknown runtime"):
        rt.from_spec("tpu-pod")
    assert isinstance(rt.resolve(None), dpp.Local)


def test_learning_rejects_host_runtime():
    m = _model()
    batch = m.sample(jax.random.PRNGKey(5), 8)
    with pytest.raises(ValueError, match="host"):
        m.fit(batch, iters=1, runtime=dpp.Host())


def test_service_rejects_host_runtime():
    with pytest.raises(ValueError, match="host"):
        _model().service(runtime=dpp.Host())


def test_runtime_paths_do_not_warn():
    """The runtime= spellings are the non-deprecated surface."""
    m = _model()
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        m.sample(jax.random.PRNGKey(1), 4, runtime=dpp.Local())
        m.sample(jax.random.PRNGKey(2), 2, runtime=dpp.Host())
        m.fit(m.sample(jax.random.PRNGKey(3), 8), iters=1,
              runtime=dpp.Local())
        m.service(cache=dpp.SpectralCache(), runtime=dpp.Local()).sample(2)


# ---------------------------------------------------------------------------
# architecture: placement flows through runtimes, not strings/flags
# ---------------------------------------------------------------------------

def test_no_consumer_passes_placement_strings_or_distributed():
    """The invariant lives in repro.analysis as the ``runtime-placement``
    rule (TP/TN fixtures and a parity test in test_analysis.py); here we
    pin that the real tree runs clean."""
    from repro.analysis import analyze_paths
    findings, errors, n_files = analyze_paths(
        [ROOT / "src", ROOT / "examples", ROOT / "benchmarks"],
        select=["runtime-placement"], root=ROOT)
    assert not errors, [e.render() for e in errors]
    assert not findings, [f.render() for f in findings]
    assert n_files > 60            # the rule actually scanned the tree
    # the learn.py occurrences are exactly the shim (argparse def + handler)
    learn = (ROOT / "src/repro/launch/learn.py").read_text()
    assert learn.count('"--distributed"') == 1 and "deprecated" in learn


# ---------------------------------------------------------------------------
# Mesh == Local equivalence (the CI mesh job)
# ---------------------------------------------------------------------------

def _mesh_equivalence_checks():
    """Shared body: runs wherever >= 8 devices exist (in-process in the CI
    mesh job, in a subprocess with forced host devices under tier-1)."""
    assert jax.device_count() >= 8, jax.device_count()
    from repro.core.distributed import shard_select_no_replace
    from repro.core.krk_picard import krk_picard_step

    rt = dpp.Mesh(axes={"data": 8})
    m = _model()

    # -- sampling: bit-for-bit on shared keys, divisible or not ------------
    loc = m.sample(jax.random.PRNGKey(1), 64)
    msh = m.sample(jax.random.PRNGKey(1), 64, runtime=rt)
    np.testing.assert_array_equal(np.asarray(loc.indices),
                                  np.asarray(msh.indices))
    np.testing.assert_array_equal(np.asarray(loc.mask), np.asarray(msh.mask))
    np.testing.assert_array_equal(np.asarray(loc.truncated),
                                  np.asarray(msh.truncated))
    pad_l = m.sample(jax.random.PRNGKey(2), 13)          # pads 13 -> 16
    pad_m = m.sample(jax.random.PRNGKey(2), 13, runtime=rt)
    np.testing.assert_array_equal(np.asarray(pad_l.indices),
                                  np.asarray(pad_m.indices))
    k_l = m.sample(jax.random.PRNGKey(3), 24, k=3)
    k_m = m.sample(jax.random.PRNGKey(3), 24, k=3, runtime=rt)
    np.testing.assert_array_equal(np.asarray(k_l.indices),
                                  np.asarray(k_m.indices))
    # repeat calls reuse one cached executable per static config (DPP +
    # k-DPP above) and stay exact — the Local one-compile-per-shape
    # contract holds on the mesh
    assert len(rt._mapped_cache) == 2, rt._mapped_cache.keys()
    again = m.sample(jax.random.PRNGKey(1), 64, runtime=rt)
    np.testing.assert_array_equal(np.asarray(again.indices),
                                  np.asarray(loc.indices))
    assert len(rt._mapped_cache) == 2

    # -- service: identical draws AND stats aggregated over all shards ----
    # each placement runs under its own process-wide tracker, so the obs
    # emissions (not just the ServiceStats view) must agree on shared keys
    from repro import obs
    svc_l = m.service(seed=7, cache=dpp.SpectralCache(), k_max=3)
    svc_m = m.service(seed=7, cache=dpp.SpectralCache(), k_max=3, runtime=rt)
    with obs.use(obs.InMemoryTracker()) as t_l:
        draws_l = svc_l.sample(20)
    with obs.use(obs.InMemoryTracker()) as t_m:
        draws_m = svc_m.sample(20)
    assert draws_l == draws_m
    assert svc_l.stats == svc_m.stats          # incl. truncations (k_max=3
    assert svc_m.stats.truncations > 0         # undersized on purpose)
    svc_keys = {k for k in t_l.counters if k.startswith("service.")}
    assert svc_keys == {k for k in t_m.counters
                        if k.startswith("service.")}
    for k in sorted(svc_keys):                 # per-shard pad rows sliced
        assert t_l.counters[k] == t_m.counters[k], k    # before aggregation
    assert t_m.counters.get("runtime.mesh.map_keys_calls", 0) > 0
    assert "runtime.mesh.map_keys_calls" not in t_l.counters

    # -- fit: constant schedule --------------------------------------------
    batch = m.sample(jax.random.PRNGKey(4), 32)
    init = dpp.random_kron(jax.random.PRNGKey(5), (4, 5))
    rl = init.fit(batch, iters=3, a=1.0)
    rm = init.fit(batch, iters=3, a=1.0, runtime=rt)
    np.testing.assert_allclose(np.asarray(rm.model.factors[0]),
                               np.asarray(rl.model.factors[0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(rm.model.factors[1]),
                               np.asarray(rl.model.factors[1]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(rm.log_likelihoods, rl.log_likelihoods,
                               rtol=2e-5, atol=2e-5)
    assert rm.ll_sweeps == rl.ll_sweeps

    # -- fit: Armijo — schedule parity regained on the mesh ----------------
    sched = dpp.schedules.armijo(a0=64.0, max_backtracks=12)
    al = init.fit(batch, iters=3, schedule=sched)
    am = init.fit(batch, iters=3, schedule=sched, runtime=rt)
    assert float(al.state.sched.a) == float(am.state.sched.a)
    assert int(al.state.sched.backtracks) == int(am.state.sched.backtracks)
    assert int(am.state.sched.backtracks) > 0       # a0=64 must backtrack
    np.testing.assert_allclose(am.log_likelihoods, al.log_likelihoods,
                               rtol=2e-5, atol=2e-4)
    lls = np.asarray(am.log_likelihoods)
    assert np.all(np.diff(lls) > -1e-3), lls        # Thm 3.2 ascent held
    for f in am.model.factors:
        assert np.linalg.eigvalsh(np.asarray(f)).min() > 0

    # -- fit: sharded stochastic minibatches replay on the host ------------
    rs = init.fit(batch, algorithm="krk-stochastic", iters=4,
                  minibatch_size=16, seed=2, runtime=rt)
    P_, n_local, mb_local = 8, batch.n // 8, 16 // 8
    key = jax.random.PRNGKey(2)
    L1, L2 = init.factors
    for _ in range(4):
        key, k_sel = jax.random.split(key)
        rows = []
        for s in range(P_):
            sel = np.asarray(shard_select_no_replace(
                jax.random.fold_in(k_sel, s), n_local, mb_local))
            rows.extend(s * n_local + sel)
        sub = SubsetBatch(batch.indices[np.asarray(rows)],
                          batch.mask[np.asarray(rows)])
        L1, L2 = krk_picard_step(L1, L2, sub, 1.0)
    np.testing.assert_allclose(np.asarray(rs.model.factors[0]),
                               np.asarray(L1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(rs.model.factors[1]),
                               np.asarray(L2), rtol=1e-4, atol=1e-4)

    # -- guards -------------------------------------------------------------
    odd = SubsetBatch(batch.indices[:13], batch.mask[:13])
    with pytest.raises(ValueError, match="even_batch"):
        init.fit(odd, iters=1, runtime=rt)
    assert rt.even_batch(odd).n == 8
    with pytest.raises(ValueError, match="dense"):
        init.fit(batch, iters=1, use_dense_theta=True, runtime=rt)
    with pytest.raises(ValueError, match="minibatches"):
        # Local raises from jax.random.choice; Mesh must too, not clip
        init.fit(batch, algorithm="krk-stochastic", iters=1,
                 minibatch_size=2 * batch.n, runtime=rt)
    with pytest.raises(ValueError, match="without replacement"):
        shard_select_no_replace(jax.random.PRNGKey(0), 4, 8)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs >= 8 devices (the CI mesh job)")
def test_mesh_matches_local_in_process():
    _mesh_equivalence_checks()


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="already covered by the in-process variant")
def test_mesh_matches_local_subprocess():
    """Tier-1 coverage of the 8-device equivalence suite: rerun this module
    under forced host devices (the main process must keep one device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",   # never the chip
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC + os.pathsep + str(ROOT / "tests"))
    out = subprocess.run(
        [sys.executable, "-c",
         "import test_runtime as t; t._mesh_equivalence_checks(); "
         "print('MESH_EQUIV_OK')"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_EQUIV_OK" in out.stdout
