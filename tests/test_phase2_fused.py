"""Fused Pallas phase-2 selection kernel (kernels.phase2_select) vs the
jax while_loop reference, plus the degenerate-spectrum and truncation
correctness fixes that ride along.

The fused kernel and the reference canonicalize the factored columns to
the same (G1, Gr) pair and run bit-identical arithmetic, so the contract
is *draw-for-draw equality on shared PRNG keys* — asserted exactly, not
statistically, across factor counts, tilings and batch shapes.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KronDPP, random_krondpp
from repro.kernels import ops
from repro.sampling import SpectralCache
from repro.sampling.batched import (_phase1_one, gather_factor_columns,
                                    picks_to_lists, sample_krondpp_batched)
from repro.sampling.kdpp import sample_kdpp_batched

pallas = pytest.mark.pallas


def _assert_rows_distinct(picks):
    for row in np.asarray(picks):
        real = row[row >= 0].tolist()
        assert len(set(real)) == len(real), row


# ---------------------------------------------------------------------------
# draw-for-draw equality: fused kernel vs while_loop reference
# ---------------------------------------------------------------------------

@pallas
@pytest.mark.parametrize("sizes", [(12,), (3, 4), (6, 5), (2, 3, 2)])
@pytest.mark.parametrize("seed", [0, 1])
def test_fused_matches_reference_draw_for_draw(sizes, seed):
    """Property: identical picks on shared keys for m = 1, 2, 3 kernels
    across a batch (the acceptance contract for the fused path)."""
    m = random_krondpp(jax.random.PRNGKey(seed), sizes)
    spec = SpectralCache().spectrum(m)
    k_max = spec.suggested_k_max()
    key = jax.random.PRNGKey(100 + seed)
    p_ref, c_ref, t_ref = sample_krondpp_batched(
        key, spec, k_max, 16, backend="reference")
    p_pal, c_pal, t_pal = sample_krondpp_batched(
        key, spec, k_max, 16, backend="pallas")
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_ref))
    np.testing.assert_array_equal(np.asarray(c_pal), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(t_pal), np.asarray(t_ref))
    _assert_rows_distinct(p_pal)


@pallas
@pytest.mark.parametrize("sizes", [(16, 4), (13, 5), (8, 128), (9, 130),
                                   (3, 600)])
def test_fused_matches_reference_tiled_and_padded(sizes):
    """Padding the factor pair to the (8, 128) tiling — aligned, ragged,
    and more than one lane tile of trailing items (five tiles: a ragged
    tile fold) — must not change a single pick."""
    m = random_krondpp(jax.random.PRNGKey(7), sizes)
    spec = SpectralCache().spectrum(m)
    lams, vecs = tuple(spec.lams), tuple(spec.vecs)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    us, Gs, k_eff, _ = jax.vmap(
        lambda k: _phase1_one(k, lams, vecs, 8))(keys)
    p_ref = ops.phase2_select(us, Gs, sizes, k_eff, backend="reference")
    p_pal = ops.phase2_select(us, Gs, sizes, k_eff, backend="pallas")
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_ref))
    _assert_rows_distinct(p_pal)


@pallas
def test_fused_kdpp_matches_reference():
    m = random_krondpp(jax.random.PRNGKey(3), (3, 4))
    spec = SpectralCache().spectrum(m)
    key = jax.random.PRNGKey(9)
    p_ref = sample_kdpp_batched(key, spec, 3, 12, backend="reference")
    p_pal = sample_kdpp_batched(key, spec, 3, 12, backend="pallas")
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_ref))
    assert all(len(set(r)) == 3 for r in picks_to_lists(p_pal))


@pallas
def test_fused_unbatched_entry_matches_batched_row():
    """ops.phase2_select accepts a single sample ((k_max,) uniforms) and
    must agree with the same sample run through the batched entry."""
    m = random_krondpp(jax.random.PRNGKey(4), (4, 3))
    spec = SpectralCache().spectrum(m)
    lams, vecs = tuple(spec.lams), tuple(spec.vecs)
    us, Gs, k_eff, _ = _phase1_one(jax.random.PRNGKey(11), lams, vecs, 6)
    one = ops.phase2_select(us, Gs, (4, 3), k_eff, backend="pallas")
    ref = ops.phase2_select(us, Gs, (4, 3), k_eff, backend="reference")
    assert one.shape == (6,)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(ref))


# ---------------------------------------------------------------------------
# item indexing: exact beyond 2**24 items, refused where it cannot be
# ---------------------------------------------------------------------------

def test_reference_picks_exact_beyond_2_pow_24_items():
    """N = 8 x (2**21 + 128) > 2**24 items: a float32 item position
    rounds odd indices here. Two one-hot columns put all the mass on two
    odd items above 2**24; the projection DPP must pick exactly both."""
    N1, Nr = 8, 2 ** 21 + 128
    cols = (Nr - 1, 2 ** 24 + 1 - 7 * Nr)        # both items in row 7
    G1 = jnp.zeros((N1, 2), jnp.float32).at[7, :].set(1.0)
    Gr = jnp.zeros((Nr, 2), jnp.float32).at[cols, (0, 1)].set(1.0)
    want = sorted(7 * Nr + c for c in cols)
    assert want[0] > 2 ** 24 and all(i % 2 for i in want)
    for us in ([0.3, 0.7], [0.9, 0.1]):
        picks = ops.phase2_select(jnp.asarray(us), (G1, Gr), (N1, Nr),
                                  jnp.asarray(2, jnp.int32),
                                  backend="reference")
        assert sorted(np.asarray(picks).tolist()) == want


@pytest.mark.parametrize("backend", [None, "reference", "pallas"])
def test_phase2_select_refuses_unindexable_grid(backend):
    """A trailing fold longer than 2**24 items cannot be indexed exactly
    in float32: every engine raises instead of returning a neighbour."""
    k = 2
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((k,), (2, k), (2 ** 24 + 1, k))]
    with pytest.raises(ValueError, match="outside that range"):
        jax.eval_shape(
            lambda us, g1, g2: ops.phase2_select(
                us, (g1, g2), (2, 2 ** 24 + 1), jnp.asarray(k, jnp.int32),
                backend=backend), *shapes)


# ---------------------------------------------------------------------------
# degenerate spectra: residual-mass collapse must not emit duplicates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", pytest.param(
    "pallas", marks=pallas)])
def test_degenerate_columns_early_exit_no_duplicates(backend):
    """k_eff beyond the columns' numerical span (here: a duplicated
    eigen-index, the gathered-column picture of a rank-deficient factor)
    used to keep drawing off an all-zero cumsum — clamp-picking item N-1
    every remaining step (duplicates) or "selecting" extra items from
    roundoff noise (impossible subsets for a projection DPP). The loop
    must stop at the span and pad with -1."""
    m = random_krondpp(jax.random.PRNGKey(8), (3, 4))
    spec = SpectralCache().spectrum(m)
    sel = jnp.asarray([2, 5, 5, 7], jnp.int32)          # span is 3, not 4
    valid = jnp.asarray([True, True, True, True])
    Gs = gather_factor_columns(spec.vecs, (3, 4), sel, valid)
    for seed in range(6):
        us = jax.random.uniform(jax.random.PRNGKey(seed), (4,))
        picks = np.asarray(ops.phase2_select(us, Gs, (3, 4),
                                             jnp.asarray(4, jnp.int32),
                                             backend=backend))
        real = picks[picks >= 0]
        assert len(real) <= 3, picks                    # span exhausted
        assert len(set(real.tolist())) == len(real), picks
        assert (picks[len(real):] == -1).all(), picks   # -1 tail


@pytest.mark.parametrize("backend", ["reference", pytest.param(
    "pallas", marks=pallas)])
def test_rank_deficient_kron_factor_no_duplicates(backend):
    """Issue regression: numerically rank-deficient Kron factors must
    never yield a subset with repeated indices, on either backend."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((6, 2)).astype(np.float32)
    L1 = jnp.asarray(X @ X.T) * 10.0                    # rank 2 of 6
    L2 = 5.0 * jnp.eye(4, dtype=jnp.float32)
    spec = SpectralCache().spectrum(KronDPP((L1, L2)))
    for seed in range(4):
        picks, counts, _ = sample_krondpp_batched(
            jax.random.PRNGKey(seed), spec, 12, 32, backend=backend)
        _assert_rows_distinct(picks)
        # rank(L1 ⊗ L2) = 2 * 4: no subset can exceed it
        assert int(np.asarray(counts).max()) <= 8


def test_kdpp_below_rank_pads_with_minus_one():
    """sample_kdpp_batched promises exactly k distinct items when
    rank >= k; below rank (a zero-probability conditioning event — the
    unclamped ESP draw degenerated to fully empty rows) the draw must
    degrade to exactly rank distinct items with trailing -1 padding."""
    L1 = jnp.diag(jnp.asarray([2.0, 1.0, 0.0, 0.0]))    # exact rank 2
    L2 = jnp.asarray(np.diag([3.0, 1.5, 0.5]).astype(np.float32))
    spec = SpectralCache().spectrum(KronDPP((L1, L2)))  # rank 6 of 12
    picks = np.asarray(sample_kdpp_batched(jax.random.PRNGKey(0), spec,
                                           8, 32))      # k=8 > rank=6
    assert picks.shape == (32, 8)
    for row in picks:
        real = row[row >= 0]
        assert len(real) == 6                           # rank items, not 0
        assert len(set(real.tolist())) == len(real)
        assert (row[len(real):] == -1).all()            # trailing pad
    # at k == rank the promise holds exactly: k distinct items per row
    picks = np.asarray(sample_kdpp_batched(jax.random.PRNGKey(1), spec,
                                           6, 16))
    assert (picks >= 0).all()
    _assert_rows_distinct(picks)
    assert all(len(set(r.tolist())) == 6 for r in picks)


# ---------------------------------------------------------------------------
# k_max truncation must be observable end to end
# ---------------------------------------------------------------------------

def test_truncation_flag_propagates_to_service_and_facade():
    from repro.dpp import Kron
    from repro.sampling import SamplingService
    big = KronDPP((5.0 * jnp.eye(3), 5.0 * jnp.eye(3)))   # E|Y| ~ 8.7
    spec = SpectralCache().spectrum(big)
    # engine level: the forced-tiny budget flags every draw
    picks, counts, truncated = sample_krondpp_batched(
        jax.random.PRNGKey(0), spec, 2, 8)
    assert np.asarray(truncated).all()
    assert (np.asarray(counts) == 2).all()
    # an adequate budget flags none
    _, _, truncated = sample_krondpp_batched(jax.random.PRNGKey(0), spec,
                                             spec.N, 8)
    assert not np.asarray(truncated).any()
    # service stats count clipped draws
    svc = SamplingService(big, k_max=2, seed=0)
    svc.sample(5)
    assert svc.stats.truncations == svc.stats.samples_drawn > 0
    # facade SubsetBatch carries the provenance
    batch = Kron(big.factors).sample(jax.random.PRNGKey(1), 6, k_max=2)
    assert batch.truncated is not None
    assert batch.truncation_count() == 6
    full = Kron(big.factors).sample(jax.random.PRNGKey(1), 6)
    assert full.truncation_count() == 0
    # batches without sampler provenance stay at 0 (observed data)
    from repro.core import SubsetBatch
    assert SubsetBatch.from_lists([[0, 1]]).truncation_count() == 0


# ---------------------------------------------------------------------------
# rescale target validation (bisection must not silently saturate)
# ---------------------------------------------------------------------------

def test_gain_for_expected_size_rejects_unachievable_targets():
    from repro.sampling.spectral import (gain_for_expected_size,
                                         rescale_expected_size)
    log_lams = jnp.log(jnp.asarray([4.0, 2.0, 1.0, 0.5]))
    for bad in (0.0, -1.0, 4.0, 7.5, float("nan")):
        with pytest.raises(ValueError, match="not achievable"):
            gain_for_expected_size(log_lams, bad)
    g = gain_for_expected_size(log_lams, 2.0)           # interior target OK
    assert np.isfinite(g) and g > 0
    # zero eigenvalues shrink the achievable range to (0, rank)
    rank_def = jnp.log(jnp.asarray([4.0, 2.0, 0.0, 0.0]))
    with pytest.raises(ValueError, match="not achievable"):
        gain_for_expected_size(rank_def, 2.0)           # rank = 2 < N = 4
    # both public entry points surface the error
    dpp = random_krondpp(jax.random.PRNGKey(0), (3, 4))
    with pytest.raises(ValueError, match="not achievable"):
        rescale_expected_size(dpp, 12.0)                # target == N
    from repro.dpp import Kron
    with pytest.raises(ValueError, match="not achievable"):
        Kron(dpp.factors).rescale(0.0)
    ok = Kron(dpp.factors).rescale(5.0)                 # interior still works
    assert abs(ok.expected_size() - 5.0) < 1e-3


@pallas
def test_statistical_exactness_survives_on_fused_path():
    """The fused kernel is the sampler on TPU — its draws must satisfy the
    same closed-form marginals the reference is validated against."""
    from repro.core.dpp import marginal_kernel
    m = random_krondpp(jax.random.PRNGKey(5), (2, 3))
    K = np.asarray(marginal_kernel(np.asarray(m.full_matrix())))
    spec = SpectralCache().spectrum(m)
    picks, _, _ = sample_krondpp_batched(jax.random.PRNGKey(0), spec,
                                         num_samples=3000, backend="pallas")
    mem = np.zeros((3000, 6))
    for b, row in enumerate(np.asarray(picks)):
        mem[b, row[row >= 0]] = 1.0
    np.testing.assert_allclose(mem.mean(0), np.diag(K), atol=0.05)
