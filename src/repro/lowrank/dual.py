"""Dual spectrum of a low-rank kernel L = φφᵀ, φ = V·√q.

The r×r dual Gram C = φᵀφ shares its nonzero eigenvalues with the N×N
kernel L (Kulesza & Taskar §3.3, as implemented in DPPy): if (d, w) is
an eigenpair of C with d > 0 then u = φw/√d is a unit eigenvector of L
with the same eigenvalue, det(I_N + L) = det(I_r + C), and the marginal
kernel is K = φ (C + I)⁻¹ φᵀ. ``DualSpectrum`` packages that
factorization with the same size/budget protocol as ``FactorSpectrum``
so the facade, ``SamplingService`` and the serving tier consume it
unchanged, plus ``sample_rows``/``sample_rows_kdpp`` hooks the batched
samplers dispatch through (duck-typed, so ``repro.sampling`` never
imports this package).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .. import obs


@dataclasses.dataclass(frozen=True)
class DualSpectrum:
    """Eigendecomposition of the rank-r dual Gram C = Vᵀ diag(q) V.

    phi:  (N, r) feature rows φ = V·√q (so L = φφᵀ).
    lams: (r,) dual eigenvalues, clipped to >= 0, ascending. These ARE
          the nonzero eigenvalues of L — everything the N-dimensional
          spectrum feeds (phase 1, expected size, rescale gains) reads
          them directly.
    W:    (r, r) orthonormal dual eigenvectors (columns).
    """
    phi: jax.Array
    lams: jax.Array
    W: jax.Array

    @property
    def N(self) -> int:
        return int(self.phi.shape[0])

    @property
    def rank(self) -> int:
        return int(self.phi.shape[1])

    def log_eigenvalues(self) -> jax.Array:
        """log of the r dual eigenvalues (-inf for zeros). The kernel's
        remaining N - r eigenvalues are exactly zero and contribute
        nothing to inclusion probabilities, sizes, or gains — consumers
        like ``gain_for_expected_size`` count rank as the number of
        finite entries, which is precisely the dual rank."""
        return jnp.log(self.lams)

    def basis(self) -> jax.Array:
        """E = W·diag(d^{-1/2}) (r, r): column j maps the dual
        eigenvector w_j to the coefficient vector of L's eigenvector
        u_j = φ E[:, j]. Zero-eigenvalue columns are zeroed — phase 1
        selects them with probability 0, so the guard only suppresses
        inf·0 NaNs."""
        inv = jnp.where(self.lams > 0.0, self.lams, 1.0) ** -0.5
        return self.W * jnp.where(self.lams > 0.0, inv, 0.0)[None, :]

    def expected_size(self) -> float:
        """E|Y| = Σ d/(1+d) = Σ σ(log d) over the r dual eigenvalues. One
        device-to-host sync, counted as ``dpp.host_syncs``."""
        obs.current_tracker().counter("dpp.host_syncs")
        return float(jnp.sum(jax.nn.sigmoid(self.log_eigenvalues())))

    def size_std(self) -> float:
        obs.current_tracker().counter("dpp.host_syncs")
        ll = self.log_eigenvalues()
        p = jax.nn.sigmoid(ll)
        return float(jnp.sqrt(jnp.sum(p * jax.nn.sigmoid(-ll))))

    def suggested_k_max(self, num_std: float = 6.0) -> int:
        """Static phase-2 budget: E|Y| + num_std·σ, clamped to [1, rank]
        (a low-rank draw can never exceed r items)."""
        k = math.ceil(self.expected_size() + num_std * self.size_std()) + 1
        return max(1, min(k, self.rank))

    # -- sampler dispatch hooks --------------------------------------------
    # ``sample_krondpp_keyed`` / ``sample_kdpp_batched`` (the two draw
    # dispatch points) call these when present instead of assembling
    # N-dimensional eigenvectors.
    def sample_rows(self, row_keys: jax.Array, k_max: int, backend=None,
                    runtime=None):
        from .sample import sample_dual_keyed
        return sample_dual_keyed(row_keys, self, int(k_max),
                                 backend=backend, runtime=runtime)

    def sample_rows_kdpp(self, row_keys: jax.Array, k: int, backend=None,
                         runtime=None):
        from .sample import sample_dual_kdpp_keyed
        return sample_dual_kdpp_keyed(row_keys, self, int(k),
                                      backend=backend, runtime=runtime)


def dual_spectrum(V: jax.Array, q: jax.Array, cache) -> DualSpectrum:
    """DualSpectrum for L = V diag(q) Vᵀ through a ``SpectralCache`` —
    r×r eigh on miss, O(1) on hit. Keyed on ``(id(V), id(q))``, so a
    q-only update (the per-tenant serving path) is one fresh r×r miss
    and zero N×N work."""
    phi, lams, W = cache.spectrum_lowrank(V, q)
    return DualSpectrum(phi, lams, W)
