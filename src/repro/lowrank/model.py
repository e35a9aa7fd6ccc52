"""``LowRank(V, q)`` — the third facade model, entirely dual-space.

L = V diag(q) Vᵀ with a shared (N, r) diversity basis V and per-item
quality scores q >= 0. Every facade operation runs on the rank-r dual
factorization (``dual.DualSpectrum``): r×r eigh + O(Nr) projections —
the N×N kernel exists only behind the ``MAX_DENSE_N`` guard
(``dense_kernel``, the Host-runtime oracle). The SpectralCache keys the
dual on ``(id(V), id(q))``, so the per-tenant serving pattern — one
shared V, per-tenant q — costs one r×r eigh per tenant and zero N×N
work, ever.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dpp import SubsetBatch
# lowrank is a peer subsystem of the facade internals, not a consumer
from ..dpp.model import DPPModel, MAX_DENSE_N, _as_index_set
from ..dpp import runtime as runtime_mod
from ..sampling.spectral import (SpectralCache, default_cache,
                                 gain_for_expected_size)
from .dual import DualSpectrum, dual_spectrum


@jax.tree_util.register_pytree_node_class
class LowRank(DPPModel):
    """Low-rank L-ensemble L = V diag(q) Vᵀ behind the facade protocol.

    V: (N, r) diversity basis rows (any real matrix, r <= N for a
       nondegenerate model).
    q: (N,) nonnegative per-item quality scores; defaults to ones.

    The kernel's rank is at most r, so draws never exceed r items and
    ``rescale`` targets must lie in (0, rank). Not a dataclass for the
    same reason as ``Kron``: constructor arguments are normalized.
    """

    _default_algorithm = "lowrank"

    def __init__(self, V, q=None):
        V = jnp.asarray(V)
        if V.ndim != 2:
            raise ValueError(f"V must be (N, r), got shape {V.shape}")
        if q is None:
            q = jnp.ones((V.shape[0],), V.dtype)
        else:
            q = jnp.asarray(q, V.dtype)
            if q.shape != (V.shape[0],):
                raise ValueError(
                    f"q must be ({V.shape[0]},) to match V's rows, got "
                    f"shape {q.shape}")
        self._V = V
        self._q = q

    def __repr__(self):
        return f"LowRank(N={self.N}, rank={self.rank})"

    # -- structure ----------------------------------------------------------
    @property
    def V(self) -> jax.Array:
        return self._V

    @property
    def q(self) -> jax.Array:
        return self._q

    @property
    def rank(self) -> int:
        return int(self._V.shape[1])

    @property
    def factors(self) -> Tuple[jax.Array, ...]:
        raise TypeError(
            "LowRank has no N x N factor representation; use .V/.q, the "
            "dual spectrum(), or dense_kernel() under the max_dense guard")

    @property
    def m(self) -> int:
        # one spectral-cache lookup per model, like Dense
        return 1

    @property
    def sizes(self) -> Tuple[int, ...]:
        return (self.N,)

    @property
    def N(self) -> int:
        return int(self._V.shape[0])

    def _phi(self) -> jax.Array:
        """φ = V·√q (N, r), so L = φφᵀ."""
        return self._V * jnp.sqrt(jnp.maximum(self._q, 0.0))[:, None]

    def dense_kernel(self, max_dense: int = MAX_DENSE_N) -> jax.Array:
        """The full N x N kernel φφᵀ — O(N²) memory, guarded. Only the
        Host oracle and small-N parity tests come through here; every
        production path stays O(Nr)."""
        if self.N > max_dense:
            raise ValueError(
                f"materializing the full kernel needs N <= max_dense "
                f"({self.N} > {max_dense}); pass max_dense= explicitly to "
                f"opt into O(N^2) memory")
        phi = self._phi()
        return phi @ phi.T

    # -- spectrum -----------------------------------------------------------
    def spectrum(self, cache: Optional[SpectralCache] = None,
                 runtime: Optional[runtime_mod.Runtime] = None
                 ) -> DualSpectrum:
        """The rank-r dual spectrum off a ``SpectralCache`` — one r×r
        eigh on first touch of this (V, q) pair, O(1) after. Under a
        ``Mesh`` runtime the dual arrays are placed replicated (pinned,
        so the broadcast is paid once per cache entry)."""
        cache = cache if cache is not None else default_cache()
        spec = dual_spectrum(self._V, self._q, cache)
        if runtime is not None and getattr(runtime, "is_mesh", False):
            phi, lams, W = runtime.replicate_pinned(
                (spec.phi, spec.lams, spec.W))
            spec = DualSpectrum(phi, lams, W)
        return spec

    def rescale(self, expected_size: float,
                cache: Optional[SpectralCache] = None) -> "LowRank":
        """Scalar gain on q so E|Y| hits ``expected_size``, solved on the
        r dual eigenvalues (they ARE the kernel's nonzero spectrum).
        Raises ``ValueError`` outside the achievable (0, rank) range,
        same contract as Dense/Kron."""
        spec = self.spectrum(cache)
        g = gain_for_expected_size(spec.log_eigenvalues(), expected_size)
        return LowRank(self._V, self._q * g)

    # -- sampling -----------------------------------------------------------
    # sample() is inherited: the base draws through the batched samplers,
    # whose dispatch points (sampling.batched.sample_krondpp_keyed,
    # sampling.kdpp.sample_kdpp_batched) hand the draw to the dual-space
    # engine via the DualSpectrum's sample_rows/sample_rows_kdpp hooks.
    # Only the Host oracle needs the guarded dense kernel.
    def _host_factors(self) -> Tuple[jax.Array, ...]:
        return (self.dense_kernel(),)

    # -- likelihood ---------------------------------------------------------
    def log_prob(self, batch: SubsetBatch,
                 cache: Optional[SpectralCache] = None) -> jax.Array:
        """(n,) log P(Y_i) off the dual: det(L_Y) = det(φ_Y φ_Yᵀ) per
        subset (|Y| × |Y| slogdet over gathered feature rows — a subset
        larger than the rank has a singular Gram and log P = -inf, which
        the cholesky-based factored objective would NaN on), normalizer
        log det(I_N + L) = log det(I_r + C) = Σ softplus(log d)."""
        spec = self.spectrum(cache)
        log_z = jnp.sum(jax.nn.softplus(spec.log_eigenvalues()))
        phi = spec.phi

        def one(idx, mask):
            P = phi[idx]
            S = P @ P.T
            m2 = jnp.outer(mask, mask)
            Sm = jnp.where(m2, S, jnp.eye(S.shape[0], dtype=S.dtype))
            sign, ld = jnp.linalg.slogdet(Sm)
            return jnp.where(sign > 0, ld, -jnp.inf)

        return jax.vmap(one)(batch.indices, batch.mask) - log_z

    # -- marginals ----------------------------------------------------------
    def marginal_kernel_submatrix(self, idx,
                                  cache: Optional[SpectralCache] = None
                                  ) -> jax.Array:
        """K[idx, idx] for K = L(L+I)⁻¹ = φ (C+I)⁻¹ φᵀ (push-through
        identity): gather the k feature rows, rotate into the dual
        eigenbasis, scale by 1/(1+d) — O(k r² + k² r), no N×N."""
        idx = _as_index_set(idx, self.N)
        spec = self.spectrum(cache)
        P = spec.phi[idx] @ spec.W                      # (k, r)
        inv1pd = jax.nn.sigmoid(-spec.log_eigenvalues())  # 1/(1+d)
        return (P * inv1pd[None, :]) @ P.T

    # -- conditioning -------------------------------------------------------
    def condition(self, observed, max_dense: int = MAX_DENSE_N
                  ) -> "LowRank":
        """The conditional DPP given ``observed ⊆ Y``, closed in feature
        space: the Schur complement of L on the complement rows equals
        (φ_Ā Π)(φ_Ā Π)ᵀ with the rank-(r-|A|) projector
        Π = I_r − φ_Aᵀ (φ_A φ_Aᵀ)⁻¹ φ_A — so conditioning stays low-rank
        at O(Nr + |A|³) cost and the result is another ``LowRank``
        (max_dense is never needed; accepted for protocol parity)."""
        A = np.asarray(_as_index_set(observed, self.N))
        if A.size == 0:
            return self
        phi = self._phi()
        phi_A = phi[A]                                   # (a, r)
        G = phi_A @ phi_A.T
        chol = jnp.linalg.cholesky(G)
        # NaN = potrf failed outright; a pivot² vanishing relative to the
        # Gram's scale = numerically singular (e.g. duplicated rows leave
        # a float-noise pivot that potrf happens to accept)
        piv2 = jnp.diagonal(chol) ** 2
        tol = 1e-6 * jnp.max(jnp.diagonal(G))
        if (not bool(jnp.all(jnp.isfinite(chol)))
                or bool(jnp.any(piv2 <= tol))):
            raise ValueError(
                f"cannot condition on {observed!r}: L_A is singular "
                f"(P(A ⊆ Y) = 0 — e.g. linearly dependent items of a "
                f"rank-deficient kernel)")
        comp = np.setdiff1d(np.arange(self.N), A)
        X = jax.scipy.linalg.cho_solve((chol, True), phi_A)  # G⁻¹ φ_A
        proj = jnp.eye(phi.shape[1], dtype=phi.dtype) - phi_A.T @ X
        return LowRank(phi[comp] @ proj)

    # -- MAP ----------------------------------------------------------------
    def map(self, k: int, max_dense: int = MAX_DENSE_N) -> jax.Array:
        """Greedy MAP in feature space: the fast-greedy det gain of item
        i given selected set S is its residual feature mass
        ‖φ_i‖² − ‖B_Sᵀ φ_i‖² (B_S an orthonormal basis of the selected
        rows) — identical to the dense fast-greedy gains, computed in
        O(N r k) without the N×N kernel (max_dense unused, kept for
        protocol parity)."""
        phi = np.asarray(self._phi(), np.float64)
        N, r = phi.shape
        k = int(k)
        resid = (phi * phi).sum(axis=1)
        B = np.zeros((r, min(k, r)))
        picked = np.zeros(N, bool)
        picks = []
        for t in range(k):
            gains = np.where(picked, -np.inf, resid)
            i = int(np.argmax(gains))
            picks.append(i)
            picked[i] = True
            if t < B.shape[1]:
                b = phi[i] - B[:, :t] @ (B[:, :t].T @ phi[i])
                b = b - B[:, :t] @ (B[:, :t].T @ b)
                n2 = float(b @ b)
                if n2 > 1e-12:
                    b = b / np.sqrt(n2)
                    B[:, t] = b
                    resid = np.maximum(resid - (phi @ b) ** 2, 0.0)
        return jnp.asarray(np.asarray(picks, np.int64), jnp.int32)

    # -- learning -----------------------------------------------------------
    def fit(self, batch: SubsetBatch, algorithm: Optional[str] = None,
            max_dense: int = MAX_DENSE_N, **fit_kwargs):
        """Maximum-likelihood fit of (V, q) in the dual
        (``algorithm="lowrank"``: Picard-style q fixed-point alternating
        with projected-gradient V steps — ``repro.learning.fit``).
        Returns the engine's ``FitReport`` with ``report.model`` a
        ``LowRank``."""
        from ..learning.api import fit as _fit
        if algorithm is None:
            algorithm = self._default_algorithm
        if algorithm != "lowrank":
            raise ValueError(
                f"LowRank models learn with algorithm='lowrank' (dual-"
                f"space Picard + projected gradient); {algorithm!r} needs "
                f"an explicit Dense/Kron kernel")
        return _fit(self, batch, algorithm="lowrank", **fit_kwargs)

    # -- subclass hooks -----------------------------------------------------
    def _wrap_factors(self, factors):
        raise TypeError("LowRank is not factor-parameterized")

    def _fit_params(self, algorithm: str, max_dense: int = MAX_DENSE_N):
        return self

    def tree_flatten(self):
        return (self._V, self._q), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)
