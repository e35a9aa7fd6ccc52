"""Exact DPP sampling (paper Alg. 2) and its KronDPP specialization (Sec. 4).

Full kernel:   O(N^3 + N k^3)   (eigendecomposition dominates)
KronDPP m=2:   O(N^{3/2} + N k^3)
KronDPP m=3:   O(N + N k^3) = O(N k^3)

``sample_kdpp`` is the k-DPP oracle (Kulesza & Taskar 2011, Alg. 1): its
phase 1 is the float64 log-space ESP draw in place of the Bernoulli one.
The phase-2 selection loop is shared. It is a host-side sampler that runs
eagerly with numpy-style control flow; the per-step linear algebra is jax.

.. deprecated::
    The host loop is kept as the *reference oracle* (tests validate the
    device samplers against it). Production callers should use the
    ``repro.dpp`` facade (``model.sample`` / ``model.service``), whose
    device-resident batched subsystem in :mod:`repro.sampling` amortizes
    the factor eigendecompositions and draws whole batches in one
    jit+vmap device call.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .krondpp import KronDPP


def _phase2_select(rng: np.random.Generator, V: np.ndarray) -> List[int]:
    """Standard elementary-DPP projection sampling.

    V: (N, k) orthonormal columns. Returns k selected item indices.
    Per iteration: sample i ~ (1/|V|) sum_j V[i,j]^2, then project the basis
    onto the complement of e_i and re-orthonormalize (Gram-Schmidt via QR).
    """
    Y: List[int] = []
    V = V.copy()
    while V.shape[1] > 0:
        p = (V ** 2).sum(axis=1)
        p = np.maximum(p, 0.0)
        p = p / p.sum()
        i = int(rng.choice(len(p), p=p))
        Y.append(i)
        # Eliminate the component along e_i: pick column with largest |V[i,j]|
        j = int(np.argmax(np.abs(V[i])))
        col = V[:, j].copy()
        denom = col[i]
        V = V - np.outer(col / denom, V[i])
        V = np.delete(V, j, axis=1)
        if V.shape[1] > 0:
            # Re-orthonormalize (thin QR keeps O(N k^2) per step -> O(N k^3))
            V, _ = np.linalg.qr(V)
    return Y


def sample_dpp(rng: np.random.Generator, eigvals: np.ndarray, eigvecs: np.ndarray
               ) -> List[int]:
    """Alg. 2 with a precomputed eigendecomposition of L."""
    lam = np.asarray(eigvals)
    probs = lam / (1.0 + lam)
    J = np.nonzero(rng.random(lam.shape[0]) < probs)[0]
    if len(J) == 0:
        return []
    V = np.asarray(eigvecs)[:, J]
    return _phase2_select(rng, V)


def sample_full_dpp(rng: np.random.Generator, L: np.ndarray) -> List[int]:
    """O(N^3) baseline sampler for a dense kernel."""
    lam, vecs = np.linalg.eigh(np.asarray(L))
    lam = np.maximum(lam, 0.0)
    return sample_dpp(rng, lam, vecs)


def sample_krondpp(rng: np.random.Generator, dpp: KronDPP) -> List[int]:
    """Sec. 4 sampler: factor eigendecompositions + lazy eigenvectors.

    Phase 1 runs over the N eigenvalues as an outer product (never
    materializing eigenvectors); only the |J| selected eigenvectors are
    built, each in O(N), so setup is O(sum N_i^3 + N|J|).
    """
    eigs = [np.linalg.eigh(np.asarray(f)) for f in dpp.factors]
    lams = [np.maximum(e[0], 0.0) for e in eigs]
    vecs = [e[1] for e in eigs]

    # Phase 1 over the product spectrum, factor-by-factor to stay O(N) memory.
    lam_all = lams[0]
    for l in lams[1:]:
        lam_all = np.multiply.outer(lam_all, l).reshape(-1)
    probs = lam_all / (1.0 + lam_all)
    J = np.nonzero(rng.random(lam_all.shape[0]) < probs)[0]
    if len(J) == 0:
        return []

    return _phase2_select(rng, _kron_columns(vecs, J))


def _kron_columns(vecs: Sequence[np.ndarray], J: np.ndarray) -> np.ndarray:
    """The eigenvectors of L_1 ⊗ ... ⊗ L_m at product indices J (row-major),
    built lazily: v_(i1..im) = kron(v1_i1, ..., vm_im). (N, |J|)."""
    sizes = [v.shape[0] for v in vecs]
    cols = []
    for g in J:
        parts = []
        rem = int(g)
        for s in sizes[::-1]:
            parts.append(rem % s)
            rem //= s
        parts = parts[::-1]
        v = vecs[0][:, parts[0]]
        for k in range(1, len(sizes)):
            v = np.outer(v, vecs[k][:, parts[k]]).reshape(-1)
        cols.append(v)
    return np.stack(cols, axis=1)


def log_esp_table(log_lam: np.ndarray, k: int) -> np.ndarray:
    """float64 log e_j(λ_1..λ_n) for n = 0..N, j = 0..k — shape (N+1, k+1),
    by the recursion e_j^n = e_j^{n-1} + λ_n e_{j-1}^{n-1} in log space
    (-inf entries of ``log_lam``, zero eigenvalues, add nothing)."""
    T = np.full((log_lam.shape[0] + 1, k + 1), -np.inf)
    T[:, 0] = 0.0
    for n, ll in enumerate(log_lam, start=1):
        T[n, 1:] = np.logaddexp(T[n - 1, 1:], T[n - 1, :-1] + ll)
    return T


def sample_kdpp(rng: np.random.Generator, factors: Sequence[np.ndarray],
                k: int, num_samples: int = 1) -> List[List[int]]:
    """``num_samples`` k-DPP draws (Kulesza & Taskar 2011, Alg. 1) from
    L = L_1 ⊗ ... ⊗ L_m (m = 1 is a dense kernel), in float64.

    Each factor is eigendecomposed once, in float64; its eigenvalues at
    or below its rounding level (size x the machine epsilon of the
    factor's own dtype x the largest) count as zero. Phase 1 goes over
    the product spectrum from the last eigenvalue to the first and keeps
    eigenvalue n with probability λ_n e_{k'-1}(λ_1..λ_{n-1}) /
    e_{k'}(λ_1..λ_n), k' being the number still to keep; the ESP table is
    built once for all draws. Below rank, where |Y| = k has probability
    0, a draw keeps all rank eigenvectors, as the device sampler does.
    Phase 2 is the projection-DPP chain rule on the kept columns.
    """
    log_lam, vecs = np.zeros(1), []
    for f in factors:
        f = np.asarray(f)
        lam, vec = np.linalg.eigh(f.astype(np.float64))
        tol = lam.shape[0] * np.finfo(f.dtype).eps * np.abs(lam).max()
        with np.errstate(divide="ignore"):
            ll = np.where(lam > tol, np.log(np.maximum(lam, tol)), -np.inf)
        log_lam = (log_lam[:, None] + ll[None, :]).reshape(-1)
        vecs.append(vec)
    table = log_esp_table(log_lam, k)
    k0 = min(int(k), int(np.isfinite(log_lam).sum()))
    out = []
    for _ in range(num_samples):
        J, k_rem = [], k0
        for n in range(log_lam.shape[0], 0, -1):
            if k_rem == 0:
                break
            log_p = log_lam[n - 1] + table[n - 1, k_rem - 1] - table[n, k_rem]
            if rng.random() < np.exp(min(log_p, 0.0)):
                J.append(n - 1)
                k_rem -= 1
        J = np.array(J[::-1], np.int64)
        out.append(_phase2_select(rng, _kron_columns(vecs, J))
                   if len(J) else [])
    return out


# ---------------------------------------------------------------------------
# Greedy MAP (used by the serving-side KV compaction; jit-able, fixed k)
# ---------------------------------------------------------------------------

def greedy_map_kdpp(L: jax.Array, k: int) -> jax.Array:
    """Greedy MAP for a k-DPP: iteratively add the item maximizing the
    conditional variance (Chen et al. 2018 fast greedy MAP, Cholesky-update
    form). O(N k^2); jit-able with static k. Returns (k,) int32 indices.

    d_i tracks the conditional variance of each item; c_i rows build the
    Cholesky factor of L_Y restricted to chosen items.
    """
    N = L.shape[0]

    from ..kernels.ref import degeneracy_eps
    eps = degeneracy_eps(L)

    def body(state, _):
        d, C, chosen_mask, t = state
        scores = jnp.where(chosen_mask, -jnp.inf, d)
        j = jnp.argmax(scores)
        # When the conditional variance collapses (k beyond numerical rank),
        # 1/sqrt(d_j) explodes, d goes NaN, and every later pick is poisoned.
        # Clamp the divisor and zero the update for degenerate picks so they
        # stay valid indices and leave the remaining state intact.
        ok = d[j] > eps
        dj = jnp.maximum(d[j], eps)
        # e = (L[:, j] - C @ C[j]) / sqrt(d_j)
        e = (L[:, j] - C @ C[j]) / jnp.sqrt(dj)
        e = jnp.where(ok, e, 0.0)
        d_new = jnp.maximum(d - e * e, 0.0)
        C_new = jax.lax.dynamic_update_index_in_dim(C.T, e, t, axis=0).T
        return (d_new, C_new, chosen_mask.at[j].set(True), t + 1), j

    d0 = jnp.diagonal(L)
    C0 = jnp.zeros((N, k), L.dtype)
    (_, _, _, _), picks = jax.lax.scan(
        body, (d0, C0, jnp.zeros((N,), bool), 0), None, length=k)
    return picks.astype(jnp.int32)
