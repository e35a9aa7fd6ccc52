"""Plain float64 reference of KrK-Picard (Mariet & Sra 2016, Alg. 1 with
the Appendix B contractions) under the Armijo step rule.

State: factors (L1, L2) of L = L1 kron L2 and training subsets Y_i with
items y = r * N2 + u. One sweep:

    Theta = (1/n) sum_i U_i L_{Y_i}^{-1} U_i^T      (never stored dense)
    A[k, l] = Tr(Theta_(kl) L2),  C = sum_ij L1[i, j] Theta_(ij)
    alpha_k = sum_u d2_u / (1 + d1_k d2_u)
    beta_u  = d2_u^2 sum_k d1_k / (1 + d1_k d2_u)
    L1 <- sym(L1 + (a / N2) (L1 A L1 - P1 diag(d1^2 alpha) P1^T))
    L2 <- sym(L2 + (a / N1) (L2 C L2 - P2 diag(beta) P2^T))

with A, C, alpha from (L1, L2) for the first half-update, and C, beta
recomputed at the updated L1 for the second. Each half-update tries the
step a and halves it (at most 8 times) until the candidate factor is
positive definite and the mean log-likelihood

    phi = (1/n) sum_i log det L_{Y_i} - sum_kl log(1 + d1_k d2_l)

does not fall below the value before it by more than 1e-6; if no trial
passes the factor stays. The trial step of a sweep is min(a0, 1.3 a)
after the last accepted a > 0, else a0 = 1.5; the accepted a of a sweep
is the smaller of its two half-steps.

Every function takes ``rnd``, applied to each stored intermediate: the
identity for the float64 reference, and for the control that has to
fail a rounding to the precision below the configuration's
(``BELOW``): for float32 at JAX's highest matmul precision, ``high``,
the two-bfloat16 split (about 16 significant bits) that a three-pass
matmul sees; for float32 at the default precision, ``bf16``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import ml_dtypes
import numpy as np

A0, GROW, SHRINK, MAX_BACKTRACKS, ASCENT_TOL = 1.5, 1.3, 0.5, 8, 1e-6


def exact(x):
    return x


def bf16(x):
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def high(x):
    x = np.asarray(x, np.float32)
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi + (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)


#: the control's rounding for a configuration's ``matmul_precision``
BELOW = {"highest": high, "default": bf16}


class Subsets:
    """Training subsets as float64-ready gather indices."""

    def __init__(self, indices: np.ndarray, mask: np.ndarray, n1: int,
                 n2: int):
        mask = np.asarray(mask, bool)
        width = max(1, int(mask.any(0).nonzero()[0].max(initial=0)) + 1)
        self.mask = mask[:, :width]
        idx = np.where(self.mask, np.asarray(indices, np.int64)[:, :width], 0)
        self.n1, self.n2 = n1, n2
        r, u = idx // n2, idx % n2
        self.m2 = self.mask[:, :, None] & self.mask[:, None, :]
        self.eye = np.eye(idx.shape[1])[None]
        # flat positions of L1[r_a, r_b] and L2[u_a, u_b], which are
        # also where A and C gather their terms
        self.flat1 = r[:, :, None] * n1 + r[:, None, :]
        self.flat2 = u[:, :, None] * n2 + u[:, None, :]
        self._memo = {}

    @property
    def n(self) -> int:
        return self.mask.shape[0]

    def blocks(self, L1, L2, rnd=exact):
        """Identity-padded L_{Y_i} (n, w, w), and the gathered factor
        blocks L1[r, r], L2[u, u]."""
        b1 = self._gather(L1, self.flat1, 1)
        b2 = self._gather(L2, self.flat2, 2)
        return rnd(np.where(self.m2, b1 * b2, self.eye)), b1, b2

    def _gather(self, L, flat, slot):
        # a half-update's trials move one factor and keep the other
        hit = self._memo.get(slot)
        if hit is None or hit[0] is not L:
            hit = self._memo[slot] = (L, np.take(L, flat))
        return hit[1]


def log_likelihood(L1, L2, data: Subsets, rnd=exact) -> float:
    sub, _, _ = data.blocks(L1, L2, rnd)
    try:
        chol = rnd(np.linalg.cholesky(sub))
    except np.linalg.LinAlgError:
        return -np.inf
    logdet = rnd(2.0 * np.log(np.diagonal(chol, axis1=1, axis2=2)).sum(1))
    d1 = rnd(np.clip(np.linalg.eigvalsh(L1), 0.0, None))
    d2 = rnd(np.clip(np.linalg.eigvalsh(L2), 0.0, None))
    return float(np.mean(logdet)
                 - np.sum(rnd(np.log1p(rnd(np.outer(d1, d2))))))


def theta_stats(L1, L2, data: Subsets, rnd=exact
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(A, C) of the module docstring."""
    N1, N2 = L1.shape[0], L2.shape[0]
    sub, b1, b2 = data.blocks(L1, L2, rnd)
    M = rnd(np.linalg.inv(sub) * data.m2)
    W = rnd(M * np.swapaxes(b2, 1, 2))      # M[a, b] L2[u_b, u_a]
    Wp = rnd(M * b1)                        # M[a, b] L1[r_a, r_b]
    A = np.bincount(data.flat1.ravel(), W.ravel(), N1 * N1)
    C = np.bincount(data.flat2.ravel(), Wp.ravel(), N2 * N2)
    return (rnd(A.reshape(N1, N1) / data.n),
            rnd(C.reshape(N2, N2) / data.n))


def _alpha_beta(d1, d2):
    denom = 1.0 + np.outer(d1, d2)
    return (d2[None, :] / denom).sum(1), (d2[None, :] ** 2 * d1[:, None]
                                           / denom).sum(0)


def _halfstep(update, ll_of, ll_ref: float, a: float):
    for k in range(MAX_BACKTRACKS + 1):
        if k:
            a *= SHRINK
        cand = update(a)
        ll = ll_of(cand)
        if (np.linalg.eigvalsh(cand)[0] > 0.0 and np.isfinite(ll)
                and ll >= ll_ref - ASCENT_TOL):
            return cand, ll, a, k
    return update(0.0), ll_ref, 0.0, MAX_BACKTRACKS


def sweep(L1, L2, data: Subsets, a_trial: float, rnd=exact):
    """One KrK-Picard sweep -> (L1, L2, ll, accepted a, backtracks)."""
    N1, N2 = L1.shape[0], L2.shape[0]
    A, _ = theta_stats(L1, L2, data, rnd)
    d1, P1 = (rnd(x) for x in np.linalg.eigh(L1))
    d2, P2 = (rnd(x) for x in np.linalg.eigh(L2))
    alpha, _ = (rnd(x) for x in _alpha_beta(d1, d2))
    G1 = rnd(rnd(rnd(L1 @ A) @ L1)
             - rnd(rnd(P1 * (d1 ** 2 * alpha)[None, :]) @ P1.T))

    def sym(M):
        return rnd(0.5 * (M + M.T))

    def ll(a, b):
        return log_likelihood(a, b, data, rnd)

    L1n, ll1, a1, bt1 = _halfstep(lambda a: sym(L1 + (a / N2) * G1),
                                  lambda M: ll(M, L2), ll(L1, L2), a_trial)
    _, C = theta_stats(L1n, L2, data, rnd)
    _, beta = (rnd(x) for x in _alpha_beta(rnd(np.linalg.eigvalsh(L1n)),
                                           d2))
    G2 = rnd(rnd(rnd(L2 @ C) @ L2)
             - rnd(rnd(P2 * beta[None, :]) @ P2.T))
    L2n, ll2, a2, bt2 = _halfstep(lambda a: sym(L2 + (a / N1) * G2),
                                  lambda M: ll(L1n, M), ll1, a_trial)
    return L1n, L2n, ll2, min(a1, a2), bt1 + bt2


def fit(factors: Sequence[np.ndarray], data: Subsets, sweeps: int,
        rnd=exact) -> Tuple[Tuple[np.ndarray, np.ndarray], List[float], int]:
    """-> (final factors, [LL at start and after each sweep], backtracks)."""
    L1, L2 = (rnd(np.asarray(f, np.float64)) for f in factors)
    lls = [log_likelihood(L1, L2, data, rnd)]
    a_prev, backtracks = A0, 0
    for _ in range(sweeps):
        a_trial = min(A0, a_prev * GROW) if a_prev > 0.0 else A0
        L1, L2, ll, a_prev, bt = sweep(L1, L2, data, a_trial, rnd)
        lls.append(log_likelihood(L1, L2, data, rnd))
        backtracks += bt
    return (L1, L2), lls, backtracks


def compare(got: Sequence[np.ndarray], got_lls: Sequence[float],
            want: Sequence[np.ndarray], want_lls: Sequence[float]) -> dict:
    """The numbers a fit is held to.

    ll_gap      widest |LL - LL_ref| / |LL_ref| over the trajectory
                (start and every sweep); a fit whose trajectory has
                another length reads 1.
    factor_rel_gap
                worst factor of |L_f - L_f,ref| / |L_f,ref| (Frobenius
                norms): how far the program's factor lies from the
                reference's, as a share of the factor. Rounding moves a
                factor by about the same share on every data set, where
                the fit's own move from the start varies tenfold by seed.
    """
    if len(got_lls) != len(want_lls):
        ll_gap = 1.0
    else:
        ll_gap = max(abs(g - w) / abs(w) for g, w in zip(got_lls, want_lls))
    gaps = [np.linalg.norm(np.asarray(g, np.float64) - w) / np.linalg.norm(w)
            for g, w in zip(got, want)]
    return {"ll_gap": float(ll_gap), "factor_rel_gap": float(max(gaps))}
