"""Plain float64 reference for keyed exact Kron-DPP draws, and the gap
by which a served draw departs from it.

A draw is a pure function of its PRNG key (the program's documented
keying, which the serving tier's determinism rests on):

    k1, k2 = split(row_key)
    u  = uniform(k1, (N,))      phase 1: eigen-index g is kept iff
                                u[g] < lambda_g / (1 + lambda_g)
    us = uniform(k2, (k_max,))  phase 2: step t picks the first item whose
                                prefix mass exceeds us[t] * total mass

with k_max = ceil(E|Y| + 6 sd) + 1. ``RowChecker`` regenerates u and us
from the keys and replays the chain rule in float64 numpy on the
materialised eigenvector columns, following the served picks. Two
numbers come out of a row:

phase1_gap  0 when the reference keeps the same eigen-indices as the
            served draw; otherwise |logit(u_g) - log lambda_g| of the
            eigen-index that has to be toggled to explain the draw: the
            relative eigenvalue error that a near-tie would need.
phase2_gap  the widest distance, over the row's steps, between the
            inverse-CDF target us[t] * total and the CDF interval of the
            served pick, as a share of the total mass: 0 when the target
            falls inside, about U(0, 1) for a wrong item, and 1 for a
            zero-mass item, a pick past the draw's size or a missing one.

``control_picks`` is the same reference computed from bfloat16-rounded
operands (float32 accumulation), the control that has to fail.
"""

from __future__ import annotations

import functools
import math
import zlib
from itertools import combinations
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

#: A row whose phase-2 gap exceeds this, or whose size differs from the
#: reference's, is explained by toggling near-tie eigen-indices.
EXPLAIN_ABOVE = 1e-4
#: Eigen-indices with |logit(u) - log lambda| below this are toggle
#: candidates; at most ``MAX_CANDIDATES`` of them, nearest first.
TOGGLE_WINDOW = 0.5
MAX_CANDIDATES = 4


def k_max_for(log_lam: np.ndarray) -> int:
    """ceil(E|Y| + 6 sd) + 1, clamped to [1, N]."""
    p = 0.5 * (1.0 + np.tanh(0.5 * log_lam))
    e, sd = float(p.sum()), float(np.sqrt(np.sum(p * (1.0 - p))))
    return max(1, min(math.ceil(e + 6.0 * sd) + 1, log_lam.size))


@functools.partial(jax.jit, static_argnames=("n_items", "k_max"))
def _uniforms(row_keys, n_items: int, k_max: int):
    def one(k):
        k1, k2 = jax.random.split(k)
        return (jax.random.uniform(k1, (n_items,)),
                jax.random.uniform(k2, (k_max,)))
    return jax.vmap(one)(row_keys)


@jax.jit
def _served_keys(base, tags, seqs, rows):
    def one(tag, s, j):
        return jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(base, tag), s), j)
    return jax.vmap(one)(tags, seqs, rows)


def served_row_keys(service_seed: int, requests: Sequence[Tuple[str, int, int]]
                    ) -> jax.Array:
    """Row keys of (tenant, seq, row) triples: fold_in(fold_in(fold_in(
    PRNGKey(seed), crc32(tenant)), seq), row)."""
    tags = np.array([zlib.crc32(t.encode()) & 0x7FFFFFFF
                     for t, _, _ in requests], np.uint32)
    seqs = np.array([s for _, s, _ in requests], np.uint32)
    rows = np.array([j for _, _, j in requests], np.uint32)
    return _served_keys(jax.random.PRNGKey(service_seed), tags, seqs, rows)


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float32)


def factor_spectra(factors) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(eigenvalues, eigenvectors) of each float32 factor by
    ``jnp.linalg.eigh`` on the factor's device: the decomposition at the
    configuration's precision that the replay starts from."""
    return [tuple(np.asarray(x) for x in jnp.linalg.eigh(f))
            for f in factors]


class RowChecker:
    """float64 replay of keyed draws from L = L1 kron L2, given each
    factor's eigendecomposition (``factor_spectra``)."""

    def __init__(self, spectra: Sequence[Tuple[np.ndarray, np.ndarray]]):
        self.d, self.P = [], []
        for d, P in spectra:
            self.d.append(np.clip(np.asarray(d, np.float64), 0.0, None))
            self.P.append(np.asarray(P, np.float64))
        self.N2 = self.P[1].shape[0]
        self.N = self.P[0].shape[0] * self.N2
        with np.errstate(divide="ignore"):
            self.log_lam = (np.log(self.d[0])[:, None]
                            + np.log(self.d[1])[None, :]).reshape(-1)
        self.k_max = k_max_for(self.log_lam)

    def uniforms(self, row_keys) -> Tuple[np.ndarray, np.ndarray]:
        u, us = _uniforms(jnp.asarray(row_keys), self.N, self.k_max)
        return np.asarray(u, np.float64), np.asarray(us, np.float64)

    def _columns(self, J: np.ndarray, P=None) -> np.ndarray:
        P1, P2 = self.P if P is None else P
        V = P1[:, J // self.N2][:, None, :] * P2[:, J % self.N2][None, :, :]
        return V.reshape(self.N, len(J))

    def _selected(self, u: np.ndarray) -> np.ndarray:
        with np.errstate(divide="ignore"):
            keep = np.log(u) - np.log1p(-u) < self.log_lam
        return np.nonzero(keep)[0][: self.k_max]

    def phase2_gap(self, J: np.ndarray, us: np.ndarray,
                   picks: Sequence[int]) -> float:
        """Widest inverse-CDF gap of ``picks`` along the chain rule on the
        columns J (see the module docstring)."""
        if len(picks) != len(J):
            return 1.0
        V = self._columns(J)
        norms = np.sum(V * V, axis=1)
        B = np.zeros((0, len(J)))
        widest = 0.0
        for t, i in enumerate(picks):
            if not 0 <= i < self.N or norms[i] <= 0.0:
                return 1.0
            c = np.cumsum(norms)
            total, r = c[-1], us[t] * c[-1]
            hi, lo = c[i], c[i] - norms[i]
            widest = max(widest, max(lo - r, r - hi, 0.0) / total)
            w = V[i]
            q = w - (B @ w) @ B
            q = q - (B @ q) @ B
            q = q / np.sqrt(max(q @ q, 1e-300))
            norms = np.maximum(norms - (V @ q) ** 2, 0.0)
            norms[i] = 0.0
            B = np.vstack([B, q])
        return widest

    def check_row(self, u: np.ndarray, us: np.ndarray,
                  picks: Sequence[int]) -> Tuple[float, float]:
        """(phase1_gap, phase2_gap) of one served row."""
        picks = [int(i) for i in picks]
        J = self._selected(u)
        gap2 = self.phase2_gap(J, us, picks)
        if gap2 <= EXPLAIN_ABOVE:
            return 0.0, gap2
        with np.errstate(divide="ignore"):
            z = np.abs(np.log(u) - np.log1p(-u) - self.log_lam)
        near = np.argsort(z)[:MAX_CANDIDATES]
        near = [int(g) for g in near if z[g] < TOGGLE_WINDOW]
        best = (0.0, gap2)
        for n_toggle in (1, 2):
            for toggled in combinations(near, n_toggle):
                Jt = np.array(sorted(set(J.tolist()) ^ set(toggled)), np.int64)
                if len(Jt) != len(picks):
                    continue
                g = self.phase2_gap(Jt[: self.k_max], us, picks)
                if g < best[1]:
                    best = (float(max(z[list(toggled)])), g)
            if best[1] <= EXPLAIN_ABOVE:
                break
        return best

    def control_picks(self, u: np.ndarray, us: np.ndarray) -> List[int]:
        """The reference draw with every stored operand rounded to
        bfloat16 (arithmetic in float32)."""
        P = [_bf16(p) for p in self.P]
        d = [_bf16(x) for x in self.d]
        with np.errstate(divide="ignore"):
            log_lam = _bf16((np.log(d[0])[:, None]
                             + np.log(d[1])[None, :]).reshape(-1))
            keep = _bf16(np.log(u) - np.log1p(-u)) < log_lam
        J = np.nonzero(keep)[0][: self.k_max]
        V = _bf16(self._columns(J, P))
        norms = _bf16(np.sum(V * V, axis=1))
        B = np.zeros((0, len(J)), np.float32)
        picks = []
        for t in range(len(J)):
            c = _bf16(np.cumsum(norms))
            if c[-1] <= 0.0:
                break
            i = int(min(np.searchsorted(c, np.float32(us[t]) * c[-1],
                                        side="right"), self.N - 1))
            picks.append(i)
            w = V[i]
            q = _bf16(w - (B @ w) @ B)
            q = _bf16(q - (B @ q) @ B)
            q = _bf16(q / np.sqrt(max(float(q @ q), 1e-30)))
            norms = _bf16(np.maximum(norms - _bf16(V @ q) ** 2, 0.0))
            norms[i] = 0.0
            B = np.vstack([B, q])
        return picks


def check_rows(checker: RowChecker, row_keys, rows: Sequence[Sequence[int]],
               control: bool = False) -> dict:
    """Widest phase-1 and phase-2 gaps over ``rows`` drawn from
    ``row_keys``; with ``control`` the rows are replaced by the bfloat16
    control's own draws from the same keys."""
    u, us = checker.uniforms(row_keys)
    g1 = g2 = 0.0
    for b, row in enumerate(rows):
        if control:
            row = checker.control_picks(u[b], us[b])
        a, c = checker.check_row(u[b], us[b], row)
        g1, g2 = max(g1, a), max(g2, c)
    return {"phase1_gap": float(g1), "phase2_gap": float(g2),
            "rows": len(rows)}
