"""Algorithmic operations and bytes of exact Kron-DPP draws and of
KrK-Picard learning sweeps.

Counted from the shapes and the drawn sizes alone, so they read the same
work whatever engine does it. For L = L_1 kron L_2 with N = N1 N2 items
and a draw of k items (k chain-rule steps on k eigenvector columns):

phase 1    N adds for the log product spectrum, N sigmoids, N compares
           and N adds to compact the kept eigen-indices: 4N
phase 2    step t: the prefix mass (N adds), the picked row of V (k
           multiplies), two Gram-Schmidt passes against t basis rows
           (8tk), V q off the factors (N1 k multiplies, then 2 N k for
           the (N1, k) x (k, N2) product) and the downdate of the
           residual mass (2N): 2Nk + 3N + N1 k + k + 8tk

Bytes are the least a call must move through HBM: the factors'
eigenvectors and eigenvalues once per call, and the picks of every row
(4 bytes per slot of the (rows, k_max) output). Uniforms, keys and the
gathered columns can live on chip.

A KrK-Picard sweep (Alg. 1, block-CCCP order) of L = L_1 kron L_2 over n
training subsets, subset i of k_i items, makes two Theta-statistics
passes (A at (L1, L2), C at the half-updated L1) and two factor eighs:

Theta pass  per subset: the Hadamard product of the gathered blocks
            L1[r, r] and L2[u, u] (k^2 multiplies), the inverse of the
            symmetric positive definite k x k result (k^3: Cholesky k^3/3,
            the triangle's inverse k^3/3, their product k^3/3), the two
            weighted blocks M * L2[u, u]^T and M * L1[r, r] (2 k^2) and
            their scatter-adds into A and C (2 k^2): k^3 + 5 k^2
eigh        of an s x s factor: 4 s^3 / 3, the Householder
            tridiagonalisation that a dense symmetric eigensolver starts
            with (the eigenvectors and the QR steps are not counted)

Each subset is counted at its own size: the padding of every subset to
the batch's slot width is the program's choice, not the algorithm's.
The Armijo trials' log-likelihoods and the tracked log-likelihood are
left out: a sweep at a fixed step needs neither. Bytes: per pass the
subsets' item indices (4 bytes each) and both factors read once, and A
and C written once (4-byte floats); the gathered blocks can live on
chip. At the paper's sizes the bytes set the least time.
"""

from __future__ import annotations

import collections
from typing import Iterable, Sequence, Tuple


def draw_flops(factor_sizes: Sequence[int], k: int) -> float:
    n1, n2 = factor_sizes
    n = n1 * n2
    phase2 = k * (2 * n * k + 3 * n + n1 * k + k) + 4 * k * k * (k - 1)
    return float(4 * n + phase2)


def call_bytes(factor_sizes: Sequence[int], rows: int, k_max: int) -> float:
    return float(4 * sum(s * s + s for s in factor_sizes)
                 + 4 * rows * k_max)


def window_work(factor_sizes: Sequence[int], sizes: Iterable[int],
                calls: int, k_max: int) -> Tuple[float, float]:
    """(operations, bytes) of every draw of a window of ``calls`` calls
    whose rows drew ``sizes`` items."""
    hist = collections.Counter(int(k) for k in sizes)
    flops = sum(c * draw_flops(factor_sizes, k) for k, c in hist.items())
    rows_per_call = sum(hist.values()) // max(1, calls)
    return flops, calls * call_bytes(factor_sizes, rows_per_call, k_max)


def sweep_flops(factor_sizes: Sequence[int],
                subset_sizes: Iterable[int]) -> float:
    """Operations of one KrK-Picard sweep (the module docstring)."""
    hist = collections.Counter(int(k) for k in subset_sizes)
    theta = sum(c * (k ** 3 + 5 * k * k) for k, c in hist.items())
    return float(2 * theta + sum(4 * s ** 3 / 3 for s in factor_sizes))


def sweep_bytes(factor_sizes: Sequence[int],
                subset_sizes: Iterable[int]) -> float:
    """Least HBM bytes of one KrK-Picard sweep (the module docstring)."""
    items = sum(int(k) for k in subset_sizes)
    squares = sum(s * s for s in factor_sizes)
    return float(2 * 4 * (items + 2 * squares))


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> Tuple[float, str]:
    """(least time / measured time, the bound that sets the least time)."""
    t_flops = flops / peak["flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "hbm"
    return max(t_flops, t_bytes) / seconds, bound
