"""Algorithmic operations and bytes of exact Kron k-DPP draws.

Counted from the shapes alone, as ``bench/counts.py`` counts the plain
draw, so they read the same work whatever engine does it. For
L = L_1 kron L_2 with N = N1 N2 items, slates of k items and a call of
``rows`` slates:

ESP table    once a call (it depends only on the spectrum and k): N adds
             for the log product spectrum, then per eigenvalue and per
             j = 1..k one add and one logaddexp (max, subtract, exp,
             log1p, add): N + 6Nk
conditional  per row, per eigenvalue: two table reads combined with the
draw         log eigenvalue (2 adds), the exp, the clamp, the compare
             with u and the update of the count still to keep: 6N
phase 2      per row, the chain rule at k, ``counts.draw_flops`` less its
             phase 1

Bytes are the least a call must move through HBM, ``counts.call_bytes``
at a width of k: the factors' eigenvectors and eigenvalues once, and the
picks of every row. The table, the uniforms and the gathered columns can
live on chip.
"""

from __future__ import annotations

from typing import Sequence, Tuple

from bench import counts


def esp_flops(factor_sizes: Sequence[int], k: int) -> float:
    n = factor_sizes[0] * factor_sizes[1]
    return float(n + 6 * n * k)


def row_flops(factor_sizes: Sequence[int], k: int) -> float:
    """Conditional draw and phase 2 of one slate."""
    n = factor_sizes[0] * factor_sizes[1]
    phase2 = counts.draw_flops(factor_sizes, k) \
        - counts.draw_flops(factor_sizes, 0)
    return float(6 * n + phase2)


def window_work(factor_sizes: Sequence[int], k: int, rows: int,
                calls: int) -> Tuple[float, float]:
    """(operations, bytes) of a window of ``calls`` calls that drew
    ``rows`` slates of k items in all."""
    flops = calls * esp_flops(factor_sizes, k) \
        + rows * row_flops(factor_sizes, k)
    nbytes = calls * counts.call_bytes(factor_sizes, rows // max(1, calls),
                                       k)
    return flops, nbytes
