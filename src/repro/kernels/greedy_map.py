"""Pallas TPU kernel: one step of fast greedy k-DPP MAP (Chen et al. 2018
Cholesky-update form) — the serving-side hot loop of DPP KV-cache compaction.

Per selection step, for the chosen item j with conditional variance d_j:
    e = (L[:, j] - C @ C[j]) / sqrt(d_j)       # (N,)  — O(Nk) work
    d <- d - e * e

The O(Nk) update dominates the O(N k^2) total; this kernel tiles it over N.
The dynamically-indexed small operands (L column j, row C[j], scalar d_j) are
gathered by XLA outside (O(N + k)) and passed in.

Layout: the item axis runs along the 128-wide lane axis. The Cholesky
buffer is held transposed, Ct = C^T of shape (k, N), and every per-item
vector is a (1, N) row, so each grid step streams a (k, bn) / (1, bn) tile
with bn a multiple of 128 — each read exactly once per step (memory-bound
roofline: 4·N·k bytes per step). ``C @ C[j]`` is an exact f32 sublane
reduction of ``Ct * C[j]``, no MXU pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(lcol_ref, ct_ref, cj_ref, dj_ref, d_ref, e_ref, dnew_ref):
    proj = jnp.sum(ct_ref[...] * cj_ref[...], axis=0, keepdims=True)
    e = (lcol_ref[...] - proj) / jnp.sqrt(jnp.maximum(dj_ref[...], 1e-12))
    e_ref[...] = e
    dnew_ref[...] = d_ref[...] - e * e


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def greedy_map_update_pallas(lcol: jax.Array, Ct: jax.Array, cj: jax.Array,
                             dj: jax.Array, d: jax.Array,
                             block_n: int = 512, interpret: bool = False):
    """One greedy-MAP update step on lane-major operands.

    lcol: (1, N) kernel column of the chosen item; Ct: (k, N) transposed
    Cholesky buffer; cj: (k, 1) column Ct[:, j]; dj: (1, 1) chosen
    variance; d: (1, N) variances. N must be a multiple of ``block_n``,
    itself a multiple of 128 (``kernels.ops`` pads).
    Returns (e, d_new), each (1, N) f32.
    """
    k, N = Ct.shape
    if N % block_n or block_n % 128:
        raise ValueError(f"N={N} must split into 128-aligned blocks of "
                         f"{block_n}")
    row = pl.BlockSpec((1, block_n), lambda i: (0, i))
    e, dnew = pl.pallas_call(
        _kernel,
        grid=(N // block_n,),
        in_specs=[
            row,
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((1, N), jnp.float32)] * 2,
        interpret=interpret,
    )(lcol, Ct, cj, dj, d)
    return e, dnew
