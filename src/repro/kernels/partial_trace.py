"""Pallas TPU kernel for the Appendix-B partial-trace contractions:

    A[k,l] = Tr(Θ_(kl) · L2) = Σ_{u,v} Θ4[k,u,l,v] L2[v,u]      (B.1)
    C[u,v] = Σ_{i,j} L1[i,j] Θ4[i,u,j,v]                        (B.2)

These are the batch-mode hot spots of KrK-Picard once Θ is materialized
(O(N²) data → memory-bound; the kernel's job is to stream Θ HBM→VMEM in
tiling-aligned 2-D blocks).

One kernel serves both. (B.2) is (B.1) on the transposed blocks:
C = A'(Θ4.transpose(1, 0, 3, 2), L1ᵀ), so ``kernels.ops`` computes C by
handing this kernel Θ with its factor axes swapped.

Layout: Θ is the 2-D (N1·N2p, N1·N2p) matrix of N2p × N2p blocks, N2p the
trailing factor size padded to the 128-lane tiling (zero rows/columns add
nothing). Grid step (i, j) reads the (bk·N2p, bl·N2p) tile holding the
blocks (k, l) ∈ [i·bk, (i+1)·bk) × [j·bl, (j+1)·bl), multiplies it
elementwise by the resident tiling of L2ᵀ, and reduces each N2p × N2p block
to a scalar with two indicator matmuls (E_r · S · E_c) at full f32
precision. The (bk, bl) result lands in an (N1/bk, N1/bl, bk, bl) output
whose block is the whole trailing pair of dims, so any bk, bl dividing N1
is a legal block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(theta_ref, w_ref, o_ref, *, n2p: int):
    s = theta_ref[...] * w_ref[...]                # (bk·n2p, bl·n2p)
    bk, bl = o_ref.shape[2], o_ref.shape[3]
    er = (jax.lax.broadcasted_iota(jnp.int32, (bk, bk * n2p), 1) // n2p
          == jax.lax.broadcasted_iota(jnp.int32, (bk, bk * n2p), 0))
    ec = (jax.lax.broadcasted_iota(jnp.int32, (bl * n2p, bl), 0) // n2p
          == jax.lax.broadcasted_iota(jnp.int32, (bl * n2p, bl), 1))
    rows = jnp.dot(er.astype(jnp.float32), s, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)
    o_ref[0, 0] = jnp.dot(rows, ec.astype(jnp.float32), precision=_HIGHEST,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bk", "bl", "interpret"))
def partial_trace_pallas(theta4: jax.Array, M: jax.Array, bk: int = 1,
                         bl: int = 1, interpret: bool = False) -> jax.Array:
    """A[k,l] = Σ_{u,v} theta4[k,u,l,v] M[v,u].

    theta4: (N1, N2p, N1, N2p) with N2p a multiple of 128 (or the whole
    trailing size, e.g. under interpret); M: (N2p, N2p); bk, bl divide N1.
    Returns A: (N1, N1) f32.
    """
    N1, N2p = theta4.shape[0], theta4.shape[1]
    if N1 % bk or N1 % bl:
        raise ValueError(f"tiles ({bk}, {bl}) must divide N1={N1}")
    theta = theta4.reshape(N1 * N2p, N1 * N2p).astype(jnp.float32)
    w = jnp.tile(M.T.astype(jnp.float32), (bk, bl))
    out = pl.pallas_call(
        functools.partial(_kernel, n2p=N2p),
        grid=(N1 // bk, N1 // bl),
        in_specs=[
            pl.BlockSpec((bk * N2p, bl * N2p), lambda i, j: (i, j)),
            pl.BlockSpec((bk * N2p, bl * N2p), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bk, bl), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N1 // bk, N1 // bl, bk, bl),
                                       jnp.float32),
        interpret=interpret,
    )(theta, w)
    return out.transpose(0, 2, 1, 3).reshape(N1, N1)
