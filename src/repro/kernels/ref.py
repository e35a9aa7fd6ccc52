"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kron_matvec_ref(A: jax.Array, B: jax.Array, X: jax.Array) -> jax.Array:
    """Y[b] = (A ⊗ B) X[b] via the vec-trick in plain jnp.

    fp32 accumulation (matches the kernel's MXU accumulate contract)."""
    N1, N2 = A.shape[0], B.shape[0]
    X3 = X.reshape(X.shape[0], N1, N2)
    Y = jnp.einsum("ki,biu,vu->bkv", A, X3, B,
                   preferred_element_type=jnp.float32)
    return Y.reshape(X.shape[0], N1 * N2).astype(X.dtype)


def partial_trace_A_ref(theta4: jax.Array, L2: jax.Array) -> jax.Array:
    """A[k,l] = Σ_{u,v} Θ4[k,u,l,v] L2[v,u]."""
    return jnp.einsum("kulv,vu->kl", theta4, L2).astype(jnp.float32)


def partial_trace_C_ref(theta4: jax.Array, L1: jax.Array) -> jax.Array:
    """C[u,v] = Σ_{i,j} L1[i,j] Θ4[i,u,j,v]."""
    return jnp.einsum("iujv,ij->uv", theta4, L1).astype(jnp.float32)


def greedy_map_update_ref(lcol, Ct, cj, dj, d):
    """e = (L[:, j] - C @ C[j]) / sqrt(d_j), with Ct = C^T (k, N)."""
    e = (lcol - cj @ Ct) / jnp.sqrt(jnp.maximum(dj[0], 1e-12))
    return e.astype(jnp.float32), (d - e * e).astype(jnp.float32)


def degeneracy_eps(L: jax.Array) -> jax.Array:
    """Conditional-variance collapse threshold for greedy MAP, relative to
    the kernel's own scale (greedy MAP is scale-equivariant, so an absolute
    cutoff would zero every update for small-magnitude kernels). Shared by
    the reference and Pallas-routed greedy_map_kdpp implementations so the
    two paths cannot drift."""
    return 1e-8 * jnp.maximum(jnp.max(jnp.diagonal(L)), 1e-30)
