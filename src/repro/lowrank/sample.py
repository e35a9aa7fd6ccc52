"""Batched dual-space sampling for low-rank kernels (O(Nr) per draw).

The exact-DPP pipeline from ``sampling.batched`` transplanted to the
rank-r dual representation: phase 1 draws eigen-indices over the r dual
eigenvalues (Bernoulli for the DPP, the shared ESP recursion for the
k-DPP), phase 2 runs the same projection-DPP Gram–Schmidt chain rule as
``phase2_select_reference`` — with its own inverse CDF (a flat ``cumsum``
and ``searchsorted``, not the Kron engines' two-level row/column search,
so the two do not agree draw for draw) — and the
orthonormal basis lives in r-dimensional *coefficient* space and rows of
the implicit eigenvector matrix U = φ·E are projected through φ on
demand. Per selection step that is one O(r·k) row product and one O(Nr)
matvec; the N×N kernel and its N-dimensional eigenvectors never exist.

Memory note: the residual-norm initialization is a ``lax.scan`` over the
k_max selected columns accumulating a (batch, N) carry — the obvious
``((φΓ)²).sum(-1)`` would materialize a (batch, N, k_max) transient,
which at N = 65536 is hundreds of MB for nothing.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.phase2_select import EPS as _EPS
from ..kernels.phase2_select import MASS_EPS as _MASS_EPS
from ..sampling.batched import bernoulli_mask, draw_slots, resolve_runtime
from ..sampling.kdpp import _phase1_kdpp
from .dual import DualSpectrum


def _check_backend(backend: Optional[str]) -> None:
    if backend not in (None, "reference"):
        raise ValueError(
            f"the dual-space sampler has no fused engine; backend must be "
            f"None or 'reference', got {backend!r}")


def _phase2_dual_one(us: jax.Array, phi: jax.Array, Gamma: jax.Array,
                     k_eff: jax.Array) -> jax.Array:
    """Projection-DPP selection in r-dim coefficient space.

    Gamma (r, k_max) holds the selected eigenvectors' coefficient
    columns (invalid slots zeroed), so the implicit row i of the
    selected eigenvector matrix is U[i] = Γᵀφ_i. Same chain-rule loop,
    CGS2 re-orthogonalization, mass-exhaustion early exit and -1 padding
    as ``phase2_select_reference``; the inverse-CDF draw is a flat
    ``cumsum`` + ``searchsorted`` over the N items.
    """
    k_max = Gamma.shape[1]
    N = phi.shape[0]

    def _norm_step(acc, g):
        c = phi @ g                      # one (N,) column at a time
        return acc + c * c, None

    norms0, _ = jax.lax.scan(_norm_step, jnp.zeros((N,), phi.dtype),
                             Gamma.T)
    B0 = jnp.zeros((k_max, k_max), phi.dtype)
    picks0 = jnp.full((k_max,), -1, jnp.int32)

    def cond(state):
        t, alive = state[0], state[1]
        return (t < k_eff) & alive

    def body(state):
        t, _, B, norms, picks = state
        csum = jnp.cumsum(norms)
        alive = csum[-1] > _MASS_EPS
        i = jnp.searchsorted(csum, us[t] * csum[-1], side="right")
        i = jnp.minimum(i, N - 1).astype(jnp.int32)
        w = Gamma.T @ phi[i]             # row U[i], O(r k)
        qv = w - B @ (B.T @ w)
        qv = qv - B @ (B.T @ qv)         # CGS2: second pass kills drift
        qn2 = jnp.sum(qv * qv)
        qv = jnp.where(qn2 > _EPS,
                       qv / jnp.sqrt(jnp.maximum(qn2, _EPS)), 0.0)
        ct = phi @ (Gamma @ qv)          # U q, O(Nr)
        norms_new = jnp.maximum(norms - ct * ct, 0.0).at[i].set(0.0)
        norms = jnp.where(alive, norms_new, norms)
        B = jnp.where(alive, B.at[:, t].set(qv), B)
        picks = jnp.where(alive, picks.at[t].set(i), picks)
        return t + 1, alive, B, norms, picks

    _, _, _, _, picks = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), jnp.asarray(True),
                     B0, norms0, picks0))
    return picks


def _phase1_dual_one(key: jax.Array, mask_rule, E: jax.Array, k_slots: int):
    """One draw's spectrum phase on the r dual eigenvalues:
    (us, Gamma (r, k_slots), k_eff, truncated)."""
    us, sel, valid, k_eff, truncated = draw_slots(key, mask_rule, k_slots)
    Gamma = E[:, sel] * valid[None, :].astype(E.dtype)
    return us, Gamma, k_eff, truncated


def _phase2_dual(us, phi, Gammas, k_eff):
    return jax.vmap(
        lambda u, G, ke: _phase2_dual_one(u, phi, G, ke))(us, Gammas, k_eff)


@functools.partial(jax.jit, static_argnames=("k_max",))
def _sample_dual(keys, phi, log_lams, E, k_max):
    us, Gammas, k_eff, truncated = jax.vmap(lambda key: _phase1_dual_one(
        key, lambda k1: bernoulli_mask(k1, log_lams), E, k_max))(keys)
    return _phase2_dual(us, phi, Gammas, k_eff), k_eff, truncated


@functools.partial(jax.jit, static_argnames=("k",))
def _sample_dual_kdpp(keys, phi, log_lams, E, k):
    us, Gammas, k_eff, _ = jax.vmap(lambda key: _phase1_dual_one(
        key, lambda k1: _phase1_kdpp(k1, log_lams, k), E, k))(keys)
    return _phase2_dual(us, phi, Gammas, k_eff)


def sample_dual_keyed(row_keys: jax.Array, dual: DualSpectrum, k_max: int,
                      backend: Optional[str] = None, runtime=None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact low-rank DPP draws from per-row PRNG keys.

    Same contract as ``sample_krondpp_keyed``: (picks (B, k_max) int32
    with -1 padding, counts (B,) int32, truncated (B,) bool). Row i is a
    function of ``row_keys[i]`` alone (batching-invariant), so the async
    serving tier's per-(tenant, seq, row) keying reproduces draws no
    matter how traffic coalesced. The draw runs through
    ``runtime.map_keys`` (default ``Local()``) with the dual
    factorization flowing through operands.
    """
    _check_backend(backend)
    return resolve_runtime(runtime).map_keys(
        lambda ks, ops: _sample_dual(ks, *ops, int(k_max)),
        row_keys, operands=(dual.phi, dual.log_eigenvalues(), dual.basis()),
        static_key=("sample_dual", int(k_max)))


def sample_dual_kdpp_keyed(row_keys: jax.Array, dual: DualSpectrum, k: int,
                           backend: Optional[str] = None, runtime=None
                           ) -> jax.Array:
    """Exact low-rank k-DPP draws from per-row keys: (B, k) int32 picks,
    exactly min(k, dual rank) distinct items per row, -1 padded."""
    _check_backend(backend)
    return resolve_runtime(runtime).map_keys(
        lambda ks, ops: _sample_dual_kdpp(ks, *ops, int(k)),
        row_keys, operands=(dual.phi, dual.log_eigenvalues(), dual.basis()),
        static_key=("sample_dual_kdpp", int(k)))
