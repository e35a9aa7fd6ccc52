"""Distributed KrK-Picard — the paper's learner scaled over the mesh.

Parallel decomposition (beyond the paper, which is single-node MATLAB):
  * Θ-statistics (the A and C matrices of Appendix B) are SUMS over training
    subsets → shard the subset batch over the data axes and psum the per-
    shard A/C (shard_map; one (N1² + N2²)-sized all-reduce per sweep).
  * The closed-form (I+L)^{-1} contractions need only the factor
    eigendecompositions (N1³ + N2³ flops) → replicated (off critical path).
  * Updates are rank-N1/N2 symmetric products → done replicated after psum.

This keeps per-device work at O((n/P)(κ³ + κ²·max(N1,N2))) and communication
at O(N) per sweep — the paper's stochastic memory bound, fleet-wide.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dpp import SubsetBatch
from .krk_picard import _alpha_beta, _subset_AC


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: outputs declared
    replicated are replicated by construction (psum'd statistics,
    replicated factor updates). ``repro.dpp.runtime`` uses it too."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_distributed_krk_step(mesh: Mesh, data_axes=("data",),
                              shard_updates: bool = True,
                              fresh_spectrum: bool = True):
    """Returns a jitted (L1, L2, batch, a) -> (L1', L2') step.

    The subset batch must be sharded over `data_axes` on dim 0 (n must divide
    the axis size product).

    Beyond-paper performance knobs (EXPERIMENTS.md §Perf P3):
      shard_updates:  shard the O(N_i^3) update matmuls (L_i@X@L_i and the
        P diag P^T reconstructions) over the "model" axis instead of
        replicating them — divides their flops+bytes by the TP degree at the
        cost of one (N_i^2)-sized all-gather each.
      fresh_spectrum: paper-faithful recomputation of eigh(L1) after the L1
        update, used by the L2 update. False reuses the pre-update spectrum
        (one fewer N^{3/2} eigendecomposition per sweep); ascent is then no
        longer guaranteed by Thm 3.2 but holds empirically (validated in
        tests/test_distributed.py).
    """
    spec_b = P(data_axes)
    spec_r = P()
    tp = "model" if "model" in mesh.axis_names else None

    def _sh(x, col_sharded: bool):
        if not (shard_updates and tp):
            return x
        spec = P(None, tp) if col_sharded else P()
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def local_AC(L1, L2, indices, mask):
        A, C = jax.vmap(lambda i, m: _subset_AC(L1, L2, i, m))(indices, mask)
        # mean over the GLOBAL batch: local sum / global count, then psum
        n_local = indices.shape[0]
        A = jax.lax.psum(A.sum(0), data_axes)
        C = jax.lax.psum(C.sum(0), data_axes)
        n_global = jax.lax.psum(jnp.asarray(n_local, jnp.float32), data_axes)
        return A / n_global, C / n_global

    shard_AC = shard_map_unchecked(
        local_AC, mesh,
        in_specs=(spec_r, spec_r, spec_b, spec_b),
        out_specs=(spec_r, spec_r))

    def update_factor(L, X, P_, d, coef, a, N_other):
        """L + a/N_other (L X L - P diag(coef) P^T), matmuls TP-sharded."""
        LX = _sh(L @ _sh(X, True), True)
        LXL = LX @ L
        recon = _sh(P_ * coef[None, :], True) @ P_.T
        Ln = L + (a / N_other) * (LXL - recon)
        return 0.5 * (Ln + Ln.T)

    @jax.jit
    def step(L1, L2, batch: SubsetBatch, a: float = 1.0):
        N1, N2 = L1.shape[0], L2.shape[0]
        A, C0 = shard_AC(L1, L2, batch.indices, batch.mask)
        d1, P1 = jnp.linalg.eigh(L1)
        d2, P2 = jnp.linalg.eigh(L2)
        alpha, _ = _alpha_beta(d1, d2)
        L1n = update_factor(L1, A, P1, d1, d1 ** 2 * alpha, a, N2)

        if fresh_spectrum:
            _, C = shard_AC(L1n, L2, batch.indices, batch.mask)
            d1n, _ = jnp.linalg.eigh(L1n)
        else:
            C = C0                       # stale-A/C and stale-spectrum variant
            d1n = d1
        _, beta = _alpha_beta(d1n, d2)
        L2n = update_factor(L2, C, P2, d2, beta, a, N1)
        return L1n, L2n

    return step


def shard_subsets(mesh: Mesh, batch: SubsetBatch, data_axes=("data",)
                  ) -> SubsetBatch:
    """Place a subset batch sharded over the data axes on dim 0 (all
    fields, including the optional truncation provenance). The one
    batch-sharding helper — ``runtime.Mesh.shard_batch`` delegates here."""
    sh = NamedSharding(mesh, P(data_axes))
    trunc = getattr(batch, "truncated", None)
    return SubsetBatch(jax.device_put(batch.indices, sh),
                       jax.device_put(batch.mask, sh),
                       None if trunc is None else jax.device_put(trunc, sh))


def shard_select_no_replace(key, n: int, m: int) -> jax.Array:
    """(m,) uniform without-replacement indices into [0, n) — a partial
    Fisher-Yates shuffle (``fori_loop`` of randint swaps), NOT
    ``jax.random.choice``.

    Deliberate: ``choice(replace=False)`` / ``permutation`` lower to a
    sort of random keys, and on jax 0.4.x the SPMD partitioner miscompiles
    sort-based ops on shard-varying values inside ``jit(shard_map(...))``
    — the selected rows feed downstream consumers garbage while the
    selection itself reads back correctly (verified empirically under 8
    forced host devices; eager shard_map agrees with the host chain, the
    jitted one does not). The swap loop uses only randint + point
    updates, which partition correctly. Host code replaying a shard's
    selection must call THIS function with ``fold_in(key, shard_index)``
    (see tests/test_runtime.py).
    """
    if m > n:
        raise ValueError(f"cannot draw {m} rows without replacement from "
                         f"a population of {n}")
    idx = jnp.arange(n, dtype=jnp.int32)

    def body(t, state):
        idx, key = state
        key, sub = jax.random.split(key)
        j = jax.random.randint(sub, (), t, n)
        vi, vj = idx[t], idx[j]
        return idx.at[t].set(vj).at[j].set(vi), key

    idx, _ = jax.lax.fori_loop(0, m, body, (idx, key))
    return idx[:m]


def _data_shards(mesh: Mesh, data_axes) -> int:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in data_axes:
        out *= shape[a]
    return out


def make_distributed_krk_sweep(mesh: Mesh, schedule, data_axes=("data",),
                               minibatch_size=None, fresh_theta: bool = True):
    """The full KrK-Picard sweep of ``learning.engine._krk_sweep`` as ONE
    ``shard_map`` region over the data axes — the mechanism behind the
    ``repro.dpp.runtime.Mesh`` learning mode.

    Returns a jitted ``(L1, L2, indices, mask, key, a_trial) ->
    (L1', L2', a_accepted, n_backtracks)`` with ``indices``/``mask``
    sharded over ``data_axes`` on dim 0 and everything else replicated.

    What runs where (closing the two distributed ROADMAP items the plain
    ``make_distributed_krk_step`` could not):

      * **per-shard minibatches** (``minibatch_size``): each data shard
        draws its share (``minibatch_size / P`` rows) of the sweep's
        minibatch from its local rows via ``shard_select_no_replace`` on
        ``fold_in(key, shard_index)`` — the stochastic path finally
        scales past one device instead of consuming the full sharded
        batch every sweep. The key chain is deterministic and
        host-replayable (see tests/test_runtime.py).
      * **Armijo backtracking**: the acceptance log-likelihood is the
        per-shard subset-logdet sum ``psum``'d over the data axes, so the
        backtracking ``while_loop`` sees the GLOBAL sweep objective and
        every shard takes identical accept/shrink branches — the mesh
        mode regains the Thm 3.2 PSD + ascent guarantee (and the
        constant/1-√t/Armijo schedule parity) of the local engine.

    Θ-statistics are psum'd exactly as in ``make_distributed_krk_step``;
    factor eigendecompositions and updates run replicated.
    """
    from ..learning import schedules as schedules_mod
    from ..learning.objective import (logdet_I_plus_kron,
                                      subset_logdets_factored)

    shards = _data_shards(mesh, data_axes)
    if minibatch_size is not None and minibatch_size % shards:
        raise ValueError(
            f"minibatch_size={minibatch_size} must divide evenly over the "
            f"{shards} data shards (each shard draws its share locally)")
    mb_local = (minibatch_size // shards) if minibatch_size else None
    armijo = schedule.kind == "armijo"
    spec_b = P(data_axes)
    spec_r = P()

    def local_sweep(L1, L2, indices, mask, key, a_trial):
        N1, N2 = L1.shape[0], L2.shape[0]
        if mb_local is not None:
            sid = jnp.zeros((), jnp.int32)
            for ax in data_axes:
                sid = sid * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
            sel = shard_select_no_replace(jax.random.fold_in(key, sid),
                                          indices.shape[0], mb_local)
            indices, mask = indices[sel], mask[sel]
        sub = SubsetBatch(indices, mask)
        n_glob = jax.lax.psum(
            jnp.asarray(indices.shape[0], jnp.float32), data_axes)

        def dist_ll(factors):
            s = jax.lax.psum(
                jnp.sum(subset_logdets_factored(factors, sub)), data_axes)
            return s / n_glob - logdet_I_plus_kron(factors)

        def dist_AC(L1_, L2_):
            A, C = jax.vmap(
                lambda i, m: _subset_AC(L1_, L2_, i, m))(sub.indices,
                                                         sub.mask)
            return (jax.lax.psum(A.sum(0), data_axes) / n_glob,
                    jax.lax.psum(C.sum(0), data_axes) / n_glob)

        # -- op-for-op the engine's _krk_sweep, on psum'd statistics ----
        A, C0 = dist_AC(L1, L2)
        d1, P1 = jnp.linalg.eigh(L1)
        d2, P2 = jnp.linalg.eigh(L2)
        alpha, beta0 = _alpha_beta(d1, d2)
        G1 = L1 @ A @ L1 - (P1 * (d1 ** 2 * alpha)[None, :]) @ P1.T

        def upd1(a):
            Ln = L1 + (a / N2) * G1
            return 0.5 * (Ln + Ln.T)

        if armijo:
            ll_ref = dist_ll((L1, L2))
            L1n, ll1, a1, bt1 = schedules_mod.armijo_halfstep(
                schedule, upd1, lambda M: dist_ll((M, L2)), ll_ref, a_trial)
        else:
            L1n, a1, bt1 = upd1(a_trial), a_trial, jnp.zeros((), jnp.int32)

        if fresh_theta:
            _, C = dist_AC(L1n, L2)
            _, beta = _alpha_beta(jnp.linalg.eigvalsh(L1n), d2)
        else:
            C, beta = C0, beta0
        G2 = L2 @ C @ L2 - (P2 * beta[None, :]) @ P2.T

        def upd2(a):
            Ln = L2 + (a / N1) * G2
            return 0.5 * (Ln + Ln.T)

        if armijo:
            L2n, _, a2, bt2 = schedules_mod.armijo_halfstep(
                schedule, upd2, lambda M: dist_ll((L1n, M)), ll1, a_trial)
            return L1n, L2n, jnp.minimum(a1, a2), bt1 + bt2
        return L1n, upd2(a_trial), a_trial, jnp.zeros((), jnp.int32)

    sweep = shard_map_unchecked(
        local_sweep, mesh,
        in_specs=(spec_r, spec_r, spec_b, spec_b, spec_r, spec_r),
        out_specs=(spec_r, spec_r, spec_r, spec_r))
    return jax.jit(sweep)
