"""Jit'd public wrappers around the Pallas kernels.

Handles padding to the TPU's (8, 128) tiling and VMEM-aware block-size
selection. On TPU every wrapper runs its kernel compiled — never in
interpret mode. Off TPU the wrappers run the jnp reference; tests force a
kernel (``force_pallas=True`` / ``backend="pallas"``) to run it in
interpret mode. Every decision emits a ``kernels.<op>.<engine>`` counter,
so a run can show which engine it compiled against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs
from . import ref
from .kron_matvec import kron_matvec_pallas
from .partial_trace import partial_trace_pallas
from .greedy_map import greedy_map_update_pallas
from .phase2_select import canonical_pair, padded_dims, phase2_select_pallas

_VMEM_BUDGET = 12 * 2 ** 20  # bytes we allow a single kernel tile set to claim


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _count_dispatch(op: str, engine: str) -> None:
    """Emit a ``kernels.<op>.<engine>`` counter at each dispatch decision.

    These wrappers usually run INSIDE a jit trace, so the counter fires
    once per compiled specialization (the decision point), not once per
    executed call — exactly what "which engine did this program compile
    against" wants, and a no-op side-effect-free call under the default
    ``NullTracker``. Only static config crosses the tracker boundary
    (never tracer values)."""
    obs.current_tracker().counter(f"kernels.{op}.{engine}")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# kron_matvec
# ---------------------------------------------------------------------------

def kron_matvec(A: jax.Array, B: jax.Array, X: jax.Array,
                force_pallas: bool = False) -> jax.Array:
    """Batched (A ⊗ B) X. X: (batch, N1*N2)."""
    N1, N2 = A.shape[0], B.shape[0]
    batch = X.shape[0]
    use_pallas = _on_tpu() or force_pallas
    _count_dispatch("kron_matvec", "pallas" if use_pallas else "reference")
    if not use_pallas:
        return ref.kron_matvec_ref(A, B, X)
    align = 128 if _on_tpu() else 1
    P1, P2 = _round_up(N1, align), _round_up(N2, align)
    bb = 1
    while bb < 8 and (bb * 2 * P1 * P2 * 2 + P1 * P1 + P2 * P2) * 4 <= _VMEM_BUDGET:
        bb *= 2
    Bp = _round_up(batch, bb)
    Ap = jnp.zeros((P1, P1), A.dtype).at[:N1, :N1].set(A)
    Bp_ = jnp.zeros((P2, P2), B.dtype).at[:N2, :N2].set(B)
    Xp = jnp.zeros((Bp, P1 * P2), X.dtype)
    Xp = Xp.at[:batch].set(
        jnp.pad(X.reshape(batch, N1, N2), ((0, 0), (0, P1 - N1), (0, P2 - N2))
                ).reshape(batch, P1 * P2))
    Y = kron_matvec_pallas(Ap, Bp_, Xp, block_batch=bb,
                           interpret=not _on_tpu())
    return Y[:batch].reshape(batch, P1, P2)[:, :N1, :N2].reshape(batch, N1 * N2)


def kron_eigvec_batch(P1: jax.Array, P2: jax.Array, i: jax.Array,
                      j: jax.Array, force_pallas: bool = False) -> jax.Array:
    """Columns of P1 ⊗ P2 at index pairs (i, j) — batched lazy eigenvector
    assembly for the sampling subsystem. i, j: (k,) int32. Returns (N, k).

    Identity: (P1 ⊗ P2) vec(e_i e_j^T) = vec(P1[:, i] P2[:, j]^T), so on
    TPU this reuses the ``kron_matvec`` Pallas path on the one-hot batch
    (two MXU matmuls); elsewhere the gather + outer product costs O(N k)
    instead of the matmul route's O(N (N1+N2) k).
    """
    N1, N2 = P1.shape[0], P2.shape[0]
    if _on_tpu() or force_pallas:
        E = jnp.zeros((i.shape[0], N1 * N2), P1.dtype)
        E = E.at[jnp.arange(i.shape[0]), i * N2 + j].set(1.0)
        return kron_matvec(P1, P2, E, force_pallas=force_pallas).T
    return (P1[:, i][:, None, :] * P2[:, j][None, :, :]).reshape(
        N1 * N2, i.shape[0])


# ---------------------------------------------------------------------------
# phase-2 projection-DPP selection (sampling hot path)
# ---------------------------------------------------------------------------

def _phase2_fits(N1: int, Nr: int, k: int) -> bool:
    """Whether the fused kernel's VMEM set fits the budget: the padded
    factor pair (double-buffered), the basis, and the (N1p, Nrp) norms
    grid plus the step's few same-shape temporaries. ``padded_dims``
    raises, for either engine, on a grid its picks cannot index."""
    N1p, Nrp, K = padded_dims(N1, Nr, k)
    need = (4 * (N1p + Nrp) * K + K * K + 6 * N1p * Nrp) * 4
    return need <= _VMEM_BUDGET


def phase2_select(us, Gs, sizes, k_eff, backend=None):
    """Projection-DPP phase-2 selection — the ops-level dispatch point.

    us:    (k_max,) or (B, k_max) per-step uniforms.
    Gs:    factored eigenvector columns, each (N_f, k_max) or
           (B, N_f, k_max) (``gather_factor_columns``).
    k_eff: () or (B,) int32 live step counts.
    Returns picks of shape us.shape, int32, -1 in padded/dead slots.

    backend: None — auto (fused Pallas kernel on TPU when its VMEM set
        fits, jax while_loop reference otherwise — the choice shows in
        the ``kernels.phase2_select.<engine>`` counter); "reference" —
        force the while_loop; "pallas" — force the fused kernel
        (interpret mode off-TPU, the CPU test path).
    Both backends run one step function on the same padded (G1, Gr)
    pair, so picks agree draw-for-draw on shared uniforms
    (property-tested in tests/test_phase2_fused.py).
    """
    got = tuple(int(G.shape[-2]) for G in Gs)
    if got != tuple(int(s) for s in sizes):
        raise ValueError(f"sizes {tuple(sizes)} inconsistent with the "
                         f"factor-column row counts {got}")
    N1, Nr = (1, got[0]) if len(got) == 1 else (got[0], 1)
    for n in got[1:]:
        Nr *= n
    fits = _phase2_fits(N1, Nr, int(us.shape[-1]))
    if backend is None:
        backend = "pallas" if _on_tpu() and fits else "reference"
    k_eff = jnp.asarray(k_eff, jnp.int32)
    batched = us.ndim == 2
    if backend == "reference":
        _count_dispatch("phase2_select", "reference")
        from ..sampling.batched import phase2_select_reference
        if not batched:
            return phase2_select_reference(us, Gs, sizes, k_eff)
        return jax.vmap(
            lambda u, G, ke: phase2_select_reference(u, G, sizes, ke)
        )(us, tuple(Gs), k_eff)
    if backend != "pallas":
        raise ValueError(f"phase2_select backend must be None, 'reference' "
                         f"or 'pallas', got {backend!r}")
    if not fits:
        raise ValueError(
            f"phase2_select fused kernel needs its resident set (norms "
            f"N1*Nr={N1}*{Nr}, factor pair, basis) inside the "
            f"{_VMEM_BUDGET >> 20}MiB VMEM budget; use "
            f"backend='reference' for this shape")
    _count_dispatch("phase2_select", "pallas")
    if not batched:
        Gs = tuple(G[None] for G in Gs)
        us, k_eff = us[None], k_eff[None]
    G1, Gr = canonical_pair(Gs)
    picks = phase2_select_pallas(us, k_eff, G1, Gr, interpret=not _on_tpu())
    return picks if batched else picks[0]


# ---------------------------------------------------------------------------
# partial traces (KrK-Picard batch route)
# ---------------------------------------------------------------------------

def _partial_trace_pallas(theta4: jax.Array, M: jax.Array) -> jax.Array:
    """out[k,l] = Σ_{u,v} theta4[k,u,l,v] M[v,u] on the kernel. On TPU the
    trailing size pads to the 128-lane tiling (zero blocks add nothing)
    and the (bk, bl) tile is the largest divisor pair of the leading size
    whose working set — the Θ tile double-buffered, the resident M tiling
    and the product — fits the VMEM budget."""
    n, m = theta4.shape[0], theta4.shape[1]
    mp = _round_up(m, 128) if _on_tpu() else m
    if mp != m:
        theta4 = jnp.pad(theta4, ((0, 0), (0, mp - m), (0, 0), (0, mp - m)))
        M = jnp.pad(M, ((0, mp - m), (0, mp - m)))
    block = 5 * mp * mp * 4
    divs = [d for d in range(1, n + 1) if n % d == 0]
    bk = max(d for d in divs if d * d * block <= _VMEM_BUDGET or d == 1)
    bl = max(d for d in divs if bk * d * block <= _VMEM_BUDGET or d == 1)
    return partial_trace_pallas(theta4, M, bk=bk, bl=bl,
                                interpret=not _on_tpu())


def partial_trace_A(theta: jax.Array, L2: jax.Array, N1: int, N2: int,
                    force_pallas: bool = False) -> jax.Array:
    """A[k,l] = Σ_{u,v} Θ4[k,u,l,v] L2[v,u], (N1, N1)."""
    theta4 = theta.reshape(N1, N2, N1, N2)
    if not (_on_tpu() or force_pallas):
        _count_dispatch("partial_trace_A", "reference")
        return ref.partial_trace_A_ref(theta4, L2)
    _count_dispatch("partial_trace_A", "pallas")
    return _partial_trace_pallas(theta4, L2)


def partial_trace_C(theta: jax.Array, L1: jax.Array, N1: int, N2: int,
                    force_pallas: bool = False) -> jax.Array:
    """C[u,v] = Σ_{i,j} L1[i,j] Θ4[i,u,j,v], (N2, N2). The kernel runs it
    as the A contraction on Θ with its factor axes swapped, against L1ᵀ."""
    theta4 = theta.reshape(N1, N2, N1, N2)
    if not (_on_tpu() or force_pallas):
        _count_dispatch("partial_trace_C", "reference")
        return ref.partial_trace_C_ref(theta4, L1)
    _count_dispatch("partial_trace_C", "pallas")
    return _partial_trace_pallas(theta4.transpose(1, 0, 3, 2), L1.T)


# ---------------------------------------------------------------------------
# greedy MAP (k-DPP) built on the Pallas step kernel
# ---------------------------------------------------------------------------

def greedy_map_update(lcol, Ct, cj, dj, d, force_pallas: bool = False):
    """One fast-greedy update: lcol (N,), transposed Cholesky buffer
    Ct (k, N), its chosen column cj (k,), dj (1,), d (N,) ->
    (e, d_new), each (N,) f32. The kernel path needs N a multiple of 128
    (``greedy_map_kdpp`` pads its state once)."""
    if not (_on_tpu() or force_pallas):
        _count_dispatch("greedy_map_update", "reference")
        return ref.greedy_map_update_ref(lcol, Ct, cj, dj, d)
    _count_dispatch("greedy_map_update", "pallas")
    k, N = Ct.shape
    bn = 128     # per step: k Ct rows + 4 vectors, double-buffered, f32
    while N % (2 * bn) == 0 and (k + 4) * 2 * (2 * bn) * 4 <= _VMEM_BUDGET:
        bn *= 2
    e, d_new = greedy_map_update_pallas(lcol[None], Ct, cj[:, None],
                                        dj[None], d[None], block_n=bn,
                                        interpret=not _on_tpu())
    return e[0], d_new[0]


def greedy_map_kdpp(L: jax.Array, k: int, force_pallas: bool = False) -> jax.Array:
    """Full greedy MAP selection of k items using the step kernel.

    Equivalent to core.sampling.greedy_map_kdpp; this version routes the
    O(Nk) inner update through the Pallas kernel. The state is padded to
    a lane-aligned Np = round_up(N, 128) once: padded items start chosen
    with zero variance, so they are never picked and the per-step kernel
    call needs no padding copy.
    """
    N = L.shape[0]
    Np = _round_up(N, 128)
    eps = ref.degeneracy_eps(L)

    def body(state, t):
        d, Ct, chosen = state
        scores = jnp.where(chosen, -jnp.inf, d)
        j = jnp.argmax(scores)
        # Degenerate conditional variance (k beyond numerical rank): a raw
        # 1/sqrt(d_j) blows up e and poisons every later pick with NaN.
        # Clamp the divisor and zero the update so the pick stays a valid
        # index and the remaining state is untouched.
        ok = d[j] > eps
        lcol = jnp.pad(L[:, j].astype(jnp.float32), (0, Np - N))
        e, d_upd = greedy_map_update(
            lcol, Ct, Ct[:, j], jnp.maximum(d[j], eps)[None], d,
            force_pallas=force_pallas)
        e = jnp.where(ok, e, 0.0)
        d_new = jnp.where(ok, jnp.maximum(d_upd, 0.0), d)
        Ct_new = jax.lax.dynamic_update_index_in_dim(Ct, e, t, axis=0)
        return (d_new, Ct_new, chosen.at[j].set(True)), j

    d0 = jnp.pad(jnp.diagonal(L).astype(jnp.float32), (0, Np - N))
    Ct0 = jnp.zeros((k, Np), jnp.float32)
    (_, _, _), picks = jax.lax.scan(
        body, (d0, Ct0, jnp.arange(Np) >= N), jnp.arange(k))
    return picks.astype(jnp.int32)
