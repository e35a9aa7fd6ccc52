"""Exact k-DPP sampling on the factored spectrum (Kulesza & Taskar Alg. 8).

A k-DPP conditions the DPP on |Y| = k. Phase 1 draws which eigenvectors
survive using elementary symmetric polynomials (ESPs): going over the N
eigenvalues from last to first, eigenvalue n is kept with

    P(include) = λ_n · e_{k-1}(λ_1..λ_{n-1}) / e_k(λ_1..λ_n),

k dropping by one on each inclusion — exactly k eigenvectors survive.
The ESP table e_j(λ_1..λ_n) is the O(N k) recursion e_j^n = e_j^{n-1} +
λ_n e_{j-1}^{n-1}, computed in log-space (ESPs of 10^4+ eigenvalues
overflow float range long before N does).

The draw takes k vector passes, not N serial steps. Between two
inclusions the count j still to keep is fixed, and item n is rejected
with 1 - p_n = e_j^{n-1} / e_j^n (e_j^n = e_j(λ_1..λ_n)). Those factors
telescope: below the last kept item M + 1, the next one kept is m with
probability λ_m e_{j-1}^{m-1} / e_j^M. Each decision reads only its own
uniform and j, so the next kept item is the largest index below the last
kept one whose uniform falls under its keep probability at j: one masked
compare-and-max over the N items per kept item, on a (N, k+1) table of
every keep probability. On the same uniforms these are the serial
scan's decisions, bit for bit.

Phase 2 is shared with ``batched.py``: lazy factored eigenvector gather,
then one batched ``kernels.ops.phase2_select`` call (fused Pallas kernel
on TPU, jax reference elsewhere), so the whole thing is jit/vmap clean.

The spectrum is factored — only the O(N) product eigenvalues are ever
built, never the N eigenvectors — so a KronDPP k-DPP costs
O(sum N_i^3 + N k) setup instead of O(N^3). A dense kernel is the m=1
case (``sample_kdpp_dense``), which is what the serving layer uses for
stochastic KV-cache eviction.

The keep-probability table depends only on the spectrum and k, so it
is built once per (spectrum, k): ``kdpp_keep_table`` in a jitted call of
its own, held in the ``FactorSpectrum``'s memo (shared by its placed
copies), and handed to the jitted draw as an operand. Each host-level
call of ``sample_kdpp_batched`` counts ``dpp.kdpp.esp_builds`` as 1 when
it built the table and 0 when the memo held it, and a hit also as
``dpp.kdpp.esp_hits``; the low-rank dual builds its r-row table inside
the draw and counts 1 a call. Under the default ``NullTracker`` the
counters are no-ops. ``sample_kdpp_dense`` (an eigh inside the trace)
builds its table in the trace.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..kernels import ops as kernel_ops
from .batched import draw_slots, gather_factor_columns, resolve_runtime
from .spectral import FactorSpectrum, log_product_spectrum

_NEG_INF = -jnp.inf


def log_esp_table(log_lam: jax.Array, k: int) -> jax.Array:
    """log e_j(λ_1..λ_n) for n = 0..N, j = 0..k — shape (N+1, k+1).

    log_lam may contain -inf (zero eigenvalues); the recursion is pure
    logaddexp so those contribute nothing.
    """
    row0 = jnp.full((k + 1,), _NEG_INF).at[0].set(0.0)

    def body(prev, ll):
        new = prev.at[1:].set(jnp.logaddexp(prev[1:], prev[:-1] + ll))
        return new, new

    _, rows = jax.lax.scan(body, row0, log_lam)
    return jnp.concatenate([row0[None], rows], axis=0)


def kdpp_keep_table(log_lam: jax.Array, k: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Phase 1's per-spectrum state: ``(P, k0)``.

    ``P[m, j]`` (N, k+1) is item m's keep probability with j items still
    to keep, λ_m e_{j-1}(λ_1..λ_{m-1}) / e_j(λ_1..λ_m), 0 at j = 0 and
    where e_j is 0. ``k0 = min(k, rank)`` () int32 is the size of the
    draw: |Y| = k conditions on a zero-probability event when the kernel
    has fewer than k nonzero eigenvalues (every e_k denominator is -inf)
    — an unclamped draw would degenerate to the empty mask — so below
    rank the draw takes the largest achievable size and phase 2 pads the
    remaining row slots with -1. Depends only on the spectrum and k."""
    table = log_esp_table(log_lam, k)
    k0 = jnp.minimum(jnp.asarray(k, jnp.int32),
                     jnp.sum(jnp.isfinite(log_lam)).astype(jnp.int32))
    j = jnp.arange(k + 1)
    log_num = log_lam[:, None] + table[:-1, jnp.maximum(j - 1, 0)]
    log_den = table[1:]
    P = jnp.exp(jnp.minimum(log_num - log_den, 0.0))
    P = jnp.where((j > 0) & jnp.isfinite(log_den), P, 0.0)
    return P, k0


def kdpp_mask(key: jax.Array, P: jax.Array, k0: jax.Array,
              k: int) -> jax.Array:
    """Conditional eigenvalue draw from ``kdpp_keep_table``'s ``(P, k0)``:
    (N,) bool mask with exactly k0 set.

    Item m (0-based) is decided by ``u[N-1-m]`` against ``P[m, j]``, its
    keep probability with j items still to keep. Trip t of k looks for the
    largest m below the last kept item with ``u < P[m, k0 - t]``: every
    item in between was rejected at that same count, as the serial scan
    from m = N-1 down would reject it. A trip that finds none ends the
    draw (``live``), since the scan would then reject every item left. The
    count is the same in every row on a trip, so under a batch ``vmap``
    the column of ``P`` is one unbatched slice."""
    N = P.shape[0]
    u = jax.random.uniform(key, (N,))[::-1]       # u[m] decides item m
    m = jnp.arange(N, dtype=jnp.int32)

    def trip(t, carry):
        mask, limit, live = carry
        k_rem = k0 - t
        p = jax.lax.dynamic_index_in_dim(P, jnp.maximum(k_rem, 0), axis=1,
                                         keepdims=False)
        hit = jnp.max(jnp.where((u < p) & (m < limit), m, -1))
        take = live & (k_rem > 0) & (hit >= 0)
        return mask | (take & (m == hit)), jnp.where(take, hit, limit), take

    mask, _, _ = jax.lax.fori_loop(
        0, k, trip, (jnp.zeros((N,), bool), jnp.int32(N), jnp.bool_(True)))
    return mask


def _phase1_kdpp(key: jax.Array, log_lam: jax.Array, k: int) -> jax.Array:
    """The conditional eigenvalue draw with its table built in the trace
    (``kdpp_keep_table`` then ``kdpp_mask``): for callers whose spectrum
    exists only inside the trace, or differs per call."""
    P, k0 = kdpp_keep_table(log_lam, k)
    return kdpp_mask(key, P, k0, k)


def keep_table_of(lams: Tuple[jax.Array, ...], k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """``kdpp_keep_table`` of the product spectrum of ``lams``, as a
    program of its own: what ``FactorSpectrum.kdpp_keep_table`` memoizes.

    The compiled program is keyed on the ESP recursion bound to
    ``log_esp_table`` at call time, so a rebound one (the k-DPP cell's
    planted fault, ``bench/kdpp_control.py``) is traced anew instead of
    being served from the cache of the sound one, and back again."""
    return _keep_table_program(tuple(lams), int(k), log_esp_table)


@functools.partial(jax.jit, static_argnames=("k", "esp"))
def _keep_table_program(lams, k, esp):
    del esp                  # read by kdpp_keep_table; keys the program
    return kdpp_keep_table(log_product_spectrum(lams), k)


def _phase1_one_kdpp(key: jax.Array, vecs: Tuple[jax.Array, ...],
                     P: jax.Array, k0: jax.Array, k: int):
    """One sample's conditional spectrum draw: (us, columns, k_eff)."""
    sizes = tuple(v.shape[0] for v in vecs)
    # the ESP draw sets at most k entries, so no truncation is possible;
    # below numerical rank it sets fewer and phase 2 pads with -1
    us, sel, valid, k_eff, _ = draw_slots(
        key, lambda k1: kdpp_mask(k1, P, k0, k), k)
    return us, gather_factor_columns(vecs, sizes, sel, valid), k_eff


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def _sample_kdpp_batched(keys, vecs, P, k0, k, backend=None):
    sizes = tuple(v.shape[0] for v in vecs)
    us, Gs, k_eff = jax.vmap(
        lambda kk: _phase1_one_kdpp(kk, vecs, P, k0, k))(keys)
    return kernel_ops.phase2_select(us, Gs, sizes, k_eff, backend=backend)


def sample_kdpp_batched(key: jax.Array, spectrum: FactorSpectrum, k: int,
                        num_samples: int = 1,
                        backend: Optional[str] = None,
                        runtime=None) -> jax.Array:
    """``num_samples`` exact k-DPP samples in one device call.

    Returns (num_samples, k) int32 — every row has exactly k distinct
    items when the kernel has rank >= k; below rank the draw degrades to
    exactly rank distinct items with trailing -1 padding (never
    duplicates, never an empty degenerate row). Phase 2 for the whole batch
    is one ``kernels.ops.phase2_select`` call (fused Pallas kernel on TPU;
    ``backend`` forces an engine). Under a ``repro.dpp.runtime`` mesh
    runtime the key batch is sharded over the data axes and draws match
    the single-device call bit-for-bit on shared keys.
    """
    tracker = obs.current_tracker()
    keys = jax.random.split(key, num_samples)
    # duck-typed dispatch, as in sample_krondpp_keyed: low-rank dual
    # spectra run the conditional draw on their r dual eigenvalues
    kdpp_hook = getattr(spectrum, "sample_rows_kdpp", None)
    if kdpp_hook is not None:
        tracker.counter("dpp.kdpp.esp_builds")
        return kdpp_hook(keys, int(k), backend=backend, runtime=runtime)
    (P, k0), built = spectrum.kdpp_keep_table(k)
    # 0 on a hit too, so a window of hits reads 0 builds, not no counter
    tracker.counter("dpp.kdpp.esp_builds", int(built))
    if not built:
        tracker.counter("dpp.kdpp.esp_hits")
    return resolve_runtime(runtime).map_keys(
        lambda ks, ops: _sample_kdpp_batched(ks, *ops, int(k), backend),
        keys, operands=(tuple(spectrum.vecs), P, k0),
        static_key=("sample_kdpp_batched", int(k), backend))


def sample_kdpp_dense(key: jax.Array, L: jax.Array, k: int) -> jax.Array:
    """Exact k-DPP sample from a dense kernel, fully jit/vmap-able.

    The eigendecomposition happens inside the trace (m=1 spectrum), so this
    composes with vmap over per-head kernels in the serving layer. Phase 2
    stays on the vmap-transparent reference engine.
    """
    lam, vec = jnp.linalg.eigh(L)
    P, k0 = kdpp_keep_table(log_product_spectrum((jnp.maximum(lam, 0.0),)),
                            int(k))
    us, Gs, k_eff = _phase1_one_kdpp(key, (vec,), P, k0, int(k))
    return kernel_ops.phase2_select(us, Gs, (L.shape[0],), k_eff,
                                    backend="reference")
