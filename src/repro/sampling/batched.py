"""Device-resident batched exact KronDPP sampling (paper Alg. 2 / Sec. 4).

The host sampler in ``core.sampling`` draws one subset at a time with numpy
control flow. Here the whole pipeline is fixed-shape jax, jit-compiled once
per (k_max, batch) shape:

phase 1  Bernoulli draw over the product spectrum, computed factor-wise as
         an O(N) log-eigenvalue vector (N eigenvectors are never
         materialized). The random |J| selected eigen-indices are compacted
         into a static (k_max,) slot array with a validity mask (one
         cumsum + k_max binary searches); draws whose |J| exceeds the
         static budget carry a truncation flag. ``vmap``-ped over the
         batch of PRNG keys.
phase 2  Lazy Kronecker eigenvectors kept in *factored* form — the m
         gathered factor-column blocks, O(sum N_i k) bytes — then the
         projection-DPP selection loop: the Gram-Schmidt chain rule on
         K = V V^T (cf. DPPy's ``proj_dpp_sampler_eig``; Gautier et al.
         2018) run in the k-dimensional coefficient space, so each step
         needs no QR and only one O(N)-output product off the factors.
         The whole batch goes through ``kernels.ops.phase2_select`` in ONE
         call: the fused Pallas kernel on TPU (state resident in VMEM
         across steps), or the ``lax.while_loop`` reference here
         (``phase2_select_reference``) elsewhere. Categorical draws are
         inverse-CDF on one uniform per step.

Everything is pure jax (no host callbacks), so the sampler runs where the
arrays live — CPU, GPU, or TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.kron import split_indices_multi
from ..kernels import ops as kernel_ops
from ..kernels.phase2_select import (canonical_pair, init_norms, pad_pair,
                                     select_step)
from .spectral import FactorSpectrum, log_product_spectrum


# ---------------------------------------------------------------------------
# Fixed-shape helpers (shared with kdpp.py)
# ---------------------------------------------------------------------------

def compact_selection(mask: jax.Array, k_max: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Indices of up to k_max True entries of mask, left-packed.

    Returns (sel (k_max,) int32, valid (k_max,) bool, truncated () bool).
    One O(N) cumsum + k_max binary searches (an argsort or scatter would
    cost far more on every backend); if more than k_max entries are set,
    the lowest indices win and ``truncated`` is True so callers can count
    clipped draws instead of silently serving them (callers size k_max so
    overflow is a many-sigma event — but it must be observable).
    """
    N = mask.shape[0]
    cs = jnp.cumsum(mask.astype(jnp.int32))
    ranks = jnp.arange(1, k_max + 1, dtype=jnp.int32)
    sel = jnp.searchsorted(cs, ranks, side="left")   # idx of c-th True
    valid = ranks <= cs[-1]
    truncated = cs[-1] > k_max
    return jnp.minimum(sel, N - 1).astype(jnp.int32), valid, truncated


def split_mixed_radix(sel: jax.Array, sizes: Tuple[int, ...]
                      ) -> Tuple[jax.Array, ...]:
    """Global eigen-indices -> per-factor column indices — the shared
    row-major convention (``kron.split_indices_multi``)."""
    return split_indices_multi(sel, sizes)


def gather_factor_columns(spectrum_vecs: Tuple[jax.Array, ...],
                          sizes: Tuple[int, ...], sel: jax.Array,
                          valid: jax.Array) -> Tuple[jax.Array, ...]:
    """The selected eigenvectors in *factored* form: G_f = P_f[:, idx_f],
    (N_f, k_max) each — O(sum N_f · k) gathered bytes instead of the O(N k)
    materialized Kronecker columns. Invalid slots are zeroed (in the first
    factor; the column products then vanish everywhere downstream).
    """
    parts = split_mixed_radix(sel, sizes)
    Gs = [P[:, p] for P, p in zip(spectrum_vecs, parts)]
    Gs[0] = Gs[0] * valid[None, :].astype(Gs[0].dtype)
    return tuple(Gs)


def assemble_eigvecs(spectrum_vecs: Tuple[jax.Array, ...],
                     sizes: Tuple[int, ...], sel: jax.Array,
                     valid: jax.Array) -> jax.Array:
    """Materialize the selected Kronecker eigenvectors, (N, k_max).

    The batched form of ``kron.kron_eigvec``: the factored columns
    (``gather_factor_columns``) folded by outer products, O(N k). The
    sampler itself stays in factored form and never builds this matrix;
    this is the reference assembly used by tests and by callers that want
    explicit eigenvectors.
    """
    Gs = gather_factor_columns(spectrum_vecs, sizes, sel, valid)
    V = Gs[0]
    for G in Gs[1:]:
        V = (V[:, None, :] * G[None, :, :]).reshape(-1, sel.shape[0])
    return V


def phase2_select_reference(us: jax.Array, Gs: Tuple[jax.Array, ...],
                            sizes: Tuple[int, ...], k_eff: jax.Array
                            ) -> jax.Array:
    """Projection-DPP selection from k_eff orthonormal Kronecker columns,
    given in factored form (``gather_factor_columns``) with one uniform
    per step in ``us``. Returns (k_max,) int32 picks, -1 in padded slots.

    This is the jax reference (and the CPU/GPU production path) that the
    fused Pallas kernel must match draw-for-draw: both canonicalize the
    factors to the (G1, Gr) pair, pad it to the same (8, 128)-aligned
    shapes (``kernels.phase2_select.pad_pair``) and run the same step
    function (``select_step``). For m >= 3 the trailing factors fold into
    one (N/N_1, k) block ONCE per sample.

    Chain rule on the marginal kernel K = V V^T, run in the k-dimensional
    coefficient space: selecting item i conditions the remaining process
    on the span of row V[i], so we Gram-Schmidt the selected *rows* into
    an orthonormal basis (one basis row per step, tiny) and downdate the
    per-item residual variances norms -= (V q_t)^2, held as an
    (N1, Nr) grid. V is never built. Categorical draws are inverse-CDF on
    the row-major prefix mass; selected items get exactly zero mass and
    are never drawn again.

    Degenerate spectra: numerically rank-deficient factors can exhaust
    the selectable mass while t < k_eff (total <= MASS_EPS); the loop
    then exits early with the remaining slots at -1.

    The loop is a ``while_loop`` bounded by the *data-dependent* k_eff
    (<= the static k_max): a typical draw has |J| well under the k_max
    tail bound, so under vmap the batch pays for its slowest lane rather
    than everyone running k_max masked steps.
    """
    G1, Gr = canonical_pair(Gs)
    N1, Nr = int(G1.shape[0]), int(Gr.shape[0])
    k_max = us.shape[0]
    us, g1, gr = pad_pair(us, G1, Gr)
    K = g1.shape[1]

    def cond(state):
        t, alive = state[0], state[1]
        return (t < k_eff) & alive

    def body(state):
        t, _, bt, norms, picks = state
        alive, i, norms_new, bt_new = select_step(t, us[t], g1, gr, norms,
                                                  bt, N1=N1, Nr=Nr)
        norms = jnp.where(alive, norms_new, norms)
        bt = jnp.where(alive, bt_new, bt)
        picks = jnp.where(alive, picks.at[t].set(i), picks)
        return t + 1, alive, bt, norms, picks

    _, _, _, _, picks = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), jnp.asarray(True),
                     jnp.zeros((K, K), jnp.float32), init_norms(g1, gr),
                     jnp.full((K,), -1, jnp.int32)))
    return picks[:k_max]


# ---------------------------------------------------------------------------
# The batched sampler
# ---------------------------------------------------------------------------

def bernoulli_mask(key: jax.Array, log_lam: jax.Array) -> jax.Array:
    """The DPP's spectrum draw: eigen-index n is kept with probability
    λ/(1+λ) = sigmoid(log λ), on the log-space fold so a huge product
    spectrum never overflows to NaN probabilities."""
    u = jax.random.uniform(key, log_lam.shape)
    return u < jax.nn.sigmoid(log_lam)


def draw_slots(key: jax.Array, mask_rule, k_slots: int):
    """Phase 1 of one draw, shared by every sampler: the eigen-index mask
    from ``mask_rule(subkey)`` (``bernoulli_mask``, or the k-DPP's
    ``kdpp._phase1_kdpp``), compacted into ``k_slots`` static slots, and
    the phase-2 uniforms, one per slot.

    Returns (us (k_slots,), sel (k_slots,) int32, valid (k_slots,) bool,
    k_eff () int32 = min(|mask|, k_slots), truncated () bool). The first
    half of ``jax.random.split(key)`` feeds the mask and the second the
    uniforms: every draw's picks depend on that order.
    """
    k1, k2 = jax.random.split(key)
    mask = mask_rule(k1)
    sel, valid, truncated = compact_selection(mask, k_slots)
    k_eff = jnp.minimum(jnp.sum(mask), k_slots).astype(jnp.int32)
    us = jax.random.uniform(k2, (k_slots,))
    return us, sel, valid, k_eff, truncated


def resolve_runtime(runtime):
    """``runtime``, or the default ``Local()`` placement for None.
    Imported at call time: ``repro.dpp`` imports this package."""
    if runtime is None:
        from ..dpp.runtime import default_runtime
        return default_runtime()
    return runtime


def _phase1_one(key: jax.Array, lams: Tuple[jax.Array, ...],
                vecs: Tuple[jax.Array, ...], k_max: int):
    """One sample's spectrum draw: (us, factored columns, k_eff, trunc)."""
    sizes = tuple(l.shape[0] for l in lams)
    ll = log_product_spectrum(lams)
    us, sel, valid, k_eff, truncated = draw_slots(
        key, lambda k1: bernoulli_mask(k1, ll), k_max)
    Gs = gather_factor_columns(vecs, sizes, sel, valid)
    return us, Gs, k_eff, truncated


@functools.partial(jax.jit, static_argnames=("k_max", "backend"))
def _sample_batched(keys, lams, vecs, k_max, backend=None):
    sizes = tuple(l.shape[0] for l in lams)
    us, Gs, k_eff, truncated = jax.vmap(
        lambda k: _phase1_one(k, lams, vecs, k_max))(keys)
    picks = kernel_ops.phase2_select(us, Gs, sizes, k_eff, backend=backend)
    return picks, k_eff, truncated


def sample_krondpp_batched(key: jax.Array, spectrum: FactorSpectrum,
                           k_max: Optional[int] = None, num_samples: int = 1,
                           backend: Optional[str] = None, runtime=None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Draw ``num_samples`` exact KronDPP samples in one device call.

    Returns (picks (num_samples, k_max) int32 with -1 padding,
    counts (num_samples,) int32, truncated (num_samples,) bool — True for
    draws whose |J| overflowed the static k_max budget and were clipped).
    One compile per (k_max, num_samples) shape; repeat calls at the same
    shape reuse the executable. ``backend`` selects the phase-2 engine
    (None = auto: fused Pallas kernel on TPU, jax reference elsewhere).

    Row i is drawn from ``jax.random.split(key, num_samples)[i]`` by
    ``sample_krondpp_keyed``, which also takes ``runtime``.
    """
    keys = jax.random.split(key, num_samples)
    return sample_krondpp_keyed(keys, spectrum, k_max, backend=backend,
                                runtime=runtime)


def sample_krondpp_keyed(row_keys: jax.Array, spectrum: FactorSpectrum,
                         k_max: Optional[int] = None,
                         backend: Optional[str] = None, runtime=None
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``sample_krondpp_batched`` with the per-row PRNG keys supplied —
    the one dispatch point of the exact-DPP draw.

    ``row_keys`` is a (num_samples, 2) uint32 key array; row i is drawn
    from ``row_keys[i]`` alone, so the result for a given key does not
    depend on which other keys share the device call. This is the
    batching-invariance primitive the async serving tier builds on: a
    request keyed by (tenant, sequence number) draws the same subsets
    whether the background flush coalesced it with 0 or 63 neighbours.

    Same return contract as ``sample_krondpp_batched``:
    (picks (num_samples, k_max) int32 with -1 padding, counts
    (num_samples,) int32, truncated (num_samples,) bool).

    ``runtime`` selects placement (``repro.dpp.runtime``, default
    ``Local()``): the draw runs through ``runtime.map_keys``, which under
    a mesh shards the keys over the data axes, so draws match the
    single-device call bit-for-bit on shared keys.
    """
    if k_max is None:
        k_max = spectrum.suggested_k_max()
    k_max = int(k_max)
    # duck-typed dispatch: spectra that carry their own row sampler (the
    # low-rank DualSpectrum) bypass the Kronecker eigenvector machinery —
    # same (picks, counts, truncated) contract, same per-key determinism
    rows_hook = getattr(spectrum, "sample_rows", None)
    if rows_hook is not None:
        return rows_hook(row_keys, k_max, backend=backend, runtime=runtime)
    # spectra flow through operands (not closures) so a mesh can cache
    # one compiled executable per (k_max, backend) + shape
    return resolve_runtime(runtime).map_keys(
        lambda ks, ops: _sample_batched(ks, *ops, k_max, backend),
        row_keys, operands=(tuple(spectrum.lams), tuple(spectrum.vecs)),
        static_key=("sample_krondpp_batched", k_max, backend))


def picks_to_lists(picks):
    """(B, k_max) padded device picks -> python lists (host boundary)."""
    import numpy as np
    arr = np.asarray(picks)
    return [[int(i) for i in row[row >= 0]] for row in arr]


def compile_cache_size() -> int:
    """Number of compiled (k_max, batch) specializations — test hook for
    the 'one compile per shape' contract."""
    return _sample_batched._cache_size()
