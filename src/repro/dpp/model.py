"""The model family behind the ``repro.dpp`` facade.

``DPPModel`` is the one public seam for every DPP operation in the repo:
sampling (device or host), likelihood, marginals, conditioning, MAP,
rescaling, and learning. Two first-class implementations:

``Dense(L)``
    an explicit N x N L-ensemble kernel — the m=1 degenerate case of the
    factored machinery, so it rides the exact same device pipelines.
``Kron(factors)``
    the paper's Kronecker kernel L = L_1 ⊗ ... ⊗ L_m, absorbing
    ``core.KronDPP``. The full kernel is never materialized except behind
    an explicit ``max_dense`` guard (conditioning / MAP fallbacks).

Everything host-facing dispatches through the spectrum: per-factor
eigendecompositions held in a ``SpectralCache`` (eigh paid once per factor
identity), the product spectrum folded in log space so huge kernels never
overflow. WHERE the work runs is a separate, orthogonal axis owned by
``repro.dpp.runtime``: ``sample`` / ``fit`` / ``spectrum`` / ``service``
take ``runtime=`` (``Local()`` default, ``Mesh(axes={"data": n})`` for
SPMD sharding, ``Host()`` for the numpy oracle) — the pre-runtime
``backend=`` strings survive only as DeprecationWarning shims.

These models are host-level entry points (they make shape decisions like
``suggested_k_max`` off concrete spectra). Inside a jit trace, use the
building blocks in ``repro.dpp.functional`` instead.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.dpp import SubsetBatch
from ..core.kron import split_indices_multi
from ..core.krondpp import KronDPP, random_krondpp
from ..kernels import ops as kernel_ops
from ..sampling.batched import sample_krondpp_batched
from ..sampling.kdpp import sample_kdpp_batched
from ..sampling.service import SamplingService
from ..sampling.spectral import (FactorSpectrum, SpectralCache, default_cache,
                                 gain_for_expected_size)
from . import runtime as runtime_mod

#: Guard for operations that must materialize the full N x N kernel
#: (``Kron.condition`` / ``Kron.map`` dense fallbacks). Raising it is an
#: explicit opt-in to O(N^2) memory.
MAX_DENSE_N = 4096


def _as_index_set(idx, n: int) -> jnp.ndarray:
    """Validate and canonicalize a host-side index set: 1-D, in range,
    deduplicated (inclusion events have set semantics)."""
    arr = np.atleast_1d(np.asarray(idx, np.int64))
    if arr.ndim != 1:
        raise ValueError(f"index set must be scalar or 1-D, got {arr.shape}")
    if arr.size and (arr.min() < 0 or arr.max() >= n):
        raise ValueError(f"indices out of range [0, {n}): {idx!r}")
    return jnp.asarray(np.unique(arr), jnp.int32)


def _place_spectrum(spec: FactorSpectrum,
                    runtime: Optional[runtime_mod.Runtime]
                    ) -> FactorSpectrum:
    """Replicate a spectrum's arrays over a mesh runtime (identity for
    Local/Host/None). Uses the mesh's identity-pinned cache: spectrum
    arrays are themselves cached (``SpectralCache``), so repeated
    sampling against one kernel pays the host -> devices broadcast once,
    not per call. The placed copy shares the spectrum's memoized size
    moments (``dataclasses.replace``)."""
    if runtime is not None and getattr(runtime, "is_mesh", False):
        return dataclasses.replace(
            spec, lams=runtime.replicate_pinned(tuple(spec.lams)),
            vecs=runtime.replicate_pinned(tuple(spec.vecs)))
    return spec


def _picks_to_subsets(picks: jax.Array,
                      truncated: Optional[jax.Array] = None) -> SubsetBatch:
    """(B, k_max) -1-padded device picks -> a padded SubsetBatch, carrying
    the sampler's per-row truncation provenance when available."""
    mask = picks >= 0
    return SubsetBatch(jnp.where(mask, picks, 0).astype(jnp.int32), mask,
                       truncated)


class DPPModel:
    """Shared implementation of the facade protocol.

    Subclasses provide ``factors`` (tuple of PD factor matrices; a dense
    kernel is the 1-tuple), ``_wrap_factors`` and ``_default_algorithm``.
    Every method below is written against the factored spectrum, so Dense
    and Kron behave identically up to the factor count.
    """

    # -- structure ----------------------------------------------------------
    @property
    def factors(self) -> Tuple[jax.Array, ...]:
        raise NotImplementedError

    @property
    def m(self) -> int:
        return len(self.factors)

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(f.shape[0]) for f in self.factors)

    @property
    def N(self) -> int:
        out = 1
        for s in self.sizes:
            out *= s
        return out

    def dense_kernel(self, max_dense: int = MAX_DENSE_N) -> jax.Array:
        """The full N x N kernel — O(N^2) memory, guarded."""
        if self.N > max_dense:
            raise ValueError(
                f"materializing the full kernel needs N <= max_dense "
                f"({self.N} > {max_dense}); pass max_dense= explicitly to "
                f"opt into O(N^2) memory")
        return KronDPP(tuple(self.factors)).full_matrix()

    # -- spectrum -----------------------------------------------------------
    def spectrum(self, cache: Optional[SpectralCache] = None,
                 runtime: Optional[runtime_mod.Runtime] = None
                 ) -> FactorSpectrum:
        """Per-factor eigendecompositions off a ``SpectralCache`` —
        O(Σ N_i³) on first touch, O(1) for every later call against the
        same factor arrays. Under a ``Mesh`` runtime the spectrum arrays
        are placed replicated over the mesh (the cache entry itself stays
        device-agnostic)."""
        cache = cache if cache is not None else default_cache()
        return _place_spectrum(cache.spectrum(self), runtime)

    def expected_size(self, cache: Optional[SpectralCache] = None) -> float:
        """E|Y| = Σ λ/(1+λ) off the log-space product spectrum."""
        return self.spectrum(cache).expected_size()

    def rescale(self, expected_size: float,
                cache: Optional[SpectralCache] = None) -> "DPPModel":
        """Scalar-rescale the kernel so E|Y| hits ``expected_size``
        (log-space bisection; overflow-safe for huge products).

        Raises ``ValueError`` when ``expected_size`` is outside the
        achievable open range (0, rank): no scalar gain can push
        E|Y| = Σ λ/(1+λ) to 0, or past the number of nonzero
        eigenvalues."""
        spec = self.spectrum(cache)
        g = gain_for_expected_size(spec.log_eigenvalues(), expected_size)
        gm = g ** (1.0 / self.m)
        return self._wrap_factors(tuple(f * gm for f in self.factors))

    # -- sampling -----------------------------------------------------------
    def sample(self, key: jax.Array,
               batch_shape: Union[int, Tuple[int, ...]] = (),
               k: Optional[int] = None,
               runtime: Optional[runtime_mod.Runtime] = None,
               k_max: Optional[int] = None,
               cache: Optional[SpectralCache] = None,
               backend: Optional[str] = None) -> SubsetBatch:
        """Exact DPP (or, with ``k``, k-DPP) samples as a ``SubsetBatch``.

        batch_shape: int or tuple; the returned batch has n = prod(shape)
            rows (1 for the default ``()``).
        runtime: execution placement (``repro.dpp.runtime``):
            ``Local()`` / None — the batched jit+vmap subsystem, one
            device call for the whole batch; ``Mesh(axes={"data": n})`` —
            the same pipeline with the key batch sharded over the mesh
            (draws match Local bit-for-bit on shared keys); ``Host()`` —
            the float64 numpy reference oracle, one eigh + one subset per
            draw for the DPP, one eigh + one ESP table per call for the
            k-DPP (``core.sampling.sample_kdpp``).
        k_max: static phase-2 budget override for the device DPP path
            (defaults to the spectrum's E|Y| + 6σ bound).
        backend: deprecated placement strings ("device"/"host"), shimmed
            onto runtimes with a DeprecationWarning.
        """
        rt = runtime_mod.resolve(runtime, backend=backend)
        shape = (batch_shape,) if isinstance(batch_shape, int) \
            else tuple(batch_shape)
        n = 1
        for s in shape:
            n *= int(s)
        if rt.kind == "host":
            return self._sample_host(key, n, k)
        # one span per host phase of the call; with the default
        # NullTracker each is the shared inert span
        start_span = obs.spans.start_span
        with start_span("dpp.sample", rows=n, k=k) as root:
            with start_span("dpp.sample.spectrum"):
                spec = self.spectrum(cache, runtime=rt)
            with start_span("dpp.sample.k_max"):
                if k is not None:
                    # exact-k draws cannot overflow their k-slot budget
                    k_max = k
                elif k_max is None:
                    k_max = spec.suggested_k_max()
                k_max = int(k_max)
            root.set_tag("k_max", k_max)
            with start_span("dpp.sample.draw"):
                if k is not None:
                    picks = sample_kdpp_batched(key, spec, k_max, n,
                                                runtime=rt)
                    truncated = None
                else:
                    picks, _, truncated = sample_krondpp_batched(
                        key, spec, k_max, n, runtime=rt)
            with start_span("dpp.sample.pack"):
                return _picks_to_subsets(picks, truncated)

    def _sample_host(self, key: jax.Array, n: int,
                     k: Optional[int] = None) -> SubsetBatch:
        from ..core.sampling import (sample_full_dpp, sample_kdpp,
                                     sample_krondpp)
        seed = int(jax.random.randint(key, (), 0, np.iinfo(np.int32).max))
        rng = np.random.default_rng(seed)
        factors = self._host_factors()
        if k is not None:
            subs = sample_kdpp(rng, factors, int(k), n)
            return SubsetBatch.from_lists(subs, k_max=max(1, int(k)))
        if len(factors) == 1:
            subs = [sample_full_dpp(rng, np.asarray(factors[0]))
                    for _ in range(n)]
        else:
            krondpp = KronDPP(tuple(factors))
            subs = [sample_krondpp(rng, krondpp) for _ in range(n)]
        k_max = max(1, max((len(s) for s in subs), default=1))
        return SubsetBatch.from_lists(subs, k_max=k_max)

    def _host_factors(self) -> Tuple[jax.Array, ...]:
        """The factor matrices the Host oracle decomposes."""
        return self.factors

    def service(self, **kwargs) -> SamplingService:
        """A micro-batching ``SamplingService`` over this model (submit /
        coalesce / one vmapped device call / scatter). Pass
        ``runtime=Mesh(...)`` to shard every flush over a mesh."""
        return SamplingService(self, **kwargs)

    def serving(self, config=None, **kwargs):
        """The async continuous-batching tier over this model
        (``repro.serving.AsyncSamplingService``): background deadline/
        max-batch flush thread, multi-tenant weighted-round-robin queues
        with admission control, futures tickets. Draws are keyed by
        (tenant, sequence number), so they are reproducible regardless of
        how the background thread coalesces traffic."""
        from ..serving import AsyncSamplingService
        return AsyncSamplingService(self, config, **kwargs)

    # -- likelihood ---------------------------------------------------------
    def log_prob(self, batch: SubsetBatch,
                 cache: Optional[SpectralCache] = None) -> jax.Array:
        """(n,) log P(Y_i) = log det(L_{Y_i}) - log det(L + I) for a padded
        subset batch, off the factored objective — the N x N kernel is
        never materialized and the normalizer comes from the log-space
        product-spectrum fold."""
        from ..learning.objective import subset_logdets_factored
        spec = self.spectrum(cache)
        log_z = jnp.sum(jax.nn.softplus(spec.log_eigenvalues()))
        return subset_logdets_factored(tuple(self.factors), batch) - log_z

    def log_likelihood(self, batch: SubsetBatch,
                       cache: Optional[SpectralCache] = None) -> jax.Array:
        """Mean log P(Y_i) over the batch (the learners' objective phi)."""
        return jnp.mean(self.log_prob(batch, cache))

    # -- marginals ----------------------------------------------------------
    def marginal_kernel_submatrix(self, idx,
                                  cache: Optional[SpectralCache] = None
                                  ) -> jax.Array:
        """K[idx, idx] for the marginal kernel K = L(L+I)^{-1}, gathered
        from the factored spectrum in O(k² N) without forming K:
        K[a,b] = Σ_g σ(log λ_g) · Π_f P_f[a_f, g_f] P_f[b_f, g_f].
        Indices are validated and deduplicated (set semantics)."""
        idx = _as_index_set(idx, self.N)
        spec = self.spectrum(cache)
        parts = split_indices_multi(idx, spec.sizes)
        rows = [V[p, :] for V, p in zip(spec.vecs, parts)]   # (k, N_f) each
        p_inc = jax.nn.sigmoid(spec.log_eigenvalues()).reshape(spec.sizes)
        T = p_inc[None, None]                    # (1, 1, N_1, ..., N_m)
        for R in rows:
            E = R[:, None, :] * R[None, :, :]    # (k, k, N_f)
            E = E.reshape(E.shape + (1,) * (T.ndim - 3))
            T = (E * T).sum(axis=2)              # contract factor f's axis
        return T

    def marginal(self, idx, cache: Optional[SpectralCache] = None
                 ) -> jax.Array:
        """P(idx ⊆ Y) = det(K_idx): a scalar index gives the singleton
        inclusion probability K_ii, an index set the joint inclusion
        probability."""
        K_sub = self.marginal_kernel_submatrix(idx, cache)
        if K_sub.shape[0] == 1:
            return K_sub[0, 0]
        return jnp.linalg.det(K_sub)

    # -- conditioning -------------------------------------------------------
    def condition(self, observed, max_dense: int = MAX_DENSE_N
                  ) -> "DPPModel":
        """The conditional DPP given ``observed ⊆ Y`` (Kulesza & Taskar
        closure): an L-ensemble over the complement ground set with the
        Schur-complement kernel L' = L_Ā - L_{Ā,A} L_A^{-1} L_{A,Ā}.

        Item i of the returned model is the i-th element of
        ``sorted(set(range(N)) - set(observed))``. An empty ``observed``
        is a no-op and returns ``self`` (type and factored structure
        preserved). Kron kernels fall back to the dense Schur complement
        behind the ``max_dense`` guard (the complement of a product index
        set is not a product set, so there is no factored closed form).
        """
        A = np.asarray(_as_index_set(observed, self.N))
        if A.size == 0:
            return self
        L = self.dense_kernel(max_dense)
        comp = np.setdiff1d(np.arange(self.N), A)
        L_A = L[jnp.ix_(A, A)]
        L_cA = L[jnp.ix_(comp, A)]
        chol = jnp.linalg.cholesky(L_A)
        if not bool(jnp.all(jnp.isfinite(chol))):
            # det(L_A) = 0: P(A ⊆ Y) = 0, the conditional is undefined —
            # fail loudly instead of propagating a silent all-NaN model
            raise ValueError(
                f"cannot condition on {observed!r}: L_A is singular "
                f"(P(A ⊆ Y) = 0 — e.g. linearly dependent items of a "
                f"rank-deficient kernel)")
        X = jax.scipy.linalg.cho_solve((chol, True), L_cA.T)   # L_A^{-1} L_{A,Ā}
        schur = L[jnp.ix_(comp, comp)] - L_cA @ X
        return Dense(0.5 * (schur + schur.T))

    # -- MAP ----------------------------------------------------------------
    def map(self, k: int, max_dense: int = MAX_DENSE_N) -> jax.Array:
        """Greedy MAP subset of size k (Chen et al. 2018 fast greedy,
        ``kernels.ops`` — Pallas-kernel update on TPU). Kron kernels run
        on the guarded dense materialization."""
        return kernel_ops.greedy_map_kdpp(self.dense_kernel(max_dense),
                                          int(k))

    # -- learning -----------------------------------------------------------
    def fit(self, batch: SubsetBatch, algorithm: Optional[str] = None,
            max_dense: int = MAX_DENSE_N, **fit_kwargs):
        """Maximum-likelihood fit via the scan-compiled ``repro.learning``
        engine. Returns the engine's ``FitReport`` with ``report.model``
        wrapped back into a facade model (``Kron`` for krk/joint,
        ``Dense`` for em). All engine kwargs (iters, schedule,
        minibatch_size, checkpoint_dir, runtime, ...) pass through —
        ``runtime=Mesh(axes={"data": n})`` runs mesh-sharded KrK sweeps
        (Θ-statistics and Armijo acceptance LLs psum'd over the data
        axes); ``max_dense`` bounds the dense materialization a Kron
        model needs for ``algorithm="em"``."""
        from ..learning.api import fit as _fit
        if algorithm is None:
            algorithm = self._default_algorithm
        rep = _fit(self._fit_params(algorithm, max_dense), batch,
                   algorithm=algorithm, **fit_kwargs)
        if isinstance(rep.model, KronDPP):
            fitted = Kron(tuple(rep.model.factors))
        else:
            fitted = Dense(jnp.asarray(rep.model))
        return dataclasses.replace(rep, model=fitted)

    # -- subclass hooks -----------------------------------------------------
    def _wrap_factors(self, factors: Tuple[jax.Array, ...]) -> "DPPModel":
        raise NotImplementedError

    def _fit_params(self, algorithm: str, max_dense: int = MAX_DENSE_N):
        raise NotImplementedError


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)       # array fields: generated __eq__ would
class Dense(DPPModel):                 # raise on ambiguous truth values
    """An explicit N x N L-ensemble kernel behind the facade protocol."""
    L: jax.Array

    _default_algorithm = "em"

    def __post_init__(self):
        self.L = jnp.asarray(self.L)

    @property
    def factors(self) -> Tuple[jax.Array, ...]:
        return (self.L,)

    def dense_kernel(self, max_dense: int = MAX_DENSE_N) -> jax.Array:
        return self.L          # already dense; no guard needed

    def spectrum(self, cache: Optional[SpectralCache] = None,
                 runtime: Optional[runtime_mod.Runtime] = None
                 ) -> FactorSpectrum:
        cache = cache if cache is not None else default_cache()
        return _place_spectrum(cache.spectrum_dense(self.L), runtime)

    def _wrap_factors(self, factors):
        return Dense(factors[0])

    def _fit_params(self, algorithm: str, max_dense: int = MAX_DENSE_N):
        if algorithm != "em":
            raise ValueError(
                f"Dense kernels learn with algorithm='em'; {algorithm!r} "
                f"needs a factored Kron model")
        return self.L

    def tree_flatten(self):
        return (self.L,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
class Kron(DPPModel):
    """The paper's Kronecker kernel L = L_1 ⊗ ... ⊗ L_m (m = 2 or 3),
    absorbing ``core.KronDPP`` behind the facade protocol.

    Deliberately NOT a dataclass: the stored tuple is normalized from
    whatever ``factors`` the caller passes (including a ``KronDPP``), so
    the constructor argument is not a field and ``dataclasses.replace``
    would mis-wire it.
    """

    _default_algorithm = "krk"

    def __init__(self, factors):
        if isinstance(factors, KronDPP):
            factors = factors.factors
        self._factors = tuple(jnp.asarray(f) for f in factors)

    def __repr__(self):
        return f"Kron(sizes={self.sizes})"

    @property
    def factors(self) -> Tuple[jax.Array, ...]:
        return self._factors

    def to_krondpp(self) -> KronDPP:
        """The underlying ``core.KronDPP`` (for legacy interop)."""
        return KronDPP(self._factors)

    def _wrap_factors(self, factors):
        return Kron(factors)

    def _fit_params(self, algorithm: str, max_dense: int = MAX_DENSE_N):
        if algorithm == "em":
            return self.dense_kernel(max_dense)
        return self._factors

    def tree_flatten(self):
        return self._factors, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children))


# ---------------------------------------------------------------------------
# Constructors
# ---------------------------------------------------------------------------

def from_kernel(L) -> Dense:
    """Facade model over an explicit dense kernel."""
    return Dense(jnp.asarray(L))


def from_factors(*factors) -> Kron:
    """Facade model over Kronecker factors (pass 2 or 3 PD matrices)."""
    if len(factors) == 1 and isinstance(factors[0], (tuple, list)):
        factors = tuple(factors[0])
    return Kron(factors)


def random_kron(key: jax.Array, sizes: Sequence[int], dtype=jnp.float32,
                scale: float = 1.0) -> Kron:
    """Paper Sec. 5.1 random init (L_i = X^T X, X ~ U[0, sqrt(2)])."""
    return Kron(random_krondpp(key, tuple(sizes), dtype, scale))
