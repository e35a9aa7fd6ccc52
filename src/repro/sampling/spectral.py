"""Factor eigendecomposition cache for device-resident sampling.

Exact DPP sampling (paper Alg. 2 / Sec. 4) is two phases: a spectrum draw
and a projection-selection loop. The only O(N_i^3) work is the per-factor
``eigh`` — everything downstream is O(N k) — so repeated sampling against
one kernel should pay for the eigendecomposition exactly once. The cache
here is keyed on *factor identity* (not value), so two KronDPPs that share
a factor array share its spectrum, and the KrK-Picard training loop (which
rebuilds factors every step) naturally misses.

Entries hold a strong reference to the keyed factor, so an ``id()`` can
never be recycled by a different live array while its entry is cached.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..core.krondpp import KronDPP


def log_product_spectrum(lams: Tuple[jax.Array, ...]) -> jax.Array:
    """log of the Kronecker product spectrum {prod_i lams[i][g_i]}, folded
    factor-wise in log space (row-major global order, matching
    ``KronDPP.split_indices``).

    This is THE spectrum fold for the subsystem — a linear-space fold
    overflows float32 once per-factor eigenvalues multiply past ~3e38,
    silently turning inclusion probabilities into NaN. Zero eigenvalues
    map to -inf, which every consumer handles (sigmoid -> 0, logaddexp
    ignores). Usable inside jit.
    """
    v = jnp.log(lams[0])
    for l in lams[1:]:
        v = (v[:, None] + jnp.log(l)[None, :]).reshape(-1)
    return v


class _SizeMoments:
    """Memo of one spectrum's (E|Y|, sd|Y|). A ``FactorSpectrum`` and its
    placed copies (``dataclasses.replace``) share one, so it is filled
    once per cached spectrum; the lock makes concurrent first callers
    compute it once."""

    __slots__ = ("lock", "value")

    def __init__(self):
        self.lock = threading.Lock()
        self.value: Optional[Tuple[float, float]] = None  #: guarded-by: lock


#: (k, placement) pairs whose k-DPP keep-probability table one spectrum
#: holds, least recently used out first. An entry is an (N, k+1) float32
#: table: 360 KB at N = 10^4, k = 8.
KEEP_TABLES_MAX = 4


class _KeepTables:
    """Memo of one spectrum's k-DPP keep-probability tables
    (``sampling.kdpp.kdpp_keep_table``). Shared like ``_SizeMoments``;
    the lock makes concurrent first callers of one key build it once. A
    table that is a tracer (built under an outer trace) is handed back and
    not stored."""

    __slots__ = ("lock", "tables")

    def __init__(self):
        self.lock = threading.Lock()
        self.tables = collections.OrderedDict()  #: guarded-by: lock

    def get(self, key, build):
        """(table for ``key``, whether ``build()`` made it on this call)."""
        with self.lock:
            table = self.tables.get(key)
            if table is not None:
                self.tables.move_to_end(key)
                return table, False
            table = build()
            if not any(isinstance(x, jax.core.Tracer)
                       for x in jax.tree_util.tree_leaves(table)):
                self.tables[key] = table
                while len(self.tables) > KEEP_TABLES_MAX:
                    self.tables.popitem(last=False)
            return table, True


@dataclasses.dataclass(frozen=True)
class FactorSpectrum:
    """Per-factor eigendecompositions of L = L_1 ⊗ ... ⊗ L_m.

    lams[i]: (N_i,) eigenvalues of factor i, clipped to >= 0, ascending.
    vecs[i]: (N_i, N_i) orthonormal eigenvectors (columns).

    The product spectrum {prod_i lams[i][g_i]} is only ever materialized as
    an O(N) vector; the N eigenvectors are assembled lazily per sample.

    Two memos ride on the spectrum, shared by its placed copies
    (``dataclasses.replace``) and empty in a spectrum ``SpectralCache``
    rebuilds: the size moments (``size_moments``) and, per k, the k-DPP
    keep-probability table (``kdpp_keep_table``).
    """
    lams: Tuple[jax.Array, ...]
    vecs: Tuple[jax.Array, ...]
    _moments: _SizeMoments = dataclasses.field(
        default_factory=_SizeMoments, compare=False, repr=False)
    _keep_tables: _KeepTables = dataclasses.field(
        default_factory=_KeepTables, compare=False, repr=False)

    @property
    def m(self) -> int:
        return len(self.lams)

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(l.shape[0]) for l in self.lams)

    @property
    def N(self) -> int:
        out = 1
        for s in self.sizes:
            out *= s
        return out

    def eigenvalues(self) -> jax.Array:
        """All N eigenvalues, row-major factor-index order (matches
        ``KronDPP.split_indices``). Reference only — overflows float32 for
        huge products; the sampling paths use ``log_eigenvalues``."""
        v = self.lams[0]
        for l in self.lams[1:]:
            v = jnp.outer(v, l).reshape(-1)
        return v

    def log_eigenvalues(self) -> jax.Array:
        """log of the product spectrum (``log_product_spectrum``)."""
        return log_product_spectrum(self.lams)

    def expected_size(self) -> float:
        """E|Y| = sum λ/(1+λ) = sum sigmoid(log λ) — overflow-safe. One
        device-to-host sync, counted as ``dpp.host_syncs``."""
        obs.current_tracker().counter("dpp.host_syncs")
        return float(jnp.sum(jax.nn.sigmoid(self.log_eigenvalues())))

    def size_std(self) -> float:
        """sqrt(Var|Y|), Var|Y| = sum p(1-p) with p = λ/(1+λ). One
        device-to-host sync, counted as ``dpp.host_syncs``."""
        obs.current_tracker().counter("dpp.host_syncs")
        ll = self.log_eigenvalues()
        p = jax.nn.sigmoid(ll)
        return float(jnp.sqrt(jnp.sum(p * jax.nn.sigmoid(-ll))))

    def size_moments(self) -> Tuple[float, float]:
        """(E|Y|, sd|Y|), memoized: the first call runs ``expected_size``
        and ``size_std`` (their two syncs), later calls on this spectrum
        or its placed copies read the memo with no device op. Counted as
        ``spectral_cache.moments_misses`` / ``.moments_hits``."""
        tracker = obs.current_tracker()
        memo = self._moments
        with memo.lock:
            if memo.value is None:
                tracker.counter("spectral_cache.moments_misses")
                memo.value = (self.expected_size(), self.size_std())
            else:
                tracker.counter("spectral_cache.moments_hits")
            return memo.value

    def kdpp_keep_table(self, k: int):
        """``((P, k0), built)``: k-DPP phase 1's keep-probability table at
        ``k`` (``sampling.kdpp.kdpp_keep_table``) and whether this call
        built it. The first call at a k dispatches one jitted build and
        does not wait for it; later calls on this spectrum or its placed
        copies read the memo. The table lives where ``lams`` live, so each
        placement (one device, or replicated over a mesh) builds its own
        once. Under an outer trace the build is traced and not kept."""
        from .kdpp import keep_table_of      # kdpp imports this module
        k = int(k)
        # a tracer has no sharding: its table is a tracer too, never kept
        placement = getattr(self.lams[0], "sharding", None)
        return self._keep_tables.get(
            (k, placement), lambda: keep_table_of(tuple(self.lams), k))

    def suggested_k_max(self, num_std: float = 6.0) -> int:
        """Static phase-2 budget: E|Y| + num_std·σ, clamped to [1, N].

        Samples larger than k_max are truncated (lowest eigen-indices kept);
        at 6σ that is a ~1e-9 event per draw.
        """
        e, sd = self.size_moments()
        k = math.ceil(e + num_std * sd) + 1
        return max(1, min(k, self.N))


class _CacheStats(dict):
    """Counter snapshot that is also callable returning itself, so both
    the original ``cache.stats`` property access and the facade-era
    ``cache.stats()`` call read the same dict."""

    def __call__(self) -> "_CacheStats":
        return self


class SpectralCache:
    """LRU cache of per-factor eigendecompositions, keyed on array identity.

    ``spectrum(dpp)`` looks up each factor independently, so hits/misses
    count factor lookups (a 2-factor KronDPP costs two lookups). It
    returns one stable ``FactorSpectrum`` per factor tuple while the
    factors' entries live, so the spectrum's memoized size moments
    (``suggested_k_max``) are computed once, not on every call.

    Thread-safe: one lock guards the LRU map and the hit/miss/eviction
    counters — the serving tier's background flush thread and foreground
    fitters race on the default shared cache. A miss holds the lock
    across its ``eigh`` too, so concurrent lookups of the same factor
    decompose it once, not once per thread."""

    def __init__(self, maxsize: int = 16):
        self.maxsize = maxsize
        self._entries = collections.OrderedDict()  #: guarded-by: _lock
        self._spectra = collections.OrderedDict()  #: guarded-by: _lock
        self.hits = 0                              #: guarded-by: _lock
        self.misses = 0                            #: guarded-by: _lock
        self.evictions = 0                         #: guarded-by: _lock
        self._lock = threading.RLock()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def stats(self) -> "_CacheStats":
        """Counters for observability: factor-lookup hits/misses, LRU
        evictions, and the current entry count. Surfaced in the sampling
        benchmark JSON so cache behavior shows up in the perf trend.

        Usable as ``cache.stats()`` (the facade-era spelling) and as
        ``cache.stats["hits"]`` (the PR-1 property contract). The key
        style (snake_case counter names) matches ``ServiceStats`` —
        ``service.stats()`` and ``cache.stats()`` are the same shape —
        and every lookup also emits ``spectral_cache.hits`` / ``.misses``
        / ``.evictions`` counters plus a ``spectral_cache.eigh_s`` wall-
        time sample through ``repro.obs.current_tracker()``."""
        with self._lock:
            return _CacheStats(hits=self.hits, misses=self.misses,
                               evictions=self.evictions,
                               size=len(self._entries))

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._spectra.clear()

    @staticmethod
    def _key(f: jax.Array) -> tuple:
        return (id(f), tuple(f.shape), str(f.dtype))

    def _factor(self, f: jax.Array) -> Tuple[jax.Array, jax.Array]:
        tracker = obs.current_tracker()
        key = self._key(f)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self.hits += 1
                tracker.counter("spectral_cache.hits")
                self._entries.move_to_end(key)
                return hit[1], hit[2]
            self.misses += 1
            tracker.counter("spectral_cache.misses")
            if obs.enabled(tracker):
                # the block_until_ready exists only to make the eigh timer
                # an honest wall-clock sample; the NullTracker path keeps
                # jax's normal async dispatch. The span makes the recompute
                # show up INSIDE whatever request trace paid for the miss.
                with obs.spans.start_span("spectral_cache.eigh",
                                          tracker=tracker,
                                          n=int(f.shape[0])):
                    with tracker.timer("spectral_cache.eigh_s",
                                       n=int(f.shape[0])):
                        lam, vec = jax.block_until_ready(jnp.linalg.eigh(f))
            else:
                lam, vec = jnp.linalg.eigh(f)
            lam = jnp.maximum(lam, 0.0)
            self._entries[key] = (f, lam, vec)   # strong ref pins the id
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                tracker.counter("spectral_cache.evictions")
            return lam, vec

    def spectrum(self, dpp: KronDPP) -> FactorSpectrum:
        """FactorSpectrum for a KronDPP — O(sum N_i^3) on miss, O(1) on hit."""
        return self._spectrum(tuple(dpp.factors))

    def spectrum_dense(self, L: jax.Array) -> FactorSpectrum:
        """A dense kernel is the m=1 degenerate case — the whole batched
        pipeline (phase 1/2, k-DPP) works on it unchanged."""
        return self._spectrum((L,))

    def _spectrum(self, factors: Tuple[jax.Array, ...]) -> FactorSpectrum:
        """The same FactorSpectrum object for the same factor arrays, as
        long as each factor's eigh entry is the one it was built from (its
        eigenvalue arrays are the same objects); an evicted and
        recomputed factor gets a new spectrum, and a new memo.
        Same LRU bound as the factor entries; the entry pins the factors'
        ids like ``_factor``'s."""
        pairs = [self._factor(f) for f in factors]
        lams = tuple(p[0] for p in pairs)
        vecs = tuple(p[1] for p in pairs)
        key = tuple(self._key(f) for f in factors)
        with self._lock:
            hit = self._spectra.get(key)
            if hit is not None and all(
                    a is b for a, b in zip(hit[1].lams, lams)):
                self._spectra.move_to_end(key)
                return hit[1]
            spec = FactorSpectrum(lams, vecs)
            self._spectra[key] = (factors, spec)   # strong refs pin the ids
            while len(self._spectra) > self.maxsize:
                self._spectra.popitem(last=False)
            return spec

    def spectrum_lowrank(self, V: jax.Array, q: jax.Array
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """``(phi, lams, W)`` for the rank-r dual of L = V diag(q) Vᵀ.

        phi = V·√q (N, r); ``lams``/``W`` eigendecompose the r×r dual Gram
        C = φᵀφ = Vᵀ diag(q) V, which shares its nonzero spectrum with L
        (Kulesza & Taskar §3.3) — the ONLY factorization on this path, so
        a low-rank model never pays an N×N eigh. Keyed on
        ``(id(V), id(q))``: a q-only update (per-tenant quality reweight)
        reuses nothing stale and costs exactly one fresh r×r eigh, while
        repeat lookups of the same (V, q) pair are hits. The entry pins
        strong references to both arrays, same as ``_factor``."""
        tracker = obs.current_tracker()
        r = int(V.shape[1])
        key = ("lowrank", id(V), id(q), tuple(V.shape), tuple(q.shape),
               str(V.dtype))
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self.hits += 1
                tracker.counter("spectral_cache.hits")
                self._entries.move_to_end(key)
                return hit[1], hit[2], hit[3]
            self.misses += 1
            tracker.counter("spectral_cache.misses")

            def _dual():
                phi = V * jnp.sqrt(jnp.maximum(q, 0.0))[:, None]
                C = phi.T @ phi
                lam, W = jnp.linalg.eigh(0.5 * (C + C.T))
                return phi, jnp.maximum(lam, 0.0), W

            if obs.enabled(tracker):
                # timer/span tagged n=r: the zero-N×N-eigh acceptance test
                # reads these tags to prove the hot path never factors N×N
                with obs.spans.start_span("spectral_cache.eigh",
                                          tracker=tracker, n=r):
                    with tracker.timer("spectral_cache.eigh_s", n=r):
                        phi, lam, W = jax.block_until_ready(_dual())
            else:
                phi, lam, W = _dual()
            self._entries[key] = ((V, q), phi, lam, W)  # pins both ids
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                tracker.counter("spectral_cache.evictions")
            return phi, lam, W


def gain_for_expected_size(log_lams: "jax.Array", target: float,
                           iters: int = 100) -> float:
    """Scalar gain g such that E|Y| = Σ σ(log g + log λ) hits ``target`` —
    bisection on log g over the log-space product spectrum, so huge kernels
    never overflow the fold. Shared by ``rescale_expected_size`` and the
    ``repro.dpp`` facade's ``Model.rescale``.

    Raises ``ValueError`` when ``target`` is outside the achievable open
    range (0, rank): E|Y| = Σ λ/(1+λ) tends to 0 as g -> 0 and to the
    number of nonzero eigenvalues as g -> ∞, never reaching either end, so
    the bisection used to silently saturate at its bounds (g ≈ e^±60) and
    hand callers a wildly mis-scaled kernel instead of an error."""
    import numpy as np
    ll = np.asarray(log_lams, np.float64)
    rank = int(np.isfinite(ll).sum())         # log λ = -inf for zero eigs
    target = float(target)
    if not np.isfinite(target) or target <= 0.0 or target >= rank:
        raise ValueError(
            f"target expected size {target} is not achievable: E|Y| = "
            f"Σ λ/(1+λ) of this spectrum is confined to the open interval "
            f"(0, {rank}) (rank = number of nonzero eigenvalues, "
            f"N = {ll.size}); rescale to a size strictly inside it")
    lo, hi = -60.0, 60.0                      # g in [~1e-26, ~1e26]
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        e = (1.0 / (1.0 + np.exp(-(ll + mid)))).sum()
        if e > target:
            hi = mid
        else:
            lo = mid
    return float(np.exp(0.5 * (lo + hi)))


def rescale_expected_size(dpp: KronDPP, target: float,
                          iters: int = 100) -> KronDPP:
    """Scalar-rescale the factors so E|Y| hits ``target``. Raw
    U[0, sqrt(2)] kernels have E|Y| ~ N, which buries any benchmark
    comparison under the shared O(N k³) selection cost; callers rescale to
    a workload-sized E|Y|.

    Raises ``ValueError`` (from ``gain_for_expected_size``) when ``target``
    lies outside the spectrum's achievable (0, rank) range.
    """
    lams = tuple(jnp.maximum(jnp.linalg.eigvalsh(f), 0.0)
                 for f in dpp.factors)
    g = gain_for_expected_size(log_product_spectrum(lams), target, iters)
    return KronDPP(tuple(f * (g ** (1.0 / dpp.m)) for f in dpp.factors))


_DEFAULT_CACHE: Optional[SpectralCache] = None


def default_cache() -> SpectralCache:
    """Process-wide cache shared by the convenience entry points."""
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None:
        _DEFAULT_CACHE = SpectralCache()
    return _DEFAULT_CACHE
