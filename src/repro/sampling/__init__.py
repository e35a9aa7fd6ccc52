"""repro.sampling — device-resident batched exact DPP sampling (Sec. 4).

NOTE: the public API for sampling is the ``repro.dpp`` facade
(``Dense(L)`` / ``Kron(factors)`` → ``model.sample`` / ``model.service``).
This package is the engine behind it: the paper's asymptotic win
(O(N^{3/2}) exact sampling for m=2, O(N) for m=3) turned into measured
throughput — spectrum draw, lazy Kronecker eigenvector assembly, and
projection selection as fixed-shape jax, jit-compiled and vmapped over
PRNG keys. The host-side numpy sampler in ``core.sampling`` remains as
the reference oracle.

Module map
----------
spectral.py  ``FactorSpectrum`` (per-factor eigendecompositions, product
             spectrum helpers) and ``SpectralCache`` — the O(sum N_i^3)
             eigh keyed on factor identity so repeated sampling against
             one kernel pays for it once.
batched.py   ``sample_krondpp_keyed`` — the one dispatch point of the
             exact-DPP draw (``sample_krondpp_batched`` splits one key
             and calls it): phase-1 Bernoulli over the factored
             spectrum, compaction to a static (k_max,) slot array, lazy
             eigenvector gather, and the phase-2 projection selection.
             Also the fixed-shape building blocks every sampler shares
             (``draw_slots``: mask -> slots + phase-2 uniforms).
kdpp.py      ``sample_kdpp_batched`` (the k-DPP's one dispatch point) /
             ``sample_kdpp_dense`` — exactly-k sampling via the
             log-space elementary-symmetric-polynomial recursion on the
             factored spectrum; its keep-probability table is built
             once per (spectrum, k) and memoized on the spectrum.
service.py   ``SamplingService`` — micro-batching front-end (submit →
             coalesce → one vmapped device call → scatter) used via
             ``model.service()`` by the data pipeline and serving layers.

Placement: every sampler and the service take ``runtime=`` (a
``repro.dpp.runtime`` Runtime, default ``Local()``); each draw runs
through ``runtime.map_keys``, which under ``Mesh`` shards the PRNG-key
batch over the mesh's data axes with draws bit-for-bit equal to the
single-device call on shared keys.

The sampler entry points are not re-exported here: code goes through
``repro.dpp`` (or ``repro.dpp.functional`` inside a jit trace), and
subsystem-internal callers import from the submodules directly.
"""

from .spectral import (FactorSpectrum, SpectralCache, default_cache,
                       gain_for_expected_size, log_product_spectrum,
                       rescale_expected_size)
from .batched import compile_cache_size, picks_to_lists
from .kdpp import log_esp_table
from .service import SamplingService, SampleTicket


__all__ = [
    "FactorSpectrum", "SpectralCache", "default_cache",
    "log_product_spectrum", "rescale_expected_size",
    "gain_for_expected_size",
    "picks_to_lists", "compile_cache_size", "log_esp_table",
    "SamplingService", "SampleTicket",
]
