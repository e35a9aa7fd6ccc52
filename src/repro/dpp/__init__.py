"""repro.dpp — the one model-centric probabilistic API for this repo.

A DPP is a model, not a bag of free functions. Build one, then ask it for
everything the literature treats as table stakes (cf. DPPy's unified
object API, arXiv:1809.07258):

    import jax
    from repro import dpp

    model = dpp.random_kron(jax.random.PRNGKey(0), (20, 25))   # N = 500
    model = model.rescale(expected_size=10.0)

    batch = model.sample(jax.random.PRNGKey(1), 64)    # exact, one device call
    logp  = model.log_prob(batch)                      # (64,) per-subset
    p_i   = model.marginal(3)                          # P(3 in Y)
    p_ij  = model.marginal([3, 7])                     # P({3,7} ⊆ Y)
    cond  = model.condition([3, 7])                    # new model, A ⊆ Y given
    mapset = model.map(k=10)                           # greedy MAP subset
    report = model.fit(batch, algorithm="krk",         # compiled learning
                       schedule=dpp.schedules.armijo())

``Dense(L)`` and ``Kron(factors)`` implement one shared protocol
(``DPPModel``); a dense kernel is just the one-factor case of the factored
machinery, so both ride the same device-resident pipelines
(``repro.sampling``, ``repro.learning``) and the same ``SpectralCache``.
In-trace consumers (vmapped serving paths) use ``repro.dpp.functional``.

WHERE that work runs is owned by one placement seam —
``repro.dpp.runtime``. A ``Runtime`` object (``Local()``,
``Mesh(axes={"data": n})``, ``Host()``) is THE placement entry point:
pass it as ``runtime=`` to ``model.sample`` / ``model.fit`` /
``model.spectrum`` / ``model.service``:

    from repro.dpp import runtime
    rt = runtime.Mesh(axes={"data": 8})        # SPMD over 8 devices
    batch = model.sample(jax.random.PRNGKey(1), 4096, runtime=rt)
    report = model.fit(batch, schedule=dpp.schedules.armijo(), runtime=rt)

Under ``Mesh`` the key batch / training subsets are sharded over the data
axes and reductions are psum'd; draws and fits reproduce ``Local`` on
shared keys (bit-for-bit for sampling). The pre-runtime placement
spellings — ``backend="device"|"host"`` strings, ``fit(mesh=...)``, the
``--distributed`` CLI flag — are DeprecationWarning shims onto runtimes,
as are the pre-facade ``core.fit_*`` drivers. Sampling has no such
shims: it goes through the models here (``repro.dpp.functional`` inside
a jit trace).
"""

from ..learning import schedules
from ..sampling.service import SampleTicket, SamplingService
from ..sampling.spectral import FactorSpectrum, SpectralCache, default_cache
from . import functional, runtime
from .model import (MAX_DENSE_N, Dense, DPPModel, Kron, from_factors,
                    from_kernel, random_kron)
from .runtime import Host, Local, Mesh, Runtime

# LowRank/DualSpectrum resolve lazily (PEP 562): repro.lowrank subclasses
# .model's DPPModel, so an eager import here would be circular when the
# lowrank package is imported first. Consumers spell it dpp.LowRank
# either way — repro.lowrank internals stay behind this facade.
_LOWRANK_EXPORTS = ("LowRank", "DualSpectrum", "nystrom_features",
                    "random_fourier_features")


def __getattr__(name):
    if name in _LOWRANK_EXPORTS:
        from .. import lowrank
        value = getattr(lowrank, name)
        globals()[name] = value      # cache: later lookups skip this hook
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "DPPModel", "Dense", "Kron", "LowRank", "MAX_DENSE_N",
    "from_kernel", "from_factors", "random_kron",
    "functional", "schedules",
    "runtime", "Runtime", "Local", "Mesh", "Host",
    "FactorSpectrum", "DualSpectrum", "SpectralCache", "default_cache",
    "SamplingService", "SampleTicket",
    "nystrom_features", "random_fourier_features",
]
