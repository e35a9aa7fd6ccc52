"""KronDPP core — the paper's algorithms as composable JAX building blocks.

NOTE: the public, model-centric API is the ``repro.dpp`` facade
(``Dense`` / ``Kron`` with sample / log_prob / marginal / condition /
map / fit). This package holds the math it is built from:
    KronDPP, SubsetBatch
    kron (algebra), sampling (host reference oracle, greedy MAP)
    krk_picard (Alg. 1), joint_picard (Alg. 3), picard ([25]), em ([10])
    clustering (Sec. 3.3 greedy SUKP)
The ``fit_*`` drivers here are deprecated shims that warn and delegate
to the learning engines behind the facade.
"""

from . import kron, dpp, sampling, clustering
from .dpp import SubsetBatch, log_likelihood, picard_delta
from .krondpp import KronDPP, random_krondpp
from .krk_picard import (krk_picard_step, krk_picard_stochastic_step,
                         fit_krk_picard, accumulate_AC, AC_from_dense_theta,
                         compute_AC)
from .picard import picard_step, fit_picard
from .joint_picard import joint_picard_step, fit_joint_picard
from .em import fit_em
from .sampling import sample_full_dpp, sample_krondpp, greedy_map_kdpp
from .clustering import greedy_subset_clustering

__all__ = [
    "KronDPP", "SubsetBatch", "random_krondpp", "log_likelihood", "picard_delta",
    "krk_picard_step", "krk_picard_stochastic_step", "fit_krk_picard",
    "accumulate_AC", "AC_from_dense_theta", "compute_AC",
    "picard_step", "fit_picard", "joint_picard_step", "fit_joint_picard",
    "fit_em", "sample_full_dpp", "sample_krondpp", "greedy_map_kdpp",
    "greedy_subset_clustering", "kron", "dpp", "sampling", "clustering",
]
