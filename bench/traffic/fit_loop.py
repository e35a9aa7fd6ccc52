"""Repeated KrK-Picard fits of one data set from one seeded start.

Traffic parameters: ``iters`` and ``log_every`` (sweeps per fit, sweeps
per compiled chunk) and ``check_fits`` (how many of the window's fits,
drawn from the seed, are compared with the float64 reference). Every
call is ``Kron.fit(batch, algorithm="krk", iters, log_every,
schedule=armijo())`` on one chip (``Local()``), under
``jax.default_matmul_precision`` of the configuration's
``matmul_precision``.

The configuration fixes the data: a planted Kron model (seeded paper
Sec. 5.1 factors at E|Y| = ``expected_size``), ``subsets`` exact draws
from it by the benchmark's own sampler, padded to ``subset_width``, and
the start, a second seeded draw of the paper's init scaled to the same
E|Y|, so that the sweeps follow the data rather than a change of scale.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import data, krk_ref


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, seed
        self.cfg, self.tr = cell.config, cell.traffic
        self.key = data.run_key(seed)

    def _fit(self):
        from repro import dpp
        with jax.default_matmul_precision(self.cfg["matmul_precision"]):
            rep = self.model.fit(self.batch, algorithm="krk",
                                 iters=self.tr["iters"],
                                 log_every=self.tr["log_every"],
                                 schedule=dpp.schedules.armijo(),
                                 runtime=dpp.Local())
        jax.block_until_ready(rep.model.factors)
        return rep

    def setup(self) -> None:
        from repro import dpp
        from repro.core import SubsetBatch
        sizes = tuple(self.cfg["sizes"])
        planted = data.kron_factors(jax.random.fold_in(self.key, 0), sizes,
                                    self.cfg["expected_size"])
        idx, mask = data.planted_subsets(jax.random.fold_in(self.key, 1),
                                         planted, self.cfg["subsets"],
                                         self.cfg["subset_width"])
        self.start = data.kron_factors(jax.random.fold_in(self.key, 2),
                                       sizes, self.cfg["expected_size"])
        self.batch = SubsetBatch(idx, mask)
        self.model = dpp.Kron(self.start)
        self._fit()

    def run(self, seconds: float, annotate) -> dict:
        reps = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with annotate("bench.fit"):
                reps.append(self._fit())
        window_s = time.perf_counter() - t0
        self.reps = reps
        sweeps = sum(r.sweeps for r in reps)
        self.work = {"sweeps": sweeps}
        return {"attempted": len(reps), "failed": 0, "window_s": window_s,
                "metrics": {"krk_sweeps_per_s": sweeps / window_s},
                "work": self.work,
                "info": {"fits": len(reps), "sweeps": sweeps,
                         "final_ll": reps[-1].log_likelihoods[-1]}}

    def release(self) -> None:
        # the sizes of the window's sweeps, for their operation count
        # (bench/counts.py)
        mask = np.asarray(self.batch.mask)
        self.work.update(factor_sizes=tuple(self.cfg["sizes"]),
                         subset_sizes=mask.sum(1), subsets=mask.shape[0],
                         width=mask.shape[1])
        rng = np.random.default_rng(self.seed)
        picked = rng.choice(len(self.reps),
                            min(self.tr["check_fits"], len(self.reps)),
                            replace=False)
        self.got = [([np.asarray(f) for f in self.reps[i].model.factors],
                     list(self.reps[i].log_likelihoods)) for i in picked]
        self.host_start = [np.asarray(f) for f in self.start]
        self.host_batch = (np.asarray(self.batch.indices),
                           np.asarray(self.batch.mask))
        del self.reps, self.model, self.batch, self.start

    def check(self, control: bool = False) -> dict:
        """The numbers compared, each with its limit; with ``control``
        the reference computed in the precision below the
        configuration's (``krk_ref.BELOW``) stands in for the program."""
        n1, n2 = self.cfg["sizes"]
        subsets = krk_ref.Subsets(*self.host_batch, n1, n2)
        want, want_lls, _ = krk_ref.fit(self.host_start, subsets,
                                        self.tr["iters"])
        got = self.got
        if control:
            rnd = krk_ref.BELOW[self.cfg["matmul_precision"]]
            f, lls, _ = krk_ref.fit(self.host_start, subsets,
                                    self.tr["iters"], rnd=rnd)
            got = [(f, lls)]
        worst = {"ll_gap": 0.0, "factor_rel_gap": 0.0}
        for factors, lls in got:
            gaps = krk_ref.compare(factors, lls, want, want_lls)
            worst = {k: max(worst[k], gaps[k]) for k in worst}
        lim = self.cfg["limits"]
        return {k: {"value": v, "limit": lim[k]} for k, v in worst.items()}
