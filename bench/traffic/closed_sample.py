"""Closed loop, one caller: back-to-back blocking ``Kron.sample`` calls.

Traffic parameters: ``batch`` (subsets per call) and ``check_calls``
(how many of the window's calls, drawn from the seed, are compared with
the reference row by row). Call ``i`` of the window draws from
``fold_in(key, i)``; its row ``b`` from ``split(fold_in(key, i),
batch)[b]``.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import data, draw_ref


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, seed
        self.cfg, self.tr = cell.config, cell.traffic
        self.key = data.run_key(seed)

    def setup(self) -> None:
        from repro import dpp
        self.factors = data.kron_factors(jax.random.fold_in(self.key, 0),
                                         self.cfg["sizes"],
                                         self.cfg["expected_size"])
        self.model = dpp.Kron(self.factors)
        self.call_key = jax.random.fold_in(self.key, 1)
        warm = self.model.sample(jax.random.fold_in(self.key, 2),
                                 self.tr["batch"])
        jax.block_until_ready((warm.indices, warm.mask))

    def run(self, seconds: float, annotate) -> dict:
        batch, outs = self.tr["batch"], []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with annotate("bench.dispatch"):
                out = self.model.sample(
                    jax.random.fold_in(self.call_key, len(outs)), batch)
            with annotate("bench.block"):
                jax.block_until_ready((out.indices, out.mask))
            outs.append(out)
        window_s = time.perf_counter() - t0
        self.outs, self.work = outs, {}
        return {"attempted": len(outs), "failed": 0, "window_s": window_s,
                "metrics": {"sample_rows_per_s": len(outs) * batch
                            / window_s},
                "work": self.work,
                "info": {"calls": len(outs), "rows": len(outs) * batch}}

    def release(self) -> None:
        # the drawn sizes of every row of the window, for the sampler's
        # operation count (bench/counts.py)
        self.work.update(
            sizes=np.concatenate([np.asarray(o.mask).sum(1)
                                  for o in self.outs]),
            factor_sizes=tuple(self.cfg["sizes"]), calls=len(self.outs),
            k_max=int(self.outs[0].indices.shape[1]))
        rng = np.random.default_rng(self.seed)
        picked = sorted(rng.choice(len(self.outs),
                                   min(self.tr["check_calls"],
                                       len(self.outs)), replace=False))
        self.rows = {}
        for c in picked:
            o = self.outs[c]
            idx, mask = np.asarray(o.indices), np.asarray(o.mask)
            self.rows[int(c)] = ([r[m].tolist() for r, m in zip(idx, mask)],
                                 int(idx.shape[1]))
        self.spectra = draw_ref.factor_spectra(self.factors)
        del self.factors
        del self.outs, self.model

    def check(self, control: bool = False) -> dict:
        """The numbers compared, each with its limit; with ``control``
        the bfloat16 reference stands in for the program's rows."""
        checker = draw_ref.RowChecker(self.spectra)
        keys, rows, width_gap = [], [], 0
        for c, (rs, width) in self.rows.items():
            keys.append(jax.random.split(
                jax.random.fold_in(self.call_key, c), len(rs)))
            rows.extend(rs)
            width_gap = max(width_gap, abs(width - checker.k_max))
        got = draw_ref.check_rows(checker, np.concatenate(keys), rows,
                                  control)
        lim = self.cfg["limits"]
        return {"k_max_gap": {"value": width_gap, "limit": 0},
                "phase1_gap": {"value": got["phase1_gap"],
                               "limit": lim["phase1_gap"]},
                "phase2_gap": {"value": got["phase2_gap"],
                               "limit": lim["phase2_gap"]}}
