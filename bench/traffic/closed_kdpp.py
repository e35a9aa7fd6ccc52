"""Closed loop, one caller: back-to-back blocking k-DPP ``Kron.sample``
calls, each a batch of fixed-size slates.

Traffic parameters as in ``closed_sample``: ``batch`` (slates per call)
and ``check_calls`` (how many of the window's calls, drawn from the seed,
are compared with the reference row by row). The slate size is the
configuration's ``k``. Call ``i`` of the window is ``model.sample(
fold_in(key, i), batch, k=k)``; its row ``b`` draws from
``split(fold_in(key, i), batch)[b]``, replayed by ``bench/kdpp_ref.py``.
The window's row sizes and call count go into ``work`` for the readers.
"""

from __future__ import annotations

import jax
import numpy as np

from bench import data, kdpp_ref
from bench.traffic import closed_sample


class _Slates:
    """The model as the closed loop calls it: every call draws k-DPP
    rows."""

    def __init__(self, model, k: int):
        self.model, self.k = model, k

    def sample(self, key, n):
        return self.model.sample(key, n, k=self.k)


class Driver(closed_sample.Driver):
    def setup(self) -> None:
        from repro import dpp
        self.factors = data.kron_factors(jax.random.fold_in(self.key, 0),
                                         self.cfg["sizes"],
                                         self.cfg["expected_size"])
        self.model = _Slates(dpp.Kron(self.factors), int(self.cfg["k"]))
        self.call_key = jax.random.fold_in(self.key, 1)
        warm = self.model.sample(jax.random.fold_in(self.key, 2),
                                 self.tr["batch"])
        jax.block_until_ready((warm.indices, warm.mask))

    def check(self, control: bool = False) -> dict:
        """The numbers compared, each with its limit; with ``control``
        the bfloat16 reference stands in for the program's rows."""
        checker = kdpp_ref.KdppChecker(self.spectra, self.cfg["k"])
        keys, rows = [], []
        for c, (rs, _) in self.rows.items():
            keys.append(jax.random.split(
                jax.random.fold_in(self.call_key, c), len(rs)))
            rows.extend(rs)
        got = kdpp_ref.check_rows(checker, np.concatenate(keys), rows,
                                  control)
        lim = self.cfg["limits"]
        return {"wrong_size_rows": {"value": got["wrong_size_rows"],
                                    "limit": 0},
                "kdpp_phase1_gap": {"value": got["kdpp_phase1_gap"],
                                    "limit": lim["kdpp_phase1_gap"]},
                "phase2_gap": {"value": got["phase2_gap"],
                               "limit": lim["phase2_gap"]}}
