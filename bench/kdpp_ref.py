"""Plain float64 reference for keyed exact Kron k-DPP draws, and the gap
by which a drawn row departs from it.

A k-DPP row is a pure function of its PRNG key (the program's documented
keying, ``repro.sampling.kdpp``):

    keys   = split(call_key, rows)
    k1, k2 = split(row_key)
    u  = uniform(k1, (N,))   phase 1 runs over the row-major product
                             spectrum from the last eigen-index to the
                             first, u[t] deciding eigen-index N - 1 - t:
                             keep it iff u[t] < lambda_g e_{k'-1}(lambda_0
                             .. lambda_{g-1}) / e_{k'}(lambda_0 ..
                             lambda_g), k' being the number still to keep
    us = uniform(k2, (k,))   phase 2 on the kept eigen-indices, ascending:
                             step t picks the first item whose prefix mass
                             exceeds us[t] * total mass

``KdppChecker`` builds the ESP table e_j in float64 (log space) from the
factors' eigendecompositions, regenerates u and us from the keys, and
replays phase 1 exactly; phase 2 is ``draw_ref.RowChecker.phase2_gap``.
Three numbers come out of a set of rows:

kdpp_phase1_gap  0 when the float64 draw keeps the eigen-indices that
                 explain the row; otherwise, |logit(u) - logit(p)| of the
                 decision that has to flip to explain it: the relative
                 error in the inclusion odds lambda_g e_{k'-1} / e_{k'}
                 (both over the eigen-indices before g) that a near-tie
                 would need. Near-ties and the one-or-two-flip search
                 follow ``draw_ref.RowChecker.check_row``.
phase2_gap       as in ``draw_ref``: 0 when each pick's inverse-CDF target
                 falls inside its interval, about U(0, 1) for a wrong
                 item, 1 for a wrong size or a zero-mass item
wrong_size_rows  rows without exactly k distinct items in [0, N)

``control_picks`` computes the ESP table, the phase-1 decisions and the
chain rule from bfloat16-rounded operands (float32 arithmetic): the
control that has to fail.
"""

from __future__ import annotations

from itertools import combinations
from typing import List, Sequence, Tuple

import numpy as np

from bench import draw_ref
from bench.draw_ref import (EXPLAIN_ABOVE, MAX_CANDIDATES, TOGGLE_WINDOW,
                            _bf16)


def log_esp_table(log_lam: np.ndarray, k: int, rnd=None) -> np.ndarray:
    """log e_j(lambda_0 .. lambda_{n-1}) for n = 0..N, j = 0..k, as an
    (N+1, k+1) float64 array; with ``rnd`` each row is rounded by it."""
    T = np.full((log_lam.shape[0] + 1, k + 1), -np.inf)
    T[:, 0] = 0.0
    for n, ll in enumerate(log_lam, start=1):
        T[n, 1:] = np.logaddexp(T[n - 1, 1:], T[n - 1, :-1] + ll)
        if rnd is not None:
            T[n] = rnd(T[n])
    return T


def _logit(log_p: np.ndarray) -> np.ndarray:
    with np.errstate(divide="ignore", invalid="ignore"):
        return log_p - np.log1p(-np.exp(np.minimum(log_p, 0.0)))


class KdppChecker:
    """float64 replay of keyed k-DPP draws from L = L1 kron L2, given each
    factor's eigendecomposition (``draw_ref.factor_spectra``)."""

    def __init__(self, spectra, k: int):
        self.rows = draw_ref.RowChecker(spectra)
        self.N, self.k = self.rows.N, int(k)
        self.log_lam = self.rows.log_lam
        self.table = log_esp_table(self.log_lam, self.k)
        self.k0 = min(self.k, int(np.isfinite(self.log_lam).sum()))
        self._control = None

    def uniforms(self, row_keys) -> Tuple[np.ndarray, np.ndarray]:
        u, us = draw_ref._uniforms(row_keys, self.N, self.k)
        return np.asarray(u, np.float64), np.asarray(us, np.float64)

    def draw(self, u: np.ndarray, flips=(), log_lam=None, table=None
             ) -> Tuple[np.ndarray, np.ndarray]:
        """(kept eigen-indices ascending, |logit(u) - logit(p)| of every
        step) of the phase-1 draw from ``u``, with the decisions at the
        steps in ``flips`` inverted. Between two inclusions the number
        still to keep is fixed, so each stretch is one vector pass."""
        ll = self.log_lam if log_lam is None else log_lam
        T = self.table if table is None else table
        z = np.full(self.N, np.inf)
        kept, k_rem, t = [], self.k0, 0
        with np.errstate(divide="ignore", invalid="ignore"):
            logit_u = np.log(u) - np.log1p(-u)
            while t < self.N and k_rem > 0:
                g = np.arange(self.N - 1 - t, -1, -1)
                log_p = ll[g] + T[g, k_rem - 1] - T[g + 1, k_rem]
                log_p = np.where(np.isfinite(T[g + 1, k_rem]), log_p, -np.inf)
                keep = u[t:] < np.exp(np.minimum(log_p, 0.0))
                for f in flips:
                    if f >= t:
                        keep[f - t] = not keep[f - t]
                hit = np.flatnonzero(keep)
                stop = hit[0] if hit.size else self.N - t - 1
                z[t:t + stop + 1] = np.abs(logit_u[t:t + stop + 1]
                                           - _logit(log_p[:stop + 1]))
                if not hit.size:
                    break
                kept.append(self.N - 1 - (t + stop))
                k_rem -= 1
                t += stop + 1
        return np.array(sorted(kept), np.int64), z

    def check_row(self, u: np.ndarray, us: np.ndarray,
                  picks: Sequence[int]) -> Tuple[float, float]:
        """(kdpp_phase1_gap, phase2_gap) of one drawn row."""
        picks = [int(i) for i in picks]
        J, z = self.draw(u)
        gap2 = self.rows.phase2_gap(J, us, picks)
        if gap2 <= EXPLAIN_ABOVE:
            return 0.0, gap2
        near = [int(t) for t in np.argsort(z)[:MAX_CANDIDATES]
                if z[t] < TOGGLE_WINDOW]
        best = (0.0, gap2)
        for n_flip in (1, 2):
            for flips in combinations(sorted(near), n_flip):
                Jt, zt = self.draw(u, flips)
                if len(Jt) != len(picks):
                    continue
                g = self.rows.phase2_gap(Jt, us, picks)
                if g < best[1]:
                    best = (float(max(zt[list(flips)])), g)
            if best[1] <= EXPLAIN_ABOVE:
                break
        return best

    def wrong_size(self, picks: Sequence[int]) -> bool:
        items = {int(i) for i in picks}
        return (len(picks) != self.k or len(items) != self.k
                or not all(0 <= i < self.N for i in items))

    def control_picks(self, u: np.ndarray, us: np.ndarray) -> List[int]:
        """The reference draw with the spectrum, the ESP table and every
        operand of the chain rule rounded to bfloat16 (arithmetic in
        float32)."""
        if self.k > self.rows.k_max:
            raise ValueError(f"the control chain rule holds at most "
                             f"{self.rows.k_max} picks; k = {self.k}")
        if self._control is None:
            d = [_bf16(x).astype(np.float64) for x in self.rows.d]
            with np.errstate(divide="ignore"):
                ll = _bf16((np.log(d[0])[:, None]
                            + np.log(d[1])[None, :]).reshape(-1))
            ll = ll.astype(np.float64)
            self._control = ll, log_esp_table(ll, self.k, rnd=_bf16)
        ll, table = self._control
        J, _ = self.draw(_bf16(u).astype(np.float64), log_lam=ll,
                         table=table)
        # draw_ref's bfloat16 chain rule keeps the eigen-indices g with
        # logit(u[g]) < log lambda_g: u = 0 on J and 1 elsewhere keeps J
        keep_J = np.ones(self.N)
        keep_J[J] = 0.0
        return self.rows.control_picks(keep_J, us)


def check_rows(checker: KdppChecker, row_keys,
               rows: Sequence[Sequence[int]], control: bool = False) -> dict:
    """Widest phase-1 and phase-2 gaps and the count of wrong-size rows
    over ``rows`` drawn from ``row_keys``; with ``control`` the rows are
    replaced by the bfloat16 control's own draws from the same keys."""
    u, us = checker.uniforms(row_keys)
    g1 = g2 = 0.0
    wrong = 0
    for b, row in enumerate(rows):
        if control:
            row = checker.control_picks(u[b], us[b])
        wrong += checker.wrong_size(row)
        a, c = checker.check_row(u[b], us[b], row)
        g1, g2 = max(g1, a), max(g2, c)
    return {"kdpp_phase1_gap": float(g1), "phase2_gap": float(g2),
            "wrong_size_rows": wrong}
