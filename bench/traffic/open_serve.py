"""Open loop through the async serving tier (``Kron.serving``).

Traffic parameters: ``rate_rps`` (offered requests per second, all
tenants together), ``tenants`` (name -> weighted-round-robin weight),
``rows`` ([lo, hi]: subsets per request, uniform), the serving config
(``max_batch``, ``deadline_ms``, ``max_queue_depth``), ``check_requests``
(completed requests drawn from the seed and compared row by row with the
reference) and ``drain_s`` (how long past the window a request may take
to resolve before it counts as failed).

Arrivals are Poisson at ``rate_rps`` with the rate split evenly over the
tenants. Every seed gets the same work in another order: the n =
rate x seconds inter-arrival gaps are the n midpoint quantiles of the
exponential distribution, tenants and request sizes come in equal
counts, and the seed permutes each. One thread submits each request at
its due time; a request is timed from when it was due to when its
ticket resolves, so a late submit counts against the server. How late
the submits ran is reported beside the result. A resolved ticket is
dropped at once, as a front end drops a request it has answered: the
window keeps its resolve time, whether its size was right, and the rows
of the ``check_requests`` requests drawn from the seed beforehand, so
that what the window holds for the check does not grow the heap that
the collector scans.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from bench import data, draw_ref


def schedule(rate: float, seconds: float, tenants, rows, seed: int):
    """(due times from the window's start, tenant names, request sizes)."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    names = list(tenants)
    who = [names[i] for i in rng.permutation(np.arange(n) % len(names))]
    lo, hi = rows
    size = lo + rng.permutation(np.arange(n) % (hi - lo + 1))
    return due, who, [int(s) for s in size]


class _Collector(threading.Thread):
    """Records when each ticket resolves: waits on the oldest pending
    ticket, then sweeps every pending ticket that is done. Of a resolved
    ticket it keeps the resolve time, counts a wrong number of rows, and
    keeps the rows only of the requests in ``keep``."""

    def __init__(self, keep):
        super().__init__(name="bench-collector", daemon=True)
        self.cond = threading.Condition()
        self.pending = []                     # [(index, ticket, rows)]
        self.keep = keep
        self.done_at, self.kept = {}, {}
        self.wrong = 0
        self.closed = False

    def add(self, i, ticket, rows):
        with self.cond:
            self.pending.append((i, ticket, rows))
            self.cond.notify()

    def close(self):
        with self.cond:
            self.closed = True
            self.cond.notify()

    def _resolved(self, i, ticket, rows, now):
        try:
            got = ticket.result(timeout=0)
        except Exception:                     # noqa: BLE001 — a failed
            return                            # request is counted, not raised
        self.done_at[i] = now
        self.wrong += len(got) != rows
        if i in self.keep:
            self.kept[i] = (ticket.tenant, ticket.seq, rows, got)

    def run(self):
        while True:
            with self.cond:
                while not self.pending and not self.closed:
                    self.cond.wait()
                if not self.pending:
                    return
                oldest = self.pending[0][1]
            try:
                oldest.result(timeout=0.002)
            except Exception:                 # noqa: BLE001 — a timeout or
                pass                          # the request's own failure
            now = time.perf_counter()
            with self.cond:
                keep = []
                for i, t, rows in self.pending:
                    if t.done():
                        self._resolved(i, t, rows, now)
                    else:
                        keep.append((i, t, rows))
                self.pending = keep


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed = cell, seed
        self.cfg, self.tr = cell.config, cell.traffic
        self.key = data.run_key(seed)
        self.service_seed = int(seed) % 2 ** 31

    def _service(self, seed):
        from repro.serving import ServingConfig
        t = self.tr
        return self.model.serving(
            ServingConfig(max_batch=t["max_batch"],
                          deadline_ms=t["deadline_ms"],
                          max_queue_depth=t["max_queue_depth"]),
            tenants=t["tenants"], seed=seed)

    def setup(self) -> None:
        from repro import dpp
        self.factors = data.kron_factors(jax.random.fold_in(self.key, 0),
                                         self.cfg["sizes"],
                                         self.cfg["expected_size"])
        self.model = dpp.Kron(self.factors)
        # every padded flush size the window can produce: powers of two
        # up to max_batch, and the flush that overshoots it, since a flush
        # drains whole requests until it holds max_batch rows (at most
        # max_batch + hi - 1 of them, padded to 2 x max_batch)
        mb, hi = self.tr["max_batch"], self.tr["rows"][1]
        sizes = [1 << i for i in range(mb.bit_length()) if 1 << i <= mb]
        if hi > 1:
            sizes.append(mb + hi - 1)
        warm = self._service(self.service_seed ^ 0x5A5A5A5A)
        for b in sizes:
            warm.submit(b, tenant="warmup").result(timeout=600.0)
        warm.close()
        self.svc = self._service(self.service_seed)

    def run(self, seconds: float, annotate) -> dict:
        from repro.serving import QueueFull, ServiceClosed
        due, who, size = schedule(self.tr["rate_rps"], seconds,
                                  self.tr["tenants"], self.tr["rows"],
                                  self.seed)
        rng = np.random.default_rng(self.seed)
        col = _Collector(set(rng.choice(
            len(due), min(self.tr["check_requests"], len(due)),
            replace=False).tolist()))
        col.start()
        late, refused = np.zeros(len(due)), 0
        t0 = time.perf_counter()
        for i, (d, t, s) in enumerate(zip(due, who, size)):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                with annotate("bench.wait"):
                    time.sleep(wait)
            late[i] = time.perf_counter() - (t0 + d)
            try:
                with annotate("bench.submit"):
                    ticket = self.svc.submit(s, tenant=t)
            except (QueueFull, ServiceClosed):
                refused += 1
                continue
            col.add(i, ticket, s)
        window_s = time.perf_counter() - t0
        limit = time.perf_counter() + self.tr["drain_s"]
        while col.pending and time.perf_counter() < limit:
            time.sleep(0.01)
        col.close()
        col.join(5.0)
        done = sorted(col.done_at)
        failed = len(due) - len(done)
        self.wrong, self.kept = col.wrong, col.kept
        # in due order, for the knee sweep's backlog test
        self.last_latencies = lat = np.asarray(
            [col.done_at[i] - (t0 + due[i]) for i in done])
        p95 = 1e3 * float(np.percentile(lat, 95)) if len(lat) else \
            float("inf")
        return {"attempted": len(due), "failed": failed,
                "window_s": window_s,
                "metrics": {"serve_p95_ms": p95},
                "info": {"requests": len(due), "completed": len(lat),
                         "refused": refused,
                         "p50_ms": 1e3 * float(np.percentile(lat, 50))
                         if len(lat) else None,
                         "late_p50_ms": 1e3 * float(np.percentile(late, 50)),
                         "late_p95_ms": 1e3 * float(np.percentile(late, 95)),
                         "late_max_ms": 1e3 * float(late.max())}}

    def release(self) -> None:
        self.svc.close()
        self.spectra = draw_ref.factor_spectra(self.factors)
        del self.factors
        del self.svc, self.model

    def check(self, control: bool = False) -> dict:
        """The numbers compared, each with its limit; with ``control``
        the bfloat16 reference stands in for the served rows."""
        checker = draw_ref.RowChecker(self.spectra)
        picked = [self.kept[i] for i in sorted(self.kept)]
        triples = [(t, s, j) for t, s, n, rows in picked
                   for j in range(len(rows))]
        rows = [r for _, _, _, rs in picked for r in rs]
        got = draw_ref.check_rows(
            checker, draw_ref.served_row_keys(self.service_seed, triples),
            rows, control)
        lim = self.cfg["limits"]
        return {"wrong_size_requests": {"value": self.wrong, "limit": 0},
                "phase1_gap": {"value": got["phase1_gap"],
                               "limit": lim["phase1_gap"]},
                "phase2_gap": {"value": got["phase2_gap"],
                               "limit": lim["phase2_gap"]}}
