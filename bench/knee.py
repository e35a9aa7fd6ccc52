"""Sweep the offered rate of an open-loop serving cell to find its knee.

    python3 bench/knee.py --workload kron-serve-poisson --seed 7 \
        --seconds 8 --rates 200,400,800

One process, one model, one warm-up; then for each rate a fresh service
takes the cell's traffic at that rate for ``--seconds``. Per rate it
prints one JSON line: completions per second over the arrivals' span,
p50/p95 latency from due time, how long the backlog took to drain after
the last arrival, and the median latency of the last fifth of requests
over that of the first fifth (near 1 when the backlog does not grow).
The knee is the highest rate that completes at the offered rate with no
growing backlog; the cell's traffic file offers 0.8 x that rate.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="kron-serve-poisson")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import cell as cell_mod
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_mod.Cell(args.workload, spec)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("knee: JAX finds no TPU", file=sys.stderr)
        return 2
    drv = cell.driver.Driver(cell, args.seed, jax.devices()[:1])
    drv.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        drv.tr = dict(drv.tr, rate_rps=rate)
        t0 = time.perf_counter()
        win = drv.run(args.seconds, lambda name: contextlib.nullcontext())
        lat = np.asarray(drv.last_latencies)
        fifth = max(1, len(lat) // 5)
        print(json.dumps({
            "rate_rps": rate, "completed_per_s": len(lat) / win["window_s"],
            "offered": win["attempted"], "failed": win["failed"],
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p95_ms": win["metrics"]["serve_p95_ms"],
            "drain_s": time.perf_counter() - t0 - win["window_s"],
            "growth": float(np.median(lat[-fifth:])
                            / np.median(lat[:fifth])),
            **win["info"]}), flush=True)
        drv.svc.close()
        drv.svc = drv._service(drv.service_seed)
    drv.svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
