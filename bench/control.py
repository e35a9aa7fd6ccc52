"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 [--fault half_batch] [--seconds 2] \
        [--precision high]

For each seed, in one process: the cell's set-up and a short window of
its own traffic at its own size, then the comparison with the reference
(the program's reading). For each control seed, the same comparison with
the control in the program's place: the reference computed in the
precision below the configuration's (``check(control=True)`` of the
cell's driver). ``--precision`` runs the program at that matmul
precision in place of the configuration's ``matmul_precision``: the
program's own lower-precision path. ``--fault`` plants a fault in the
program for every seed instead:

    half_batch   each KrK-Picard sweep sees only the first half of the
                 training subsets (Theta statistics and Armijo
                 log-likelihoods averaged over the rest); the tracked
                 log-likelihood still covers all of them
    state_unchanged
                 each KrK-Picard sweep returns the factors it was given

One JSON line per reading on standard output, with whether the program
(``program_correct``) and the control (``control_correct``) stay within
the configuration's limits, as a run of the cell would judge them.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` planted (see the module docstring)."""
    if fault is None:
        yield
        return
    import jax.numpy as jnp
    from repro.core.dpp import SubsetBatch
    from repro.learning import api, engine
    orig = engine.LearningEngine._krk_sweep

    def half(self, params, sub, a_trial):
        n = sub.indices.shape[0] // 2
        return orig(self, params,
                    SubsetBatch(sub.indices[:n], sub.mask[:n]), a_trial)

    def frozen(self, params, sub, a_trial):
        return params, a_trial, jnp.zeros((), jnp.int32)
    sweeps = {"half_batch": half, "state_unchanged": frozen}
    if fault not in sweeps:
        raise SystemExit(f"unknown fault {fault!r}")
    # engines (and their compiled sweeps) are cached per configuration:
    # the fault has to reach a fresh trace
    api._ENGINE_CACHE.clear()
    engine.LearningEngine._krk_sweep = sweeps[fault]
    try:
        yield
    finally:
        engine.LearningEngine._krk_sweep = orig
        api._ENGINE_CACHE.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--precision", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import cell as cell_mod
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_mod.Cell(args.workload, spec)
    if args.precision is not None:
        cell.config = dict(cell.config, matmul_precision=args.precision)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: the cell's chips are not there", file=sys.stderr)
        return 2
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = cell.driver.Driver(cell, seed, devices[: cell.chips])
        with planted(args.fault):
            drv.setup()
            win = drv.run(args.seconds, lambda name: contextlib.nullcontext())
        drv.release()
        checks = drv.check()
        out = {"workload": cell.name, "seed": seed, "fault": args.fault,
               "precision": args.precision, "attempted": win["attempted"],
               "program": {k: v["value"] for k, v in checks.items()},
               "program_correct": cell_mod.passes(checks)}
        if seed in controls:
            checks = drv.check(control=True)
            out["control"] = {k: v["value"] for k, v in checks.items()}
            out["control_correct"] = cell_mod.passes(checks)
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
