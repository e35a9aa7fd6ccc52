"""Record one traced window of a cell as a small JSON file, for the
readers' tests.

    python3 bench/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --out <file.json>

One process, on the chip: the cell's set-up, then its traced window of
``--seconds`` (``bench/cell.py``'s ``run_window``, as ``--trace 1``
runs it), reduced to what the readers read. A fit-loop window
runs whole fits, so a short ``--seconds`` records exactly one. The file
holds device 0's XLA ops (each named by its HLO instruction), the host
events, the program's counters and observations over the window, and
the work the driver counted, all times in ns from the first op.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import cell as cell_mod
    cell_mod.use_compile_cache()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_mod.Cell(args.workload, spec)
    import jax
    import numpy as np
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("record_trace: the cell's chips are not there", file=sys.stderr)
        return 2
    compiles = cell_mod._compile_counter()
    drv = cell.driver.Driver(cell, args.seed, devices[: cell.chips])
    drv.setup()
    win, tracker, tr = cell_mod.run_window(drv, args.seconds, devices[:1],
                                           compiles, trace=True)
    drv.release()
    ops = tr.ops[min(tr.ops)]
    t0 = min(s for _, s, _ in ops)
    t1 = max(e for _, _, e in ops)
    work = {k: (np.asarray(v).tolist() if isinstance(v, np.ndarray) else v)
            for k, v in win.get("work", {}).items()}
    rec = {
        "note": f"one traced window of {cell.name} (seed {args.seed}, "
                f"{args.seconds} s asked, {win['window_s']:.3f} s run) on "
                f"one {devices[0].device_kind}: XLA ops of device 0 and "
                f"the host events in their span, times in ns from the "
                f"first op; the program's counters and observations and "
                f"the driver's work over the window",
        "attempted": win["attempted"],
        "counters": dict(tracker.counters),
        "observations": {k: list(v)
                         for k, v in tracker.observations.items()},
        "work": work,
        "ops": {"0": [[n.split(" = ")[0].lstrip("%"), s - t0, e - t0]
                      for n, s, e in ops]},
        "host": [[n, s - t0, e - t0] for n, s, e in tr.host
                 if e > t0 and s < t1],
    }
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(rec, separators=(",", ":")))
    print(json.dumps({"out": args.out, "ops": len(rec["ops"]["0"]),
                      "host": len(rec["host"]), "window": win["info"],
                      "compiles_in_window": compiles["names"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
