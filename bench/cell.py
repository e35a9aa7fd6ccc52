"""Run one benchmark cell once and print its result line.

    python3 bench/cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` with that name.
Everything else is found by name, so a new cell, configuration, traffic
mix or per-layer metric is new files and entries, never an edit:

    bench/configs/<config>.json   sizes, precision, limits (``configs``)
    bench/traffic/<traffic>.json  parameters of the cell's traffic; its
                                  ``kind`` names the driver
    bench/traffic/<kind>.py       the driver of one traffic kind
    bench/metrics/<metric>.py     the reader of one per-layer metric

A run sets up the program and warms every shape its traffic uses
(``setup_s``), measures for ``--seconds``, reads the device's peak
memory, frees the program's state, and only then checks what the window
produced against the plain reference. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` profiles the same window and reports
its per-layer metrics, ``busy_s``/``window_s`` and a breakdown. The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key
of that object. A run that finds no TPU, or fewer chips than the cell
asks for, exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import contextlib                                           # noqa: E402
import gc                                                   # noqa: E402
import importlib.util                                       # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import shutil                                               # noqa: E402
import sys                                                  # noqa: E402
import tempfile                                             # noqa: E402
from pathlib import Path                                    # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, this file's directory leads sys.path; its modules are
# imported as ``bench.*`` from the checkout instead, so that none of them
# (``trace``, ``data``) shadows a module of the same name
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
#: JAX's persistent compilation cache, at a fixed path inside the
#: checkout, so that only a cell's first run there compiles.
CACHE_DIR = ROOT / ".jax_cache"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with its configuration and traffic files,
    found under ``root`` (the checkout)."""

    def __init__(self, name: str, spec: dict, root: Path = ROOT):
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(by_name)}")
        w = by_name[name]
        self.name, self.chips = name, int(w["chips"])
        cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
        self.config = json.loads((root / cfg["file"]).read_text())
        self.traffic = json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        self.driver = load_module(
            root / "bench" / "traffic" / f"{self.traffic['kind']}.py")
        self.metrics_dir = root / "bench" / "metrics"

        def applies(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in spec["end_to_end"] if applies(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", ())
                          or ("workloads" not in m and m["moves"] in moved)]


class Reading:
    """What a per-layer metric reader may read: the device trace of the
    window, the program's counters and observations in it, and the work
    the traffic driver counted."""

    def __init__(self, trace, tracker, work: dict, device_kind: str):
        self.trace = trace
        self.counters = dict(tracker.counters)
        self.observations = {k: list(v)
                             for k, v in tracker.observations.items()}
        self.work = work
        self.device_kind = device_kind


def _compile_counter():
    """Names the programs lowered or compiled while ``on`` is set."""
    import jax
    events = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    state = {"on": False, "names": []}

    def listen(event, duration, fun_name="?", **_):
        if state["on"] and event in events:
            state["names"].append(fun_name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    return state


def passes(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` and keep
    every program there; call before JAX compiles anything."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def run_window(drv, seconds: float, devices, compiles: dict,
               trace: bool):
    """The measured window: ``drv.run`` for ``seconds`` with ``compiles``
    on. With ``trace``, under the profiler and with an
    ``obs.InMemoryTracker`` installed; returns ``(win, tracker,
    DeviceTrace of devices)``, and ``(win, None, None)`` without."""
    import jax
    from repro import obs
    from bench import trace as trace_mod
    annotate = lambda name: contextlib.nullcontext()       # noqa: E731
    if trace:
        tracker = obs.InMemoryTracker()
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        prev = obs.configure(tracker)
        annotate = jax.profiler.TraceAnnotation
    compiles["on"] = True
    try:
        win = drv.run(seconds, annotate)
    finally:
        compiles["on"] = False
        if trace:
            obs.configure(prev)
            jax.profiler.stop_trace()
    if not trace:
        return win, None, None
    tr = trace_mod.DeviceTrace.from_dir(log_dir, win["window_s"],
                                        devices=[d.id for d in devices])
    shutil.rmtree(log_dir, ignore_errors=True)
    return win, tracker, tr


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> int:
    """Run one cell. ``root`` is the checkout that holds
    ``BENCHMARK.json``; tests pass another and ``require_tpu=False``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = Cell(args.workload, spec, root)
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    compiles = _compile_counter()
    drv = cell.driver.Driver(cell, args.seed, devices[: cell.chips])
    drv.setup()
    setup_s = time.perf_counter() - T_START

    win, tracker, tr = run_window(drv, args.seconds, devices[: cell.chips],
                                  compiles, bool(args.trace))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[: cell.chips])
    drv.release()
    gc.collect()
    checks = drv.check()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if args.trace:
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        reading = Reading(tr, tracker, win.get("work", {}),
                          devices[0].device_kind)
        for m in cell.per_layer:
            value = load_module(cell.metrics_dir / f"{m['name']}.py"
                                ).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.device_ops(),
                     "idle_gaps": tr.idle_gaps()}
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else \
                win["metrics"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    print(json.dumps({"window": win.get("info", {}),
                      "compiles_in_window": compiles["names"],
                      "setup_s": setup_s}), file=sys.stderr)
    correct = passes(checks)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr)
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
