"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Observability is wired
through ``repro.obs``: every benchmark's metrics flow through the
configured tracker (``common.json_report`` emits a ``benchmark.report``
event per result), ``--jsonl`` captures the whole run as an append-only
run log, and ``--trace`` exports it as one Chrome trace-event file per
benchmark (see the README "Observability" section).

A benchmark that raises no longer lets the process end green: the
harness keeps running the remaining benchmarks (so one broken module
does not hide the rest of the trend data) but exits nonzero, naming
every failure.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback
from typing import List


def _modules():
    from . import (facade_api, kernel_bench, lowrank_dual,
                   paper_fig1_engine, paper_fig1_synthetic,
                   paper_fig1c_stochastic, paper_sec4_batched_sampling,
                   paper_sec4_phase2_fused, paper_sec4_sampling,
                   paper_table1_quality, paper_table2_runtime, roofline,
                   runtime_scaling, serving_load)
    return (paper_fig1_synthetic, paper_fig1c_stochastic,
            paper_fig1_engine,
            paper_table1_quality, paper_table2_runtime,
            paper_sec4_sampling, paper_sec4_batched_sampling,
            paper_sec4_phase2_fused,
            facade_api, lowrank_dual, runtime_scaling,
            kernel_bench, roofline, serving_load)


def _short(mod) -> str:
    return mod.__name__.rsplit(".", 1)[-1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Run the benchmark suite (CSV to stdout, JSON reports "
                    "via benchmarks.common).")
    parser.add_argument(
        "--only", action="append", default=None, metavar="NAME",
        help="run only this benchmark module (repeatable), e.g. "
             "--only facade_api")
    parser.add_argument(
        "--jsonl", default=None, metavar="PATH",
        help="append every tracker emission (benchmark.report events, "
             "service/learning/cache metrics) to PATH as a JSONL run log")
    parser.add_argument(
        "--trace", nargs="?", const="traces", default=None, metavar="DIR",
        help="export a chrome://tracing trace-event file per benchmark to "
             "DIR/<name>.trace.json (implies a JSONL run log; default DIR: "
             "./traces)")
    parser.add_argument(
        "--list", action="store_true", help="list benchmark names and exit")
    args = parser.parse_args(argv)

    mods = _modules()
    if args.list:
        for mod in mods:
            print(_short(mod))
        return 0
    if args.only:
        by_name = {_short(m): m for m in mods}
        unknown = [n for n in args.only if n not in by_name]
        if unknown:
            parser.error(f"unknown benchmark(s) {unknown}; "
                         f"choose from {sorted(by_name)}")
        mods = tuple(by_name[n] for n in args.only)

    from repro import obs
    if args.trace and not args.jsonl:
        # the Chrome export reads span records back out of a run log
        os.makedirs(args.trace, exist_ok=True)
        args.jsonl = os.path.join(args.trace, "run_log.jsonl")
    if args.jsonl:
        obs.configure(obs.current_tracker(), jsonl=args.jsonl)
    tracker = obs.current_tracker()

    failures: List[str] = []
    print("name,us_per_call,derived")
    for mod in mods:
        name = _short(mod)
        t0 = time.perf_counter()
        try:
            # the scope tags every emission with bench=<name> (what the
            # per-bench Chrome export filters on); the span makes the
            # benchmark itself the root of any request traces it starts
            with tracker.scope(bench=name), \
                    obs.spans.start_span("benchmark", tracker=tracker,
                                         bench=name):
                mod.main()
            tracker.observe("benchmark.wall_s", time.perf_counter() - t0,
                            bench=name)
        except Exception as e:      # keep the harness running, fail at exit
            traceback.print_exc()
            print(f"{mod.__name__},error,0,{type(e).__name__}: {e}",
                  file=sys.stderr)
            tracker.counter("benchmark.failures", bench=name)
            failures.append(f"{name}: {type(e).__name__}: {e}")
    if args.trace:
        os.makedirs(args.trace, exist_ok=True)
        for mod in mods:
            name = _short(mod)
            out = os.path.join(args.trace, f"{name}.trace.json")
            exported = obs.ChromeTraceExporter(
                tag_filter={"bench": name}).export(args.jsonl, out)
            print(f"run.py: wrote {out} "
                  f"({len(exported['traceEvents'])} events)", file=sys.stderr)
    if failures:
        print(f"run.py: {len(failures)} benchmark(s) FAILED:",
              file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.configure()
    sys.exit(main())
