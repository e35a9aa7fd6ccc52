"""Tests of the readers of the program's facade spans and host-sync
counter, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_program_spans.py

``idle_share.sample.facade``, ``sample.k_max_ms`` and
``sample.host_syncs_per_call`` read the ``dpp.sample`` spans that
``DPPModel.sample`` writes into the profiler trace and its
``dpp.host_syncs`` counter. They are checked on a hand-made trace whose
answers are worked out below, on a recorded chip trace of a program with
the spans, and on the recorded trace of a program without them, where
each reads nothing.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest                                               # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import cell, trace                              # noqa: E402

READERS = ("idle_share.sample.facade", "sample.k_max_ms",
           "sample.host_syncs_per_call")
MS = 1_000_000                                              # ns


class _Tracker:
    def __init__(self, counters):
        self.counters, self.observations = counters, {}


def _read(name, tr, counters=None, calls=0):
    reader = cell.load_module(ROOT / "bench" / "metrics" / f"{name}.py")
    return reader.read(cell.Reading(tr, _Tracker(counters or {}),
                                    {"calls": calls}, "TPU v5 lite"))


def _recorded(name):
    rec = json.loads((ROOT / "bench" / "testdata" / name).read_text())
    ops = {int(d): [tuple(e) for e in v] for d, v in rec["ops"].items()}
    span = max(e for _, _, e in ops[0]) - min(s for _, s, _ in ops[0])
    return rec, trace.DeviceTrace(ops, span / 1e9,
                                  [tuple(h) for h in rec["host"]])


def test_readers_by_hand():
    # device 0 busy [5, 10] [30, 40] [70, 80] ms of a 100 ms window:
    # idle gaps (10, 30) and (40, 70)
    ops = {0: [("fusion.1", 5 * MS, 10 * MS), ("fusion.2", 30 * MS, 35 * MS),
               ("phase2_select_pallas.1", 34 * MS, 40 * MS),
               ("while.3", 70 * MS, 80 * MS)]}
    host = [
        # a call that ends in busy time: clipped to [5, 8], all busy, 0
        ("dpp.sample", 0, 8 * MS),
        # gap (10, 30) inside [2, 25]: 15 ms; phases do not count twice
        ("bench.dispatch", 1 * MS, 26 * MS),
        ("dpp.sample", 2 * MS, 25 * MS),
        ("dpp.sample.k_max", 3 * MS, 7 * MS),
        ("dpp.sample.draw", 12 * MS, 24 * MS),
        ("bench.block", 26 * MS, 29 * MS),      # harness, not the facade
        # gap (40, 70) inside [38, 65]: 25 ms
        ("dpp.sample", 38 * MS, 65 * MS),
        ("dpp.sample.k_max", 50 * MS, 56 * MS),
        # past the last busy span: no gap there
        ("dpp.sample", 82 * MS, 95 * MS),
    ]
    tr = trace.DeviceTrace(ops, window_s=0.1, host=host)
    assert tr.idle_share == pytest.approx(0.75)
    facade = _read("idle_share.sample.facade", tr)
    assert facade == pytest.approx(100.0 * (15 + 25) / 100)
    assert facade <= 100.0 * tr.idle_share
    assert _read("sample.k_max_ms", tr) == pytest.approx((4 + 6) / 2)
    assert _read("sample.host_syncs_per_call", tr,
                 {"dpp.host_syncs": 8}, calls=4) == 2.0
    # the counter absent from a program that opens the spans reads 0
    assert _read("sample.host_syncs_per_call", tr, calls=4) == 0.0
    assert _read("sample.host_syncs_per_call", tr, calls=0) is None


def test_readers_on_a_recorded_chip_trace_with_the_spans():
    rec, tr = _recorded("sample_trace_spans.json")
    roots = [h for h in tr.host if h[0] == "dpp.sample"]
    assert len(roots) == rec["calls"] == 2
    for name in ("dpp.sample.spectrum", "dpp.sample.k_max",
                 "dpp.sample.draw", "dpp.sample.pack"):
        inner = [h for h in tr.host if h[0] == name]
        assert len(inner) == 2, name
        assert all(r[1] <= s and e <= r[2] for (_, s, e), r in
                   zip(inner, roots)), name
    k_max = [e - s for n, s, e in tr.host if n == "dpp.sample.k_max"]
    assert _read("sample.k_max_ms", tr) == pytest.approx(
        sum(k_max) / 2 / MS)
    facade = _read("idle_share.sample.facade", tr)
    assert 0 < facade <= 100.0 * tr.idle_share
    assert _read("sample.host_syncs_per_call", tr, rec["counters"],
                 rec["calls"]) == 2.0


def test_readers_find_nothing_in_a_program_without_the_spans():
    _, tr = _recorded("sample_trace.json")
    for name in READERS:
        assert _read(name, tr, {}, calls=2) is None, name
