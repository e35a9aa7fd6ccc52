"""Tests of the serving and learning cells' readers on recorded chip
traces, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_recorded_readers.py

``serve_trace.json`` and ``learn_trace.json`` are traced windows of
``kron-serve-poisson`` and ``krk-learn-batch`` on one TPU v5 lite, made
by ``bench/record_trace.py``: device 0's ops, the host events, and the
program's counters, observations and the driver's work over the window.
Each reader of those cells reads a value in its range from its own
cell's recording, and the readers of the program's counters and of the
driver's work read nothing from a trace of another cell, which has
neither. The idle shares read the trace alone, so they read any trace.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np                                          # noqa: E402
import pytest                                               # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import cell, counts, peaks, trace               # noqa: E402

KIND = "TPU v5 lite"
SERVE_TRAFFIC = json.loads(
    (ROOT / "bench" / "traffic" / "serve_poisson.json").read_text())


class _Tracker:
    def __init__(self, rec):
        self.counters = rec.get("counters", {})
        self.observations = rec.get("observations", {})


def _recorded(name):
    rec = json.loads((ROOT / "bench" / "testdata" / name).read_text())
    ops = {int(d): [tuple(e) for e in v] for d, v in rec["ops"].items()}
    span = max(e for _, _, e in ops[0]) - min(s for _, s, _ in ops[0])
    tr = trace.DeviceTrace(ops, span / 1e9, [tuple(h) for h in rec["host"]])
    return rec, tr


def _read(metric, rec, tr):
    reader = cell.load_module(ROOT / "bench" / "metrics" / f"{metric}.py")
    return reader.read(cell.Reading(tr, _Tracker(rec), rec.get("work", {}),
                                    KIND))


def test_serving_readers_on_a_recorded_window():
    rec, tr = _recorded("serve_trace.json")
    c, obs_ = rec["counters"], rec["observations"]
    rows = _read("serve.rows_per_call", rec, tr)
    assert rows == pytest.approx(c["serving.requested_rows"]
                                 / c["service.device_calls"])
    # a flush drains whole requests up to max_batch rows
    hi = SERVE_TRAFFIC["max_batch"] + SERVE_TRAFFIC["rows"][1] - 1
    assert 1.0 <= rows <= hi
    wait = _read("serve.queue_wait_p95_ms", rec, tr)
    assert wait == pytest.approx(
        1e3 * np.percentile(obs_["serving.queue_wait_s"], 95))
    assert 0.0 < wait < 4 * SERVE_TRAFFIC["deadline_ms"]
    idle = _read("idle_share.serve", rec, tr)
    assert idle == pytest.approx(100.0 * tr.idle_share)
    assert 0.0 < idle < 100.0


def test_learning_readers_on_a_recorded_window():
    rec, tr = _recorded("learn_trace.json")
    c, w = rec["counters"], rec["work"]
    assert w["sweeps"] == c["learning.sweeps"] > 0
    bt = _read("learn.backtracks_per_sweep", rec, tr)
    assert bt == pytest.approx(c.get("learning.backtracks", 0)
                               / c["learning.sweeps"])
    # at most max_backtracks (8) per half-update, two half-updates
    assert 0.0 <= bt <= 16.0
    idle = _read("idle_share.learn", rec, tr)
    assert idle == pytest.approx(100.0 * tr.idle_share)
    assert 0.0 <= idle < 100.0
    share = _read("learn.sweep_roofline", rec, tr)
    least = w["sweeps"] * counts.sweep_bytes(w["factor_sizes"],
                                             w["subset_sizes"]) \
        / peaks.peaks(KIND)["hbm_bytes_per_s"]
    assert share == pytest.approx(100.0 * least / tr.busy_s)
    assert 0.0 < share < 100.0
    assert len(w["subset_sizes"]) == w["subsets"]
    assert max(w["subset_sizes"]) <= w["width"]


@pytest.mark.parametrize("metric,other", [
    ("serve.rows_per_call", "learn_trace.json"),
    ("serve.queue_wait_p95_ms", "learn_trace.json"),
    ("learn.backtracks_per_sweep", "serve_trace.json"),
    ("learn.sweep_roofline", "serve_trace.json"),
    ("serve.rows_per_call", "sample_trace_spans.json"),
    ("serve.queue_wait_p95_ms", "sample_trace_spans.json"),
    ("learn.backtracks_per_sweep", "sample_trace_spans.json"),
    ("learn.sweep_roofline", "sample_trace_spans.json"),
])
def test_readers_find_nothing_without_their_counters(metric, other):
    rec, tr = _recorded(other)
    assert _read(metric, rec, tr) is None
