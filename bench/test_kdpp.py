"""Tests of the k-DPP cell's harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_kdpp.py

The cell ``kron-kdpp-k8-b64`` (traffic kind ``closed_kdpp``, keyed
replay ``bench/kdpp_ref.py``) runs end to end through ``cell.main`` with
its look for a chip off (``require_tpu=False``) on a tiny configuration
that keeps the real limits: the program passes, the bfloat16 control and
the planted ESP fault (``bench/kdpp_control.py``) fail. The replay's
phase 1 draws the k-DPP's eigen-index law. The three readers read a
recorded chip window of the cell (``testdata/kdpp_trace.json``, made by
``bench/record_trace.py``) and nothing from windows that lack what they
read.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np                                          # noqa: E402
import pytest                                               # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import (cell, counts, kdpp_control, kdpp_counts,  # noqa: E402
                   kdpp_ref, peaks, trace)
from bench.held import held                                 # noqa: E402

KIND = "TPU v5 lite"
CELL = "kron-kdpp-k8-b64"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
READERS = ("kdpp.phase2.kernel_share", "kdpp.sampler_roofline",
           "kdpp.esp_builds_per_call")


@pytest.fixture()
def kdpp_root(tmp_path):
    """A checkout holding the real traffic drivers, readers and limits,
    and a tiny k-DPP cell ``kdpp-tiny`` (8 x 8 items, k = 3)."""
    (tmp_path / "bench").mkdir()
    for sub in ("traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    (tmp_path / "bench" / "configs").mkdir()
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "kron-kdpp-1e4.json").read_text())
    cfg.update(sizes=[8, 8], expected_size=4.0, k=3)
    (tmp_path / "bench" / "configs" / "kdpp-tiny.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "kdpp_tiny.json").write_text(
        json.dumps({"kind": "closed_kdpp", "batch": 16, "check_calls": 2}))
    spec = dict(SPEC, configs=[
        {"name": "kdpp-tiny", "source": "test", "reduced": [],
         "file": "bench/configs/kdpp-tiny.json", "why": "test"}],
        workloads=[{"name": "kdpp-tiny", "config": "kdpp-tiny",
                    "traffic": "kdpp_tiny", "chips": 1, "why": "test"}])
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["kdpp-tiny"] if CELL in m["workloads"] else []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def _run(root, capsys, seed=4294967311):
    rc = cell.main(["--workload", "kdpp-tiny", "--seed", str(seed),
                    "--seconds", "0.5", "--trace", "0"],
                   root=root, require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_resolves():
    c = cell.Cell(CELL, SPEC)
    assert c.traffic["kind"] == "closed_kdpp" and c.config["k"] == 8
    assert {m["name"] for m in c.end_to_end} == {"sample_rows_per_s",
                                                 "setup_s"}
    assert {m["name"] for m in c.per_layer} == set(READERS)
    assert set(c.config["limits"]) == {"kdpp_phase1_gap", "phase2_gap"}


def test_closed_kdpp_and_the_replay_run_end_to_end(kdpp_root, capsys):
    out = _run(kdpp_root, capsys)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"sample_rows_per_s", "setup_s"}
    assert set(out["checks"]) == {"wrong_size_rows", "kdpp_phase1_gap",
                                  "phase2_gap"}
    assert out["checks"]["wrong_size_rows"]["value"] == 0
    assert list(out)[-1] == "checks"


def test_program_passes_and_control_fails(kdpp_root):
    import jax
    spec = json.loads((kdpp_root / "BENCHMARK.json").read_text())
    c = cell.Cell("kdpp-tiny", spec, kdpp_root)
    drv = c.driver.Driver(c, 11, jax.devices()[:1])
    drv.setup()
    drv.run(0.5, lambda name: contextlib.nullcontext())
    drv.release()
    assert drv.work["k_max"] == 3
    assert set(np.asarray(drv.work["sizes"]).tolist()) == {3}
    program = drv.check()
    assert all(v["value"] <= v["limit"] for v in program.values()), program
    ctrl = drv.check(control=True)
    assert any(v["value"] > v["limit"] for v in ctrl.values()), ctrl


def test_the_esp_fault_is_not_correct(kdpp_root, capsys):
    with kdpp_control.planted("esp_k_minus_1"):
        out = _run(kdpp_root, capsys, seed=7)
    assert out["correct"] is False, out["checks"]
    # the sound program runs again once the fault is lifted
    assert _run(kdpp_root, capsys, seed=8)["correct"] is True


def test_the_replay_draws_the_kdpp_eigen_index_law():
    """Phase 1 of the replay keeps J with probability prod lambda_J / e_k."""
    checker = kdpp_ref.KdppChecker([(np.array([1.0, 2.0]), np.eye(2)),
                                    (np.array([0.2, 1.0, 1.5]), np.eye(3))], 2)
    lam = np.exp(checker.log_lam)
    e_k = sum(np.prod(lam[list(c)]) for c in itertools.combinations(range(6),
                                                                     2))
    rng = np.random.default_rng(0)
    n = 20000
    seen = {}
    for _ in range(n):
        J, _ = checker.draw(rng.random(6))
        seen[tuple(J)] = seen.get(tuple(J), 0) + 1
        assert len(J) == 2
    for J in itertools.combinations(range(6), 2):
        p = np.prod(lam[list(J)]) / e_k
        f = seen.get(J, 0) / n
        assert abs(f - p) < 5 * np.sqrt(p * (1 - p) / n) + 1e-3, (J, f, p)


def test_kdpp_counts_by_hand():
    # N = 2 x 3, k = 2: the table N + 6Nk = 6 + 72; a row 6N = 36 plus
    # the chain rule at k = 2 (bench/counts.py: 48 + 48 + 16 = 112)
    assert kdpp_counts.esp_flops((2, 3), 2) == 78
    assert kdpp_counts.row_flops((2, 3), 2) == 36 + 112
    flops, nbytes = kdpp_counts.window_work((2, 3), 2, rows=10, calls=2)
    assert flops == 2 * 78 + 10 * 148
    assert nbytes == 2 * counts.call_bytes((2, 3), 5, 2)


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


def test_kdpp_readers_on_a_recorded_window():
    rec, tr = _recorded("kdpp_trace.json")
    w = rec["work"]
    assert w["calls"] == rec["attempted"] > 0 and w["k_max"] == 8
    assert len(w["sizes"]) == 64 * w["calls"] and set(w["sizes"]) == {8}
    assert _read("kdpp.esp_builds_per_call", rec, tr) == 1.0
    # the window's last call is not held: its wait ends after the last op
    calls, ops, busy_s = held(cell.Reading(tr, _Tracker(rec), w, KIND))
    assert calls == w["calls"] - 1 and 0 < busy_s < tr.busy_s
    share = _read("kdpp.phase2.kernel_share", rec, tr)
    kernel = [e - s for n, s, e in ops if "phase2_select_pallas" in n]
    assert len(kernel) == calls
    assert share == pytest.approx(100.0 * sum(kernel) / 1e9 / busy_s)
    assert 0.0 < share < 50.0
    roof = _read("kdpp.sampler_roofline", rec, tr)
    flops, _ = kdpp_counts.window_work(w["factor_sizes"], 8, 64 * calls,
                                       calls)
    assert roof == pytest.approx(
        100.0 * flops / peaks.peaks(KIND)["flops"] / busy_s)
    assert 0.0 < roof < 100.0


def test_kdpp_readers_hold_to_the_calls_a_cut_trace_holds():
    """A trace that ends inside a call (the profiler's buffer full) is
    read over the calls before it, not over the window's work."""
    rec, tr = _recorded("kdpp_trace.json")
    blocks = [h for h in tr.host if h[0] == "bench.block"]
    cut = (blocks[1][2] + blocks[2][2]) // 2          # inside call 3
    ops = [(n, s, min(e, cut)) for n, s, e in tr.ops[0] if s < cut]
    short = trace.DeviceTrace({0: ops}, tr.window_s, tr.host)
    first_two = trace.DeviceTrace(
        {0: [o for o in ops if o[1] < blocks[1][2]]}, tr.window_s, tr.host)
    assert held(cell.Reading(short, _Tracker(rec), rec["work"],
                             KIND))[0] == 2
    for metric in ("kdpp.phase2.kernel_share", "kdpp.sampler_roofline"):
        got = _read(metric, rec, short)
        assert got == pytest.approx(_read(metric, rec, first_two))
        assert got != pytest.approx(_read(metric, rec, tr))


@pytest.mark.parametrize("metric,other", [
    ("kdpp.esp_builds_per_call", "sample_trace_spans.json"),
    ("kdpp.esp_builds_per_call", "learn_trace.json"),
    ("kdpp.esp_builds_per_call", "serve_trace.json"),
    ("kdpp.phase2.kernel_share", "learn_trace.json"),
    ("kdpp.sampler_roofline", "learn_trace.json"),
    ("kdpp.sampler_roofline", "serve_trace.json"),
])
def test_kdpp_readers_find_nothing_without_their_counters(metric, other):
    rec, tr = _recorded(other)
    assert _read(metric, rec, tr) is None
