"""Tests of the benchmark harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_bench.py

They drive the harness without its look for a chip (``require_tpu=False``):
the trace reduction on a recorded chip trace and on hand-made events, the
operation counts against a hand count, a new cell and metric taken as
data, the bfloat16 controls failing the limits that the program passes,
and each fault a cell can have, planted under a whole run, turning
``correct`` false.
"""

from __future__ import annotations

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

from bench import cell, control, counts, trace            # noqa: E402

TINY_CONFIGS = {
    "kron-tiny": {"sizes": [8, 8], "expected_size": 4.0,
                  "dtype": "float32", "runtime": {"kind": "local"}},
    # the tiny learning cell states the default matmul precision, whose
    # control (the bfloat16 reference) fails the chip's limits at this
    # size; the "highest" of the real cell and its control are checked
    # by test_learning_control_at_high_reads_above_the_program
    "krk-tiny": {"sizes": [6, 6], "expected_size": 3.0, "subsets": 64,
                 "subset_width": 8, "dtype": "float32",
                 "matmul_precision": "default",
                 "runtime": {"kind": "local"}},
}
TINY_TRAFFIC = {
    "sample_tiny": {"kind": "closed_sample", "batch": 16, "check_calls": 2},
    "serve_tiny": {"kind": "open_serve", "rate_rps": 40.0,
                   "tenants": {"t0": 2, "t1": 1, "t2": 1, "t3": 1},
                   "rows": [1, 4], "max_batch": 64, "deadline_ms": 25.0,
                   "max_queue_depth": 8192, "check_requests": 20,
                   "drain_s": 30.0},
    "fit_tiny": {"kind": "fit_loop", "iters": 3, "log_every": 3,
                 "check_fits": 1},
}
TINY_CELLS = {"sample-tiny": ("kron-tiny", "sample_tiny"),
              "serve-tiny": ("kron-tiny", "serve_tiny"),
              "learn-tiny": ("krk-tiny", "fit_tiny")}


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout holding the real traffic drivers and metric readers, the
    real limits, and tiny configurations and cells."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    limits = {}
    for f in sorted((ROOT / "bench" / "configs").glob("*.json")):
        limits.update(json.loads(f.read_text())["limits"])
    (tmp_path / "bench").mkdir()
    for sub in ("traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    (tmp_path / "bench" / "configs").mkdir()
    configs = []
    for name, cfg in TINY_CONFIGS.items():
        keys = ("ll_gap", "factor_rel_gap") if "subsets" in cfg else \
            ("phase1_gap", "phase2_gap")
        cfg = dict(cfg, limits={k: limits[k] for k in keys})
        path = tmp_path / "bench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test", "reduced": [],
                        "file": f"bench/configs/{name}.json", "why": "test"})
    for name, t in TINY_TRAFFIC.items():
        (tmp_path / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    spec = dict(real, configs=configs, workloads=[
        {"name": w, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for w, (c, t) in TINY_CELLS.items()])
    by_kind = {"closed_sample": "sample-tiny", "open_serve": "serve-tiny",
               "fit_loop": "learn-tiny"}
    real_kind = {w["name"]: json.loads(
        (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text()
    )["kind"] for w in real["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({by_kind[real_kind[w]]
                                     for w in m["workloads"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def run_cell(root, workload, capsys, seed=5, seconds=1.0):
    rc = cell.main(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", "0"],
                   root=root, require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- trace reduction ---------------------------------------------------------

def test_trace_reduction_by_hand():
    ops = {0: [("fusion.1", 0, 10), ("fusion.2", 5, 15),
               ("%k.1 = custom-call", 20, 30),
               ("all-reduce.3", 25, 40), ("fusion.4", 50, 60)]}
    host = [("bench.wait", 12, 19), ("bench.dispatch", 41, 49),
            ("PjitFunction(f)", 42, 45)]
    tr = trace.DeviceTrace(ops, window_s=100e-9, host=host)
    assert tr.busy_s == pytest.approx(45e-9)         # [0,15] [20,40] [50,60]
    assert tr.idle_share == pytest.approx(0.55)
    assert tr.kernel_s("custom-call") == pytest.approx(10e-9)
    assert tr.collective_s() == pytest.approx(10e-9)  # 30..40 is exposed
    assert dict(tr.idle_gaps()) == {"bench.wait": pytest.approx(5e-9),
                                    "PjitFunction(f)": pytest.approx(10e-9)}
    assert tr.device_ops(1) == [["all-reduce.3", pytest.approx(15e-9)]]
    with pytest.raises(trace.KernelMissing):
        tr.kernel_s("phase2_select_pallas")
    assert trace.DeviceTrace({0: ops[0][:3]}, 1.0).collective_s() is None


def test_trace_reduction_on_a_recorded_chip_trace():
    rec = json.loads((ROOT / "bench" / "testdata" /
                      "sample_trace.json").read_text())
    ops = {int(d): [tuple(e) for e in v] for d, v in rec["ops"].items()}
    span = max(e for _, _, e in ops[0]) - min(s for _, s, _ in ops[0])
    tr = trace.DeviceTrace(ops, span / 1e9, [tuple(h) for h in rec["host"]])
    # two sample calls: two phase-2 kernel events, summed by hand
    p2 = [e - s for n, s, e in ops[0] if "phase2_select_pallas" in n]
    assert len(p2) == 2
    assert tr.kernel_s("phase2_select_pallas") == pytest.approx(sum(p2) / 1e9)
    assert 0 < tr.busy_s <= tr.window_s
    assert tr.kernel_s("phase2_select_pallas") < tr.busy_s
    # every nanosecond of the span is busy or in exactly one idle gap
    idle = sum(v for _, v in tr.idle_gaps(n=10 ** 6))
    assert idle + tr.busy_s == pytest.approx(tr.window_s, rel=1e-9)
    assert tr.device_ops(1)[0][0].startswith("phase2_select_pallas")


# -- operation counts --------------------------------------------------------

def test_counts_by_hand():
    # N = 2 x 3, a draw of k = 2: phase 1 4N = 24; phase 2 step 0:
    # 2Nk + 3N + N1 k + k = 24 + 18 + 4 + 2 = 48, step 1 adds 8tk = 16
    assert counts.draw_flops((2, 3), 2) == 24 + 48 + 48 + 16
    # eigenvectors and eigenvalues (4 + 2 + 9 + 3 floats) + 5 x 4 picks
    assert counts.call_bytes((2, 3), 5, 4) == 4 * 18 + 4 * 20
    flops, nbytes = counts.window_work((2, 3), [2, 2, 0, 0], 2, 4)
    assert flops == 2 * 136 + 2 * 24
    assert nbytes == 2 * counts.call_bytes((2, 3), 2, 4)
    share, bound = counts.roofline_share(2e12, 1e9, 1.0,
                                         {"flops": 1e15,
                                          "hbm_bytes_per_s": 1e12})
    assert (share, bound) == (pytest.approx(2e-3), "compute")


def test_sweep_counts_by_hand():
    # factors 2 x 2 and 3 x 3, subsets of 1, 2 and 2 items. A Theta pass:
    # k^3 + 5k^2 per subset = 6 + 28 + 28 = 62, twice a sweep = 124; the
    # eighs 4/3 (8 + 27) = 140/3
    assert counts.sweep_flops((2, 3), [1, 2, 2]) == pytest.approx(
        124 + 140 / 3)
    # per pass: 5 item indices, both factors read (4 + 9) and A, C
    # written (4 + 9): 31 words of 4 bytes, twice a sweep
    assert counts.sweep_bytes((2, 3), [1, 2, 2]) == 2 * 4 * 31
    # an empty slot costs nothing
    assert counts.sweep_flops((2, 3), [0, 1, 2, 2]) == \
        counts.sweep_flops((2, 3), [1, 2, 2])


# -- a new cell and metric are data ------------------------------------------

def test_new_cell_and_metric_are_files(tiny_root, capsys):
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
              if p.is_file()}
    (tiny_root / "bench" / "traffic" / "sample_b8.json").write_text(
        json.dumps({"kind": "closed_sample", "batch": 8, "check_calls": 1}))
    (tiny_root / "bench" / "metrics" / "calls_seen.sample.py").write_text(
        "def read(r):\n    return r.work.get('calls')\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "sample-b8", "config": "kron-tiny",
                              "traffic": "sample_b8", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "sample_rows_per_s":
            m["workloads"].append("sample-b8")
    spec["per_layer"].append({"name": "calls_seen.sample", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "sample_rows_per_s",
                              "workloads": ["sample-b8"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    c = cell.Cell("sample-b8", spec, tiny_root)
    assert c.traffic["batch"] == 8
    assert [m["name"] for m in c.per_layer] == ["calls_seen.sample"]
    reader = cell.load_module(c.metrics_dir / "calls_seen.sample.py")
    assert reader.read(cell.Reading(None, _Counters(), {"calls": 3},
                                    "cpu")) == 3
    out = run_cell(tiny_root, "sample-b8", capsys)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"sample_rows_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    after = {p: p.read_bytes() for p in before}
    assert after == before


class _Counters:
    counters, observations = {}, {}


# -- controls and faults -----------------------------------------------------

@pytest.mark.parametrize("workload", sorted(TINY_CELLS))
def test_program_passes_and_control_fails(tiny_root, workload):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    c = cell.Cell(workload, spec, tiny_root)
    import contextlib
    import jax
    drv = c.driver.Driver(c, 11, jax.devices()[:1])
    drv.setup()
    drv.run(0.5, lambda name: contextlib.nullcontext())
    drv.release()
    program = drv.check()
    assert all(v["value"] <= v["limit"] for v in program.values()), program
    ctrl = drv.check(control=True)
    assert any(v["value"] > v["limit"] for v in ctrl.values()), ctrl


def test_learning_control_at_high_reads_above_the_program(tiny_root):
    """The control of a float32-at-highest configuration, the reference
    with every intermediate held to two bfloat16 terms, reads a factor
    gap at least three times the program's. The chip's limit was set
    from full-size readings on the chip (PERF.md)."""
    import contextlib
    import jax
    path = tiny_root / "bench" / "configs" / "krk-tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    matmul_precision="highest")))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    c = cell.Cell("learn-tiny", spec, tiny_root)
    drv = c.driver.Driver(c, 11, jax.devices()[:1])
    drv.setup()
    drv.run(0.5, lambda name: contextlib.nullcontext())
    drv.release()
    program, ctrl = drv.check(), drv.check(control=True)
    assert (ctrl["factor_rel_gap"]["value"]
            >= 3 * program["factor_rel_gap"]["value"])


def test_serving_setup_warms_every_flush_size(tiny_root):
    """A flush drains whole requests until it holds max_batch rows, so it
    can overshoot to max_batch + 3 rows (padded to 128): set-up compiles
    that shape too, and nothing compiles when the window first meets it."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    c = cell.Cell("serve-tiny", spec, tiny_root)
    import jax
    drv = c.driver.Driver(c, 3, jax.devices()[:1])
    drv.setup()
    compiles = cell._compile_counter()
    compiles["on"] = True
    try:
        for rows in (1, 3, 5, 64, 67):
            drv.svc.submit(rows, tenant="t0").result(timeout=60.0)
    finally:
        compiles["on"] = False
        drv.svc.close()
    assert compiles["names"] == []


def _alter_first_pick(monkeypatch):
    from repro.dpp import model

    orig = model.sample_krondpp_batched

    def altered(*a, **kw):
        picks, counts_, trunc = orig(*a, **kw)
        n = a[1].N
        return picks.at[:, 0].set((picks[:, 0] + 1) % n), counts_, trunc
    monkeypatch.setattr(model, "sample_krondpp_batched", altered)


def _alter_served_row(monkeypatch):
    from repro.sampling.service import SamplingService

    orig = SamplingService.draw_keyed

    def altered(self, row_keys):
        rows, trunc, coll = orig(self, row_keys)
        rows = [[(i + 1) % self.spectrum.N for i in r] for r in rows]
        return rows, trunc, coll
    monkeypatch.setattr(SamplingService, "draw_keyed", altered)


FAULTS = {
    "altered_pick": ("sample-tiny", _alter_first_pick),
    "altered_served_row": ("serve-tiny", _alter_served_row),
    "state_unchanged": ("learn-tiny", None),
    "half_batch": ("learn-tiny", None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(tiny_root, capsys, monkeypatch,
                                        fault):
    from repro.learning import api
    workload, plant = FAULTS[fault]
    # engines are cached per configuration; a fault must reach the trace
    monkeypatch.setattr(api, "_ENGINE_CACHE", {})
    if plant is None:
        with control.planted(fault):
            out = run_cell(tiny_root, workload, capsys)
    else:
        plant(monkeypatch)
        out = run_cell(tiny_root, workload, capsys)
    assert out["correct"] is False, out["checks"]


# -- every cell of BENCHMARK.json resolves -----------------------------------

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY_OF_KIND = {"closed_sample": "sample-tiny", "open_serve": "serve-tiny",
                "fit_loop": "learn-tiny"}


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(tiny_root, workload):
    """The cell's configuration, traffic, driver and readers are files
    that load; a tiny cell of its driver's kind yields every end-to-end
    metric that applies to it, and each of its per-layer readers reads
    that window's counters and work without an error."""
    import contextlib
    import jax
    from repro import obs
    c = cell.Cell(workload, SPEC)
    w = {x["name"]: x for x in SPEC["workloads"]}[workload]
    cfg = {x["name"]: x for x in SPEC["configs"]}[w["config"]]
    assert (ROOT / cfg["file"]).is_file()
    assert hasattr(c.driver, "Driver")
    readers = {m["name"]: cell.load_module(c.metrics_dir / f"{m['name']}.py")
               for m in c.per_layer}
    assert readers and all(hasattr(r, "read") for r in readers.values())
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2

    tiny_spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    tiny = cell.Cell(TINY_OF_KIND[c.traffic["kind"]], tiny_spec, tiny_root)
    drv = tiny.driver.Driver(tiny, 13, jax.devices()[:1])
    drv.setup()
    tracker = obs.InMemoryTracker()
    prev = obs.configure(tracker)
    try:
        win = drv.run(0.5, lambda name: contextlib.nullcontext())
    finally:
        obs.configure(prev)
    drv.release()
    assert names - {"setup_s"} <= set(win["metrics"])
    busy = trace.DeviceTrace({0: [("fusion.1", 0, 10 ** 9)]}, 2.0)
    reading = cell.Reading(busy, tracker, win.get("work", {}), "TPU v5 lite")
    for name, reader in readers.items():
        value = reader.read(reading)
        assert value is None or np.isfinite(value), name


def test_no_result_without_a_chip(capsys):
    rc = cell.main(["--workload", "kron-sample-b256", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""
