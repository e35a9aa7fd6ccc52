"""repro.obs — the unified metrics/tracing layer, and the regression gate.

Four layers of coverage:

  * trackers: the four primitives aggregate correctly (InMemory), round-
    trip through the JSONL run log, fan out through tee, and the
    ``configure``/``use`` seam installs and restores the process-wide
    sink.
  * the NullTracker zero-overhead contract: timer/scope hand back one
    shared context manager, per-call cost is bounded, and instrumentation
    inside jit-traced code fires at trace time only (once per compiled
    specialization — never per executed call).
  * instrumented hot paths: ``SamplingService`` (ServiceStats as a live
    view over ``service.*`` counters, naming parity with
    ``SpectralCache.stats()``), the spectral cache hit/miss/eigh stream,
    ``learning.fit`` events + per-sweep metrics, and the ``kernels.ops``
    dispatch counters.
  * the benchmark regression gate (benchmarks/regression.py): equal
    reports pass, a committed report with throughput inflated >25%
    fails (exit 2 through main), and mismatched config fingerprints or
    schema versions refuse the comparison outright.
"""

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dpp, obs
from repro.core import random_krondpp
from repro.sampling import SpectralCache
from repro.sampling.service import ServiceStats

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))          # `import benchmarks.*` (namespace pkg)

from benchmarks.common import SCHEMA_VERSION, report_meta       # noqa: E402
from benchmarks.regression import (GATED, compare_reports,      # noqa: E402
                                   extract_metrics, merge_best)
from benchmarks.regression import main as regression_main       # noqa: E402


def _model():
    return dpp.random_kron(jax.random.PRNGKey(0), (4, 5)).rescale(4.0)


# ---------------------------------------------------------------------------
# trackers: primitives, sinks, and the configure/use seam
# ---------------------------------------------------------------------------

def test_in_memory_tracker_aggregates_by_name():
    t = obs.InMemoryTracker()
    t.counter("c")
    t.counter("c", 4, shard=1)        # tags fold away in the aggregate
    t.gauge("g", 1.5)
    t.gauge("g", 2.5)                 # last value wins
    t.observe("lat_s", 0.1)
    t.observe("lat_s", 0.3)
    t.event("done", ok=True)
    assert t.counters == {"c": 5}
    assert t.counter_value("c") == 5 and t.counter_value("absent") == 0
    assert t.gauges == {"g": 2.5}
    assert t.observations["lat_s"] == [0.1, 0.3]
    assert t.events == [{"name": "done", "ok": True}]
    snap = t.snapshot()
    assert snap["counters"] == {"c": 5} and snap["events"] == 1
    assert snap["timers"]["lat_s"]["count"] == 2
    assert snap["timers"]["lat_s"]["sum_s"] == pytest.approx(0.4)
    assert t.percentile("lat_s", 0) == 0.1
    assert t.percentile("lat_s", 99) == 0.3
    assert np.isnan(t.percentile("absent", 50))


def test_timer_and_scope_tags():
    t = obs.InMemoryTracker(keep_records=True)
    with t.scope(run="r1", shard=0):
        with t.scope(shard=3):        # inner scope overrides
            t.counter("work", 2, op="mv")
            with t.timer("step_s", phase="p2"):
                time.sleep(0.01)
        t.event("flush", n=7)
    recs = {r["name"]: r for r in t.records}
    assert recs["work"]["tags"] == {"run": "r1", "shard": 3, "op": "mv"}
    assert recs["step_s"]["tags"] == {"run": "r1", "shard": 3, "phase": "p2"}
    assert t.observations["step_s"][0] >= 0.01
    assert t.events == [{"name": "flush", "run": "r1", "shard": 0, "n": 7}]
    with t.scope(a=1):                # stack unwinds cleanly
        pass
    t.counter("untagged")
    assert {r["name"]: r["tags"] for r in t.records}["untagged"] == {}


def test_jsonl_tracker_round_trips(tmp_path):
    path = tmp_path / "run.jsonl"
    with obs.JsonlTracker(str(path)) as t:
        with t.scope(bench="demo"):
            t.counter("calls", 3)
            t.observe("wall_s", 0.25)
        t.gauge("step", np.float32(0.5))       # numpy scalars coerce
        t.event("report", rows=[1, 2], arr=jnp.arange(2))
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["counter", "observe", "gauge",
                                         "event"]
    assert recs[0]["name"] == "calls" and recs[0]["value"] == 3
    assert recs[0]["tags"] == {"bench": "demo"}
    assert recs[1]["seconds"] == 0.25
    assert recs[2]["value"] == 0.5             # json-clean, not a repr
    assert recs[3]["fields"] == {"rows": [1, 2], "arr": [0, 1]}
    assert all(r["t"] > 0 for r in recs)


def test_tee_fans_out_and_collapses_nulls():
    a, b = obs.InMemoryTracker(), obs.InMemoryTracker()
    teed = obs.tee(a, obs.NullTracker(), b)
    teed.counter("x")
    teed.gauge("y", 2.0)
    teed.observe("z", 0.1)
    teed.event("e")
    for t in (a, b):
        assert t.counters == {"x": 1} and t.gauges == {"y": 2.0}
        assert len(t.observations["z"]) == 1 and len(t.events) == 1
    assert obs.tee(a) is a                     # single sink: no Tee wrapper
    assert isinstance(obs.tee(obs.NullTracker(), obs.NullTracker()),
                      obs.NullTracker)
    assert not obs.enabled(obs.NullTracker())
    assert obs.enabled(a)


def test_configure_and_use_restore_previous(tmp_path):
    assert isinstance(obs.current_tracker(), obs.NullTracker)
    t = obs.InMemoryTracker()
    prev = obs.configure(t)
    try:
        assert obs.current_tracker() is t
        with obs.use(obs.InMemoryTracker()) as inner:
            assert obs.current_tracker() is inner
        assert obs.current_tracker() is t      # use() restored
    finally:
        obs.configure(prev)
    assert isinstance(obs.current_tracker(), obs.NullTracker)
    # configure(tracker, jsonl=...) tees them; configure() resets
    path = tmp_path / "log.jsonl"
    obs.configure(t, jsonl=str(path))
    try:
        obs.current_tracker().counter("both")
    finally:
        obs.configure()
    assert t.counters["both"] == 1
    assert json.loads(path.read_text())["name"] == "both"
    assert isinstance(obs.current_tracker(), obs.NullTracker)


# ---------------------------------------------------------------------------
# the NullTracker zero-overhead contract
# ---------------------------------------------------------------------------

def test_null_tracker_shares_one_context_manager():
    null = obs.NullTracker()
    cm = null.timer("a", tag=1)
    assert cm is null.timer("b") is null.scope(run="r")   # no per-use alloc
    with cm:
        pass                                              # and it is inert


def test_null_tracker_per_call_overhead_is_bounded():
    """The default sink must stay cheap enough to leave in every hot path:
    a counter + a timer block per iteration, bounded at 20us/iter — two
    orders of magnitude above the real cost, so the assertion only fires
    on a genuine regression (e.g. someone allocating per call)."""
    null = obs.NullTracker()
    n = 20_000
    for _ in range(1000):                     # warm the bytecode path
        null.counter("service.device_calls")
    t0 = time.perf_counter()
    for _ in range(n):
        null.counter("service.device_calls", 1)
        with null.timer("service.flush_s"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"NullTracker costs {per_call * 1e6:.2f}us/iter"


def test_tracker_calls_in_jit_fire_at_trace_time_only():
    """Instrumentation inside jit-traced code (the kernels.ops dispatch
    counters) must be a trace-time effect: once per compiled
    specialization, never per executed call — so the NullTracker default
    adds literally nothing to the executed program."""
    t = obs.InMemoryTracker()
    with obs.use(t):
        @jax.jit
        def f(x):
            obs.current_tracker().counter("test.traced", shape=x.shape[0])
            return 2.0 * x
        for i in range(5):
            out = f(jnp.arange(3, dtype=jnp.float32) + i)
        np.testing.assert_allclose(np.asarray(out), [8.0, 10.0, 12.0])
        assert t.counters["test.traced"] == 1        # one specialization
        f(jnp.arange(4, dtype=jnp.float32))          # new shape: retrace
        assert t.counters["test.traced"] == 2
    # under the NullTracker the same body compiles and runs emission-free
    out = f(jnp.arange(3, dtype=jnp.float32))
    assert t.counters["test.traced"] == 2


# ---------------------------------------------------------------------------
# instrumented hot paths
# ---------------------------------------------------------------------------

def test_service_stats_is_a_live_view_with_both_spellings():
    m = _model()
    with obs.use(obs.InMemoryTracker()) as t:
        svc = m.service(seed=3, cache=dpp.SpectralCache())
        rows = svc.sample(5)
    assert len(rows) == 5
    # attribute spelling (pre-obs contract) and dict-call spelling
    # (cache.stats() parity) read the same counters
    assert svc.stats.samples_requested == 5
    assert svc.stats.flushes == 1 and svc.stats.device_calls >= 1
    assert svc.stats.samples_drawn >= 5          # power-of-two round-up
    snap = svc.stats()
    assert isinstance(snap, dict)
    assert set(snap) == set(ServiceStats.KEYS)
    assert snap["flushes"] == 1 == svc.stats["flushes"]
    with pytest.raises(KeyError):
        svc.stats["nope"]
    # equality: snapshots, against ServiceStats and plain dicts
    assert svc.stats == svc.stats and svc.stats == snap
    assert ServiceStats(flushes=1) == ServiceStats(flushes=1)
    assert ServiceStats(flushes=1) != ServiceStats(flushes=2)
    with pytest.raises(TypeError, match="unknown ServiceStats field"):
        ServiceStats(bogus=1)
    # the process-wide tracker saw the SAME stream the view reads
    for k in ServiceStats.KEYS:
        assert t.counters.get(f"service.{k}", 0) == snap[k]
    # latency/occupancy stream: one ticket -> one queue-wait sample
    assert len(t.observations["service.queue_wait_s"]) == 1
    assert len(t.observations["service.flush_s"]) == 1
    assert len(t.observations["service.device_call_s"]) >= 1
    assert 0.0 < t.gauges["service.batch_occupancy"] <= 1.0
    assert 0.0 <= t.gauges["service.truncation_rate"] <= 1.0


def test_service_and_cache_stats_share_key_style():
    """Satellite: the two stats surfaces return plain dicts in the same
    snake_case style, both via the () spelling and legacy access."""
    cache = dpp.SpectralCache()
    m = _model()
    svc = m.service(cache=cache)
    svc.sample(2)
    c, s = cache.stats(), svc.stats()
    for d in (c, s):
        assert isinstance(d, dict)
        assert all(k == k.lower() and " " not in k for k in d)
    assert c["hits"] == cache.stats["hits"]          # PR-1 property spelling
    assert s["flushes"] == svc.stats.flushes         # pre-obs attr spelling


def test_explicit_service_tracker_overrides_process_tracker():
    mine = obs.InMemoryTracker()
    with obs.use(obs.InMemoryTracker()) as global_t:
        svc = _model().service(cache=dpp.SpectralCache(), tracker=mine)
        svc.sample(2)
    assert mine.counters["service.flushes"] == 1
    assert "service.flushes" not in global_t.counters
    assert svc.stats.flushes == 1                    # private view still live


def test_spectral_cache_emits_hit_miss_and_eigh_time():
    k = random_krondpp(jax.random.PRNGKey(0), (4, 5))
    with obs.use(obs.InMemoryTracker()) as t:
        cache = SpectralCache()
        cache.spectrum(k)
        cache.spectrum(k)            # identity-keyed: pure hits
    assert t.counters["spectral_cache.misses"] == 2      # one per factor
    assert t.counters["spectral_cache.hits"] == 2
    assert "spectral_cache.evictions" not in t.counters
    assert len(t.observations["spectral_cache.eigh_s"]) == 2
    assert all(x >= 0 for x in t.observations["spectral_cache.eigh_s"])
    assert cache.stats() == {"hits": 2, "misses": 2, "evictions": 0,
                             "size": 2}


def test_learning_fit_emits_sweep_metrics_and_event():
    m = _model()
    batch = m.sample(jax.random.PRNGKey(4), 16)
    init = dpp.random_kron(jax.random.PRNGKey(5), (4, 5))
    with obs.use(obs.InMemoryTracker()) as t:
        rep = init.fit(batch, iters=3, a=1.0, log_every=1)
    assert t.counters["learning.sweeps"] == 3
    assert len(t.observations["learning.chunk_s"]) == 3
    assert t.gauges["learning.step_size"] == 1.0
    assert t.gauges["learning.log_likelihood"] == pytest.approx(
        rep.log_likelihoods[-1], abs=1e-5)
    (ev,) = [e for e in t.events if e["name"] == "learning.fit"]
    assert ev["algorithm"] == "krk" and ev["runtime"] == "local"
    assert ev["sweeps"] == 3 and ev["backtracks"] == 0
    assert ev["sweeps_per_sec"] > 0


def test_kernels_ops_dispatch_counters():
    from repro.kernels import ops
    with obs.use(obs.InMemoryTracker()) as t:
        A = jnp.eye(3, dtype=jnp.float32)
        B = jnp.eye(2, dtype=jnp.float32)
        X = jnp.ones((1, 6), dtype=jnp.float32)
        ops.kron_matvec(A, B, X)
    engine = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert t.counters[f"kernels.kron_matvec.{engine}"] == 1


def test_benchmark_harness_exits_nonzero_on_failure(monkeypatch, capsys):
    """Satellite: one raising benchmark no longer lets the run end green —
    the harness finishes the rest, then exits 1 naming the failure."""
    import types

    import benchmarks.run as run_mod

    def _boom():
        raise RuntimeError("kaput")

    boom = types.SimpleNamespace(__name__="benchmarks.boom", main=_boom)
    fine = types.SimpleNamespace(__name__="benchmarks.fine",
                                 main=lambda: print("fine,1,ok"))
    monkeypatch.setattr(run_mod, "_modules", lambda: (boom, fine))
    with obs.use(obs.InMemoryTracker()) as t:
        rc = run_mod.main([])
    assert rc == 1
    out = capsys.readouterr()
    assert "fine,1,ok" in out.out            # later benchmarks still ran
    assert "boom: RuntimeError: kaput" in out.err
    assert t.counters["benchmark.failures"] == 1
    assert len(t.observations["benchmark.wall_s"]) == 1   # the survivor
    monkeypatch.setattr(run_mod, "_modules", lambda: (fine,))
    assert run_mod.main([]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the benchmark regression gate
# ---------------------------------------------------------------------------

def _report(bench="facade_api", config=None, **row_overrides):
    rows = [{"N": 64, "kron_sample_us": 100.0, "dense_sample_us": 400.0,
             "kron_log_prob_us": 50.0},
            {"N": 1024, "kron_sample_us": 900.0, "dense_sample_us": 8000.0,
             "kron_log_prob_us": 300.0}]
    for row in rows:
        row.update(row_overrides)
    return {**report_meta(config or {"sizes": [[8, 8]]}),
            "bench": bench, "rows": rows}


def test_extract_metrics_labels_rows():
    got = extract_metrics("facade_api", _report())
    assert got["N=64/kron_sample_us"] == (100.0, False)
    assert got["N=1024/dense_sample_us"] == (8000.0, False)
    assert len(got) == 6
    # unknown metrics in a row are skipped, not KeyErrored
    assert extract_metrics("runtime_scaling", {"rows": [{"workload": "w"}]}) \
        == {}


def test_regression_gate_passes_on_equal_and_improved_runs():
    committed = _report()
    assert compare_reports("facade_api", committed, _report()) == []
    faster = _report(kron_sample_us=50.0)          # latency halved: a win
    assert compare_reports("facade_api", committed, faster) == []
    # within-threshold noise passes too (+20% < 25%)
    noisy = _report(kron_sample_us=120.0)
    assert compare_reports("facade_api", committed, noisy) == []


def test_regression_gate_fails_on_inflated_committed_report():
    """Acceptance criterion: artificially inflate the committed numbers by
    2x and the gate must fail."""
    fresh = _report()
    inflated = _report(kron_sample_us=50.0, dense_sample_us=200.0,
                       kron_log_prob_us=25.0)      # commits claim 2x faster
    problems = compare_reports("facade_api", inflated, fresh)
    assert len(problems) == 6                      # every metric regressed
    assert all("threshold 25%" in p for p in problems)
    assert any("+100%" in p for p in problems)     # the true-2x rows say so
    # higher-is-better direction: sweeps/s halved fails, doubled passes
    sw = {**report_meta({}), "bench": "paper_fig1_engine",
          "rows": [{"n": 64, "engine_sweeps_per_s": 10.0}]}
    half = {**sw, "rows": [{"n": 64, "engine_sweeps_per_s": 5.0}]}
    dbl = {**sw, "rows": [{"n": 64, "engine_sweeps_per_s": 20.0}]}
    assert compare_reports("paper_fig1_engine", sw, half) \
        and compare_reports("paper_fig1_engine", sw, dbl) == []
    # threshold is honored (override lands on both rows, so the worst
    # apparent "regression" against the inflated baseline is +3900%)
    assert compare_reports("facade_api", inflated, fresh,
                           threshold=40.0) == []


def test_regression_gate_takes_best_of_fresh_runs():
    """Noise is one-sided: a throttled fresh run must not fail the gate
    when a second clean run hits the committed numbers."""
    committed = _report()
    throttled = _report(kron_sample_us=500.0)      # 5x slower: pure noise
    clean = _report()
    assert compare_reports("facade_api", committed, throttled)   # alone: fails
    assert compare_reports("facade_api", committed,
                           [throttled, clean]) == []             # best-of: ok
    # a REAL regression slows every run, so best-of still catches it
    assert compare_reports("facade_api", committed,
                           [throttled, _report(kron_sample_us=200.0)])
    merged = merge_best("facade_api", [throttled, clean])
    assert merged["N=64/kron_sample_us"] == (100.0, False)       # min wins
    sw = {"rows": [{"n": 64, "engine_sweeps_per_s": 10.0}]}
    sw2 = {"rows": [{"n": 64, "engine_sweeps_per_s": 30.0}]}
    assert merge_best("paper_fig1_engine", [sw, sw2])[
        "n=64/engine_sweeps_per_s"] == (30.0, True)              # max wins


def test_regression_gate_refuses_fingerprint_and_schema_drift():
    committed = _report(config={"sizes": [[8, 8]]})
    fresh = _report(config={"sizes": [[16, 16]]})  # workload changed
    problems = compare_reports("facade_api", committed, fresh)
    assert len(problems) == 1 and "fingerprint mismatch" in problems[0]
    # an unstamped (pre-schema) committed report must demand a re-commit
    legacy = {k: v for k, v in _report().items()
              if k not in ("schema_version", "config_fingerprint")}
    problems = compare_reports("facade_api", legacy, _report())
    assert len(problems) == 1 and "schema_version" in problems[0]
    # --no-fingerprint escape hatch: raw numbers only
    assert compare_reports("facade_api", committed, fresh,
                           check_fingerprint=False) == []


def test_regression_main_compare_exit_codes(tmp_path, capsys):
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    fresh = tmp_path / "fresh.json"
    good.write_text(json.dumps(_report()))
    bad.write_text(json.dumps(_report(kron_sample_us=40.0)))   # inflated
    fresh.write_text(json.dumps(_report()))
    assert regression_main(["--compare", str(good), str(fresh)]) == 0
    assert "passed" in capsys.readouterr().out
    assert regression_main(["--compare", str(bad), str(fresh)]) == 2
    assert "FAILED" in capsys.readouterr().err
    ungated = tmp_path / "ungated.json"
    ungated.write_text(json.dumps(_report(bench="mystery")))
    assert regression_main(["--compare", str(ungated), str(fresh)]) == 2


def test_committed_reports_are_gate_compatible():
    """Every gated benchmark has a committed, schema-stamped report whose
    metrics the gate can extract — the CI regression job's precondition."""
    for bench in GATED:
        path = ROOT / "benchmarks" / "reports" / f"{bench}.json"
        assert path.exists(), f"missing committed report {path}"
        report = json.loads(path.read_text())
        assert report["schema_version"] == SCHEMA_VERSION, bench
        assert report["config_fingerprint"] == report_meta(
            {k: v for k, v in report["config"].items()}
        )["config_fingerprint"], bench
        metrics = extract_metrics(bench, report)
        assert metrics, f"{bench}: gate extracts no metrics"
        assert all(v > 0 for v, _ in metrics.values()), bench
        # a committed report always agrees with itself
        assert compare_reports(bench, report, report) == []


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from a table keyed by device_kind; a chip the table does
    not know is an error, never another chip's numbers."""
    from benchmarks.roofline import analyze, peaks
    assert peaks("TPU v5 lite")["flops"] == 197e12
    rec = {"arch": "a", "shape": "s", "chips": 256, "kind": "train",
           "params": 1, "global_batch": 1, "seq_len": 1,
           "flops_per_device": 1e12, "bytes_accessed_per_device": 1e9}
    assert analyze({**rec, "device_kind": "TPU v5 lite"})["dominant"] \
        == "compute"
    for kind in ("cpu", None):
        with pytest.raises(ValueError, match="no published peaks"):
            analyze({**rec, "device_kind": kind})
