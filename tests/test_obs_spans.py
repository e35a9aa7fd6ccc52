"""repro.obs.spans — request-level span tracing.

Covers the span primitives (context-local nesting, explicit parenting
across a thread hop, NullTracker parity), the end-to-end service path
(trace id minted at ``submit()``, ``queue-wait → coalesce → device-call
→ scatter`` children under each ticket's root span), the JSONL →
chrome://tracing export, the ``repro.obs.report`` terminal summary, and
the JsonlTracker multi-thread round-trip (whole-line interleaving,
per-thread scope isolation).
"""

import json
import pathlib
import sys
import threading
import time

import jax
import pytest

from repro import dpp, obs
from repro.obs import spans

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))          # `import benchmarks.*` (namespace pkg)


def _model():
    return dpp.random_kron(jax.random.PRNGKey(0), (4, 5)).rescale(4.0)


def _span_events(tracker):
    return [e for e in tracker.events if e["name"] == "span"]


#: the spans ``DPPModel.sample`` opens on its device path, root first
FACADE_SPANS = ("dpp.sample", "dpp.sample.spectrum", "dpp.sample.k_max",
                "dpp.sample.draw", "dpp.sample.pack")


def _profiled(log_dir, fn):
    """Run ``fn`` under ``jax.profiler`` and return its result and the
    host events of the trace as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    (path,) = pathlib.Path(log_dir).rglob("*.xplane.pb")
    host = [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events]
    return out, host


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_nested_spans_share_a_trace_and_parent_contextually():
    t = obs.InMemoryTracker()
    with spans.start_span("root", tracker=t, kind="request") as root:
        assert spans.current_span() is root
        with spans.start_span("child", tracker=t) as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            with spans.start_span("grandchild", tracker=t) as gc:
                assert gc.parent_id == child.span_id
        assert spans.current_span() is root     # child popped on exit
    assert spans.current_span() is None
    by_op = {e["op"]: e for e in _span_events(t)}
    assert set(by_op) == {"root", "child", "grandchild"}
    assert by_op["root"]["parent"] is None
    assert by_op["root"]["kind"] == "request"
    assert by_op["child"]["parent"] == by_op["root"]["span"]
    assert by_op["grandchild"]["parent"] == by_op["child"]["span"]
    assert all(e["trace"] == by_op["root"]["trace"] for e in by_op.values())
    assert all(e["dur_s"] >= 0 for e in by_op.values())


def test_sibling_spans_both_parent_on_the_enclosing_span():
    t = obs.InMemoryTracker()
    with spans.start_span("root", tracker=t) as root:
        with spans.start_span("a", tracker=t):
            pass
        with spans.start_span("b", tracker=t):  # after a closed
            pass
    by_op = {e["op"]: e for e in _span_events(t)}
    assert by_op["a"]["parent"] == root.span_id
    assert by_op["b"]["parent"] == root.span_id


def test_explicit_parent_carries_a_trace_across_a_thread_hop():
    t = obs.InMemoryTracker()
    with spans.start_span("request", tracker=t) as root:
        captured = spans.current_span()         # the thread-hop spelling

        def worker():
            # contextvars do NOT cross threads: without the explicit
            # parent this would start a fresh root trace
            assert spans.current_span() is None
            with spans.start_span("work", tracker=t, parent=captured):
                pass
            with spans.start_span("by-ids", tracker=t,
                                  parent=(captured.trace_id,
                                          captured.span_id)):
                pass

        th = threading.Thread(target=worker)
        th.start()
        th.join()
    by_op = {e["op"]: e for e in _span_events(t)}
    for op in ("work", "by-ids"):
        assert by_op[op]["trace"] == root.trace_id
        assert by_op[op]["parent"] == root.span_id


def test_emit_span_synthesizes_records_without_a_context_manager():
    t = obs.InMemoryTracker()
    sid = spans.emit_span(t, "offline", trace_id="tr-1", parent_id=None,
                          ts=123.0, dur_s=0.5, n=3)
    (e,) = _span_events(t)
    assert e["span"] == sid and e["trace"] == "tr-1" and e["n"] == 3
    assert e["ts"] == 123.0 and e["dur_s"] == 0.5


def test_null_tracker_start_span_is_the_shared_inert_span():
    a = spans.start_span("x", tracker=obs.NullTracker())
    b = spans.start_span("y", tracker=obs.NullTracker(), parent=(("t", "s")))
    assert a is spans.NULL_SPAN and b is spans.NULL_SPAN
    with a as s:
        assert s.trace_id is None and s.span_id is None
    assert spans.current_span() is None         # no contextvar writes


def test_null_tracker_start_span_per_call_overhead_is_bounded():
    null = obs.NullTracker()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.start_span("hot", tracker=null):
            pass
    per_call = (time.perf_counter() - t0) / n
    # same budget the tracker-primitive no-overhead test pins: the null
    # path must stay an isinstance check + one shared context manager
    assert per_call < 20e-6, f"start_span(null) costs {per_call*1e6:.2f}µs"
    # what the facade adds to each DPPModel.sample call under the default
    # process tracker: the root span, its four phase spans, one late tag
    # and two host-sync counters
    assert not obs.enabled(obs.current_tracker())
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.start_span("dpp.sample", rows=256) as root:
            for phase in FACADE_SPANS[1:]:
                with spans.start_span(phase):
                    pass
            root.set_tag("k_max", 29)
        obs.current_tracker().counter("dpp.host_syncs")
        obs.current_tracker().counter("dpp.host_syncs")
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"the facade's spans cost {per_call*1e6:.2f}µs"


# ---------------------------------------------------------------------------
# the profiler clock: live spans as host events of a jax.profiler trace
# ---------------------------------------------------------------------------

def _kron_234():
    return dpp.random_kron(jax.random.PRNGKey(3), (2, 3, 4)).rescale(3.0)


def test_live_span_is_a_host_event_of_the_profiler_trace(tmp_path):
    t = obs.InMemoryTracker()

    def work():
        with spans.start_span("outer", tracker=t):
            with spans.start_span("inner", tracker=t):
                pass
            spans.emit_span(t, "after-the-fact", trace_id="tr", ts=0.0,
                            dur_s=1.0)
    _, host = _profiled(tmp_path, work)
    names = [h[0] for h in host]
    assert names.count("outer") == 1 and names.count("inner") == 1
    assert "after-the-fact" not in names       # emit_span: no annotation
    (o,) = [h for h in host if h[0] == "outer"]
    (i,) = [h for h in host if h[0] == "inner"]
    assert o[1] <= i[1] and i[2] <= o[2]


def test_facade_sample_spans_reach_the_profiler_trace(tmp_path):
    model = _kron_234()
    key = jax.random.PRNGKey(7)
    t = obs.InMemoryTracker()
    with obs.use(t):
        _, host = _profiled(tmp_path, lambda: model.sample(key, 8).indices)
    events = {}
    for name, start, end in host:
        if name in FACADE_SPANS:
            assert name not in events, f"{name} twice"
            events[name] = (start, end)
    assert set(events) == set(FACADE_SPANS)
    lo, hi = events["dpp.sample"]
    phases = [events[n] for n in FACADE_SPANS[1:]]
    assert all(lo <= s <= e <= hi for s, e in phases)
    # the phases follow one another in the facade's order
    assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
    assert t.counters["dpp.host_syncs"] == 2    # expected_size, size_std
    with obs.use(obs.InMemoryTracker()) as again:
        model.sample(key, 8)
    assert again.counters.get("dpp.host_syncs", 0) == 0   # memoized k_max
    (root,) = [e for e in _span_events(t) if e["op"] == "dpp.sample"]
    assert root["rows"] == 8 and root["k_max"] == model.spectrum() \
        .suggested_k_max()
    children = {e["op"] for e in _span_events(t)
                if e["parent"] == root["span"]}
    assert children == set(FACADE_SPANS[1:])


@pytest.mark.parametrize("k", [None, 3])
def test_facade_draws_are_bit_identical_with_tracing_on_and_off(tmp_path, k):
    model = _kron_234()
    key = jax.random.PRNGKey(11)
    off = model.sample(key, 16, k=k)
    with obs.use(obs.InMemoryTracker()):
        on, _ = _profiled(tmp_path, lambda: model.sample(key, 16, k=k))
    for a, b in ((off.indices, on.indices), (off.mask, on.mask)):
        assert a.dtype == b.dtype
        assert (jax.device_get(a) == jax.device_get(b)).all()
    if k is None:
        assert (jax.device_get(off.truncated)
                == jax.device_get(on.truncated)).all()


def test_null_tracker_gives_the_facade_no_spans(tmp_path, monkeypatch):
    model = _kron_234()
    key = jax.random.PRNGKey(5)
    model.sample(key, 4)                         # warm: spectrum cached

    def no_span(*a, **kw):
        raise AssertionError("a Span was built under the NullTracker")
    monkeypatch.setattr(spans, "Span", no_span)
    assert not obs.enabled(obs.current_tracker())
    _, host = _profiled(tmp_path, lambda: model.sample(key, 4).indices)
    assert not [h for h in host if h[0].startswith("dpp.")]
    assert spans.current_span() is None


def test_facade_root_span_carries_k_and_kdpp_calls_count_esp_builds():
    model = _kron_234()
    key = jax.random.PRNGKey(9)
    t = obs.InMemoryTracker()
    with obs.use(t):
        model.sample(key, 6, k=3)
        model.sample(key, 6)
    roots = [e for e in _span_events(t) if e["op"] == "dpp.sample"]
    assert [r["k"] for r in roots] == [3, None]
    assert [r["k_max"] for r in roots][0] == 3
    assert t.counters["dpp.kdpp.esp_builds"] == 1     # the k-DPP call only


def test_null_tracker_kdpp_sample_costs_no_span_and_no_counter(
        tmp_path, monkeypatch):
    """Under the default NullTracker a k-DPP call builds no span and its
    ESP-build counter is the tracker's no-op."""
    model = _kron_234()
    key = jax.random.PRNGKey(5)
    model.sample(key, 4, k=2)                    # warm: spectrum cached

    def no_span(*a, **kw):
        raise AssertionError("a Span was built under the NullTracker")
    monkeypatch.setattr(spans, "Span", no_span)
    counted = []
    monkeypatch.setattr(obs.NullTracker, "counter",
                        lambda self, name, value=1, **tags:
                        counted.append(name))
    assert not obs.enabled(obs.current_tracker())
    _, host = _profiled(tmp_path, lambda: model.sample(key, 4, k=2).indices)
    assert not [h for h in host if h[0].startswith("dpp.")]
    assert counted.count("dpp.kdpp.esp_builds") == 1   # the no-op sink
    n = 20_000
    null = obs.NullTracker()
    monkeypatch.undo()
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.start_span("dpp.sample", tracker=null, rows=64, k=8):
            pass
        null.counter("dpp.kdpp.esp_builds")
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"k tag and counter cost {per_call*1e6:.2f}µs"


# ---------------------------------------------------------------------------
# the service request path
# ---------------------------------------------------------------------------

def test_ticket_trace_is_stable_from_submit_through_flush():
    ext = obs.InMemoryTracker()
    svc = _model().service(seed=0, tracker=ext)
    t1 = svc.submit(3)
    t2 = svc.submit(2)
    trace1, root1 = t1.trace_id, t1._span_id    # minted at submit()
    svc.flush()
    assert t1.trace_id == trace1 and t1._span_id == root1
    events = _span_events(ext)
    for ticket in (t1, t2):
        mine = [e for e in events if e["trace"] == ticket.trace_id]
        by_op = {e["op"]: e for e in mine}
        assert {"service.request", "queue-wait", "coalesce", "device-call",
                "scatter"} <= set(by_op)
        root = by_op["service.request"]
        assert root["span"] == ticket._span_id and root["parent"] is None
        assert root["num_samples"] == ticket.num_samples
        for op in ("queue-wait", "coalesce", "device-call", "scatter"):
            assert by_op[op]["parent"] == ticket._span_id, op
        # children fall inside the root's wall-clock extent
        lo, hi = root["ts"], root["ts"] + root["dur_s"]
        eps = 1e-6          # clock mapping rounds at µs scale
        for op in ("queue-wait", "coalesce", "device-call", "scatter"):
            e = by_op[op]
            assert e["ts"] >= lo - eps
            assert e["ts"] + e["dur_s"] <= hi + eps


def test_flush_emits_no_spans_without_an_external_tracker():
    svc = _model().service(seed=0)              # process tracker is Null
    svc.sample(4)
    assert svc._metrics.events == []            # accumulator stays bounded


def test_flush_spans_ride_a_thread_hop():
    ext = obs.InMemoryTracker()
    svc = _model().service(seed=0, tracker=ext)
    ticket = svc.submit(2)
    th = threading.Thread(target=svc.flush)     # flush on a worker thread
    th.start()
    th.join()
    assert len(ticket.result()) == 2
    mine = [e for e in _span_events(ext) if e["trace"] == ticket.trace_id]
    assert {"service.request", "queue-wait", "device-call",
            "scatter"} <= {e["op"] for e in mine}


# ---------------------------------------------------------------------------
# export + report
# ---------------------------------------------------------------------------

def _service_run_log(tmp_path):
    path = tmp_path / "run.jsonl"
    prev = obs.configure(jsonl=str(path))
    try:
        svc = _model().service(seed=0)
        svc.submit(3)
        svc.submit(2)
        svc.flush()
    finally:
        obs.configure(prev)
    return path


def test_chrome_trace_export_is_valid_and_well_formed(tmp_path):
    run_log = _service_run_log(tmp_path)
    out = tmp_path / "trace.json"
    obs.ChromeTraceExporter().export(str(run_log), str(out))
    trace = json.loads(out.read_text())         # valid JSON end to end
    events = trace["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert len(complete) >= 10                  # 2 tickets x 5 spans
    for e in complete:
        assert isinstance(e["name"], str) and e["name"]
        assert e["ts"] >= 0 and e["dur"] >= 0   # µs, anchored at file start
        assert e["pid"] == 1 and isinstance(e["tid"], int)
        assert "trace" in e["args"]
    # every ticket trace renders as its own labelled lane
    lanes = {e["tid"] for e in complete
             if e["args"].get("parent") is None
             and e["name"] == "service.request"}
    assert len(lanes) == 2
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["tid"] for m in meta} >= lanes


def test_chrome_trace_export_tag_filter_splits_benches(tmp_path):
    path = tmp_path / "run.jsonl"
    with obs.JsonlTracker(str(path)) as t:
        with t.scope(bench="a"):
            with spans.start_span("alpha", tracker=t):
                pass
        with t.scope(bench="b"):
            with spans.start_span("beta", tracker=t):
                pass
    only_a = obs.ChromeTraceExporter(tag_filter={"bench": "a"}).convert(
        obs.read_run_log(str(path)))
    names = {e["name"] for e in only_a["traceEvents"] if e["ph"] == "X"}
    assert names == {"alpha"}


def test_report_cli_prints_counters_spans_and_latency_breakdown(
        tmp_path, capsys):
    run_log = _service_run_log(tmp_path)
    out = tmp_path / "trace.json"
    from repro.obs import report
    rc = report.main([str(run_log), "--traces", "2", "--trace", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "== counters ==" in text
    assert "service.device_calls" in text
    assert "== top spans (by total duration) ==" in text
    assert "traces ==" in text                  # per-trace latency breakdown
    assert "service.request" in text
    for op in ("queue-wait", "device-call", "scatter"):
        assert op in text
    assert "100.0%" in text                     # root share of itself
    json.loads(out.read_text())                 # --trace export also valid


def test_report_cli_on_spanless_log(tmp_path, capsys):
    path = tmp_path / "flat.jsonl"
    with obs.JsonlTracker(str(path)) as t:
        t.counter("c", 2)
    from repro.obs import report
    assert report.main([str(path)]) == 0
    assert "(no spans in log)" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# JsonlTracker concurrency
# ---------------------------------------------------------------------------

def test_jsonl_tracker_multi_thread_round_trip(tmp_path):
    path = tmp_path / "concurrent.jsonl"
    n_threads, n_each = 8, 200
    t = obs.JsonlTracker(str(path))
    barrier = threading.Barrier(n_threads)

    def emitter(i):
        barrier.wait()                          # maximize interleaving
        with t.scope(thread=i):
            for j in range(n_each):
                t.counter("c", 1, j=j)
                if j % 5 == 0:
                    with spans.start_span("work", tracker=t, i=i, j=j):
                        pass

    threads = [threading.Thread(target=emitter, args=(i,))
               for i in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t.close()

    lines = path.read_text().splitlines()
    recs = [json.loads(line) for line in lines]     # no torn/corrupt lines
    n_spans = n_threads * len(range(0, n_each, 5))
    assert len(recs) == n_threads * n_each + n_spans
    counters = [r for r in recs if r["kind"] == "counter"]
    assert len(counters) == n_threads * n_each
    # per-thread scope tags never bleed across threads
    for r in recs:
        tags = r.get("tags", {})
        assert "thread" in tags
        if r["kind"] == "event":
            assert r["fields"]["i"] == tags["thread"]


def test_scope_tags_are_thread_local():
    t = obs.InMemoryTracker(keep_records=True)
    ready = threading.Event()
    release = threading.Event()

    def other():
        ready.set()
        release.wait(timeout=5)
        t.counter("from_other")                 # no scope on THIS thread

    with t.scope(main=True):
        th = threading.Thread(target=other)
        th.start()
        ready.wait(timeout=5)
        t.counter("from_main")
        release.set()
        th.join()
    tags = {r["name"]: r["tags"] for r in t.records}
    assert tags["from_main"] == {"main": True}
    assert tags["from_other"] == {}


def test_jsonl_tracker_write_after_close_is_a_noop(tmp_path):
    path = tmp_path / "closed.jsonl"
    t = obs.JsonlTracker(str(path))
    t.counter("before")
    t.close()
    t.counter("after")                          # must not raise
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["before"]


# ---------------------------------------------------------------------------
# benchmark CLI --trace seam
# ---------------------------------------------------------------------------

def test_regression_cli_trace_requires_jsonl(capsys):
    import benchmarks.regression as regression
    with pytest.raises(SystemExit) as exc:
        regression.main(["--trace", "out.json"])
    assert exc.value.code == 2
    assert "--trace needs --jsonl" in capsys.readouterr().err
