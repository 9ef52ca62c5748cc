"""Span tracer + flight recorder + /tracez //statusz //healthz surface.

Covers the PR-3 acceptance line end to end: span nesting/attributes and
ring eviction under concurrent writers, Chrome trace-event export schema,
the live REST endpoints, and an RTPU_TRACE'd range sweep producing the
job → sweep → hop → {fold, stage, ship, compute} → superstep timeline.
Plus the request-scoped trace context layer: capture/adopt/carry across
thread handoffs, trace-id inheritance, cross-thread flow arrows in the
Chrome export, and the ``for_trace`` reconstruction surface (the /slz
exemplar workflow's other half lives in tests/test_slo.py).
"""

import json
import threading
import urllib.request

import pytest

from raphtory_tpu.obs.trace import (NULL_SPAN, TRACER, TraceContext,
                                    Tracer)


@pytest.fixture
def global_trace():
    """Enable the process tracer for one test, restoring prior state (CI
    may run the whole tier with RTPU_TRACE_DUMP, i.e. tracing already on)."""
    was = TRACER.enabled
    TRACER.enable()
    try:
        yield TRACER
    finally:
        TRACER.enabled = was


def test_span_nesting_and_attributes():
    tr = Tracer(enabled=True, ring=64)
    with tr.span("outer", job_id="j1") as outer:
        with tr.span("inner", hop=3, bytes=128) as inner:
            inner.set(extra="late")
        assert inner.parent == outer.sid
    assert tr.recent(0) == [] and tr.recent(-1) == []
    events = tr.recent(10)
    assert [e["name"] for e in events] == ["inner", "outer"]  # exit order
    by_name = {e["name"]: e for e in events}
    assert by_name["inner"]["parent"] == by_name["outer"]["sid"]
    assert by_name["outer"]["parent"] == 0
    assert by_name["inner"]["args"] == {"hop": 3, "bytes": 128,
                                        "extra": "late"}
    assert by_name["outer"]["args"] == {"job_id": "j1"}
    # inner nests inside outer on the timeline too
    assert by_name["outer"]["ts"] <= by_name["inner"]["ts"]
    assert by_name["inner"]["dur"] <= by_name["outer"]["dur"]


def test_span_records_error_and_unwinds_stack():
    tr = Tracer(enabled=True, ring=64)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("nope")
    (ev,) = tr.recent(10)
    assert ev["args"]["error"].startswith("ValueError")
    with tr.span("after") as sp:
        assert sp.parent == 0   # the failed span was popped


def test_disabled_tracer_is_free_and_records_nothing():
    tr = Tracer(enabled=False, ring=64)
    assert tr.span("x", a=1) is NULL_SPAN
    with tr.span("x"):
        pass
    tr.instant("i")
    tr.complete("c", 0.1)
    assert tr.recorded == 0 and tr.recent(10) == []


def test_ring_eviction_under_concurrent_writers():
    tr = Tracer(enabled=True, ring=64)
    n_threads, per_thread = 8, 200

    def writer(k):
        for i in range(per_thread):
            with tr.span("w", thread=k, i=i):
                pass

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert tr.recorded == total
    assert len(tr.recent(10**6)) == 64          # bounded: only newest kept
    assert tr.dropped == total - 64
    # every surviving event is intact (no torn writes)
    for e in tr.recent(10**6):
        assert e["name"] == "w" and {"thread", "i"} <= set(e["args"])


def test_instant_and_complete_events():
    tr = Tracer(enabled=True, ring=64)
    tr.instant("watermark.advance", source="s1", watermark=42)
    tr.complete("fold.stall", 0.25, hops=3)
    inst, comp = tr.recent(10)
    assert inst["ph"] == "i" and inst["args"]["watermark"] == 42
    assert comp["ph"] == "X" and comp["dur"] == pytest.approx(250_000, rel=0.01)


def test_chrome_trace_export_schema(tmp_path):
    tr = Tracer(enabled=True, ring=64)
    with tr.span("a", x=1):
        with tr.span("b"):
            pass
    tr.instant("mark")
    doc = tr.chrome_trace()
    # round-trips through JSON (the loadability half of the acceptance)
    doc = json.loads(json.dumps(doc))
    events = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in events)
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:   # required trace-event schema fields
        for field in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert field in e, field
        assert e["dur"] >= 0 and e["ts"] >= 0
    for e in events:
        if e["ph"] == "i":
            assert {"ts", "pid", "tid", "name"} <= set(e)
    # dump writes the same document to disk
    path = tr.dump(str(tmp_path / "trace.json"))
    on_disk = json.loads(open(path).read())
    assert len(on_disk["traceEvents"]) == len(events)


def _graph(n=3_000, name="tr1", seed=2):
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.ingestion.pipeline import IngestionPipeline
    from raphtory_tpu.ingestion.source import RandomSource

    pipe = IngestionPipeline()
    pipe.add_source(RandomSource(n, id_pool=200, seed=seed, name=name))
    pipe.run()
    return TemporalGraph(pipe.log, pipe.watermarks)


def test_range_sweep_produces_full_span_timeline(global_trace):
    """Acceptance: a range-sweep run yields a loadable Chrome trace with
    spans for job → sweep → hop → {fold, stage, ship, compute} →
    superstep, and the per-sweep phase breakdown rides the sweep span."""
    import numpy as np

    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.engine.device_sweep import DeviceSweep
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery

    TRACER.clear()
    g = _graph(name="tr_sweep", seed=5)
    # engine-level pipelined sweep: hop.ship comes from the staged applies
    ds = DeviceSweep(g.log)
    pr = PageRank(max_steps=10)
    res, _ = ds.run_sweep(pr, [300, 600, 900], windows=[10_000, 100])
    np.asarray(res[-1])
    assert set(ds.last_phase_seconds) == {"fold", "stage", "ship", "compute"}
    # job-level: the full chain through the analysis manager
    job = AnalysisManager(g).submit(
        PageRank(max_steps=10), RangeQuery(200, 900, 350,
                                           windows=(10_000, 100)))
    assert job.wait(120) and job.status == "done", job.error

    doc = json.loads(json.dumps(TRACER.chrome_trace()))
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    for e in xs:
        for field in ("ph", "ts", "dur", "pid", "tid", "name"):
            assert field in e, field
    names = {e["name"] for e in xs}
    assert "job" in names
    assert {"sweep.range", "sweep.columnar"} & names
    assert "hop.fold" in names
    assert "ship.stage" in names     # host staging copies
    assert "ship.wire" in names      # wire/in-flight completion waits
    assert "hop.ship" in names       # device-sweep staged applies
    assert "hop.compute" in names
    assert "superstep.block" in names
    job_ev = next(e for e in xs if e["name"] == "job")
    assert job_ev["args"]["job_id"] == job.id
    assert job_ev["args"]["status"] == "done"
    sweep_ev = next(e for e in xs if e["name"].startswith("sweep."))
    assert {"fold_seconds", "stage_seconds", "ship_seconds",
            "compute_seconds", "n_hops"} <= set(sweep_ev["args"])


def test_endpoints_over_live_rest_server(global_trace):
    from raphtory_tpu.algorithms import DegreeBasic
    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery
    from raphtory_tpu.jobs.rest import RestServer

    g = _graph(name="tr_rest", seed=7)
    g.view_at(int(g.latest_time))   # cold fold → a snapshot.fold span
    mgr = AnalysisManager(g)
    job = mgr.submit(DegreeBasic(), ViewQuery(g.latest_time))
    assert job.wait(120) and job.status == "done", job.error
    srv = RestServer(mgr, port=0).start()
    try:
        def get(path):
            return json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{path}", timeout=10).read())

        # graded liveness (obs/budget.py): with no RTPU_SLO_TARGET set
        # there is nothing to burn, so the grade is "ok"
        hz = get("/healthz")
        assert hz["status"] == "ok" and hz["targets"] == []

        st = get("/statusz")
        assert st["jobs"][job.id] == "done"
        assert st["log_events"] == g.log.n
        assert st["watermark"]["safe_time"] >= g.latest_time
        assert "tr_rest" in st["watermark"]["sources"]
        assert st["transfer"]["depth"] >= 1
        assert "bsp._compiled_runner" in st["compile_caches"]
        assert st["trace"]["enabled"] is True

        tz = get("/tracez?n=500")
        assert tz["enabled"] is True
        names = {e["name"] for e in tz["spans"]}
        assert "job" in names and "snapshot.fold" in names
        # full chrome document over the wire
        chrome = get("/tracez?format=chrome")["trace"]
        assert any(e["ph"] == "M" for e in chrome["traceEvents"])
        # runtime toggle round-trip
        assert get("/tracez?enable=0")["enabled"] is False
        assert get("/tracez?enable=1")["enabled"] is True
    finally:
        srv.stop()


def test_tracez_dump_writes_server_side_file(global_trace, tmp_path):
    from raphtory_tpu.jobs.manager import AnalysisManager
    from raphtory_tpu.jobs.rest import RestServer

    with TRACER.span("dumpme"):
        pass
    srv = RestServer(AnalysisManager(_graph(500, name="tr_dump")),
                     port=0).start()
    try:
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/tracez?dump=1", timeout=10).read())
        assert "dumped" in out
        doc = json.loads(open(out["dumped"]).read())
        assert any(e.get("name") == "dumpme" for e in doc["traceEvents"])
    finally:
        srv.stop()


def test_watermark_and_ingest_spans(global_trace):
    TRACER.clear()
    _graph(1_000, name="tr_wm", seed=9)
    names = {e["name"] for e in TRACER.recent(10**6)}
    assert "ingest.source" in names
    assert "ingest.append" in names
    assert "watermark.advance" in names
    assert "watermark.finish" in names
    app = next(e for e in TRACER.recent(10**6)
               if e["name"] == "ingest.append")
    assert app["args"]["source"] == "tr_wm" and app["args"]["events"] > 0


def test_root_span_allocates_trace_children_inherit():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    with tr.span("root") as root:
        assert root.trace
        with tr.span("child") as child:
            assert child.trace == root.trace
    with tr.span("other") as other:
        assert other.trace != root.trace   # a NEW request, a new trace
    evs = {e["name"]: e for e in tr.recent(10)}
    assert evs["child"]["trace"] == evs["root"]["trace"]
    assert evs["other"]["trace"] != evs["root"]["trace"]


def test_capture_adopt_links_across_threads():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    with tr.span("submit") as root:
        ctx = tr.capture()
        assert ctx == TraceContext(root.trace, root.sid)

        def work():
            with tr.adopt(ctx):
                with tr.span("worker.task"):
                    pass
        t = threading.Thread(target=work, name="pool-w0")
        t.start()
        t.join()
    evs = {e["name"]: e for e in tr.recent(10)}
    assert evs["worker.task"]["trace"] == evs["submit"]["trace"]
    assert evs["worker.task"]["parent"] == evs["submit"]["sid"]
    assert evs["worker.task"]["tid"] != evs["submit"]["tid"]


def test_capture_none_when_disabled_or_idle():
    tr = Tracer(enabled=False, ring=64, annotate=False)
    assert tr.capture() is None
    fn = lambda: 1                      # noqa: E731
    assert tr.carry(fn) is fn           # zero-cost identity when off
    tr2 = Tracer(enabled=True, ring=64, annotate=False)
    assert tr2.capture() is None        # nothing open on this thread
    with tr2.adopt(None):               # adopt(None) is a safe no-op
        with tr2.span("x") as sp:
            assert sp.trace             # still allocates its own trace
    assert NULL_SPAN.trace is None
    # a hashable value object: contexts deduplicate in sets/dicts
    a, b = TraceContext("t", 1), TraceContext("t", 1)
    assert len({a, b}) == 1 and {a: 1}[b] == 1


def test_adopt_restores_on_exception_and_nests():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    c1 = TraceContext("t-1", 11)
    c2 = TraceContext("t-2", 22)
    with pytest.raises(ValueError):
        with tr.adopt(c1):
            with tr.adopt(c2):
                assert tr.capture() == c2
                raise ValueError("boom")
    # both adoptions unwound despite the exception
    assert tr.capture() is None
    with tr.adopt(c1):
        with tr.adopt(c2):
            pass
        assert tr.capture() == c1       # inner restored the outer
    assert tr.capture() is None


def test_carry_runs_fn_under_submitters_context():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    seen = []
    with tr.span("submit") as root:
        wrapped = tr.carry(
            lambda: seen.append(tr.capture() and tr.capture().trace_id))
    t = threading.Thread(target=wrapped)
    t.start()
    t.join()
    assert seen == [root.trace]


def test_instant_and_complete_tagged_with_ambient_trace():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    with tr.span("outer") as sp:
        tr.instant("mark")
        tr.complete("stall", 0.01)
    evs = {e["name"]: e for e in tr.recent(10)}
    assert evs["mark"]["trace"] == sp.trace
    assert evs["stall"]["trace"] == sp.trace
    assert evs["stall"]["parent"] == sp.sid


def test_for_trace_reconstructs_one_request():
    tr = Tracer(enabled=True, ring=256, annotate=False)
    with tr.span("req.a") as a:
        ctx = tr.capture()
        t = threading.Thread(
            target=tr.carry(lambda: tr.span("a.child").__enter__().__exit__(
                None, None, None)))
        t.start()
        t.join()
    with tr.span("req.b"):
        pass
    mine = tr.for_trace(a.trace)
    assert {e["name"] for e in mine} == {"req.a", "a.child"}
    assert all(e["trace"] == a.trace for e in mine)
    assert ctx.trace_id == a.trace
    assert tr.for_trace("no-such-trace") == []


def test_chrome_export_draws_cross_thread_flow_arrows():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    with tr.span("submit"):
        ctx = tr.capture()

        def work():
            with tr.adopt(ctx), tr.span("hop"):
                pass
        t = threading.Thread(target=work)
        t.start()
        t.join()
    doc = json.loads(json.dumps(tr.chrome_trace()))
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "handoff"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    s, f = (next(e for e in flows if e["ph"] == p) for p in ("s", "f"))
    assert s["id"] == f["id"] and s["tid"] != f["tid"]
    assert s["ts"] <= f["ts"]
    # same-thread nesting draws NO arrow
    tr2 = Tracer(enabled=True, ring=64, annotate=False)
    with tr2.span("a"):
        with tr2.span("b"):
            pass
    doc2 = tr2.chrome_trace()
    assert not [e for e in doc2["traceEvents"]
                if e.get("cat") == "handoff"]


def test_thread_rename_refreshes_track_metadata():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    me = threading.current_thread()
    old = me.name
    try:
        me.name = "before-rename"
        with tr.span("s1"):
            pass
        me.name = "after-rename"   # pool naming / recycled-ident case
        with tr.span("s2"):
            pass
        doc = tr.chrome_trace()
        rows = [e for e in doc["traceEvents"]
                if e["ph"] == "M" and e["tid"] == (me.ident or 0)]
        assert rows and rows[0]["args"]["name"] == "after-rename"
    finally:
        me.name = old


def test_register_aux_rides_in_other_data():
    tr = Tracer(enabled=True, ring=64, annotate=False)
    tr.register_aux("payload", lambda: {"x": 1})
    tr.register_aux("absent", lambda: None)
    tr.register_aux("broken", lambda: 1 / 0)
    with tr.span("s"):
        pass
    other = tr.chrome_trace()["otherData"]
    assert other["payload"] == {"x": 1}
    assert "absent" not in other and "broken" not in other


def test_sweep_phase_histogram_observed(global_trace):
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.engine.device_sweep import DeviceSweep
    from raphtory_tpu.obs.metrics import METRICS

    def hist_count(phase):
        for metric in METRICS.sweep_phase_seconds.collect():
            for s in metric.samples:
                if (s.name.endswith("_count")
                        and s.labels.get("phase") == phase):
                    return s.value
        return 0.0

    before = {ph: hist_count(ph)
              for ph in ("fold", "stage", "ship", "compute")}
    g = _graph(name="tr_hist", seed=11)
    ds = DeviceSweep(g.log)
    ds.run_sweep(PageRank(max_steps=5), [400, 800], windows=[10_000])
    for ph, prev in before.items():
        assert hist_count(ph) == prev + 1, ph


# ---- the recorder's budget (ISSUE 37): a default that holds a benchmark
# window whole; past it, ``dropped`` says how many events went


def test_default_ring_holds_32768_events(monkeypatch):
    from raphtory_tpu.obs import trace as obs_trace

    monkeypatch.delenv("RTPU_TRACE_RING", raising=False)
    assert obs_trace.DEFAULT_RING == 32_768
    assert Tracer(enabled=True).ring_size == 32_768
    monkeypatch.setenv("RTPU_TRACE_RING", "4096")       # the knob stays
    assert Tracer(enabled=True).ring_size == 4096


@pytest.mark.parametrize("ring,dropped,first,wrapped,last", [
    # 4,096 events are the newest 68 traces and 16 events of the 69th:
    # the window's first request is gone, and ``dropped`` says so
    (4096, 200 * 60 - 4096, 0, 16, 60),
    # the default holds the 200 requests whole, with nothing dropped
    (None, 0, 60, 60, 60),
])
def test_dropped_says_whether_the_ring_still_holds_the_window(
        monkeypatch, ring, dropped, first, wrapped, last):
    monkeypatch.delenv("RTPU_TRACE_RING", raising=False)
    tr = Tracer(enabled=True, ring=ring, annotate=False)
    ids = []
    for _ in range(200):
        with tr.span("request") as root:
            for i in range(58):
                with tr.span("stage", i=i):
                    pass
            tr.instant("mark")
        ids.append(root.trace)
    assert tr.status()["recorded"] == 200 * 60
    assert tr.status()["dropped"] == dropped
    assert [len(tr.for_trace(ids[k])) for k in (0, -69, -1)] \
        == [first, wrapped, last]
