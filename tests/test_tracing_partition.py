"""ISSUE 25: every second of a served request under one name.

A Range, a View and a Live job each leave the spans of the issue's
table A in ONE trace, the ledger's phases partition the job thread's
wall (fold inline and fold on workers), JAX's own program builds land
in the job's trace and in ``/statusz`` ``compile_caches.jax``, and a
span's ``self`` is its duration less its same-thread children."""

import time

import jax
import jax.numpy as jnp
import pytest

from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import (AnalysisManager, LiveQuery,
                                       RangeQuery, ViewQuery)
from raphtory_tpu.obs import device as obs_device
from raphtory_tpu.obs.trace import TRACER, Tracer
from raphtory_tpu.utils.synth import gab_like_log

KINDS = ("range", "view", "live")
T_SPAN = 100_000


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")      # fold for real
    monkeypatch.setenv("RTPU_BATCH_WINDOW_MS", "0")    # no collect window
    was = TRACER.enabled
    TRACER.enable()
    yield
    (TRACER.enable if was else TRACER.disable)()


@pytest.fixture(scope="module")
def log():
    # big enough that a job's fixed costs (thread start, imports done by
    # the warm-up) are small next to its phases
    return gab_like_log(n_vertices=4000, n_edges=120_000, t_span=T_SPAN)


def _copy(log):
    out = EventLog()
    out.append_batch(*(log.column(c)
                       for c in ("time", "kind", "src", "dst")))
    return out


def _pagerank():
    return registry.resolve("PageRank", {"max_steps": 20, "tol": 0})


def _query(kind: str, k: int = 0):
    if kind == "range":
        # 5 hops do not split into equal chunks: one dispatch group, so
        # RTPU_FOLD_WORKERS=1 folds it inline and >1 on the pool
        t0 = 60_000 + 6_000 * k
        return RangeQuery(start=t0, end=t0 + 4_000, jump=1_000,
                          windows=(T_SPAN, 20_000))
    if kind == "view":
        return ViewQuery(80_000 + 1_000 * k, window=T_SPAN)
    return LiveQuery(repeat=0.01, max_runs=1)


def _run(mgr, kind: str, k: int = 0):
    job = mgr.submit(_pagerank(), _query(kind, k))
    assert job.wait(300)
    assert job.status == "done", job.error
    spans = [e for e in TRACER.for_trace(job.trace_id) if e["ph"] == "X"]
    return job, spans


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("kind", KINDS)
def test_job_leaves_the_spans_of_table_a_in_one_trace(traced, log, kind):
    if kind == "range":
        # a log no test has indexed yet: its first request is the miss
        log = _copy(log)
    mgr = AnalysisManager(TemporalGraph(log))
    job, spans = _run(mgr, kind)
    (root,) = _named(spans, "job")
    assert {s["trace"] for s in spans} == {job.trace_id}

    # engine.build: what was built, on how much, why
    (build,) = _named(spans, "engine.build")
    reason = {"range": "request", "view": "pin", "live": "rebase"}[kind]
    engine = "DeviceSweep" if kind == "view" else "HopBatchedPageRank"
    assert build["args"]["reason"] == reason
    assert build["args"]["engine"] == engine
    assert build["args"]["events"] == log.n
    assert build["args"]["n_pad"] >= 4000 and build["args"]["m_pad"] > 0
    assert job.ledger.phase_seconds["build"] == pytest.approx(
        build["dur"] / 1e6, rel=0.05, abs=2e-3)

    # job.emit: one span a dispatch, with its rows
    emits = _named(spans, "job.emit")
    assert sum(s["args"]["rows"] for s in emits) == len(job.results)
    assert len(emits) == 1

    # job.publish: after the job span closed, still in its trace
    (pub,) = _named(spans, "job.publish")
    assert pub["args"]["status"] == "done"
    assert pub["parent"] == root["sid"]
    assert pub["ts"] >= root["ts"] + root["dur"] - 1.0

    if kind != "view":
        # one payload prep a dispatch
        lays = _named(spans, "engine.layout")
        assert {s["args"]["stage"] for s in lays} == {"payload"}
        (sweep,) = _named(spans, "sweep.columnar")
        for lay in lays:
            assert isinstance(lay["args"]["cached"], bool)
            assert sweep["ts"] <= lay["ts"] and lay["ts"] + lay["dur"] \
                <= sweep["ts"] + sweep["dur"] + 1.0
    if kind == "live":
        # the epoch span is the WHOLE epoch: build, dispatch and emit lie
        # inside it, and it says which engine served
        (ep,) = _named(spans, "live.epoch")
        assert ep["args"]["mode"] == "rebase"
        assert ep["args"]["n_pad"] == build["args"]["n_pad"]
        assert ep["args"]["m_pad"] == build["args"]["m_pad"]
        assert "time" in ep["args"]
        for inner in (build, emits[0], _named(spans, "sweep.columnar")[0]):
            assert ep["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= ep["ts"] + ep["dur"] + 1.0
        assert ep["dur"] >= 0.9 * (root["dur"] - 20e3)

    # a second request: a Range makes its engine again, now a fork of
    # the log's index; a View on the pinned sweep and the trace of it
    # have no build at all
    if kind != "live":
        job2, spans2 = _run(mgr, kind, k=1)
        builds2 = _named(spans2, "engine.build")
        if kind == "view":
            assert builds2 == []
            assert "build" not in job2.ledger.phase_seconds
        else:
            assert build["args"]["index"] == "miss"
            (build2,) = builds2
            assert build2["args"]["index"] == "hit"
            assert job2.ledger.phase_seconds["build"] \
                < job.ledger.phase_seconds["build"]


@pytest.mark.parametrize("fold", ("inline", "workers"))
@pytest.mark.parametrize("kind", KINDS)
def test_phases_partition_the_job_threads_wall(traced, log, monkeypatch,
                                               kind, fold):
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1" if fold == "inline" else "4")
    mgr = AnalysisManager(TemporalGraph(log))
    _run(mgr, kind)                       # imports, compiles, the pin
    job, spans = _run(mgr, kind, k=1)
    led = job.ledger.as_dict()
    ph = led["phase_seconds"]
    total = led["queue_wait_seconds"] + sum(ph.values())
    assert total == pytest.approx(led["wall_seconds"], rel=0.01)
    assert led["phase_overlap_seconds"] == 0.0
    assert ph["other"] < 0.10 * led["wall_seconds"], ph
    if kind == "range":
        folds = _named(spans, "hop.fold")
        on_job_thread = {s["tid"] for s in _named(spans, "job")}
        if fold == "inline":
            assert {s["tid"] for s in folds} == on_job_thread
            assert "serial" in led["fold"]["seconds_by_mode"]
        else:
            # the workers' seconds are cost (the fold block), and only
            # what the job thread waited on them is wall
            assert {s["tid"] for s in folds}.isdisjoint(on_job_thread)
            stall = sum(s["dur"] for s in _named(spans, "fold.stall")) / 1e6
            assert ph["fold"] == pytest.approx(stall, abs=2e-3)
            assert led["fold"]["seconds_by_mode"]["parallel"] > 0


def test_overlapping_phases_are_reported_not_clamped():
    from raphtory_tpu.obs.ledger import Ledger

    led = Ledger("q")
    led.add_phase("fold", 0.7)
    led.add_phase("compute", 0.6)
    led.finish(1.0)
    d = led.as_dict()
    assert d["phase_seconds"]["other"] == 0.0
    assert d["phase_overlap_seconds"] == pytest.approx(0.3)
    twice = Ledger("sum").merge(led).merge(led)
    assert twice.phase_overlap_seconds == pytest.approx(0.6)
    ok = Ledger("ok")
    ok.add_phase("build", 0.5)
    ok.finish(1.0)
    assert ok.as_dict()["phase_overlap_seconds"] == 0.0
    assert ok.phase_seconds["other"] == pytest.approx(0.5)
    assert ok.bound() == "host_bound"      # an engine build is host work


def _job_that_calls(log, fn):
    """A real View job whose program's ``reduce`` (host code on the job
    thread, inside the job's trace) calls ``fn`` once."""
    from dataclasses import dataclass

    from raphtory_tpu.algorithms.pagerank import PageRank

    @dataclass(frozen=True)
    class Calls(PageRank):
        def reduce(self, result, view, window=None):
            fn()
            return super().reduce(result, view, window=window)

    job = AnalysisManager(TemporalGraph(log)).submit(
        Calls(max_steps=2), ViewQuery(90_000))
    assert job.wait(300)
    assert job.status == "done", job.error
    return job


@pytest.mark.parametrize("which", ("fresh_jit", "same_jit"))
def test_jax_program_builds_land_in_the_jobs_trace(traced, log, which):
    def make():
        # a new function object every call, as parallel/columns.py's
        # per-request closure is: jax keys its caches on the function
        def body(x):
            return jnp.cumsum(x * 3.0 + 1.0)
        return body

    # the build tables are the process's: judge "the ten functions with
    # most seconds" on what this test builds, not on what the test files
    # that shared this worker compiled before it
    obs_device.clear_compiles()
    x = jnp.arange(257, dtype=jnp.float32)
    fn = jax.jit(make())
    fn(x).block_until_ready()             # built once, outside any trace
    counts = []

    def inside_the_job():
        counts.append(obs_device.jax_builds_block()["stages"])
        if which == "fresh_jit":
            # a NEW jit object over an identical function: jax traces,
            # lowers and asks the backend (or its caches) again
            jax.jit(make())(x).block_until_ready()
        else:
            fn(x).block_until_ready()
        counts.append(obs_device.jax_builds_block()["stages"])

    job = _job_that_calls(log, inside_the_job)
    before, after = counts
    grew = {st: after[st]["count"] - before[st]["count"] for st in after}
    ev = [e for e in TRACER.for_trace(job.trace_id)
          if e["name"].startswith("xla.") and "body" in e["args"].get(
              "fun", "")]
    if which == "fresh_jit":
        assert {e["name"] for e in ev} == {"xla.trace", "xla.lower",
                                           "xla.backend_compile"}
        assert min(grew.values()) >= 1
        emit = [e for e in TRACER.for_trace(job.trace_id)
                if e["name"] == "job.emit"][0]
        assert all(e["parent"] == emit["sid"] and e["tid"] == emit["tid"]
                   for e in ev)
        assert any("body" in f["fun"] for f in
                   obs_device.jax_builds_block()["top_funs"])
    else:
        assert ev == []
        assert grew == {"trace": 0, "lower": 0, "backend_compile": 0}


def test_statusz_serves_the_jax_build_counters(traced, log):
    from raphtory_tpu.jobs.rest import _compile_cache_sizes

    jax.jit(lambda v: v - 2.0)(jnp.ones(33)).block_until_ready()
    block = _compile_cache_sizes()["jax"]
    assert block["watching"]
    assert set(block["stages"]) == {"trace", "lower", "backend_compile"}
    assert block["stages"]["backend_compile"]["count"] >= 1
    assert block["stages"]["backend_compile"]["seconds"] > 0
    assert set(block["persistent_cache"]) == {"hits", "misses"}
    assert len(block["top_funs"]) <= 10


def test_listener_is_silent_and_free_when_tracing_is_off(monkeypatch):
    was = TRACER.enabled
    TRACER.disable()
    try:
        before = obs_device.jax_builds_block()
        recorded = TRACER.recorded
        jax.jit(lambda v: v * 5.0 - 1.0)(jnp.ones(17)).block_until_ready()
        assert obs_device.jax_builds_block() == before
        assert TRACER.recorded == recorded

        # free: off, the listener returns before it touches the tracer,
        # the lock or its arguments
        def boom(*a, **k):
            raise AssertionError("listener did work with tracing off")

        monkeypatch.setattr(obs_device._TRACER, "complete", boom)
        monkeypatch.setattr(obs_device, "_COMPILE_LOCK", None)
        obs_device._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 1.0, fun_name="f")
        obs_device._on_jax_event("/jax/compilation_cache/cache_hits")
    finally:
        (TRACER.enable if was else TRACER.disable)()
    # and on, it never raises into jax's compile path
    TRACER.enable()
    try:
        obs_device._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", "not a number")
        obs_device._on_jax_duration("/some/other/event", 1.0)
    finally:
        (TRACER.enable if was else TRACER.disable)()


def test_self_time_is_duration_less_same_thread_children():
    tr = Tracer(enabled=True, annotate=False)
    with tr.span("parent"):
        time.sleep(0.02)
        with tr.span("child"):
            time.sleep(0.12)
            with tr.span("grandchild"):
                time.sleep(0.01)
        # two already-happened children, the second NESTED in the first
        # (a jit traced inside a trace): counted once
        time.sleep(0.02)
        tr.complete("done.inner", 0.005)
        tr.complete("done.outer", 0.015)
    ev = {e["name"]: e for e in tr.recent(10)}
    p, c, g = ev["parent"], ev["child"], ev["grandchild"]
    assert g["self"] == pytest.approx(g["dur"])
    assert c["self"] == pytest.approx(c["dur"] - g["dur"])
    outer, inner = ev["done.outer"], ev["done.inner"]
    assert inner["self"] == pytest.approx(inner["dur"])
    assert outer["self"] == pytest.approx(outer["dur"] - inner["dur"],
                                          abs=200.0)
    assert p["self"] == pytest.approx(
        p["dur"] - c["dur"] - outer["dur"], abs=200.0)
    assert p["self"] >= 20e3
    # /tracez?trace_id= sums self by name: one thread's values add up to
    # its root span
    by_name = Tracer.self_seconds(tr.for_trace(p["trace"]))
    assert sum(by_name.values()) == pytest.approx(p["dur"] / 1e6, abs=1e-3)
    assert list(by_name)[0] == "child"      # largest first


def test_tracez_returns_self_seconds_for_a_trace(traced, log):
    import json
    import urllib.request

    from raphtory_tpu.jobs.rest import RestServer

    mgr = AnalysisManager(TemporalGraph(log))
    server = RestServer(mgr, port=0).start()
    try:
        job, spans = _run(mgr, "range")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/tracez?trace_id="
                f"{job.trace_id}", timeout=30) as r:
            doc = json.loads(r.read())
    finally:
        server.stop()
    assert {s["name"] for s in doc["spans"]} >= {"job", "engine.build",
                                                 "job.publish"}
    ss = doc["self_seconds"]
    assert set(ss) == {s["name"] for s in doc["spans"] if s["ph"] == "X"}
    assert ss["engine.build"] > 0
    (root,) = [s for s in doc["spans"] if s["name"] == "job"]
    # the job thread's own spans account for the whole job span
    tid = root["tid"]
    own = sum(s["self"] for s in doc["spans"]
              if s["ph"] == "X" and s["tid"] == tid
              and s["name"] != "job.publish")
    assert own == pytest.approx(root["dur"], rel=0.01)


def test_a_retraced_program_traces_the_scanned_sum_as_cached_calls(traced):
    """The mesh column route builds a NEW jit object a request, so JAX
    traces its program again a request and every jnp wrapper traced is one
    ``xla.trace`` event in the flight recorder's ring (4,096 events: the
    benchmark reads a request's spans from it after the window). The
    scanned sum's helpers are jitted, so a re-trace sees a few cached calls
    where the unrolled scan would be some two hundred wrappers — which
    pushed the window's first request out of the ring on the chip."""
    from raphtory_tpu.ops.segment import sorted_segment_sum

    assert obs_device.watch_jax_builds()
    ids = jnp.sort(jnp.arange(5000, dtype=jnp.int32) % 37)
    data = jnp.ones((5000, 6), jnp.float32)

    def traces():
        return obs_device.jax_builds_block()["stages"]["trace"]["count"]

    def fresh():
        return jax.jit(lambda d: sorted_segment_sum(d, ids, 37))(
            data).block_until_ready()

    fresh()                               # everything built once
    before = traces()
    fresh()                               # a new jit object: traced anew
    assert 1 <= traces() - before <= 25, traces() - before
