"""ISSUE 37: the host spans that hold the chip idle get stages.

``engine.build``, ``hop.fold`` and ``comm.exchange`` are taken apart
where the work happens: a Live rebase epoch writes ``index.lookup`` /
``ids`` / ``pairs`` / ``tables`` / ``fork`` under its one
``engine.build``, a Range's fold units write ``fold.seed`` /
``fold.advance`` / ``fold.payload`` under their ``hop.fold``, the mesh
route's dispatch writes ``comm.put`` under ``comm.exchange`` (the call
of the program is what the exchange keeps as its own) — each in the
request's trace, on the thread that does the work, the children's
seconds inside the parent's — and with tracing off none of them
allocates a span."""

import jax
import numpy as np
import pytest

from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.engine.hopbatch import HopBatchedPageRank
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import (AnalysisManager, LiveQuery,
                                       RangeQuery)
from raphtory_tpu.obs import trace as obs_trace
from raphtory_tpu.obs.trace import NULL_SPAN, TRACER, Tracer
from raphtory_tpu.parallel.columns import (_compiled_columns,
                                           run_columns_sharded)

from test_sweep import random_log

INDEX_STAGES = ("index.lookup", "index.ids", "index.pairs", "index.tables",
                "index.fork")
FOLD_STAGES = ("fold.seed", "fold.advance", "fold.payload")
COMM_STAGES = ("comm.put",)
STAGES = INDEX_STAGES + FOLD_STAGES + COMM_STAGES


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    monkeypatch.setenv("RTPU_BATCH_WINDOW_MS", "0")    # no collect window
    was = TRACER.enabled
    TRACER.enable()
    yield
    (TRACER.enable if was else TRACER.disable)()


def _log(seed, n_events=6000, n_ids=300):
    return random_log(np.random.default_rng(seed), n_events=n_events,
                      n_ids=n_ids, t_span=1000)


def _pagerank():
    return registry.resolve("PageRank", {"max_steps": 20, "tol": 0})


def _spans(job):
    assert job.wait(300) and job.status == "done", job.error
    return [e for e in TRACER.for_trace(job.trace_id) if e["ph"] == "X"]


def _named(spans, *names):
    return [s for s in spans if s["name"] in names]


def _children(spans, parent):
    return [s for s in spans if s["parent"] == parent["sid"]]


def _inside(parent, child, slack_us=1.0):
    return (parent["tid"] == child["tid"]
            and parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


def _descends(spans, child, ancestor):
    by_sid = {s["sid"]: s for s in spans}
    while child is not None and child["parent"] != ancestor["sid"]:
        child = by_sid.get(child["parent"])
    return child is not None


def _unit_seeds(spans):
    """The ``fold.seed`` spans ``hopbatch._seed_fork`` writes — a fold
    unit's checkpoint lookup and its fork — without the deferred copies
    that share the name."""
    return [s for s in _named(spans, "fold.seed")
            if "deferred" not in s["args"]]


def _deferred_seeds(spans, fold):
    """The ``fold.seed deferred=true`` spans under ``fold``: the copies of
    shared fold state its builder took before a first write. Each lies on
    the fold's thread, in its trace, and never inside a ``fold.advance``
    (``benchmark/layers.span_share`` sums whole durations: nested, the
    copy would count in two shares)."""
    by_sid = {s["sid"]: s for s in spans}
    got = [s for s in _named(spans, "fold.seed")
           if s["args"].get("deferred") and _descends(spans, s, fold)]
    for s in got:
        assert s["args"]["deferred"] is True and s["args"]["nbytes"] > 0
        assert s["tid"] == fold["tid"] and s["trace"] == fold["trace"]
        assert by_sid[s["parent"]]["name"] in ("hop.fold",
                                               "fold.checkpoint")
    return got


# --------------------------------------------------------- engine.build


def test_live_rebase_epoch_takes_its_engine_build_apart(traced):
    log = _log(37)
    mgr = AnalysisManager(TemporalGraph(log))
    spans = _spans(mgr.submit(_pagerank(), LiveQuery(repeat=0.01,
                                                     max_runs=1)))
    (build,) = _named(spans, "engine.build")
    assert build["args"]["reason"] == "rebase"
    assert build["args"]["index"] == "miss"
    stages = _named(spans, *INDEX_STAGES)
    assert [s["name"] for s in sorted(stages, key=lambda s: s["ts"])] \
        == list(INDEX_STAGES)               # one of each, in this order
    for s in stages:
        assert s["trace"] == build["trace"] and _inside(build, s)
        assert _descends(spans, s, build)
    by = {s["name"]: s for s in stages}
    # the status is the parent's ``index``, the pads its ``n_pad`` / ``m_pad``
    assert by["index.lookup"]["args"] == by["index.tables"]["args"] == {}
    assert by["index.ids"]["args"]["events"] == log.n
    assert 0 < by["index.ids"]["args"]["ids"] <= build["args"]["n_pad"]
    assert 0 < by["index.pairs"]["args"]["pairs"] <= build["args"]["m_pad"]
    # a fork shares 18 B an id and 18 B a pair, and copies none of them
    assert by["index.fork"]["args"] == {"nbytes": 0, "shared": 18 * (
        by["index.ids"]["args"]["ids"] + by["index.pairs"]["args"]["pairs"])}
    # the children's seconds are the parent's, less its own
    assert sum(s["dur"] for s in stages) <= build["dur"]
    assert build["self"] == pytest.approx(
        build["dur"] - sum(s["dur"] for s in _children(spans, build)),
        abs=1.0)

    # the epoch's fold carries its stages too: one advance from the
    # log's first event, one base payload
    (fold,) = _named(spans, "hop.fold")
    adv, = _named(_children(spans, fold), "fold.advance")
    assert adv["args"]["rows"] == log.n
    # the engine's builder writes here for the first time: the copy its
    # fork put off is a span of its own, before the advance and beside it
    (own,) = _deferred_seeds(spans, fold)
    assert own["parent"] == fold["sid"] and _inside(fold, own)
    assert own["args"]["nbytes"] == by["index.fork"]["args"]["shared"]
    assert own["ts"] + own["dur"] <= adv["ts"] + 1.0
    pay, = _named(_children(spans, fold), "fold.payload")
    assert pay["args"]["base"] is True and pay["args"]["bytes"] > 0


def test_second_engine_over_an_unchanged_log_only_looks_up_and_forks(
        traced):
    log = _log(38)
    mgr = AnalysisManager(TemporalGraph(log))
    q = RangeQuery(start=600, end=800, jump=100, windows=(500,))
    first = _spans(mgr.submit(_pagerank(), q))
    assert {s["name"] for s in _named(first, *INDEX_STAGES)} \
        == set(INDEX_STAGES)
    second = _spans(mgr.submit(
        _pagerank(), RangeQuery(start=700, end=900, jump=100,
                                windows=(500,))))
    (build,) = _named(second, "engine.build")
    assert build["args"]["index"] == "hit"
    stages = sorted(_named(second, *INDEX_STAGES), key=lambda s: s["ts"])
    assert [s["name"] for s in stages] == ["index.lookup", "index.fork"]
    assert all(_inside(build, s) for s in stages)
    # on a hit the fork is no longer the build: it copies nothing
    assert stages[1]["args"]["nbytes"] == 0
    assert stages[1]["args"]["shared"] > 0


# ------------------------------------------------------------- hop.fold


def test_range_on_two_fold_workers_writes_the_fold_stages(traced,
                                                          monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "2")
    log = _log(39)
    mgr = AnalysisManager(TemporalGraph(log))
    hops = list(range(500, 1000, 100))      # 5 hops: one group, two units
    spans = _spans(mgr.submit(_pagerank(), RangeQuery(
        start=hops[0], end=hops[-1], jump=100, windows=(1000, 300))))
    (job,) = _named(spans, "job")
    folds = _named(spans, "hop.fold")
    assert len(folds) == 2
    assert {f["args"]["mode"] for f in folds} == {"parallel"}
    assert all(f["tid"] != job["tid"] for f in folds)      # on the pool
    assert all(f["args"]["worker"].startswith("sweep-fold")
               for f in folds)
    n_adv = 0
    for fold in folds:
        assert fold["trace"] == job["trace"]
        kids = _children(spans, fold)
        assert {k["name"] for k in kids} <= {
            "fold.seed", "fold.checkpoint", "fold.advance", "fold.payload"}
        (seed,) = _unit_seeds(kids)
        assert seed["args"]["seed"] in ("start", "live", "checkpoint")
        # the fork copies nothing where it is made; the unit's builder
        # copies what it shares once, on this worker, when it first folds
        assert seed["args"]["nbytes"] == 0 and seed["args"]["shared"] > 0
        (own,) = _deferred_seeds(spans, fold)
        assert own["args"]["nbytes"] == seed["args"]["shared"]
        assert own["ts"] >= seed["ts"] + seed["dur"] - 1.0
        # one advance and one payload a hop, each inside its fold
        adv = _named(kids, "fold.advance")
        pay = _named(kids, "fold.payload")
        assert len(adv) == len(pay) == fold["args"]["hops"]
        n_adv += len(adv)
        assert all(_inside(fold, k) for k in kids)
        # the bulk advance to the unit's boundary nests in fold.checkpoint
        for cp in _named(kids, "fold.checkpoint"):
            (bulk,) = _named(_children(spans, cp), "fold.advance")
            assert bulk["args"]["time"] == cp["args"]["time"]
        # hop.fold's self is what its children leave
        assert fold["self"] == pytest.approx(
            fold["dur"] - sum(k["dur"] for k in kids), abs=1.0)
    assert n_adv == len(hops)
    # exactly one payload of the request is the base snapshot
    pays = _named(spans, "fold.payload")
    assert sum(p["args"]["base"] for p in pays) == 1
    assert all(p["args"]["bytes"] >= 0 for p in pays)
    # every log row up to the last hop was folded once a unit that
    # reached it: the advances' rows are real row counts
    rows = [a["args"]["rows"] for a in _named(spans, "fold.advance")]
    assert all(r >= 0 for r in rows) and max(rows) > 0


def test_inline_column_fold_writes_an_advance_and_a_payload_a_hop(
        traced, monkeypatch):
    """The serial arm of the mesh route's fold (``fold_payloads(delta=
    False)`` at one fold worker: ``_fold_columns`` on the calling
    thread, under its own ``hop.fold``)."""
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1")
    hb = HopBatchedPageRank(_log(40), tol=0, max_steps=5)
    hops = [400, 600, 800]
    with TRACER.span("job") as root:
        hb.fold_payloads(hops, delta=False)
    spans = [e for e in TRACER.for_trace(root.trace) if e["ph"] == "X"]
    (fold,) = _named(spans, "hop.fold")
    assert fold["args"]["hops"] == 3 and "mode" not in fold["args"]
    kids = _children(spans, fold)
    assert all(_inside(fold, k) for k in kids)
    # the engine's own builder folds here: the copy its fork from the
    # index put off comes first, then an advance and a payload a hop
    assert _deferred_seeds(spans, fold) == kids[:1]
    assert kids[0]["args"]["nbytes"] == hb.sw.fork_nbytes()
    kids = kids[1:]
    assert [k["name"] for k in kids] == ["fold.advance", "fold.payload"] * 3
    assert [k["args"]["time"] for k in kids[::2]] == hops
    assert [k["args"]["base"] for k in kids[1::2]] == [True, False, False]
    t = hb.tables
    row = (t.m_pad + t.n_pad) * (np.dtype(t.tdtype).itemsize + 1)
    assert {k["args"]["bytes"] for k in kids[1::2]} == {row}


@pytest.mark.parametrize("workers", [1, 4])
def test_mesh_range_job_writes_the_fold_stages_where_it_folds(
        traced, monkeypatch, workers):
    """A mesh Range job follows the one-chip route's fold: its stages lie
    under a worker's ``hop.fold mode=parallel`` and the job thread's wait
    is a ``fold.stall``; at one fold worker they lie under the job
    thread's own ``hop.fold``."""
    from raphtory_tpu.parallel import sharded

    monkeypatch.setenv("RTPU_FOLD_WORKERS", str(workers))
    mgr = AnalysisManager(TemporalGraph(_log(44)),
                          mesh=sharded.make_mesh(4, 2))
    hops = [300, 500, 700, 900]
    spans = _spans(mgr.submit(_pagerank(), RangeQuery(
        start=hops[0], end=hops[-1], jump=200, windows=(1000, 300))))
    (job,) = _named(spans, "job")
    folds = _named(spans, "hop.fold")
    assert len(folds) == workers and \
        sum(f["args"]["hops"] for f in folds) == 4
    for fold in folds:
        kids = _children(spans, fold)
        assert all(_inside(fold, k) for k in kids)
        adv, pay = (_named(kids, n) for n in ("fold.advance",
                                              "fold.payload"))
        assert len(adv) == len(pay) == fold["args"]["hops"]
        # every unit's first row is the full fold state, the rest copies
        assert [p["args"]["base"] for p in pay] \
            == [True] + [False] * (len(pay) - 1)
        if workers == 1:
            assert fold["tid"] == job["tid"] and "mode" not in fold["args"]
            assert {k["name"] for k in kids} == {
                "fold.seed", "fold.advance", "fold.payload"}
            assert _deferred_seeds(spans, fold) == _named(kids, "fold.seed")
        else:
            assert fold["tid"] != job["tid"]
            assert fold["args"]["mode"] == "parallel"
            assert fold["args"]["worker"].startswith("sweep-fold")
            (seed,) = _unit_seeds(kids)
            assert seed["args"]["nbytes"] == 0
            (own,) = _deferred_seeds(spans, fold)
            assert own["args"]["nbytes"] == seed["args"]["shared"] > 0
            for cp in _named(kids, "fold.checkpoint"):
                (bulk,) = _named(_children(spans, cp), "fold.advance")
                assert bulk["args"]["time"] == cp["args"]["time"]
    stalls = _named(spans, "fold.stall")
    assert (len(stalls) > 0) == (workers > 1)
    assert {s["tid"] for s in stalls} <= {job["tid"]}
    assert sorted(a["args"]["time"] for f in folds
                  for a in _named(_children(spans, f), "fold.advance")) \
        == hops
    # the exchange starts when the last unit has been waited for
    (xchg,) = _named(spans, "comm.exchange")
    assert xchg["ts"] >= max(f["ts"] + f["dur"] for f in folds) - 1.0


# -------------------------------------------------------- comm.exchange


def test_column_sharded_dispatch_splits_comm_exchange(traced):
    # the program is built by a key's first call only: make this one it,
    # whatever ran before
    _compiled_columns.cache_clear()
    log = _log(41, n_events=900, n_ids=50)
    hb = HopBatchedPageRank(log, tol=0, max_steps=5)
    hops = [400, 700, 999]
    _, cols = hb._fold_columns(hops)
    with TRACER.span("job") as root:
        run_columns_sharded(hb.tables, *cols, hops, [1000, 300],
                            jax.devices()[:4], tol=0, max_steps=5)
    spans = [e for e in TRACER.for_trace(root.trace) if e["ph"] == "X"]
    (xchg,) = _named(spans, "comm.exchange")
    (put,) = _named(_children(spans, xchg), *COMM_STAGES)
    assert put["args"]["arrays"] == 9          # six tables, three columns
    assert put["args"]["bytes"] * 3 == xchg["args"]["bytes"]   # x (4 - 1)
    assert _inside(xchg, put)
    # the program build's events come after the puts, inside the
    # exchange: the call is what the exchange keeps for itself
    built = [e for e in _named(spans, "xla.trace", "xla.lower",
                               "xla.backend_compile")
             if xchg["ts"] <= e["ts"] <= xchg["ts"] + xchg["dur"]]
    assert built and all(e["ts"] >= put["ts"] + put["dur"] - 1.0
                         for e in built)
    assert xchg["self"] <= xchg["dur"] - put["dur"] + 1.0
    # the wait for the chips stays outside the exchange
    (wait,) = _named(spans, "comm.block_wait")
    assert wait["ts"] >= xchg["ts"] + xchg["dur"] - 1.0
    # a second call of the key finds its program: the exchange still
    # holds the puts, and no build event
    with TRACER.span("job") as again:
        run_columns_sharded(hb.tables, *cols, hops, [1000, 300],
                            jax.devices()[:4], tol=0, max_steps=5)
    spans = [e for e in TRACER.for_trace(again.trace) if e["ph"] == "X"]
    (xchg,) = _named(spans, "comm.exchange")
    (put,) = _named(_children(spans, xchg), *COMM_STAGES)
    assert _inside(xchg, put)
    assert not [e for e in spans if e["name"].startswith("xla.")]


# ---------------------------------------------------------- tracing off


def test_with_tracing_off_no_stage_allocates_a_span(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "2")
    made = []
    real = obs_trace.Span.__init__

    def counting(self, tracer, name, attrs):
        made.append(name)
        real(self, tracer, name, attrs)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting)
    was = TRACER.enabled
    TRACER.disable()
    try:
        assert TRACER.span("index.ids") is NULL_SPAN
        log = _log(42, n_events=900, n_ids=50)
        before = TRACER.recorded
        hb = HopBatchedPageRank(log, tol=0, max_steps=5)
        assert hb.index_status == "miss"
        hb.run([400, 600, 800, 999], [1000], chunks=2)      # pool folds
        hb2 = HopBatchedPageRank(log, tol=0, max_steps=5)
        assert hb2.index_status == "hit"
        _, cols = hb2._fold_columns([500, 999])             # inline fold
        run_columns_sharded(hb2.tables, *cols, [500, 999], [1000],
                            jax.devices()[:2], tol=0, max_steps=5)
        assert made == [] and TRACER.recorded == before
    finally:
        (TRACER.enable if was else TRACER.disable)()
    # and on, the same drive allocates every one of them
    tr_was = TRACER.enabled
    TRACER.enable()
    try:
        log = _log(43, n_events=900, n_ids=50)
        hb = HopBatchedPageRank(log, tol=0, max_steps=5)
        hb.run([400, 600, 800, 999], [1000], chunks=2)
        _, cols = HopBatchedPageRank(log, tol=0, max_steps=5)._fold_columns(
            [500, 999])
        run_columns_sharded(hb.tables, *cols, [500, 999], [1000],
                            jax.devices()[:2], tol=0, max_steps=5)
        assert set(STAGES) <= set(made)
    finally:
        (TRACER.enable if tr_was else TRACER.disable)()


def test_the_stage_names_are_nine_of_the_issue_s_ten():
    # ``comm.enqueue`` went in review: no metric could read it, and the
    # exchange's self time says the same
    assert len(set(STAGES)) == 9
    # a private tracer's self time over the stage shape: a parent whose
    # stages cover it keeps nothing for itself
    tr = Tracer(enabled=True, ring=64, annotate=False)
    with tr.span("engine.build") as p:
        for name in INDEX_STAGES:
            with tr.span(name):
                pass
    ev = tr.for_trace(p.trace)
    assert [e["name"] for e in ev] == [*INDEX_STAGES, "engine.build"]
    assert Tracer.self_seconds(ev)["engine.build"] <= ev[-1]["dur"] / 1e6
