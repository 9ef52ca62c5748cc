"""Column-sharded (view-axis) range sweeps vs the single-device columnar
engine — values must be bit-identical; the mesh only splits the work."""

import numpy as np
import pytest

import jax

from test_stage_spans import (_log, _named, _pagerank, _spans,
                              _unit_seeds)
from test_sweep import random_log

from raphtory_tpu.engine.hopbatch import HopBatchedPageRank
from raphtory_tpu.parallel.columns import (_compiled_columns,
                                           run_columns_sharded)


@pytest.mark.parametrize("n_dev,windows", [
    (8, [1000, 30, None]),   # C=15 pads to 16
    (4, [1000, 25]),         # C=10 pads to 12
    (1, [1000]),             # degenerate mesh
])
def test_column_sharded_matches_single_device(n_dev, windows):
    rng = np.random.default_rng(3)
    log = random_log(rng, n_events=900, n_ids=50, t_span=100)
    hops = [20, 40, 60, 80, 99]
    one, steps1 = HopBatchedPageRank(log, tol=1e-7, max_steps=20).run(
        hops, windows)

    hb = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
    _, cols = hb._fold_columns([int(x) for x in hops])
    many, steps2 = run_columns_sharded(
        hb.tables, *cols, hops, windows, jax.devices()[:n_dev],
        tol=1e-7, max_steps=20)
    # tight-tolerance, not bitwise: the column-sharded program partitions
    # the f32 segment sums differently from the single-device one, and
    # some XLA versions round the fused reductions differently (~1e-8)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=1e-5, atol=1e-7)
    assert int(steps1) == steps2


def _weighted_log(rng, n=700):
    """``n`` edge adds over 40 ids, each with a ``weight`` property."""
    from raphtory_tpu.core.events import EventLog

    src = rng.integers(0, 40, n)
    dst = rng.integers(0, 40, n)
    times = np.sort(rng.integers(0, 100, n))
    log = EventLog()
    log.append_batch(
        times, np.full(n, 2, np.uint8), src.astype(np.int64),
        dst.astype(np.int64),
        props=[(i, {"weight": float(rng.uniform(0.5, 3.0))})
               for i in range(n)])
    return log


@pytest.mark.parametrize("kind", ["cc", "bfs", "sssp"])
def test_column_sharded_cc_bfs_match_single_device(kind):
    from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                              HopBatchedSSSP)

    rng = np.random.default_rng(7)
    if kind == "sssp":
        log = _weighted_log(rng)
    else:
        log = random_log(rng, n_events=900, n_ids=50, t_span=100)
    hops = [20, 40, 60, 80, 99]
    windows = [1000, 30]
    seeds = (0, 1, 2)
    if kind == "cc":
        hb = HopBatchedCC(log, max_steps=60)
        kw = dict(kind="cc", max_steps=60)
    elif kind == "bfs":
        hb = HopBatchedBFS(log, seeds, directed=False, max_steps=50)
        kw = dict(kind="bfs", seeds=seeds, directed=False, max_steps=50)
    else:
        hb = HopBatchedSSSP(log, seeds, "weight", directed=False,
                            max_steps=50)
        kw = dict(kind="bfs", seeds=seeds, directed=False, max_steps=50)
    one, steps1 = hb.run(hops, windows)

    hb2 = type(hb)(log, *( (seeds, "weight") if kind == "sssp"
                           else (seeds,) if kind == "bfs" else ()),
                   **({"directed": False, "max_steps": 50}
                      if kind != "cc" else {"max_steps": 60}))
    _, cols = hb2._fold_columns([int(x) for x in hops])
    if kind == "sssp":
        *cols, wcols = cols
        kw["weight_cols"] = wcols
    many, steps2 = run_columns_sharded(
        hb2.tables, *cols, hops, windows, jax.devices(), **kw)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(many))
    assert int(steps1) == steps2


def test_mesh_pagerank_range_job_rides_column_sharding(monkeypatch):
    """With a mesh set, PageRank Range jobs take the view-axis route and
    agree with mesh-less per-view jobs."""
    from test_jobs import _graph

    from raphtory_tpu.jobs import manager as mgr_mod
    from raphtory_tpu.jobs import registry
    from raphtory_tpu.jobs.manager import (AnalysisManager, RangeQuery,
                                           ViewQuery)
    from raphtory_tpu.parallel import sharded

    taken = []
    orig = mgr_mod.Job._try_range_mesh_columns

    def spy(self, q):
        r = orig(self, q)
        taken.append(r)
        return r

    monkeypatch.setattr(mgr_mod.Job, "_try_range_mesh_columns", spy)
    g = _graph()
    mesh = sharded.make_mesh(4, 2)
    mgr = AnalysisManager(g, mesh=mesh)

    def pr():
        return registry.resolve("PageRank",
                                {"max_steps": 200, "tol": 1e-9})

    q = RangeQuery(start=20, end=90, jump=10, windows=(100, 25))
    job = mgr.submit(pr(), q)
    assert job.wait(120)
    assert job.status == "done", job.error
    assert taken == [True]
    assert len(job.results) == 8 * 2

    flat = AnalysisManager(g)   # no mesh: independent reference rows
    for t in (20, 90):
        vjob = flat.submit(pr(), ViewQuery(t, windows=(100, 25)))
        assert vjob.wait(60)
        for vrow in vjob.results:
            rrow = next(r for r in job.results
                        if r["time"] == t
                        and r["windowsize"] == vrow["windowsize"])
            assert rrow["result"]["sum"] == pytest.approx(
                vrow["result"]["sum"], abs=1e-4)
            ra, rb = dict(rrow["result"]["top10"]), \
                dict(vrow["result"]["top10"])
            assert set(ra) == set(rb)
            for k in ra:
                assert ra[k] == pytest.approx(rb[k], abs=1e-5)


def test_mesh_cc_range_job_rides_column_sharding(monkeypatch):
    from test_jobs import _graph

    from raphtory_tpu.jobs import manager as mgr_mod
    from raphtory_tpu.jobs import registry
    from raphtory_tpu.jobs.manager import (AnalysisManager, RangeQuery,
                                           ViewQuery)
    from raphtory_tpu.parallel import sharded

    taken = []
    orig = mgr_mod.Job._try_range_mesh_columns

    def spy(self, q):
        r = orig(self, q)
        taken.append(r)
        return r

    monkeypatch.setattr(mgr_mod.Job, "_try_range_mesh_columns", spy)
    g = _graph()
    mgr = AnalysisManager(g, mesh=sharded.make_mesh(4, 2))

    def cc():
        return registry.resolve("ConnectedComponents", {"max_steps": 60})

    job = mgr.submit(cc(), RangeQuery(start=20, end=90, jump=10,
                                      windows=(100, 25)))
    assert job.wait(120)
    assert job.status == "done", job.error
    assert taken == [True]

    flat = AnalysisManager(g)
    for t in (20, 90):
        vjob = flat.submit(cc(), ViewQuery(t, windows=(100, 25)))
        assert vjob.wait(60)
        for vrow in vjob.results:
            rrow = next(r for r in job.results
                        if r["time"] == t
                        and r["windowsize"] == vrow["windowsize"])
            assert rrow["result"] == vrow["result"]


# ------------------------------------------- the mesh route's host fold


@pytest.fixture
def traced(monkeypatch):
    from raphtory_tpu.core.sweep import fold_cache
    from raphtory_tpu.obs.trace import TRACER

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    monkeypatch.setenv("RTPU_BATCH_WINDOW_MS", "0")    # no collect window
    fold_cache().clear()
    was = TRACER.enabled
    TRACER.enable()
    yield
    (TRACER.enable if was else TRACER.disable)()


def _done(job):
    assert job.wait(300) and job.status == "done", job.error
    return job


def _range_graph(seed):
    from raphtory_tpu.core.service import TemporalGraph

    return TemporalGraph(_log(seed))


@pytest.mark.parametrize("workers", [2, 4])
def test_second_mesh_range_job_folds_from_the_first_ones_checkpoint(
        traced, monkeypatch, workers):
    """The mesh route folds as the one-chip route does: a request that
    starts where the last one ended seeds every fold unit from a cached
    checkpoint — none advances from the log's first event — on the fold
    pool, and the rows are the one-chip route's."""
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
    from raphtory_tpu.parallel import sharded

    monkeypatch.setenv("RTPU_FOLD_WORKERS", str(workers))
    g = _range_graph(51)
    mgr = AnalysisManager(g, mesh=sharded.make_mesh(4, 2))
    windows = (1000, 300)
    first = _spans(mgr.submit(_pagerank(), RangeQuery(
        start=300, end=600, jump=100, windows=windows)))
    assert {s["args"]["seed"] for s in _unit_seeds(first)} \
        == {"start"}                        # nothing cached yet
    q = RangeQuery(start=600, end=900, jump=100, windows=windows)
    job = mgr.submit(_pagerank(), q)
    spans = _spans(job)
    (root,) = _named(spans, "job")
    folds = _named(spans, "hop.fold")
    assert len(folds) == min(workers, 4)    # one dispatch group, sub-split
    assert {f["args"]["mode"] for f in folds} == {"parallel"}
    assert all(f["tid"] != root["tid"] for f in folds)
    assert sum(f["args"]["hops"] for f in folds) == 4
    seeds = _unit_seeds(spans)
    assert len(seeds) == len(folds)
    assert {s["args"]["seed"] for s in seeds} == {"checkpoint"}
    # each unit copies the checkpoint it shares once, when it first folds
    assert len(_named(spans, "fold.seed")) == 2 * len(folds)
    cps = _named(spans, "fold.checkpoint")
    assert cps and all(c["args"]["seed"] == "checkpoint"
                       and c["args"]["seeded_from"] >= 300
                       and c["args"]["stored"] for c in cps)
    # the job thread only waits
    stalls = _named(spans, "fold.stall")
    assert stalls and {s["tid"] for s in stalls} == {root["tid"]}
    assert _named(spans, "comm.exchange")   # and then the mesh served

    ref = _done(AnalysisManager(g).submit(_pagerank(), q))
    assert len(job.results) == len(ref.results) == 4 * len(windows)
    for vrow in ref.results:
        rrow = next(r for r in job.results
                    if r["time"] == vrow["time"]
                    and r["windowsize"] == vrow["windowsize"])
        assert rrow["result"]["sum"] == pytest.approx(
            vrow["result"]["sum"], abs=1e-4)
        ra, rb = dict(rrow["result"]["top10"]), \
            dict(vrow["result"]["top10"])
        assert set(ra) == set(rb)
        for k in ra:
            assert ra[k] == pytest.approx(rb[k], abs=1e-5)


@pytest.mark.parametrize("fold", ["inline", "workers"])
def test_mesh_range_job_phases_partition_its_wall(traced, monkeypatch,
                                                  fold):
    """The ``fold`` phase of a mesh job is what the job thread folded
    inline or waited for the units — never the workers' seconds, which
    would overlap the wall."""
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
    from raphtory_tpu.parallel import sharded

    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1" if fold == "inline" else "4")
    mgr = AnalysisManager(_range_graph(52), mesh=sharded.make_mesh(4, 2))
    _done(mgr.submit(_pagerank(), RangeQuery(
        start=300, end=600, jump=100, windows=(1000, 300))))
    job = mgr.submit(_pagerank(), RangeQuery(
        start=600, end=900, jump=100, windows=(1000, 300)))
    spans = _spans(job)
    led = job.ledger.as_dict()
    ph = led["phase_seconds"]
    assert led["phase_overlap_seconds"] == 0.0
    assert 0.0 < ph["fold"] <= led["wall_seconds"]
    assert led["queue_wait_seconds"] + sum(ph.values()) == pytest.approx(
        led["wall_seconds"], rel=0.01)
    (root,) = _named(spans, "job")
    folds = _named(spans, "hop.fold")
    if fold == "inline":
        assert [f["tid"] for f in folds] == [root["tid"]]
        assert not _named(spans, "fold.stall")
        assert ph["fold"] == pytest.approx(folds[0]["dur"] / 1e6,
                                           rel=0.05, abs=2e-3)
    else:
        assert len(folds) == 4
        assert all(f["tid"] != root["tid"] for f in folds)
        stall = sum(s["dur"] for s in _named(spans, "fold.stall")) / 1e6
        assert ph["fold"] == pytest.approx(stall, abs=2e-3)
        # the units' seconds are cost; side by side they may exceed it
        assert ph["fold"] <= sum(f["dur"] for f in folds) / 1e6 + 2e-3


@pytest.mark.parametrize("directed", [False, True])
def test_mesh_weighted_sssp_range_job_still_folds_serially(
        traced, monkeypatch, directed):
    """Weighted SSSP threads a weight cursor through its fold
    (``supports_parallel_fold = False``): on the mesh route it keeps the
    inline fold on the job thread whatever the pool's size, and answers
    as the one-chip route does."""
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.engine import hopbatch
    from raphtory_tpu.jobs import registry
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
    from raphtory_tpu.parallel import sharded

    monkeypatch.setenv("RTPU_FOLD_WORKERS", "4")
    rng = np.random.default_rng(53)
    n = 700
    log = EventLog()
    log.append_batch(
        np.sort(rng.integers(0, 100, n)), np.full(n, 2, np.uint8),
        rng.integers(0, 40, n).astype(np.int64),
        rng.integers(0, 40, n).astype(np.int64),
        props=[(i, {"weight": float(rng.uniform(0.5, 3.0))})
               for i in range(n)])
    g = TemporalGraph(log)

    def sssp():
        return registry.resolve("SSSP", {
            "seeds": [0, 1, 2], "weight_prop": "weight",
            "directed": directed, "max_steps": 50})

    q = RangeQuery(start=40, end=99, jump=20, windows=(1000, 30))
    ref = _done(AnalysisManager(g).submit(sssp(), q))

    def boom(*a, **k):
        raise AssertionError("a weighted-SSSP fold went to the pool")

    monkeypatch.setattr(hopbatch._HopBatched, "_fold_groups_parallel", boom)
    job = AnalysisManager(g, mesh=sharded.make_mesh(4, 2)).submit(sssp(), q)
    spans = _spans(job)
    (root,) = _named(spans, "job")
    (fold,) = _named(spans, "hop.fold")
    assert fold["tid"] == root["tid"] and fold["args"]["hops"] == 3
    assert fold["args"]["engine"] == "HopBatchedSSSP"
    # no unit was seeded: the one ``fold.seed`` is the engine's builder
    # taking its own copy of the index's fold state before its first write
    assert not _unit_seeds(spans)
    (own,) = _named(spans, "fold.seed")
    assert own["args"]["deferred"] is True and own["tid"] == root["tid"]
    assert _named(spans, "comm.exchange")
    assert len(job.results) == len(ref.results) == 3 * 2
    for vrow in ref.results:
        rrow = next(r for r in job.results
                    if r["time"] == vrow["time"]
                    and r["windowsize"] == vrow["windowsize"])
        assert rrow["result"] == vrow["result"]


# ------------------------------------- the program is built once a key


KINDS = ["pagerank", "cc", "bfs", "sssp"]     # sssp = weighted bfs
BUILD_EVENTS = ("xla.trace", "xla.lower", "xla.backend_compile")


def _kind_case(kind, max_steps=12, damping=0.85):
    """``(args, kw, one, steps)``: the positional arguments and keywords
    of a ``run_columns_sharded`` call of that kind over five hops and two
    windows, and what the one-device ``hopbatch`` runner of the same
    statics gives for it."""
    from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                              HopBatchedSSSP)

    rng = np.random.default_rng(11)
    hops, windows, seeds = [20, 40, 60, 80, 99], [1000, 30], (0, 1, 2)
    if kind == "sssp":
        log = _weighted_log(rng)
    else:
        log = random_log(rng, n_events=900, n_ids=50, t_span=100)
    if kind == "pagerank":
        make = lambda: HopBatchedPageRank(log, damping=damping, tol=1e-7,
                                          max_steps=max_steps)
        kw = dict(kind="pagerank", tol=1e-7)
    elif kind == "cc":
        make = lambda: HopBatchedCC(log, max_steps=max_steps)
        kw = dict(kind="cc")
    elif kind == "bfs":
        make = lambda: HopBatchedBFS(log, seeds, max_steps=max_steps)
        kw = dict(kind="bfs", seeds=seeds)
    else:
        make = lambda: HopBatchedSSSP(log, seeds, "weight",
                                      max_steps=max_steps)
        kw = dict(kind="bfs", seeds=seeds)
    kw.update(max_steps=max_steps, damping=damping)
    one, steps = make().run(hops, windows)
    hb = make()
    _, cols = hb._fold_columns(hops)
    if kind == "sssp":
        *cols, kw["weight_cols"] = cols
    return (hb.tables, *cols, hops, windows), kw, np.asarray(one), int(steps)


def _same_values(kind, one, many):
    if kind == "pagerank":
        # another partition of the same f32 sums (see the first test)
        np.testing.assert_allclose(one, np.asarray(many), rtol=1e-5,
                                   atol=1e-7)
    else:
        np.testing.assert_array_equal(one, np.asarray(many))


def _exchange_builds(root):
    """The program-build events inside the one ``comm.exchange`` of the
    trace under ``root``."""
    from raphtory_tpu.obs.trace import TRACER

    spans = [e for e in TRACER.for_trace(root.trace) if e["ph"] == "X"]
    (xchg,) = _named(spans, "comm.exchange")
    return [e for e in _named(spans, *BUILD_EVENTS)
            if xchg["ts"] <= e["ts"] <= xchg["ts"] + xchg["dur"]]


@pytest.mark.parametrize("kind", KINDS)
def test_column_program_is_built_by_a_keys_first_call_only(traced, kind):
    from raphtory_tpu.obs.trace import TRACER

    args, kw, one, steps1 = _kind_case(kind)
    devices = jax.devices()[:4]
    _compiled_columns.cache_clear()
    with TRACER.span("job") as first:
        a, steps_a = run_columns_sharded(*args, devices, **kw)
    assert {e["name"] for e in _exchange_builds(first)} \
        == set(BUILD_EVENTS)
    info = _compiled_columns.cache_info()
    assert (info.misses, info.hits, info.currsize) == (1, 0, 1)

    with TRACER.span("job") as second:
        b, steps_b = run_columns_sharded(*args, devices, **kw)
    assert _exchange_builds(second) == []
    info = _compiled_columns.cache_info()
    assert (info.misses, info.hits, info.currsize) == (1, 1, 1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # bitwise
    assert steps_a == steps_b == steps1
    _same_values(kind, one, a)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("static", ["max_steps", "damping", "devices",
                                    "tile_budget"])
def test_column_program_of_another_static_is_another_key(monkeypatch,
                                                         static, kind):
    """Every value the traced block reads is in the factory's key: a
    request that differs in one of them builds its own program (a miss),
    and its values are the one-device runner's of the same statics."""
    args, kw, _, _ = _kind_case(kind)
    devices = jax.devices()[:4]
    _compiled_columns.cache_clear()
    run_columns_sharded(*args, devices, **kw)
    if static == "max_steps":
        args, kw, one, steps1 = _kind_case(kind, max_steps=3)
    elif static == "damping":
        args, kw, one, steps1 = _kind_case(kind, damping=0.5)
    else:
        args, kw, one, steps1 = _kind_case(kind)
        if static == "devices":
            devices = jax.devices()[4:8]
        else:
            # nothing tiles at this size: the budget keys all the same
            monkeypatch.setenv("RTPU_TILE_BUDGET_MB", "1")
    many, steps2 = run_columns_sharded(*args, devices, **kw)
    info = _compiled_columns.cache_info()
    assert (info.misses, info.hits, info.currsize) == (2, 0, 2)
    _same_values(kind, one, many)
    assert steps2 == steps1


def test_statusz_counts_one_build_and_one_hit_a_mesh_request(traced):
    """``/statusz`` ``compile_caches`` lists the factory beside the
    one-chip engines': the first served mesh Range of a key is its one
    miss, every request after it a hit with no build in its trace."""
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
    from raphtory_tpu.jobs.rest import _compile_cache_sizes
    from raphtory_tpu.parallel import sharded

    _compiled_columns.cache_clear()
    mgr = AnalysisManager(_range_graph(53), mesh=sharded.make_mesh(
        4, 1, devices=jax.devices()[:4]))
    for n, start in enumerate((300, 400, 500)):
        spans = _spans(mgr.submit(_pagerank(), RangeQuery(
            start=start, end=start + 300, jump=100, windows=(1000, 300))))
        assert _named(spans, "comm.exchange")
        assert bool(_named(spans, *BUILD_EVENTS)) == (n == 0)
        assert _compile_cache_sizes()["columns._compiled_columns"] == {
            "size": 1, "misses": 1, "hits": n}
