"""Warm View path: repeat View/Live dispatches ride a shared resident
DeviceSweep (delta-advance + one dispatch) and agree with the cold path
(ref: ReaderWorker.scala:293-352 builds a lens per job — the bar)."""

import pytest

from raphtory_tpu.jobs import manager as mgr_mod
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import AnalysisManager, LiveQuery, ViewQuery


def _graph(n=300):
    from test_jobs import _graph as g

    return g(n)


@pytest.fixture
def spy(monkeypatch):
    taken = []
    orig = mgr_mod.Job._try_view_resident

    def wrapper(self, t, q):
        r = orig(self, t, q)
        taken.append(r)
        return r

    monkeypatch.setattr(mgr_mod.Job, "_try_view_resident", wrapper)
    return taken


def test_view_jobs_share_resident_sweep_and_match_cold(spy):
    g = _graph()
    mgr = AnalysisManager(g)

    def pr():
        return registry.resolve("PageRank", {"max_steps": 50, "tol": 1e-9})

    # ascending timestamps: all should ride the resident sweep
    warm = {}
    for t in (30, 60, 90):
        job = mgr.submit(pr(), ViewQuery(t, windows=(100, 25)))
        assert job.wait(60) and job.status == "done", job.error
        warm[t] = job.results
    assert spy.count(True) == 3
    assert g._resident is not None
    sweep_obj = g._resident

    # same timestamps again: same sweep object, no rebuild
    job = mgr.submit(pr(), ViewQuery(90, windows=(100, 25)))
    assert job.wait(60) and job.status == "done", job.error
    assert g._resident is sweep_obj

    # cold-path reference rows (force the resident route off)
    saved = mgr_mod.Job._try_view_resident
    mgr_mod.Job._try_view_resident = lambda self, t, q: False
    try:
        for t in (30, 90):
            cold = mgr.submit(pr(), ViewQuery(t, windows=(100, 25)))
            assert cold.wait(60) and cold.status == "done", cold.error
            for crow, wrow in zip(cold.results, warm[t]):
                assert crow["windowsize"] == wrow["windowsize"]
                assert crow["result"]["sum"] == pytest.approx(
                    wrow["result"]["sum"], abs=1e-4)
                ca, wa = dict(crow["result"]["top10"]), \
                    dict(wrow["result"]["top10"])
                assert set(ca) == set(wa)
                for k in ca:
                    assert ca[k] == pytest.approx(wa[k], abs=1e-5)
    finally:
        mgr_mod.Job._try_view_resident = saved


def test_descending_view_falls_back_cold(spy):
    g = _graph()
    mgr = AnalysisManager(g)
    p = registry.resolve("DegreeBasic")
    j1 = mgr.submit(p, ViewQuery(90))
    assert j1.wait(60) and j1.status == "done", j1.error
    # t=30 < sweep clock (90): resident declines, cold path serves
    j2 = mgr.submit(registry.resolve("DegreeBasic"), ViewQuery(30))
    assert j2.wait(60) and j2.status == "done", j2.error
    assert spy == [True, False]
    assert len(j2.results) == 1


def test_occurrence_program_uses_cold_path(spy):
    g = _graph()
    mgr = AnalysisManager(g)
    seeds = (int(g.log.column("src")[0]),)
    p = registry.resolve("TaintTracking",
                        {"seeds": seeds, "start_time": 0, "max_steps": 5})
    job = mgr.submit(p, ViewQuery(90))
    assert job.wait(60) and job.status == "done", job.error
    assert spy == [False]


def test_live_job_rides_resident(spy):
    g = _graph()
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=10, event_time=True, max_runs=3)
    job = mgr.submit(registry.resolve("DegreeBasic"), q)
    assert job.wait(30) and job.status == "done", job.error
    assert len(job.results) == 3
    assert spy.count(True) >= 2   # monotone targets reuse the sweep


def test_small_time_acquire_does_not_mask_staleness(spy):
    """An acquire BELOW the post-pin min syncs the version without
    re-pinning; a later acquire ABOVE it must still re-pin (the staleness
    check runs on every acquire, not only on version change)."""
    g = _graph()
    mgr = AnalysisManager(g)
    p = lambda: registry.resolve("DegreeBasic")  # noqa: E731
    j0 = mgr.submit(p(), ViewQuery(90))
    assert j0.wait(60) and j0.status == "done", j0.error
    pinned = g._resident

    g.log.add_edge(95, 998, 999)
    # small-time acquire: t=90 < 95 → legally reuses the old pin (and
    # syncs _resident_version along the way)
    j1 = mgr.submit(p(), ViewQuery(90))
    assert j1.wait(60) and j1.status == "done", j1.error
    assert g._resident is pinned
    # large-time acquire: must NOT serve the stale pin
    j2 = mgr.submit(p(), ViewQuery(96))
    assert j2.wait(60) and j2.status == "done", j2.error
    assert g._resident is not pinned
    assert j2.results[0]["result"]["vertices"] == \
        j1.results[0]["result"]["vertices"] + 2


def test_failed_resident_dispatch_discards_sweep(spy, monkeypatch):
    """A transport failure mid-dispatch drops the resident sweep
    (partially applied deltas must never be reused) and the job still
    completes on the cold path."""
    from raphtory_tpu.engine.device_sweep import DeviceSweep

    g = _graph()
    mgr = AnalysisManager(g)
    j0 = mgr.submit(registry.resolve("DegreeBasic"), ViewQuery(50))
    assert j0.wait(60) and j0.status == "done", j0.error
    assert g._resident is not None

    def boom(self, *a, **k):
        raise RuntimeError("UNAVAILABLE: injected device loss")

    monkeypatch.setattr(DeviceSweep, "run", boom)
    j1 = mgr.submit(registry.resolve("DegreeBasic"), ViewQuery(60))
    assert j1.wait(60) and j1.status == "done", j1.error   # cold path served
    assert g._resident is None                              # discarded
    monkeypatch.undo()
    j2 = mgr.submit(registry.resolve("DegreeBasic"), ViewQuery(70))
    assert j2.wait(60) and j2.status == "done", j2.error
    assert g._resident is not None                          # re-pinned fresh


def test_device_error_on_resident_route_fails_the_job(spy, monkeypatch):
    """An error that is neither transport nor OOM — a program the
    compiler refuses, a runtime INTERNAL — must fail the job with the
    error, not decline to the cold path and end ``done``."""
    from raphtory_tpu.engine.device_sweep import DeviceSweep

    g = _graph()
    mgr = AnalysisManager(g)

    def boom(self, *a, **k):
        raise RuntimeError("INTERNAL: compiler refused the program")

    monkeypatch.setattr(DeviceSweep, "run", boom)
    j = mgr.submit(registry.resolve("DegreeBasic"), ViewQuery(60))
    assert j.wait(60) and j.status == "failed"
    assert "compiler refused" in j.error
    assert g._resident is None      # inconsistent state still discarded


def test_ingestion_after_pin_invalidates(spy):
    """Events appended after the pin (past what was safe) force a re-pin,
    so the resident path never serves a stale fold."""
    g = _graph()
    mgr = AnalysisManager(g)
    p = lambda: registry.resolve("DegreeBasic")  # noqa: E731
    j1 = mgr.submit(p(), ViewQuery(50))
    assert j1.wait(60) and j1.status == "done", j1.error
    first_sweep = g._resident

    g.log.add_edge(95, 998, 999)   # new event beyond the old pin
    j2 = mgr.submit(p(), ViewQuery(95))
    assert j2.wait(60) and j2.status == "done", j2.error
    assert spy == [True, True]
    assert g._resident is not first_sweep   # re-pinned

    # the re-pinned fold sees the post-pin event: matches a cold view at 95
    saved = mgr_mod.Job._try_view_resident
    mgr_mod.Job._try_view_resident = lambda self, t, q: False
    try:
        cold = mgr.submit(p(), ViewQuery(95))
        assert cold.wait(60) and cold.status == "done", cold.error
        assert j2.results[0]["result"] == cold.results[0]["result"]
    finally:
        mgr_mod.Job._try_view_resident = saved
