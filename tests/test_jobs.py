"""Job layer: View/Range/Live queries, window matrix, REST API over real HTTP."""

import json
import time
import urllib.request

import numpy as np
import pytest

from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.ingestion.pipeline import IngestionPipeline
from raphtory_tpu.ingestion.source import IterableSource
from raphtory_tpu.ingestion.updates import EdgeAdd
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import (
    AnalysisManager,
    LiveQuery,
    RangeQuery,
    ViewQuery,
)
from raphtory_tpu.jobs.rest import RestServer


def _graph(n=200):
    pipe = IngestionPipeline()
    rng = np.random.default_rng(0)
    updates = [
        EdgeAdd(int(t), int(a), int(b))
        for t, a, b in zip(
            np.sort(rng.integers(0, 100, n)),
            rng.integers(0, 30, n),
            rng.integers(0, 30, n),
        )
    ]
    pipe.add_source(IterableSource(updates, name="test"))
    pipe.run()
    return TemporalGraph(pipe.log, pipe.watermarks)


def test_view_job():
    g = _graph()
    mgr = AnalysisManager(g)
    job = mgr.submit(registry.resolve("ConnectedComponents"), ViewQuery(90))
    assert job.wait(30)
    assert job.status == "done"
    assert len(job.results) == 1
    row = job.results[0]
    assert row["time"] == 90
    assert row["result"]["vertices"] > 0
    assert "viewTime" in row


def test_range_job_with_single_window():
    g = _graph()
    mgr = AnalysisManager(g)
    q = RangeQuery(start=20, end=90, jump=35, window=50)
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(60)
    assert job.status == "done"
    assert [r["time"] for r in job.results] == [20, 55, 90]
    assert all(r["windowsize"] == 50 for r in job.results)


def test_range_job_batched_windows():
    g = _graph()
    mgr = AnalysisManager(g)
    q = RangeQuery(start=50, end=90, jump=40, windows=(100, 20, 5))
    job = mgr.submit(registry.resolve("PageRank", {"max_steps": 10}), q)
    assert job.wait(60)
    assert job.status == "done", job.error
    # 2 hops x 3 windows
    assert len(job.results) == 6
    assert {r["windowsize"] for r in job.results} == {100, 20, 5}
    for r in job.results:
        assert np.isfinite(r["result"]["sum"])


def test_live_job_event_time_advance():
    g = _graph()
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=30, event_time=True, max_runs=3)
    job = mgr.submit(registry.resolve("DegreeBasic"), q)
    assert job.wait(30)
    assert job.status == "done", job.error
    assert len(job.results) == 3
    times = [r["time"] for r in job.results]
    assert times[1] - times[0] == 30


def test_live_job_kill():
    g = _graph()
    mgr = AnalysisManager(g)
    job = mgr.submit(registry.resolve("DegreeBasic"), LiveQuery(repeat=0.05))
    time.sleep(0.3)
    mgr.kill(job.id)
    assert job.wait(10)
    assert job.status == "killed"
    assert len(job.results) >= 1


def test_failed_job_surfaces_error():
    """A job blocked by the watermark fence fails with StaleViewError in
    job.error (per-phase error surfacing, like the reference's catches)."""
    from raphtory_tpu.ingestion.watermark import WatermarkRegistry

    wm = WatermarkRegistry()
    wm.register("slow-source")  # live source that never advances
    g = TemporalGraph(watermarks=wm)
    g.log.add_edge(1, 1, 2)
    mgr = AnalysisManager(g)
    job = mgr.submit(registry.resolve("DegreeBasic"), ViewQuery(100),
                     wait_timeout=0.1)
    assert job.wait(30)
    assert job.status == "failed"
    assert "StaleViewError" in job.error


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return json.loads(r.read())


@pytest.fixture()
def server():
    g = _graph()
    mgr = AnalysisManager(g)
    srv = RestServer(mgr, port=0).start()
    yield srv
    srv.stop()


def test_rest_view_roundtrip(server):
    out = _post(server.port, "/ViewAnalysisRequest",
                {"analyserName": "ConnectedComponents", "timestamp": 90})
    jid = out["jobID"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        res = _get(server.port, f"/AnalysisResults?jobID={jid}")
        if res["status"] in ("done", "failed"):
            break
        time.sleep(0.05)
    assert res["status"] == "done", res
    assert res["results"][0]["result"]["vertices"] > 0


def test_rest_range_windowed_and_kill(server):
    out = _post(server.port, "/RangeAnalysisRequest", {
        "analyserName": "PageRank", "params": {"max_steps": 5},
        "start": 10, "end": 90, "jump": 20,
        "windowType": "batched", "windowSet": [100, 10],
    })
    jid = out["jobID"]
    _get(server.port, f"/KillTask?jobID={jid}")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        res = _get(server.port, f"/AnalysisResults?jobID={jid}")
        if res["status"] in ("done", "killed", "failed"):
            break
        time.sleep(0.05)
    assert res["status"] in ("done", "killed")


def test_rest_errors(server):
    # unknown analyser -> 400
    try:
        _post(server.port, "/ViewAnalysisRequest",
              {"analyserName": "Nope", "timestamp": 5})
        raise AssertionError("expected HTTPError")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "unknown analyser" in json.loads(e.read())["error"]
    # unknown job -> 404
    try:
        _get(server.port, "/AnalysisResults?jobID=zzz")
        raise AssertionError("expected HTTPError")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_rest_dynamic_analyser(server):
    src = (
        "from dataclasses import dataclass\n"
        "from raphtory_tpu.algorithms import PageRank\n"
        "program = PageRank(max_steps=3)\n"
    )
    out = _post(server.port, "/ViewAnalysisRequest",
                {"rawFile": src, "timestamp": 90})
    jid = out["jobID"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        res = _get(server.port, f"/AnalysisResults?jobID={jid}")
        if res["status"] in ("done", "failed"):
            break
        time.sleep(0.05)
    assert res["status"] == "done", res


def test_registry_lists_builtins():
    ns = registry.names()
    assert {"ConnectedComponents", "PageRank", "DegreeBasic"} <= set(ns)


def test_single_device_range_uses_device_sweep_and_matches():
    """Without a mesh, qualifying Range queries run on the device-resident
    sweep; results must match the per-view path exactly (per-vid)."""
    import numpy as np

    from raphtory_tpu.algorithms import ConnectedComponents
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.engine import bsp
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery

    rng = np.random.default_rng(12)
    from test_sweep import random_log

    log = random_log(rng, n_events=400, n_ids=30, t_span=60)
    g = TemporalGraph(log)
    mgr = AnalysisManager(g)          # no mesh
    cc = ConnectedComponents(max_steps=40)
    job = mgr.submit(cc, RangeQuery(start=20, end=60, jump=20, window=30))
    assert job.wait(120), job.error
    assert job.status == "done", job.error
    assert len(job.results) == 3
    for row in job.results:
        view = g.view_at(row["time"], exact=False)
        want, _ = bsp.run(cc, view, window=30)
        expect = cc.reduce(want, view, window=30)
        assert row["result"]["vertices"] == expect["vertices"], row["time"]
        assert row["result"]["clusters"] == expect["clusters"], row["time"]
        assert row["result"]["top5"] == expect["top5"], row["time"]


def test_module_entrypoint_serves_rest(tmp_path):
    """python -m raphtory_tpu serve: boots the node, ingests a CSV, serves
    the REST job API, shuts down on SIGTERM."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time as _t
    import urllib.request

    csv = tmp_path / "edges.csv"
    csv.write_text("".join(f"{i % 9},{(i + 1) % 9},{i}\n" for i in range(300)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["RAPHTORY_TPU_REST_PORT"] = "18231"
    env["RAPHTORY_TPU_METRICS_PORT"] = "18232"
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    p = subprocess.Popen(
        [sys.executable, "-m", "raphtory_tpu", "serve", "--csv", str(csv),
         "--platform", "cpu"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = _t.monotonic() + 120
        up = False
        while _t.monotonic() < deadline:
            try:
                req = urllib.request.Request(
                    "http://127.0.0.1:18231/ViewAnalysisRequest",
                    data=json.dumps({
                        "analyserName": "ConnectedComponents",
                        "jobID": "boot", "timestamp": 299}).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=5)
                up = True
                break
            except OSError:
                _t.sleep(0.3)
        assert up, "server never came up"
        deadline = _t.monotonic() + 60
        while _t.monotonic() < deadline:
            rows = json.loads(urllib.request.urlopen(
                "http://127.0.0.1:18231/AnalysisResults?jobID=boot",
                timeout=5).read())
            if rows["status"] == "done":
                break
            _t.sleep(0.2)
        assert rows["status"] == "done", rows
        assert rows["results"][0]["result"]["vertices"] == 9
        # metrics endpoint answers too
        body = urllib.request.urlopen(
            "http://127.0.0.1:18232/metrics", timeout=5).read().decode()
        assert "rtpu_" in body or "updates" in body, body[:200]
    finally:
        p.send_signal(signal.SIGTERM)
        try:
            out, _ = p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
    assert p.returncode == 0, out[-2000:]


def test_range_query_rejects_nonpositive_jump():
    with pytest.raises(ValueError, match="jump"):
        RangeQuery(start=0, end=10, jump=0)
    with pytest.raises(ValueError, match="jump"):
        RangeQuery(start=0, end=10, jump=-5)

def _assert_range_rows_match_view_jobs(job, make_program, mgr, approx=None):
    """Every Range row must agree with an independently-computed per-view
    job at the same (time, windowsize)."""
    for t in (20, 60, 90):
        vjob = mgr.submit(make_program(), ViewQuery(t, windows=(100, 25)))
        assert vjob.wait(30)
        for vrow in vjob.results:
            rrow = next(r for r in job.results
                        if r["time"] == t
                        and r["windowsize"] == vrow["windowsize"])
            if approx is None:
                assert rrow["result"] == vrow["result"], \
                    (t, vrow["windowsize"])
            else:
                approx(rrow["result"], vrow["result"])


_HOPBATCH_CASES = [
    ("HopBatchedPageRank",
     lambda: registry.resolve("PageRank", {"max_steps": 200, "tol": 1e-9})),
    ("HopBatchedCC",
     lambda: registry.resolve("ConnectedComponents", {"max_steps": 60})),
    ("HopBatchedBFS",
     lambda: registry.resolve(
         "BFS", {"seeds": (0, 1), "directed": False, "max_steps": 50})),
]


@pytest.mark.parametrize("hb_name,make_program", _HOPBATCH_CASES,
                         ids=[c[0] for c in _HOPBATCH_CASES])
def test_range_jobs_ride_hopbatch_and_match_view_jobs(
        monkeypatch, hb_name, make_program):
    from raphtory_tpu.engine import hopbatch

    calls = []
    orig = getattr(hopbatch, hb_name).run

    def spy(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(getattr(hopbatch, hb_name), "run", spy)
    g = _graph()
    mgr = AnalysisManager(g)
    q = RangeQuery(start=20, end=90, jump=10, windows=(100, 25))
    job = mgr.submit(make_program(), q)
    assert job.wait(60)
    assert job.status == "done", job.error
    assert calls, f"{hb_name} route was not taken"
    assert len(job.results) == 8 * 2   # every (hop, window) row emitted

    def approx_pr(a, b):
        assert a["sum"] == pytest.approx(b["sum"], abs=1e-4)
        ra, rb = dict(a["top10"]), dict(b["top10"])
        assert set(ra) == set(rb)
        for k in ra:
            assert ra[k] == pytest.approx(rb[k], abs=1e-5)

    _assert_range_rows_match_view_jobs(
        job, make_program, mgr,
        approx=approx_pr if hb_name == "HopBatchedPageRank" else None)


def test_range_bfs_on_device_sweep_matches_view_jobs(monkeypatch):
    """reduce_shell_safe on SSSP also unlocks the device-resident range
    path (hopbatch declined here) — pin its semantics too."""
    from raphtory_tpu.jobs import manager as _mgr_mod

    monkeypatch.setattr(_mgr_mod.Job, "_try_range_hopbatch",
                        lambda self, q: False)
    taken = []
    orig = _mgr_mod.Job._try_range_device

    def spy(self, q):
        r = orig(self, q)
        taken.append(r)
        return r

    monkeypatch.setattr(_mgr_mod.Job, "_try_range_device", spy)

    def bfs():
        return registry.resolve(
            "BFS", {"seeds": (0, 1), "directed": False, "max_steps": 50})

    g = _graph()
    mgr = AnalysisManager(g)
    q = RangeQuery(start=20, end=90, jump=10, windows=(100, 25))
    job = mgr.submit(bfs(), q)
    assert job.wait(120)
    assert job.status == "done", job.error
    assert taken == [True], "device-resident route was not taken"
    _assert_range_rows_match_view_jobs(job, bfs, mgr)


def test_range_weighted_sssp_rides_hopbatch_and_matches_view_jobs(
        monkeypatch):
    from raphtory_tpu.engine import hopbatch

    calls = []
    orig = hopbatch.HopBatchedSSSP.run

    def spy(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(hopbatch.HopBatchedSSSP, "run", spy)
    pipe = IngestionPipeline()
    rng = np.random.default_rng(5)
    updates = [
        EdgeAdd(int(t), int(a), int(b),
                props={"weight": float(rng.uniform(0.5, 3.0))})
        for t, a, b in zip(np.sort(rng.integers(0, 100, 300)),
                           rng.integers(0, 30, 300),
                           rng.integers(0, 30, 300))
    ]
    pipe.add_source(IterableSource(updates, name="w"))
    pipe.run()
    g = TemporalGraph(pipe.log, pipe.watermarks)
    mgr = AnalysisManager(g)

    def sssp():
        return registry.resolve(
            "SSSP", {"seeds": (0, 1), "weight_prop": "weight",
                     "directed": False, "max_steps": 60})

    q = RangeQuery(start=20, end=90, jump=10, windows=(100, 25))
    job = mgr.submit(sssp(), q)
    assert job.wait(60)
    assert job.status == "done", job.error
    assert calls, "hopbatch weighted-SSSP route was not taken"
    assert len(job.results) == 8 * 2
    _assert_range_rows_match_view_jobs(job, sssp, mgr)


def test_declinable_is_transport_or_oom_only():
    from raphtory_tpu.jobs.manager import declinable
    from raphtory_tpu.resilience.faults import FaultError

    class XlaRuntimeError(RuntimeError):
        pass

    assert declinable(XlaRuntimeError("UNAVAILABLE: connection lost"))
    assert declinable(FaultError("UNAVAILABLE: injected fault"))
    assert declinable(MemoryError())
    assert declinable(XlaRuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert not declinable(XlaRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel"))
    assert not declinable(TypeError("a bug"))


def _small_graph():
    from raphtory_tpu.core.events import EventLog

    log = EventLog()
    rng = np.random.default_rng(7)
    for t in range(1, 80):
        a, b = (int(x) for x in rng.integers(0, 12, 2))
        log.add_edge(t, a, b)
    return TemporalGraph(log)


@pytest.mark.parametrize("route", ["range_columnar", "live_epoch",
                                   "range_mesh_columns"])
@pytest.mark.parametrize("error, status", [
    ("INTERNAL: compiler refused the program", "failed"),
    ("UNAVAILABLE: connection lost", "done")])
def test_device_error_fails_the_job_transport_error_declines(
        monkeypatch, route, error, status):
    """A columnar Range, a Live epoch or a column-sharded mesh Range that
    hits a device error ends ``failed`` with that error; a transport
    error still declines to the next rung (the mesh: vertex sharding)
    and the job ends ``done``."""
    from raphtory_tpu.engine.hopbatch import HopBatchedCC
    from raphtory_tpu.parallel import columns, sharded

    class XlaRuntimeError(RuntimeError):
        pass

    def boom(*a, **k):
        raise XlaRuntimeError(error)

    mesh = None
    if route == "range_mesh_columns":
        monkeypatch.setattr(columns, "run_columns_sharded", boom)
        mesh = sharded.make_mesh(4, 2)
    else:
        monkeypatch.setattr(HopBatchedCC, "run", boom)
    mgr = AnalysisManager(_small_graph(), mesh=mesh)
    q = (LiveQuery(repeat=20, event_time=True, max_runs=2)
         if route == "live_epoch" else RangeQuery(20, 60, 20))
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(120)
    assert job.status == status, job.error
    if status == "failed":
        assert error in job.error and not job.results
    else:
        assert len(job.results) == (2 if route == "live_epoch" else 3)
