"""How a window ends, and what the harness hears (ISSUE 48): a closed
loop whose traffic file's schedule is used up closes its window and
prints a result; the schedule's length is on the work line; an idle gap
is named by the stage span it sits under; a wrapped recorder ring fails
the run; a subscription with no trace is read without one; and every
bound in BENCHMARK.json is the one PERF.md's section 2 states."""

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import BenchFailure, client, run, xplane
from benchmark.loops import subscription

BENCH = run.load_json(run.ROOT, "BENCHMARK.json")

#: (configuration, traffic) -> requests the schedule holds
SCHEDULES = {
    ("twitter_wpr", "range_windows"): 54,
    ("twitter_wpr", "view_asof"): 217,
    ("graph500_cdlp", "range_communities"): 108,
    ("graph500_lcc", "range_clustering"): 108,
    ("reddit_sgc", "range_propagation"): 108,
}


def _files(config, traffic):
    return (run.load_json(run.HERE, "configs", config + ".json"),
            run.load_json(run.HERE, "traffic", traffic + ".json"))


def test_a_used_up_schedule_is_a_value_error_of_its_own():
    assert issubclass(client.ScheduleUsedUp, ValueError)
    cfg, tr = _files("twitter_wpr", "range_windows")
    with pytest.raises(client.ScheduleUsedUp, match="schedule is used up"):
        client.hop_times(cfg, tr, 54)
    with pytest.raises(client.ScheduleUsedUp):
        client.request_body(cfg, tr, 54)
    assert client.hop_times(cfg, tr, 53)[-1] <= cfg["graph"]["t_span"]


@pytest.mark.parametrize("config,traffic", sorted(SCHEDULES))
def test_schedule_requests_of_the_traffic_files(config, traffic):
    cfg, tr = _files(config, traffic)
    assert client.schedule_requests(cfg, tr) == SCHEDULES[config, traffic]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_schedule_requests_is_where_hop_times_raises(cell):
    loaded = run.load_cell(cell)
    cfg, tr = loaded["config"], loaded["traffic"]
    n = client.schedule_requests(cfg, tr)
    if tr["loop"] != "closed":
        assert n is None        # a subscription has no schedule
        return
    # far more than a window completes today, and exactly the edge
    assert n >= 54
    client.hop_times(cfg, tr, n - 1)
    with pytest.raises(client.ScheduleUsedUp):
        client.hop_times(cfg, tr, n)


# ---------------------------------------------- a window the schedule ends


def _short(loaded, requests):
    """The cell's files with a schedule of about ``requests`` requests:
    ``start_frac`` moved towards the end of the span, nothing else."""
    cfg, tr = loaded["config"], loaded["traffic"]
    hops = requests * int(tr["hops_per_request"])
    span = cfg["graph"]["t_span"]
    tr["start_frac"] = (span - (hops - 0.5) * int(cfg["hop_s"])) / span
    return loaded


def test_a_used_up_schedule_closes_the_window_with_a_result(capsys,
                                                            monkeypatch):
    """Fewer requests than the window has time for: a result, nothing
    failed, every completed request counted, the rate over the time to
    the last completion — not a ValueError and exit 1."""
    cell, seen = "twitter_wpr.range_windows", {}
    small = run.load_json(run.HERE, "rehearsal.json")["config"]
    load_cell, measure = run.load_cell, run.measure

    def short_cell(workload, **kw):
        loaded = load_cell(workload, **kw)
        # sized on the tiny configuration run_cell(tiny=True) runs
        at = {**loaded, "config": run.merge(loaded["config"], small)}
        loaded["traffic"] = _short(at, 5)["traffic"]
        return loaded

    def spy(r, *a):
        seen["run"] = r
        return measure(r, *a)

    monkeypatch.setattr(run, "load_cell", short_cell)
    monkeypatch.setattr(run, "measure", spy)
    args = argparse.Namespace(workload=cell, seed=2**31 + 48, seconds=120.0,
                              trace=0, rehearsal=False)
    assert run.run_cell(args, require_chip=False, tiny=True) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    out, rec = lines[-1], seen["run"].rec
    n = client.schedule_requests(seen["run"].cfg, seen["run"].traffic)
    assert n == 5
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == n == len(rec["requests"])
    assert rec["schedule_used_up"] is True
    assert rec["window_s"] < 100.0          # closed well before --seconds
    work = next(ln for ln in lines if ln["phase"] == "work")
    assert work["schedule_used_up"] is True and work["schedule_requests"] == n
    assert work["requests_completed"] == n and work["views_completed"] == 12 * n
    t_last = max(r["t_done"] for r in rec["requests"])
    assert out["metrics"]["views_per_s"]["value"] == pytest.approx(
        12 * n / (t_last - rec["t_window"]))
    # the numbers compared, each beside its limit, come last in the line
    assert list(out)[-1] == "compared"
    assert out["compared"]["rank_rel_err"]["limit"] == 1e-4
    assert out["compared"]["steps"] == {"value": [20], "limit": 20}


def test_a_window_with_time_left_says_the_schedule_is_not_used_up(capsys):
    args = argparse.Namespace(workload="twitter_wpr.view_asof",
                              seed=2**31 + 49, seconds=2.0, trace=0,
                              rehearsal=False)
    assert run.run_cell(args, require_chip=False, tiny=True) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    work = next(ln for ln in lines if ln["phase"] == "work")
    assert work["schedule_used_up"] is False
    assert work["schedule_requests"] > work["requests_completed"] >= 2


def test_a_traced_run_whose_schedule_ends_stops_its_trace(tmp_path):
    """The command itself, from a copy whose ``view_asof.json`` holds four
    requests: request 0 untraced, three traced, then the schedule ends
    with the profiler still on — it is stopped as at the close, the line
    is printed, and the process's trace directory is gone."""
    root = tmp_path / "checkout"
    shutil.copytree(run.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(run.ROOT, "raphtory_tpu"), root / "raphtory_tpu")
    cell = "twitter_wpr.view_asof"
    loaded = run.load_cell(cell)
    loaded["config"] = run.merge(
        loaded["config"], run.load_json(run.HERE, "rehearsal.json")["config"])
    tr = _short(loaded, 4)["traffic"]
    (root / "benchmark/traffic/view_asof.json").write_text(json.dumps(tr))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark/run.py"), "--workload", cell,
         "--seed", str(2**31 + 50), "--seconds", "120", "--trace", "1",
         "--rehearsal"], cwd=root, env=env, capture_output=True, text=True,
        timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["attempted"] == 4 and lines[-1]["failed"] == 0
    work = next(ln for ln in lines if ln["phase"] == "work")
    assert work["schedule_used_up"] is True and work["schedule_requests"] == 4
    traced = next(ln for ln in lines if ln["phase"] == "trace_not_reduced")
    assert traced["traced_items"] == 3 and traced["trace_bytes"] > 0
    assert not os.path.exists(root / ".bench_trace")
    # the numbers compared are the last lines of standard error
    tail = p.stderr.strip().splitlines()[-7:]
    assert all(re.match(r"compared \S+ .+ limit \S+$", ln) for ln in tail)
    assert {ln.split()[1] for ln in tail} == set(lines[-1]["compared"])


# ------------------------------------------- what the harness hears


STAGES = ("fold.seed", "fold.advance", "fold.payload", "fold.checkpoint",
          "index.ids", "index.pairs", "index.tables", "index.fork",
          "index.lookup", "index.triangles", "comm.put")


def test_an_idle_gap_is_named_by_the_stage_it_sits_under():
    assert set(STAGES) <= set(run.HOST_SPANS)
    assert len(run.HOST_SPANS) == len(set(run.HOST_SPANS))
    host = [("job", 0, 10_000), ("hop.fold", 1_000, 9_000),
            ("fold.advance", 2_000, 5_000), ("engine.build", 9_000, 10_000),
            ("index.fork", 9_200, 9_700), ("not.a.span", 0, 10_000)]
    gaps = xplane.gaps_by_span([(5_000, 6_000)], 0, 10_000, host,
                               set(run.HOST_SPANS))
    assert gaps == pytest.approx({
        "job": 1e-6, "hop.fold": 4e-6, "fold.advance": 3e-6,
        "engine.build": 0.5e-6, "index.fork": 0.5e-6})


def test_a_wrapped_recorder_ring_fails_the_run(monkeypatch):
    get = client.Rest.get

    def wrapped(self, path):
        doc = get(self, path)
        if path == "/statusz":
            doc["trace"]["dropped"] = 7
        return doc

    monkeypatch.setattr(client.Rest, "get", wrapped)
    args = argparse.Namespace(workload="twitter_wpr.view_asof",
                              seed=2**31 + 51, seconds=1.0, trace=0,
                              rehearsal=False)
    with pytest.raises(BenchFailure, match="trace.dropped is 7"):
        run.run_cell(args, require_chip=False, tiny=True)


def test_a_subscription_with_no_trace_is_collected_without_one():
    """With the recorder off (``RTPU_TRACE=0``) the job's document has no
    ``traceID``: no span is asked for, and none is read."""
    def no_spans(trace_id):
        raise AssertionError("asked for the spans of no trace")

    fake = types.SimpleNamespace(
        traffic={"poll_ms": 10}, rec={"epochs": [{"row": {"time": 5}}]},
        rest=types.SimpleNamespace(spans=no_spans))
    loop = subscription.Loop(fake)
    loop.doc = {"status": "killed", "results": [{"time": 5}],
                "ledger": {"phase_seconds": {}}}
    loop.collect()
    assert fake.rec["spans"] == [] and fake.rec["work_wall_s"] == 0.0
    assert fake.rec["epochs"][0]["mode"] is None
    assert fake.rec["ledgers"][0]["wall_s"] == 0.0


# --------------------------------------------------- the bounds, twice


def _bounds_of_perf_md():
    with open(os.path.join(run.ROOT, "PERF.md")) as f:
        text = f.read()
    sec = text.split("\n## 2.", 1)[1].split("\n## 3.", 1)[0]
    out = {}
    for ln in sec.splitlines():
        cols = [c.strip() for c in ln.split("|")]
        m = re.fullmatch(r"`([A-Za-z0-9_.]+)`", cols[1]) \
            if len(cols) > 6 else None
        if m:
            out[m.group(1)] = float(re.match(r"[0-9.]+", cols[5]).group())
    return out


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]])
def test_a_bound_is_the_one_perf_md_states(metric):
    (entry,) = [m for m in BENCH["end_to_end"] if m["name"] == metric]
    assert _bounds_of_perf_md()[metric] == entry["bound"]
