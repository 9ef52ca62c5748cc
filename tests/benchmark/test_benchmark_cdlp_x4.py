"""``graph500_cdlp_x4``: graph500_cdlp's query on the pod layout. The
configuration is ``graph500_cdlp.json`` but for its layout and what
describes it, the traffic ``range_communities.json`` but for the mesh
route it pins, each ``mesh_cdlp.*`` metric reduces a hand-made record to
the number its definition says and reports nothing where the program
wrote no such span or counter (the parent commit), and BENCHMARK.json
holds the configuration, the cell and the metrics as its last entries.

The cell is on the accepted lists whose metrics the PARENT's program
reports on this route too (the driver runs the cell on the parent with
this benchmark): not on ``mesh_range.block_wait_share``,
``fold_seed_share`` and ``fold_from_start_share``, and ``routes.mesh``
pins no ``comm.block_wait``, all of which only PR 54's program writes
here (PERF.md section 7: a ``benchmark`` PR's, once PR 54 is the
parent)."""

import json
import os
import subprocess
import sys

import pytest

pytest.register_assert_rewrite("benchmark_rules")

import benchmark_rules as rules  # noqa: E402

from benchmark import client, layers, run  # noqa: E402

ROOT = run.ROOT
BENCH = rules.load_bench(ROOT)
CONFIG, CELL = "graph500_cdlp_x4", "graph500_cdlp_x4.range_communities_mesh"
CFG = run.load_json(run.HERE, "configs", "graph500_cdlp_x4.json")
ONE = run.load_json(run.HERE, "configs", "graph500_cdlp.json")
TRAFFIC = run.load_json(run.HERE, "traffic", "range_communities_mesh.json")
ONE_TRAFFIC = run.load_json(run.HERE, "traffic", "range_communities.json")

#: the accepted lists the cell is appended to: the mesh cell's end-to-end
#: metric, the ingest's rate, and every ``mesh_range.*`` metric that reads
#: something the vertex-sharded route wrote before PR 54 too
LISTS = ["mesh_views_per_s", "setup.bulk_ingest_updates_per_s",
         "mesh_range.jobs_other_share", "mesh_range.fold_share",
         "mesh_range.comm_exchange_share",
         "mesh_range.device_idle_share", "mesh_range.peak_hbm_share",
         "mesh_range.build_share", "mesh_range.compute_share",
         "mesh_range.program_builds"]
#: the new per-layer metrics, appended last in this order: (unit, better,
#: source); each lists the cell alone, moves mesh_views_per_s, layer mesh
NEW = {
    "mesh_cdlp.partition_build_share": ("%", "lower", "program_span"),
    "mesh_cdlp.partition_patch_share": ("%", "lower", "program_span"),
    "mesh_cdlp.partition_built_share": ("%", "lower", "program_span"),
    "mesh_cdlp.exchange_bytes_per_view": ("bytes", "lower",
                                          "program_counter"),
    "mesh_cdlp.mode_rows_per_view": ("rows", "lower", "program_counter"),
}
#: silent on this route (PR 51), held to exact lists by
#: ``test_benchmark_stage_metrics.py``, or read from what the parent of
#: PR 54 does not write here: the cell is on none of them
NOT_ON = {"mesh_range.program_build_share", "mesh_range.program_lower_share",
          "mesh_range.fold_advance_share", "mesh_range.fold_payload_share",
          "mesh_range.table_put_share", "mesh_range.block_wait_share",
          "mesh_range.fold_seed_share", "mesh_range.fold_from_start_share"}


def _differs(a: dict, b: dict) -> set:
    return {k for k in set(a) | set(b) if a.get(k) != b.get(k)}


def test_the_configuration_is_graph500_cdlp_on_the_pod_layout():
    assert _differs(CFG, ONE) <= {"name", "source", "deployment", "why",
                                  "chips", "mesh", "graph", "reduced",
                                  "assumed"}
    assert _differs(CFG["graph"], ONE["graph"]) <= {"scale", "note"}
    assert CFG["graph"]["scale"] in (17, 18, 19)
    for same in ("algorithm", "windows", "hop_s", "guarantees", "correct"):
        assert CFG[same] == ONE[same], same
    x4 = run.load_json(run.HERE, "configs", "twitter_wpr_x4.json")
    assert (CFG["chips"], CFG["mesh"]) == (4, x4["mesh"])   # the same boot
    assert sorted(CFG["reduced"]) == ["events", "ids"]
    assert "fits one chip" in CFG["reduced"]["events"]
    assert set(ONE["assumed"]) < set(CFG["assumed"])
    assert len(CFG["source"]) <= 200
    for word in ("Graphalytics v1.0", "CDLP", "Graph500 R-MAT",
                 "BASELINE.json config 5"):
        assert word in CFG["source"], word


def test_the_traffic_is_range_communities_with_the_mesh_route_pinned():
    assert _differs(TRAFFIC, ONE_TRAFFIC) == {"name", "why", "routes"}
    assert TRAFFIC["routes"]["one_chip"] == ONE_TRAFFIC["routes"]["one_chip"]
    mesh = TRAFFIC["routes"]["mesh"]
    assert len(mesh["dcn"]) == 1 and mesh["dcn"][0] in ("halo", "all_gather")
    assert mesh["spans"] == ["comm.exchange"]     # what the parent writes too
    assert "kernels" not in mesh          # no hopbatch kernel serves it
    # the same requests as the one-chip cell, so the rows compare
    assert [client.request_body(CFG, TRAFFIC, k) for k in range(-1, 4)] \
        == [client.request_body({**ONE, "graph": CFG["graph"]},
                                ONE_TRAFFIC, k) for k in range(-1, 4)]
    assert client.schedule_requests(CFG, TRAFFIC) == 108
    assert client.rows_expected(CFG, TRAFFIC) == 6


def test_the_cell_is_in_the_benchmark_by_appends_and_passes_every_rule():
    rules.every_rule(BENCH, ROOT)
    (config,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert config == BENCH["configs"][-1]
    assert (config["source"], config["file"], config["reduced"]) == (
        CFG["source"], f"benchmark/configs/{CONFIG}.json",
        sorted(CFG["reduced"]))
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == BENCH["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC["name"], 4) and len(cell["why"]) <= 200
    new = BENCH["per_layer"][-len(NEW):]
    assert [m["name"] for m in new] == list(NEW)
    for m in new:
        assert (m["unit"], m["better"], m["source"]) == NEW[m["name"]]
        assert (m["layer"], m["moves"], m["workloads"]) \
            == ("mesh", "mesh_views_per_s", [CELL])
    for m in BENCH["end_to_end"] + BENCH["per_layer"][:-len(NEW)]:
        on = m.get("workloads", [])
        assert (CELL in on) == (m["name"] in LISTS), m["name"]
        assert CELL not in on[:-1], m["name"]       # appended, so last
    loaded = rules.load_cell(ROOT, CELL)
    assert {m["name"] for m in loaded["end_to_end"]} \
        == {"mesh_views_per_s", "setup_s"}
    reported = [m["name"] for m in loaded["per_layer"]]
    assert set(reported) == (set(LISTS) | set(NEW)) - {"mesh_views_per_s"}
    assert not NOT_ON & set(reported)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    """The cell's rehearsal on four virtual devices, traced and not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 54), "--seconds", "10",
         "--trace", str(trace), "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is False and "rehearsal" in out
    assert (out["device"]["platform"], out["device"]["count"]) == ("cpu", 4)
    assert out["attempted"] >= 2 and out["failed"] == 0
    phases = [json.loads(ln) for ln in lines[:-1]]
    work = next(ph for ph in phases if ph["phase"] == "work")
    assert work["supersteps"] == [10] and not work["schedule_used_up"]
    assert {c[0] for c in work["comm_exchange"]} <= {"halo", "all_gather"}
    summary = next(ph for ph in phases if ph["phase"] == "check_summary")
    assert summary["ok"] and summary["rows_compared"] >= 3
    assert not summary["route_failures"]        # the pinned span found
    if trace:       # every host-read metric of the cell has a reading
        known = {m["name"] for m in BENCH["per_layer"]
                 if CELL in m.get("workloads", [])}
        silent_on_a_cpu = {n for n in known
                           if "device_idle" in n or "peak_hbm" in n}
        assert set(out["metrics"]) >= known - silent_on_a_cpu
    else:
        assert set(out["metrics"]) == {"mesh_views_per_s", "setup_s"}


def _spec(name):
    unit = NEW[name][0]
    return {**run.load_json(run.HERE, "layer_metrics", name + ".json"),
            "name": name, "unit": unit}


def _span(name, ms, **args):
    return {"name": name, "dur": ms * 1000.0, "args": args}


#: two requests of 2.0 s each: one built the partition, one found it held
RECORD = {
    "work_wall_s": 4.0,
    "spans": [_span("partition.build", 400.0, status="built"),
              _span("partition.build", 0.4, status="held"),
              _span("partition.patch", 30.0, rows=10, seed=True),
              _span("partition.patch", 10.0, rows=4),
              _span("comm.block_wait", 900.0), _span("comm.block_wait", 700.0),
              _span("comm.exchange", 5.0, route="all_gather")],
    "ledgers": [
        {"wall_s": 2.0, "views": 6, "ledger": {
            "dcn": {"bytes": 6000}, "device": {"mode_rows": 1200}}},
        {"wall_s": 2.0, "views": 6, "ledger": {
            "dcn": {"bytes": 3000}, "device": {"mode_rows": 1200}}}],
}
#: the same requests on a program that writes none of it
BARE = {"work_wall_s": 4.0,
        "spans": [_span("comm.exchange", 5.0, route="all_gather")],
        "ledgers": [{"wall_s": 2.0, "views": 6,
                     "ledger": {"device": {}}}] * 2}


@pytest.mark.parametrize("name,want", [
    ("mesh_cdlp.partition_build_share", 100.0 * 0.4004 / 4.0),
    ("mesh_cdlp.partition_patch_share", 100.0 * 0.040 / 4.0),
    ("mesh_cdlp.partition_built_share", 50.0),
    ("mesh_cdlp.exchange_bytes_per_view", 9000 / 12),
    ("mesh_cdlp.mode_rows_per_view", 2400 / 12),
])
def test_each_new_metric_reduces_a_hand_made_record(name, want):
    spec = _spec(name)
    assert spec["what"] and spec["reducer"] in layers.REDUCERS
    assert layers.reduce_metric(spec, RECORD) == pytest.approx(want)
    # nothing to read: no reading, and no exception
    assert layers.reduce_metric(spec, BARE) is None
    assert layers.reduce_metric(spec, {}) is None
