"""BENCHMARK.json against the files it names, and the data-driven rule:
a new configuration, traffic mix or metric is a new file and no code."""

import json
import os
import re
import shutil

import pytest

from benchmark import algorithms, layers, loops, run

ROOT = run.ROOT
BENCH = run.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_lengths_and_files():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names)), kind
        assert all(NAME.match(n) for n in names), kind
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        doc = run.load_json(ROOT, c["file"])
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert doc["guarantees"] and doc["correct"]["limits"]
    for w in BENCH["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(BENCH["workloads"]) // 2)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_what_it_must(cell):
    loaded = run.load_cell(cell)
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded["per_layer"], "a cell reports at least one layer metric"
    for spec in loaded["end_to_end"] + loaded["per_layer"]:
        assert spec["reducer"] in layers.REDUCERS, spec["name"]
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    for spec in loaded["per_layer"]:
        assert moves[spec["name"]] in e2e, (
            f"{spec['name']} moves {moves[spec['name']]}, which {cell} "
            "does not report")
    routes = loaded["traffic"]["routes"]
    assert routes["one_chip"]
    # the algorithm's reference and the loop's driver are found by name
    algo = algorithms.load(loaded["config"]["algorithm"]["module"])
    assert all(callable(getattr(algo, f)) for f in (
        "reference", "control", "stated", "compare", "least_bytes"))
    loop = loops.load(loaded["traffic"]["loop"])
    assert all(callable(getattr(loop, f)) for f in (
        "boot", "warm", "window", "stop", "collect", "done", "rows", "jobs",
        "events", "work"))
    if loaded["config"].get("mesh"):
        assert loaded["cell"]["chips"] == 4


def test_bounds_and_layers_are_well_formed():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers_named = {m["layer"] for m in BENCH["per_layer"]}
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers_named:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_dropping_files_in_adds_a_cell_and_a_metric(tmp_path):
    """A later PR's cell: copies of nothing, edits of nothing — three new
    files and three new entries, and the harness finds them."""
    root = tmp_path / "checkout"
    shutil.copytree(run.HERE, root / "benchmark")
    bench = json.loads(json.dumps(BENCH))
    cfg = run.load_json(run.HERE, "configs", "twitter_wpr.json")
    cfg["name"] = "twitter_wpr_week"
    cfg["windows"] = [604800]
    (root / "benchmark/configs/twitter_wpr_week.json").write_text(
        json.dumps(cfg))
    traffic = run.load_json(run.HERE, "traffic", "range_windows.json")
    traffic.update(name="range_daily", hops_per_request=2)
    (root / "benchmark/traffic/range_daily.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/layer_metrics/range.stall_share.json").write_text(
        json.dumps({"reducer": "span_share", "span": "fold.stall"}))
    cell = "twitter_wpr_week.range_daily"
    bench["configs"].append({"name": "twitter_wpr_week", "source": "x",
                             "file": "benchmark/configs/twitter_wpr_week.json",
                             "reduced": [], "why": "y"})
    bench["workloads"].append({"name": cell, "config": "twitter_wpr_week",
                               "traffic": "range_daily", "chips": 1,
                               "why": "z"})
    for m in bench["end_to_end"]:
        if m["name"] == "views_per_s":
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "range.stall_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "host fold",
        "moves": "views_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    loaded = run.load_cell(cell, root=str(root),
                           here=str(root / "benchmark"))
    assert loaded["config"]["windows"] == [604800]
    assert loaded["traffic"]["hops_per_request"] == 2
    assert [m["name"] for m in loaded["per_layer"]][-1] == "range.stall_share"
    from benchmark import client
    assert client.rows_expected(loaded["config"], loaded["traffic"]) == 2
    body = client.request_body(loaded["config"], loaded["traffic"], 1)
    assert body["windowSet"] == [604800] and body["end"] - body["start"] == 3600
    rec = {"work_wall_s": 2.0, "spans": [
        {"name": "fold.stall", "dur": 500000.0},
        {"name": "hop.fold", "dur": 900000.0}]}
    spec = loaded["per_layer"][-1]
    assert layers.reduce_metric(spec, rec) == pytest.approx(25.0)
    assert layers.reduce_metric(spec, {"work_wall_s": 2.0, "spans": []}) \
        is None       # nothing to read: left out of the line


def test_reducers_on_a_hand_made_record():
    reqs = [{"t_done": 12.0, "views": 12, "latency_s": 11.0},
            {"t_done": 24.5, "views": 12, "latency_s": 12.4}]
    rec = {"t_window": 0.5, "requests": reqs,
           "ledgers": [{"wall_s": 10.0, "views": 12, "ledger": {
               "phase_seconds": {"fold": 2.0, "compute": 6.0,
                                 "device_wait": 1.0, "other": 0.5},
               "h2d": {"bytes": 2400}}}]}
    # the rate runs to the last completion, not to the window's end
    assert layers.reduce_metric({"reducer": "rate", "count": "views"},
                                rec) == pytest.approx(24 / 24.0)
    assert layers.reduce_metric(
        {"reducer": "quantile", "over": "requests", "of": "latency_s",
         "q": 50}, rec) == 11.0
    assert layers.reduce_metric(
        {"reducer": "ledger_phase_share", "phases": ["fold"]},
        rec) == pytest.approx(20.0)
    assert layers.reduce_metric(
        {"reducer": "ledger_phase_share", "complement": True},
        rec) == pytest.approx(10.0)
    assert layers.reduce_metric(
        {"reducer": "ledger_sum_per", "path": "h2d.bytes", "per": "views"},
        rec) == pytest.approx(200.0)
    spans = {"spans": [{"name": "live.epoch", "args": {"mode": "rebase"}},
                       {"name": "live.epoch", "args": {"mode": "incremental"}},
                       {"name": "xla.compile", "args": {}}]}
    assert layers.reduce_metric(
        {"reducer": "span_count", "span": "xla.compile"}, spans) == 1.0
    assert layers.reduce_metric(
        {"reducer": "span_count", "span": "xla.compile"}, {}) is None
    assert layers.reduce_metric(
        {"reducer": "span_arg_share", "span": "live.epoch", "arg": "mode",
         "equals": "rebase"}, spans) == pytest.approx(50.0)
    hist = {"h": {"buckets": [0.1, 1.0], "counts": [90, 9, 1]}}
    assert layers.reduce_metric(
        {"reducer": "histogram_quantile", "path": "h", "q": 95}, hist) == 1.0
    assert layers.quantile(range(1, 41), 95) == 38.0
    assert layers.spread([1.0, 1.0, 1.1, 0.9, 1.0, 1.0]) == pytest.approx(
        0.05, abs=0.03)
    # a roofline share from least bytes: 819e9 B in 2 s of kernel = 50 %
    x = {"least_bytes": 819e9, "chips": 1,
         "program_seconds": {"jit_run": 2.0, "jit_apply": 9.0},
         "busy_s": 3.0, "window_s": 4.0}
    rec = {"xplane": x, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert layers.reduce_metric(
        {"reducer": "trace_kernel_roofline", "program": "jit_run"},
        rec) == pytest.approx(50.0)
    assert layers.reduce_metric({"reducer": "trace_idle_share"},
                                rec) == pytest.approx(25.0)
