"""BENCHMARK.json against the files it names, and the data-driven rule:
a new configuration, traffic mix or metric is a new file and no code —
and no edit of a test: the rules are ``benchmark_rules.py``'s plain
functions, called here on the real tree and on made-up later PRs'."""

import json
import os
import shutil

import pytest

pytest.register_assert_rewrite("benchmark_rules")

import benchmark_rules as rules  # noqa: E402

from benchmark import layers, run  # noqa: E402

ROOT = run.ROOT
BENCH = rules.load_bench(ROOT)
CELLS = rules.cells_of(BENCH)


def test_names_lengths_and_files():
    rules.names_lengths_and_files(BENCH, ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_what_it_must(cell):
    rules.cell_finds_its_files_and_reports_what_it_must(BENCH, ROOT, cell)


def test_bounds_and_layers_are_well_formed():
    rules.bounds_and_layers_are_well_formed(BENCH, ROOT)


# ------------------------------------------------------- made-up later PRs

# names no real PR takes: the tests must pass on a tree that already has
# the configuration these stand in for
BIG, BIG_CELL = "madeup_big", "madeup_big.range_windows"
NEW_METRIC = "range.madeup_tiled_share"
FAMILY = ("range.", "setup.")


def _checkout(tmp_path):
    """A copy of what the rules read: the benchmark's files, PERF.md and
    BENCHMARK.json (returned parsed; ``_write`` puts it back)."""
    root = tmp_path / "checkout"
    shutil.copytree(run.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "PERF.md"), root / "PERF.md")
    return root, json.loads(json.dumps(BENCH))


def _write(root, bench):
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return bench, str(root)


def _later_pr(root, bench):
    """ISSUE 32's addition, the plainest a later PR makes: a second
    configuration (a copy at scale 19); one cell on a traffic file that
    is there, appended to its end-to-end metric, to EVERY per-layer list
    of its family (table D's among them), to ``setup.compile_s`` and to
    ``setup.bulk_ingest_updates_per_s``; one new per-layer metric last,
    on a reducer ``layers.py`` has, listing the new cell only. Files
    added: two. Files edited: BENCHMARK.json."""
    cfg = run.load_json(run.HERE, "configs", "twitter_wpr.json")
    cfg["name"] = BIG
    cfg["graph"]["scale"] = 19
    cfg["source"] = "the same deployment at R-MAT scale 19"
    (root / f"benchmark/configs/{BIG}.json").write_text(json.dumps(cfg))
    (root / f"benchmark/layer_metrics/{NEW_METRIC}.json").write_text(
        json.dumps({"reducer": "span_arg_share", "span": "hop.compute",
                    "arg": "tiled", "equals": True,
                    "what": "dispatches on the edge-tiled arm"}))
    bench["configs"].append({
        "name": BIG, "source": cfg["source"],
        "file": f"benchmark/configs/{BIG}.json",
        "reduced": sorted(cfg["reduced"]), "why": "a chip that holds more"})
    bench["workloads"].append({
        "name": BIG_CELL, "config": BIG, "traffic": "range_windows",
        "chips": 1, "why": "the Range sweep where a dispatch is tiled"})
    for m in bench["end_to_end"]:
        if m["name"] == "views_per_s":
            m["workloads"].append(BIG_CELL)
    for m in bench["per_layer"]:
        if m["name"].startswith(FAMILY):
            m["workloads"].append(BIG_CELL)
    bench["per_layer"].append({
        "name": NEW_METRIC, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "engines", "moves": "views_per_s",
        "workloads": [BIG_CELL]})
    return bench


def test_a_later_prs_cell_and_metric_pass_every_rule(tmp_path):
    root, bench = _checkout(tmp_path)
    rules.every_rule(*_write(root, bench))      # the copy as it stands
    bench, where = _write(root, _later_pr(root, bench))
    rules.every_rule(bench, where)
    loaded = rules.load_cell(where, BIG_CELL)
    assert loaded["config"]["graph"]["scale"] == 19
    got = [m["name"] for m in loaded["per_layer"]]
    assert got[-1] == NEW_METRIC
    # the new cell reports every metric of its family, and its own
    assert got[:-1] == [m["name"] for m in BENCH["per_layer"]
                        if m["name"].startswith(FAMILY)]
    assert {"range.build_share", "range.emit_share", "range.layout_share",
            "range.program_builds", "setup.compile_s",
            "setup.bulk_ingest_updates_per_s"} <= set(got)
    assert [m["name"] for m in loaded["end_to_end"]] == \
        ["views_per_s", "setup_s"]
    rec = {"spans": [{"name": "hop.compute", "args": {"tiled": True}},
                     {"name": "hop.compute", "args": {"tiled": False}},
                     {"name": "hop.compute", "args": {}},
                     {"name": "hop.fold", "args": {}}]}
    spec = loaded["per_layer"][-1]
    assert layers.reduce_metric(spec, rec) == pytest.approx(100.0 / 3)
    assert layers.reduce_metric(spec, {"spans": []}) is None


def _moves_what_the_cell_lacks(root, bench):
    # view.publish_share moves view_p50_s; a Range cell reports no such
    next(m for m in bench["per_layer"]
         if m["name"] == "view.publish_share")["workloads"].append(BIG_CELL)


def _metric_without_a_file(root, bench):
    os.remove(root / f"benchmark/layer_metrics/{NEW_METRIC}.json")


def _metric_without_a_list(root, bench):
    del bench["per_layer"][-1]["workloads"]


def _a_cell_of_no_configuration(root, bench):
    bench["configs"] = [c for c in bench["configs"] if c["name"] != BIG]


def _table_d_reordered(root, bench):
    (i,) = [i for i, m in enumerate(bench["per_layer"])
            if m["name"] == "range.build_share"]
    bench["per_layer"].append(bench["per_layer"].pop(i))


def _the_tables_cell_displaced(root, bench):
    next(m for m in bench["per_layer"]
         if m["name"] == "range.emit_share")["workloads"].reverse()


@pytest.mark.parametrize("fault", [
    _moves_what_the_cell_lacks, _metric_without_a_file,
    _metric_without_a_list, _a_cell_of_no_configuration,
    _table_d_reordered, _the_tables_cell_displaced],
    ids=lambda f: f.__name__.strip("_"))
def test_a_later_pr_that_breaks_a_rule_is_refused(tmp_path, fault):
    root, bench = _checkout(tmp_path)
    bench = _later_pr(root, bench)
    fault(root, bench)
    with pytest.raises((AssertionError, FileNotFoundError)):
        rules.every_rule(*_write(root, bench))


def test_dropping_files_in_adds_a_cell_and_a_metric(tmp_path):
    """A later PR's cell: copies of nothing, edits of nothing — three new
    files and three new entries, and the harness finds them."""
    root, bench = _checkout(tmp_path)
    cfg = run.load_json(run.HERE, "configs", "twitter_wpr.json")
    cfg["name"] = "madeup_week"
    cfg["source"] = "x"
    cfg["windows"] = [604800]
    (root / "benchmark/configs/madeup_week.json").write_text(
        json.dumps(cfg))
    traffic = run.load_json(run.HERE, "traffic", "range_windows.json")
    traffic.update(name="madeup_daily", hops_per_request=2)
    (root / "benchmark/traffic/madeup_daily.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/layer_metrics/range.madeup_stall_share.json").write_text(
        json.dumps({"reducer": "span_share", "span": "fold.stall"}))
    cell = "madeup_week.madeup_daily"
    bench["configs"].append({"name": "madeup_week", "source": "x",
                             "file": "benchmark/configs/madeup_week.json",
                             "reduced": sorted(cfg["reduced"]), "why": "y"})
    bench["workloads"].append({"name": cell, "config": "madeup_week",
                               "traffic": "madeup_daily", "chips": 1,
                               "why": "z"})
    for m in bench["end_to_end"]:
        if m["name"] == "views_per_s":
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "range.madeup_stall_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "host fold",
        "moves": "views_per_s", "workloads": [cell]})
    rules.every_rule(*_write(root, bench))
    loaded = rules.load_cell(root, cell)
    assert loaded["config"]["windows"] == [604800]
    assert loaded["traffic"]["hops_per_request"] == 2
    assert loaded["per_layer"][-1]["name"] == "range.madeup_stall_share"
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
