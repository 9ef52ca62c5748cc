"""The rules BENCHMARK.json and the files it names are held to, as plain
functions of ``(bench, root)``: the parsed BENCHMARK.json and the
checkout it sits in. The tests call them on the real tree; and on trees
a made-up later PR has added to, so that the tests themselves are held
to the data-driven rule: a cell, a configuration or a per-layer metric
is new files and new entries, and no test is edited for it.

``TABLE_D`` is ISSUE 25's table D: the sixteen per-layer metrics that PR
added, each with the cell it was added for. It is a rule, not a
snapshot: a later PR may append its cell to any of those lists and its
own metrics after them.
"""

import os
import re

from benchmark import algorithms, layers, loops, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
METRIC_KEYS = {"name", "unit", "better", "source", "layer", "moves",
               "workloads"}
SPAN_REDUCERS = ("span_share", "span_count")

#: name -> (first cell, reducer, layer, moves, unit, better)
TABLE_D = {
    "range.build_share": ("twitter_wpr.range_windows", "ledger_phase_share",
                          "engines", "views_per_s", "%", "lower"),
    "range.emit_share": ("twitter_wpr.range_windows", "ledger_phase_share",
                         "REST / jobs", "views_per_s", "%", "lower"),
    "range.layout_share": ("twitter_wpr.range_windows", "span_share",
                           "engines", "views_per_s", "%", "lower"),
    "range.program_builds": ("twitter_wpr.range_windows", "span_count",
                             "engines", "views_per_s", "count", "lower"),
    "mesh_range.build_share": ("twitter_wpr_x4.range_windows",
                               "ledger_phase_share", "engines",
                               "mesh_views_per_s", "%", "lower"),
    "mesh_range.compute_share": ("twitter_wpr_x4.range_windows",
                                 "ledger_phase_share", "engines",
                                 "mesh_views_per_s", "%", "higher"),
    "mesh_range.block_wait_share": ("twitter_wpr_x4.range_windows",
                                    "span_share", "mesh",
                                    "mesh_views_per_s", "%", "higher"),
    "mesh_range.program_builds": ("twitter_wpr_x4.range_windows",
                                  "span_count", "engines",
                                  "mesh_views_per_s", "count", "lower"),
    "mesh_range.program_build_share": ("twitter_wpr_x4.range_windows",
                                       "span_share", "engines",
                                       "mesh_views_per_s", "%", "lower"),
    "mesh_range.program_lower_share": ("twitter_wpr_x4.range_windows",
                                       "span_share", "engines",
                                       "mesh_views_per_s", "%", "lower"),
    "live.build_s_per_epoch": ("twitter_wpr.live_tail", "ledger_sum_per",
                               "engines", "live_staleness_p50_s", "s",
                               "lower"),
    "live.jobs_other_share": ("twitter_wpr.live_tail", "ledger_phase_share",
                              "REST / jobs", "live_staleness_p50_s", "%",
                              "lower"),
    "live.program_builds": ("twitter_wpr.live_tail", "span_count", "engines",
                            "live_staleness_p50_s", "count", "lower"),
    "live.program_build_share": ("twitter_wpr.live_tail", "span_share",
                                 "engines", "live_staleness_p50_s", "%",
                                 "lower"),
    "view.publish_share": ("twitter_wpr.view_asof", "span_share",
                           "REST / jobs", "view_p50_s", "%", "lower"),
    "view.program_builds": ("twitter_wpr.view_asof", "span_count", "engines",
                            "view_p50_s", "count", "lower"),
}


def load_bench(root):
    return run.load_json(root, "BENCHMARK.json")


def cells_of(bench):
    return [w["name"] for w in bench["workloads"]]


def load_cell(root, cell):
    """``run.load_cell`` on the checkout at ``root``."""
    return run.load_cell(cell, root=str(root),
                         here=os.path.join(str(root), "benchmark"))


def _perf(root):
    with open(os.path.join(root, "PERF.md")) as f:
        return f.read()


# ---------------------------------------------------- BENCHMARK.json's files


def names_lengths_and_files(bench, root):
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[kind]]
        assert len(names) == len(set(names)), kind
        assert all(NAME.match(n) for n in names), kind
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files)), "a file per configuration"
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(root, c["file"])), c["file"]
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        doc = run.load_json(root, c["file"])
        assert doc["source"] == c["source"], c["name"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"]), c["name"]
        assert doc["guarantees"] and doc["correct"]["limits"], c["name"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}, \
        "every configuration is used by some cell, and every cell's exists"
    for w in bench["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"], w["name"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 2)
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 << 10


def cell_finds_its_files_and_reports_what_it_must(bench, root, cell):
    loaded = load_cell(root, cell)      # a metric without its file raises
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2, cell
    assert loaded["per_layer"], "a cell reports at least one layer metric"
    for spec in loaded["end_to_end"] + loaded["per_layer"]:
        assert spec["reducer"] in layers.REDUCERS, spec["name"]
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
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


def bounds_and_layers_are_well_formed(bench, root):
    cells = cells_of(bench)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace"), m["name"]
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]
    perf = _perf(root)
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    for m in bench["per_layer"]:
        assert set(m) <= METRIC_KEYS, m["name"]
        assert m["moves"] in e2e, m["name"]
        # a list, always: a metric without one would have to be reported
        # by every cell a later PR adds, whatever that cell runs
        assert m.get("workloads"), f"{m['name']} lists no cell"
        assert len(m["workloads"]) == len(set(m["workloads"])), m["name"]
        assert set(m["workloads"]) <= set(cells), m["name"]


# ------------------------------------------------------------- table D


def table_d_is_there_once_and_in_order(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    for name in TABLE_D:
        assert names.count(name) == 1, name
    assert [n for n in names if n in TABLE_D] == list(TABLE_D)


def table_d_metric_resolves(bench, root, name):
    cell, reducer, layer, moves, unit, better = TABLE_D[name]
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"][0] == cell, "the table's cell stays first"
    assert (entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == (layer, moves, unit, better)
    assert entry["source"] == ("program_span" if reducer in SPAN_REDUCERS
                               else "program_counter")
    path = os.path.join(root, "benchmark", "layer_metrics", name + ".json")
    spec = run.load_json(path)
    assert spec["reducer"] == reducer and reducer in layers.REDUCERS
    assert spec["what"]
    perf = _perf(root)
    assert layer in perf and f"`{name}`" in perf, \
        f"PERF.md does not name {name} under a layer"


def cell_reports_its_share_of_table_d(bench, root, cell):
    loaded = load_cell(root, cell)
    got = {s["name"] for s in loaded["per_layer"]} & set(TABLE_D)
    want = {n for n, row in TABLE_D.items() if row[0] == cell}
    assert got >= want, f"{cell} lost {sorted(want - got)}"
    e2e = {m["name"] for m in loaded["end_to_end"]}
    assert {TABLE_D[n][3] for n in got} <= e2e, (
        f"{cell} reports a table-D metric whose `moves` it does not report")


def every_rule(bench, root):
    """All of the above, for every cell and every table-D metric."""
    root = str(root)
    names_lengths_and_files(bench, root)
    bounds_and_layers_are_well_formed(bench, root)
    table_d_is_there_once_and_in_order(bench)
    for name in TABLE_D:
        table_d_metric_resolves(bench, root, name)
    for cell in cells_of(bench):
        cell_finds_its_files_and_reports_what_it_must(bench, root, cell)
        cell_reports_its_share_of_table_d(bench, root, cell)
