"""ISSUE 38's two engagement counters of the mesh Range route's fold:
data files on reducers the harness has, appended last to BENCHMARK.json.
Each reads what the pooled fold writes (``fold.seed`` / ``fold.checkpoint``
spans) and is left out — never raises — where the route writes none: the
parent commit, an inline fold."""

import json
import os

import pytest

from benchmark import layers, run

CELL = "twitter_wpr_x4.range_windows"
NEW = {
    "mesh_range.fold_from_start_share": ("span_arg_share", "fold.checkpoint"),
    "mesh_range.fold_seed_share": ("span_share", "fold.seed"),
}


def _spec(name):
    (spec,) = [s for s in run.load_cell(CELL)["per_layer"]
               if s["name"] == name]
    return spec


def _span(name, dur_s, **args):
    return {"name": name, "dur": dur_s * 1e6, "ts": 0.0, "args": args}


def test_the_two_counters_were_appended_in_their_order():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    # present once, side by side, after every metric PR 37 left: later
    # PRs' entries may follow them
    at = names.index(list(NEW)[0])
    assert names[at:at + 2] == list(NEW) and names.count(list(NEW)[1]) == 1
    assert at > names.index("mesh_range.table_put_share")


@pytest.mark.parametrize("name", list(NEW))
def test_counter_is_a_data_file_of_the_host_fold_layer(name):
    reducer, span = NEW[name]
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_span", "layer": "host fold",
                     "moves": "mesh_views_per_s", "workloads": [CELL]}
    spec = _spec(name)
    assert (spec["reducer"], spec["span"]) == (reducer, span)
    assert span in spec["what"]


@pytest.mark.parametrize("name", list(NEW))
def test_counter_reads_the_pooled_fold_and_is_left_out_without_it(name):
    units = [_span("fold.seed", 0.05, seed="checkpoint", nbytes=1),
             _span("fold.seed", 0.03, seed="checkpoint", nbytes=1),
             _span("fold.checkpoint", 0.1, seed="checkpoint", stored=True),
             _span("fold.checkpoint", 0.1, seed="checkpoint", stored=True),
             _span("fold.checkpoint", 2.0, seed="start", stored=True),
             _span("fold.checkpoint", 0.1, seed="checkpoint", stored=True)]
    rec = {"work_wall_s": 8.0, "spans": [_span("job", 7.9), *units]}
    want = {"mesh_range.fold_from_start_share": 25.0,
            "mesh_range.fold_seed_share": 1.0}[name]
    assert layers.reduce_metric(_spec(name), rec) == pytest.approx(want)
    # the parent commit, weighted SSSP, one fold worker: the fold is
    # inline and writes neither span
    inline = {"work_wall_s": 8.0, "spans": [
        _span("job", 7.9), _span("hop.fold", 2.1),
        _span("fold.advance", 1.9, time=1, rows=9),
        _span("fold.payload", 0.1, base=True, bytes=9)]}
    assert layers.reduce_metric(_spec(name), inline) is None
    assert layers.reduce_metric(_spec(name), {}) is None
